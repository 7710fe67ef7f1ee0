"""Compute-plane observability + the dftrace CLI: the streaming train
loop's live histograms (with trace exemplars), the profile_dir wiring,
and the trace-merge tool."""

import contextlib


from dragonfly2_tpu.utils import tracing


# ---------------------------------------------------------------------------
# ingest pipeline histograms + exemplars
# ---------------------------------------------------------------------------


def test_ingest_histograms_carry_owning_trace(tmp_path):
    """One streamed fit under an active fit span: the decode_wait/h2d/
    step histograms move and their exemplars carry the owning trace_id;
    StreamStats accumulates the same splits."""
    from dragonfly2_tpu.schema import synth, wire
    from dragonfly2_tpu.trainer import metrics as M
    from dragonfly2_tpu.trainer.ingest import stream_train_mlp

    path = tmp_path / "d.dfb"
    path.write_bytes(wire.encode_train_block(synth.make_download_records(60, seed=0)))

    def counts():
        return (
            M.INGEST_DECODE_WAIT_SECONDS._default_child().count,
            M.INGEST_H2D_SECONDS._default_child().count,
            M.INGEST_STEP_SECONDS._default_child().count,
        )

    before = counts()
    prev = tracing._sample_ratio
    tracing._sample_ratio = 1.0
    try:
        with tracing.get("trainer").span("fit", model="mlp") as fit:
            _, stats = stream_train_mlp(
                path, batch_size=32, eval_every=0, hidden_dims=(8,)
            )
    finally:
        tracing._sample_ratio = prev
    after = counts()
    assert after[0] > before[0]  # decode waits observed per shard
    assert after[1] > before[1] and after[2] > before[2]  # per superbatch
    assert stats.h2d_s >= 0 and stats.step_s > 0
    # at least one exemplar across the three series names the fit's trace
    exemplars = [
        ex
        for h in (
            M.INGEST_DECODE_WAIT_SECONDS,
            M.INGEST_H2D_SECONDS,
            M.INGEST_STEP_SECONDS,
        )
        for ex in h._default_child().exemplars.values()
    ]
    assert any(labels.get("trace_id") == fit.trace_id for labels, _, _ in exemplars)


def test_ingest_unsampled_run_records_no_exemplars(tmp_path):
    from dragonfly2_tpu.schema import synth, wire
    from dragonfly2_tpu.trainer import metrics as M
    from dragonfly2_tpu.trainer.ingest import stream_train_mlp

    path = tmp_path / "d.dfb"
    path.write_bytes(wire.encode_train_block(synth.make_download_records(40, seed=1)))
    prev = tracing._sample_ratio
    tracing._sample_ratio = 0.0
    seen = {
        k: dict(h._default_child().exemplars)
        for k, h in {
            "dw": M.INGEST_DECODE_WAIT_SECONDS,
            "h2d": M.INGEST_H2D_SECONDS,
            "st": M.INGEST_STEP_SECONDS,
        }.items()
    }
    try:
        with tracing.get("trainer").span("fit", model="mlp"):
            stream_train_mlp(path, batch_size=32, eval_every=0, hidden_dims=(8,))
    finally:
        tracing._sample_ratio = prev
    # values observed (counts move) but NO new exemplars — an unsampled
    # trace must not be advertised on /metrics
    assert dict(M.INGEST_H2D_SECONDS._default_child().exemplars) == seen["h2d"]
    assert dict(M.INGEST_STEP_SECONDS._default_child().exemplars) == seen["st"]


# ---------------------------------------------------------------------------
# profile_dir wiring
# ---------------------------------------------------------------------------


def test_profile_dir_drives_jax_profiler(tmp_path, monkeypatch):
    """TrainingConfig.profile_dir → ONE jax.profiler.trace a round,
    open around the three fits (JAX allows one session a process, and
    the fits run on three threads); empty opens none. A real round
    under the real profiler is in tests/test_round_phases.py."""
    import jax

    from dragonfly2_tpu.trainer.storage import TrainerStorage
    from dragonfly2_tpu.trainer.training import Training, TrainingConfig

    calls, open_ = [], []

    @contextlib.contextmanager
    def fake_trace(path, **kw):
        calls.append(path)
        open_.append(path)
        try:
            yield
        finally:
            open_.remove(path)

    fits = []

    def fake_fit(self, model, parent_span, splits, fn, *args):
        fits.append((model, list(open_)))
        return {}

    monkeypatch.setattr(jax.profiler, "trace", fake_trace)
    monkeypatch.setattr(Training, "_timed_fit", fake_fit)
    storage = TrainerStorage(tmp_path)
    Training(storage, config=TrainingConfig(profile_dir="")).train("10.0.0.1", "h")
    assert calls == [] and sorted(m for m, _ in fits) == ["gnn", "gru", "mlp"]
    del fits[:]
    prof = str(tmp_path / "prof")
    Training(storage, config=TrainingConfig(profile_dir=prof)).train("10.0.0.1", "h")
    assert calls == [f"{prof}/round"]
    assert sorted(fits) == [(m, [f"{prof}/round"]) for m in ("gnn", "gru", "mlp")]
    assert open_ == []


def test_trainer_server_config_plumbs_profile_dir(tmp_path):
    from dragonfly2_tpu.trainer.server import TrainerServer, TrainerServerConfig

    server = TrainerServer(
        TrainerServerConfig(
            data_dir=str(tmp_path / "t"), profile_dir=str(tmp_path / "prof")
        )
    )
    assert server.training.config.profile_dir == str(tmp_path / "prof")


# ---------------------------------------------------------------------------
# dftrace CLI
# ---------------------------------------------------------------------------


def _export_two_services(trace_dir):
    """Two per-service export files holding one cross-service trace (and
    a second, older trace), like a run under DF_TRACE_DIR produces."""
    tracing.configure(str(trace_dir))
    try:
        tr_a = tracing.get("dfdaemon")
        tr_b = tracing.get("scheduler")
        # older unrelated trace
        tr_a.start_span("stale_root").end()
        with tr_a.span("rpc.Download") as root:
            import time as _t

            with tr_a.span("peer_task"):
                _t.sleep(0.02)
                with tr_b.span("rpc.AnnouncePeer"):
                    with tr_b.span("schedule"):
                        _t.sleep(0.01)
                with tr_b.span("evaluate"):
                    pass
        return root.trace_id
    finally:
        tracing.configure(None)


def test_dftrace_merges_services_and_marks_critical_path(tmp_path, capsys):
    from dragonfly2_tpu.tools import dftrace

    tid = _export_two_services(tmp_path)
    # default invocation renders the LATEST trace merged across files
    assert dftrace.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert f"trace {tid}" in out
    for name in ("rpc.Download", "peer_task", "rpc.AnnouncePeer", "schedule"):
        assert name in out
    # spans from both service files joined into one tree
    assert "(dfdaemon)" in out and "(scheduler)" in out
    # critical path printed root→leaf and the slowest span per level marked
    assert "critical path: rpc.Download" in out
    assert "schedule" in out.split("critical path:")[1]
    assert "slowest@L0" in out and "slowest@L1" in out
    # child ordering/parenting: schedule is indented under rpc.AnnouncePeer
    lines = out.splitlines()
    sched_line = next(l for l in lines if l.lstrip().startswith("schedule"))
    announce_line = next(l for l in lines if l.lstrip().startswith("rpc.AnnouncePeer"))
    assert len(sched_line) - len(sched_line.lstrip()) > len(announce_line) - len(
        announce_line.lstrip()
    )


def test_dftrace_list_and_explicit_trace(tmp_path, capsys):
    from dragonfly2_tpu.tools import dftrace

    tid = _export_two_services(tmp_path)
    assert dftrace.main([str(tmp_path), "--list"]) == 0
    out = capsys.readouterr().out
    assert tid in out
    assert "stale_root" in out  # the older trace summarized too
    assert dftrace.main([str(tmp_path), "--trace", tid]) == 0
    assert f"trace {tid}" in capsys.readouterr().out


def test_dftrace_reads_otlp_exports(tmp_path, capsys):
    from dragonfly2_tpu.tools import dftrace

    tracing.configure(str(tmp_path), fmt="otlp")
    try:
        tr = tracing.get("trainer")
        with tr.span("rpc.Train") as root:
            with tr.span("fit", model="mlp"):
                pass
    finally:
        tracing.configure(None, fmt="jsonl")
    assert dftrace.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert f"trace {root.trace_id}" in out
    assert "fit" in out and "(trainer)" in out


def test_dftrace_skips_torn_lines(tmp_path, capsys):
    from dragonfly2_tpu.tools import dftrace

    tid = _export_two_services(tmp_path)
    # a live process's torn last line must not block the rest
    with open(tmp_path / "dfdaemon.spans.jsonl", "a") as f:
        f.write('{"trace_id": "torn')
    assert dftrace.main([str(tmp_path)]) == 0
    assert f"trace {tid}" in capsys.readouterr().out


def test_dftrace_empty_dir_errors(tmp_path, capsys):
    from dragonfly2_tpu.tools import dftrace

    assert dftrace.main([str(tmp_path)]) == 1
    assert "no spans" in capsys.readouterr().err
