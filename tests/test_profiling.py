"""dfprof continuous profiling plane (ISSUE 12): sampler start/stop/
overflow, phase-ledger accounting under concurrency, the /debug/prof
endpoint, the Diagnose profile section over real gRPC, the dfprof CLI
render/diff, stall dumps carrying a sample window that names the hot
frame, and the live-capture-vs-StreamStats share agreement."""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import pytest

from dragonfly2_tpu.utils import flight, profiling, tracing


def _busy_package_work(stop: threading.Event) -> None:
    # real package frames for the sampler to fold (synth is pure numpy)
    from dragonfly2_tpu.schema import synth

    while not stop.is_set():
        synth.make_download_records(50, seed=1)


# ---------------------------------------------------------------------------
# sampler
# ---------------------------------------------------------------------------


class TestSampler:
    def test_sample_folds_package_stacks_by_role(self):
        stop = threading.Event()
        t = threading.Thread(
            target=_busy_package_work, args=(stop,), name="daemon.busy-7", daemon=True
        )
        t.start()
        p = profiling.SamplingProfiler(hz=200)
        try:
            for _ in range(50):
                p.sample_once()
                time.sleep(0.001)
        finally:
            stop.set()
            t.join(2)
        stats = p.stats()
        # the numeric suffix folds away: attribution is by ROLE
        assert "daemon.busy" in stats["roles"]
        collapsed = p.collapsed()
        busy = [l for l in collapsed.splitlines() if l.startswith("daemon.busy;")]
        assert busy, f"no stacks for the busy role: {collapsed!r}"
        # package frames only, dotted module sites
        assert any("schema.synth.make_download_records" in l for l in busy)
        # collapsed lines end in the fold count
        assert all(l.rsplit(" ", 1)[1].isdigit() for l in busy)

    def test_start_stop_lifecycle(self):
        p = profiling.SamplingProfiler(hz=500)
        assert not p.running()
        assert p.start()
        assert p.running()
        assert not p.start()  # idempotent while running
        deadline = time.time() + 5
        while p.samples == 0 and time.time() < deadline:
            time.sleep(0.01)
        assert p.samples > 0, "background sampler took no sweeps"
        p.stop()
        assert not p.running()
        n = p.samples
        time.sleep(0.05)
        assert p.samples == n, "sampler kept sweeping after stop"

    def test_hz_zero_never_starts(self):
        p = profiling.SamplingProfiler(hz=0)
        assert not p.start()
        assert not p.running()

    def test_trie_overflow_drop_counts(self):
        # node budget of 1 means no stack below the role root ever fits
        p = profiling.SamplingProfiler(hz=100, max_nodes=1)
        stop = threading.Event()
        t = threading.Thread(
            target=_busy_package_work, args=(stop,), name="daemon.over-1", daemon=True
        )
        t.start()
        try:
            for _ in range(30):
                p.sample_once()
                time.sleep(0.001)
        finally:
            stop.set()
            t.join(2)
        assert p.dropped > 0, "overflowing trie never drop-counted"
        assert p.stats()["trie_nodes"] <= 1
        # truncated samples still attribute at the deepest existing node
        assert p.folded(), "overflow discarded the samples entirely"

    def test_windowed_fold_excludes_old_samples(self):
        p = profiling.SamplingProfiler(hz=100)
        old = (time.time_ns() - int(120e9), "daemon.old", ("schema.synth.x",))
        new = (time.time_ns(), "daemon.new", ("schema.synth.y",))
        p._ring.extend([old, new])
        folded = p.folded(60.0)
        roles = {role for role, _ in folded}
        assert roles == {"daemon.new"}

    def test_thread_role_folding(self):
        assert profiling.thread_role("trainer.ingest-decode-3") == (
            "trainer.ingest-decode"
        )
        assert profiling.thread_role("daemon.announce-1a2b3c4d") == "daemon.announce"
        # digit-free hex peer-id slices fold too (every peer must not
        # mint its own role/trie root)
        assert profiling.thread_role("daemon.announce-deadbeef") == "daemon.announce"
        assert profiling.thread_role("scheduler.fleet-renew") == (
            "scheduler.fleet-renew"
        )
        assert profiling.thread_role("Thread-12") == "Thread"


# ---------------------------------------------------------------------------
# phase ledger
# ---------------------------------------------------------------------------


class TestPhaseLedger:
    def test_observe_and_context_accounting(self):
        ph = profiling.phase_type("trainer.test_ledger")
        base = ph.snapshot()
        ph.observe(0.25)
        with ph:
            time.sleep(0.01)
        snap = ph.snapshot()
        assert snap["count"] == base["count"] + 2
        assert snap["total_s"] >= base["total_s"] + 0.25
        assert snap["max_s"] >= 0.25
        assert snap["active"] == 0

    def test_declaration_is_idempotent_and_validated(self):
        a = profiling.phase_type("trainer.test_idem")
        b = profiling.phase_type("trainer.test_idem")
        assert a is b
        with pytest.raises(ValueError):
            profiling.phase_type("nodot")
        with pytest.raises(ValueError):
            profiling.phase_type("Upper.case")

    def test_concurrent_phases_account_exactly(self):
        """N threads × M entries each, some overlapping — counts and
        totals must be exact (the ledger is the cross-service wall
        attribution; racy drops would skew shares)."""
        ph = profiling.phase_type("trainer.test_conc")
        base = ph.snapshot()
        threads = 8
        each = 200
        barrier = threading.Barrier(threads)

        def work():
            barrier.wait()
            for _ in range(each):
                with ph:
                    pass
                ph.observe(0.001)

        ts = [threading.Thread(target=work, daemon=True) for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(10)
        snap = ph.snapshot()
        assert snap["count"] == base["count"] + threads * each * 2
        expected = base["total_s"] + threads * each * 0.001
        assert snap["total_s"] == pytest.approx(expected, rel=0.5)
        assert snap["active"] == 0

    def test_nested_reentry_on_one_thread(self):
        ph = profiling.phase_type("trainer.test_nest")
        base = ph.snapshot()["count"]
        with ph:
            with ph:
                pass
        assert ph.snapshot()["count"] == base + 2
        assert ph.active == 0

    def test_a_split_holds_no_phase_declared_inner(self):
        """A phase declared ``inner`` is entered inside another ``with``
        phase on its thread: the ledger's and the trace's, and no
        split's, which holds the outer one and so counts no second
        twice. ``last_s`` is what the block this thread left last took."""
        outer = profiling.phase_type("trainer.test_outer")
        inner = profiling.phase_type("trainer.test_inner", inner=True)
        # declared once; a reader that fetched the name first does not undo it
        assert profiling.phase_type("trainer.test_inner") is inner and inner.inner
        assert profiling.phase_type("trainer.test_read_first").inner is False
        assert profiling.phase_type("trainer.test_read_first", inner=True).inner is True
        base = {ph.name: ph.snapshot() for ph in (outer, inner)}
        with profiling.split() as mine:
            with outer:
                with inner:
                    pass
                with inner:
                    time.sleep(0.002)
        assert {name: n for name, (n, _) in mine.items()} == {outer.name: 1}
        moved = {ph.name: ph.snapshot()["count"] - base[ph.name]["count"] for ph in (outer, inner)}
        assert moved == {outer.name: 1, inner.name: 2}
        assert mine[outer.name][1] == outer.last_s >= inner.last_s >= 0.002
        assert outer.snapshot()["total_s"] - base[outer.name]["total_s"] == pytest.approx(outer.last_s, abs=1e-6)

    @pytest.mark.parametrize("generation, entries", [(2, 1), (0, 0), (1, 0)])
    def test_a_full_collection_is_a_phase_and_a_young_one_is_not(self, generation, entries):
        import gc

        profiling.watch_collections()
        profiling.watch_collections()  # idempotent: one hook
        assert gc.callbacks.count(profiling._on_collection) == 1
        ph = profiling.PH_GC_FULL
        base = ph.snapshot()
        with profiling.split() as mine:
            gc.collect(generation)
        snap = ph.snapshot()
        assert snap["count"] - base["count"] == entries and snap["active"] == 0
        assert snap["total_s"] >= base["total_s"]
        assert not mine  # a collection is no work of the thread it fell on

    def test_snapshot_shares_sum_within_group(self):
        a = profiling.phase_type("manager.test_share_a")
        b = profiling.phase_type("manager.test_share_b")
        a.observe(3.0)
        b.observe(1.0)
        snap = profiling.ledger_snapshot()
        group = {
            k: v for k, v in snap.items() if k.startswith("manager.test_share")
        }
        # other manager.* phases may exist process-wide; shares are
        # still proportional to totals within the group
        assert snap["manager.test_share_a"]["share"] == pytest.approx(
            3 * snap["manager.test_share_b"]["share"], rel=0.01
        )
        assert len(group) == 2


# ---------------------------------------------------------------------------
# /debug/prof
# ---------------------------------------------------------------------------


class TestDebugProfEndpoint:
    @pytest.fixture()
    def server(self):
        from dragonfly2_tpu.utils.metrics import MetricsServer, Registry

        srv = MetricsServer(Registry("t_prof"))
        addr = srv.start()
        yield addr
        srv.stop()

    def test_200_with_collapsed_and_phases(self, server):
        profiling.phase_type("trainer.test_http").observe(0.5)
        body = json.loads(
            urllib.request.urlopen(f"http://{server}/debug/prof").read()
        )
        assert "collapsed" in body
        assert "trainer.test_http" in body["phases"]
        assert body["phases"]["trainer.test_http"]["count"] >= 1
        # windowed form narrows via the recent-sample ring
        body = json.loads(
            urllib.request.urlopen(f"http://{server}/debug/prof?seconds=30").read()
        )
        assert body["window_s"] == 30.0

    def test_collapsed_format_is_text(self, server):
        resp = urllib.request.urlopen(
            f"http://{server}/debug/prof?format=collapsed"
        )
        assert resp.headers["Content-Type"].startswith("text/plain")

    @pytest.mark.parametrize(
        "query",
        [
            "bogus=1", "seconds=abc", "seconds=-5", "seconds=", "format=xml",
            "seconds=nan", "seconds=inf",
        ],
    )
    def test_unknown_or_bad_params_400(self, server, query):
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(f"http://{server}/debug/prof?{query}")
        assert exc.value.code == 400
        assert "error" in json.loads(exc.value.read())


# ---------------------------------------------------------------------------
# Diagnose profile section over real gRPC
# ---------------------------------------------------------------------------


class TestDiagnoseProfile:
    def test_diagnose_carries_profile_section(self):
        from dragonfly2_tpu.rpc import gen  # noqa: F401
        import diagnose_pb2  # noqa: E402

        from dragonfly2_tpu.rpc import glue
        from dragonfly2_tpu.rpc.diagnose import DiagnoseService

        profiling.phase_type("trainer.test_diag").observe(0.125)
        server, port = glue.serve({glue.DIAGNOSE_SERVICE: DiagnoseService()})
        try:
            channel = glue.dial(f"127.0.0.1:{port}")
            client = glue.ServiceClient(channel, glue.DIAGNOSE_SERVICE)
            resp = client.Diagnose(
                diagnose_pb2.DiagnoseRequest(include_stacks=False), timeout=5
            )
            snap = json.loads(resp.snapshot_json)
            prof = snap["profile"]
            assert "collapsed" in prof
            assert prof["phases"]["trainer.test_diag"]["count"] >= 1
            assert "hz" in prof and "samples" in prof
            channel.close()
        finally:
            server.stop(grace=0)


# ---------------------------------------------------------------------------
# dfprof CLI
# ---------------------------------------------------------------------------

_CANNED = {
    "service": "trainer",
    "hz": 20,
    "samples": 12,
    "window_s": None,
    "collapsed": (
        "trainer.ingest-dispatch;trainer.ingest._dispatch_loop;trainer.ingest.put 7\n"
        "trainer.ingest-dispatch;trainer.ingest._dispatch_loop 3\n"
        "scheduler.announce-pump;scheduler.scheduling.schedule_candidate_parents 2"
    ),
    "phases": {
        "trainer.buffer_wait": {
            "count": 4, "total_s": 7.9, "mean_s": 1.975, "max_s": 3.0,
            "active": 0, "share": 0.79,
        },
        "trainer.step": {
            "count": 4, "total_s": 2.1, "mean_s": 0.525, "max_s": 1.0,
            "active": 0, "share": 0.21,
        },
    },
}


class TestDfprofCli:
    def test_render_top_and_phases(self, tmp_path, capsys):
        from dragonfly2_tpu.tools import dfprof

        cap = tmp_path / "cap.json"
        cap.write_text(json.dumps(_CANNED))
        assert dfprof.main([str(cap), "--top", "5"]) == 0
        out = capsys.readouterr().out
        # self-time ranking: put is the leaf of 7 samples → hottest
        lines = [l for l in out.splitlines() if "trainer.ingest.put" in l]
        assert lines and lines[0].lstrip().startswith("7")
        # total ≥ self: _dispatch_loop is on 10 stacks, leaf of 3
        assert any(
            "trainer.ingest._dispatch_loop" in l and " 10 " in f" {l} "
            for l in out.splitlines()
        )
        assert "trainer.buffer_wait" in out and "79%" in out

    def test_collapsed_text_input_and_flag(self, tmp_path, capsys):
        from dragonfly2_tpu.tools import dfprof

        raw = tmp_path / "cap.txt"
        raw.write_text(_CANNED["collapsed"])
        assert dfprof.main([str(raw), "--collapsed"]) == 0
        out = capsys.readouterr().out
        assert "trainer.ingest._dispatch_loop;trainer.ingest.put 7" in out

    def test_diff_names_the_movers(self, tmp_path, capsys):
        from dragonfly2_tpu.tools import dfprof

        before = tmp_path / "a.json"
        after = tmp_path / "b.json"
        before.write_text(json.dumps(_CANNED))
        moved = dict(_CANNED)
        moved["collapsed"] = (
            "trainer.ingest-dispatch;trainer.ingest._dispatch_loop;trainer.ingest.put 2\n"
            "trainer.ingest-dispatch;trainer.ingest._dispatch_loop;schema.wire.decode 9"
        )
        moved["phases"] = {
            "trainer.buffer_wait": {
                "count": 8, "total_s": 2.0, "mean_s": 0.25, "max_s": 1.0,
                "active": 0, "share": 0.2,
            }
        }
        after.write_text(json.dumps(moved))
        assert dfprof.main(["--diff", str(before), str(after)]) == 0
        out = capsys.readouterr().out
        assert "+9" in out and "schema.wire.decode" in out
        assert "-5" in out and "trainer.ingest.put" in out
        assert "trainer.buffer_wait" in out  # phase movement section

    def test_rpc_live_capture(self, tmp_path, capsys):
        from dragonfly2_tpu.rpc import glue
        from dragonfly2_tpu.rpc.diagnose import DiagnoseService
        from dragonfly2_tpu.tools import dfprof

        profiling.phase_type("trainer.test_cli_rpc").observe(0.1)
        server, port = glue.serve({glue.DIAGNOSE_SERVICE: DiagnoseService()})
        try:
            save = tmp_path / "live.json"
            rc = dfprof.main(
                ["--rpc", f"127.0.0.1:{port}", "--save", str(save), "--top", "3"]
            )
            assert rc == 0
            out = capsys.readouterr().out
            assert "trainer.test_cli_rpc" in out
            saved = json.loads(save.read_text())
            assert "collapsed" in saved and "phases" in saved
        finally:
            server.stop(grace=0)

    def test_unreachable_rpc_fails_cleanly(self, capsys):
        from dragonfly2_tpu.tools import dfprof

        assert dfprof.main(["--rpc", "127.0.0.1:1"]) == 1
        assert "dfprof:" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# stall dump carries the sample window (the acceptance wiring)
# ---------------------------------------------------------------------------


class TestStallDumpWindow:
    def test_forced_ingest_stall_dump_names_hot_frame(self, tmp_path, monkeypatch):
        """The PR 4 stubbed-slow-step stall, now with the profiler
        running: the dump's meta.profile window must exist and name the
        dispatcher as a hot frame — a wedged fit explains itself."""
        import numpy as np

        from dragonfly2_tpu.schema import synth, wire
        from dragonfly2_tpu.trainer import ingest

        monkeypatch.setenv("DF_DIAG_DIR", str(tmp_path / "diag"))
        monkeypatch.setenv("DF_STALL_FACTOR", "3.0")

        def fake_get_step(lr, wd, warmup_steps=64):
            class _Opt:
                def init(self, params):
                    return {}

            calls = {"n": 0}

            def step(params, opt_state, xy):
                calls["n"] += 1
                if calls["n"] == 12:
                    time.sleep(0.4)  # the wedged superbatch
                return params, opt_state, np.float32(0.1)

            return _Opt(), step

        monkeypatch.setattr(ingest, "_get_step", fake_get_step)
        real_watchdog = flight.StallWatchdog

        def small_floor_watchdog(name, **kw):
            kw["floor_s"] = 0.05
            kw["cooldown_s"] = 3600.0
            return real_watchdog(name, **kw)

        monkeypatch.setattr(flight, "StallWatchdog", small_floor_watchdog)

        block = wire.encode_train_block(synth.make_download_records(400, seed=0))
        data = tmp_path / "d.dfb"
        data.write_bytes(block)

        # a fast process-wide sampler so the 0.4s stall collects samples
        prof = profiling.profiler()
        old_hz = prof.hz
        prof.hz = 200.0
        try:
            prof.start()
            ingest.stream_train_mlp(
                str(data),
                passes=4,
                batch_size=64,
                eval_every=0,
                params={"unused": np.zeros(1)},
                workers=1,
            )
        finally:
            prof.stop()
            prof.hz = old_hz
        dumps = sorted((tmp_path / "diag").glob("*.jsonl"))
        assert dumps, "stall watchdog produced no dump"
        meta = json.loads(dumps[0].read_text().splitlines()[0])["meta"]
        assert meta["reason"].startswith("stall-trainer.step")
        prof_section = meta.get("profile")
        assert prof_section, "dump carries no dfprof window"
        assert prof_section["window_s"] > 0
        # the hot frame: the step-stage thread wedged inside its loop
        assert "trainer.ingest._step_loop" in prof_section["collapsed"], (
            prof_section["collapsed"]
        )
        # the ledger rode along with the live ingest legs accounted
        assert prof_section["phases"]["trainer.step"]["count"] > 0

    def test_dfdoctor_renders_the_window(self, tmp_path, capsys):
        from dragonfly2_tpu.tools import dfdoctor

        dump = tmp_path / "svc-1-2-stall.jsonl"
        meta = {
            "meta": {
                "reason": "stall-trainer.step",
                "service": "trainer",
                "pid": 1,
                "dumped_at_ns": time.time_ns(),
                "profile": {
                    "window_s": 30.0,
                    "collapsed": (
                        "trainer.ingest-dispatch;trainer.ingest._dispatch_loop 9\n"
                        "trainer.ingest-decode;schema.wire.decode 1"
                    ),
                    "phases": {
                        "trainer.buffer_wait": {
                            "count": 3, "total_s": 7.9, "share": 0.79,
                        },
                    },
                },
            }
        }
        dump.write_text(json.dumps(meta) + "\n")
        assert dfdoctor.main(["--diag", str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "hot frames" in out
        assert "trainer.ingest._dispatch_loop" in out
        assert "trainer.buffer_wait=79%" in out


# ---------------------------------------------------------------------------
# acceptance: live capture share agrees with StreamStats
# ---------------------------------------------------------------------------


class TestLedgerAgreesWithStreamStats:
    def test_buffer_wait_share_within_ten_percent(self, tmp_path, monkeypatch):
        """Run a real (stubbed-step, slow device leg) streaming fit and
        compare the phase ledger's buffer_wait share of the four ingest
        legs against the same ratio from StreamStats — the acceptance
        bound is 10%."""
        import numpy as np

        from dragonfly2_tpu.schema import synth, wire
        from dragonfly2_tpu.trainer import ingest

        monkeypatch.delenv("DF_DIAG_DIR", raising=False)

        def fake_get_step(lr, wd, warmup_steps=64):
            class _Opt:
                def init(self, params):
                    return {}

            def step(params, opt_state, xy):
                time.sleep(0.02)  # slow device leg → real buffer_wait
                return params, opt_state, np.float32(0.1)

            return _Opt(), step

        monkeypatch.setattr(ingest, "_get_step", fake_get_step)

        legs = (
            "trainer.decode_wait", "trainer.buffer_wait",
            "trainer.h2d", "trainer.step",
        )
        before = {
            name: profiling.phase_type(name).snapshot()["total_s"] for name in legs
        }

        block = wire.encode_train_block(synth.make_download_records(800, seed=0))
        data = tmp_path / "d.dfb"
        data.write_bytes(block)
        _, stats = ingest.stream_train_mlp(
            str(data),
            passes=6,
            batch_size=64,
            eval_every=0,
            params={"unused": np.zeros(1)},
            workers=1,
        )
        after = profiling.ledger_snapshot()
        deltas = {
            name: after[name]["total_s"] - before[name] for name in legs
        }
        ledger_total = sum(deltas.values())
        assert ledger_total > 0
        ledger_share = deltas["trainer.buffer_wait"] / ledger_total
        stats_total = (
            stats.decode_wait_s + stats.buffer_wait_s + stats.h2d_s + stats.step_s
        )
        stats_share = stats.buffer_wait_s / stats_total
        assert stats.buffer_wait_s > 0, "stub produced no buffer pressure"
        assert ledger_share == pytest.approx(stats_share, abs=0.10), (
            f"ledger {ledger_share:.3f} vs StreamStats {stats_share:.3f}"
        )

    def test_buffer_wait_live_series_observed(self, tmp_path, monkeypatch):
        """The satellite series: trainer_ingest_buffer_wait_seconds
        moves during a fit, like its decode_wait/h2d/step siblings."""
        import numpy as np

        from dragonfly2_tpu.schema import synth, wire
        from dragonfly2_tpu.trainer import ingest
        from dragonfly2_tpu.trainer import metrics as M

        def fake_get_step(lr, wd, warmup_steps=64):
            class _Opt:
                def init(self, params):
                    return {}

            def step(params, opt_state, xy):
                time.sleep(0.005)
                return params, opt_state, np.float32(0.1)

            return _Opt(), step

        monkeypatch.setattr(ingest, "_get_step", fake_get_step)
        child = M.INGEST_BUFFER_WAIT_SECONDS._default_child()
        before = child.count
        block = wire.encode_train_block(synth.make_download_records(400, seed=0))
        data = tmp_path / "d.dfb"
        data.write_bytes(block)
        with tracing.get("trainer").start_span("fit", model="mlp") as span:
            ingest.stream_train_mlp(
                str(data),
                passes=4,
                batch_size=64,
                eval_every=0,
                params={"unused": np.zeros(1)},
                workers=1,
            )
        assert child.count > before, "buffer-wait histogram never observed"
        # exemplars carry the owning fit's trace_id like the siblings
        exemplars = [ex for ex in child.exemplars.values()]
        assert any(
            labels.get("trace_id") == span.trace_id for labels, _v, _ts in exemplars
        )


# ---------------------------------------------------------------------------
# install + telemetry section
# ---------------------------------------------------------------------------


class TestInstallAndTelemetry:
    def test_install_respects_df_prof_disable(self, monkeypatch):
        monkeypatch.setenv("DF_PROF", "0")
        p = profiling.profiler()
        was_running = p.running()
        profiling.install("testsvc")
        try:
            assert p.running() == was_running  # no new sampler under DF_PROF=0
            assert "testsvc" in p.service.split("+")
        finally:
            if not was_running:
                profiling.stop()

    def test_telemetry_section_carries_phases_and_hot_stacks(self, monkeypatch):
        profiling.phase_type("trainer.test_tel").observe(1.0)
        # a fresh instance: the process-wide ring may hold thousands of
        # samples from other tests, and the top-K assertion needs a
        # deterministic hot stack
        p = profiling.SamplingProfiler(hz=20)
        p._ring.append(
            (time.time_ns(), "trainer.ingest-dispatch", ("trainer.ingest.x",))
        )
        p.samples += 1
        monkeypatch.setattr(profiling, "_profiler", p)
        section = profiling.telemetry_section()
        assert section["phases"]["trainer.test_tel"]["count"] >= 1
        assert any(
            "trainer.ingest-dispatch;trainer.ingest.x" == h["stack"]
            for h in section.get("hot", [])
        )

    def test_reporter_payload_includes_prof(self):
        from dragonfly2_tpu.utils.telemetry import TelemetryReporter

        profiling.phase_type("trainer.test_push").observe(0.5)
        rep = TelemetryReporter(
            client=None,
            service="trainer",
            instance="t",
            prefixes=("dragonfly_trainer_",),
        )
        payload, _cur = rep.build_payload()
        assert "prof" in payload
        assert "trainer.test_push" in payload["prof"]["phases"]
