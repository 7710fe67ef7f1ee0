"""bench.py emission contract around failed timed repeats: a transient
device-link failure mid-repeat must not discard runs that DID finish
(emit best + ``run_error``), and must produce the error line — never a
traceback with no JSON — when no run completed. The heavy phases
(dataset synthesis, the real streaming fit) are stubbed with REAL (tiny)
files of both payload formats — the host-split section decodes them for
real; everything else in main() runs too.
"""

import json
import time

import pytest

import bench
from dragonfly2_tpu.schema import synth, wire
from dragonfly2_tpu.trainer import ingest
from dragonfly2_tpu.trainer.ingest import StreamStats

# these tests call main() in-process
pytestmark = pytest.mark.usefixtures("compile_cache_off")


def _fake_synthesize(d, shards, shard_bytes):
    paths = []
    for i in range(2):
        p = f"{d}/shard-{i}.csv"
        with open(p, "w") as f:
            f.write("x\n")
        paths.append(p)
    return paths


def _fake_synthesize_binary(d, shards, shard_bytes):
    block = wire.encode_train_block(synth.make_download_records(5, seed=0))
    paths = []
    for i in range(2):
        p = f"{d}/shard-{i}.dfb"
        with open(p, "wb") as f:
            f.write(block)
        paths.append(p)
    return paths


def _stats(records=1000):
    s = StreamStats()
    s.download_records = records
    s.pairs = records * 4
    s.steps = 8
    return s


def _fake_chaos_soak():
    # the real soak spins a scheduler + two daemons (~15s); emission
    # tests only assert the KEYS ride the artifact — the soak itself is
    # covered end-to-end by tests/test_fault_injection.py
    return {
        "chaos_downloads": 4,
        "chaos_success_rate": 1.0,
        "chaos_hangs": 0,
        "chaos_faults_injected": 3,
        "chaos_wall_s": 0.1,
    }


def _fake_fleet_soak():
    # the real soak spawns 3 scheduler processes and SIGKILLs one
    # (~10s); the soak itself is covered by tests/test_stress_tool.py
    return {
        "fleet_shards": 3,
        "fleet_peers": 150,
        "fleet_success_rate": 1.0,
        "fleet_hangs": 0,
        "fleet_blackout_ms": 2100.0,
        "fleet_wrong_shard_retries": 42,
        "schedule_ops_per_s": 55.0,
        "fleet_wall_s": 0.1,
        # ISSUE 20 two-arm failover comparison + adoption verdict
        "fleet_blackout_ms_replicated": 2300.0,
        "fleet_blackout_ms_rebuild": 4100.0,
        "fleet_rebuild_fallbacks": 3,
        "fleet_rebuild_wall_s": 0.1,
        "swarm_adopt_ms": 4.2,
        "swarm_adopt_outcome": "adopted",
        "fleet_victim_cohort": 3,
        "fleet_victim_recognized": 3,
        "fleet_victim_fallbacks": 0,
        "swarm_replica_diff_clean": 1,
    }


def _fake_serving_bench():
    # the real soak runs two 32-thread evaluator arms (~5s); emission
    # tests only assert the KEYS ride the artifact — the soak itself is
    # covered end-to-end by tests/test_stress_tool.py
    return {
        "serving_ops_per_s_batched": 3600.0,
        "serving_ops_per_s_per_call": 2400.0,
        "evaluator_batch_occupancy": 70.0,
        "schedule_decision_p99_us": 11000.0,
        "serving_p99_bound_us": 23000.0,
        "serving_backend": "jax",
        "serving_lost": 0,
    }


def _fake_wave_bench():
    # the real soak runs two evaluator arms over a live scoring service
    # (~5s); emission tests only assert the KEYS ride the artifact — the
    # soak itself is covered end-to-end by tests/test_stress_tool.py
    return {
        "wave_decisions_per_s": 3300.0,
        "wave_decisions_per_s_per_op": 2000.0,
        "wave_occupancy_rows": 80.0,
        "wave_unpack_p99_us": 90.0,
        "wave_rankings_match": 1,
        "wave_lost": 0,
        "serving_backend": "jax",
    }


def _fake_multichip_bench():
    # the real curve spawns 4 fresh-interpreter subprocesses (~1 min);
    # emission tests only assert the KEYS ride the artifact — the
    # harness itself is covered by tests/test_multichip_ingest.py
    return {
        "multichip_scaling": {"1": 40000.0, "2": 21000.0, "4": 11000.0, "8": 6000.0},
        "multichip_platform": "cpu-forced-host-devices",
        "mesh_h2d_per_shard": 1.0,
        "mesh_pack_thread_transfers": 0,
    }


def _fake_data_plane_bench():
    # the real race holds 2×256 live sockets for ~10s; emission tests
    # only assert the KEYS ride the artifact — the race itself is
    # covered end-to-end by tests/test_data_plane.py + the CLI soak
    return {
        "data_plane_bytes_per_s": 500e6,
        "data_plane_bytes_per_s_buffered": 430e6,
        "data_plane_connections": 256,
        "piece_serve_p99_us": 40000.0,
        "daemon_rss_mb": 40.0,
        "data_plane_hangs": 0,
        "data_plane_errors": 0,
    }


def _fake_preheat_bench():
    # the real soak trains a GRU forecaster and runs planner sweeps
    # (~10s); emission tests only assert the KEYS ride the artifact —
    # the soak itself is covered end-to-end by tests/test_preheat.py
    # and the CLI soak
    return {
        "preheat_cold_p50_ms": 0.3,
        "preheat_cold_p50_ms_nopreheat": 5.1,
        "preheat_hit_ratio": 1.0,
        "forecast_rate": 8000.0,
    }


def _fake_registry_bench():
    # the real soak spawns two daemons + proxies + gateways (~1s);
    # emission tests only assert the KEYS ride the artifact — the soak
    # itself is covered end-to-end by tests/test_flows.py and the CLI
    # soak (stress --registry)
    return {
        "proxy_pull_p50_ms": 9.5,
        "layer_dedup_ratio": 0.33,
        "p2p_efficiency": 0.83,
        "flow_conserved": 1,
        "registry_bad_bytes": 0,
        "registry_wall_s": 0.4,
    }


def _fake_flow_overhead_bench():
    return {
        "flow_accounting_overhead_pct": 1.1,
        "flow_account_us": 0.4,
        "schedule_op_flow_us": 33.0,
    }


def _fake_swarm_overhead_bench():
    return {
        "swarm_account_overhead_pct": 1.2,
        "swarm_account_us": 0.5,
        "swarm_snapshot_us": 45.0,
        "schedule_op_swarm_us": 33.0,
    }


def _run_main(monkeypatch, capfd, fit_stub):
    monkeypatch.setattr(bench, "synthesize_dataset", _fake_synthesize)
    monkeypatch.setattr(bench, "synthesize_dataset_binary", _fake_synthesize_binary)
    monkeypatch.setattr(bench, "chaos_soak_bench", _fake_chaos_soak)
    monkeypatch.setattr(bench, "fleet_shard_kill_bench", _fake_fleet_soak)
    monkeypatch.setattr(bench, "serving_bench", _fake_serving_bench)
    monkeypatch.setattr(bench, "wave_bench", _fake_wave_bench)
    monkeypatch.setattr(bench, "data_plane_bench", _fake_data_plane_bench)
    monkeypatch.setattr(bench, "multichip_scaling_bench", _fake_multichip_bench)
    monkeypatch.setattr(bench, "preheat_bench", _fake_preheat_bench)
    monkeypatch.setattr(bench, "registry_bench", _fake_registry_bench)
    monkeypatch.setattr(bench, "flow_overhead_bench", _fake_flow_overhead_bench)
    monkeypatch.setattr(bench, "swarm_overhead_bench", _fake_swarm_overhead_bench)
    monkeypatch.setattr(ingest, "stream_train_mlp", fit_stub)
    monkeypatch.setenv("DF_BENCH_REPEATS", "3")
    bench.main()
    lines = [l for l in capfd.readouterr().out.splitlines() if l.strip()]
    assert len(lines) == 1, f"exactly one JSON line expected, got: {lines}"
    return json.loads(lines[0])


def test_midrun_failure_keeps_completed_runs(monkeypatch, capfd):
    calls = {"n": 0}

    def stub(paths, **kw):
        calls["n"] += 1
        if calls["n"] == 1:  # warmup
            return None, _stats(0)
        if calls["n"] == 3:  # second timed run: the link "resets"
            raise RuntimeError("link reset")
        return None, _stats()

    rec = _run_main(monkeypatch, capfd, stub)
    assert rec["value"] > 0  # run 1's measurement survived
    assert "run 2/3 failed: link reset" in rec["run_error"]
    assert "error" not in rec


def test_failure_before_any_run_emits_error_line(monkeypatch, capfd):
    calls = {"n": 0}

    def stub(paths, **kw):
        calls["n"] += 1
        if calls["n"] == 1:  # warmup succeeds
            return None, _stats(0)
        raise RuntimeError("link down")

    rec = _run_main(monkeypatch, capfd, stub)
    assert rec["value"] == 0.0
    assert "run 1/3 failed: link down" in rec["error"]


def test_warmup_failure_emits_error_line(monkeypatch, capfd):
    def stub(paths, **kw):
        raise RuntimeError("link died in compile")

    rec = _run_main(monkeypatch, capfd, stub)
    assert rec["value"] == 0.0
    assert "warmup fit failed: link died in compile" in rec["error"]


def test_all_runs_complete_emits_best(monkeypatch, capfd):
    def stub(paths, **kw):
        return None, _stats(1000)

    rec = _run_main(monkeypatch, capfd, stub)
    assert rec["records"] == 1000
    # best = highest rate; the stub's wall time is real, so assert the
    # relationship rather than which draw won
    assert len(rec["run_rates"]) == 3
    assert rec["value"] == max(rec["run_rates"])
    assert "run_error" not in rec and "error" not in rec
    # the line names the backend the fit ran on, as jax reports it — no
    # relabelled fallback platform exists any more
    import jax

    assert rec["platform"] == jax.devices()[0].platform
    assert "fallback_reason" not in rec


def test_emits_decode_rate_per_payload_format(monkeypatch, capfd):
    """The artifact must carry the host-side decode rate for BOTH
    payload formats plus the production format name (ISSUE r6: the
    bottleneck split is a measured fact, per format)."""

    def stub(paths, **kw):
        return None, _stats(1000)

    rec = _run_main(monkeypatch, capfd, stub)
    assert rec["payload_format"] == wire.FORMAT_NAME
    assert rec["decode_only_rate_binary"] > 0
    assert "stream_only_rate" in rec
    from dragonfly2_tpu.schema import native

    if native.available():
        assert "decode_only_rate_csv" in rec
    # the e2e runs rode the binary shards
    assert rec["value"] == max(rec["run_rates"])
    # per-run producer stage split rides along
    for detail in rec["run_details"]:
        assert {"read_s", "cast_s", "enqueue_s"} <= set(detail)


def test_emits_topology_engine_rates(monkeypatch, capfd):
    """The artifact must carry the topology-engine soak numbers
    (ISSUE 2: the device adjacency is a measured subsystem, not a
    side effect): deltas-applied-per-second through flush and the
    est_rtt query p50."""

    def stub(paths, **kw):
        return None, _stats(1000)

    rec = _run_main(monkeypatch, capfd, stub)
    assert rec["topology_flush_rate"] > 0
    assert rec["topology_query_p50"] > 0
    assert "topology_error" not in rec


def test_emits_tracing_overhead(monkeypatch, capfd):
    """The artifact carries the tracing-overhead measurement (ISSUE 3:
    the unsampled span path is a measured cost on the scheduling hot
    path, not a hope): the relative overhead vs a stubbed-out tracing
    module, plus the absolute per-schedule cost of the unsampled span
    sequence."""

    def stub(paths, **kw):
        return None, _stats(1000)

    rec = _run_main(monkeypatch, capfd, stub)
    assert "tracing_error" not in rec
    assert rec["tracing_overhead_pct"] >= 0.0
    assert 0.0 < rec["tracing_unsampled_us"] < 50.0
    assert rec["schedule_op_us"] > 0


def test_emits_recorder_overhead(monkeypatch, capfd):
    """The artifact carries the flight-recorder overhead measurement
    (ISSUE 4: the always-on emitters are a measured cost on the
    scheduling hot path): the relative overhead plus the absolute
    per-emit cost and the schedule-op wall it was charged against."""

    def stub(paths, **kw):
        return None, _stats(1000)

    rec = _run_main(monkeypatch, capfd, stub)
    assert "recorder_error" not in rec
    assert rec["recorder_overhead_pct"] >= 0.0
    assert 0.0 < rec["recorder_emit_us"] < 50.0
    assert rec["schedule_op_with_recorder_us"] > 0


def test_emits_data_plane_keys(monkeypatch, capfd):
    """The artifact must carry the data-plane race (ISSUE 14: zero-copy
    serve throughput strictly above the buffered arm, the p99 serve
    tail, and daemon RSS are measured facts on every bench run)."""

    def stub(paths, **kw):
        return None, _stats(1000)

    rec = _run_main(monkeypatch, capfd, stub)
    assert "data_plane_error" not in rec
    assert rec["data_plane_bytes_per_s"] > rec["data_plane_bytes_per_s_buffered"]
    assert rec["piece_serve_p99_us"] > 0
    assert rec["daemon_rss_mb"] > 0
    assert rec["data_plane_hangs"] == 0


def test_data_plane_keys_survive_warmup_failure(monkeypatch, capfd):
    """host_rates (data-plane numbers included) ride every exit path —
    a dead device link must not discard the serve-side race."""

    def stub(paths, **kw):
        raise RuntimeError("link died in compile")

    rec = _run_main(monkeypatch, capfd, stub)
    assert "warmup fit failed" in rec["error"]
    assert rec["data_plane_bytes_per_s"] > 0
    assert rec["data_plane_bytes_per_s_buffered"] > 0


def test_recorder_overhead_survives_warmup_failure(monkeypatch, capfd):
    """host_rates (recorder numbers included) ride every exit path."""

    def stub(paths, **kw):
        raise RuntimeError("link died in compile")

    rec = _run_main(monkeypatch, capfd, stub)
    assert "warmup fit failed" in rec["error"]
    assert rec["recorder_overhead_pct"] >= 0.0
    assert rec["recorder_emit_us"] > 0


# Overhead gates are absolute-µs-OR-ratio (ISSUE 13 recalibration): the
# ratio denominators drifted as the schedule op itself got faster (PR 12
# measured ~23µs, down from 56-152µs when the 2% bars were set), so a
# fixed ~0.7-2µs emit/span/pre-flight cost can breach 2% on the
# UNMODIFIED tree purely through calibration drift. A cost under this
# floor is irreducibly tiny — well under 2% of any deployment-scale op —
# so it passes regardless of what the denominator did this round.
OVERHEAD_ABS_FLOOR_US = 3.0


def test_recorder_overhead_under_two_percent_or_abs_floor():
    """Acceptance bar (ISSUE 4, recalibrated in ISSUE 13): the always-on
    flight-recorder emitters cost < 2% of the scheduling hot-path wall
    OR under the absolute floor. Best-of-3 bench calls so container CPU
    contention can't fail a genuinely-cheap path."""
    runs = [bench.recorder_overhead_bench() for _ in range(3)]
    ok = any(
        r["recorder_overhead_pct"] < 2.0
        or r["recorder_emit_us"] < OVERHEAD_ABS_FLOOR_US
        for r in runs
    )
    assert ok, f"flight-recorder overhead too high: {runs}"


def test_recorder_bench_restores_enabled_state():
    """The microbench toggles the recorder's enabled flag; a bench run
    must leave recording in its prior state."""
    from dragonfly2_tpu.utils import flight

    prev = flight.enabled()
    try:
        flight.set_enabled(True)
        bench.recorder_overhead_bench(iters=50, trials=1)
        assert flight.enabled()
    finally:
        flight.set_enabled(prev)


def test_tracing_overhead_under_two_percent_or_abs_floor():
    """Acceptance bar (recalibrated in ISSUE 13): the disabled/unsampled
    tracing path costs < 2% of the scheduling hot-path wall OR under the
    absolute floor. Best-of-3 bench calls so container CPU contention
    can't fail a genuinely-cheap path."""
    runs = [bench.tracing_overhead_bench() for _ in range(3)]
    ok = any(
        r["tracing_overhead_pct"] < 2.0
        or r["tracing_unsampled_us"] < OVERHEAD_ABS_FLOOR_US
        for r in runs
    )
    assert ok, f"unsampled tracing overhead too high: {runs}"


def test_tracing_bench_restores_global_state():
    """The microbench patches tracing internals; a bench run must leave
    the module usable (sampled spans record again afterwards)."""
    from dragonfly2_tpu.utils import tracing

    prev = tracing._sample_ratio
    tracing._sample_ratio = 1.0
    try:
        bench.tracing_overhead_bench(iters=50, trials=1)
        tr = tracing.get("post-bench")
        tr.start_span("alive").end()
        assert tr.finished[-1].name == "alive"
    finally:
        tracing._sample_ratio = prev


def test_topology_rates_survive_warmup_failure(monkeypatch, capfd):
    """host_rates (topology numbers included) ride every exit path —
    a dead device link must not discard the scheduler-side soak."""

    def stub(paths, **kw):
        raise RuntimeError("link died in compile")

    rec = _run_main(monkeypatch, capfd, stub)
    assert "warmup fit failed" in rec["error"]
    assert rec["topology_flush_rate"] > 0
    assert rec["topology_query_p50"] > 0


def test_binary_decode_outruns_csv_decode(tmp_path):
    """Pure-decode microbench on the SAME records: the columnar block
    decoder must be strictly faster than the CSV decoder — the whole
    premise of shipping binary (acceptance: decode rate above the CSV
    decoder's on the same data)."""
    from dragonfly2_tpu.schema import native

    if not native.available():
        pytest.skip("native CSV decoder unavailable")
    from dragonfly2_tpu.schema.columnar import write_csv

    recs = synth.make_download_records(1500, seed=0)
    csv_path = tmp_path / "d.csv"
    write_csv(csv_path, recs)
    bin_path = tmp_path / "d.dfb"
    bin_path.write_bytes(wire.encode_train_block(recs))

    def rate(fn, passes):
        t0 = time.perf_counter()
        n = 0
        for _, _, n in fn(passes):
            pass
        return n / (time.perf_counter() - t0)

    # warm both once (page cache + lazy init), then measure
    for fn in (
        lambda p: wire.stream_train_pairs(bin_path, passes=p, half=True),
        lambda p: native.stream_pairs_file(csv_path, passes=p, half=True),
    ):
        for _ in fn(1):
            pass
    binary_rate = rate(lambda p: wire.stream_train_pairs(bin_path, passes=p, half=True), 8)
    csv_rate = rate(lambda p: native.stream_pairs_file(csv_path, passes=p, half=True), 8)
    assert binary_rate > csv_rate, (
        f"binary decode {binary_rate:.0f} rec/s must beat csv {csv_rate:.0f} rec/s"
    )


def test_emits_resilience_overhead_and_chaos_keys(monkeypatch, capfd):
    """The artifact carries the resilience-layer measurement (ISSUE 5:
    the fault-free pre-flight is a measured cost on the scheduling hot
    path) plus the chaos-soak numbers — both riding host_rates."""

    def stub(paths, **kw):
        return None, _stats(1000)

    rec = _run_main(monkeypatch, capfd, stub)
    assert "resilience_error" not in rec
    assert rec["resilience_overhead_pct"] >= 0.0
    assert 0.0 < rec["resilience_call_us"] < 50.0
    assert rec["schedule_op_resilience_us"] > 0
    assert "chaos_error" not in rec
    assert rec["chaos_success_rate"] == 1.0
    assert rec["chaos_hangs"] == 0
    assert "fleet_error" not in rec
    assert rec["fleet_success_rate"] == 1.0
    assert rec["fleet_hangs"] == 0
    assert rec["fleet_blackout_ms"] > 0
    assert rec["schedule_ops_per_s"] > 0
    # the ISSUE 20 two-arm failover keys ride the same artifact
    assert 0 < rec["fleet_blackout_ms_replicated"] < rec["fleet_blackout_ms_rebuild"]
    assert rec["swarm_adopt_ms"] > 0
    assert rec["swarm_adopt_outcome"] == "adopted"
    assert rec["fleet_victim_fallbacks"] == 0
    assert rec["swarm_replica_diff_clean"] == 1


def test_resilience_and_chaos_keys_survive_warmup_failure(monkeypatch, capfd):
    """host_rates (resilience + chaos numbers included) ride every exit
    path — a dead device link must not discard the host-side soak."""

    def stub(paths, **kw):
        raise RuntimeError("link died in compile")

    rec = _run_main(monkeypatch, capfd, stub)
    assert "warmup fit failed" in rec["error"]
    assert rec["resilience_overhead_pct"] >= 0.0
    assert rec["chaos_success_rate"] == 1.0
    assert rec["fleet_blackout_ms"] > 0  # fleet soak keys ride it too
    assert rec["fleet_blackout_ms_replicated"] > 0
    assert rec["swarm_adopt_ms"] > 0


def test_chaos_soak_failure_rides_exit_path(monkeypatch, capfd):
    """A chaos soak that can't run must degrade to a ``chaos_error`` key
    on the one JSON line — never a traceback with no artifact."""

    def stub(paths, **kw):
        return None, _stats(1000)

    def broken_soak():
        raise RuntimeError("no loopback in sandbox")

    monkeypatch.setattr(bench, "synthesize_dataset", _fake_synthesize)
    monkeypatch.setattr(bench, "synthesize_dataset_binary", _fake_synthesize_binary)
    monkeypatch.setattr(bench, "chaos_soak_bench", broken_soak)
    monkeypatch.setattr(bench, "fleet_shard_kill_bench", _fake_fleet_soak)
    monkeypatch.setattr(bench, "serving_bench", _fake_serving_bench)
    monkeypatch.setattr(bench, "multichip_scaling_bench", _fake_multichip_bench)
    monkeypatch.setattr(bench, "preheat_bench", _fake_preheat_bench)
    monkeypatch.setattr(bench, "registry_bench", _fake_registry_bench)
    monkeypatch.setattr(bench, "flow_overhead_bench", _fake_flow_overhead_bench)
    monkeypatch.setattr(bench, "swarm_overhead_bench", _fake_swarm_overhead_bench)
    monkeypatch.setattr(ingest, "stream_train_mlp", stub)
    monkeypatch.setenv("DF_BENCH_REPEATS", "3")
    bench.main()
    lines = [l for l in capfd.readouterr().out.splitlines() if l.strip()]
    assert len(lines) == 1
    rec = json.loads(lines[0])
    assert "no loopback in sandbox" in rec["chaos_error"]
    assert rec["resilience_overhead_pct"] >= 0.0  # its sibling still ran
    assert rec["fleet_success_rate"] == 1.0  # and so did the fleet soak


def test_fleet_soak_failure_rides_exit_path(monkeypatch, capfd):
    """A fleet shard-kill soak that can't run (no subprocess spawn in a
    sandbox) must degrade to a ``fleet_error`` key on the one JSON line,
    leaving its siblings intact."""

    def stub(paths, **kw):
        return None, _stats(1000)

    def broken_fleet():
        raise RuntimeError("scheduler shard failed to become READY")

    monkeypatch.setattr(bench, "synthesize_dataset", _fake_synthesize)
    monkeypatch.setattr(bench, "synthesize_dataset_binary", _fake_synthesize_binary)
    monkeypatch.setattr(bench, "chaos_soak_bench", _fake_chaos_soak)
    monkeypatch.setattr(bench, "fleet_shard_kill_bench", broken_fleet)
    monkeypatch.setattr(bench, "serving_bench", _fake_serving_bench)
    monkeypatch.setattr(bench, "multichip_scaling_bench", _fake_multichip_bench)
    monkeypatch.setattr(bench, "preheat_bench", _fake_preheat_bench)
    monkeypatch.setattr(bench, "registry_bench", _fake_registry_bench)
    monkeypatch.setattr(bench, "flow_overhead_bench", _fake_flow_overhead_bench)
    monkeypatch.setattr(bench, "swarm_overhead_bench", _fake_swarm_overhead_bench)
    monkeypatch.setattr(ingest, "stream_train_mlp", stub)
    monkeypatch.setenv("DF_BENCH_REPEATS", "3")
    bench.main()
    lines = [l for l in capfd.readouterr().out.splitlines() if l.strip()]
    assert len(lines) == 1
    rec = json.loads(lines[0])
    assert "failed to become READY" in rec["fleet_error"]
    assert rec["chaos_success_rate"] == 1.0


def test_emits_jit_hygiene_keys(monkeypatch, capfd):
    """The artifact carries the dispatch-plane hygiene measurement
    (ISSUE 11): zero recompiles on a warm fit and ~one H2D per
    superbatch, riding host_rates."""

    def stub(paths, **kw):
        return None, _stats(1000)

    rec = _run_main(monkeypatch, capfd, stub)
    assert "jit_hygiene_error" not in rec
    assert rec["jit_recompiles_per_fit"] == 0  # warm fit reuses every executable
    assert 0.0 < rec["h2d_transfers_per_superbatch"] <= 2.0


def test_jit_hygiene_keys_survive_warmup_failure(monkeypatch, capfd):
    """host_rates (jit-hygiene numbers included) ride every exit path —
    a dead device link must not discard the dispatch-plane counters."""

    def stub(paths, **kw):
        raise RuntimeError("link died in compile")

    rec = _run_main(monkeypatch, capfd, stub)
    assert "warmup fit failed" in rec["error"]
    assert rec["jit_recompiles_per_fit"] == 0
    assert rec["h2d_transfers_per_superbatch"] > 0


def test_jit_hygiene_bench_steady_state():
    """Acceptance bar (ISSUE 11): the production step cache serves a
    warm fit with ZERO recompiles, and the packed superbatch feed costs
    exactly one H2D per dispatch."""
    out = bench.jit_hygiene_bench(batch=256, steps_per_call=2, superbatches=3)
    assert out["jit_recompiles_per_fit"] == 0
    assert out["h2d_transfers_per_superbatch"] == 1.0


def test_emits_multichip_scaling_and_overlap_keys(monkeypatch, capfd):
    """ISSUE 15: the artifact carries the standing dp=1/2/4/8 scaling
    curve (honestly platform-labeled), the sharded-put witness gates,
    and the h2d_overlap_pct of the best timed run — plus the full
    per-split device-leg attribution inside run_details."""

    def stub(paths, **kw):
        s = _stats(1000)
        s.h2d_s = 0.5
        s.h2d_overlap_s = 0.4
        s.step_s = 2.0
        return None, s

    rec = _run_main(monkeypatch, capfd, stub)
    assert "multichip_error" not in rec
    assert set(rec["multichip_scaling"]) == {"1", "2", "4", "8"}
    assert rec["multichip_platform"] == "cpu-forced-host-devices"
    assert rec["mesh_h2d_per_shard"] == 1.0
    assert rec["mesh_pack_thread_transfers"] == 0
    assert rec["h2d_overlap_pct"] == 80.0
    for detail in rec["run_details"]:
        assert {"h2d_s", "h2d_overlap_s", "step_s"} <= set(detail)


def test_multichip_keys_survive_warmup_failure(monkeypatch, capfd):
    """host_rates (the multichip curve included) ride every exit path —
    a dead device link must not discard the standing scaling curve."""

    def stub(paths, **kw):
        raise RuntimeError("link died in compile")

    rec = _run_main(monkeypatch, capfd, stub)
    assert "warmup fit failed" in rec["error"]
    assert set(rec["multichip_scaling"]) == {"1", "2", "4", "8"}


def test_multichip_bench_failure_rides_exit_path(monkeypatch, capfd):
    """A multichip curve that can't run (no subprocess spawn in a
    sandbox) must degrade to a ``multichip_error`` key on the one JSON
    line, leaving its siblings intact."""

    def stub(paths, **kw):
        return None, _stats(1000)

    def broken_multichip():
        raise RuntimeError("spawn blocked by sandbox")

    monkeypatch.setattr(bench, "synthesize_dataset", _fake_synthesize)
    monkeypatch.setattr(bench, "synthesize_dataset_binary", _fake_synthesize_binary)
    monkeypatch.setattr(bench, "chaos_soak_bench", _fake_chaos_soak)
    monkeypatch.setattr(bench, "fleet_shard_kill_bench", _fake_fleet_soak)
    monkeypatch.setattr(bench, "serving_bench", _fake_serving_bench)
    monkeypatch.setattr(bench, "data_plane_bench", _fake_data_plane_bench)
    monkeypatch.setattr(bench, "multichip_scaling_bench", broken_multichip)
    monkeypatch.setattr(bench, "preheat_bench", _fake_preheat_bench)
    monkeypatch.setattr(bench, "registry_bench", _fake_registry_bench)
    monkeypatch.setattr(bench, "flow_overhead_bench", _fake_flow_overhead_bench)
    monkeypatch.setattr(bench, "swarm_overhead_bench", _fake_swarm_overhead_bench)
    monkeypatch.setattr(ingest, "stream_train_mlp", stub)
    monkeypatch.setenv("DF_BENCH_REPEATS", "3")
    bench.main()
    lines = [l for l in capfd.readouterr().out.splitlines() if l.strip()]
    assert len(lines) == 1
    rec = json.loads(lines[0])
    assert "spawn blocked" in rec["multichip_error"]
    assert rec["chaos_success_rate"] == 1.0  # siblings still ran


def test_emits_telemetry_overhead(monkeypatch, capfd):
    """The artifact carries the telemetry-plane measurement (ISSUE 9:
    the reporter's per-push snapshot+encode is a measured duty cycle,
    not a hope), riding host_rates on every exit path."""

    def stub(paths, **kw):
        return None, _stats(1000)

    rec = _run_main(monkeypatch, capfd, stub)
    assert "telemetry_error" not in rec
    assert 0.0 <= rec["telemetry_push_overhead_pct"] < 2.0
    assert rec["telemetry_snapshot_us"] > 0
    assert rec["telemetry_series"] >= 1


def test_telemetry_overhead_survives_warmup_failure(monkeypatch, capfd):
    """host_rates (telemetry numbers included) ride every exit path."""

    def stub(paths, **kw):
        raise RuntimeError("link died in compile")

    rec = _run_main(monkeypatch, capfd, stub)
    assert "warmup fit failed" in rec["error"]
    assert rec["telemetry_push_overhead_pct"] >= 0.0
    assert rec["telemetry_snapshot_us"] > 0


def test_telemetry_overhead_under_two_percent():
    """Acceptance bar (ISSUE 9): the telemetry reporter's per-push work
    costs < 2% duty cycle over the push interval. Best-of-3 bench calls
    so container CPU contention can't fail a genuinely-cheap path."""
    vals = [
        bench.telemetry_overhead_bench()["telemetry_push_overhead_pct"]
        for _ in range(3)
    ]
    assert min(vals) < 2.0, f"telemetry push overhead too high: {vals}"


def test_emits_prof_overhead(monkeypatch, capfd):
    """The artifact carries the dfprof sampler measurement (ISSUE 12:
    the continuous profiler's sweep duty cycle is measured, not hoped),
    riding host_rates like every prior observability gate."""

    def stub(paths, **kw):
        return None, _stats(1000)

    rec = _run_main(monkeypatch, capfd, stub)
    assert "prof_error" not in rec
    assert rec["prof_overhead_pct"] >= 0.0
    assert rec["prof_sample_us"] > 0
    assert rec["prof_hz"] > 0


def test_prof_overhead_survives_warmup_failure(monkeypatch, capfd):
    """host_rates (dfprof numbers included) ride every exit path."""

    def stub(paths, **kw):
        raise RuntimeError("link died in compile")

    rec = _run_main(monkeypatch, capfd, stub)
    assert "warmup fit failed" in rec["error"]
    assert rec["prof_overhead_pct"] >= 0.0
    assert rec["prof_sample_us"] > 0


def test_prof_overhead_under_two_percent():
    """Acceptance bar (ISSUE 12): the always-on sampler costs < 2% of
    one core at the configured rate. Best-of-3 bench calls so container
    CPU contention can't fail a genuinely-cheap path."""
    vals = [bench.prof_overhead_bench()["prof_overhead_pct"] for _ in range(3)]
    assert min(vals) < 2.0, f"dfprof sampler overhead too high: {vals}"


def test_resilience_overhead_under_two_percent_or_abs_floor():
    """Acceptance bar (ISSUE 5, recalibrated in ISSUE 13): the
    resilience layer's fault-free pre-flight costs < 2% of the
    scheduling hot-path wall OR under the absolute floor. Best-of-3
    bench calls so container CPU contention can't fail a genuinely-cheap
    path."""
    runs = [bench.resilience_overhead_bench() for _ in range(3)]
    ok = any(
        r["resilience_overhead_pct"] < 2.0
        or r["resilience_call_us"] < OVERHEAD_ABS_FLOOR_US
        for r in runs
    )
    assert ok, f"resilience overhead too high: {runs}"


def test_emits_serving_keys(monkeypatch, capfd):
    """The artifact carries the batched-serving soak numbers (ISSUE 13:
    schedule decisions/sec is the product metric — batched vs per-call
    rates, batch occupancy, and the p99 decision tail are measured
    facts), riding host_rates like every prior gate."""

    def stub(paths, **kw):
        return None, _stats(1000)

    rec = _run_main(monkeypatch, capfd, stub)
    assert "serving_error" not in rec
    assert rec["serving_ops_per_s_batched"] > 0
    assert rec["serving_ops_per_s_per_call"] > 0
    assert rec["evaluator_batch_occupancy"] > 0
    assert rec["schedule_decision_p99_us"] > 0
    assert rec["serving_lost"] == 0


def test_serving_keys_survive_warmup_failure(monkeypatch, capfd):
    """host_rates (serving numbers included) ride every exit path — a
    dead device link must not discard the scheduler-side soak."""

    def stub(paths, **kw):
        raise RuntimeError("link died in compile")

    rec = _run_main(monkeypatch, capfd, stub)
    assert "warmup fit failed" in rec["error"]
    assert rec["serving_ops_per_s_batched"] > 0
    assert rec["evaluator_batch_occupancy"] > 0


def test_serving_bench_failure_rides_exit_path(monkeypatch, capfd):
    """A serving soak that can't run must degrade to a ``serving_error``
    key on the one JSON line, leaving its siblings intact."""

    def stub(paths, **kw):
        return None, _stats(1000)

    def broken_serving():
        raise RuntimeError("no threads in sandbox")

    monkeypatch.setattr(bench, "synthesize_dataset", _fake_synthesize)
    monkeypatch.setattr(bench, "synthesize_dataset_binary", _fake_synthesize_binary)
    monkeypatch.setattr(bench, "chaos_soak_bench", _fake_chaos_soak)
    monkeypatch.setattr(bench, "fleet_shard_kill_bench", _fake_fleet_soak)
    monkeypatch.setattr(bench, "serving_bench", broken_serving)
    monkeypatch.setattr(bench, "wave_bench", _fake_wave_bench)
    # stubbed like in every sibling: left real by omission, the multichip
    # curve alone spawned four subprocess fits (~1 min) this test never reads
    monkeypatch.setattr(bench, "data_plane_bench", _fake_data_plane_bench)
    monkeypatch.setattr(bench, "multichip_scaling_bench", _fake_multichip_bench)
    monkeypatch.setattr(bench, "preheat_bench", _fake_preheat_bench)
    monkeypatch.setattr(bench, "registry_bench", _fake_registry_bench)
    monkeypatch.setattr(bench, "flow_overhead_bench", _fake_flow_overhead_bench)
    monkeypatch.setattr(bench, "swarm_overhead_bench", _fake_swarm_overhead_bench)
    monkeypatch.setattr(ingest, "stream_train_mlp", stub)
    monkeypatch.setenv("DF_BENCH_REPEATS", "3")
    bench.main()
    lines = [l for l in capfd.readouterr().out.splitlines() if l.strip()]
    assert len(lines) == 1
    rec = json.loads(lines[0])
    assert "no threads in sandbox" in rec["serving_error"]
    assert rec["chaos_success_rate"] == 1.0  # siblings unharmed
    assert rec["fleet_success_rate"] == 1.0
    assert rec["wave_decisions_per_s"] > 0  # the wave soak still rode


def test_emits_wave_keys(monkeypatch, capfd):
    """The artifact carries the wave-scheduling soak numbers (ISSUE 16:
    wave-packed vs per-op-batched decisions/sec, wave occupancy rows,
    and the segment-unpack p99 are measured facts), riding host_rates
    like every prior gate."""

    def stub(paths, **kw):
        return None, _stats(1000)

    rec = _run_main(monkeypatch, capfd, stub)
    assert "wave_error" not in rec
    assert rec["wave_decisions_per_s"] > 0
    assert rec["wave_decisions_per_s_per_op"] > 0
    assert rec["wave_occupancy_rows"] > 0
    assert rec["wave_unpack_p99_us"] > 0
    assert rec["wave_rankings_match"] == 1
    assert rec["wave_lost"] == 0


def test_wave_keys_survive_warmup_failure(monkeypatch, capfd):
    """host_rates (wave numbers included) ride every exit path — a dead
    device link must not discard the scheduler-side wave soak."""

    def stub(paths, **kw):
        raise RuntimeError("link died in compile")

    rec = _run_main(monkeypatch, capfd, stub)
    assert "warmup fit failed" in rec["error"]
    assert rec["wave_decisions_per_s"] > 0
    assert rec["wave_occupancy_rows"] > 0


def test_wave_bench_failure_rides_exit_path(monkeypatch, capfd):
    """A wave soak that can't run must degrade to a ``wave_error`` key
    on the one JSON line, leaving its siblings intact."""

    def stub(paths, **kw):
        return None, _stats(1000)

    def broken_wave():
        raise RuntimeError("no wave threads in sandbox")

    monkeypatch.setattr(bench, "synthesize_dataset", _fake_synthesize)
    monkeypatch.setattr(bench, "synthesize_dataset_binary", _fake_synthesize_binary)
    monkeypatch.setattr(bench, "chaos_soak_bench", _fake_chaos_soak)
    monkeypatch.setattr(bench, "fleet_shard_kill_bench", _fake_fleet_soak)
    monkeypatch.setattr(bench, "serving_bench", _fake_serving_bench)
    monkeypatch.setattr(bench, "wave_bench", broken_wave)
    monkeypatch.setattr(bench, "data_plane_bench", _fake_data_plane_bench)
    monkeypatch.setattr(bench, "multichip_scaling_bench", _fake_multichip_bench)
    monkeypatch.setattr(bench, "preheat_bench", _fake_preheat_bench)
    monkeypatch.setattr(bench, "registry_bench", _fake_registry_bench)
    monkeypatch.setattr(bench, "flow_overhead_bench", _fake_flow_overhead_bench)
    monkeypatch.setattr(bench, "swarm_overhead_bench", _fake_swarm_overhead_bench)
    monkeypatch.setattr(ingest, "stream_train_mlp", stub)
    monkeypatch.setenv("DF_BENCH_REPEATS", "3")
    bench.main()
    lines = [l for l in capfd.readouterr().out.splitlines() if l.strip()]
    assert len(lines) == 1
    rec = json.loads(lines[0])
    assert "no wave threads in sandbox" in rec["wave_error"]
    assert rec["serving_ops_per_s_batched"] > 0  # siblings unharmed
    assert rec["chaos_success_rate"] == 1.0


def test_emits_preheat_keys(monkeypatch, capfd):
    """The artifact carries the predictive-preheat soak numbers
    (ISSUE 17: armed vs no-preheat cold-start p50, the seed hit ratio,
    and the steady-state forecast rate are measured facts), riding
    host_rates like every prior gate."""

    def stub(paths, **kw):
        return None, _stats(1000)

    rec = _run_main(monkeypatch, capfd, stub)
    assert "preheat_error" not in rec
    assert rec["preheat_cold_p50_ms"] > 0
    assert rec["preheat_cold_p50_ms_nopreheat"] > rec["preheat_cold_p50_ms"]
    assert 0.0 <= rec["preheat_hit_ratio"] <= 1.0
    assert rec["forecast_rate"] > 0


def test_preheat_keys_survive_warmup_failure(monkeypatch, capfd):
    """host_rates (preheat numbers included) ride every exit path — a
    dead device link must not discard the forecast→place soak."""

    def stub(paths, **kw):
        raise RuntimeError("link died in compile")

    rec = _run_main(monkeypatch, capfd, stub)
    assert "warmup fit failed" in rec["error"]
    assert rec["preheat_cold_p50_ms"] > 0
    assert rec["preheat_cold_p50_ms_nopreheat"] > 0
    assert rec["forecast_rate"] > 0


def test_preheat_bench_failure_rides_exit_path(monkeypatch, capfd):
    """A preheat soak that can't run must degrade to a
    ``preheat_error`` key on the one JSON line, leaving its siblings
    intact."""

    def stub(paths, **kw):
        return None, _stats(1000)

    def broken_preheat():
        raise RuntimeError("no forecaster in sandbox")

    monkeypatch.setattr(bench, "synthesize_dataset", _fake_synthesize)
    monkeypatch.setattr(bench, "synthesize_dataset_binary", _fake_synthesize_binary)
    monkeypatch.setattr(bench, "chaos_soak_bench", _fake_chaos_soak)
    monkeypatch.setattr(bench, "fleet_shard_kill_bench", _fake_fleet_soak)
    monkeypatch.setattr(bench, "serving_bench", _fake_serving_bench)
    monkeypatch.setattr(bench, "wave_bench", _fake_wave_bench)
    monkeypatch.setattr(bench, "data_plane_bench", _fake_data_plane_bench)
    monkeypatch.setattr(bench, "multichip_scaling_bench", _fake_multichip_bench)
    monkeypatch.setattr(bench, "preheat_bench", broken_preheat)
    monkeypatch.setattr(bench, "registry_bench", _fake_registry_bench)
    monkeypatch.setattr(bench, "flow_overhead_bench", _fake_flow_overhead_bench)
    monkeypatch.setattr(bench, "swarm_overhead_bench", _fake_swarm_overhead_bench)
    monkeypatch.setattr(ingest, "stream_train_mlp", stub)
    monkeypatch.setenv("DF_BENCH_REPEATS", "3")
    bench.main()
    lines = [l for l in capfd.readouterr().out.splitlines() if l.strip()]
    assert len(lines) == 1
    rec = json.loads(lines[0])
    assert "no forecaster in sandbox" in rec["preheat_error"]
    assert rec["wave_decisions_per_s"] > 0  # siblings unharmed
    assert rec["chaos_success_rate"] == 1.0


def test_emits_flow_ledger_keys(monkeypatch, capfd):
    """The artifact carries the flow-ledger soak numbers (ISSUE 18:
    proxy pull p50, the second tag's dedup ratio and p2p efficiency,
    per-plane byte conservation, and the accounting overhead are
    measured facts), riding host_rates like every prior gate."""

    def stub(paths, **kw):
        return None, _stats(1000)

    rec = _run_main(monkeypatch, capfd, stub)
    assert "registry_error" not in rec
    assert rec["proxy_pull_p50_ms"] > 0
    assert rec["layer_dedup_ratio"] > 0
    assert rec["p2p_efficiency"] > 0.5
    assert rec["flow_conserved"] == 1
    assert rec["registry_bad_bytes"] == 0
    assert "flow_error" not in rec
    assert rec["flow_accounting_overhead_pct"] >= 0.0
    assert rec["flow_account_us"] > 0


def test_flow_ledger_keys_survive_warmup_failure(monkeypatch, capfd):
    """host_rates (flow-ledger numbers included) ride every exit path —
    a dead device link must not discard the traffic-plane soak."""

    def stub(paths, **kw):
        raise RuntimeError("link died in compile")

    rec = _run_main(monkeypatch, capfd, stub)
    assert "warmup fit failed" in rec["error"]
    assert rec["layer_dedup_ratio"] > 0
    assert rec["p2p_efficiency"] > 0.5
    assert rec["flow_accounting_overhead_pct"] >= 0.0


def test_registry_soak_failure_rides_exit_path(monkeypatch, capfd):
    """A registry soak that can't run must degrade to a
    ``registry_error`` key on the one JSON line, leaving its siblings
    intact."""

    def stub(paths, **kw):
        return None, _stats(1000)

    def broken_registry():
        raise RuntimeError("no proxies in sandbox")

    monkeypatch.setattr(bench, "synthesize_dataset", _fake_synthesize)
    monkeypatch.setattr(bench, "synthesize_dataset_binary", _fake_synthesize_binary)
    monkeypatch.setattr(bench, "chaos_soak_bench", _fake_chaos_soak)
    monkeypatch.setattr(bench, "fleet_shard_kill_bench", _fake_fleet_soak)
    monkeypatch.setattr(bench, "serving_bench", _fake_serving_bench)
    monkeypatch.setattr(bench, "wave_bench", _fake_wave_bench)
    monkeypatch.setattr(bench, "data_plane_bench", _fake_data_plane_bench)
    monkeypatch.setattr(bench, "multichip_scaling_bench", _fake_multichip_bench)
    monkeypatch.setattr(bench, "preheat_bench", _fake_preheat_bench)
    monkeypatch.setattr(bench, "registry_bench", broken_registry)
    monkeypatch.setattr(bench, "flow_overhead_bench", _fake_flow_overhead_bench)
    monkeypatch.setattr(bench, "swarm_overhead_bench", _fake_swarm_overhead_bench)
    monkeypatch.setattr(ingest, "stream_train_mlp", stub)
    monkeypatch.setenv("DF_BENCH_REPEATS", "3")
    bench.main()
    lines = [l for l in capfd.readouterr().out.splitlines() if l.strip()]
    assert len(lines) == 1
    rec = json.loads(lines[0])
    assert "no proxies in sandbox" in rec["registry_error"]
    assert rec["flow_account_us"] > 0  # its sibling still rode
    assert rec["chaos_success_rate"] == 1.0


def test_flow_accounting_overhead_under_two_percent_or_abs_floor():
    """Acceptance bar (ISSUE 18, same recalibrated form as ISSUE 13):
    the per-piece flow-ledger attribution costs < 2% of the scheduling
    hot-path wall OR under the absolute floor. Best-of-3 bench calls so
    container CPU contention can't fail a genuinely-cheap path."""
    runs = [bench.flow_overhead_bench() for _ in range(3)]
    ok = any(
        r["flow_accounting_overhead_pct"] < 2.0
        or r["flow_account_us"] < OVERHEAD_ABS_FLOOR_US
        for r in runs
    )
    assert ok, f"flow accounting overhead too high: {runs}"


def test_flow_overhead_bench_resets_ledger():
    """The microbench pumps fake bytes through the ledger; a bench run
    must leave the module counters clean for whatever runs next."""
    from dragonfly2_tpu.utils import flows

    bench.flow_overhead_bench(iters=50, trials=1)
    assert flows.snapshot()["total_bytes"] == 0
    assert flows.task_plane("bench-task") == "file"


def test_emits_swarm_observatory_keys(monkeypatch, capfd):
    """The artifact carries the swarm-observatory numbers (ISSUE 19:
    per-piece accounting overhead and snapshot materialisation cost are
    measured facts), riding host_rates like every prior gate."""

    def stub(paths, **kw):
        return None, _stats(1000)

    rec = _run_main(monkeypatch, capfd, stub)
    assert "swarm_error" not in rec
    assert rec["swarm_account_overhead_pct"] >= 0.0
    assert rec["swarm_account_us"] > 0
    assert rec["swarm_snapshot_us"] > 0


def test_swarm_overhead_under_two_percent_or_abs_floor():
    """Acceptance bar (ISSUE 19, same recalibrated form as the flow
    gate): the observatory's per-piece bookkeeping costs < 2% of the
    scheduling hot-path wall OR under the absolute floor. Best-of-3
    bench calls so container CPU contention can't fail a genuinely-cheap
    path."""
    runs = [bench.swarm_overhead_bench() for _ in range(3)]
    ok = any(
        r["swarm_account_overhead_pct"] < 2.0
        or r["swarm_account_us"] < OVERHEAD_ABS_FLOOR_US
        for r in runs
    )
    assert ok, f"swarm accounting overhead too high: {runs}"


def test_swarm_overhead_bench_resets_ledger():
    """The microbench registers fake peers; a bench run must leave the
    observatory empty for whatever runs next."""
    from dragonfly2_tpu.scheduler import swarm

    bench.swarm_overhead_bench(iters=50, trials=1)
    snap = swarm.snapshot()
    assert snap["task_count"] == 0
    assert snap["peer_count"] == 0
