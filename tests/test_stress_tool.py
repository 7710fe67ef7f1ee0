"""Stress load generator (reference test/tools/stress) against an
in-process cluster."""

import json
import os
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from dragonfly2_tpu.client.daemon import Daemon, DaemonConfig
from dragonfly2_tpu.rpc.glue import serve
from dragonfly2_tpu.scheduler import resource as res
from dragonfly2_tpu.scheduler.evaluator import BaseEvaluator
from dragonfly2_tpu.scheduler.scheduling import Scheduling, SchedulingConfig
from dragonfly2_tpu.scheduler.service import SERVICE_NAME as SCHED_SERVICE
from dragonfly2_tpu.scheduler.service import SchedulerService
from dragonfly2_tpu.tools import stress

# these tests call main() in-process
pytestmark = pytest.mark.usefixtures("compile_cache_off")


@pytest.fixture
def cluster(tmp_path):
    payload = os.urandom(64 * 1024)

    class Origin(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            self.send_response(200)
            self.send_header("Content-Length", str(len(payload)))
            self.end_headers()
            self.wfile.write(payload)

        def do_HEAD(self):
            self.send_response(200)
            self.send_header("Content-Length", str(len(payload)))
            self.send_header("Accept-Ranges", "bytes")
            self.end_headers()

    origin = ThreadingHTTPServer(("127.0.0.1", 0), Origin)
    threading.Thread(target=origin.serve_forever, daemon=True).start()

    service = SchedulerService(
        res.Resource(),
        Scheduling(BaseEvaluator(), SchedulingConfig(retry_interval=0.05)),
    )
    server, port = serve({SCHED_SERVICE: service})
    d = Daemon(
        DaemonConfig(
            data_dir=str(tmp_path / "d"),
            scheduler_address=f"127.0.0.1:{port}",
            hostname="stress-host",
            ip="127.0.0.1",
            announce_interval=60.0,
        )
    )
    d.start()
    yield {
        "daemon": f"127.0.0.1:{d.port}",
        "origin": f"http://127.0.0.1:{origin.server_port}",
        "payload": payload,
    }
    d.stop()
    server.stop(0)
    origin.shutdown()
    origin.server_close()


def test_stress_daemon_mode_counts_and_percentiles(cluster):
    stats = stress.run(
        cluster["origin"] + "/obj-{i}.bin",
        daemon=cluster["daemon"],
        connections=3,
        requests=12,
    )
    assert stats["requests"] >= 12 and stats["failures"] == 0
    assert stats["bytes"] >= 12 * 64 * 1024
    lat = stats["latency_s"]
    assert 0 < lat["min"] <= lat["p50"] <= lat["p90"] <= lat["p99"] <= lat["max"]
    assert stats["rps"] > 0 and stats["throughput_mb_s"] > 0


def test_stress_duration_stop_and_csv(cluster, tmp_path):
    out = tmp_path / "samples.csv"
    stats = stress.run(
        cluster["origin"] + "/one.bin",  # single task: dedup/reuse path
        daemon=cluster["daemon"],
        connections=2,
        duration=2.0,
        output=str(out),
    )
    assert stats["requests"] > 0
    lines = out.read_text().strip().splitlines()
    assert lines[0] == "ok,seconds,bytes,error"
    assert len(lines) == stats["requests"] + 1


def test_stress_cli_json_line(cluster, capsys):
    rc = stress.main(
        [
            "--url", cluster["origin"] + "/cli-{i}.bin",
            "--daemon", cluster["daemon"],
            "-c", "2", "-n", "4",
        ]
    )
    assert rc == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    parsed = json.loads(line)
    assert parsed["failures"] == 0 and parsed["requests"] >= 4


def test_stress_requires_exactly_one_target():
    with pytest.raises(ValueError):
        stress.run("http://x", daemon="a", proxy="b", requests=1)
    with pytest.raises(ValueError):
        stress.run("http://x", requests=1)


def test_shard_kill_soak_success_and_bounded_blackout():
    """Acceptance (ISSUE 8): 3 real scheduler shards under KV leases,
    simulated-peer announce load, one shard SIGKILL'd mid-load —
    success rate must be 1.0 with zero hangs, and the measured
    ``fleet_blackout_ms`` bounded by one lease TTL + one membership
    poll + announce/backoff slack. Deterministic: the blackout ends
    when the dead lease expires, not on a race.

    With the telemetry plane riding along (ISSUE 9), the manager's
    view of the kill must MATCH the daemon-measured one: the victim's
    shard flips stale within the staleness envelope of the same
    SIGKILL, and the manager aggregates live schedule ops across the
    surviving shards."""
    lease_ttl, poll = 1.5, 0.3
    stats = stress.shard_kill_soak(
        peers=60,
        shards=3,
        workers=8,
        lease_ttl=lease_ttl,
        renew_interval=0.4,
        poll_interval=poll,
    )
    assert stats["fleet_success_rate"] == 1.0, stats
    assert stats["fleet_hangs"] == 0
    assert stats["fleet_shards"] == 3
    # blackout: bounded below by ~nothing, above by TTL + poll + slack
    assert 0 <= stats["fleet_blackout_ms"] <= (lease_ttl + poll + 3.0) * 1e3, stats
    assert stats["schedule_ops_per_s"] > 0
    assert stats["fleet_wrong_shard_retries"] > 0  # the window was real
    # the manager's view of the member kill (telemetry plane): all 3
    # shards reported in, and the victim flipped stale within the
    # staleness envelope of the SAME SIGKILL the announce plane measured:
    # last push ≤0.5s before the kill + staleness floor 5s + soak poll
    # 0.25s + scheduling slack — i.e. the manager detects the kill at
    # its own (coarser) granularity, never misses it, never pre-dates it
    assert "fleet_telemetry_error" not in stats, stats
    assert stats["fleet_manager_shards"] == 3
    # staleness floor is 5s (max(3×0.5s push interval, 5.0)): detection
    # can't physically land before ~4.5s (last push up to 0.5s pre-kill)
    # and must land within floor + push/poll/scheduling slack
    assert 3_000 <= stats["fleet_manager_blackout_ms"] <= 9_000, stats
    assert stats["fleet_manager_schedule_ops_per_s"] > 0
    json.dumps(stats)  # one JSON-serializable line


def test_shard_kill_cli_gates_on_success(capsys):
    rc = stress.main(["--chaos", "--shard-kill", "--shard-peers", "30"])
    line = capsys.readouterr().out.strip().splitlines()[-1]
    parsed = json.loads(line)
    assert rc == 0, parsed
    assert parsed["fleet_success_rate"] == 1.0


def test_soak_ingest_tool_reports_bounded_memory():
    """The soak tool streams a multi-shard dataset and reports flat RSS
    (working set independent of decoded bytes — the 1B-record property).
    Decode volume is verified by MEASUREMENT: two passes must count
    exactly twice one pass's records, untruncated."""
    import json as _json

    from dragonfly2_tpu.tools import soak_ingest

    one = soak_ingest.run(mb=48, passes=1, batch_size=8192, steps_per_call=2, workers=1)
    two = soak_ingest.run(mb=48, passes=2, batch_size=8192, steps_per_call=2, workers=1)
    assert not one["truncated"] and not two["truncated"]
    assert one["records"] > 0
    assert two["records"] == 2 * one["records"]
    # growth must be a small fraction of what flowed through (generous
    # bound: jit arenas and allocator slack are real, hoarding is not)
    assert two["rss_growth_mb"] < two["decoded_mb"]
    _json.dumps(two)  # one JSON-serializable line
