#!/usr/bin/env python3
"""Two-scheduler cluster e2e: the shared-KV (Redis role) deployment shape.

    manager (gRPC + embedded RESP KV server)
    → scheduler-1 + scheduler-2, both pointed at the manager's KV
    → daemon A + daemon B with BOTH schedulers in their static list
    → dfgets whose task ids deterministically hash to each scheduler
      (consistent-hash affinity actually splits the workload)
    → SyncProbes from both daemons land in the ONE shared store
    → each scheduler's topology snapshot exports edges the OTHER
      scheduler's clients synced (cross-process sharing, the round-4
      verdict's last architectural hole)

Reference shape: N schedulers × one Redis
(scheduler/networktopology/network_topology.go:88-89 takes a
redis.UniversalClient; key schema pkg/redis/redis.go). Exit 0 = PASS.
"""

from __future__ import annotations

import glob as globmod
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from hack.run_cluster import Proc  # noqa: E402 — shared process harness


def wait_for(pred, timeout: float, what: str, interval: float = 0.5):
    deadline = time.time() + timeout
    while time.time() < deadline:
        v = pred()
        if v:
            return v
        time.sleep(interval)
    raise TimeoutError(f"timed out waiting for {what}")


def main() -> int:
    work = tempfile.mkdtemp(prefix="dfcluster2-")
    env = dict(
        os.environ,
        PYTHONPATH=REPO,
        PYTHONUNBUFFERED="1",
        # a CPU harness: trainer and scheduler each own device planes,
        # and a chip belongs to one process at a time
        JAX_PLATFORMS="cpu",
    )
    procs: list[Proc] = []
    try:
        manager = Proc(
            "manager",
            [
                "-m", "dragonfly2_tpu.manager",
                "--set", f"data_dir={work}/manager",
                "--set", "kv_port=0",
                "--set", "kv_host=127.0.0.1",
            ],
            env,
        )
        procs.append(manager)
        manager_addr = manager.wait_ready()
        kv_addr = manager.kv_addr
        assert kv_addr, "manager did not report a KV address"
        print(f"manager kv at {kv_addr}")

        scheds = []
        for i in (1, 2):
            s = Proc(
                f"scheduler-{i}",
                [
                    "-m", "dragonfly2_tpu.scheduler",
                    "--set", f"data_dir={work}/scheduler-{i}",
                    "--set", f"manager_address={manager_addr}",
                    "--set", f"kv_address={kv_addr}",
                    "--set", f"hostname=sched-{i}",
                    "--set", "storage_buffer_size=1",
                    # fast probe-graph CSV export so the cross-visibility
                    # assertion lands within the script's lifetime
                    "--set", "topology_snapshot_interval=2.0",
                ],
                env,
            )
            procs.append(s)
            scheds.append(s)
        sched_addrs = [s.wait_ready() for s in scheds]
        sched_list = ",".join(sched_addrs)

        daemons = []
        for name in ("a", "b"):
            d = Proc(
                f"daemon-{name}",
                [
                    "-m", "dragonfly2_tpu.client.daemon",
                    "--set", f"data_dir={work}/daemon-{name}",
                    "--set", f"hostname=host-{name}",
                    "--set", f"scheduler_address={sched_list}",
                    "--set", "piece_length=65536",
                    "--set", "schedule_timeout=10.0",
                    "--set", "probe_interval=0.5",
                ],
                env,
            )
            procs.append(d)
            daemons.append(d)
        daemon_addrs = [d.wait_ready() for d in daemons]

        # -- task affinity: pick origin files whose task ids hash to EACH
        # scheduler, so the split is deterministic, not luck
        from dragonfly2_tpu.rpc.glue import ConsistentHashRing
        from dragonfly2_tpu.utils.idgen import task_id_v1

        ring = ConsistentHashRing(sched_addrs)
        by_sched: dict[str, list[str]] = {a: [] for a in sched_addrs}
        i = 0
        while any(len(v) < 2 for v in by_sched.values()):
            path = os.path.join(work, f"origin-{i}.bin")
            url = f"file://{path}"
            node = ring.pick(task_id_v1(url, None))
            if len(by_sched[node]) < 2:
                with open(path, "wb") as f:
                    f.write(os.urandom(96 * 1024 + i))
                by_sched[node].append(url)
            i += 1
        urls = [u for v in by_sched.values() for u in v]

        for j, url in enumerate(urls):
            out = os.path.join(work, f"out-{j}.bin")
            rc = subprocess.run(
                [
                    sys.executable, "-m", "dragonfly2_tpu.client.dfget",
                    url, "-O", out, "--daemon", daemon_addrs[j % 2],
                ],
                env=env, cwd=REPO, capture_output=True, text=True, timeout=120,
            )
            assert rc.returncode == 0, f"dfget {url} failed: {rc.stderr[-2000:]}"
            assert (
                open(out, "rb").read() == open(url[len("file://"):], "rb").read()
            ), f"bytes mismatch for {url}"
        print(f"PASS {len(urls)} dfgets across both daemons")

        # -- consistent-hash affinity split the workload: each scheduler
        # wrote Download records for ITS tasks
        def records_of(i):
            rows = []
            for p in globmod.glob(
                os.path.join(work, f"scheduler-{i}", "records", "**", "download*.csv"),
                recursive=True,
            ):
                if os.path.getsize(p) > 0:
                    rows.append(p)
            return rows

        wait_for(lambda: records_of(1) and records_of(2), 30,
                 "download records on both schedulers")
        print("PASS task affinity split records across both schedulers")

        # -- SyncProbes from both daemons landed in the ONE shared store
        from dragonfly2_tpu.utils.kvstore import RemoteKVStore

        kv = RemoteKVStore(kv_addr)

        def probe_srcs():
            srcs = set()
            for key in kv.scan_iter("networktopology:*"):
                srcs.add(key.split(":", 2)[1])
            return srcs if len(srcs) >= 2 else None

        srcs = wait_for(probe_srcs, 60, "probe edges from two hosts in the shared KV")
        assert len(srcs) >= 2, srcs
        counts = kv.scan_iter("probedcount:*")
        assert counts, "no probed-count counters in the shared store"
        print(f"PASS SyncProbes from {len(srcs)} hosts share one KV store ({len(counts)} counters)")

        # -- cross-process visibility: EACH scheduler's topology snapshot
        # exports edges for BOTH daemons, including the edge synced via
        # the other scheduler (both read the same store; hosts are known
        # everywhere because the daemon announces to every scheduler)
        def snapshot_srcs(i):
            srcs = set()
            for p in globmod.glob(
                os.path.join(
                    work, f"scheduler-{i}", "records", "**", "networktopology*.csv"
                ),
                recursive=True,
            ):
                if os.path.getsize(p) == 0:
                    continue
                with open(p) as f:
                    header = f.readline().strip().split(",")
                    try:
                        idx = header.index("host.id")
                    except ValueError:
                        continue
                    for line in f:
                        cells = line.split(",")
                        if len(cells) > idx and cells[idx]:
                            srcs.add(cells[idx])
            return srcs

        wait_for(
            lambda: len(snapshot_srcs(1)) >= 2 and len(snapshot_srcs(2)) >= 2,
            60,
            "both schedulers exporting both hosts' probe edges",
        )
        print("PASS each scheduler snapshots the SHARED graph (both hosts' edges)")

        print("CLUSTER2 E2E: ALL PASS")
        return 0
    finally:
        for p in reversed(procs):
            p.stop()
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
