"""Subprocess-level e2e: the real service binaries
(`python -m dragonfly2_tpu.{manager,scheduler,trainer}` and
`python -m dragonfly2_tpu.client.daemon`) boot as OS processes, a real
dfget runs against them, and bytes + training records land — the
reference's kind/compose e2e suite in miniature (test/e2e/dfget_test.go,
hack/install-e2e-test.sh)."""

import os
import subprocess
import sys


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_run_cluster_script():
    env = dict(os.environ, DF_QUIET="1", JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "hack", "run_cluster.py")],
        env=env,
        cwd=REPO,
        capture_output=True,
        text=True,
        # headroom over the script's own internal deadlines (the model
        # wait alone may take 240s when three first-compiles share one
        # CPU core) — the script fails itself long before this fires
        timeout=480,
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-3000:]}"
    assert "CLUSTER E2E: ALL PASS" in proc.stdout


def test_run_cluster_two_schedulers_shared_kv():
    """Round-4 verdict item 2: TWO scheduler processes sharing the Redis
    role through the manager's embedded RESP KV server — consistent-hash
    affinity splits tasks, SyncProbes from both daemons land in one
    store, and each scheduler snapshots the whole shared probe graph."""
    env = dict(os.environ, DF_QUIET="1", JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "hack", "run_cluster_multisched.py")],
        env=env,
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=420,
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-3000:]}"
    assert "CLUSTER2 E2E: ALL PASS" in proc.stdout
