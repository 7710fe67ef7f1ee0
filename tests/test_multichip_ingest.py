"""The dp>1 ingest fit path (ISSUE 15): per-device sharded puts,
donated step state, the overlapped transfer/step stages, and the
dp-vs-single-device loss trajectory — exercised on the session's forced
host-platform devices (tests/conftest.py arms 8) plus one subprocess
run of the tools/multichip_fit harness with its jit-witness gates.
"""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import jax

from dragonfly2_tpu.parallel.mesh import make_mesh
from dragonfly2_tpu.schema import synth, wire
from dragonfly2_tpu.trainer import ingest

REPO = Path(__file__).resolve().parent.parent

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs >=4 forced host-platform devices"
)


def _block_file(tmp_path, n=800, seed=0):
    p = tmp_path / "d.dfb"
    p.write_bytes(wire.encode_train_block(synth.make_download_records(n, seed=seed)))
    return str(p)


# ---------------------------------------------------------------------------
# sharded put: row placement
# ---------------------------------------------------------------------------


class TestShardedPut:
    def test_each_device_holds_exactly_its_row_shard(self):
        """parallel.sharding.shard_superbatch: device i's shard must be
        rows [i·per, (i+1)·per) of the host buffer — each chip received
        only its slice, nothing resharded."""
        from dragonfly2_tpu.parallel.sharding import shard_superbatch

        mesh = make_mesh(jax.devices()[:4], dp=4)
        buf = np.arange(8 * 20, dtype=np.float32).reshape(8, 20)
        arr = shard_superbatch(mesh, buf)
        assert arr.shape == (8, 20)
        per = 2
        seen = 0
        for s in arr.addressable_shards:
            i = list(mesh.devices.flat).index(s.device)
            np.testing.assert_array_equal(
                np.asarray(s.data), buf[i * per : (i + 1) * per]
            )
            seen += 1
        assert seen == 4
        np.testing.assert_array_equal(np.asarray(arr), buf)

    def test_scan_layout_shards_batch_dim(self):
        """k>1 superbatches shard dim 1 (the batch dim); the leading
        scan axis stays whole on every device."""
        from dragonfly2_tpu.parallel.sharding import shard_superbatch

        mesh = make_mesh(jax.devices()[:4], dp=4)
        buf = np.arange(3 * 8 * 5, dtype=np.float32).reshape(3, 8, 5)
        arr = shard_superbatch(mesh, buf, batch_dim=1)
        for s in arr.addressable_shards:
            i = list(mesh.devices.flat).index(s.device)
            assert s.data.shape == (3, 2, 5)
            np.testing.assert_array_equal(
                np.asarray(s.data), buf[:, i * 2 : (i + 1) * 2]
            )

    def test_indivisible_batch_raises(self):
        from dragonfly2_tpu.parallel.sharding import shard_superbatch

        mesh = make_mesh(jax.devices()[:4], dp=4)
        with pytest.raises(ValueError, match="not divisible"):
            shard_superbatch(mesh, np.zeros((6, 3), np.float32))


# ---------------------------------------------------------------------------
# donation: the step consumes its carried state
# ---------------------------------------------------------------------------


def test_step_donates_carried_state_buffer_not_rereadable():
    """_get_step/_get_scan_step donate (params, opt_state): after one
    dispatch the old device buffers are invalidated — re-reading raises
    instead of silently aliasing stale HBM. Pinned for both the single
    and the scan step, and for dp-sharded inputs."""
    from dragonfly2_tpu.models.mlp import init_mlp
    from dragonfly2_tpu.parallel.sharding import replicate, shard_superbatch
    from dragonfly2_tpu.schema.features import MLP_FEATURE_DIM

    opt, step = ingest._get_step(3e-3, 1e-4)
    params = init_mlp(jax.random.PRNGKey(0), [MLP_FEATURE_DIM, 16, 1])
    opt_state = opt.init(params)
    old_w = params["layers"][0]["w"]
    xy = np.zeros((8, MLP_FEATURE_DIM + 1), np.float16)
    import jax.numpy as jnp

    params, opt_state, _ = step(params, opt_state, jnp.asarray(xy))
    with pytest.raises(RuntimeError):
        np.asarray(old_w)

    # the dp-sharded scan variant donates identically
    mesh = make_mesh(jax.devices()[:4], dp=4)
    opt, scan_step = ingest._get_scan_step(3e-3, 1e-4, 2)
    params = replicate(mesh, init_mlp(jax.random.PRNGKey(0), [MLP_FEATURE_DIM, 16, 1]))
    opt_state = opt.init(params)
    old_w = params["layers"][0]["w"]
    dev = shard_superbatch(
        mesh, np.zeros((2, 8, MLP_FEATURE_DIM + 1), np.float16), batch_dim=1
    )
    params, opt_state, _ = scan_step(params, opt_state, dev)
    with pytest.raises(RuntimeError):
        np.asarray(old_w)


# ---------------------------------------------------------------------------
# dp>1 vs dp=1: same stream, comparable loss trajectory
# ---------------------------------------------------------------------------


def test_dp4_loss_trajectory_matches_dp1_on_same_stream(tmp_path):
    """The sharded fit must be the SAME fit: identical stream, identical
    batch schedule, loss trajectory equal to the single-device run up to
    cross-shard reduction order (float32 compute on this backend, so the
    tolerance is tight)."""
    p = _block_file(tmp_path, n=900, seed=5)
    mesh = make_mesh(jax.devices()[:4], dp=4)
    kw = dict(passes=2, batch_size=64, eval_every=0, workers=1)
    p1, s1 = ingest.stream_train_mlp(p, **kw)
    p4, s4 = ingest.stream_train_mlp(p, mesh=mesh, **kw)
    assert s1.steps == s4.steps > 0
    assert len(s1.losses) == len(s4.losses)
    np.testing.assert_allclose(
        np.asarray(s1.losses), np.asarray(s4.losses), rtol=1e-4, atol=1e-6
    )
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=2e-4, atol=2e-5,
        )


def test_indivisible_batch_falls_back_unsharded(tmp_path, caplog):
    """A batch that doesn't divide the dp axis degrades to the
    replicated feed (with a warning), never fails the fit — the
    auto-mesh default must be safe for every dataset size."""
    p = _block_file(tmp_path, n=300, seed=1)
    mesh = make_mesh(jax.devices()[:4], dp=4)
    _, stats = ingest.stream_train_mlp(
        p, passes=1, batch_size=63, eval_every=0, mesh=mesh
    )
    assert stats.steps > 0


# ---------------------------------------------------------------------------
# overlap accounting
# ---------------------------------------------------------------------------


def test_h2d_overlap_measured_under_busy_step(tmp_path, monkeypatch):
    """With the step stage deliberately slow, later superbatches'
    transfers run while a step executes — h2d_overlap_s must catch a
    real fraction of h2d_s, and never exceed it."""

    def fake_get_step(lr, wd, warmup_steps=64):
        class _Opt:
            def init(self, params):
                return {}

        def step(params, opt_state, xy):
            time.sleep(0.03)  # device leg busy; transfers should overlap
            return params, opt_state, np.float32(0.1)

        return _Opt(), step

    monkeypatch.setattr(ingest, "_get_step", fake_get_step)
    p = _block_file(tmp_path, n=800, seed=2)
    _, stats = ingest.stream_train_mlp(
        p,
        passes=6,
        batch_size=64,
        eval_every=0,
        params={"unused": np.zeros(1)},
        workers=1,
    )
    assert stats.steps > 4
    assert stats.h2d_s > 0
    assert 0 < stats.h2d_overlap_s <= stats.h2d_s


def test_stream_done_event_carries_overlap_split(tmp_path):
    """EV_STREAM_DONE attributes h2d/h2d_overlap/step once per run —
    the flight-ring form of the per-run split, with the transfer wall
    recorded by the transfer stage and step wall by the step stage (no
    double count of one superbatch's wall)."""
    from dragonfly2_tpu.utils import flight

    p = _block_file(tmp_path, n=600, seed=3)
    _, stats = ingest.stream_train_mlp(p, passes=2, batch_size=64, eval_every=0)
    ring = flight.recorder().snapshot(["trainer"])["trainer"]
    events = [e for e in ring if e.get("type") == "trainer.stream_done"]
    assert events, "no stream_done event in the trainer ring"
    ev = events[-1]
    assert "h2d_overlap_s" in ev
    assert ev["h2d_s"] >= ev["h2d_overlap_s"] >= 0
    # per-superbatch events: each carries BOTH stage measurements
    supers = [e for e in ring if e.get("type") == "trainer.superbatch"]
    assert supers
    assert {"h2d_s", "step_s"} <= set(supers[-1])


# ---------------------------------------------------------------------------
# auto-mesh promotion
# ---------------------------------------------------------------------------


def test_training_builds_dp_mesh_by_default(tmp_path):
    """Training promotes the dormant mesh= plumbing: with >1 addressable
    device the default config fits data-parallel; auto_mesh=False (or an
    explicit mesh) opts out."""
    from dragonfly2_tpu.trainer.storage import TrainerStorage
    from dragonfly2_tpu.trainer.training import Training, TrainingConfig

    storage = TrainerStorage(tmp_path / "store")
    t = Training(storage)
    assert t.mesh is not None
    assert dict(t.mesh.shape) == {"dp": len(jax.devices())}
    t_off = Training(storage, config=TrainingConfig(auto_mesh=False))
    assert t_off.mesh is None


def test_training_raises_when_the_fit_mesh_cannot_be_built(tmp_path, monkeypatch):
    """A multi-chip host whose mesh construction fails must not turn
    into a single-device fit that looks healthy."""
    from dragonfly2_tpu.parallel import mesh as mesh_mod
    from dragonfly2_tpu.trainer.storage import TrainerStorage
    from dragonfly2_tpu.trainer.training import Training

    def broken():
        raise RuntimeError("device enumeration failed")

    monkeypatch.setattr(mesh_mod, "auto_dp_mesh", broken)
    with pytest.raises(RuntimeError, match="device enumeration failed"):
        Training(TrainerStorage(tmp_path / "store"))


# ---------------------------------------------------------------------------
# the subprocess harness (tools/multichip_fit)
# ---------------------------------------------------------------------------


def test_multichip_fit_subprocess_witness_gates(tmp_path):
    """tools/multichip_fit in a fresh process with forced host-platform
    devices: the dp=2 fit must report exactly one H2D per device shard
    per superbatch (no double upload via resharding) and ZERO device
    feeds from the packing thread — the ISSUE 15 dispatch-plane gates."""
    env = {
        k: v
        for k, v in os.environ.items()
        if not k.startswith(("DF_LOCK_WITNESS", "DF_JIT_WITNESS"))
    }
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    proc = subprocess.run(
        [
            sys.executable,
            "-m",
            "dragonfly2_tpu.tools.multichip_fit",
            "--dp",
            "2",
            "--mb",
            "2",
            "--batch-size",
            "1024",
            "--steps-per-call",
            "2",
            "--passes",
            "8",
            "--time-budget-s",
            "2",
        ],
        capture_output=True,
        text=True,
        timeout=150,
        env=env,
        cwd=str(REPO),
    )
    blob = proc.stdout + proc.stderr
    if proc.returncode != 0 and "addressable devices" in blob:
        pytest.skip("forced host-platform device count unsupported here")
    assert proc.returncode == 0, blob[-800:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["dp"] == 2
    assert rec["records"] > 0 and rec["steps"] > 0
    assert rec["forced_host_devices"] is True
    assert rec["h2d_per_shard"] == 1.0
    assert rec["pack_thread_transfers"] == 0
