"""Forced-host-platform data-parallel fit harness (ISSUE 15): one
streamed MLP fit on a ``dp``-wide mesh of host-platform devices, with
the dispatch plane witnessed, printed as one JSON line.

    python -m dragonfly2_tpu.tools.multichip_fit --dp 4 --mb 12

Sets ``XLA_FLAGS=--xla_force_host_platform_device_count`` BEFORE jax
initializes (when the caller didn't), so the dp>1 ingest code path —
per-device sharded puts, replicated params, donated step state, the
scan+dp batch layout — runs end to end in a CPU-only image. This is the
harness behind the ``tools/soak_ingest.py --mesh`` arm and the
subprocess test in tests/test_multichip_ingest.py.

The harness also enforces the dispatch-plane contract with the
jit-witness taps (hack/dfanalyze/jitwitness.py):

- ``h2d_per_shard`` — host→device conversions per superbatch per device
  shard. Exactly 1.0 on a clean pipeline: each chip receives its row
  shard once, and nothing re-uploads via resharding.
- ``pack_thread_transfers`` — conversions issued by the packing thread.
  Must be 0: the device leg lives on the transfer/step stage threads.

The dp>1 rates are honest CODE-PATH numbers, not ICI bandwidth claims:
forced host-platform devices share the host's cores, so the curve shows
the sharding/collective machinery's cost shape on this container, with
the platform labeled in the artifact.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time


def ensure_devices(n: int) -> None:
    """Arrange for ≥ ``n`` addressable devices. Must run before jax's
    first backend query; if jax is already initialized with fewer
    devices, raise — the caller should have spawned a fresh process.

    Only the host-platform device-count flag is set — it is inert
    unless the CPU backend ends up selected, so a host with ≥ n REAL
    chips runs on them (the platform is labeled in every artifact).
    Callers that specifically want the CPU code-path proof (the
    subprocess test) export JAX_PLATFORMS=cpu themselves."""
    if "jax" in sys.modules and getattr(sys.modules["jax"], "devices", None):
        import jax

        try:
            have = len(jax.devices())
        except Exception:
            have = 0
        if have < n:
            raise RuntimeError(
                f"jax already initialized with {have} devices < dp={n};"
                " run the harness in a fresh process"
            )
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={max(n, 1)}"
        ).strip()


def run(
    dp: int,
    mb: int = 12,
    batch_size: int = 8192,
    steps_per_call: int = 4,
    passes: int = 64,
    time_budget_s: float = 8.0,
    workers: int = 1,
) -> dict:
    import jax

    devices = jax.devices()
    if len(devices) < dp:
        raise RuntimeError(
            f"{len(devices)} addressable devices < dp={dp}"
            " (is --xla_force_host_platform_device_count set before jax"
            " initialized?)"
        )

    from dragonfly2_tpu.schema.synth import synthesize_dataset_binary
    from dragonfly2_tpu.trainer.ingest import stream_train_mlp

    mesh = None
    if dp > 1:
        from dragonfly2_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(devices[:dp], dp=dp)
    if batch_size % dp:
        raise ValueError(f"batch_size {batch_size} not divisible by dp={dp}")

    # the witness taps are optional: the harness is spawned from the
    # repo root (tests/soak), where hack/ is importable; a
    # site-installed package still measures throughput without them
    try:
        from hack.dfanalyze import jitwitness
    except ImportError:
        jitwitness = None

    with tempfile.TemporaryDirectory(prefix="dfmc-") as d:
        paths = synthesize_dataset_binary(
            d, shards=2, shard_bytes=mb * 1024 * 1024 // 2
        )
        k = max(steps_per_call, 1)
        # warmup compiles the (dp-specific) executables outside the
        # timed + witnessed window
        stream_train_mlp(
            paths[0],
            passes=1,
            max_records=2 * k * batch_size // 4,
            batch_size=batch_size,
            workers=1,
            eval_every=0,
            mesh=mesh,
            steps_per_call=k,
        )

        pack_thread = threading.current_thread().name
        tap_cm = jitwitness.transfer_tap() if jitwitness else None
        t0 = time.perf_counter()
        if tap_cm:
            tap_cm.__enter__()
        try:
            _, stats = stream_train_mlp(
                paths,
                passes=passes,
                batch_size=batch_size,
                workers=workers,
                eval_every=0,
                mesh=mesh,
                steps_per_call=k,
                time_budget_s=time_budget_s,
            )
        finally:
            if tap_cm:
                tap_cm.__exit__(None, None, None)
        dt = time.perf_counter() - t0

    out = {
        "metric": "multichip_fit",
        "dp": dp,
        "platform": devices[0].platform,
        "forced_host_devices": "--xla_force_host_platform_device_count"
        in os.environ.get("XLA_FLAGS", ""),
        "records": stats.download_records,
        "steps": stats.steps,
        "truncated": stats.truncated,
        "wall_s": round(dt, 2),
        "records_per_s": round(stats.download_records / dt, 1) if dt else 0.0,
        "h2d_s": round(stats.h2d_s, 4),
        "step_s": round(stats.step_s, 4),
        "h2d_overlap_pct": stats.h2d_overlap_pct,
    }
    dispatches = stats.steps // k
    if jitwitness is not None and dispatches:
        out["h2d_per_shard"] = round(tap_cm.h2d / (dispatches * dp), 3)
        out["pack_thread_transfers"] = tap_cm.by_thread.get(pack_thread, 0)
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="df-multichip-fit", description=__doc__)
    p.add_argument("--dp", type=int, default=8, help="data-parallel width")
    p.add_argument("--mb", type=int, default=12, help="on-disk dataset size")
    p.add_argument("--batch-size", type=int, default=8192)
    p.add_argument("--steps-per-call", type=int, default=4)
    p.add_argument("--passes", type=int, default=64)
    p.add_argument("--time-budget-s", type=float, default=8.0)
    p.add_argument("--workers", type=int, default=1)
    args = p.parse_args(argv)
    ensure_devices(args.dp)
    from dragonfly2_tpu.utils.jitcache import enable_compile_cache

    enable_compile_cache()
    out = run(
        args.dp,
        mb=args.mb,
        batch_size=args.batch_size,
        steps_per_call=args.steps_per_call,
        passes=args.passes,
        time_budget_s=args.time_budget_s,
        workers=args.workers,
    )
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
