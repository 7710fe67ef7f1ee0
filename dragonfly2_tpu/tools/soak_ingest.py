"""Sustained-ingestion soak: stream-train over an on-disk dataset while
sampling process RSS — the bounded-memory evidence behind the 1B-record
north star (SURVEY §6): the streaming path's working set must stay flat
no matter how many bytes flow through it.

    python -m dragonfly2_tpu.tools.soak_ingest --mb 512 --passes 2
    python -m dragonfly2_tpu.tools.soak_ingest --mb 256 --mesh 4

Prints one JSON line: records/sec, bytes decoded, RSS baseline / peak /
growth. Growth staying orders of magnitude below the dataset size is
the point — the decode queue, packing buffers, and device feed are all
fixed-size (trainer/ingest.py), so terabyte datasets ride through the
same few hundred MB of host memory. ``--mesh N`` runs the dp-N
data-parallel arm (ISSUE 15: per-device sharded puts + the overlapped
transfer/step stages get a standing soak), forcing host-platform
devices when the backend has fewer than N chips.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time


def _rss_mb() -> float:
    with open("/proc/self/statm") as f:
        pages = int(f.read().split()[1])
    return pages * os.sysconf("SC_PAGE_SIZE") / 1e6


def run(
    mb: int,
    passes: int,
    batch_size: int,
    steps_per_call: int,
    workers: int,
    mesh_devices: int = 0,
) -> dict:
    from dragonfly2_tpu.schema.synth import synthesize_dataset_csv
    from dragonfly2_tpu.trainer.ingest import stream_train_mlp

    mesh = None
    if mesh_devices > 1:
        # the dp>1 overlap + sharded-put path gets a standing soak arm
        # (ISSUE 15): main() forced the host-platform device count
        # before jax loaded, so this works in a CPU-only image too
        import jax

        from dragonfly2_tpu.parallel.mesh import make_mesh

        devices = jax.devices()
        if len(devices) < mesh_devices:
            raise RuntimeError(
                f"{len(devices)} addressable devices < --mesh {mesh_devices}"
            )
        mesh = make_mesh(devices[:mesh_devices], dp=mesh_devices)
        if batch_size % mesh_devices:
            raise ValueError(
                f"--batch-size {batch_size} not divisible by --mesh {mesh_devices}"
            )

    samples: list[float] = []
    stop = threading.Event()

    def sampler():
        while not stop.is_set():
            samples.append(_rss_mb())
            stop.wait(0.25)

    with tempfile.TemporaryDirectory(prefix="dfsoak-") as d:
        shards = max(2, workers)
        paths = synthesize_dataset_csv(
            d, shards=shards, shard_bytes=mb * 1024 * 1024 // shards
        )
        dataset_bytes = sum(os.path.getsize(p) for p in paths)

        # warmup compiles the step OUTSIDE the sampled window so jit
        # arena growth doesn't read as streaming growth
        stream_train_mlp(
            paths[0], passes=1, max_records=steps_per_call * batch_size,
            batch_size=batch_size, workers=1, eval_every=0,
            steps_per_call=steps_per_call, mesh=mesh,
        )
        baseline = _rss_mb()
        t = threading.Thread(target=sampler, daemon=True)
        t.start()
        t0 = time.perf_counter()
        try:
            _, stats = stream_train_mlp(
                paths, passes=passes, batch_size=batch_size, workers=workers,
                eval_every=0, steps_per_call=steps_per_call, mesh=mesh,
            )
        finally:
            # a failed stream must not leak a forever-sampling thread
            stop.set()
            t.join()
        dt = time.perf_counter() - t0

    import jax

    peak = max(samples) if samples else baseline
    return {
        "metric": "ingest_soak",
        # honest platform label: --mesh may run on real chips or on
        # forced host-platform devices depending on what's addressable
        "platform": jax.devices()[0].platform,
        "mesh_devices": mesh_devices if mesh is not None else 1,
        "h2d_overlap_pct": stats.h2d_overlap_pct,
        "dataset_mb": round(dataset_bytes / 1e6, 1),
        "passes": passes,
        "decoded_mb": round(dataset_bytes * passes / 1e6, 1),
        "records": stats.download_records,
        "truncated": stats.truncated,
        "records_per_s": round(stats.download_records / dt, 1),
        "wall_s": round(dt, 2),
        "rss_baseline_mb": round(baseline, 1),
        "rss_peak_mb": round(peak, 1),
        "rss_growth_mb": round(peak - baseline, 1),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="df-soak-ingest", description=__doc__)
    p.add_argument("--mb", type=int, default=512, help="on-disk dataset size")
    p.add_argument("--passes", type=int, default=2)
    p.add_argument("--batch-size", type=int, default=65_536)
    p.add_argument("--steps-per-call", type=int, default=4)
    p.add_argument("--workers", type=int, default=min(4, os.cpu_count() or 1))
    p.add_argument(
        "--mesh",
        type=int,
        default=0,
        metavar="N",
        help="dp-N data-parallel fit (sharded puts + overlap); forces"
        " host-platform devices when the backend has fewer than N",
    )
    args = p.parse_args(argv)
    if args.mesh > 1:
        # must happen before jax initializes (run() imports it)
        from dragonfly2_tpu.tools.multichip_fit import ensure_devices

        ensure_devices(args.mesh)
    from dragonfly2_tpu.utils.jitcache import enable_compile_cache

    enable_compile_cache()
    stats = run(
        args.mb,
        args.passes,
        args.batch_size,
        args.steps_per_call,
        args.workers,
        mesh_devices=args.mesh,
    )
    print(json.dumps(stats))
    return 0


if __name__ == "__main__":
    sys.exit(main())
