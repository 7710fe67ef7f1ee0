#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json, on the accelerator.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's inputs from the seed, warms every shape (set-up),
measures for ``--seconds``, checks what the timed path produced against
the plain reference, and prints one JSON object as the last line of
stdout. ``--trace 0`` reports the cell's end-to-end metrics, ``--trace
1`` its per-layer metrics with the device's busy time. Without a TPU, or
with fewer chips than the cell asks for, it exits 3 and prints no result.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.normpath(os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class Context:
    """What a generator is handed: the cell, the seed, the window's
    length, a scratch directory inside the checkout, the devices, and
    the marks that open and close the measured window."""

    def __init__(self, cell, seed, seconds, trace, workdir, devices, tap, tracer):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace, self.workdir, self.devices = trace, workdir, devices
        self.tap, self.tracer = tap, tracer
        self.setup_s = None
        self.compiles_in_window = None
        self.retraces_in_window = None
        self.device = None
        self.compiled_in_setup = self._requests0 = 0
        # perf_counter readings a generator leaves at the end of each
        # set-up phase; printed as seconds since the process started
        self.marks: dict = {"process_start": _T_START}

    def window_opens(self) -> None:
        self.setup_s = time.perf_counter() - _T_START
        self.compiled_in_setup = self.tap.really_compiled()
        self._requests0 = self.tap.compiles
        self.compile_s_setup = self.tap.compile_s

    def window_closes(self) -> None:
        from benchmarks.harness import device as dev

        # a program that builds a jit wrapper anew for each fit traces it
        # again every round; with the persistent cache that is a lookup,
        # not a compilation. What may not happen in the window is a miss.
        self.compiles_in_window = self.tap.really_compiled() - self.compiled_in_setup
        self.retraces_in_window = self.tap.compiles - self._requests0
        self.compiled_by_close = self.tap.really_compiled()
        # the peak is read before the reference runs: it is the program's
        self.device = dev.describe(self.devices)


def run_cell(cell, seed: int, seconds: float, trace: bool, devices: list, workroot: str) -> dict:
    """Drive one run; everything but the look for a chip."""
    from benchmarks.harness import cells, device as dev
    from benchmarks.harness.layer_readers import read_metric

    os.makedirs(workroot, exist_ok=True)
    workdir = tempfile.mkdtemp(prefix=f"{cell.name}-", dir=workroot)
    tap = dev.CompileTap()
    tracer = dev.Tracer(trace, os.path.join(workdir, "trace"))
    ctx = Context(cell, seed, seconds, trace, workdir, devices, tap, tracer)
    try:
        out = cells.generator_for(cell.traffic).run(ctx)
        traced = tracer.reduce()
        gc.collect()
        checks = [("compiles_in_window", float(ctx.compiles_in_window), 0.0)]
        t_ref = time.perf_counter()
        checks += out["after_window"]()
        print(f"reference: the comparison took {time.perf_counter() - t_ref:.2f}s", flush=True)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    correct = True
    for name, value, limit in checks:
        ok = (not math.isnan(value)) and value <= limit
        correct = correct and ok
        print(f"check {name}: {value!r} limit {limit!r} {'ok' if ok else 'NOT CORRECT'}", flush=True)
    print(f"notes: {json.dumps(out.get('notes', {}))}", flush=True)
    print(
        "set-up phases end at (s): "
        + json.dumps({k: round(v - _T_START, 2) for k, v in ctx.marks.items()}),
        flush=True,
    )
    print(
        f"compile: {tap.compiles} executables asked for, {tap.cache_requests} through the"
        f" persistent cache with {tap.cache_hits} hits, {tap.compile_s:.2f}s compiling or"
        f" loading ({ctx.compile_s_setup:.2f}s of it in set-up); compiled: {ctx.compiled_in_setup} in"
        f" set-up, {ctx.compiles_in_window} inside the window,"
        f" {tap.really_compiled() - ctx.compiled_by_close} by the reference after it;"
        f" {ctx.retraces_in_window} traced again and loaded inside the window",
        flush=True,
    )
    device = dict(ctx.device)
    if trace:
        probes = dict(out["probes"])
        probes["device_trace"] = traced
        metrics = {}
        for decl in cell.per_layer:
            value = read_metric(decl, probes)
            if value is not None:
                metrics[decl["name"]] = {"value": value, "unit": decl["unit"]}
        device["busy_s"] = traced["busy_s"]
        device["window_s"] = traced["window_s"]
        result_extra = {
            "breakdown": {"device_ops": traced["device_ops"], "idle_gaps": traced["idle_gaps"]}
        }
    else:
        values = dict(out["metrics"], setup_s=ctx.setup_s)
        metrics = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end
            if m["name"] in values
        }
        result_extra = {}
    return {
        "correct": bool(correct),
        "attempted": int(out["attempted"]),
        "failed": int(out["failed"]),
        "metrics": metrics,
        "device": device,
        **result_extra,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks.harness import cells, device as dev

    cell = cells.load_cell(args.workload)
    from dragonfly2_tpu.utils.jitcache import enable_compile_cache

    enable_compile_cache()
    devices = dev.require_chips(cell.chips)
    dev.peaks_for(devices[0].device_kind)  # a device not in the table is an error
    result = run_cell(
        cell, args.seed, args.seconds, bool(args.trace), devices,
        os.path.join(ROOT, ".bench_work"),
    )
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
