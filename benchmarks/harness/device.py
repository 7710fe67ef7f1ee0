"""The accelerator a run holds: the refusal to measure without one, what
JAX reports of it, compilations counted from ``jax.monitoring``, and a
traced stretch of the window.
"""

from __future__ import annotations

import json
import os
import shutil
import threading
import time

PEAKS_FILE = os.path.join(os.path.dirname(__file__), "..", "peaks.json")


class NoAccelerator(SystemExit):
    """Raised (exit code 3, nothing printed on stdout) when JAX offers
    no TPU or fewer chips than the cell asks for."""


def require_chips(chips: int) -> list:
    import jax

    devices = jax.devices()
    # the program spans every chip it can address, so a host with more
    # chips than the cell asks for would run another cell
    if devices[0].platform != "tpu" or len(devices) != chips:
        import sys

        print(
            f"refusing to measure: need {chips} TPU chip(s), JAX offers"
            f" {len(devices)} x {devices[0].platform}",
            file=sys.stderr,
        )
        raise NoAccelerator(3)
    return devices


def peaks_for(device_kind: str) -> dict:
    """The published peaks of this device; one not in the table is an
    error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks on record for device kind {device_kind!r}")
    return table[device_kind]


def describe(devices: list) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }


class CompileTap:
    """Counts executables asked of the backend, those looked up in the
    persistent cache and the hits among them, from JAX's own monitoring
    events. A copy of ``chip_smoke._watch_compiles``, which is sound."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_requests = 0

        def on_event(event: str, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/compile_requests_use_cache":
                self.cache_requests += 1

        def on_duration(event: str, secs: float, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += secs

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def really_compiled(self) -> int:
        """Lookups the persistent cache missed: what XLA really compiled.
        (Every lookup, hit or miss, also reports a compile duration.)"""
        return max(self.cache_requests - self.cache_hits, 0)


class Tracer:
    """A ``jax.profiler`` trace of one stretch of the window, reduced
    after the window closes. With ``enabled`` false it does nothing."""

    def __init__(self, enabled: bool, trace_dir: str):
        self.enabled = enabled
        self.dir = trace_dir
        self.window_s = self.stop_s = 0.0
        self._t0 = 0.0
        self._on = False

    def start(self) -> None:
        if not self.enabled or self._on:
            return
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._on = True
        self._t0 = time.perf_counter()

    def stop(self) -> None:
        if not self._on:
            return
        import jax

        self.window_s = time.perf_counter() - self._t0
        jax.profiler.stop_trace()
        self._on = False
        self.stop_s = time.perf_counter() - self._t0 - self.window_s

    def record_later(self, from_s: float, seconds: float) -> "threading.Thread | None":
        """Record ``seconds`` of trace starting ``from_s`` seconds from now,
        on a thread of its own; the caller joins it before the window
        closes. None when not enabled."""
        if not self.enabled:
            return None

        def record():
            time.sleep(from_s)
            self.start()
            time.sleep(seconds)
            self.stop()

        thread = threading.Thread(target=record, name="bench.tracer", daemon=True)
        thread.start()
        return thread

    def reduce(self) -> "dict | None":
        if not self.enabled:
            return None
        from benchmarks.harness.trace_reduce import reduce_trace

        t0 = time.perf_counter()
        out = reduce_trace(self.dir)
        out["window_s"] = self.window_s
        print(
            f"trace: {self.window_s:.2f}s recorded, {self.stop_s:.2f}s to write it out,"
            f" {time.perf_counter() - t0:.2f}s to reduce it",
            flush=True,
        )
        shutil.rmtree(self.dir, ignore_errors=True)
        return out
