"""Reads of what the program already counts, taken from outside as
differences over a window: its Prometheus registry, its dfprof phases,
and return values of functions it resolves at call time.
"""

from __future__ import annotations

import contextlib


def prom_series() -> dict:
    """The process's metric registry as a scrape would read it."""
    from dragonfly2_tpu.utils.metrics import default_registry

    out = {}
    for line in default_registry.expose().splitlines():
        if line and not line.startswith("#"):
            name, _, value = line.rpartition(" ")
            try:
                out[name] = float(value)
            except ValueError:
                continue
    return out


def series_delta(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


def phase_counts(names: list) -> dict:
    from dragonfly2_tpu.utils import profiling

    out = {}
    for name in names:
        snap = profiling.phase_type(name).snapshot()
        out[name] = {"count": snap["count"], "total_s": snap["total_s"]}
    return out


def phase_delta(before: dict, after: dict) -> dict:
    return {
        k: {
            "count": after[k]["count"] - before[k]["count"],
            "total_s": after[k]["total_s"] - before[k]["total_s"],
        }
        for k in after
    }


@contextlib.contextmanager
def spy(module, name: str, sink: list, with_args: bool = False):
    """Record what ``module.name`` returns (with ``with_args``, its
    positional and keyword arguments and what it returned) while it stays in place for
    every caller that resolves it at call time (``list.append`` is
    atomic, so fits on three threads may share a sink)."""
    real = getattr(module, name)

    def spied(*args, **kwargs):
        out = real(*args, **kwargs)
        sink.append((args, kwargs, out) if with_args else out)
        return out

    setattr(module, name, spied)
    try:
        yield
    finally:
        setattr(module, name, real)
