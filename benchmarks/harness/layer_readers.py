"""Per-layer metrics as declarations over what a run already collects.

A generator hands back ``probes``: one bundle per kind of source
(``stream_stats``, ``fit_duration``, ``serving_snapshot``, ``prof_phase``,
``prom_series``, ``harness_clock``, ``device_trace``). A metric's file
under ``layer_metrics/`` names a kind and how to read it. A reader that
finds nothing to read returns None, and the metric is left out of the
result line.
"""

from __future__ import annotations

import statistics


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of a non-empty sequence."""
    s = sorted(values)
    k = min(len(s) - 1, max(0, int(round(q / 100.0 * len(s) + 0.5)) - 1))
    return float(s[k])


def _stat(values, stat: str) -> "float | None":
    values = list(values)
    if not values:
        return None
    if stat == "median":
        return float(statistics.median(values))
    if stat == "mean":
        return float(sum(values) / len(values))
    if stat == "sum":
        return float(sum(values))
    if stat.startswith("p"):
        return percentile(values, float(stat[1:]))
    raise ValueError(f"unknown stat {stat!r}")


def _stream_stats(rounds: list, r: dict):
    """``rounds``: one dict of StreamStats fields per round."""
    if not rounds:
        return None
    if "over" in r:
        den = sum(s[r["over"]] for s in rounds)
        return sum(s[r["field"]] for s in rounds) / den if den else None
    return _stat((s[r["field"]] for s in rounds), r.get("stat", "median"))


def _fit_duration(by_model: dict, r: dict):
    return _stat(by_model.get(r["model"], ()), r.get("stat", "median"))


def _ratio(bundle: dict, r: dict):
    num, den = bundle.get(r["num"]), bundle.get(r["den"])
    if num is None or not den:
        return None
    return num / den


def _prof_phase(phases: dict, r: dict):
    ph = phases.get(r["phase"])
    if not ph or not ph["count"]:
        return None
    return ph["total_s"] / ph["count"]


def _prom_series(series: dict, r: dict):
    value = series.get(r["series"])
    if value is None:
        return None
    per = series.get(r["per"]) if "per" in r else 1.0
    return value / per if per else None


def _harness_clock(clock: dict, r: dict):
    return _stat(clock.get(r["field"], ()), r["stat"])


def _device_trace(trace: dict, r: dict):
    if not trace or not trace.get("window_s"):
        return None
    if r["stat"] == "idle_share":
        return 1.0 - trace["busy_s"] / trace["window_s"]
    return trace.get(r["stat"])


READERS = {
    "stream_stats": _stream_stats,
    "fit_duration": _fit_duration,
    "serving_snapshot": _ratio,
    "prof_phase": _prof_phase,
    "prom_series": _prom_series,
    "harness_clock": _harness_clock,
    "device_trace": _device_trace,
}


def read_metric(decl: dict, probes: dict) -> "float | None":
    r = decl["reader"]
    bundle = probes.get(r["kind"])
    if bundle is None:
        return None
    value = READERS[r["kind"]](bundle, r)
    if value is None:
        return None
    return float(value) * float(r.get("scale", 1.0))
