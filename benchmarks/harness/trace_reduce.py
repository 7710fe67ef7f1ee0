"""From a ``jax.profiler`` trace to the device's busy time, its heaviest
operations and its longest idle gaps.

Busy is the union of the intervals in which an operation ran on a
device (the plane's "XLA Ops" line; every line of the plane where there
is none), averaged over the device planes. A gap is named by the host
event that overlaps it most, so that the breakdown says what the host
was doing while the device waited.
"""

from __future__ import annotations

import glob
import os
from typing import NamedTuple

OPS_LINE = "XLA Ops"
MAX_NAMED_GAPS = 300
MAX_HOST_EVENTS = 20_000


def short_name(name: str) -> str:
    """An XLA op event is named by its whole HLO line; keep the result
    name and the opcode (``%while.209 while``, ``%fusion.223 fusion``)."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:96]
    if rest.startswith("("):  # a tuple type: skip to its closing bracket
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1 :]
                break
    else:
        rest = rest.partition(" ")[2]
    return f"{head} {rest.split('(', 1)[0].strip()}"[:96]


class Event(NamedTuple):
    name: str
    start_ns: float
    end_ns: float


def union_length(intervals: list) -> float:
    """Total length covered by ``[(start, end), ...]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: list, lo: float, hi: float) -> list:
    """Uncovered stretches of ``[lo, hi]``, longest first."""
    out, edge = [], lo
    for s, e in sorted(intervals):
        if s > edge:
            out.append((edge, min(s, hi)))
        edge = max(edge, e)
        if edge >= hi:
            break
    if edge < hi:
        out.append((edge, hi))
    return sorted((g for g in out if g[1] > g[0]), key=lambda g: g[0] - g[1])


def name_gap(gap: tuple, host_events: list) -> str:
    """The host event overlapping ``gap`` most (the shorter on a tie)."""
    best, best_key = "host idle", (0.0, 0.0)
    for ev in host_events:
        overlap = min(ev.end_ns, gap[1]) - max(ev.start_ns, gap[0])
        if overlap <= 0:
            continue
        key = (overlap, -(ev.end_ns - ev.start_ns))
        if key > best_key:
            best, best_key = ev.name, key
    return best


def reduce_planes(device_planes: list, host_events: list, top: int = 10) -> dict:
    """``device_planes`` is one list of op Events per device. Returns
    ``busy_s`` (mean over devices), ``window_s`` (first op start to last
    op end over all devices), ``device_ops`` and ``idle_gaps``."""
    all_ops = [ev for plane in device_planes for ev in plane]
    if not all_ops:
        return {"busy_s": 0.0, "window_s": 0.0, "device_ops": [], "idle_gaps": []}
    lo = min(ev.start_ns for ev in all_ops)
    hi = max(ev.end_ns for ev in all_ops)
    busy = [
        union_length([(ev.start_ns, ev.end_ns) for ev in plane]) for plane in device_planes
    ]
    by_name: dict[str, float] = {}
    for ev in all_ops:
        by_name[ev.name] = by_name.get(ev.name, 0.0) + (ev.end_ns - ev.start_ns)
    n_dev = len(device_planes)
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    # gaps of the fullest device: where that one idles, all do or wait
    fullest = max(range(n_dev), key=lambda i: busy[i])
    named: dict[str, float] = {}
    all_gaps = gaps([(ev.start_ns, ev.end_ns) for ev in device_planes[fullest]], lo, hi)
    # name the longest gaps by the longest host events; the many short
    # ones are one entry (naming each is quadratic and says little)
    hosts = sorted(host_events, key=lambda ev: ev.start_ns - ev.end_ns)[:MAX_HOST_EVENTS]
    for g in all_gaps[:MAX_NAMED_GAPS]:
        label = name_gap(g, hosts)
        named[label] = named.get(label, 0.0) + (g[1] - g[0])
    rest = sum(g[1] - g[0] for g in all_gaps[MAX_NAMED_GAPS:])
    if rest:
        named["gaps beyond the longest %d" % MAX_NAMED_GAPS] = rest
    return {
        "busy_s": sum(busy) / n_dev / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[k, v / n_dev / 1e9] for k, v in ops],
        "idle_gaps": [[k, v / 1e9] for k, v in sorted(named.items(), key=lambda kv: -kv[1])[:top]],
    }


def outermost(events, names: dict) -> list:
    """The ops of one trace line that reach past everything before them.
    A scan's body ops lie inside their ``while`` op on the same line,
    millions of them in a fit's trace: dropping them leaves the busy time
    as it was and names the loop, not its body. ``names`` caches an op's
    whole HLO line -> its short name."""
    ops, reached = [], -1.0
    for ev in events:
        end = ev.start_ns + ev.duration_ns
        if end <= reached:
            continue
        reached = end
        raw = ev.name
        name = names.get(raw)
        if name is None:
            name = names[raw] = short_name(raw)
        ops.append(Event(name, end - ev.duration_ns, end))
    return ops


def load_xplane(trace_dir: str) -> "tuple[list, list]":
    """(device planes as lists of op Events, host Events) from the
    newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime,
    )
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    device_planes, host_events = [], []
    names: dict[str, str] = {}  # an op's whole HLO line -> its short name
    for plane in data.planes:
        lines = list(plane.lines)
        if plane.name.startswith("/device:") and "TPU" in plane.name.upper():
            chosen = [ln for ln in lines if ln.name == OPS_LINE] or lines
            ops = [ev for ln in chosen for ev in outermost(ln.events, names)]
            device_planes.append(ops)
        elif plane.name.startswith("/host:"):
            for ln in lines:
                for ev in ln.events:
                    if ev.duration_ns > 0:
                        host_events.append(
                            Event(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        )
    return [p for p in device_planes if p], host_events


def reduce_trace(trace_dir: str) -> dict:
    device_planes, host_events = load_xplane(trace_dir)
    return reduce_planes(device_planes, host_events)
