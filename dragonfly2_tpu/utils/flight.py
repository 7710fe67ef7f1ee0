"""Black-box flight recorder: always-on bounded event rings + crash/stall
dumps.

Sampled tracing (utils/tracing) answers "how long did this request
take" — but when a peer wedges, a scheduler misplaces parents, or a TPU
fit stalls, the interesting window is almost never sampled and is gone
by the time anyone looks (Dean & Barroso, The Tail at Scale: tail
anomalies are exactly what sampling misses). This module is the
flight-recorder complement: every service keeps a fixed-size in-memory
ring of structured events per category — lock-cheap (a deque append
under the GIL, no mutex on the emit path), always on, bounded — and
dumps the rings as jsonl to ``DF_DIAG_DIR`` when something goes wrong:

- **SIGTERM / fatal exception** (``install``): the process explains
  what it was doing on the way down, without anyone having raised a
  sample rate first.
- **stall watchdog** (``StallWatchdog``): a step-time or decode-wait
  observation regressing past a configurable multiple of the trailing
  median triggers a dump (and, when wired, one forced ``jax.profiler``
  capture) while the stall is still live.
- **Diagnose RPC / GET /debug/ring**: live snapshots of the rings plus
  runtime state (thread stacks, registered probes) without restarting.

Events carry the current ``trace_id``/``span_id`` automatically (from
``tracing.current_span``), so ``tools/dfdoctor.py`` can merge dumps with
``DF_TRACE_DIR`` exports into one correlated timeline.

Typed emitters are declared once per module with ``event_type`` — the
name is ``<service>.<what>`` and ``hack/check_metrics.py`` lints the
registrations (duplicates, missing service prefix) like metric series.

Env: ``DF_DIAG_DIR`` (dump directory; no dumps when unset),
``DF_FLIGHT`` (``0`` disables event recording entirely),
``DF_FLIGHT_RING`` (events kept per category, default 512),
``DF_STALL_FACTOR`` (watchdog regression multiple, default 4.0;
``0`` disables the watchdogs).
"""

# dfanalyze: hot — the ~1µs emit rides every lifecycle event

from __future__ import annotations

import collections
import json
import os
import signal
import statistics
import sys
import threading
import time
import traceback

from dragonfly2_tpu.utils import tracing
from dragonfly2_tpu.utils.metrics import default_registry as _r

RING_DEPTH_GAUGE = _r.gauge(
    "flight_ring_depth", "Events resident in a flight-recorder ring", ("category",)
)
DROPPED_TOTAL = _r.counter(
    "flight_events_dropped_total",
    "Events evicted from a full flight-recorder ring",
    ("category",),
)
DUMPS_TOTAL = _r.counter(
    "flight_dumps_total", "Flight-recorder dumps written", ("reason",)
)

_DEFAULT_RING = 512

# dump augments: zero-arg callables whose dict result is merged into
# every dump's meta line (utils/profiling attaches the last-N-seconds
# sample window here, so a stall dump names its hot frames). Module
# level, not per-recorder: the profile window belongs to the PROCESS,
# and test recorders must dump it the same way the real one does.
_dump_augments: list = []


def register_dump_augment(fn) -> None:
    """Attach extra state to every future dump's meta line. ``fn`` is a
    zero-arg callable returning a dict (merged into meta) — failures
    are swallowed at dump time, never fatal mid-crash."""
    if fn not in _dump_augments:
        _dump_augments.append(fn)


def _env_ring_size() -> int:
    try:
        return max(16, int(os.environ.get("DF_FLIGHT_RING", _DEFAULT_RING)))
    except ValueError:
        return _DEFAULT_RING


# module-level flag, read on every emit: a plain global read is the
# cheapest gate Python offers, and the bench's recorder_overhead_pct
# holds the whole emit path (this branch included) under 2% of the
# scheduling op
_enabled = os.environ.get("DF_FLIGHT", "1").lower() not in ("0", "false", "no")


# pre-bound for the emit fast path (module-global lookup beats
# attribute-chained lookups per event); binding the contextvar's own
# get skips a Python-level call frame per emit vs tracing.current_span
_current_span = tracing._current.get
_time_ns = time.time_ns


def enabled() -> bool:
    return _enabled


def dump_armed() -> bool:
    """True when a flight dump could actually land somewhere —
    ``DF_DIAG_DIR`` is set (``dump`` is a no-op without it). Hot paths
    use this to skip building payloads that exist only to be dumped:
    one getenv, no allocation."""
    return bool(os.environ.get("DF_DIAG_DIR"))


def set_enabled(on: bool) -> None:
    global _enabled
    _enabled = bool(on)


class EventType:
    """A typed emitter: ``EV = flight.event_type("scheduler.schedule")``
    once at module level, then ``EV(peer_id=..., retries=...)`` on the
    hot path. The category (ring) is the name's service segment, so one
    service's chatter can never evict another's history."""

    __slots__ = ("name", "category", "_ring", "_recorder", "_maxlen", "_dropbox")

    def __init__(self, name: str, recorder: "FlightRecorder"):
        self.name = name
        self.category = name.split(".", 1)[0]
        self._recorder = recorder
        self._ring = recorder._ring_for(self.category)
        self._maxlen = self._ring.maxlen
        self._dropbox = recorder._dropboxes[self.category]

    def __call__(self, **fields) -> None:
        # every line here is hot-path budget (one call per scheduling
        # decision, always on): the ring holds a plain tuple around the
        # kwargs dict Python already built — the event dict shape is
        # assembled lazily at snapshot/dump time, where cost is free
        if not _enabled:
            return
        span = _current_span()
        if span is not None and span.sampled:
            tid, sid = span.trace_id, span.span_id
        else:
            tid = sid = ""
        ring = self._ring
        if len(ring) == self._maxlen:
            # plain int add into a shared per-category box (GIL-atomic
            # enough for a diagnostic count); the Prometheus counter is
            # synced lazily at snapshot time so the emit path never
            # takes a metric lock
            self._dropbox[0] += 1
        ring.append((_time_ns(), self.name, tid, sid, fields))


class FlightRecorder:
    def __init__(self, ring_size: int | None = None):
        self.ring_size = ring_size or _env_ring_size()
        self._rings: dict[str, collections.deque] = {}
        # one mutable [count] box per category, shared with that
        # category's EventTypes — the emit path increments box[0]
        # without dict lookups or locks
        self._dropboxes: dict[str, list[int]] = {}
        self._dropped_synced: dict[str, int] = {}
        self._create_lock = threading.Lock()  # ring/probe creation only
        self._probes: dict[str, object] = {}
        self.service = ""
        self.dumps = 0
        self._installed = False
        self._prev_excepthook = None

    # -- declaration ---------------------------------------------------
    def event_type(self, name: str) -> EventType:
        return EventType(name, self)

    def _ring_for(self, category: str) -> collections.deque:
        ring = self._rings.get(category)
        if ring is None:
            with self._create_lock:
                # dropbox BEFORE ring: the unlocked fast path above keys
                # on the ring's existence, so everything it implies must
                # already be in place when the ring becomes visible
                self._dropboxes.setdefault(category, [0])
                self._dropped_synced.setdefault(category, 0)
                ring = self._rings.setdefault(
                    category, collections.deque(maxlen=self.ring_size)
                )
        return ring

    def register_probe(self, name: str, fn) -> None:
        """A zero-arg callable whose result rides every dump/Diagnose
        snapshot as runtime state — queue depths, topology engine stats,
        resource counts. Failures are captured, never raised."""
        with self._create_lock:
            self._probes[name] = fn

    # -- reads ---------------------------------------------------------
    def snapshot(self, categories: "list[str] | None" = None) -> dict:
        """{category: [event, ...]} — a point-in-time copy of the rings,
        each event expanded from its ring tuple into the dump/RPC dict
        shape. Also refreshes the recorder's Prometheus gauges (ring
        depth, dropped), so every scrape of /debug/ring keeps them
        current."""
        out: dict[str, list] = {}
        for cat, ring in list(self._rings.items()):
            if categories is not None and cat not in categories:
                continue
            out[cat] = [
                {"ts_ns": ts, "type": name, "trace_id": tid, "span_id": sid, **f}
                for ts, name, tid, sid, f in self._copy_ring(ring)
            ]
            RING_DEPTH_GAUGE.labels(cat).set(len(out[cat]))
            dropped = self.dropped(cat)
            delta = dropped - self._dropped_synced.get(cat, 0)
            if delta > 0:
                DROPPED_TOTAL.labels(cat).inc(delta)
                self._dropped_synced[cat] = dropped
        return out

    @staticmethod
    def _copy_ring(ring: collections.deque) -> list:
        # list(deque) can raise if a writer appends mid-iteration; the
        # emit path must never block on a reader lock, so retry instead
        for _ in range(4):
            try:
                return list(ring)
            except RuntimeError:
                continue
        return []

    def categories(self) -> list[str]:
        return sorted(self._rings)

    def dropped(self, category: str) -> int:
        box = self._dropboxes.get(category)
        return box[0] if box else 0

    def runtime_state(self, include_stacks: bool = True) -> dict:
        """Live process state for Diagnose/dumps: thread inventory (and
        stacks), per-category drop counts, registered probe results."""
        state: dict = {
            "pid": os.getpid(),
            "thread_count": threading.active_count(),
            "dropped": {c: box[0] for c, box in self._dropboxes.items()},
        }
        if include_stacks:
            frames = sys._current_frames()
            stacks = {}
            for t in threading.enumerate():
                fr = frames.get(t.ident)
                if fr is not None:
                    stacks[t.name] = "".join(traceback.format_stack(fr))
            state["thread_stacks"] = stacks
        probes = {}
        for name, fn in list(self._probes.items()):
            try:
                probes[name] = fn()
            except Exception as e:
                probes[name] = {"error": str(e)}
        if probes:
            state["probes"] = probes
        return state

    # -- dumps ---------------------------------------------------------
    def dump(self, reason: str, diag_dir: "str | None" = None) -> "str | None":
        """Write every ring as jsonl under ``DF_DIAG_DIR`` (first line:
        dump metadata + runtime state; one event per following line).
        Returns the path, or None when no diag dir is configured — a
        service without DF_DIAG_DIR must shut down exactly as before."""
        diag_dir = diag_dir or os.environ.get("DF_DIAG_DIR") or ""
        if not diag_dir:
            return None
        try:
            os.makedirs(diag_dir, exist_ok=True)
            slug = "".join(c if c.isalnum() or c in "._-" else "-" for c in reason)
            path = os.path.join(
                diag_dir,
                f"{self.service or 'proc'}-{os.getpid()}-{time.time_ns()}-{slug}.jsonl",
            )
            snap = self.snapshot()
            meta = {
                "reason": reason,
                "service": self.service,
                "pid": os.getpid(),
                "dumped_at_ns": time.time_ns(),
                "ring_size": self.ring_size,
                "events": {c: len(e) for c, e in snap.items()},
                "runtime": self.runtime_state(),
            }
            for fn in list(_dump_augments):
                try:
                    meta.update(fn() or {})
                except Exception:
                    # augments are best-effort evidence; a broken one
                    # must not cost the dump itself
                    continue
            with open(path, "w") as f:
                f.write(json.dumps({"meta": meta}, default=str) + "\n")
                for cat, events in snap.items():
                    for ev in events:
                        f.write(json.dumps({"category": cat, **ev}, default=str) + "\n")
            self.dumps += 1
            DUMPS_TOTAL.labels(reason.split(":", 1)[0].split("-", 1)[0]).inc()
            return path
        except Exception:
            # a failing dump must never turn a clean shutdown into a
            # crash (or a crash into a hang)
            return None

    # -- crash hooks ---------------------------------------------------
    def install(self, service: str) -> None:
        """Wire the crash dumps for this process: SIGTERM and uncaught
        fatal exceptions each write a dump before the previous behavior
        runs. Idempotent; a process hosting several services (tests,
        all-in-one deploys) records every name."""
        if service:
            if not self.service:
                self.service = service
            elif service not in self.service.split("+"):
                self.service += f"+{service}"
        if self._installed:
            return
        self._installed = True
        try:
            prev = signal.getsignal(signal.SIGTERM)

            def _on_term(signum, frame):
                try:
                    self.dump("sigterm")
                finally:
                    if callable(prev) and prev not in (signal.SIG_IGN, signal.SIG_DFL):
                        prev(signum, frame)
                    elif prev is signal.SIG_IGN:
                        pass  # SIGTERM was ignored before; keep ignoring
                    else:
                        # restore default and re-raise so the process
                        # still dies with the SIGTERM disposition
                        signal.signal(signal.SIGTERM, signal.SIG_DFL)
                        os.kill(os.getpid(), signal.SIGTERM)

            signal.signal(signal.SIGTERM, _on_term)
        except ValueError:
            pass  # not the main thread: signal hooks unavailable here
        self._prev_excepthook = sys.excepthook

        def _hook(exc_type, exc, tb):
            try:
                self.dump(f"fatal:{exc_type.__name__}")
            finally:
                (self._prev_excepthook or sys.__excepthook__)(exc_type, exc, tb)

        sys.excepthook = _hook
        # sys.excepthook never fires for non-main threads — and the
        # interesting crashes (conductor stream loops, scheduler pumps,
        # GC tasks) die THERE. threading.excepthook is their hook.
        prev_thread_hook = threading.excepthook

        def _thread_hook(args):
            try:
                name = args.exc_type.__name__ if args.exc_type else "Unknown"
                self.dump(f"fatal:{name}")
            finally:
                prev_thread_hook(args)

        threading.excepthook = _thread_hook


class StallWatchdog:
    """Regression detector over a stream of duration observations
    (step time per superbatch, decode wait per shard): an observation
    past ``factor ×`` the trailing median — and past an absolute floor,
    so microsecond jitter can't trip it — dumps the flight rings while
    the stall is still live and fires ``on_stall`` once (cooldown-
    limited). The trailing window is a deque; ``observe`` is called per
    superbatch/shard, never on a microsecond hot path."""

    def __init__(
        self,
        name: str,
        factor: "float | None" = None,
        window: int = 64,
        min_samples: int = 8,
        floor_s: float = 0.1,
        cooldown_s: float = 60.0,
        on_stall=None,
        event: "EventType | None" = None,
        recorder: "FlightRecorder | None" = None,
    ):
        if factor is None:
            try:
                factor = float(os.environ.get("DF_STALL_FACTOR", "4.0"))
            except ValueError:
                factor = 4.0
        self.name = name
        self.factor = factor
        self.min_samples = min_samples
        self.floor_s = floor_s
        self.cooldown_s = cooldown_s
        self.on_stall = on_stall
        self.event = event
        self.recorder = recorder or _recorder
        self.stalls = 0
        self._samples: collections.deque = collections.deque(maxlen=window)
        self._last_trigger = 0.0

    def observe(self, seconds: float) -> bool:
        """Feed one observation; True when it was judged a stall."""
        if self.factor <= 0:
            return False
        stalled = False
        if len(self._samples) >= self.min_samples:
            med = statistics.median(self._samples)
            if seconds > max(self.factor * med, self.floor_s):
                now = time.monotonic()
                if now - self._last_trigger >= self.cooldown_s:
                    self._last_trigger = now
                    self.stalls += 1
                    stalled = True
                    if self.event is not None:
                        self.event(
                            watchdog=self.name,
                            observed_s=round(seconds, 6),
                            median_s=round(med, 6),
                            factor=self.factor,
                        )
                    self.recorder.dump(f"stall-{self.name}")
                    if self.on_stall is not None:
                        try:
                            self.on_stall()
                        except Exception:
                            pass  # diagnostics must not break the pipeline
        self._samples.append(seconds)
        return stalled


_profile_fired = False


def one_shot_profile(profile_dir: str, duration_s: float = 5.0) -> bool:
    """One forced ``jax.profiler`` capture into ``profile_dir`` —
    the stall watchdog's XLA-side evidence, riding the same profile_dir
    plumbing TrainingConfig exposes. At most once per process (a stall
    storm must not leave the profiler permanently on), stopped by a
    timer thread after ``duration_s``. Returns True when a capture
    started; never raises (an already-active trace is fine — that
    capture covers the stall)."""
    global _profile_fired
    if not profile_dir or _profile_fired:
        return False
    _profile_fired = True
    try:
        import jax.profiler

        jax.profiler.start_trace(os.path.join(profile_dir, "stall"))
    except Exception:
        return False

    def _stop():
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass

    threading.Timer(duration_s, _stop).start()
    return True


# ---------------------------------------------------------------------------
# process-wide recorder + module-level convenience API
# ---------------------------------------------------------------------------

_recorder = FlightRecorder()


def recorder() -> FlightRecorder:
    return _recorder


def event_type(name: str) -> EventType:
    """Declare a typed emitter on the process-wide recorder. Call once
    at module level; the name must be ``<service>.<what>`` (linted by
    hack/check_metrics.py)."""
    return _recorder.event_type(name)


def install(service: str) -> None:
    _recorder.install(service)


def register_probe(name: str, fn) -> None:
    _recorder.register_probe(name, fn)


def dump(reason: str, diag_dir: "str | None" = None) -> "str | None":
    return _recorder.dump(reason, diag_dir=diag_dir)


def snapshot(categories: "list[str] | None" = None) -> dict:
    return _recorder.snapshot(categories)
