"""dfprof: the always-on continuous profiling plane.

Two instruments, both cheap enough to leave on in production:

- **Sampling profiler**: a daemon thread walks ``sys._current_frames()``
  at ``DF_PROF_HZ`` (default 20 Hz) and folds each thread's stack —
  package frames only, interned sites — into a bounded per-thread-role
  trie plus a bounded recent-sample ring. The trie answers "where has
  this process spent its life"; the ring answers "what was hot in the
  last N seconds" (the window flight-recorder dumps attach, so a wedged
  fit names its hot frames in the postmortem). Node growth past
  ``DF_PROF_NODES`` drop-counts instead of allocating, like a full
  flight ring.

- **Phase ledger**: named wall-clock phases declared once per module
  (``PH = profiling.phase_type("trainer.buffer_wait")``) and accounted
  continuously — ``with PH: ...`` for timed blocks, ``PH.observe(dt)``
  where the caller already measured. A ``with`` block is also a
  ``jax.profiler.TraceAnnotation`` of the phase's name while a
  profiler session is open (in a process that has loaded jax; never
  loaded for this), so any ``jax.profiler`` trace shows the program's
  phases on the host plane beside the device's ops; and it is
  credited to the thread's own
  :func:`split`, when one is open, so a caller can read what ITS work
  took apart from the process-wide totals (a phase declared ``inner``,
  one entered inside another on the same thread, is the ledger's and
  the trace's alone: a split counts no second twice). The interpreter's
  full collections are a phase too, ``process.gc_full``
  (:func:`watch_collections`). The ledger generalizes the
  trainer's per-fit StreamStats split into live, cross-service
  counters: the same buffer_wait/decode_wait/h2d/step attribution,
  scrapeable mid-fit via ``/metrics`` (``prof_phase_seconds``) and
  ``GET /debug/prof``, next to the scheduler's evaluate/topology/store
  legs and the daemon's parent-wait/read/write piece path.

Exposure: ``GET /debug/prof?seconds=N`` on every MetricsServer
(collapsed flamegraph text + the ledger as JSON), the ``Diagnose`` RPC
(``profile`` section), flight-recorder dumps (``meta.profile`` window),
telemetry pushes (top-K hot stacks + phase shares to the manager), and
``tools/dfprof.py`` (top-N self-time, ``--diff``, ``--rpc`` live
capture).

Thread-role attribution folds numbered siblings together: a thread
named ``trainer.ingest-decode-3`` profiles under the role
``trainer.ingest-decode``. Long-lived threads are therefore named
``<service>.<role>`` at creation (linted convention, like flight event
types).

Env: ``DF_PROF`` (``0`` disables the sampler entirely), ``DF_PROF_HZ``
(sample rate, default 20), ``DF_PROF_NODES`` (trie node budget,
default 8192), ``DF_PROF_RING`` (recent-sample entries, default
16384), ``DF_PROF_DEPTH`` (max frames kept per stack, default 64),
``DF_PROF_DUMP_WINDOW`` (seconds of samples attached to flight dumps,
default 30).
"""

# dfanalyze: hot — Phase.observe rides every schedule op / superbatch,
# and the sampler sweep runs DF_PROF_HZ times a second forever

from __future__ import annotations

import bisect
import collections
import contextlib
import gc
import os
import sys
import threading
import time

from dragonfly2_tpu.utils import dflog, flight
from dragonfly2_tpu.utils.metrics import default_registry as _r

logger = dflog.get("profiling")

PROF_SAMPLES_TOTAL = _r.counter(
    "prof_samples_total", "Sampler sweeps over sys._current_frames()"
)
PROF_STACKS_DROPPED_TOTAL = _r.counter(
    "prof_stacks_dropped_total",
    "Samples truncated because the stack trie hit its node budget",
)
PROF_TRIE_NODES = _r.gauge(
    "prof_trie_nodes", "Nodes resident in the sampler's stack tries"
)
PROF_SAMPLE_SECONDS = _r.histogram(
    "prof_sample_seconds",
    "Wall cost of one sampler sweep",
    buckets=(1e-5, 5e-5, 1e-4, 5e-4, 1e-3, 5e-3, 0.05, float("inf")),
)
# phase-ledger exposure: synced lazily from the ledger at snapshot time
# (every /debug/prof, Diagnose, dump, telemetry push) so the per-phase
# hot path never takes a metric lock — the flight-ring gauge pattern
PROF_PHASE_SECONDS_TOTAL = _r.counter(
    "prof_phase_seconds_total",
    "Cumulative wall seconds accounted per phase-ledger phase",
    ("phase",),
)
PROF_PHASE_TOTAL = _r.counter(
    "prof_phase_total", "Phase-ledger entries completed", ("phase",)
)
PROF_PHASE_ACTIVE = _r.gauge(
    "prof_phase_active", "Phase-ledger entries currently open", ("phase",)
)

# the prof.* flight namespace is reserved for this module (dfanalyze
# metrics pass): sampler lifecycle markers in the shared rings
EV_OVERFLOW = flight.event_type("prof.trie_overflow")
EV_WINDOW = flight.event_type("prof.window_attached")

_DEFAULT_HZ = 20.0
_DEFAULT_NODES = 8192
_DEFAULT_RING = 16384
_DEFAULT_DEPTH = 64
_DEFAULT_DUMP_WINDOW_S = 30.0
_ROLE_CACHE_MAX = 4096

# .../dragonfly2_tpu — frames outside the package are folded away so
# stacks stay role-shaped ("ingest._dispatch_loop") instead of
# interpreter-shaped ("threading.run;...")
_PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PKG_PREFIX = _PKG_DIR + os.sep


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


def _env_int(name: str, default: int) -> int:
    try:
        return max(64, int(os.environ.get(name, default)))
    except ValueError:
        return default


def default_hz() -> float:
    return _env_float("DF_PROF_HZ", _DEFAULT_HZ)


def enabled() -> bool:
    return os.environ.get("DF_PROF", "1").lower() not in ("0", "false", "no")


_HEX_CHARS = frozenset("0123456789abcdef")


def _is_id_segment(seg: str) -> bool:
    # worker indexes ("3"), or peer-id fragments — which are hex, so a
    # digit-free slice like "deadbeef" must fold too or every peer
    # mints its own role (and trie root)
    return any(c.isdigit() for c in seg) or (
        len(seg) >= 6 and set(seg) <= _HEX_CHARS
    )


def thread_role(name: str) -> str:
    """Fold numbered/id-suffixed thread names into one role: trailing
    ``-`` segments that are worker indexes or peer-id fragments
    (``trainer.ingest-decode-3``, ``daemon.announce-1a2b…``) are not
    distinct roles."""
    parts = name.split("-")
    while len(parts) > 1 and _is_id_segment(parts[-1]):
        parts.pop()
    return "-".join(parts)


class _Node:
    __slots__ = ("children", "self_n")

    def __init__(self):
        self.children: dict = {}
        self.self_n = 0


class SamplingProfiler:
    """The sampling half. One process-wide instance lives behind the
    module API (``install``/``start``/``stop``); benches and tests may
    build private instances and drive ``sample_once`` directly."""

    def __init__(
        self,
        hz: "float | None" = None,
        max_nodes: "int | None" = None,
        ring: "int | None" = None,
        max_depth: "int | None" = None,
    ):
        self.hz = hz if hz is not None else default_hz()
        self.max_nodes = max_nodes or _env_int("DF_PROF_NODES", _DEFAULT_NODES)
        self.max_depth = max_depth or _env_int("DF_PROF_DEPTH", _DEFAULT_DEPTH)
        self.service = ""
        self.samples = 0  # sweeps taken
        self.dropped = 0  # stacks truncated by the node budget
        self.sweep_errors = 0  # failed sweeps (first one logged)
        self.sample_s = 0.0  # cumulative sweep cost
        self._tries: dict[str, _Node] = {}  # role -> root
        self._node_count = 0
        self._overflowed = False
        self._ring: collections.deque = collections.deque(
            maxlen=ring or _env_int("DF_PROF_RING", _DEFAULT_RING)
        )
        self._site_cache: dict = {}  # code object -> interned site string
        self._role_cache: dict[str, str] = {}
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: "threading.Thread | None" = None

    # -- lifecycle -----------------------------------------------------
    def running(self) -> bool:
        t = self._thread
        return t is not None and t.is_alive()

    def start(self) -> bool:
        if self.hz <= 0 or self.running():
            return False
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, name="prof.sampler", daemon=True
        )
        self._thread.start()
        return True

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=2.0)
        self._thread = None

    def _loop(self) -> None:
        period = 1.0 / self.hz
        while not self._stop.wait(period):
            try:
                self.sample_once()
            except Exception:
                # a failed sweep must never kill the sampler; the next
                # tick retries — first failure logged, rest counted
                self.sweep_errors += 1
                if self.sweep_errors == 1:
                    logger.warning("dfprof sweep failed", exc_info=True)

    # -- sampling ------------------------------------------------------
    def _site(self, code) -> "str | None":
        site = self._site_cache.get(code)
        if site is None:
            fname = code.co_filename
            if not fname.startswith(_PKG_PREFIX):
                self._site_cache[code] = ""
                return None
            rel = fname[len(_PKG_PREFIX):]
            if rel.endswith(".py"):
                rel = rel[:-3]
            site = sys.intern(
                f"{rel.replace(os.sep, '.')}.{code.co_name}".replace(";", ":")
            )
            self._site_cache[code] = site
        return site or None

    def sample_once(self) -> int:
        """One sweep: every thread's current stack folded into its
        role's trie and appended to the recent ring. Returns the number
        of stacks recorded."""
        t0 = time.perf_counter()
        # thread-name map refreshed per sweep, outside our lock (the
        # interpreter's own bookkeeping lock must not nest inside it)
        names = {t.ident: t.name for t in threading.enumerate()}
        frames = sys._current_frames()
        own = threading.get_ident()
        now_ns = time.time_ns()
        recorded = 0
        with self._lock:
            for ident, frame in frames.items():
                if ident == own:
                    continue  # the sampler never profiles itself
                name = names.get(ident) or "tid"
                role = self._role_cache.get(name)
                if role is None:
                    if len(self._role_cache) >= _ROLE_CACHE_MAX:
                        # per-task threads carry fresh ids in their
                        # names; an always-on daemon must not grow the
                        # cache forever (cleared wholesale, rebuilt from
                        # the handful of live threads next sweep)
                        self._role_cache.clear()
                    role = self._role_cache.setdefault(name, thread_role(name))
                stack = []
                f = frame
                while f is not None and len(stack) < self.max_depth:
                    site = self._site(f.f_code)
                    if site is not None:
                        stack.append(site)
                    f = f.f_back
                if not stack:
                    continue  # fully outside the package (idle interpreter)
                stack.reverse()  # root-first, flamegraph order
                tup = tuple(stack)
                self._fold(role, tup)
                self._ring.append((now_ns, role, tup))
                recorded += 1
            self.samples += 1
        dt = time.perf_counter() - t0
        self.sample_s += dt
        PROF_SAMPLES_TOTAL.inc()
        PROF_SAMPLE_SECONDS.observe(dt)
        return recorded

    def _fold(self, role: str, stack: tuple) -> None:
        node = self._tries.get(role)
        if node is None:
            if self._node_count >= self.max_nodes:
                # even the role root is over budget: the sample is
                # wholly dropped (counted), like a full flight ring
                self._drop_one()
                return
            node = self._tries.setdefault(role, _Node())
            self._node_count += 1
        truncated = False
        for site in stack:
            child = node.children.get(site)
            if child is None:
                if self._node_count >= self.max_nodes:
                    truncated = True
                    break
                child = _Node()
                node.children[site] = child
                self._node_count += 1
            node = child
        node.self_n += 1
        if truncated:
            self._drop_one()

    def _drop_one(self) -> None:
        self.dropped += 1
        PROF_STACKS_DROPPED_TOTAL.inc()
        if not self._overflowed:
            # one transition marker, not one event per truncated
            # sample — an overflow storm must not spam the rings
            self._overflowed = True
            EV_OVERFLOW(nodes=self._node_count, budget=self.max_nodes)

    # -- reads ---------------------------------------------------------
    def folded(self, seconds: "float | None" = None) -> dict:
        """{(role, stack_tuple): count}. With ``seconds``, folds the
        recent-sample ring's last-N-seconds window; otherwise the
        all-time tries."""
        out: dict = {}
        if seconds is not None:
            cutoff = time.time_ns() - int(seconds * 1e9)
            with self._lock:
                entries = list(self._ring)
            for ts, role, tup in entries:
                if ts >= cutoff:
                    key = (role, tup)
                    out[key] = out.get(key, 0) + 1
            return out
        with self._lock:
            roots = list(self._tries.items())
            # DFS copies under the lock: the trie mutates per sweep and
            # a torn walk could double-count a just-split node
            for role, root in roots:
                stack: list = [(root, ())]
                while stack:
                    node, path = stack.pop()
                    if node.self_n:
                        out[(role, path)] = node.self_n
                    for site, child in node.children.items():
                        stack.append((child, path + (site,)))
        return out

    def collapsed(self, seconds: "float | None" = None) -> str:
        """Flamegraph-compatible collapsed-stack text:
        ``role;frame;frame count`` per line, sorted for determinism."""
        lines = [
            ";".join((role,) + tup) + f" {n}"
            for (role, tup), n in self.folded(seconds).items()
        ]
        return "\n".join(sorted(lines))

    def stats(self) -> dict:
        with self._lock:
            nodes = self._node_count
            roles = sorted(self._tries)
        PROF_TRIE_NODES.set(nodes)
        return {
            "service": self.service,
            "running": self.running(),
            "hz": self.hz,
            "samples": self.samples,
            "dropped": self.dropped,
            "sample_s": round(self.sample_s, 6),
            "trie_nodes": nodes,
            "roles": roles,
        }


# ---------------------------------------------------------------------------
# phase ledger
# ---------------------------------------------------------------------------

_PHASE_BUCKETS = (1e-5, 1e-4, 1e-3, 0.01, 0.1, 0.5, 1.0, 5.0, 30.0)

# jax.profiler.TraceAnnotation, bound the first time a phase is entered
# in a process that has loaded jax
_annotation = None
_modules = sys.modules


def _bind_annotation():
    """The profiler's annotation class if this process has imported
    jax, looked up in ``sys.modules`` and never imported from here: the
    dfdaemon runs without jax and a phase must not load it. A jax that
    is still mid-import has no ``profiler`` yet; the next entry asks
    again."""
    global _annotation
    profiler = getattr(_modules.get("jax"), "profiler", None)
    _annotation = getattr(profiler, "TraceAnnotation", None)
    return _annotation


class _ThreadState(threading.local):
    split = None  # the dict this thread's phases are credited to, if open


_thread = _ThreadState()


@contextlib.contextmanager
def split():
    """``with profiling.split() as mine:`` — ``mine`` fills with
    ``{phase: [entries, seconds]}`` for every phase THIS thread leaves
    (the ``with`` form) or books (``Phase.book``) inside the block. The
    ledger sums a process; two fits of the same model running at once
    share its totals, and each still reads its own work here. A phase
    declared ``inner`` is credited to none: the phase it is entered
    inside holds its seconds."""
    prev, mine = _thread.split, {}
    _thread.split = mine
    try:
        yield mine
    finally:
        _thread.split = prev


class Phase:
    """One named wall-clock phase. Declared once per module via
    :func:`phase_type`; usable as a (re-entrant, thread-safe) context
    manager or fed pre-measured durations with ``observe`` (ledger
    only) or ``book`` (ledger and the calling thread's open split). The
    ``with`` form books, or observes where the phase is declared
    ``inner``: one entered inside another ``with`` phase on the same
    thread, whose seconds that one holds already. ``last_s`` is what the
    ``with`` block the calling thread left last took.

    The hot path is ledger-only — one bisect + one short lock per
    ``observe``, plain GIL int adds for the active counter (the flight
    dropbox discipline: diagnostic-grade, never a metric lock). The
    Prometheus twins (``prof_phase_seconds_total`` /
    ``prof_phase_total`` / ``prof_phase_active``) are synced lazily by
    :func:`ledger_snapshot`, which every scrape surface calls."""

    __slots__ = (
        "name", "count", "total_s", "max_s", "bucket_counts", "active_n",
        "inner", "_lock", "_tls", "_synced_count", "_synced_total_s",
    )

    def __init__(self, name: str, inner: bool = False):
        self.name = name
        self.inner = inner
        self.count = 0
        self.total_s = 0.0
        self.max_s = 0.0
        self.bucket_counts = [0] * (len(_PHASE_BUCKETS) + 1)
        self.active_n = 0
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._synced_count = 0
        self._synced_total_s = 0.0

    def observe(self, seconds: float) -> None:
        i = bisect.bisect_left(_PHASE_BUCKETS, seconds)
        with self._lock:
            self.count += 1
            self.total_s += seconds
            if seconds > self.max_s:
                self.max_s = seconds
            self.bucket_counts[i] += 1

    def book(self, seconds: float) -> None:
        """``observe``, and credit the calling thread's open
        :func:`split` — for work measured on the thread that did it."""
        self.observe(seconds)
        mine = _thread.split
        if mine is not None:
            rec = mine.get(self.name)
            if rec is None:
                mine[self.name] = [1, seconds]
            else:
                rec[0] += 1
                rec[1] += seconds

    def __enter__(self):
        starts = getattr(self._tls, "starts", None)
        if starts is None:
            starts = self._tls.starts = []
        self.active_n += 1  # GIL add; synced to the gauge at snapshot
        # on the profiler's clock too, while a profiler session is open
        # (an annotation entered outside one is never recorded, so none
        # is built: the test is one flag read in native code)
        cls = _annotation
        if cls is None and "jax" in _modules:
            cls = _bind_annotation()
        if cls is not None and cls.is_enabled():
            ann = cls(self.name)
            ann.__enter__()
        else:
            ann = None
        starts.append((ann, time.perf_counter()))
        return self

    def __exit__(self, *exc):
        tls = self._tls
        ann, t0 = tls.starts.pop()
        tls.last_s = dt = time.perf_counter() - t0
        if ann is not None:
            ann.__exit__(None, None, None)
        self.active_n -= 1
        if self.inner:
            self.observe(dt)
        else:
            self.book(dt)
        return False

    @property
    def active(self) -> int:
        return self.active_n

    @property
    def last_s(self) -> float:
        """The seconds of the ``with`` block the calling thread left
        last: for a caller that files the same seconds elsewhere too,
        without a clock of its own."""
        return self._tls.last_s

    def snapshot(self) -> dict:
        with self._lock:
            count, total, mx = self.count, self.total_s, self.max_s
        return {
            "count": count,
            "total_s": round(total, 6),
            "mean_s": round(total / count, 6) if count else 0.0,
            "max_s": round(mx, 6),
            "active": self.active_n,
        }

    def _sync_metrics(self, count: int, total_s: float) -> None:
        """Bring the Prometheus series up to the given cumulative
        values. Callers serialize via ``_sync_lock`` — two concurrent
        snapshot surfaces (telemetry push + /debug/prof) reading the
        same ``_synced_*`` watermark would double-increment."""
        if count > self._synced_count:
            PROF_PHASE_TOTAL.labels(self.name).inc(count - self._synced_count)
            self._synced_count = count
        if total_s > self._synced_total_s:
            PROF_PHASE_SECONDS_TOTAL.labels(self.name).inc(
                total_s - self._synced_total_s
            )
            self._synced_total_s = total_s
        PROF_PHASE_ACTIVE.labels(self.name).set(self.active_n)


_phases: dict[str, Phase] = {}
_phases_lock = threading.Lock()
# serializes the lazy Prometheus sync across snapshot surfaces (the
# sync is read-watermark-then-inc, unsafe to race); never held while
# the per-observe hot path runs
_sync_lock = threading.Lock()


def phase_type(name: str, inner: bool = False) -> Phase:
    """Declare (or fetch) a named phase. Names are ``<service>.<what>``
    like flight event types and are censused by the dfanalyze metrics
    pass (duplicates, convention). Idempotent: re-declaring a name
    returns the same ledger entry. ``inner``: the phase is entered
    inside another ``with`` phase on the same thread, and no
    :func:`split` is credited it."""
    service, _, what = name.partition(".")
    if not service or not what or not all(
        c.islower() or c.isdigit() or c in "._" for c in name
    ):
        raise ValueError(f"phase name {name!r} must be <service>.<what> [a-z0-9_.]")
    ph = _phases.get(name)
    if ph is None:
        with _phases_lock:
            ph = _phases.get(name)
            if ph is None:
                ph = Phase(name, inner)
                _phases[name] = ph
    if inner:  # whoever fetched the name first (a tool reading the ledger) did not know
        ph.inner = True
    return ph


def phase(name: str) -> Phase:
    """Inline form: ``with profiling.phase("trainer.buffer_wait"): ...``.
    Prefer a module-level ``phase_type`` declaration on hot paths (the
    dict lookup here is the only difference)."""
    return _phases.get(name) or phase_type(name)


# A full collection stops every thread for as long as it takes to walk
# what the collector tracks (0.04-0.16 s beside a round, PERF.md): a span
# on the collecting thread, so a trace shows it between the device's ops
# and the ledger counts it. The young generations, thousands a second,
# return at one comparison. A collection is no work of the leg whose
# thread it fell on, so no split holds it.
PH_GC_FULL = phase_type("process.gc_full", inner=True)


def _on_collection(event: str, info: dict) -> None:
    if info["generation"] != 2:
        return
    if event == "start":
        PH_GC_FULL.__enter__()
    elif getattr(PH_GC_FULL._tls, "starts", None):  # not one under way when the hook went in
        PH_GC_FULL.__exit__(None, None, None)


def watch_collections() -> None:
    """Enter ``process.gc_full`` around every full collection from now
    on (idempotent): called where a service's process is assembled,
    :func:`install` and ``colocated.settle``."""
    if _on_collection not in gc.callbacks:
        gc.callbacks.append(_on_collection)


def ledger_snapshot() -> dict:
    """{phase: {count, total_s, mean_s, max_s, active, share}} — share
    is the phase's fraction of its service group's total wall (the
    four trainer ingest legs sum to 1.0 among themselves), so the
    buffer_wait share StreamStats reports per fit is readable live."""
    with _phases_lock:
        items = list(_phases.items())
    snaps = {name: ph.snapshot() for name, ph in items}
    with _sync_lock:
        # lazy Prometheus sync: every snapshot surface (scrape helpers,
        # /debug/prof, Diagnose, dumps, telemetry) brings the series
        # current, so the per-phase hot path never touches them
        for name, ph in items:
            ph._sync_metrics(snaps[name]["count"], snaps[name]["total_s"])
    group_totals: dict[str, float] = {}
    for name, snap in snaps.items():
        group = name.split(".", 1)[0]
        group_totals[group] = group_totals.get(group, 0.0) + snap["total_s"]
    for name, snap in snaps.items():
        total = group_totals[name.split(".", 1)[0]]
        snap["share"] = round(snap["total_s"] / total, 4) if total else 0.0
    return snaps


# ---------------------------------------------------------------------------
# process-wide instance + exposure surfaces
# ---------------------------------------------------------------------------

_profiler = SamplingProfiler()


def profiler() -> SamplingProfiler:
    return _profiler


def install(service: str) -> None:
    """Start the process-wide sampler (idempotent), next to
    ``flight.install`` in every server assembly, and watch the
    interpreter's full collections. ``DF_PROF=0`` or ``DF_PROF_HZ=0``
    leaves the phase ledger live but samples nothing."""
    if service:
        if not _profiler.service:
            _profiler.service = service
        elif service not in _profiler.service.split("+"):
            _profiler.service += f"+{service}"
    watch_collections()
    if enabled():
        _profiler.start()


def start() -> bool:
    return _profiler.start()


def stop() -> None:
    _profiler.stop()


def running() -> bool:
    return _profiler.running()


def profile_snapshot(seconds: "float | None" = None) -> dict:
    """The capture shape every surface serves (/debug/prof, Diagnose,
    dfprof --rpc): sampler stats + collapsed stacks (windowed when
    ``seconds`` is given) + the phase ledger."""
    snap = _profiler.stats()
    snap["window_s"] = seconds
    snap["collapsed"] = _profiler.collapsed(seconds)
    snap["phases"] = ledger_snapshot()
    return snap


def _dump_section() -> dict:
    """Flight-dump augment: the last DF_PROF_DUMP_WINDOW seconds of
    samples + the ledger, attached under ``meta.profile`` so a stall or
    crash dump names its hot frames without any live query."""
    window = _env_float("DF_PROF_DUMP_WINDOW", _DEFAULT_DUMP_WINDOW_S)
    collapsed = _profiler.collapsed(window)
    ledger = ledger_snapshot()
    if not collapsed and not ledger:
        return {}
    EV_WINDOW(window_s=window, samples=_profiler.samples)
    return {
        "profile": {
            "window_s": window,
            "hz": _profiler.hz,
            "collapsed": collapsed,
            "phases": ledger,
        }
    }


flight.register_dump_augment(_dump_section)


def telemetry_section(top_k: int = 5, window_s: float = 60.0) -> dict:
    """The reporter-side summary pushed to the manager: top-K hot
    stacks over the last minute plus per-phase totals/shares. Empty
    when nothing profiled (quiet process, sampler off)."""
    out: dict = {}
    folded = _profiler.folded(window_s) if _profiler.samples else {}
    if folded:
        top = sorted(folded.items(), key=lambda kv: kv[1], reverse=True)[:top_k]
        out["hot"] = [
            {"stack": ";".join((role,) + tup), "samples": n}
            for (role, tup), n in top
        ]
    phases = ledger_snapshot()
    if phases:
        out["phases"] = {
            name: {
                "count": s["count"],
                "total_s": s["total_s"],
                "share": s["share"],
            }
            for name, s in phases.items()
        }
    return out
