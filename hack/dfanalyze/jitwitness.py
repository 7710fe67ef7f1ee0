"""Runtime jit witness: record what actually compiled and what actually
transferred, so dispatch regressions the AST can't see (shape churn from
real data, a numpy array slipping into a jitted call three frames down)
still get caught — the XLA-plane twin of the lock witness.

``install()`` hooks three seams, all before the package imports:

- **compile logging** — jax logs every XLA compilation through
  ``jax._src.interpreters.pxla`` ("Compiling <fn> with global shapes and
  types [...]"); a handler on that logger records, per wrapped-function
  name, the distinct argument signatures compiled. No global jax flag is
  touched: the record is emitted at DEBUG when ``jax_log_compiles`` is
  off, so the witness captures it without turning the WARNING firehose
  on for the whole run.
- **``jax.jit`` itself** — replaced with a factory that (a) records the
  construction site when the caller is package code (a site constructing
  many wrappers is a per-call rebuild: each wrapper carries its own
  compile cache), and (b) wraps the returned callable to record an
  *implicit-transfer site* whenever a numpy leaf is passed straight into
  a jitted call from package code — on a real device link that is a
  silent H2D per call. Explicit conversions (``jnp.asarray`` /
  ``device_put`` at the boundary) produce jax Arrays and don't trip it.
- **``jax.device_put``** — recorded as *explicit* transfer sites, so the
  report can show sanctioned transfers next to the silent ones.

``jax.transfer_guard`` is the enforcement escalation: set
``DF_JIT_WITNESS_GUARD=log`` (C++ prints every implicit transfer's aval
to stderr) or ``=disallow`` (every implicit transfer raises at its exact
site) and ``install()`` applies it process-wide. The JSON dump stays the
witness's own record either way — the guard's log lands in C++ stderr
where Python can't join it.

Opt-in: ``DF_JIT_WITNESS=1`` makes ``tests/conftest.py`` call
``install()`` and dump to ``DF_JIT_WITNESS_OUT`` (default
``dfanalyze-jit-witness.json``) at session end, for
``python -m hack.dfanalyze --jit-witness-report <dump>``.
"""

from __future__ import annotations

import json
import logging
import os
import sys
import threading

_state_lock = threading.Lock()

_installed = False
_package_roots: tuple[str, ...] = ()
_raw_jit = None
_raw_device_put = None
_handler = None
_logger_prev: tuple | None = None  # (level, propagate)

# fn name -> {"count": total compiles, "signatures": set of sig strings}
_compiles: dict[str, dict] = {}
# ("file:line", wrapped fn name) -> wrappers built. Keyed by target TOO:
# a shared memoization helper (utils.jitcache.jit_once) constructs many
# DISTINCT functions' wrappers at one line, one each — site-only keying
# would sum them into a false churn verdict against the helper itself
_wrapper_sites: dict[tuple[str, str], int] = {}
# (file, fn, line, target, explicit, thread) -> count. Thread names are
# part of the record so the report can enforce WHERE a transfer ran —
# the ingest pipeline's contract is that every device feed lives on the
# dedicated transfer stage, never the packing thread (ISSUE 15)
_transfers: dict[tuple, int] = {}

# a function compiled for hundreds of shapes only needs enough recorded
# signatures to prove the storm; cap the per-function set
_MAX_SIGS_KEPT = 64

_PXLA_LOGGER = "jax._src.interpreters.pxla"


def _note_compile(name: str, sig: str) -> None:
    with _state_lock:
        info = _compiles.setdefault(name, {"count": 0, "signatures": set()})
        info["count"] += 1
        if len(info["signatures"]) < _MAX_SIGS_KEPT:
            info["signatures"].add(sig)


class _CompileLogHandler(logging.Handler):
    """Parses pxla's per-compilation record. Message shape (stable since
    the pjit unification): ``Compiling <name> with global shapes and
    types [<avals>]. Argument mapping: ...``; ``<name>`` is the wrapped
    function's own, or ``jit(<name>)`` as jax 0.9 writes it — recorded
    bare either way, since the static pass joins on the function name."""

    def emit(self, record: logging.LogRecord) -> None:
        try:
            msg = record.getMessage()
        except Exception:  # a mis-formatted record must never kill the run
            return
        if not msg.startswith("Compiling "):
            return
        rest = msg[len("Compiling "):]
        name, sep, tail = rest.partition(" with global shapes and types ")
        if not sep:
            return
        if name.startswith("jit(") and name.endswith(")"):
            name = name[len("jit("):-1]
        sig = tail.split(". Argument mapping", 1)[0]
        _note_compile(name, sig)


def _rel_site(filename: str, lineno: int) -> str | None:
    for root in _package_roots:
        if root in filename:
            rel = root + filename.rsplit(root, 1)[1]
            return f"{rel}:{lineno}"
    return None


def _package_frame() -> tuple[str, str, int] | None:
    """(relpath, function, line) of the nearest package frame, skipping
    this module's own frames. None when no package code is on the stack
    (a test or tool driving jax directly is not the package's bug)."""
    f = sys._getframe(2)
    depth = 0
    while f is not None and depth < 30:
        fn = f.f_code.co_filename
        if fn != __file__:
            site = _rel_site(fn, f.f_lineno)
            if site is not None:
                rel, _, line = site.rpartition(":")
                return rel, f.f_code.co_name, int(line)
        f = f.f_back
        depth += 1
    return None


def _note_transfer(target: str, explicit: bool) -> None:
    frame = _package_frame()
    if frame is None:
        return
    rel, fn, line = frame
    key = (rel, fn, line, target, explicit, threading.current_thread().name)
    with _state_lock:
        _transfers[key] = _transfers.get(key, 0) + 1


def _has_host_leaf(tree) -> bool:
    import numpy as np

    from jax import tree_util

    for leaf in tree_util.tree_leaves(tree):
        if isinstance(leaf, np.ndarray):
            return True
    return False


class _WitnessJit:
    """Transparent proxy over the real jit wrapper: records implicit
    host-leaf feeds, forwards everything else (lower/clear_cache/attrs)."""

    __slots__ = ("_fn", "_target")

    def __init__(self, fn, target: str):
        self._fn = fn
        self._target = target

    def __call__(self, *args, **kwargs):
        if _has_host_leaf((args, kwargs)):
            _note_transfer(self._target, explicit=False)
        return self._fn(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._fn, name)

    def __repr__(self) -> str:
        return f"<WitnessJit {self._target} {self._fn!r}>"


def _direct_package_frame() -> tuple[str, str, int] | None:
    """Like ``_package_frame`` but only accepts the IMMEDIATE caller
    (first frame outside this module): jax-internal machinery (pallas,
    custom-call lowering) constructs jits of its own with package code
    further up-stack, and charging those to the package would read as
    wrapper churn the package can't fix."""
    f = sys._getframe(2)
    while f is not None and f.f_code.co_filename == __file__:
        f = f.f_back
    if f is None:
        return None
    site = _rel_site(f.f_code.co_filename, f.f_lineno)
    if site is None:
        return None
    rel, _, line = site.rpartition(":")
    return rel, f.f_code.co_name, int(line)


def _witness_jit(fun=None, **kwargs):
    if fun is None:
        # functools.partial(jax.jit, static_argnames=...) applied later
        import functools

        return functools.partial(_witness_jit, **kwargs)
    wrapped = _raw_jit(fun, **kwargs)
    frame = _direct_package_frame()
    if frame is None:
        return wrapped  # not package code: hand back the raw wrapper
    rel, _, line = frame
    target = getattr(fun, "__name__", repr(fun))
    with _state_lock:
        key = (f"{rel}:{line}", target)
        _wrapper_sites[key] = _wrapper_sites.get(key, 0) + 1
    return _WitnessJit(wrapped, target)


def _witness_device_put(x, *args, **kwargs):
    if _has_host_leaf(x):
        _note_transfer("device_put", explicit=True)
    return _raw_device_put(x, *args, **kwargs)


def install(package_roots: tuple[str, ...] = ("dragonfly2_tpu/",)) -> None:
    """Patch the jax seams. Requires jax importable; call BEFORE the
    package imports so module-level jit constructions are witnessed."""
    global _installed, _package_roots, _raw_jit, _raw_device_put
    global _handler, _logger_prev
    if _installed:
        return
    import jax

    _package_roots = tuple(package_roots)
    _raw_jit = jax.jit
    _raw_device_put = jax.device_put
    jax.jit = _witness_jit
    jax.device_put = _witness_device_put

    lg = logging.getLogger(_PXLA_LOGGER)
    _logger_prev = (lg.level, lg.propagate)
    _handler = _CompileLogHandler(level=logging.DEBUG)
    lg.addHandler(_handler)
    lg.setLevel(logging.DEBUG)
    # DEBUG spam from pxla must not leak into pytest's captured logs or
    # stderr — the witness is the only consumer of these records
    lg.propagate = False

    guard = os.environ.get("DF_JIT_WITNESS_GUARD", "")
    if guard:
        jax.config.update("jax_transfer_guard", guard)
    _installed = True


def uninstall() -> None:
    global _installed, _handler, _logger_prev
    if not _installed:
        return
    import jax

    jax.jit = _raw_jit
    jax.device_put = _raw_device_put
    lg = logging.getLogger(_PXLA_LOGGER)
    if _handler is not None:
        lg.removeHandler(_handler)
        _handler = None
    if _logger_prev is not None:
        lg.setLevel(_logger_prev[0])
        lg.propagate = _logger_prev[1]
        _logger_prev = None
    _installed = False


def active() -> bool:
    return _installed


def reset() -> None:
    with _state_lock:
        _compiles.clear()
        _wrapper_sites.clear()
        _transfers.clear()


def snapshot() -> dict:
    with _state_lock:
        return {
            "compiles": {
                n: {"count": v["count"], "signatures": sorted(v["signatures"])}
                for n, v in sorted(_compiles.items())
            },
            "wrapper_sites": [
                {"site": site, "target": target, "count": n}
                for (site, target), n in sorted(_wrapper_sites.items())
            ],
            "transfers": [
                {
                    "file": rel,
                    "fn": fn,
                    "line": line,
                    "target": target,
                    "explicit": explicit,
                    "thread": thread,
                    "count": n,
                }
                for (rel, fn, line, target, explicit, thread), n in sorted(
                    _transfers.items()
                )
            ],
        }


def dump(path: str | None = None) -> str:
    path = path or os.environ.get(
        "DF_JIT_WITNESS_OUT", "dfanalyze-jit-witness.json"
    )
    with open(path, "w") as f:
        json.dump(snapshot(), f, indent=2, sort_keys=True)
    return path


# -- taps --------------------------------------------------------------------
# Lightweight context managers for the steady-state checks of tests and
# soaks (tools/stress.preheat_soak, tools/multichip_fit): count
# compiles and host→device conversions over a region without
# installing the full witness (no jax.jit patch, no site attribution).
# The counts are the taps' own: the live series
# (trainer_jit_recompiles_total, the trainer.jit_compile phase) are fed
# by the program's jax.monitoring listener in trainer/metrics.py,
# whether or not a tap is armed.


class compile_tap:
    """``with compile_tap() as t: ...`` → ``t.count`` XLA compilations
    observed in the region (any function, any thread)."""

    def __init__(self):
        self.count = 0
        self.names: list[str] = []

    def __enter__(self):
        outer = self

        class _H(logging.Handler):
            def emit(self, record):
                try:
                    msg = record.getMessage()
                except Exception:
                    return
                if msg.startswith("Compiling "):
                    outer.count += 1
                    outer.names.append(msg[len("Compiling "):].split(" ", 1)[0])

        self._h = _H(level=logging.DEBUG)
        lg = logging.getLogger(_PXLA_LOGGER)
        self._prev = (lg.level, lg.propagate)
        lg.addHandler(self._h)
        lg.setLevel(logging.DEBUG)
        lg.propagate = False
        return self

    def __exit__(self, *exc):
        lg = logging.getLogger(_PXLA_LOGGER)
        lg.removeHandler(self._h)
        # another tap/witness may still be live on this logger: only
        # restore when ours was the last handler standing
        if not lg.handlers:
            lg.setLevel(self._prev[0])
            lg.propagate = self._prev[1]


class transfer_tap:
    """``with transfer_tap() as t: ...`` → ``t.h2d`` host→device
    conversions (``jax.device_put`` / ``jnp.asarray`` called with a
    numpy array) in the region — the H2D count as the package dispatches
    it, one increment per superbatch on the steady-state single-device
    ingest path, one per DEVICE SHARD on the mesh path (the
    per-device sharded put). ``t.by_thread`` attributes each conversion
    to the thread that issued it, so the multichip harness can pin
    the no-device-work-on-the-packing-thread contract."""

    def __init__(self):
        self.h2d = 0
        self.by_thread: dict[str, int] = {}

    def _note(self):
        self.h2d += 1
        name = threading.current_thread().name
        self.by_thread[name] = self.by_thread.get(name, 0) + 1

    def __enter__(self):
        import jax
        import jax.numpy as jnp
        import numpy as np

        outer = self
        self._jax, self._jnp = jax, jnp
        self._raw_put = jax.device_put
        self._raw_asarray = jnp.asarray
        # jnp.asarray lands on the public jax.device_put internally —
        # without a reentrancy guard every conversion double-counts
        tls = self._tls = threading.local()

        def put(x, *a, **kw):
            if getattr(tls, "depth", 0) == 0 and _any_np(x, np):
                outer._note()
            return outer._raw_put(x, *a, **kw)

        def asarray(x, *a, **kw):
            if isinstance(x, np.ndarray):
                outer._note()
            tls.depth = getattr(tls, "depth", 0) + 1
            try:
                return outer._raw_asarray(x, *a, **kw)
            finally:
                tls.depth -= 1

        jax.device_put = put
        jnp.asarray = asarray
        return self

    def __exit__(self, *exc):
        self._jax.device_put = self._raw_put
        self._jnp.asarray = self._raw_asarray


def _any_np(tree, np) -> bool:
    from jax import tree_util

    return any(isinstance(l, np.ndarray) for l in tree_util.tree_leaves(tree))
