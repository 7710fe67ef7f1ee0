"""hack/dfanalyze — the framework stays green on the real package and
each pass actually catches the defect class it exists for: a planted
ABBA cycle (the PR 2 shape), a blocking call under a lock, a hot-path
function-local import, a plain-Lock self-deadlock — plus the runtime
lock-witness detecting a real inverted acquisition order from a thread,
the allowlist discipline (suppression, staleness, mandatory comments),
and the mypy-baseline machinery exercised without mypy installed."""

import json
import threading
from pathlib import Path

import pytest

from hack import dfanalyze
from hack.dfanalyze import jitwitness, witness
from hack.dfanalyze.passes import blocking, hygiene, jaxhygiene, lockorder, typecheck

REPO = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# the tier-1 wiring: the real package must analyze clean
# ---------------------------------------------------------------------------


def test_repo_is_clean():
    report = dfanalyze.run()
    failures = [
        f"{f['pass']}: {f['file']}:{f['line']}: {f['message']}"
        for p in report["passes"]
        for f in p["findings"]
        if not f["allowlisted"]
    ]
    failures += report["summary"]["stale_allowlist"]
    failures += report["summary"]["allowlist_errors"]
    assert report["ok"], "\n".join(failures)


def test_every_allowlist_entry_has_a_comment():
    al = dfanalyze.Allowlist.load()
    assert al.errors == []
    assert al.entries, "allowlist should carry the audited exceptions"
    assert all(c.strip() for c in al.entries.values())


# ---------------------------------------------------------------------------
# planted-defect fixtures
# ---------------------------------------------------------------------------


@pytest.fixture
def fakepkg(tmp_path):
    pkg = tmp_path / "fakepkg"
    pkg.mkdir()
    return pkg


ABBA_FIXTURE = '''
import threading

class Engine:
    def __init__(self):
        self._lock = threading.RLock()
        self._flush_lock = threading.Lock()

    def flush(self):
        with self._flush_lock:
            with self._lock:
                pass

    def export(self):
        # the PR 2 bug shape: flush() takes _flush_lock while _lock is
        # already held -> inverts flush's _flush_lock -> _lock order
        with self._lock:
            return self.flush()
'''


def test_lockorder_catches_the_pr2_abba_shape(fakepkg):
    (fakepkg / "engine.py").write_text(ABBA_FIXTURE)
    res = lockorder.run(fakepkg)
    cycles = [f for f in res.findings if f.key.startswith("cycle:")]
    assert len(cycles) == 1
    msg = cycles[0].message
    assert "Engine._flush_lock" in msg and "Engine._lock" in msg
    assert "via flush()" in msg or "via Engine.flush()" in msg


def test_lockorder_catches_plain_lock_reentry(fakepkg):
    (fakepkg / "re.py").write_text(
        """
import threading

class S:
    def __init__(self):
        self._lock = threading.Lock()

    def _helper(self):
        with self._lock:
            pass

    def outer(self):
        with self._lock:
            self._helper()
"""
    )
    res = lockorder.run(fakepkg)
    assert any(f.key.startswith("self:") for f in res.findings)


def test_lockorder_ignores_rlock_reentry(fakepkg):
    (fakepkg / "re.py").write_text(
        """
import threading

class S:
    def __init__(self):
        self._lock = threading.RLock()

    def _helper(self):
        with self._lock:
            pass

    def outer(self):
        with self._lock:
            self._helper()
"""
    )
    res = lockorder.run(fakepkg)
    assert res.findings == []


FLEET_SHAPE_FIXTURE = '''
import threading

class Membership:
    """The scheduler/fleet.py shape: KV I/O strictly OUTSIDE the lock,
    ring mutation + owner checks under it, never nesting into a second
    lock."""

    def __init__(self, kv, ring):
        self._lock = threading.Lock()
        self.kv = kv
        self.ring = ring
        self._members = ()

    def reconcile(self):
        members = tuple(self.kv.scan_iter("fleet:member:*"))  # outside
        with self._lock:
            self._members = members

    def check_owner(self, task_id):
        with self._lock:
            return self.ring.pick(task_id)


class Selector:
    """The glue.SchedulerSelector shape: the ring lock releases BEFORE
    the dial — no call chain ever holds Membership._lock and
    Selector._lock together."""

    def __init__(self, membership):
        self._lock = threading.Lock()
        self.membership = membership

    def resolve(self, task_id):
        with self._lock:
            candidates = list(self._ring_candidates(task_id))
        return candidates[0]

    def _ring_candidates(self, task_id):
        return [task_id]
'''


def test_lockorder_fleet_shape_is_clean(fakepkg):
    """The fleet's lock model (Membership._lock, Selector._lock — KV
    I/O outside, no nesting between the two) must analyze clean; this
    fixture documents the intended shape so a regression that nests
    them shows up against a named baseline."""
    (fakepkg / "fleet.py").write_text(FLEET_SHAPE_FIXTURE)
    res = lockorder.run(fakepkg)
    assert res.findings == [], [f.message for f in res.findings]


def test_lockorder_catches_a_fleet_nesting_regression(fakepkg):
    """The defect the clean shape guards against: a reconcile that
    calls into the selector while holding the membership lock, while
    the selector's refresh calls back into membership under its own
    lock — the ABBA the fleet plane must never grow."""
    (fakepkg / "fleet_bad.py").write_text(
        '''
import threading

class BadFleet:
    def __init__(self):
        self._lock = threading.Lock()       # membership state
        self._ring_lock = threading.Lock()  # selector ring

    def reconcile(self):
        with self._lock:
            self._push_ring()  # membership -> ring

    def _push_ring(self):
        with self._ring_lock:
            pass

    def resolve(self):
        with self._ring_lock:
            self._owner()  # ring -> membership: the inversion

    def _owner(self):
        with self._lock:
            pass
'''
    )
    res = lockorder.run(fakepkg)
    cycles = [f for f in res.findings if f.key.startswith("cycle:")]
    assert cycles, [f.message for f in res.findings]
    assert "BadFleet._lock" in cycles[0].message
    assert "BadFleet._ring_lock" in cycles[0].message


WAVE_PACK_SHAPE_FIXTURE = '''
import threading

class Topo:
    """The engine side of the wave join: ONE lock hold snapshots the
    host index, the gather kernel dispatches AFTER release."""

    def __init__(self):
        self._lock = threading.RLock()

    def rtt_affinity_pairs(self):
        with self._lock:
            snap = 1  # index/edges/D snapshot only
        return snap  # kernel dispatch outside the lock


class WaveEvaluator:
    """The evaluator side: pack (topology lock inside, released before
    scoring), then rung notes under _rung_lock — no chain ever holds
    Topo._lock and WaveEvaluator._rung_lock together."""

    def __init__(self, topo):
        self._rung_lock = threading.Lock()
        self.topo = topo

    def evaluate_wave(self):
        feats = self.topo.rtt_affinity_pairs()
        self._note_rung()
        return feats

    def _note_rung(self):
        with self._rung_lock:
            pass
'''


def test_lockorder_wave_pack_shape_is_clean(fakepkg):
    """The wave-pack lock model (ISSUE 16): the topology snapshot lock
    releases before the gather dispatch and before any rung-note lock —
    this fixture names the intended shape so a nesting regression shows
    up against a baseline."""
    (fakepkg / "wave.py").write_text(WAVE_PACK_SHAPE_FIXTURE)
    res = lockorder.run(fakepkg)
    assert res.findings == [], [f.message for f in res.findings]


def test_lockorder_catches_a_wave_pack_nesting_regression(fakepkg):
    """The defect the clean shape guards against: a pack that gathers
    UNDER the rung lock while a topology callback notes the rung under
    its own lock — the ABBA the wave plane must never grow."""
    (fakepkg / "wave_bad.py").write_text(
        '''
import threading

class BadWave:
    def __init__(self):
        self._rung_lock = threading.Lock()
        self._topo_lock = threading.Lock()

    def evaluate_wave(self):
        with self._rung_lock:
            self._gather()  # rung -> topo: pack under the rung lock

    def _gather(self):
        with self._topo_lock:
            pass

    def on_flush(self):
        with self._topo_lock:
            self._note_rung()  # topo -> rung: the inversion

    def _note_rung(self):
        with self._rung_lock:
            pass
'''
    )
    res = lockorder.run(fakepkg)
    cycles = [f for f in res.findings if f.key.startswith("cycle:")]
    assert cycles, [f.message for f in res.findings]
    assert "BadWave._rung_lock" in cycles[0].message
    assert "BadWave._topo_lock" in cycles[0].message


REPLICATION_SHAPE_FIXTURE = '''
import threading

class Ledger:
    """The scheduler/swarm.py shape: every observatory hook is one
    short hold on the ledger lock; nothing under it calls out of the
    module."""

    def __init__(self):
        self._lock = threading.Lock()
        self._dirty = set()

    def on_piece(self, tid):
        with self._lock:
            self._dirty.add(tid)

    def drain_dirty(self):
        with self._lock:
            drained = set(self._dirty)
            self._dirty.clear()
            return drained

    def export_task(self, tid):
        with self._lock:
            return {"id": tid}


class Replicator:
    """The scheduler/swarm_replication.py shape: every ledger call
    happens OUTSIDE the replicator lock — the dirty drain before the
    hold, the payload exports after release — so the two locks never
    nest in either direction."""

    def __init__(self, ledger):
        self._lock = threading.Lock()
        self.ledger = ledger
        self._pending = {}

    def flush_once(self):
        dirty = self.ledger.drain_dirty()  # ledger lock, alone
        with self._lock:  # replicator lock, alone
            for tid in dirty:
                self._pending[tid] = None
            batch = list(self._pending)
            self._pending.clear()
        return [self.ledger.export_task(t) for t in batch]
'''


def test_lockorder_replication_shape_is_clean(fakepkg):
    """The replication plane's lock model (ISSUE 20): the replicator
    drains the observatory's dirty set before taking its own lock and
    exports payloads after releasing it, so Replicator._lock and
    Ledger._lock never nest — this fixture names the intended shape so
    a regression that nests them shows up against a baseline."""
    (fakepkg / "replication.py").write_text(REPLICATION_SHAPE_FIXTURE)
    res = lockorder.run(fakepkg)
    assert res.findings == [], [f.message for f in res.findings]


def test_lockorder_catches_a_replication_nesting_regression(fakepkg):
    """The defect the clean shape guards against: a flush that exports
    UNDER the replicator lock while an observatory hook notifies the
    replicator under the ledger lock — the ABBA the one-way
    replicator→ledger rule forbids."""
    (fakepkg / "replication_bad.py").write_text(
        '''
import threading

class BadReplicator:
    def __init__(self):
        self._lock = threading.Lock()         # replicator backlog
        self._ledger_lock = threading.Lock()  # observatory ledger

    def flush_once(self):
        with self._lock:
            self._export()  # replicator -> ledger: export under the hold

    def _export(self):
        with self._ledger_lock:
            pass

    def on_piece(self):
        with self._ledger_lock:
            self._mark_dirty()  # ledger -> replicator: the inversion

    def _mark_dirty(self):
        with self._lock:
            pass
'''
    )
    res = lockorder.run(fakepkg)
    cycles = [f for f in res.findings if f.key.startswith("cycle:")]
    assert cycles, [f.message for f in res.findings]
    assert "BadReplicator._lock" in cycles[0].message
    assert "BadReplicator._ledger_lock" in cycles[0].message


def test_blocking_catches_calls_under_lock(fakepkg):
    (fakepkg / "svc.py").write_text(
        """
import threading
import time

class S:
    def __init__(self):
        self._lock = threading.Lock()

    def sleepy(self):
        with self._lock:
            time.sleep(1.0)

    def _announce(self, stub):
        stub.AnnouncePeer(object())

    def rpc_under_lock(self, stub):
        with self._lock:
            self._announce(stub)

    def queue_under_lock(self, q):
        with self._lock:
            q.get(timeout=1.0)
"""
    )
    res = blocking.run(fakepkg)
    cats = {f.key.split(":")[-2] for f in res.findings}
    assert "sleep" in cats
    assert "rpc" in cats  # transitively, via _announce
    assert "queue" in cats
    # the transitive finding names the call chain
    assert any("via S._announce" in f.message for f in res.findings)


def test_hygiene_catches_hot_import_and_except_pass(fakepkg):
    (fakepkg / "hot.py").write_text(
        """# dfanalyze: hot

def hot_path():
    from fakepkg import helper
    return helper
"""
    )
    (fakepkg / "loopy.py").write_text(
        """
def churn(items):
    for it in items:
        try:
            it.work()
        except Exception:
            pass
"""
    )
    res = hygiene.run(fakepkg)
    keys = {f.key for f in res.findings}
    assert "import:fakepkg/hot.py:hot_path:fakepkg" in keys
    assert any(k.startswith("except-pass:fakepkg/loopy.py:churn") for k in keys)


def test_hygiene_catches_discarded_contextvar_token(fakepkg):
    (fakepkg / "cv.py").write_text(
        """
import contextvars

_current = contextvars.ContextVar("c", default=None)

def leak(value):
    _current.set(value)
"""
    )
    res = hygiene.run(fakepkg)
    keys = {f.key for f in res.findings}
    assert "contextvar:fakepkg/cv.py:_current:discarded" in keys
    assert "contextvar:fakepkg/cv.py:_current:noreset" in keys


def test_clean_module_has_no_findings(fakepkg):
    (fakepkg / "clean.py").write_text(
        """
import threading

class C:
    def __init__(self):
        self._lock = threading.Lock()
        self._items = []

    def add(self, x):
        with self._lock:
            self._items.append(x)

    def drain(self):
        with self._lock:
            out, self._items = self._items, []
        return out
"""
    )
    report = dfanalyze.run(package_dir=fakepkg, allowlist=dfanalyze.Allowlist())
    assert report["ok"], json.dumps(report["passes"], indent=2)


# ---------------------------------------------------------------------------
# jaxhygiene: planted fixtures for every finding kind
# ---------------------------------------------------------------------------


def test_jaxhygiene_catches_host_sync_and_side_effects_under_trace(fakepkg):
    (fakepkg / "traced.py").write_text(
        """
import jax
import numpy as np

@jax.jit
def bad_step(params, x):
    v = float(x)          # host sync under trace
    y = x.item()          # host sync under trace
    z = np.asarray(x)     # numpy pull mid-trace
    print(x)              # trace-time-only side effect
    return v + y + z
"""
    )
    res = jaxhygiene.run(fakepkg)
    keys = {f.key for f in res.findings}
    assert "host-sync:fakepkg/traced.py:bad_step:float" in keys
    assert "host-sync:fakepkg/traced.py:bad_step:item" in keys
    assert "host-sync:fakepkg/traced.py:bad_step:np.asarray" in keys
    assert "side-effect:fakepkg/traced.py:bad_step:print" in keys


def test_jaxhygiene_catches_traced_branch_but_not_static_branch(fakepkg):
    (fakepkg / "branchy.py").write_text(
        """
import functools
import jax

@functools.partial(jax.jit, static_argnames=("mode",))
def step(x, mode):
    if mode:        # static arg: legal python control flow
        x = x + 1
    if x > 0:       # traced value: crashes or bakes one branch in
        x = x * 2
    return x
"""
    )
    res = jaxhygiene.run(fakepkg)
    keys = {f.key for f in res.findings}
    assert "traced-branch:fakepkg/branchy.py:step:x" in keys
    assert not any(k.endswith(":mode") for k in keys)


def test_jaxhygiene_catches_jit_in_loop(fakepkg):
    (fakepkg / "loopy.py").write_text(
        """
import jax

def churn(fns, xs):
    out = []
    for f, x in zip(fns, xs):
        out.append(jax.jit(f)(x))  # a compile per iteration
    return out
"""
    )
    res = jaxhygiene.run(fakepkg)
    assert any(f.key.startswith("jit-in-loop:fakepkg/loopy.py:churn") for f in res.findings)


def test_jaxhygiene_catches_jit_per_call_only_in_device_hot(fakepkg):
    src = """
import jax

def fwd(params, x):
    return x

def rank(params, feats):
    return jax.jit(fwd)(params, feats)  # fresh wrapper per rank() call
"""
    (fakepkg / "cold.py").write_text(src)
    (fakepkg / "hot.py").write_text("# dfanalyze: device-hot\n" + src)
    res = jaxhygiene.run(fakepkg)
    keys = {f.key for f in res.findings}
    assert "jit-per-call:fakepkg/hot.py:rank" in keys
    assert not any("cold.py" in k for k in keys)


def test_jaxhygiene_memoized_factory_is_exempt(fakepkg):
    (fakepkg / "memo.py").write_text(
        """# dfanalyze: device-hot
import jax

_step_cache: dict = {}

def get_step(lr):
    if lr in _step_cache:
        return _step_cache[lr]

    @jax.jit
    def step(params, x):
        return params, x * lr

    _step_cache[lr] = step
    return step
"""
    )
    res = jaxhygiene.run(fakepkg)
    assert res.findings == [], [f.message for f in res.findings]


def test_jaxhygiene_catches_unstable_static_args(fakepkg):
    (fakepkg / "statics.py").write_text(
        """
import functools
import jax

@functools.partial(jax.jit, static_argnames=("shape", "opts"))
def build(x, shape, opts=[]):
    return x

def caller(x):
    return build(x, shape=[4, 4])  # a list never hits the jit cache
"""
    )
    res = jaxhygiene.run(fakepkg)
    keys = {f.key for f in res.findings}
    assert "unstable-static:fakepkg/statics.py:build:opts" in keys  # bad default
    assert "unstable-static:fakepkg/statics.py:build:shape" in keys  # bad call site


def test_jaxhygiene_catches_block_until_ready_and_host_pull(fakepkg):
    (fakepkg / "sync.py").write_text(
        """# dfanalyze: device-hot
import jax
import numpy as np

def wait_all(xs, arr, i):
    jax.block_until_ready(xs)
    return np.asarray(arr)[i]  # whole-array D2H to read one element
"""
    )
    res = jaxhygiene.run(fakepkg)
    keys = {f.key for f in res.findings}
    assert (
        "block-until-ready:fakepkg/sync.py:wait_all:jax.block_until_ready" in keys
    )
    assert "host-pull:fakepkg/sync.py:wait_all:np.asarray" in keys


def test_jaxhygiene_clean_device_hot_module(fakepkg):
    """The idioms the fixes in this PR converged on — module-scope jits,
    explicit boundary conversion, device-side indexing — analyze clean."""
    (fakepkg / "clean.py").write_text(
        """# dfanalyze: device-hot
import jax
import jax.numpy as jnp
import numpy as np

@jax.jit
def step(params, x):
    return params, x * 2

def feed(params, buf):
    return step(params, jnp.asarray(buf))

def read_one(arr, i):
    return float(np.asarray(arr[i]))  # index on device, pull one element
"""
    )
    res = jaxhygiene.run(fakepkg)
    assert res.findings == [], [f.message for f in res.findings]


def test_jaxhygiene_allowlist_suppresses_and_goes_stale(fakepkg, tmp_path):
    (fakepkg / "hot.py").write_text(
        """# dfanalyze: device-hot
import jax

def fwd(x):
    return x

def rank(feats):
    return jax.jit(fwd)(feats)
"""
    )
    key = "jit-per-call:fakepkg/hot.py:rank"
    al_file = tmp_path / "allow.txt"
    al_file.write_text(f"jaxhygiene {key}  # audited: test fixture\n")
    al = dfanalyze.Allowlist.load(al_file)
    report = dfanalyze.run(package_dir=fakepkg, allowlist=al)
    assert report["ok"], json.dumps(report["summary"], indent=2)
    assert report["summary"]["allowlisted"] == 1

    (fakepkg / "hot.py").write_text("x = 1\n")
    al2 = dfanalyze.Allowlist.load(al_file)
    report2 = dfanalyze.run(package_dir=fakepkg, allowlist=al2)
    assert not report2["ok"]
    assert report2["summary"]["stale_allowlist"] == [f"jaxhygiene {key}"]


def test_collect_jit_sites_and_device_hot_files(fakepkg):
    (fakepkg / "a.py").write_text(
        """# dfanalyze: device-hot
import jax

@jax.jit
def fwd(x):
    return x
"""
    )
    (fakepkg / "b.py").write_text("import jax\n\ndef g(x):\n    return x\n\nh = jax.jit(g)\n")
    sites = jaxhygiene.collect_jit_sites(fakepkg)
    assert "fwd" in sites and sites["fwd"][0][0] == "fakepkg/a.py"
    assert "g" in sites
    assert jaxhygiene.device_hot_files(fakepkg) == {"fakepkg/a.py"}


# ---------------------------------------------------------------------------
# allowlist discipline
# ---------------------------------------------------------------------------


def test_allowlist_suppresses_and_goes_stale(fakepkg, tmp_path):
    (fakepkg / "svc.py").write_text(
        """
import threading
import time

class S:
    def __init__(self):
        self._lock = threading.Lock()

    def sleepy(self):
        with self._lock:
            time.sleep(1.0)
"""
    )
    key = "fakepkg/svc.py:S.sleepy:S._lock:sleep:time.sleep"
    al_file = tmp_path / "allow.txt"
    al_file.write_text(f"blocking {key}  # audited: test fixture\n")
    al = dfanalyze.Allowlist.load(al_file)
    report = dfanalyze.run(package_dir=fakepkg, allowlist=al)
    assert report["ok"]
    assert report["summary"]["allowlisted"] == 1

    # same allowlist against a now-clean package -> stale entry fails
    (fakepkg / "svc.py").write_text("x = 1\n")
    al2 = dfanalyze.Allowlist.load(al_file)
    report2 = dfanalyze.run(package_dir=fakepkg, allowlist=al2)
    assert not report2["ok"]
    assert report2["summary"]["stale_allowlist"] == [f"blocking {key}"]


def test_allowlist_requires_comment(tmp_path):
    f = tmp_path / "allow.txt"
    f.write_text("blocking some:key\n")
    al = dfanalyze.Allowlist.load(f)
    assert al.errors and "comment" in al.errors[0]


# ---------------------------------------------------------------------------
# runtime lock-witness
# ---------------------------------------------------------------------------


@pytest.fixture
def fresh_witness():
    """Install the witness scoped to THIS file's locks. Under a
    DF_LOCK_WITNESS=1 session the witness is already live package-wide
    (and uninstalling it here would blind the rest of the session), so
    these meta-tests skip — the session itself is the witness test."""
    if witness.active():
        pytest.skip("lock witness already active session-wide")
    witness.reset()
    witness.install(package_roots=("tests/",))
    yield
    witness.uninstall()
    witness.reset()


def test_witness_detects_inverted_order_from_a_thread(fresh_witness, fakepkg, tmp_path):
    a = threading.Lock()
    b = threading.Lock()
    with a:
        with b:
            pass

    def inverted():
        with b:
            with a:
                pass

    t = threading.Thread(target=inverted)
    t.start()
    t.join()
    snap = witness.snapshot()
    edges = {
        (e["from"].rsplit(":", 1)[-1], e["to"].rsplit(":", 1)[-1])
        for e in snap["edges"]
    }
    assert len(edges) >= 2  # both orders observed

    report = tmp_path / "witness.json"
    report.write_text(json.dumps(snap))
    res = lockorder.witness_crosscheck(fakepkg, report)
    cycles = [f for f in res.findings if f.key.startswith("cycle:")]
    assert cycles, [f.message for f in res.findings]
    assert "static+witnessed" in cycles[0].message


def test_witness_rlock_reentry_is_not_an_edge(fresh_witness):
    r = threading.RLock()
    with r:
        with r:  # re-entry, same instance: no order edge
            pass
    assert witness.snapshot()["edges"] == []


def test_witness_flags_cross_instance_nesting(fresh_witness, fakepkg, tmp_path):
    def make():
        return threading.Lock()  # ONE creation site, two instances

    l1, l2 = make(), make()
    with l1:
        with l2:
            pass
    snap = witness.snapshot()
    assert any(e["same_site"] for e in snap["edges"])
    report = tmp_path / "witness.json"
    report.write_text(json.dumps(snap))
    res = lockorder.witness_crosscheck(fakepkg, report)
    assert any(f.key.startswith("cross-instance:") for f in res.findings)


def test_witness_cross_thread_release_purges_held_stack(fresh_witness):
    """A Lock released by another thread (the hand-off pattern, legal
    for threading.Lock) must not linger on the acquirer's held-stack and
    mint phantom order pairs."""
    lk = threading.Lock()
    other = threading.Lock()
    lk.acquire()  # main thread holds lk...
    t = threading.Thread(target=lk.release)  # ...a worker releases it
    t.start()
    t.join()
    with other:  # next acquire must NOT record a bogus lk -> other pair
        pass
    assert witness.snapshot()["edges"] == []


def test_witness_ignores_stdlib_locks(fresh_witness):
    import queue

    q = queue.Queue()  # queue's internal lock is created in stdlib code
    q.put(1)
    assert q.get() == 1
    assert witness.snapshot()["locks"] == {}


def test_witness_lock_passes_as_real_lock(fresh_witness):
    """Condition/with duck-typing: the wrappers behave like the real
    primitives (non-blocking acquire, locked(), context manager)."""
    lk = threading.Lock()
    assert lk.acquire(False) is True
    assert lk.locked()
    assert lk.acquire(False) is False
    lk.release()
    cond = threading.Condition(threading.RLock())
    with cond:
        cond.notify_all()


# ---------------------------------------------------------------------------
# runtime jit witness
# ---------------------------------------------------------------------------


@pytest.fixture
def fresh_jitwitness():
    """Install the jit witness scoped to THIS file's jax usage. Under a
    DF_JIT_WITNESS=1 session the witness is already live package-wide
    (and uninstalling it would blind the rest of the session), so these
    meta-tests skip — the session itself is the witness test."""
    pytest.importorskip("jax")
    if jitwitness.active():
        pytest.skip("jit witness already active session-wide")
    jitwitness.reset()
    jitwitness.install(package_roots=("tests/",))
    yield
    jitwitness.uninstall()
    jitwitness.reset()


def test_jitwitness_records_compiles_rewraps_and_transfers(fresh_jitwitness):
    import numpy as np

    import jax

    def wfx_fn(x):
        return x * 2

    for i in range(3):
        fn = jax.jit(wfx_fn)  # fresh wrapper each round: 3 at one site
        fn(np.ones((2 + i,), np.float32))  # numpy in: implicit transfer
    snap = jitwitness.snapshot()
    assert snap["compiles"]["wfx_fn"]["count"] == 3
    assert len(snap["compiles"]["wfx_fn"]["signatures"]) == 3
    wfx_sites = [w for w in snap["wrapper_sites"] if w["target"] == "wfx_fn"]
    assert len(wfx_sites) == 1 and wfx_sites[0]["count"] == 3
    implicit = [t for t in snap["transfers"] if not t["explicit"]]
    assert implicit and implicit[0]["target"] == "wfx_fn"


def test_jitwitness_warm_cache_records_nothing_new(fresh_jitwitness):
    import jax
    import jax.numpy as jnp

    def wfy_fn(x):
        return x + 1

    fn = jax.jit(wfy_fn)
    x = jnp.ones((4,))
    fn(x)
    jitwitness.reset()  # past the warmup
    fn(x)  # cached executable, jax array in
    snap = jitwitness.snapshot()
    assert "wfy_fn" not in snap["compiles"]
    assert [t for t in snap["transfers"] if not t["explicit"]] == []


def test_jitwitness_device_put_is_explicit(fresh_jitwitness):
    import numpy as np

    import jax

    jax.device_put(np.ones((3,), np.float32))
    snap = jitwitness.snapshot()
    assert snap["transfers"] and all(t["explicit"] for t in snap["transfers"])


def test_jitwitness_roundtrip_crosscheck(fresh_jitwitness, fakepkg, tmp_path):
    """The full loop: real compiles/wrappers/transfers recorded here,
    dumped, then joined onto a planted static package whose jit site
    names match — retrace storm, wrapper churn, and the device-hot
    implicit transfer all surface as findings."""
    import numpy as np

    import jax

    def wfz_fn(x):
        return x * 3

    for i in range(jaxhygiene.MAX_SIGNATURES + 2):
        jax.jit(wfz_fn)(np.ones((2 + i,), np.float32))
    snap = jitwitness.snapshot()
    # the witnessed facts join onto the static package by function name
    # and device-hot file; rewrite the recorded sites onto the fixture
    (fakepkg / "plane.py").write_text(
        """# dfanalyze: device-hot
import jax

def wfz_fn(x):
    return x * 3

ranked = jax.jit(wfz_fn)
"""
    )
    snap["wrapper_sites"] = [
        {"site": "fakepkg/plane.py:7", "target": "wfz_fn", "count": 99}
    ]
    snap["transfers"] = [
        {
            "file": "fakepkg/plane.py",
            "fn": "rank",
            "line": 8,
            "target": "wfz_fn",
            "explicit": False,
            "count": 12,
        }
    ]
    report = tmp_path / "jit-witness.json"
    report.write_text(json.dumps(snap))
    res = jaxhygiene.witness_crosscheck(fakepkg, report)
    keys = {f.key for f in res.findings}
    assert "retrace:wfz_fn" in keys
    assert "jit-rewrap:fakepkg/plane.py:wfz_fn" in keys
    assert "transfer:fakepkg/plane.py:rank" in keys


def test_jitwitness_crosscheck_flags_packing_thread_transfer(fakepkg, tmp_path):
    """ISSUE 15 gate: an ingest.py transfer on any thread OTHER than the
    trainer.ingest-* stages fails the crosscheck regardless of
    explicitness or frame name — notably the realistic regression where
    `put(arg)` moves back into the packing loop (fn is still "put", but
    the thread is the caller's). The sanctioned stage threads and the
    named post-stream tail functions stay clean."""
    dump = {
        "compiles": {},
        "wrapper_sites": [],
        "transfers": [
            {  # inline device work in the packing body
                "file": "dragonfly2_tpu/trainer/ingest.py",
                "fn": "stream_train_mlp",
                "line": 700,
                "target": "device_put",
                "explicit": True,
                "thread": "MainThread",
                "count": 3,
            },
            {  # the realistic regression: put() called from the packer
                "file": "dragonfly2_tpu/trainer/ingest.py",
                "fn": "put",
                "line": 544,
                "target": "device_put",
                "explicit": True,
                "thread": "trainer.fit",
                "count": 7,
            },
            {  # the transfer stage's put: sanctioned
                "file": "dragonfly2_tpu/trainer/ingest.py",
                "fn": "put",
                "line": 544,
                "target": "device_put",
                "explicit": True,
                "thread": "trainer.ingest-transfer",
                "count": 100,
            },
            {  # the named post-stream tail: sanctioned
                "file": "dragonfly2_tpu/trainer/ingest.py",
                "fn": "_ragged_tail",
                "line": 890,
                "target": "device_put",
                "explicit": True,
                "thread": "MainThread",
                "count": 1,
            },
        ],
    }
    report = tmp_path / "jit-witness.json"
    report.write_text(json.dumps(dump))
    res = jaxhygiene.witness_crosscheck(fakepkg, report)
    keys = {f.key for f in res.findings}
    assert keys == {
        "pack-transfer:stream_train_mlp:device_put",
        "pack-transfer:put:device_put",
    }, [f.message for f in res.findings]


def test_jitwitness_crosscheck_ignores_foreign_and_quiet_functions(
    fakepkg, tmp_path
):
    """jax-internal eager ops (not a package jit site) and package
    functions under the signature allowance must NOT fail the join."""
    (fakepkg / "plane.py").write_text(
        "import jax\n\ndef quiet_fn(x):\n    return x\n\nf = jax.jit(quiet_fn)\n"
    )
    dump = {
        "compiles": {
            "convert_element_type": {
                "count": 500,
                "signatures": [f"[s{i}]" for i in range(40)],
            },
            "quiet_fn": {"count": 3, "signatures": ["[a]", "[b]", "[c]"]},
        },
        # a shared memoization helper builds MANY distinct functions'
        # wrappers at one line, one each — per-(site, target) records
        # under the allowance must not read as churn
        "wrapper_sites": [
            {"site": "fakepkg/plane.py:5", "target": f"fwd_{i}", "count": 1}
            for i in range(12)
        ],
        "transfers": [],
    }
    report = tmp_path / "jit-witness.json"
    report.write_text(json.dumps(dump))
    res = jaxhygiene.witness_crosscheck(fakepkg, report)
    assert res.findings == [], [f.message for f in res.findings]


def test_witness_allowlist_entries_never_stale_on_subset_runs(fakepkg, tmp_path):
    """A subset witness run legitimately exercises none of the
    allowlisted storms — witness-pass entries are exempt from the
    stale rule (the full witnessed tier-1 audits them for rot)."""
    (fakepkg / "ok.py").write_text("x = 1\n")
    dump = tmp_path / "jw.json"
    dump.write_text(json.dumps({"compiles": {}, "wrapper_sites": [], "transfers": []}))
    al_file = tmp_path / "allow.txt"
    al_file.write_text(
        "jit-witness retrace:never_seen_here  # audited: full-session-only storm\n"
    )
    al = dfanalyze.Allowlist.load(al_file)
    report = dfanalyze.run(
        package_dir=fakepkg, allowlist=al, jit_witness_report=dump
    )
    assert report["ok"], json.dumps(report["summary"], indent=2)
    assert report["summary"]["stale_allowlist"] == []


def test_jit_witness_report_flag_requires_dump(fakepkg, capsys):
    from hack.dfanalyze.__main__ import main

    (fakepkg / "ok.py").write_text("x = 1\n")
    rc = main(["--jit-witness-report", str(fakepkg / "missing.json"), str(fakepkg)])
    assert rc == 1
    assert "jit-witness report not found" in capsys.readouterr().out


def test_bench_taps_count_compiles_and_h2d():
    """The taps (compile_tap/transfer_tap) behind the steady-state
    checks of tests and soaks: a fresh shape compiles and counts, a
    warm shape counts zero, and the H2D tap sees exactly the
    numpy→device conversions."""
    pytest.importorskip("jax")
    import numpy as np

    import jax
    import jax.numpy as jnp

    from dragonfly2_tpu.trainer import metrics as M

    @jax.jit
    def tap_probe(x):
        return x * 5

    base_compiles = M.JIT_RECOMPILES_TOTAL.value
    with jitwitness.compile_tap() as ct, jitwitness.transfer_tap() as tt:
        tap_probe(jnp.asarray(np.ones((7,), np.float32)))  # compile + 1 h2d
    assert ct.count >= 1
    assert tt.h2d == 1
    assert M.JIT_RECOMPILES_TOTAL.value >= base_compiles + 1  # census-covered series
    with jitwitness.compile_tap() as ct2, jitwitness.transfer_tap() as tt2:
        tap_probe(jnp.asarray(np.ones((7,), np.float32)))  # warm: no compile
    assert ct2.count == 0
    assert tt2.h2d == 1


# ---------------------------------------------------------------------------
# typecheck baseline machinery (runs without mypy installed)
# ---------------------------------------------------------------------------

MYPY_LINE = (
    "dragonfly2_tpu/utils/cache.py:42: error: Incompatible return value"
    ' type (got "None", expected "int")  [return-value]'
)


def test_typecheck_normalize_drops_line_numbers():
    norm = typecheck.normalize(MYPY_LINE)
    assert norm == (
        "dragonfly2_tpu/utils/cache.py|return-value|Incompatible return"
        ' value type (got "None", expected "int")'
    )
    shifted = MYPY_LINE.replace(":42:", ":99:")
    assert typecheck.normalize(shifted) == norm


def test_typecheck_baseline_suppresses_known_and_fails_new(tmp_path):
    base = tmp_path / "baseline.txt"
    typecheck.write_baseline([typecheck.normalize(MYPY_LINE)], base)
    loaded = typecheck.load_baseline(base)
    assert typecheck.findings_against_baseline([MYPY_LINE], loaded) == []
    new_line = MYPY_LINE.replace("cache.py", "digest.py")
    findings = typecheck.findings_against_baseline([new_line], loaded)
    assert len(findings) == 1
    assert "digest.py" in findings[0].message
    assert findings[0].pass_id == "typecheck"


def test_typecheck_skips_cleanly_without_mypy():
    res = typecheck.run(dfanalyze.DEFAULT_PACKAGE)
    if typecheck.mypy_available():  # pragma: no cover - image has no mypy
        assert res.skipped == ""
    else:
        assert "mypy not installed" in res.skipped
        assert res.findings == []


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_cli_exit_codes_and_json(fakepkg, capsys):
    from hack.dfanalyze.__main__ import main

    (fakepkg / "svc.py").write_text(
        """
import threading
import time

class S:
    def __init__(self):
        self._lock = threading.Lock()

    def sleepy(self):
        with self._lock:
            time.sleep(1.0)
"""
    )
    assert main(["--json", str(fakepkg)]) == 1
    out = capsys.readouterr().out
    report = json.loads(out)
    assert report["ok"] is False
    assert any(
        f["pass"] == "blocking" for p in report["passes"] for f in p["findings"]
    )
    assert main(["--list-passes"]) == 0


def test_check_metrics_shim_still_works():
    """The old entry point forwards to the migrated pass."""
    import importlib
    import sys

    sys.path.insert(0, str(REPO / "hack"))
    try:
        import check_metrics

        importlib.reload(check_metrics)
        assert check_metrics.check() == []
    finally:
        sys.path.remove(str(REPO / "hack"))


PREHEAT_PLANNER_SHAPE_FIXTURE = '''
import threading

class Window:
    """Demand side of the preheat sweep: its lock covers only the
    series dict; snapshots copy out before anything else runs."""

    def __init__(self):
        self._lock = threading.Lock()
        self._series = {}

    def observe(self, task_id, count):
        with self._lock:
            self._series[task_id] = self._series.get(task_id, 0.0) + count

    def series_batch(self):
        with self._lock:
            return dict(self._series)


class Planner:
    """Planner side: _lock guards ONLY the recently-planned map and is
    never held across the window, the forecaster, or the resource
    model — each snapshot/forecast happens before the lock, bookkeeping
    after."""

    def __init__(self, window):
        self._lock = threading.Lock()
        self._planned_at = {}
        self.window = window

    def sweep_once(self, now):
        snapshot = self.window.series_batch()  # window lock, then released
        picked = [t for t in snapshot if not self._covered(t, now)]
        with self._lock:
            for task_id in picked:
                self._planned_at[task_id] = now
        return picked

    def _covered(self, task_id, now):
        with self._lock:
            at = self._planned_at.get(task_id)
        return at is not None and now - at < 120.0

    def stats(self):
        with self._lock:
            return {"cooling": len(self._planned_at)}
'''


def test_lockorder_preheat_planner_shape_is_clean(fakepkg):
    """The preheat planner's lock model (Planner._lock for cooldown
    bookkeeping only, Window._lock for the series dict, no hold across
    the other) must analyze clean — the named baseline for the sweep's
    lock shape."""
    (fakepkg / "preheat_planner.py").write_text(PREHEAT_PLANNER_SHAPE_FIXTURE)
    res = lockorder.run(fakepkg)
    assert res.findings == [], [f.message for f in res.findings]


def test_lockorder_catches_a_preheat_nesting_regression(fakepkg):
    """The regression the clean shape guards against: a sweep that
    snapshots the window while holding the planner lock, while the
    window notifies the planner under its own lock — the ABBA a demand
    observer callback could grow."""
    (fakepkg / "preheat_bad.py").write_text(
        '''
import threading

class BadPlanner:
    def __init__(self):
        self._lock = threading.Lock()        # cooldown bookkeeping
        self._demand_lock = threading.Lock() # series dict

    def sweep_once(self):
        with self._lock:
            self._snapshot()  # planner -> demand: held across the window

    def _snapshot(self):
        with self._demand_lock:
            return {}

    def observe(self):
        with self._demand_lock:
            self._note_planned()  # demand -> planner: the inversion

    def _note_planned(self):
        with self._lock:
            pass
'''
    )
    res = lockorder.run(fakepkg)
    cycles = [f for f in res.findings if f.key.startswith("cycle:")]
    assert cycles, [f.message for f in res.findings]
    assert "BadPlanner._lock" in cycles[0].message
    assert "BadPlanner._demand_lock" in cycles[0].message


FLOW_LEDGER_SHAPE_FIXTURE = '''
import threading

_lock = threading.Lock()
_cells = {}
_ring = []


def account(plane, prov, n):
    """The utils/flows.py shape: ONE short module-lock hold per call —
    bump the cell and append the ring tuple, nothing else inside."""
    with _lock:
        _cells[(plane, prov)] = _cells.get((plane, prov), 0) + n
        _ring.append((plane, prov, n))


def snapshot():
    with _lock:
        cells = dict(_cells)
    # derived math (efficiency rollups) happens OUTSIDE the lock
    return {"total": sum(cells.values())}
'''


def test_lockorder_flow_ledger_shape_is_clean(fakepkg):
    """The flow ledger's lock model (one module-level Lock, every
    account()/snapshot() a single non-nesting hold, rollup math outside)
    must analyze clean — the named baseline for the hot-tagged
    utils/flows.py accounting path."""
    (fakepkg / "flows.py").write_text(FLOW_LEDGER_SHAPE_FIXTURE)
    res = lockorder.run(fakepkg)
    assert res.findings == [], [f.message for f in res.findings]


def test_lockorder_catches_a_flow_ledger_reentry_regression(fakepkg):
    """The regression the clean shape guards against: a rollup helper
    that re-acquires the ledger lock from inside account() — a plain
    Lock, so the first piece write would deadlock the daemon."""
    (fakepkg / "flows_bad.py").write_text(
        '''
import threading

_lock = threading.Lock()
_cells = {}


def account(plane, prov, n):
    with _lock:
        _cells[(plane, prov)] = _cells.get((plane, prov), 0) + n
        _efficiency()  # rollup under the hold: re-enters below


def _efficiency():
    with _lock:
        return sum(_cells.values())
'''
    )
    res = lockorder.run(fakepkg)
    assert any(f.key.startswith("self:") for f in res.findings), [
        f.message for f in res.findings
    ]
