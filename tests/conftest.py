"""Test harness: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's strategy of faking multi-node setups in-process
(reference client/daemon/peer/peertask_manager_test.go:77-290 fakes a whole
cluster with scripted mocks); we fake an 8-chip TPU slice with XLA host
devices so sharding/collective code paths compile and execute in CI.
"""

import os
import sys

# Must run before the first jax import: the suite runs on the CPU backend
# whatever the machine holds.
os.environ["JAX_PLATFORMS"] = "cpu"
# grpc's C core logs INFO lines (GOAWAY on abrupt server stops — which
# the fleet/resilience failover tests do on purpose) straight to stderr,
# where they interleave into pytest's progress lines and corrupt the
# tier-1 dot count. Errors still print.
os.environ.setdefault("GRPC_VERBOSITY", "ERROR")
flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402,F401

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Opt-in runtime lock-witness (hack/dfanalyze/witness.py): DF_LOCK_WITNESS=1
# wraps every threading.Lock/RLock *created by package code* so the tier-1
# run records real acquisition orders; the session-finish hook dumps them
# to DF_LOCK_WITNESS_OUT (default dfanalyze-witness.json) for
#   python -m hack.dfanalyze --witness-report <dump>
# to cross-check against the static lock graph. Must install before the
# package imports: module-level locks are created at import time.
def _flag_enabled(name: str) -> bool:
    # same off-values as the other DF_* flags (utils/flight.py): "0",
    # "false", "no" disable — exporting DF_LOCK_WITNESS=0 must not
    # install the witness
    return os.environ.get(name, "").lower() not in ("", "0", "false", "no")


def _witness_enabled() -> bool:
    return _flag_enabled("DF_LOCK_WITNESS")


def _jit_witness_enabled() -> bool:
    return _flag_enabled("DF_JIT_WITNESS")


if _witness_enabled():
    from hack.dfanalyze import witness as _lock_witness  # noqa: E402

    _lock_witness.install()

# Opt-in runtime jit witness (hack/dfanalyze/jitwitness.py): records
# per-function XLA compile counts, jit-wrapper construction sites, and
# implicit host→device transfer sites from package code; dumped at
# session end for
#   python -m hack.dfanalyze --jit-witness-report <dump>
# Must install before the package imports so module-level jit
# constructions are witnessed (jax itself is already imported above,
# which the witness requires).
if _jit_witness_enabled():
    from hack.dfanalyze import jitwitness as _jit_witness  # noqa: E402

    _jit_witness.install()

import pytest  # noqa: E402


def pytest_sessionfinish(session, exitstatus):
    if _witness_enabled():
        from hack.dfanalyze import witness as _w

        if _w.active():
            path = _w.dump()
            print(f"\nlock-witness: acquisition orders dumped to {path}")
    if _jit_witness_enabled():
        from hack.dfanalyze import jitwitness as _jw

        if _jw.active():
            path = _jw.dump()
            print(f"\njit-witness: compile/transfer record dumped to {path}")


@pytest.fixture
def compile_cache_off(monkeypatch):
    """Entry points turn the persistent compile cache on; called
    in-process from a test, that would point every later compile of the
    session at the checkout's cache directory."""
    monkeypatch.setattr(
        "dragonfly2_tpu.utils.jitcache.enable_compile_cache", lambda: ""
    )


@pytest.fixture(scope="session")
def mesh8():
    """An 8-device `dp×mp` mesh shared by sharding tests."""
    import jax
    from dragonfly2_tpu.parallel.mesh import make_mesh

    assert len(jax.devices()) == 8, "conftest must force 8 host devices"
    return make_mesh(dp=4, mp=2)
