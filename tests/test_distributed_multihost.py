"""Two-PROCESS jax.distributed bring-up (the multi-host story run for
real, not mocked): each process owns 2 virtual CPU devices, the global
mesh spans 4, and the production MLP train step runs dp-sharded across
the process boundary with its gradient all-reduce riding the
cross-process collective backend (Gloo on CPU; ICI/DCN on TPU slices —
SURVEY §5.8, parallel/distributed.py)."""

import socket
import subprocess
import sys

_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
sys.path.insert(0, "@REPO@")
import jax
from dragonfly2_tpu.parallel.distributed import ensure_initialized
assert ensure_initialized(
    coordinator_address="@COORD@", num_processes=2, process_id=int(sys.argv[1])
), "distributed runtime must come up"
assert jax.device_count() == 4 and jax.local_device_count() == 2

import numpy as np
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P
from dragonfly2_tpu.models import mlp as mlp_mod
from dragonfly2_tpu.parallel.mesh import make_mesh
from dragonfly2_tpu.schema.features import MLP_FEATURE_DIM
from dragonfly2_tpu.schema.synth import make_pair_tensors

mesh = make_mesh(jax.devices(), dp=4)
batch = 64  # global; 16 rows per device, 32 per process
x, y = make_pair_tensors(batch, seed=0)  # same data in both processes
params = mlp_mod.init_mlp(jax.random.PRNGKey(0), [MLP_FEATURE_DIM, 32, 1])
optimizer = optax.adamw(1e-3)
opt_state = optimizer.init(params)

xs = NamedSharding(mesh, P("dp", None))
ys = NamedSharding(mesh, P("dp"))
xb = jax.make_array_from_callback((batch, MLP_FEATURE_DIM), xs, lambda i: np.asarray(x)[i])
yb = jax.make_array_from_callback((batch,), ys, lambda i: np.asarray(y)[i])

@jax.jit
def step(params, opt_state, xb, yb):
    def loss_fn(p):
        return jnp.mean((mlp_mod.score_parents(p, xb) - yb) ** 2)
    loss, grads = jax.value_and_grad(loss_fn)(params)
    updates, opt_state = optimizer.update(grads, opt_state, params)
    return optax.apply_updates(params, updates), opt_state, loss

for _ in range(3):
    params, opt_state, loss = step(params, opt_state, xb, yb)
print("LOSS", sys.argv[1], f"{float(jax.block_until_ready(loss)):.8f}", flush=True)
"""



def _run_workers(template: str, n: int = 2, timeout: float = 300.0) -> list[str]:
    """Spawn ``n`` coordinated worker processes from a code template
    (@REPO@/@COORD@ substituted), assert all exit 0, return stdouts."""
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    repo = str(__import__("pathlib").Path(__file__).resolve().parents[1])
    code = template.replace("@REPO@", repo).replace("@COORD@", f"127.0.0.1:{port}")
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", code, str(i)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for i in range(n)
    ]
    outs = [p.communicate(timeout=timeout)[0] for p in procs]
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {i} failed:\n{out[-2000:]}"
    return outs


def test_two_process_dp_train_step():
    outs = _run_workers(_WORKER)
    losses = {}
    for out in outs:
        for line in out.splitlines():
            if line.startswith("LOSS"):
                _, pid, val = line.split()
                losses[pid] = float(val)
    # both processes computed the SAME loss: the all-reduce really
    # spanned the process boundary (divergence would mean local-only
    # gradients)
    assert set(losses) == {"0", "1"}
    assert losses["0"] == losses["1"]


_FED_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
sys.path.insert(0, "@REPO@")
import jax
from dragonfly2_tpu.parallel.distributed import ensure_initialized
pid = int(sys.argv[1])
assert ensure_initialized(
    coordinator_address="@COORD@", num_processes=2, process_id=pid
)
import numpy as np
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P
from dragonfly2_tpu.parallel.fedavg import fedavg_psum

# each PROCESS holds one federation member's locally-fit params: the
# fed axis spans the process boundary (the DCN analog)
mesh = Mesh(np.array(jax.devices()), ("fed",))
from jax.sharding import NamedSharding

# global [2, 2] member-params array, row i owned by process i (each
# callback only materializes the LOCAL row — the global view is sharded
# over the fed axis, which spans the process boundary)
w_global = np.stack([np.full((2,), 10.0 * (i + 1), np.float32) for i in range(2)])
n_global = np.array([100.0, 200.0], np.float32)
ws = jax.make_array_from_callback((2, 2), NamedSharding(mesh, P("fed", None)),
                                  lambda idx: w_global[idx])
ns = jax.make_array_from_callback((2,), NamedSharding(mesh, P("fed")),
                                  lambda idx: n_global[idx])

def fed(p, n):
    return fedavg_psum({"w": p}, n[0], axis_name="fed")["w"]

merged = shard_map(
    fed, mesh=mesh, in_specs=(P("fed", None), P("fed")), out_specs=P("fed", None)
)(ws, ns)
jax.block_until_ready(merged)
# only the LOCAL shard is addressable in a multiprocess array — each
# process prints ITS row of the merged result
local = np.asarray(merged.addressable_shards[0].data)[0]
# example-weighted average: (10*100 + 20*200) / 300 = 16.666…
print("FED", pid, f"{local[0]:.6f}", f"{local[1]:.6f}", flush=True)
"""


def test_two_process_fedavg_over_dcn_analog():
    """Federated merge ACROSS processes: each process contributes its
    locally-fit member params; the example-weighted FedAvg psum rides
    the cross-process collective (DCN on real multi-slice TPU)."""
    outs = _run_workers(_FED_WORKER)
    vals = {}
    for out in outs:
        for line in out.splitlines():
            if line.startswith("FED"):
                _, pid, a, b = line.split()
                vals[pid] = (float(a), float(b))
    assert set(vals) == {"0", "1"}
    want = (10.0 * 100 + 20.0 * 200) / 300
    for pid, (a, b) in vals.items():
        assert abs(a - want) < 1e-4 and abs(b - want) < 1e-4
    # both processes hold the SAME merged model
    assert vals["0"] == vals["1"]
