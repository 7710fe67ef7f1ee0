"""Model fit loops — the real implementation of the reference's training
stubs (reference trainer/training/training.go:60-98; intended flow per its
comments: load from storage → preprocess → train → upload model to manager).

Throughput design (north star: 1B records in <10 min on v5e-8):
- a device loop over device-resident minibatches, zero host↔device
  traffic inside it, an epoch fed and run in bounded slices (below) so that
  another tenant of the process is never kept from the interpreter or
  from the device's queue for longer than one;
- bfloat16 matmuls with float32 accumulation (models.*);
- data parallelism by sharding the batch dim over the mesh's `dp` axis
  with NamedSharding and letting XLA insert the gradient all-reduce;
- optional tensor parallelism of hidden dims over `mp`
  (parallel.sharding.mlp_param_spec).
"""

# dfanalyze: device-hot — every fit loop here dispatches jitted epochs;
# per-call jit wrappers or implicit host feeds cost a compile/transfer
# per fit

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable

import numpy as np

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from dragonfly2_tpu.models import gnn as gnn_mod
from dragonfly2_tpu.models import gru as gru_mod
from dragonfly2_tpu.models import mlp as mlp_mod
from dragonfly2_tpu.trainer.metrics import PH_GNN, PH_GRU, PH_MLP
from dragonfly2_tpu.utils import faults
from dragonfly2_tpu.utils.jitcache import jit_once

# fault point: fires once per fit epoch (the checkpoint granularity) —
# an ``abort`` rule here is the crash drill for checkpoint/resume, a
# ``delay`` rule models a stalling device link
FP_FIT_STEP = faults.point("trainer.fit_step")


@dataclass
class FitConfig:
    hidden_dims: tuple[int, ...] = (128, 128)
    batch_size: int = 8192
    epochs: int = 3
    learning_rate: float = 3e-3
    weight_decay: float = 1e-4
    warmup_fraction: float = 0.1
    eval_fraction: float = 0.1
    seed: int = 0
    compute_dtype: Any = jnp.bfloat16
    # elastic restart: snapshot (params, opt_state) every N epochs here
    # and resume from the latest snapshot (trainer.checkpoint)
    checkpoint_dir: str | None = None
    checkpoint_every: int = 1


@dataclass
class FitResult:
    params: Any
    metrics: dict[str, float]
    history: list[float] = field(default_factory=list)  # per-epoch mean loss


def _optimizer(cfg: FitConfig, total_steps: int) -> optax.GradientTransformation:
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=cfg.learning_rate,
        warmup_steps=max(1, int(total_steps * cfg.warmup_fraction)),
        decay_steps=max(2, total_steps),
    )
    return optax.adamw(schedule, weight_decay=cfg.weight_decay)


def _permutation(rng: np.random.Generator, n: int) -> np.ndarray:
    """``rng.permutation(n)``, element for element, with the identity it
    shuffles filled a slice at a time: numpy's own ``arange`` writes all
    of it under the interpreter lock (0.12 s at 55M pairs; the shuffle
    itself runs without the lock)."""
    perm = np.empty(n, np.int64)
    ramp = np.arange(min(n, max(FEED_SLICE_BYTES // perm.itemsize, 1)), dtype=np.int64)
    for lo in range(0, n, max(len(ramp), 1)):
        np.add(ramp[: n - lo], lo, out=perm[lo : lo + len(ramp)])
    rng.shuffle(perm)
    return perm


def _split_eval(n: int, eval_fraction: float, seed: int) -> tuple[np.ndarray, np.ndarray]:
    rng = np.random.default_rng(seed)
    perm = _permutation(rng, n)
    n_eval = int(n * eval_fraction)
    return perm[n_eval:], perm[:n_eval]


# eval forwards ride the shared memoized jit (utils.jitcache.jit_once);
# this local cache only keys the (mesh, axis)-specific sharded forward
_jit_cache: dict = {}


def _shard_arrays(mesh, *arrays, axis: str = "dp"):
    if mesh is None:
        # explicit H2D at the boundary: feeding numpy straight into the
        # jitted epoch is an implicit per-epoch transfer the jit witness
        # (rightly) flags; the cost is identical, the site is visible
        return tuple(jnp.asarray(a) for a in arrays)
    if arrays and arrays[0].shape[1] % mesh.shape[axis]:
        # _batch_steps clamps the batch to tiny shards, and a clamped
        # batch rarely divides the dp axis — feed replicated rather
        # than fail the fit (the auto-mesh default must be safe for
        # every dataset size; one small fit doesn't need parallelism)
        return tuple(jnp.asarray(a) for a in arrays)
    s = NamedSharding(mesh, P(None, axis))  # [steps, batch, ...] — batch dim sharded
    return tuple(jax.device_put(a, s) for a in arrays)


def _batch_steps(n: int, batch: int) -> tuple[int, int, int]:
    """→ (steps, rows_used, batch) with batch clamped to the training-set
    size. Shared by every fit loop so small per-host datasets and the
    empty case behave identically everywhere."""
    if n <= 0:
        raise ValueError("no training examples (empty dataset after eval split)")
    batch = min(batch, n)
    steps = max(1, n // batch)
    return steps, steps * batch, batch


# Bounded slices. A trainer may share its process, and so its interpreter
# and its chip, with a scheduler that answers peers inside a deadline
# (dragonfly2_tpu.colocated). No call of a fit may then hold either for
# long: an epoch's arrays are gathered, put on the chip and freed on the
# host a slice at a time, each put landing while the next slice is
# gathered and waited for before a third is handed over, and the epoch
# runs as one dispatch a slice, each waited for before the next is
# enqueued (the device's queue is first in, first out: a served forward
# enqueued behind a whole epoch waits for all of it, however the epoch is
# cut). A slice is as many steps as fit FEED_SLICE_BYTES, and at most
# EPOCH_SLICE_STEPS, the epoch's steps spread evenly over the slices that
# takes; every slice of an epoch has the same shape (the last is padded,
# by fewer steps than there are slices, with rows no step reads), so a
# leg compiles one executable whatever its step count. Every slice stays
# on the chip until the fit drops the epoch, and the steps, their order
# and their batches are those of one loop over the whole epoch.
FEED_SLICE_BYTES = 64 << 20  # one put: 10-17 ms of transfer on a v5e host
EPOCH_SLICE_STEPS = 512  # one dispatch: 20-30 ms of the chip at these models' steps


class _Epoch(tuple):
    """An epoch on the chip: per column, the list of its device slices
    ``[k, batch, ...]``; ``steps`` steps in all, so the last slice may
    hold fewer than ``k`` (its other rows are padding)."""

    steps: int


def _slice_steps(steps: int, step_bytes: int) -> int:
    """How many steps a slice of an epoch holds: the fewest slices that
    keep both bounds, all of one length (so the last is padded by fewer
    steps than there are slices)."""
    most = max(1, min(EPOCH_SLICE_STEPS, FEED_SLICE_BYTES // max(step_bytes, 1), steps))
    slices = -(-steps // most)
    return -(-steps // max(slices, 1))


def _slice_rows(a: np.ndarray) -> int:
    """How many rows of ``a`` make a slice of ``FEED_SLICE_BYTES``."""
    return max(FEED_SLICE_BYTES // max(a.nbytes // max(len(a), 1), 1), 1)


def _gather_slices(index: np.ndarray, rng: np.random.Generator, steps: int, batch: int, *columns: np.ndarray):
    """The epoch's ``[steps, batch, ...]`` arrays as host slices of
    bounded bytes, ``(column slice, ...)`` in step order, each gathered
    when it is asked for (a generator; the shuffle ``rng.permutation``
    of ``index`` on the first): row ``i`` of the epoch is
    ``column[index[perm[i]]]``. Each slice is an allocation of its own,
    so the host never holds an epoch whole, to free in one call (and no
    fancy index is longer than a slice: numpy checks every index under
    the interpreter lock before it copies without it, 0.11 s for an
    epoch's 49.5M); all have ``k`` steps, the last zero past the epoch's
    end."""
    k = _slice_steps(
        steps, batch * sum(c.dtype.itemsize * int(np.prod(c.shape[1:], dtype=np.int64)) for c in columns)
    )
    perm = _permutation(rng, len(index))

    def gathered(lo: int) -> tuple:
        rows = index[perm[lo * batch : min(lo + k, steps) * batch]]
        part = []
        for c in columns:
            a = c[rows]
            if len(rows) < k * batch:
                a = np.concatenate([a, np.zeros((k * batch - len(rows), *c.shape[1:]), c.dtype)])
            part.append(a.reshape(k, batch, *c.shape[1:]))
        return tuple(part)

    for lo in range(0, steps, k):
        yield gathered(lo)  # under no name here: it is the taker's to drop


def _feed_slices(mesh, host, steps: int, phases) -> _Epoch:
    """Put an epoch's host slices (``_gather_slices``) on the chip, one
    put a slice. A put is asynchronous: it lands while the next slice is
    gathered, and is waited for before the slice after that is handed
    over, so the transfer queue never holds more than two (a served
    batch's put waits behind 128 MiB at most) and the host no more than
    the two being gathered and sent. Returns the epoch of ``steps``
    steps as ``make_epoch_fn`` takes it, every slice on the chip.
    ``phases`` is the leg's: ``gather`` times the loop, ``feed_slice``
    is fed what each slice's put and wait took of it, ``feed`` times
    the wait for what had not landed when the gather ended."""
    fed: list = []
    with phases.gather:
        for part in host:
            t0 = time.perf_counter()
            fed.append(_shard_arrays(mesh, *part))
            del part  # the transfer holds it until it is through
            jax.block_until_ready(fed[-2:-1])
            phases.feed_slice.observe(time.perf_counter() - t0)
    with phases.feed:
        jax.block_until_ready(fed)
    epoch = _Epoch(list(column) for column in zip(*fed))
    epoch.steps = steps
    return epoch


def release_in_pieces(owned: list) -> int:
    """Free the host arrays in ``owned`` (emptied) a slice at a time;
    returns how many of them went that way. One ``free`` of the 4.6 GB a
    resident fit was handed unmaps them under the interpreter lock (0.4 s
    on a v5e host); here each array is shrunk from its tail by
    ``ndarray.resize``, a ``realloc`` that gives the tail's pages back in
    place, ``FEED_SLICE_BYTES`` a call, and as long again is slept after
    each (a run of such calls back to back would keep the interpreter
    nine tenths of the time, each for too short to see). An array that
    anyone else still references, or that does not own its memory, is
    refused by ``resize`` and left whole to its last holder. (A thread that walks
    ``sys._current_frames()``, as the sampling profiler does, holds this
    frame for an instant and is refused the same way: hence the second
    and third try.) The caller keeps no other name for what it hands in."""
    released = 0
    while owned:
        a = owned.pop()
        rows = _slice_rows(a)
        keep, tries = len(a) - rows, 3
        while keep > 0 and tries:
            try:
                t0 = time.perf_counter()
                a.resize((keep, *a.shape[1:]), refcheck=True)
                keep, tries = keep - rows, 3
                time.sleep(time.perf_counter() - t0)
            except ValueError:
                tries -= 1
                time.sleep(0.001)
        released += keep <= 0
    return released


def make_epoch_fn(
    loss_fn: Callable[[Any, Any], jax.Array],
    optimizer: optax.GradientTransformation,
):
    """Build the epoch function ``epoch(params, opt_state, batches)`` ->
    ``(params, opt_state, mean loss)`` over the ``_Epoch`` that
    ``_feed_slices`` returns. Each slice is one dispatch of a jitted loop
    over its steps (as many as the epoch has left: a traced count, so
    the padded last slice runs on the same executable), the carried
    state donated from one to the next and the slice waited for before
    the next is enqueued. The jitted slice takes its name from the
    loss's (``mlp_loss`` -> ``mlp_epoch``), so that a trace's host events
    (``PjitFunction(mlp_epoch)``), the XLA module and the scope of the
    step's ops say which leg they belong to; a leg's ``epoch_slice``
    phase is fed each slice's wall."""
    leg = getattr(loss_fn, "__name__", "loss").removesuffix("_loss")
    name = leg + "_epoch"
    slice_phase = {"mlp": PH_MLP, "gnn": PH_GNN, "gru": PH_GRU}.get(leg)

    def epoch_slice(params, opt_state, batches, steps):
        def body(i, carry):
            with jax.named_scope(name):
                params, opt_state, loss_sum = carry
                batch = jax.tree_util.tree_map(lambda a: a[i], batches)
                loss, grads = jax.value_and_grad(loss_fn)(params, batch)
                updates, opt_state = optimizer.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
            return params, opt_state, loss_sum + loss

        return jax.lax.fori_loop(0, steps, body, (params, opt_state, jnp.zeros((), jnp.float32)))

    epoch_slice.__name__ = epoch_slice.__qualname__ = name
    run_slice = jax.jit(epoch_slice, donate_argnums=(0, 1))

    def epoch(params, opt_state, batches):
        left, loss_sum = batches.steps, 0.0
        for part in zip(*batches):
            t0 = time.perf_counter()
            steps = min(part[0].shape[0], left)
            params, opt_state, part_sum = run_slice(params, opt_state, part, steps)
            loss_sum += float(part_sum)  # the wait: nothing is queued behind a running slice
            left -= steps
            if slice_phase is not None:
                slice_phase.epoch_slice.observe(time.perf_counter() - t0)
        return params, opt_state, loss_sum / batches.steps

    def lower(params, opt_state, batches):
        """The lowering of the epoch's slice, for a reader of its HLO."""
        return run_slice.lower(params, opt_state, tuple(column[0] for column in batches), batches.steps)

    epoch.__name__ = epoch.__qualname__ = name
    epoch.lower = lower
    return epoch


# ---------------------------------------------------------------------------
# MLP parent scorer  (reference trainMLP stub, training.go:92-98)
# ---------------------------------------------------------------------------


def train_mlp(
    features: np.ndarray,
    labels: np.ndarray,
    mesh=None,
    config: FitConfig | None = None,
) -> FitResult:
    """Fit the pair scorer: features [N, F] → label log piece cost [N].

    Evaluation metrics are MSE/MAE, matching what the manager stores with
    an MLP model upload (reference manager_server_v1.go:847-851).
    """
    cfg = config or FitConfig()
    n, f = features.shape
    with PH_MLP.split:
        train_idx, eval_idx = _split_eval(n, cfg.eval_fraction, cfg.seed)
    steps, _, batch = _batch_steps(len(train_idx), cfg.batch_size)

    key = jax.random.PRNGKey(cfg.seed)
    params = mlp_mod.init_mlp(key, [f, *cfg.hidden_dims, 1])
    # warm-start the output bias at the label mean — the regression head
    # starts unbiased instead of spending its first epochs drifting there
    params["layers"][-1]["b"] = jnp.full((1,), float(labels.mean()))
    if mesh is not None:
        from dragonfly2_tpu.parallel.sharding import replicate

        params = replicate(mesh, params)

    total_steps = steps * cfg.epochs
    optimizer = _optimizer(cfg, total_steps)
    opt_state = optimizer.init(params)

    def mlp_loss(p, batch):
        x, y = batch
        pred = mlp_mod.score_parents(p, x)
        return jnp.mean((pred - y) ** 2)

    epoch_fn = make_epoch_fn(mlp_loss, optimizer)

    ckpt, start_epoch = _open_checkpoint(cfg)
    try:
        if ckpt is not None and start_epoch > 0:
            restored = ckpt.restore_latest({"params": params, "opt_state": opt_state})
            if restored is not None:
                _, state = restored
                params, opt_state = state["params"], state["opt_state"]

        history: list[float] = []
        for epoch in range(start_epoch, cfg.epochs):
            FP_FIT_STEP()
            batches = None  # the chip holds one epoch: the last goes before the next is fed
            # per-epoch rng: a resumed run replays the exact shuffle schedule
            rng = np.random.default_rng(cfg.seed + 1 + epoch)
            host = _gather_slices(train_idx, rng, steps, batch, features, labels)
            batches = _feed_slices(mesh, host, steps, PH_MLP)
            with PH_MLP.epoch_dispatch:
                params, opt_state, mean_loss = epoch_fn(params, opt_state, batches)
            with PH_MLP.epoch_wait:
                history.append(float(mean_loss))
            _maybe_save_tree(ckpt, cfg, epoch, {"params": params, "opt_state": opt_state})

        metrics = {}
        if len(eval_idx):
            with PH_MLP.holdout:
                metrics = evaluate_mlp(params, features[eval_idx], labels[eval_idx])
        _finish_checkpoint(ckpt)
        ckpt = None
        return FitResult(params=params, metrics=metrics, history=history)
    finally:
        if ckpt is not None:
            ckpt.close()


def _open_checkpoint(cfg: FitConfig):
    """→ (FitCheckpointer | None, start_epoch). Epoch ``k`` snapshots are
    taken *after* epoch k runs, so resume starts at latest+1."""
    if not cfg.checkpoint_dir:
        return None, 0
    from dragonfly2_tpu.trainer.checkpoint import FitCheckpointer

    ckpt = FitCheckpointer(cfg.checkpoint_dir)
    latest = ckpt.latest_epoch()
    return ckpt, (latest + 1 if latest is not None else 0)


def _maybe_save_tree(ckpt, cfg: FitConfig, epoch: int, state) -> None:
    if ckpt is not None and (epoch + 1) % max(cfg.checkpoint_every, 1) == 0:
        ckpt.save(epoch, state)


def _finish_checkpoint(ckpt) -> None:
    """Successful completion: drop the run's snapshots (the next round
    must train fresh, not resume into zero epochs) and release the
    manager's background resources."""
    if ckpt is not None:
        ckpt.clear()
        ckpt.close()


def evaluate_mlp(params, features: np.ndarray, labels: np.ndarray) -> dict[str, float]:
    """The holdout's error. The holdout goes to the chip whole, as it
    always has (beside the epoch, which the fit still holds: the peak of
    a resident fit's device memory is here), but in bounded slices like
    the epoch: one put a slice, each waited for, then one forward a
    slice, each read back before the next is enqueued."""
    forward = jit_once(mlp_mod.score_parents)
    rows = _slice_rows(features)
    on_chip = [
        jax.block_until_ready(jnp.asarray(features[lo : lo + rows]))
        for lo in range(0, len(features), rows)
    ]
    pred = np.concatenate([np.asarray(forward(params, x)) for x in on_chip])
    err = pred - labels
    return {"mse": float(np.mean(err**2)), "mae": float(np.mean(np.abs(err)))}


# ---------------------------------------------------------------------------
# GraphSAGE edge-RTT  (reference trainGNN stub, training.go:82-88)
# ---------------------------------------------------------------------------


@dataclass
class GNNFitConfig(FitConfig):
    hidden_dims: tuple[int, ...] = (64, 64)
    batch_size: int = 2048  # edges per step
    epochs: int = 60  # probe graphs are small; the embedding table needs steps
    learning_rate: float = 2e-2


def _init_gnn(graph, cfg: GNNFitConfig):
    """Shared GraphSAGE init for the single-device and sharded fits: same
    seed, same embedding table, same head-bias warm start — the sharded
    path's semantics must match train_gnn's."""
    if len(graph.edge_src) == 0:
        raise ValueError("probe graph has no edges to train on")
    key = jax.random.PRNGKey(cfg.seed)
    params = gnn_mod.init_graphsage(
        key, graph.node_features.shape[1], cfg.hidden_dims, num_nodes=graph.num_nodes
    )
    params["head"]["layers"][-1]["b"] = jnp.full(
        (1,), float(graph.edge_rtt_log_ms.mean())
    )
    return params


def train_gnn(
    graph,
    mesh=None,
    config: GNNFitConfig | None = None,
) -> FitResult:
    """Fit GraphSAGE on a schema.features.ProbeGraph: predict per-edge
    log-RTT from host embeddings.

    Evaluation reports MSE/MAE plus precision/recall/f1 on the derived
    binary task "edge is faster than the median RTT" — the tuple the
    manager stores with a GNN upload (reference manager_server_v1.go:
    CreateModel GNN evaluation fields).
    """
    cfg = config or GNNFitConfig()
    e = len(graph.edge_src)
    with PH_GNN.split:
        train_idx, eval_idx = _split_eval(e, cfg.eval_fraction, cfg.seed)
    params = _init_gnn(graph, cfg)
    if mesh is not None:
        from dragonfly2_tpu.parallel.sharding import replicate

        params = replicate(mesh, params)

    node_features = jnp.asarray(graph.node_features)
    neighbors = jnp.asarray(graph.neighbors)
    neighbor_mask = jnp.asarray(graph.neighbor_mask)

    steps, _, batch = _batch_steps(len(train_idx), cfg.batch_size)
    optimizer = _optimizer(cfg, steps * cfg.epochs)
    opt_state = optimizer.init(params)

    def gnn_loss(p, b):
        src, dst, y = b
        pred = gnn_mod.forward_edge_rtt(p, node_features, neighbors, neighbor_mask, src, dst)
        return jnp.mean((pred - y) ** 2)

    epoch_fn = make_epoch_fn(gnn_loss, optimizer)

    ckpt, start_epoch = _open_checkpoint(cfg)
    try:
        if ckpt is not None and start_epoch > 0:
            restored = ckpt.restore_latest({"params": params, "opt_state": opt_state})
            if restored is not None:
                _, state = restored
                params, opt_state = state["params"], state["opt_state"]

        history: list[float] = []
        for epoch in range(start_epoch, cfg.epochs):
            rng = np.random.default_rng(cfg.seed + 1 + epoch)
            host = _gather_slices(
                train_idx, rng, steps, batch, graph.edge_src, graph.edge_dst, graph.edge_rtt_log_ms
            )
            # never sharded: the edge batches are replicated beside the graph
            batches = _feed_slices(None, host, steps, PH_GNN)
            with PH_GNN.epoch_dispatch:
                params, opt_state, mean_loss = epoch_fn(params, opt_state, batches)
            with PH_GNN.epoch_wait:
                history.append(float(mean_loss))
            _maybe_save_tree(ckpt, cfg, epoch, {"params": params, "opt_state": opt_state})

        metrics: dict[str, float] = {}
        if len(eval_idx):
            with PH_GNN.holdout:
                metrics = evaluate_gnn(params, graph, eval_idx)
        _finish_checkpoint(ckpt)
        ckpt = None
        return FitResult(params=params, metrics=metrics, history=history)
    finally:
        if ckpt is not None:
            ckpt.close()


def train_gnn_sharded(
    graph,
    mesh,
    axis: str = "gp",
    config: GNNFitConfig | None = None,
) -> FitResult:
    """Graph-parallel GraphSAGE fit: node feature/embedding tables and
    edge blocks row-sharded over ``mesh[axis]``, neighbor and endpoint
    gathers riding the ICI ring (models.gnn_sharded). Per-device HBM is
    O(N/devices) — the path for probe graphs too large for one chip;
    semantics (loss, params) match train_gnn's full-batch limit.
    """
    from dragonfly2_tpu.models import gnn_sharded as gs

    cfg = config or GNNFitConfig()
    e = len(graph.edge_src)
    shards = mesh.shape[axis]
    _, eval_idx = _split_eval(e, cfg.eval_fraction, cfg.seed)
    params = _init_gnn(graph, cfg)

    nf, nbrs, mask, src_all, dst_all, y_all, w_all = gs.pad_graph(graph, shards)
    # hold out the eval edges by zeroing their loss weight — shapes stay
    # static, sharding stays even
    w_all[eval_idx] = 0.0

    # node embedding table sharded over the axis; dense weights replicated
    embed = params.pop("node_embed", None)
    if embed is not None:
        embed = jnp.asarray(gs.pad_rows(np.asarray(embed), shards))
    from dragonfly2_tpu.parallel.sharding import replicate

    dense = replicate(mesh, params)
    if embed is not None:
        embed = jax.device_put(embed, NamedSharding(mesh, P(axis, None)))
    nf_d, nbrs_d, mask_d, src_d, dst_d, y_d, w_d = gs.shard_graph_arrays(
        mesh, axis, nf, nbrs, mask, src_all, dst_all, y_all, w_all
    )

    loss_fn = gs.make_sharded_loss(mesh, axis)
    optimizer = _optimizer(cfg, cfg.epochs)
    opt_state = optimizer.init((dense, embed))

    @jax.jit
    def step(dense, embed, opt_state):
        def wrapped(de):
            d, em = de
            return loss_fn(d, em, nf_d, nbrs_d, mask_d, src_d, dst_d, y_d, w_d)

        loss, grads = jax.value_and_grad(wrapped)((dense, embed))
        updates, opt_state2 = optimizer.update(grads, opt_state, (dense, embed))
        dense2, embed2 = optax.apply_updates((dense, embed), updates)
        return dense2, embed2, opt_state2, loss

    ckpt, start_epoch = _open_checkpoint(cfg)
    if ckpt is not None and start_epoch > 0:
        restored = ckpt.restore_latest(
            {"dense": dense, "embed": embed, "opt_state": opt_state}
        )
        if restored is not None:
            _, state = restored
            dense, embed, opt_state = state["dense"], state["embed"], state["opt_state"]

    history: list[float] = []
    for epoch in range(start_epoch, cfg.epochs):
        dense, embed, opt_state, loss = step(dense, embed, opt_state)
        history.append(float(loss))
        _maybe_save_tree(
            ckpt, cfg, epoch, {"dense": dense, "embed": embed, "opt_state": opt_state}
        )
    _finish_checkpoint(ckpt)

    metrics: dict[str, float] = {}
    if len(eval_idx):
        # eval through the sharded forward too — the whole point of this
        # path is that the graph doesn't fit one chip. The jitted
        # forward is memoized per (mesh, axis): make_sharded_forward
        # returns a fresh closure each call, and jitting that fresh
        # closure per fit recompiled an identical executable
        fwd_key = ("sharded_fwd", mesh, axis)
        fwd_jit = _jit_cache.get(fwd_key)
        if fwd_jit is None:
            fwd_jit = _jit_cache[fwd_key] = jax.jit(gs.make_sharded_forward(mesh, axis))
        # index on device, transfer only the eval rows — pulling the
        # whole padded prediction host-side to slice it was a full-array
        # D2H for a fraction of the rows
        pred = np.asarray(
            fwd_jit(dense, embed, nf_d, nbrs_d, mask_d, src_d, dst_d)[:e][eval_idx]
        )
        metrics = _edge_metrics(
            pred, graph.edge_rtt_log_ms[eval_idx], float(np.median(graph.edge_rtt_log_ms))
        )

    out_params = jax.tree_util.tree_map(np.asarray, dense)
    if embed is not None:
        # slice the padding off on device; transfer only the real rows
        out_params["node_embed"] = np.asarray(embed[: graph.num_nodes])
    return FitResult(params=out_params, metrics=metrics, history=history)


def _edge_metrics(pred: np.ndarray, y: np.ndarray, thresh: float) -> dict[str, float]:
    """MSE/MAE + precision/recall/f1 on "edge faster than median RTT" —
    the evaluation tuple the manager stores with a GNN upload (reference
    manager_server_v1.go CreateModel GNN evaluation fields)."""
    err = pred - y
    actual_fast = y < thresh
    pred_fast = pred < thresh
    tp = float(np.sum(pred_fast & actual_fast))
    fp = float(np.sum(pred_fast & ~actual_fast))
    fn = float(np.sum(~pred_fast & actual_fast))
    precision = tp / max(tp + fp, 1.0)
    recall = tp / max(tp + fn, 1.0)
    f1 = 2 * precision * recall / max(precision + recall, 1e-9)
    return {
        "mse": float(np.mean(err**2)),
        "mae": float(np.mean(np.abs(err))),
        "precision": precision,
        "recall": recall,
        "f1": f1,
    }


def evaluate_gnn(params, graph, edge_idx: np.ndarray) -> dict[str, float]:
    pred = np.asarray(
        jit_once(gnn_mod.forward_edge_rtt)(
            params,
            jnp.asarray(graph.node_features),
            jnp.asarray(graph.neighbors),
            jnp.asarray(graph.neighbor_mask),
            jnp.asarray(graph.edge_src[edge_idx]),
            jnp.asarray(graph.edge_dst[edge_idx]),
        )
    )
    return _edge_metrics(
        pred, graph.edge_rtt_log_ms[edge_idx], float(np.median(graph.edge_rtt_log_ms))
    )


# ---------------------------------------------------------------------------
# GRU piece time-series
# ---------------------------------------------------------------------------


def train_gru(
    sequences: np.ndarray,  # [N, T, F]
    labels: np.ndarray,  # [N]
    lengths: np.ndarray | None = None,
    mesh=None,
    config: FitConfig | None = None,
) -> FitResult:
    """Fit the next-piece-cost predictor over piece history sequences."""
    cfg = config or FitConfig(hidden_dims=(64,), batch_size=256, epochs=5)
    n, t, f = sequences.shape
    with PH_GRU.split:
        train_idx, eval_idx = _split_eval(n, cfg.eval_fraction, cfg.seed)
    if lengths is None:
        lengths = np.full((n,), t, np.int32)

    key = jax.random.PRNGKey(cfg.seed)
    params = gru_mod.init_gru(key, f, cfg.hidden_dims[0])
    params["head"]["layers"][-1]["b"] = jnp.full((1,), float(labels.mean()))
    if mesh is not None:
        from dragonfly2_tpu.parallel.sharding import replicate

        params = replicate(mesh, params)

    steps, _, batch = _batch_steps(len(train_idx), cfg.batch_size)
    optimizer = _optimizer(cfg, steps * cfg.epochs)
    opt_state = optimizer.init(params)

    def gru_loss(p, b):
        x, y, ln = b
        pred = gru_mod.predict_next_cost(p, x, ln)
        return jnp.mean((pred - y) ** 2)

    epoch_fn = make_epoch_fn(gru_loss, optimizer)

    history: list[float] = []
    rng = np.random.default_rng(cfg.seed + 1)
    for _ in range(cfg.epochs):
        batches = None  # the chip holds one epoch: the last goes before the next is fed
        host = _gather_slices(train_idx, rng, steps, batch, sequences, labels, lengths)
        batches = _feed_slices(mesh, host, steps, PH_GRU)
        with PH_GRU.epoch_dispatch:
            params, opt_state, mean_loss = epoch_fn(params, opt_state, batches)
        with PH_GRU.epoch_wait:
            history.append(float(mean_loss))

    metrics: dict[str, float] = {}
    if len(eval_idx):
        with PH_GRU.holdout:
            pred = np.asarray(
                jit_once(gru_mod.predict_next_cost)(
                    params, jnp.asarray(sequences[eval_idx]), jnp.asarray(lengths[eval_idx])
                )
            )
            err = pred - labels[eval_idx]
            metrics = {"mse": float(np.mean(err**2)), "mae": float(np.mean(np.abs(err)))}
    return FitResult(params=params, metrics=metrics, history=history)
