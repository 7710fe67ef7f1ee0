"""Model fit loops — the real implementation of the reference's training
stubs (reference trainer/training/training.go:60-98; intended flow per its
comments: load from storage → preprocess → train → upload model to manager).

Throughput design (north star: 1B records in <10 min on v5e-8):
- whole-epoch `lax.scan` over device-resident minibatches — one XLA call
  per epoch, zero host↔device traffic inside the loop;
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


def _split_eval(n: int, eval_fraction: float, seed: int) -> tuple[np.ndarray, np.ndarray]:
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n)
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


def make_epoch_fn(
    loss_fn: Callable[[Any, Any], jax.Array],
    optimizer: optax.GradientTransformation,
):
    """Build a jitted whole-epoch function: scan over [steps, batch, ...]
    stacked minibatches, donating the carried state. The function takes
    its name from the loss's (``mlp_loss`` -> ``mlp_epoch``), so that a
    trace's host events (``PjitFunction(mlp_epoch)``), the XLA module
    and the scope of the step's ops say which leg they belong to."""
    name = getattr(loss_fn, "__name__", "loss").removesuffix("_loss") + "_epoch"

    def epoch(params, opt_state, batches):
        def body(carry, batch):
            with jax.named_scope(name):
                params, opt_state = carry
                loss, grads = jax.value_and_grad(loss_fn)(params, batch)
                updates, opt_state = optimizer.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
            return (params, opt_state), loss

        (params, opt_state), losses = jax.lax.scan(body, (params, opt_state), batches)
        return params, opt_state, losses.mean()

    epoch.__name__ = epoch.__qualname__ = name
    return jax.jit(epoch, donate_argnums=(0, 1))


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
    steps, used, batch = _batch_steps(len(train_idx), cfg.batch_size)

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
            # per-epoch rng: a resumed run replays the exact shuffle schedule
            rng = np.random.default_rng(cfg.seed + 1 + epoch)
            with PH_MLP.gather:
                order = train_idx[rng.permutation(len(train_idx))][:used]
                xb = features[order].reshape(steps, batch, f)
                yb = labels[order].reshape(steps, batch)
            with PH_MLP.feed:
                xb, yb = _shard_arrays(mesh, xb, yb)
            with PH_MLP.epoch_dispatch:
                params, opt_state, mean_loss = epoch_fn(params, opt_state, (xb, yb))
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
    pred = np.asarray(jit_once(mlp_mod.score_parents)(params, jnp.asarray(features)))
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

    steps, used, batch = _batch_steps(len(train_idx), cfg.batch_size)
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
            with PH_GNN.gather:
                order = train_idx[rng.permutation(len(train_idx))][:used]
                sb = graph.edge_src[order].reshape(steps, batch)
                db = graph.edge_dst[order].reshape(steps, batch)
                yb = graph.edge_rtt_log_ms[order].reshape(steps, batch)
            with PH_GNN.feed:
                batches = (jnp.asarray(sb), jnp.asarray(db), jnp.asarray(yb))
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

    steps, used, batch = _batch_steps(len(train_idx), cfg.batch_size)
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
        with PH_GRU.gather:
            order = train_idx[rng.permutation(len(train_idx))][:used]
            xb = sequences[order].reshape(steps, batch, t, f)
            yb = labels[order].reshape(steps, batch)
            lb = lengths[order].reshape(steps, batch)
        with PH_GRU.feed:
            xb, yb, lb = _shard_arrays(mesh, xb, yb, lb)
        with PH_GRU.epoch_dispatch:
            params, opt_state, mean_loss = epoch_fn(params, opt_state, (xb, yb, lb))
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
