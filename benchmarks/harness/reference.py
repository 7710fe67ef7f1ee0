"""The plain references ``correct`` is decided against.

Nothing here imports the program. Inputs are the benchmark's own seeded
data (records, fleet, probe edges, weights); the program's answers are
compared with what these functions compute from the same inputs in
float32. Each reference also runs in the nearest precision below the
bfloat16 the program computes in (fp8 e4m3), which is the control that
has to come out as not correct.
"""

from __future__ import annotations

import math

import ml_dtypes
import numpy as np

MLP_FEATURE_DIM = 19
MAX_LOCATION_DEPTH = 5
INF_MS = 1.0e9
NS_PER_MS = 1e6

# peer states whose holders are never offered as parents (the
# scheduler's bad-node rule, reference evaluator_base.go)
BAD_STATES = (
    "Failed", "Leave", "Pending", "ReceivedTiny", "ReceivedSmall",
    "ReceivedNormal", "ReceivedEmpty",
)


# -- the MLP ---------------------------------------------------------------


def gelu(x: np.ndarray) -> np.ndarray:
    return 0.5 * x * (1.0 + np.tanh(0.7978845608028654 * (x + 0.044715 * x**3)))


def _quant(a: np.ndarray, precision: str) -> np.ndarray:
    if precision == "float32":
        return a.astype(np.float32)
    if precision == "fp8":
        return a.astype(ml_dtypes.float8_e4m3fn).astype(np.float32)
    raise ValueError(precision)


def mlp_forward(params: dict, x: np.ndarray, precision: str = "float32") -> np.ndarray:
    """[N, F] → [N] predicted log piece cost. ``precision`` rounds each
    matmul's inputs (float32 keeps them; fp8 is the control);
    accumulation and bias are float32 either way."""
    h = np.asarray(x, np.float32)
    n = len(params["layers"])
    for i, layer in enumerate(params["layers"]):
        h = _quant(h, precision) @ _quant(layer["w"], precision) + layer["b"].astype(
            np.float32
        )
        if i != n - 1:
            h = gelu(h)
    return h[:, 0]


# -- pair features, from plain host descriptions ---------------------------


def location_affinity(a: str, b: str) -> float:
    if not a or not b:
        return 0.0
    depth = 0
    for x, y in zip(a.split("|")[:MAX_LOCATION_DEPTH], b.split("|")[:MAX_LOCATION_DEPTH]):
        if x != y:
            break
        depth += 1
    return depth / MAX_LOCATION_DEPTH


def pair_features(
    parent_host,
    child_host,
    finished_pieces: int,
    total_pieces: int,
    content_length: int,
    succeeded: bool,
    rtt_affinity: float,
    upload_count_now: "int | None" = None,
) -> np.ndarray:
    """One (child, parent) row in the schema's feature order. Hosts are
    the seeded host records; ``upload_count_now`` is the parent host's
    concurrent uploads including the edges the swarm holds."""
    h = parent_host
    cuc = h.concurrent_upload_count if upload_count_now is None else upload_count_now
    free = h.concurrent_upload_limit - cuc
    return np.array(
        [
            min(max(finished_pieces / total_pieces, 0.0), 1.0),
            (h.upload_count - h.upload_failed_count) / max(h.upload_count, 1),
            min(max(free / h.concurrent_upload_limit, 0.0), 1.0)
            if h.concurrent_upload_limit > 0
            else 0.0,
            0.0 if h.type == "normal" else 1.0,
            1.0
            if (child_host.network.idc == h.network.idc and h.network.idc != "")
            else 0.0,
            location_affinity(child_host.network.location, h.network.location),
            h.cpu.percent / 100.0,
            h.memory.used_percent / 100.0,
            math.log1p(h.network.tcp_connection_count) / 10.0,
            math.log1p(h.network.upload_tcp_connection_count) / 10.0,
            h.disk.used_percent / 100.0,
            1.0 if succeeded else 0.0,
            h.cpu.process_percent / 100.0,
            h.memory.available / max(h.memory.total, 1),
            h.disk.inodes_used_percent / 100.0,
            child_host.cpu.percent / 100.0,
            child_host.memory.used_percent / 100.0,
            math.log1p(max(content_length, 0)) / 30.0,
            rtt_affinity,
        ],
        dtype=np.float32,
    )


def record_pairs(records: list) -> "tuple[np.ndarray, np.ndarray]":
    """Download records → the (features, labels) pairs a fit trains on:
    one row per parent with a piece cost, label log1p(mean cost in ms).
    The free-upload feature follows the record schema (1 − count/limit);
    rtt_affinity is the schema's missing value, as in an upload encoded
    without a live adjacency."""
    xs, ys = [], []
    for rec in records:
        total = max(rec.task.total_piece_count, 1)
        for p in rec.parents:
            costs = [pc.cost for pc in p.pieces if pc.cost > 0]
            if not p.id or not costs:
                continue
            row = pair_features(
                p.host, rec.host, p.finished_piece_count, total,
                rec.task.content_length, p.state == "Succeeded", 0.0,
            )
            h = p.host
            row[2] = min(
                max(1.0 - h.concurrent_upload_count / max(h.concurrent_upload_limit, 1), 0.0),
                1.0,
            )
            xs.append(row)
            ys.append(math.log1p(sum(costs) / len(costs) / NS_PER_MS))
    return np.stack(xs).astype(np.float32), np.asarray(ys, np.float32)


# -- the probe graph's RTT estimate ----------------------------------------


class RttReference:
    """est RTT between two hosts: a direct probe in either direction
    wins; otherwise min over L landmarks of d(a,l)+d(l,b), where d is
    the min-plus distance after ``iters`` relaxations over the
    symmetrised edges and the landmarks are the hosts of highest degree
    (ties by first appearance). rtt_affinity = log1p(ms)/10, 0 when
    unknown."""

    def __init__(self, num_hosts: int, edges: list, landmarks: int = 8, iters: int = 3):
        order: dict[int, int] = {}
        for s, t, _ in edges:
            order.setdefault(s, len(order))
            order.setdefault(t, len(order))
        self.direct = {(s, t): rtt for s, t, rtt in edges}
        src = np.array([s for s, _, _ in edges], np.int64)
        dst = np.array([t for _, t, _ in edges], np.int64)
        ms = np.array([rtt / NS_PER_MS for _, _, rtt in edges], np.float32)
        deg = np.bincount(src, minlength=num_hosts) + np.bincount(dst, minlength=num_hosts)
        ranked = sorted(order, key=lambda h: (-int(deg[h]), order[h]))[:landmarks]
        a = np.concatenate([src, dst])
        b = np.concatenate([dst, src])
        cost = np.concatenate([ms, ms])
        D = np.full((num_hosts, len(ranked)), np.float32(INF_MS), np.float32)
        D[ranked, np.arange(len(ranked))] = 0.0
        for _ in range(iters):
            cand = cost[:, None] + D[b]
            relaxed = np.full_like(D, np.float32(INF_MS))
            np.minimum.at(relaxed, a, cand)
            D = np.minimum(D, relaxed)
        self.D = D
        self.known = set(order)

    def affinity(self, a: int, b: int) -> float:
        if a == b:
            return 0.0
        if a not in self.known or b not in self.known:
            return 0.0
        rtt = self.direct.get((a, b)) or self.direct.get((b, a))
        if rtt is not None:
            return float(np.log1p(np.float32(rtt / NS_PER_MS)) / np.float32(10.0))
        est = float(np.min(self.D[a] + self.D[b]))
        if est >= INF_MS / 2:
            return 0.0
        return float(np.log1p(np.float32(est)) / np.float32(10.0))


# -- the six filter rules, on the swarm description ------------------------


def is_bad_node(peer: dict) -> bool:
    if peer["state"] in BAD_STATES:
        return True
    costs = peer["piece_costs"]
    if len(costs) < 2:
        return False
    mean = sum(costs[:-1]) / (len(costs) - 1)
    if len(costs) < 30:
        return costs[-1] > mean * 20
    var = sum((c - mean) ** 2 for c in costs[:-1]) / (len(costs) - 1)
    return costs[-1] > mean + 3 * math.sqrt(var)


def legal_parents(task: dict, child: dict, hosts: list, uploads_now: dict) -> list:
    """Peers of ``task`` the scheduler may offer ``child``: not itself,
    not on the child's host, not a bad node, itself fed (a parent edge,
    back-to-source or succeeded — seed hosts excepted), and with a free
    upload slot."""
    out = []
    for p in task["peers"]:
        if p["id"] == child["id"] or p["host"] == child["host"]:
            continue
        if is_bad_node(p):
            continue
        h = hosts[p["host"]]
        if (
            h.type == "normal"
            and p["in_degree"] == 0
            and p["state"] not in ("BackToSource", "Succeeded")
        ):
            continue
        if h.concurrent_upload_limit - uploads_now[p["host"]] <= 0:
            continue
        out.append(p)
    return out


def rank_gap(returned: list, costs: dict, closed: bool) -> float:
    """The widest gap by which a returned parent's reference cost lies
    above the best the reference still had to offer at that position.
    ``costs`` maps every legal parent id to its reference cost;
    ``closed`` says the scheduler saw every peer of the task (a task
    over the filter limit is sampled at random, and then only the order
    among the returned parents can be held against the reference). A
    returned parent the rules exclude is an infinite gap."""
    if any(pid not in costs for pid in returned):
        return math.inf
    worst = 0.0
    for k, pid in enumerate(returned):
        rest = (
            [c for q, c in costs.items() if q not in returned[:k]]
            if closed
            else [costs[q] for q in returned[k:]]
        )
        worst = max(worst, costs[pid] - min(rest))
    return worst


# -- a plain fit of the same model -----------------------------------------

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def _loss_fn(precision: str):
    """Mean squared error of the MLP in jax.numpy at float32 ``highest``;
    "fp8" rounds every matmul's inputs to e4m3 (the control)."""
    import jax
    import jax.numpy as jnp

    def q(a):
        if precision == "fp8":
            return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return a

    def loss_fn(p, xb, yb):
        h = xb
        n = len(p["layers"])
        for i, layer in enumerate(p["layers"]):
            h = jnp.dot(q(h), q(layer["w"]), precision="highest") + layer["b"]
            if i != n - 1:
                h = jax.nn.gelu(h)
        return jnp.mean((h[:, 0] - yb) ** 2)

    return loss_fn


def adamw_update(p, m, v, g, t, lr, weight_decay):
    """One AdamW update at step ``t`` (from 0) at the rate ``lr``, written
    out: decoupled weight decay, bias-corrected moments."""
    import jax
    import jax.numpy as jnp

    m = jax.tree_util.tree_map(lambda a, b: ADAM_B1 * a + (1 - ADAM_B1) * b, m, g)
    v = jax.tree_util.tree_map(lambda a, b: ADAM_B2 * a + (1 - ADAM_B2) * b * b, v, g)
    p = jax.tree_util.tree_map(
        lambda w, mm, vv: w
        - lr
        * (
            (mm / (1 - ADAM_B1 ** (t + 1))) / (jnp.sqrt(vv / (1 - ADAM_B2 ** (t + 1))) + ADAM_EPS)
            + weight_decay * w
        ),
        p, m, v,
    )
    return p, m, v


def _adamw(p, m, v, g, t, learning_rate, weight_decay, warmup_steps):
    """The streamed fit's optimizer: AdamW under a linear warm-up to a
    constant rate."""
    import jax.numpy as jnp

    lr = learning_rate * jnp.minimum(t / max(warmup_steps, 1), 1.0)
    return adamw_update(p, m, v, g, t, lr, weight_decay)


def _scan_fit(loss_fn, batch_rows, steps, learning_rate, weight_decay, warmup_steps):
    """``run(p0, x, y, extra) -> (params, losses)``: ``steps`` updates, the
    rows of step ``t`` given by ``batch_rows(t, extra)``. The data comes in
    as arguments: closed over, it would be baked into the executable and
    every seed would compile its own."""
    import jax
    import jax.numpy as jnp

    def run(p0, xd, yd, extra):
        def body(carry, t):
            p, m, v = carry
            rows = batch_rows(t, extra)
            loss, g = jax.value_and_grad(loss_fn)(p, xd[rows], yd[rows])
            p, m, v = _adamw(
                p, m, v, g, t.astype(jnp.float32), learning_rate, weight_decay, warmup_steps
            )
            return (p, m, v), loss

        zeros = jax.tree_util.tree_map(jnp.zeros_like, p0)
        (p, _, _), losses = jax.lax.scan(
            body, (p0, zeros, zeros), jnp.arange(steps, dtype=jnp.int32)
        )
        return p, losses

    return jax.jit(run)


def init_mlp(seed: int, dims: list) -> dict:
    rng = np.random.default_rng([seed, 5])
    return {
        "layers": [
            {
                "w": (rng.standard_normal((i, o)) * np.sqrt(2.0 / i)).astype(np.float32),
                "b": np.zeros((o,), np.float32),
            }
            for i, o in zip(dims[:-1], dims[1:])
        ]
    }


def fit_steps(pairs_per_pass: int, passes: int, batch: int, eval_every: int) -> int:
    """Whole batches the streamed fit takes: a pass's pairs less the
    holdout's share, in whole batches, over the passes."""
    return passes * ((pairs_per_pass * (eval_every - 1) // eval_every) // batch)


def fit_mlp(
    x: np.ndarray,
    y: np.ndarray,
    *,
    seed: int,
    steps: int,
    batch: int,
    hidden: tuple,
    learning_rate: float,
    weight_decay: float,
    warmup_steps: int = 64,
    precision: str = "float32",
) -> dict:
    """A plain fit of the streamed fit's model with its optimizer and
    step count, from the benchmark's own start and in its own seeded
    batch order."""
    import jax
    import jax.numpy as jnp

    params = init_mlp(seed, [x.shape[1], *hidden, 1])
    params["layers"][-1]["b"] = np.full((1,), float(y.mean()), np.float32)
    rng = np.random.default_rng([seed, 6])
    idx = np.concatenate(
        [rng.permutation(x.shape[0]) for _ in range(steps * batch // x.shape[0] + 1)]
    )[: steps * batch].reshape(steps, batch)
    run = _scan_fit(
        _loss_fn(precision), lambda t, idx: idx[t], steps, learning_rate, weight_decay, warmup_steps
    )
    p, _ = run(
        jax.tree_util.tree_map(jnp.asarray, params), jnp.asarray(x), jnp.asarray(y), jnp.asarray(idx)
    )
    return jax.tree_util.tree_map(np.asarray, p)


def mse(params: dict, x: np.ndarray, y: np.ndarray) -> float:
    return float(np.mean((mlp_forward(params, x) - y) ** 2))


# -- the streamed fit, followed step by step -------------------------------


def staged_rows(x: np.ndarray, y: np.ndarray, block_pairs: int, eval_every: int):
    """What one pass over the body feeds the optimizer, in order: pairs
    staged as float16 (features and labels), minus the content-hash
    holdout (a pair whose hash of its float16 bits falls in bucket 0 of
    ``eval_every`` is never trained on). Also the mean of the first
    block's staged labels, which the fit starts its output bias from."""
    xh, yh = x.astype(np.float16), y.astype(np.float16)
    hv = xh.view(np.uint16).sum(axis=1, dtype=np.uint64)
    hv = (hv * np.uint64(2654435761) + yh.view(np.uint16)) & np.uint64(0xFFFFFFFF)
    keep = (hv % np.uint64(eval_every)) != 0
    bias = float(yh[:block_pairs].mean())
    return xh[keep].astype(np.float32), yh[keep].astype(np.float32), bias


def replay_losses(
    rows_x: np.ndarray,
    rows_y: np.ndarray,
    bias: float,
    *,
    steps: int,
    batch: int,
    hidden: tuple,
    learning_rate: float,
    weight_decay: float,
    pad_to: int,
    warmup_steps: int = 64,
    init_seed: int = 0,
    precision: str = "float32",
) -> np.ndarray:
    """The loss before each of the first ``steps`` updates of the
    streamed fit, replayed plainly: He-normal weights from
    ``jax.random.PRNGKey(init_seed)`` (the fit's stated start), batch
    ``t`` the rows ``[t*batch, (t+1)*batch)`` of the body's trained rows
    repeated end to end. ``precision`` "fp8" is the control. The
    holdout's size follows the seed; the program compiled here must not,
    so the rows are padded to ``pad_to`` and their count is passed as
    data."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(init_seed)
    dims = [rows_x.shape[1], *hidden, 1]
    layers = []
    for fan_in, fan_out in zip(dims[:-1], dims[1:]):
        key, sub = jax.random.split(key)
        scale = jnp.sqrt(2.0 / fan_in).astype(jnp.float32)
        w = jax.random.normal(sub, (fan_in, fan_out), jnp.float32) * scale
        layers.append({"w": w, "b": jnp.zeros((fan_out,), jnp.float32)})
    layers[-1]["b"] = jnp.full((1,), bias, jnp.float32)

    run = _scan_fit(
        _loss_fn(precision),
        lambda t, n_rows: (t * batch + jnp.arange(batch)) % n_rows,
        steps, learning_rate, weight_decay, warmup_steps,
    )
    pad = max(pad_to - rows_x.shape[0], 0)
    _, losses = run(
        {"layers": layers},
        jnp.asarray(np.pad(rows_x, ((0, pad), (0, 0)))),
        jnp.asarray(np.pad(rows_y, (0, pad))),
        jnp.int32(rows_x.shape[0]),
    )
    return np.asarray(losses)


def path_gap(got, want) -> float:
    """The widest relative gap between two loss paths, step by step."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    n = min(got.size, want.size)
    if n == 0 or not np.isfinite(got[:n]).all():
        return math.inf
    return float(np.max(np.abs(got[:n] - want[:n]) / np.abs(want[:n])))


# -- the served GraphSAGE, from the probe graph ----------------------------


def probe_graph(topo_records: list, max_degree: int = 16) -> dict:
    """The probe graph from its records, plainly: nodes in order of first
    appearance (a record's source, then its destinations), a directed
    edge per probed pair in order of first appearance with the latest
    RTT, seven node features (seed flag, tcp and upload-tcp counts,
    degrees, mean log-RTT out and in), and up to ``max_degree`` in-
    neighbours a node (a node with more keeps a sample drawn by
    ``numpy.random.default_rng(0)`` in node order, which is the graph
    build's stated rule)."""
    order: dict[str, int] = {}
    meta: dict[str, tuple] = {}
    edge: dict[tuple, float] = {}
    for rec in topo_records:
        s = order.setdefault(rec.host.id, len(order))
        meta[rec.host.id] = (rec.host.type, rec.host.network)
        for d in rec.dest_hosts:
            t = order.setdefault(d.id, len(order))
            meta[d.id] = (d.type, d.network)
            if d.probes.average_rtt > 0:
                edge[(s, t)] = float(d.probes.average_rtt)
    n = len(order)
    src = np.array([s for s, _ in edge], np.int64)
    dst = np.array([t for _, t in edge], np.int64)
    rtt_log = np.log1p(np.array(list(edge.values()), np.float64) / NS_PER_MS).astype(np.float32)
    out_deg = np.bincount(src, minlength=n).astype(np.float64)
    in_deg = np.bincount(dst, minlength=n).astype(np.float64)
    out_rtt = np.bincount(src, weights=rtt_log, minlength=n) / np.maximum(out_deg, 1)
    in_rtt = np.bincount(dst, weights=rtt_log, minlength=n) / np.maximum(in_deg, 1)
    ids = list(order)
    feats = np.stack(
        [
            np.array([0.0 if meta[h][0] in ("normal", "") else 1.0 for h in ids]),
            np.log1p(np.array([float(meta[h][1].tcp_connection_count) for h in ids])) / 10.0,
            np.log1p(np.array([float(meta[h][1].upload_tcp_connection_count) for h in ids])) / 10.0,
            np.log1p(out_deg), np.log1p(in_deg), out_rtt, in_rtt,
        ],
        axis=-1,
    ).astype(np.float32)
    rng = np.random.default_rng(0)
    nbrs = np.tile(np.arange(n)[:, None], (1, max_degree))
    mask = np.zeros((n, max_degree), np.float32)
    by_dst = np.argsort(dst, kind="stable")
    sdst, ssrc = dst[by_dst], src[by_dst]
    for v in range(n):
        mine = ssrc[np.searchsorted(sdst, v, "left") : np.searchsorted(sdst, v, "right")]
        if len(mine) > max_degree:
            mine = rng.choice(mine, size=max_degree, replace=False)
        nbrs[v, : len(mine)] = mine
        mask[v, : len(mine)] = 1.0
    return {
        "order": order, "features": feats, "neighbors": nbrs, "mask": mask,
        "src": src, "dst": dst, "rtt_log": rtt_log,
    }


class GnnReference:
    """Predicted log-RTT for (child host → parent host) from
    ``probe_graph``: node features joined with the node's embedding row,
    two SAGE layers with masked-mean aggregation, L2-normalised, and the
    pairwise head. ``hosts`` index the fleet."""

    def __init__(self, topo_records: list, host_index: dict, weights: dict,
                 max_degree: int = 16, precision: str = "float32"):
        g = probe_graph(topo_records, max_degree)
        order, feats, nbrs, mask = g["order"], g["features"], g["neighbors"], g["mask"]
        h = np.concatenate([feats, weights["node_embed"]], axis=-1)
        for layer in weights["sage"]:
            gathered = h[nbrs] * mask[:, :, None]
            agg = gathered.sum(1) / np.maximum(mask.sum(1, keepdims=True), 1.0)
            z = _quant(h, precision) @ _quant(layer["w_self"], precision) + _quant(
                agg.astype(np.float32), precision
            ) @ _quant(layer["w_nbr"], precision)
            h = np.maximum(z + layer["b"], 0.0).astype(np.float32)
        self.emb = h / np.maximum(np.linalg.norm(h, axis=-1, keepdims=True), 1e-6)
        self.node_of = {host_index[hid]: i for hid, i in order.items()}
        self.head, self.precision = weights["head"], precision

    def costs(self, child_hosts: list, parent_hosts: list) -> np.ndarray:
        hs = self.emb[[self.node_of[c] for c in child_hosts]]
        hd = self.emb[[self.node_of[p] for p in parent_hosts]]
        return mlp_forward(self.head, np.concatenate([hs, hd, hs * hd], axis=-1), self.precision)
