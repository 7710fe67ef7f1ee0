"""Plain replays of the resident MLP, GraphSAGE and GRU fits of a
training round.

Nothing here imports the program. Each replay starts from the fit's
stated start (``jax.random.PRNGKey(0)`` through the stated initialiser),
takes the benchmark's own seeded data in the fit's stated order (a
holdout of a tenth drawn by ``numpy.random.default_rng(0)``, every epoch
a fresh permutation of the rest cut to whole batches), and applies AdamW
under the stated warm-up and cosine decay, all in float32 at ``highest``.
It gives back the mean loss of each epoch and the parameters at the end,
which the program's history and registered version are held to.
``precision="fp8"`` rounds every matmul's inputs to e4m3: the control.
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness import reference

MAX_PIECES_PER_PARENT = 10
GRU_MAX_SEQ = MAX_PIECES_PER_PARENT - 1
NS_PER_MS = reference.NS_PER_MS
FIT_SEED = 0  # the fits' own seed (FitConfig.seed), whatever --seed is
EVAL_FRACTION, WARMUP_FRACTION = 0.1, 0.1


# -- what a fit is fed -------------------------------------------------------


def piece_sequences(records: list) -> "tuple[np.ndarray, np.ndarray, np.ndarray]":
    """Download records → the GRU's examples, in record then parent
    order: for a parent with two or more piece costs, the log1p(ms) of
    all but the last with the piece's position beside it, the last as
    the label, and the length of the prefix."""
    seqs, labels, lengths = [], [], []
    for rec in records:
        for p in rec.parents:
            costs = [float(pc.cost) for pc in p.pieces if pc.cost > 0]
            if not p.id or len(costs) < 2:
                continue
            prefix = np.log1p(np.asarray(costs[:-1], np.float64) / NS_PER_MS)[:GRU_MAX_SEQ]
            row = np.zeros((GRU_MAX_SEQ, 2), np.float32)
            row[: len(prefix), 0] = prefix
            row[: len(prefix), 1] = (np.arange(len(prefix)) + 1) / MAX_PIECES_PER_PARENT
            seqs.append(row)
            labels.append(np.log1p(costs[-1] / NS_PER_MS))
            lengths.append(len(prefix))
    return np.stack(seqs), np.asarray(labels, np.float32), np.asarray(lengths, np.int32)


def newest(body: np.ndarray, repeats: int, cap: int) -> np.ndarray:
    """The newest ``cap`` rows of ``body`` repeated end to end."""
    n = body.shape[0]
    take = min(cap, n * repeats)
    idx = (np.arange(n * repeats - take, n * repeats)) % n
    return body[idx]


def epoch_rows(n: int, batch: int, epochs: int, per_epoch_rng: bool) -> np.ndarray:
    """[epochs * steps, batch] row numbers, as the fits draw them: the
    GraphSAGE fit seeds a generator per epoch (``FIT_SEED + 1 + epoch``),
    the GRU fit draws every epoch from one (``FIT_SEED + 1``)."""
    perm = np.random.default_rng(FIT_SEED).permutation(n)
    train = perm[int(n * EVAL_FRACTION):]
    batch = min(batch, len(train))
    steps = max(1, len(train) // batch)
    rng = np.random.default_rng(FIT_SEED + 1)
    out = []
    for epoch in range(epochs):
        if per_epoch_rng:
            rng = np.random.default_rng(FIT_SEED + 1 + epoch)
        out.append(train[rng.permutation(len(train))][: steps * batch].reshape(steps, batch))
    return np.concatenate(out).astype(np.int32)


# -- the optimizer's schedule and the scan ----------------------------------


def _schedule(t, peak: float, total: int):
    """Linear from 0 to ``peak`` over the first tenth of ``total`` steps,
    then a cosine to 0 at ``total``."""
    import jax.numpy as jnp

    warm = max(1, int(total * WARMUP_FRACTION))
    span = max(max(2, total) - warm, 1)
    frac = jnp.clip((t - warm) / span, 0.0, 1.0)
    return jnp.where(t < warm, peak * t / warm, peak * 0.5 * (1.0 + jnp.cos(jnp.pi * frac)))


def _replay(loss_fn, total: int, peak: float, weight_decay: float):
    """``run(p0, data, rows) -> (params, losses)``; the data comes in as
    arguments, so one executable serves every seed."""
    import jax
    import jax.numpy as jnp

    def run(p0, data, rows):
        def body(carry, inp):
            p, m, v = carry
            t, r = inp
            loss, g = jax.value_and_grad(loss_fn)(p, data, r)
            tf = t.astype(jnp.float32)
            p, m, v = reference.adamw_update(
                p, m, v, g, tf, _schedule(tf, peak, total), weight_decay
            )
            return (p, m, v), loss

        zeros = jax.tree_util.tree_map(jnp.zeros_like, p0)
        (p, _, _), losses = jax.lax.scan(
            body, (p0, zeros, zeros), (jnp.arange(total, dtype=jnp.int32), rows)
        )
        return p, losses

    return jax.jit(run)


def _quantiser(precision: str):
    import jax.numpy as jnp

    if precision == "fp8":
        return lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    if precision == "float32":
        return lambda a: a
    raise ValueError(precision)


def _dot(q):
    import jax.numpy as jnp

    return lambda a, b: jnp.dot(q(a), q(b), precision="highest")


def _he_mlp(key, dims: list) -> dict:
    import jax
    import jax.numpy as jnp

    layers = []
    for fan_in, fan_out in zip(dims[:-1], dims[1:]):
        key, sub = jax.random.split(key)
        w = jax.random.normal(sub, (fan_in, fan_out), jnp.float32) * jnp.sqrt(2.0 / fan_in).astype(jnp.float32)
        layers.append({"w": w, "b": jnp.zeros((fan_out,), jnp.float32)})
    return {"layers": layers}


def _head(dot, head: dict, x):
    import jax

    n = len(head["layers"])
    for i, layer in enumerate(head["layers"]):
        x = dot(x, layer["w"]) + layer["b"]
        if i != n - 1:
            x = jax.nn.gelu(x)
    return x[..., 0]


def _finish(run, p0, data: dict, rows: np.ndarray, epochs: int):
    import jax
    import jax.numpy as jnp

    p, losses = run(p0, {k: jnp.asarray(v) for k, v in data.items()}, jnp.asarray(rows))
    host = lambda tree: jax.tree_util.tree_map(np.asarray, tree)  # noqa: E731
    return {
        "history": np.asarray(losses, np.float64).reshape(epochs, -1).mean(axis=1),
        "start": host(p0),
        "params": host(p),
        "steps": int(rows.shape[0]),
        "batch": int(rows.shape[1]),
    }


# -- the resident MLP --------------------------------------------------------


def fit_mlp(x: np.ndarray, y: np.ndarray, repeats: int, *, hidden: tuple, epochs: int, batch: int,
            learning_rate: float = 3e-3, weight_decay: float = 1e-4,
            precision: str = "float32") -> dict:
    """The resident pair-scorer fit over the body ``x``, ``y`` repeated
    ``repeats`` times end to end, as an upload of replicated chunks reads:
    row ``i`` of the upload is row ``i % len(x)`` of the body, so only the
    body goes to the device."""
    import jax
    import jax.numpy as jnp

    p0 = _he_mlp(jax.random.PRNGKey(FIT_SEED), [x.shape[1], *hidden, 1])
    p0["layers"][-1]["b"] = jnp.full((1,), float(y.mean()), jnp.float32)
    dot = _dot(_quantiser(precision))

    def loss_fn(p, d, rows):
        return jnp.mean((_head(dot, p, d["x"][rows]) - d["y"][rows]) ** 2)

    rows = epoch_rows(x.shape[0] * repeats, batch, epochs, per_epoch_rng=True) % x.shape[0]
    run = _replay(loss_fn, rows.shape[0], learning_rate, weight_decay)
    return _finish(run, p0, {"x": x, "y": y}, rows.astype(np.int32), epochs)


def holdout_weights(n_body: int, repeats: int) -> np.ndarray:
    """How often each row of the body falls into the fit's holdout (the
    first tenth of its permutation of the whole upload)."""
    n = n_body * repeats
    held = np.random.default_rng(FIT_SEED).permutation(n)[: int(n * EVAL_FRACTION)]
    return np.bincount(held % n_body, minlength=n_body).astype(np.float64)


def mlp_holdout_mse(x: np.ndarray, y: np.ndarray, repeats: int, params: dict,
                    precision: str = "float32") -> float:
    """The holdout's mean squared error at fixed ``params``: the number a
    resident fit registers with its version, with no trajectory between."""
    w = holdout_weights(x.shape[0], repeats)
    plain = {"layers": [{k: np.asarray(v, np.float32) for k, v in l.items()} for l in params["layers"]]}
    err = reference.mlp_forward(plain, x, precision).astype(np.float64) - y
    return float((w * err**2).sum() / w.sum())


# -- GraphSAGE ---------------------------------------------------------------


def _gnn_data(graph: dict) -> dict:
    return {
        "features": graph["features"], "neighbors": graph["neighbors"].astype(np.int32),
        "mask": graph["mask"], "src": graph["src"].astype(np.int32),
        "dst": graph["dst"].astype(np.int32), "rtt_log": graph["rtt_log"],
    }


def _gnn_loss(precision: str):
    """Mean squared error of the predicted log-RTT over the edges
    ``rows``: two SAGE layers with masked-mean aggregation over the node
    features joined with the embedding table, L2-normalised, then the
    pairwise head."""
    import jax
    import jax.numpy as jnp

    dot = _dot(_quantiser(precision))

    def loss_fn(p, g, rows):
        h = jnp.concatenate([g["features"], p["node_embed"]], axis=-1)
        for layer in p["sage"]:
            agg = (h[g["neighbors"]] * g["mask"][:, :, None]).sum(axis=1) / jnp.maximum(
                g["mask"].sum(axis=1, keepdims=True), 1.0
            )
            h = jax.nn.relu(dot(h, layer["w_self"]) + dot(agg, layer["w_nbr"]) + layer["b"])
        h = h / jnp.maximum(jnp.linalg.norm(h, axis=-1, keepdims=True), 1e-6)
        hs, hd = h[g["src"][rows]], h[g["dst"][rows]]
        pred = _head(dot, p["head"], jnp.concatenate([hs, hd, hs * hd], axis=-1))
        return jnp.mean((pred - g["rtt_log"][rows]) ** 2)

    return loss_fn



def fit_gnn(graph: dict, *, hidden: tuple, epochs: int, batch: int = 2048,
            learning_rate: float = 2e-2, weight_decay: float = 1e-4, embed_dim: int = 16,
            head_hidden: int = 64, precision: str = "float32") -> dict:
    """The GraphSAGE fit on ``reference.probe_graph``'s arrays: predicts
    each probed edge's log-RTT from the two endpoints' embeddings."""
    import jax
    import jax.numpy as jnp

    n, f = graph["features"].shape
    key, ek = jax.random.split(jax.random.PRNGKey(FIT_SEED))
    embed = jax.random.normal(ek, (n, embed_dim), jnp.float32) * 0.1
    d, sage = f + embed_dim, []
    for h in hidden:
        key, k1, k2 = jax.random.split(key, 3)
        scale = jnp.sqrt(2.0 / d).astype(jnp.float32)
        sage.append({
            "w_self": jax.random.normal(k1, (d, h), jnp.float32) * scale,
            "w_nbr": jax.random.normal(k2, (d, h), jnp.float32) * scale,
            "b": jnp.zeros((h,), jnp.float32),
        })
        d = h
    key, hk = jax.random.split(key)
    head = _he_mlp(hk, [3 * d, head_hidden, 1])
    head["layers"][-1]["b"] = jnp.full((1,), float(graph["rtt_log"].mean()), jnp.float32)
    p0 = {"sage": sage, "head": head, "node_embed": embed}
    loss_fn = _gnn_loss(precision)
    rows = epoch_rows(len(graph["src"]), batch, epochs, per_epoch_rng=True)
    run = _replay(loss_fn, rows.shape[0], learning_rate, weight_decay)
    out = _finish(run, p0, _gnn_data(graph), rows, epochs)
    out["last_epoch_rows"] = rows[-(rows.shape[0] // epochs):]
    return out


def gnn_loss_at(graph: dict, params: dict, rows: np.ndarray, precision: str = "float32") -> float:
    """The mean over the batches ``rows`` of the fit's loss at fixed
    ``params``. Under the cosine's last steps the rate is all but 0, so a
    fit's last epoch reads this at the parameters it registers."""
    import jax
    import jax.numpy as jnp

    loss_fn = _gnn_loss(precision)
    data = {k: jnp.asarray(v) for k, v in _gnn_data(graph).items()}
    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    losses = jax.jit(lambda p, d, r: jax.vmap(lambda rr: loss_fn(p, d, rr))(r))(params, data, jnp.asarray(rows))
    return float(np.mean(np.asarray(losses, np.float64)))


# -- GRU ---------------------------------------------------------------------


def fit_gru(seqs: np.ndarray, labels: np.ndarray, lengths: np.ndarray, *, hidden: int,
            epochs: int, batch: int, learning_rate: float = 3e-3, weight_decay: float = 1e-4,
            head_hidden: int = 32, precision: str = "float32") -> dict:
    """The next-piece-cost fit: a GRU over each example's real prefix
    (state held past its length), then a two-layer head."""
    import jax
    import jax.numpy as jnp

    f = seqs.shape[2]
    keys = jax.random.split(jax.random.PRNGKey(FIT_SEED), 7)

    def dense(k, fan_in, fan_out):
        return jax.random.normal(k, (fan_in, fan_out), jnp.float32) * jnp.sqrt(1.0 / fan_in).astype(jnp.float32)

    p0 = {}
    for i, gate in enumerate("zrh"):
        p0["w" + gate] = dense(keys[2 * i], f, hidden)
        p0["u" + gate] = dense(keys[2 * i + 1], hidden, hidden)
        p0["b" + gate] = jnp.zeros((hidden,), jnp.float32)
    p0["head"] = _he_mlp(keys[6], [hidden, head_hidden, 1])
    p0["head"]["layers"][-1]["b"] = jnp.full((1,), float(labels.mean()), jnp.float32)
    dot = _dot(_quantiser(precision))

    def loss_fn(p, d, rows):
        x, ln = d["seqs"][rows], d["lengths"][rows]

        def step(h, inp):
            xt, t = inp
            z = jax.nn.sigmoid(dot(xt, p["wz"]) + dot(h, p["uz"]) + p["bz"])
            r = jax.nn.sigmoid(dot(xt, p["wr"]) + dot(h, p["ur"]) + p["br"])
            cand = jnp.tanh(dot(xt, p["wh"]) + dot(r * h, p["uh"]) + p["bh"])
            new = (1.0 - z) * cand + z * h
            return jnp.where((t < ln)[:, None], new, h), None

        h0 = jnp.zeros((x.shape[0], hidden), jnp.float32)
        final, _ = jax.lax.scan(step, h0, (x.transpose(1, 0, 2), jnp.arange(x.shape[1])))
        return jnp.mean((_head(dot, p["head"], final) - d["labels"][rows]) ** 2)

    rows = epoch_rows(seqs.shape[0], batch, epochs, per_epoch_rng=False)
    run = _replay(loss_fn, rows.shape[0], learning_rate, weight_decay)
    return _finish(run, p0, {"seqs": seqs, "labels": labels, "lengths": lengths}, rows, epochs)


# -- holding a fit to its replay ---------------------------------------------


def update_gap(got: dict, ref: dict) -> float:
    """How far the program's parameters moved from the start against how
    far the replay's did, by the worst leaf: the gap between the two
    norms, over the replay's norm for that leaf or for the median leaf,
    whichever is larger (some leaves hardly move)."""
    import jax

    start = jax.tree_util.tree_leaves(ref["start"])
    want = jax.tree_util.tree_leaves(ref["params"])
    have = jax.tree_util.tree_leaves(got)
    if len(have) != len(want) or any(np.shape(a) != np.shape(b) for a, b in zip(have, want)):
        return float("inf")
    moved_ref = [float(np.linalg.norm(np.asarray(w, np.float64) - s)) for w, s in zip(want, start)]
    moved_got = [float(np.linalg.norm(np.asarray(h, np.float64) - s)) for h, s in zip(have, start)]
    if not np.isfinite(moved_got).all():
        return float("inf")
    floor = float(np.median(moved_ref))
    return max(abs(g - r) / max(r, floor) for g, r in zip(moved_got, moved_ref))


def mismatches(*pairs) -> float:
    """Elements that differ, summed over pairs of arrays; a pair of
    different shapes counts whole."""
    total = 0
    for a, b in pairs:
        a, b = np.asarray(a), np.asarray(b)
        total += int(max(a.size, b.size)) if a.shape != b.shape else int(np.count_nonzero(a != b))
    return float(total)
