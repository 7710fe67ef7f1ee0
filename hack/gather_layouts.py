#!/usr/bin/env python3
"""What a batch taken from a resident table by row number costs on the
chip, by the table's layout (ISSUE 29's micro-benchmark; PERF.md §6).

    chiprun -- python3 hack/gather_layouts.py [--pairs 55050240]

``--legs fit`` runs the fit itself instead: ``train_mlp`` twice on pairs
made on the host at the real size, the second timed by phase.

For the MLP's pairs (19 float32 features and a label, 8,192 random rows
a step, 101 steps a dispatch) and the GRU's sequences (``[T, F]`` rows,
128 a step, 503 steps), each layout is built on the chip from a formula
of the row number, checked bit for bit on one batch, and timed inside
the ``fori_loop`` a fit's slice runs: the take alone, and the take with
the leg's real step behind it, against the step on batches that were
gathered before (the parent's form). One JSON line a reading; the
optimised HLO of each loop goes to ``chiprun_out/gather_layouts/``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.normpath(os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np
import optax

from dragonfly2_tpu.models import gru as gru_mod
from dragonfly2_tpu.models import mlp as mlp_mod
from dragonfly2_tpu.trainer import train as train_mod

F, BATCH, STEPS = 19, 8192, 101
GRU_T, GRU_F, GRU_BATCH, GRU_STEPS, GRU_ROWS = 9, 2, 128, 503, 1_000_000
OUT = os.path.join(ROOT, "chiprun_out", "gather_layouts")


def value(r, c):
    """What row ``r`` holds in column ``c`` (19 is the label): a float32
    that is exact, so a take is checked bit for bit."""
    return ((r & 0x3FFFF) * 32 + c).astype(jnp.float32)


def take_in_bounds(column, rows):
    return column.at[rows].get(mode="promise_in_bounds")


# -- the layouts: build(n) -> table, take(table, rows) -> (x [B, 19], y [B]) --


def build_plain(n):
    r = jnp.arange(n, dtype=jnp.int32)
    return value(r[:, None], jnp.arange(F, dtype=jnp.int32)[None, :]), value(r, F)


def take_plain(table, rows):
    return take_in_bounds(table[0], rows), take_in_bounds(table[1], rows)


def build_packed(per_row, width):
    """``per_row`` pairs a row of 128 lanes, each in ``width`` of them."""

    def build(n):
        m = jnp.arange(-(-n // per_row), dtype=jnp.int32)[:, None]
        lane = jnp.arange(128, dtype=jnp.int32)[None, :]
        v = value(m * per_row + lane // width, lane % width)
        return jnp.where((lane < per_row * width) & (lane % width <= F), v, 0.0)

    return build


def take_packed(per_row, width, fetch=take_in_bounds):
    def take(table, rows):
        wide = fetch(table, rows // per_row)  # [B, 128]
        sub = (rows % per_row)[:, None]
        pair = wide[:, :width]
        for j in range(1, per_row):  # a select chain: the values pass through untouched
            pair = jnp.where(sub == j, wide[:, j * width : (j + 1) * width], pair)
        return pair[:, :F], pair[:, F]

    return take


def fetch_by_dma(table, rows):
    """The table's rows fetched by a kernel: one DMA a row, all in flight."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(rows_ref, table_ref, out_ref, sem):
        def start(i, _):
            pltpu.make_async_copy(table_ref.at[rows_ref[i]], out_ref.at[i], sem).start()
            return 0

        def wait(i, _):
            pltpu.make_async_copy(table_ref.at[0], out_ref.at[i], sem).wait()
            return 0

        jax.lax.fori_loop(0, BATCH, start, 0)
        jax.lax.fori_loop(0, BATCH, wait, 0)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((BATCH, 128), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((BATCH, 128), lambda i, rows: (0, 0)),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        ),
        name="table_rows",
    )(rows, table)


LAYOUTS = {
    "plain [N,19]+[N]": (build_plain, take_plain),
    "packed 6 a row [N/6,128]": (build_packed(6, 20), take_packed(6, 20)),
    "packed 4 a row [N/4,128]": (build_packed(4, 32), take_packed(4, 32)),
    "packed 6 a row, DMA kernel": (build_packed(6, 20), take_packed(6, 20, fetch_by_dma)),
}


def timed(fn, *args, repeats=5, fresh=False):
    """Milliseconds a call: the compile apart, then the median and the least.
    ``fresh``: the first two arguments are copied for every call (a call
    that donates them leaves them unusable)."""
    def once():
        call = (*jax.block_until_ready(jax.tree_util.tree_map(jnp.copy, args[:2])), *args[2:]) if fresh else args
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*call))
        return time.perf_counter() - t0

    first = once()
    walls = [once() * 1e3 for _ in range(repeats)]
    return first, statistics.median(walls), min(walls)


def report(**line):
    print(json.dumps(line), flush=True)


def save_hlo(name, lowered_compiled):
    os.makedirs(OUT, exist_ok=True)
    text = lowered_compiled.as_text()
    with open(os.path.join(OUT, name.replace(" ", "_").replace("/", "-").replace(",", "") + ".hlo.txt"), "w") as f:
        f.write(text)
    return sorted({l.split("=")[1].split("(")[0].strip()[:90] for l in text.splitlines() if " gather(" in l})


def mlp_step_loop(take):
    cfg = train_mod.FitConfig()
    optimizer = train_mod._optimizer(cfg, 6047)

    def loss_fn(p, batch):
        x, y = batch
        return jnp.mean((mlp_mod.score_parents(p, x) - y) ** 2)

    def loop(params, opt_state, table, rows):
        def body(i, carry):
            params, opt_state, loss_sum = carry
            loss, grads = jax.value_and_grad(loss_fn)(params, take(table, rows[i]))
            updates, opt_state = optimizer.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss_sum + loss

        return jax.lax.fori_loop(0, rows.shape[0], body, (params, opt_state, jnp.zeros((), jnp.float32)))

    params = mlp_mod.init_mlp(jax.random.PRNGKey(0), [F, 128, 128, 1])
    return jax.jit(loop), params, optimizer.init(params)


def take_loop(take):
    def loop(table, rows):
        def body(i, acc):
            x, y = take(table, rows[i])
            return acc + jnp.sum(x, axis=0)[:1] + jnp.sum(y)

        return jax.lax.fori_loop(0, rows.shape[0], body, jnp.zeros((1,), jnp.float32))

    return jax.jit(loop)


def mlp(n: int, only: list[str]) -> None:
    rng = np.random.default_rng(0)
    rows = jnp.asarray(rng.integers(0, n, size=(STEPS, BATCH), dtype=np.int32))
    want_x = np.asarray(value(np.asarray(rows[0])[:, None], np.arange(F, dtype=np.int32)[None, :]))
    want_y = np.asarray(value(np.asarray(rows[0]), F))

    # the parent's form: the step on batches gathered before
    pre = (jnp.zeros((STEPS, BATCH, F), jnp.float32), jnp.zeros((STEPS, BATCH), jnp.float32))
    pre_loop, params, opt_state = mlp_step_loop(lambda batches, i_rows: (batches[0][i_rows[0]], batches[1][i_rows[0]]))
    first, med, least = timed(pre_loop, params, opt_state, pre, jnp.arange(STEPS, dtype=jnp.int32)[:, None])
    report(leg="mlp", layout="gathered before (parent)", what="step", us_per_step=med / STEPS * 1e3,
           least_us_per_step=least / STEPS * 1e3, first_s=first)
    del pre

    for name, (build, take) in LAYOUTS.items():
        if only and not any(o in name for o in only):
            continue
        try:
            t0 = time.perf_counter()
            table = jax.block_until_ready(jax.jit(build, static_argnums=0)(n))
            built = time.perf_counter() - t0
            nbytes = sum(a.nbytes for a in jax.tree_util.tree_leaves(table))
            on_chip = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use")
            got_x, got_y = jax.jit(take)(table, rows[0])
            exact = bool(np.array_equal(np.asarray(got_x), want_x) and np.array_equal(np.asarray(got_y), want_y))
            alone = take_loop(take)
            gathers = save_hlo(name + " take", alone.lower(table, rows).compile())
            first, med, least = timed(alone, table, rows)
            report(leg="mlp", layout=name, what="take", us_per_step=med / STEPS * 1e3,
                   least_us_per_step=least / STEPS * 1e3, first_s=first, exact=exact, table_bytes=nbytes,
                   bytes_in_use=on_chip, build_s=built, gathers=gathers)
            loop, params, opt_state = mlp_step_loop(take)
            save_hlo(name + " step", loop.lower(params, opt_state, table, rows).compile())
            first, med, least = timed(loop, params, opt_state, table, rows)
            report(leg="mlp", layout=name, what="take+step", us_per_step=med / STEPS * 1e3,
                   least_us_per_step=least / STEPS * 1e3, first_s=first,
                   epoch_6047_steps_s=med / STEPS * 6047 / 1e3)
        except Exception as e:  # one layout the compiler refuses does not end the others
            report(leg="mlp", layout=name, error=f"{type(e).__name__}: {str(e)[:600]}")
        table = got_x = got_y = None


def gru() -> None:
    rng = np.random.default_rng(1)
    seqs = jnp.asarray(rng.normal(size=(GRU_ROWS, GRU_T, GRU_F)).astype(np.float32))
    labels = jnp.asarray(rng.normal(size=GRU_ROWS).astype(np.float32))
    lengths = jnp.asarray(rng.integers(1, GRU_T + 1, size=GRU_ROWS, dtype=np.int32))
    host_rows = rng.integers(0, GRU_ROWS, size=(GRU_STEPS, GRU_BATCH), dtype=np.int32)
    rows = jnp.asarray(host_rows)
    table = (seqs, labels, lengths)
    cfg = train_mod.FitConfig(hidden_dims=(32,), batch_size=GRU_BATCH, epochs=10)
    optimizer = train_mod._optimizer(cfg, 70310)
    params = gru_mod.init_gru(jax.random.PRNGKey(0), GRU_F, 32)

    def loss_fn(p, b):
        x, y, ln = b
        return jnp.mean((gru_mod.predict_next_cost(p, x, ln) - y) ** 2)

    def make(take, donate=False):
        def loop(params, opt_state, data, rows):
            def body(i, carry):
                params, opt_state, loss_sum = carry
                loss, grads = jax.value_and_grad(loss_fn)(params, take(data, rows[i]))
                updates, opt_state = optimizer.update(grads, opt_state, params)
                return optax.apply_updates(params, updates), opt_state, loss_sum + loss

            return jax.lax.fori_loop(0, rows.shape[0], body, (params, opt_state, jnp.zeros((), jnp.float32)))

        return jax.jit(loop, donate_argnums=(0, 1) if donate else ())

    before = tuple(np.asarray(c)[host_rows] for c in table)
    pre = tuple(jnp.asarray(c) for c in before)
    first, med, least = timed(
        make(lambda data, i: tuple(c[i[0]] for c in data)), params, optimizer.init(params), pre,
        jnp.arange(GRU_STEPS, dtype=jnp.int32)[:, None],
    )
    report(leg="gru", layout="gathered before (parent)", what="step", us_per_step=med / GRU_STEPS * 1e3,
           least_us_per_step=least / GRU_STEPS * 1e3, first_s=first)
    take = lambda data, r: tuple(take_in_bounds(c, r) for c in data)
    got = jax.jit(take)(table, rows[3])
    exact = all(np.array_equal(np.asarray(g), b[3]) for g, b in zip(got, before))
    loop = make(take)
    gathers = save_hlo("gru plain step", loop.lower(params, optimizer.init(params), table, rows).compile())
    first, med, least = timed(loop, params, optimizer.init(params), table, rows)
    report(leg="gru", layout="plain [N,T,F]+[N]+[N]", what="take+step", us_per_step=med / GRU_STEPS * 1e3,
           least_us_per_step=least / GRU_STEPS * 1e3, first_s=first, exact=exact, gathers=gathers,
           table_bytes=sum(c.nbytes for c in table))
    # the fit's own table (trainer/train.py: 20 words a row, six rows to 128 lanes)
    from dragonfly2_tpu.trainer import metrics as M

    packed = train_mod._put_table(None, M.PH_GRU, *(np.asarray(c) for c in table))
    take = lambda data, r: data.take(r)
    got = jax.jit(take)(packed, rows[3])
    exact = all(np.array_equal(np.asarray(g), b[3]) for g, b in zip(got, before))
    loop = make(take)
    gathers = save_hlo("gru packed step", loop.lower(params, optimizer.init(params), packed, rows).compile())
    first, med, least = timed(loop, params, optimizer.init(params), packed, rows)
    report(leg="gru", layout="the fit's table, 6 rows to 128 lanes", what="take+step", us_per_step=med / GRU_STEPS * 1e3,
           least_us_per_step=least / GRU_STEPS * 1e3, first_s=first, exact=exact, gathers=gathers,
           table_bytes=packed.packed.nbytes)
    # the carry donated, as the fit's slice had it before PR 29: aliased to the outputs it lives in HBM
    first, med, least = timed(make(take, donate=True), params, optimizer.init(params), packed, rows, fresh=True)
    report(leg="gru", layout="the fit's table, 6 rows to 128 lanes", what="take+step, params and opt_state donated",
           us_per_step=med / GRU_STEPS * 1e3, least_us_per_step=least / GRU_STEPS * 1e3, first_s=first)


def fit(n: int) -> None:
    """``train_mlp`` at the real size, alone on the chip: the first fit
    compiles, the second is read by phase; then one batch of the table
    against the host's rows, and the device's peak."""
    from dragonfly2_tpu.trainer import metrics as M
    from dragonfly2_tpu.utils import profiling

    rng = np.random.default_rng(3)
    x = np.empty((n, F), np.float32)
    for lo in range(0, n, 1 << 22):  # a slice at a time: the generator has no float32 fill of 4 GB at once
        x[lo : lo + (1 << 22)] = rng.random((min(1 << 22, n - lo), F), dtype=np.float32)
    y = x[:, :3].sum(axis=1) + 0.1 * rng.random(n, dtype=np.float32)
    cfg = train_mod.FitConfig(epochs=1)
    for i in range(2):
        before = {k: (v["count"], v["total_s"]) for k, v in profiling.ledger_snapshot().items()}
        put0 = M.FIT_PUT_BYTES_TOTAL.labels("mlp").value
        t0 = time.perf_counter()
        result = train_mod.train_mlp(x, y, config=cfg)
        wall = time.perf_counter() - t0
        moved = {
            k.removeprefix("trainer."): {"n": v["count"] - before.get(k, (0, 0))[0],
                                         "s": round(v["total_s"] - before.get(k, (0, 0))[1], 4)}
            for k, v in profiling.ledger_snapshot().items()
            if v["count"] != before.get(k, (0, 0))[0]
        }
        report(leg="fit", fit=i, wall_s=wall, phases=moved, put_bytes=M.FIT_PUT_BYTES_TOTAL.labels("mlp").value - put0,
               history=result.history, metrics=result.metrics,
               peak_bytes=(jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use"))
    table = train_mod._put_table(None, M.PH_MLP, x, y)
    rows = rng.integers(0, n, size=BATCH, dtype=np.int32)
    got_x, got_y = jax.jit(lambda t, r: t.take(r))(table, rows)
    report(leg="fit", what="a batch of the table against the host's rows",
           exact=bool(np.array_equal(np.asarray(got_x), x[rows]) and np.array_equal(np.asarray(got_y), y[rows])),
           table_shape=list(table.packed.shape), peak_bytes=(jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use"))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pairs", type=int, default=55_050_240)
    ap.add_argument("--only", action="append", default=[], help="a part of a layout's name; repeatable")
    ap.add_argument("--legs", default="mlp,gru")
    args = ap.parse_args()
    d = jax.devices()[0]
    report(device={"platform": d.platform, "kind": d.device_kind, "count": jax.device_count()}, pairs=args.pairs)
    if "gru" in args.legs:
        gru()
    if "mlp" in args.legs:
        mlp(args.pairs, args.only)
    if "fit" in args.legs:
        fit(args.pairs)
    return 0


if __name__ == "__main__":
    sys.exit(main())
