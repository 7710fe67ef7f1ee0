"""Model fit loops — the real implementation of the reference's training
stubs (reference trainer/training/training.go:60-98; intended flow per its
comments: load from storage → preprocess → train → upload model to manager).

Throughput design (north star: 1B records in <10 min on v5e-8):
- a fit's columns on the chip once a fit, in the caller's order; an
  epoch is the host's permutation (drawn ahead of the fit, from its row
  count: FitOrder) as row numbers, and a device loop
  takes each step's batch from the resident table, zero host↔device
  traffic inside it; table, row numbers and epochs go in bounded slices
  (below) so that another tenant of the process is never kept from the
  interpreter or from the device's queue for longer than one;
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

import contextlib
import math
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field, replace
from functools import partial
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
from dragonfly2_tpu.trainer.metrics import LEG_PHASES, PH_GNN, PH_GRU, PH_MLP
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


def _head_bias(labels: np.ndarray):
    """The output bias a fit starts from: the mean label, as float32 in
    its own right. (Filled from a Python float without a type it is
    weakly typed, the optimizer's moments after it, and the epoch's
    slice is traced for the first epoch, again for the second, whose
    parameters are no longer weak, and again for the third, whose
    moments are not either: 0.28 s a trace for the GraphSAGE leg alone
    on the chip. The values are the same either way.)"""
    return jnp.full((1,), float(labels.mean()), jnp.float32)


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


class FitOrder:
    """A fit's order, drawn ahead of the fit: the permutation that sets
    the holdout apart and every epoch's. All of it is a function of the
    row count ``n`` and of ``cfg`` (``eval_fraction``, ``seed``,
    ``epochs``) and needs no byte of the data, so it is drawn on threads
    of its own from the moment it is made (the shuffles run without the
    interpreter lock), and the fit waits for each permutation only where
    it uses it: the holdout's and the first epoch's side by side from
    the start, epoch ``e + 1``'s from when epoch ``e``'s is taken, so
    one is drawn while the epoch before it runs. Who knows ``n`` before
    the fit has its arrays makes the order then and hands it to the fit
    (``Training._train_mlp_from``: beside the load's assembly); a fit
    handed none makes its own on entry: one path, started sooner or
    later.

    The numbers are ``_split_eval``'s and ``_permutation``'s with the
    generators the fits always had: ``default_rng(seed)`` for the
    holdout, ``default_rng(seed + 1 + epoch)`` for an epoch (a resumed
    run replays the exact shuffle schedule), or with ``carried`` the one
    generator ``default_rng(seed + 1)`` handed from each epoch's draw to
    the next. ``phases.order`` is entered on the drawing thread, once a
    permutation: its seconds are drawn, not waited, and are in no leg's
    split. A ``with`` block around the fit ends the threads and drops
    what was drawn and not taken, whether the fit returns or raises."""

    def __init__(self, phases, n: int, cfg: FitConfig, carried: bool = False):
        self._drawn_for = (n, cfg.eval_fraction, cfg.seed, cfg.epochs)
        self._phase, self._epochs, self._seed = phases.order, cfg.epochs, cfg.seed
        self._n_train = n - int(n * cfg.eval_fraction)
        self._carry = np.random.default_rng(cfg.seed + 1) if carried else None
        self._threads = ThreadPoolExecutor(max_workers=2, thread_name_prefix=self._phase.name)
        self._split = self._threads.submit(self._draw, _split_eval, n, cfg.eval_fraction, cfg.seed)
        self._ahead: dict = {}  # epoch -> its permutation's future: the one being drawn, or drawn and not taken yet
        self._start(0)

    def _draw(self, draw, *args):
        with self._phase:
            return draw(*args)

    def _start(self, epoch: int) -> None:
        if epoch < self._epochs and epoch not in self._ahead:
            rng = self._carry or np.random.default_rng(self._seed + 1 + epoch)
            self._ahead[epoch] = self._threads.submit(self._draw, _permutation, rng, self._n_train)

    def is_for(self, n: int, cfg: FitConfig) -> bool:
        """Whether a fit of ``n`` rows under ``cfg`` draws this order."""
        return self._drawn_for == (n, cfg.eval_fraction, cfg.seed, cfg.epochs)

    def split(self) -> tuple[np.ndarray, np.ndarray]:
        """``_split_eval(n, eval_fraction, seed)``: the rows to train on
        and the holdout's, waited for."""
        return self._split.result()

    def epoch(self, epoch: int) -> np.ndarray:
        """Epoch ``epoch``'s permutation of the training rows, waited
        for, and the next epoch's begun. (A fit resumed from a
        checkpoint asks for a later epoch first: that one is drawn
        then.)"""
        self._start(epoch)
        perm = self._ahead.pop(epoch).result()
        self._start(epoch + 1)
        return perm

    def close(self) -> None:
        """No draw begins after this and none is referenced from here;
        a thread in the middle of one ends with it. Not waited for: a
        fit that failed does not sit out a shuffle it will not use."""
        self._epochs = 0
        self._threads.shutdown(wait=False, cancel_futures=True)
        self._split = None
        self._ahead.clear()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


# eval forwards ride the shared memoized jit (utils.jitcache.jit_once);
# this local cache only keys the (mesh, axis)-specific sharded forward
_jit_cache: dict = {}


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
# long. A fit's columns go to the chip once a fit, in the order the caller
# handed them, a slice of FEED_SLICE_BYTES a put (contiguous views of the
# caller's arrays: the host copies nothing but the last slice's padding),
# each put landing while the next is handed over and waited for before a
# third is, and are packed there into the fit's table (_Table), which
# stays until the fit returns. An epoch is row numbers: its permutation
# comes from the fit's order (FitOrder, above: drawn on a thread of its
# own from the moment the row count is known, so by the time an epoch
# asks for it the host has it or is an epoch ahead with it), the host
# composes ``index[perm]`` a slice at a time, the slices
# go to the chip the same way (4 B a row where the columns themselves
# went before), and each step takes its batch from the table by them, on
# the chip. The epoch runs as one dispatch a slice, each waited for before
# the next is enqueued (the device's queue is first in, first out: a
# served forward enqueued behind a whole epoch waits for all of it,
# however the epoch is cut). A slice is as many steps as take
# FEED_SLICE_BYTES from the table, and at most EPOCH_SLICE_STEPS, the
# epoch's steps spread evenly over the slices that takes; every slice of
# an epoch has the same shape (the last is padded, by fewer steps than
# there are slices, with row 0, which no step reads), so a leg compiles
# one executable whatever its step count. The holdout's rows come from
# the same table by their numbers, a slice a dispatch. The steps, their
# order and their batches are those of one loop over the whole epoch
# gathered on the host.
FEED_SLICE_BYTES = 64 << 20  # one put: 10-17 ms of transfer on a v5e host
EPOCH_SLICE_STEPS = 512  # one dispatch: 20-30 ms of the chip at these models' steps
TABLE_LANES = 128  # a table row: one row of the chip's (8, 128) tile, 512 B


@jax.tree_util.register_pytree_node_class
class _Table:
    """A fit's columns on the chip, row for row as the caller handed
    them. A row's values (every column's, flattened; all of 32 bits) lie
    side by side as ``words`` words, and as many rows as fit share a
    table row of ``TABLE_LANES`` lanes (a row wider than that takes
    whole table rows of its own): XLA lays a ``[N, 19]`` array out with
    N along the lanes, from where a row comes element by element; from
    here it comes as one 512 B row of a tile and is picked out of it
    (PERF.md, PR 29: 118 us against 351 us for a batch of 8,192).
    ``columns`` is each column's ``(row shape, dtype)``."""

    def __init__(self, packed, columns: tuple):
        self.packed, self.columns = packed, columns

    def tree_flatten(self):
        return (self.packed,), self.columns

    @classmethod
    def tree_unflatten(cls, columns, children):
        return cls(children[0], columns)

    @staticmethod
    def geometry(columns: tuple) -> tuple[int, int, int]:
        """-> (words a row, rows a table row, lanes of a table row)."""
        words = sum(math.prod(shape) for shape, _ in columns)
        per_row = max(TABLE_LANES // words, 1)
        return words, per_row, -(-per_row * words // TABLE_LANES) * TABLE_LANES

    @property
    def row_bytes(self) -> int:
        return 4 * self.geometry(self.columns)[0]

    def take(self, rows) -> tuple:
        """Rows ``rows`` ([B] int32) of every column, ``(column[rows],
        ...)``, bit for bit: one gather of table rows, then each row's
        words moved to the front of its table row, by whole lanes and a
        select for every bit of its place there (three selects for the
        six places of the MLP's pairs, six for the 42 of an edge), and
        bitcasts."""
        words, per_row, _ = self.geometry(self.columns)
        picked = self.packed.at[rows // per_row].get(mode="promise_in_bounds")
        place = (rows % per_row)[:, None]
        bit = 1
        while bit < per_row:
            nearer = jnp.pad(picked[:, bit * words :], ((0, 0), (0, bit * words)))
            picked = jnp.where(place & bit != 0, nearer, picked)
            bit <<= 1
        out, at = [], 0
        for shape, dtype in self.columns:
            n = math.prod(shape)
            column = jax.lax.bitcast_convert_type(picked[:, at : at + n], dtype)
            out.append(column.reshape(rows.shape[0], *shape))
            at += n
        return tuple(out)


def _pack(packed, at, *parts):
    """Write a slice of the columns (``parts``: each column's rows, as
    they were put) into the table from table row ``at``."""
    columns = tuple((p.shape[1:], p.dtype) for p in parts)
    words, per_row, lanes = _Table.geometry(columns)
    rows = parts[0].shape[0]
    side_by_side = jnp.concatenate(
        [jax.lax.bitcast_convert_type(p, jnp.uint32).reshape(rows, -1) for p in parts], axis=1
    ).reshape(rows // per_row, per_row * words)
    block = jnp.pad(side_by_side, ((0, 0), (0, lanes - per_row * words)))
    return jax.lax.dynamic_update_slice(packed, block, (at, 0))


_pack_slice = jax.jit(_pack, donate_argnums=0)


def _on_mesh(mesh, a, spec=P()):
    """``a`` on the chip: explicitly (feeding numpy straight into a
    jitted call is an implicit transfer the jit witness, rightly,
    flags), and under a mesh with ``spec``."""
    return jnp.asarray(a) if mesh is None else jax.device_put(a, NamedSharding(mesh, spec))


def _table_slices(n: int, words: int, per_row: int) -> tuple[int, int]:
    """-> (puts, rows a put) for a table of ``n`` rows: the fewest slices
    under ``FEED_SLICE_BYTES``, the rows spread evenly over them in whole
    table rows."""
    slices = max(-(-n // (max(FEED_SLICE_BYTES // (4 * words) // per_row, 1) * per_row)), 1)
    return slices, -(-max(n, 1) // (slices * per_row)) * per_row


def resident_fit_bytes(n: int, *row_shapes: tuple) -> int:
    """What a resident fit of ``n`` rows holds on the chip at its
    fullest, reckoned from the row count alone (``row_shapes``: each
    column's row shape, 32 bits a value), before a byte of it is read:
    the table as ``_put_table`` lays it out (85.3 B a pair for the
    MLP's 19 features and a label, six pairs a 512 B row), an epoch's
    row numbers (4 B a row; the holdout's come a slice at a time and
    the epoch's are gone by then), and four slices of
    ``FEED_SLICE_BYTES`` for what passes through: two puts in flight
    and their packing while the table is laid, or the holdout's gathered
    slice and its forward. A week's 55,050,240 pairs reckon
    4,697,628,672 + 220,200,960 + 268,435,456 = 5,186,265,088 B
    (measured peak of such a round alone: 5.05 GB, PERF.md). The
    trainer admits rounds by this number (trainer/training.py
    ``RoundAdmission``), so it errs above what is measured, never
    under."""
    columns = tuple((tuple(shape), np.dtype(np.float32)) for shape in row_shapes)
    words, per_row, lanes = _Table.geometry(columns)
    slices, rows = _table_slices(n, words, per_row)
    return slices * rows // per_row * lanes * 4 + 4 * n + 4 * FEED_SLICE_BYTES


def _put_table(mesh, phases, *columns: np.ndarray) -> _Table:
    """The fit's table from its host columns: a put a slice of
    ``FEED_SLICE_BYTES`` (each column's rows ``[lo:hi]``, a view: nothing
    is copied on the host but the last slice, padded to the others'
    shape: one pack executable a fit), and one small dispatch that
    packs it. A put is asynchronous: it lands while the next is
    handed over, and is waited for before the one after that is, so the
    transfer queue never holds more than two (a served batch's put
    waits behind 128 MiB at most). ``phases.table_put`` is open from the
    first slice to the packed table's arrival, ``phases.feed_slice``
    inside it around each put and wait. Under a mesh every chip holds
    the table whole (the row numbers carry the batch's sharding)."""
    spec = tuple((c.shape[1:], jax.dtypes.canonicalize_dtype(c.dtype)) for c in columns)
    if any(dtype.itemsize != 4 for _, dtype in spec):
        raise TypeError(f"a fit's columns hold 32-bit values, not {[str(d) for _, d in spec]}")
    words, per_row, lanes = _Table.geometry(spec)
    n = len(columns[0])
    if n >= 1 << 31:
        raise ValueError(f"{n} rows: an epoch's row numbers are 32-bit")
    slices, rows = _table_slices(n, words, per_row)
    everywhere = None if mesh is None else NamedSharding(mesh, P())
    with phases.table_put:
        packed = jnp.zeros((slices * rows // per_row, lanes), jnp.uint32, device=everywhere)
        last = None
        for lo in range(0, n, rows):
            with phases.feed_slice:
                parts = []
                for c, (_, dtype) in zip(columns, spec):
                    part = c[lo : lo + rows]
                    if len(part) < rows:
                        part = np.concatenate([part, np.zeros((rows - len(part), *c.shape[1:]), c.dtype)])
                    parts.append(_on_mesh(mesh, part.astype(dtype, copy=False)))
                    phases.put_bytes.inc(parts[-1].nbytes)
                packed = _pack_slice(packed, lo // per_row, *parts)
                jax.block_until_ready(last)
                last = parts
        return _Table(jax.block_until_ready(packed), spec)


class _Epoch(tuple):
    """An epoch on the chip, as ``make_epoch_fn``'s function takes it:
    one entry a column of the fit's ``table`` (its row shape and type),
    the table itself, and ``rows``, the list of the epoch's row numbers
    in device slices ``[k, batch]`` int32; ``steps`` steps in all, so
    the last slice may hold fewer than ``k`` (its other rows are
    padding)."""

    table: _Table
    rows: list
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


def _gather_slices(index: np.ndarray, drawn: Callable[[], np.ndarray], steps: int, batch: int, row_bytes: int):
    """The epoch's row numbers as host slices ``[k, batch]`` int32 in
    step order, each composed when it is asked for (a generator; the
    epoch's permutation of ``index``, ``drawn()``, waited for on the
    first): row ``i`` of the epoch is row ``index[perm[i]]`` of the
    fit's table. ``k`` steps
    take ``FEED_SLICE_BYTES`` from a table of ``row_bytes`` a row at
    most; no index is longer than a slice (numpy checks every index
    under the interpreter lock before it copies without it, 0.11 s for
    an epoch's 49.5M); all slices have ``k`` steps, the last row 0 past
    the epoch's end."""
    k = _slice_steps(steps, batch * row_bytes)
    perm = drawn()
    for lo in range(0, steps, k):
        rows = np.zeros(k * batch, np.int32)
        part = perm[lo * batch : min(lo + k, steps) * batch]
        rows[: len(part)] = index[part]
        yield rows.reshape(k, batch)  # under no name here: it is the taker's to drop


def _feed_slices(mesh, host, table: _Table, steps: int, phases, axis: str = "dp") -> _Epoch:
    """Put an epoch's row numbers (``_gather_slices``) on the chip, one
    put a slice, two in flight at most as in ``_put_table``, the batch
    dimension sharded over the mesh's ``axis`` where it divides it (a
    batch clamped to a tiny dataset rarely does: those rows go
    replicated rather than fail the fit; one small fit doesn't need
    parallelism). Returns the epoch of ``steps`` steps over ``table``
    as ``make_epoch_fn`` takes it. ``phases`` is the leg's: ``gather``
    times the loop (the wait for the permutation, the row numbers, their puts),
    ``feed_slice`` inside it each slice's put and wait,
    ``feed`` times the wait for what had not landed when it ended."""
    fed: list = []
    with phases.gather:
        for rows in host:
            with phases.feed_slice:
                sharded = mesh is not None and rows.shape[1] % mesh.shape[axis] == 0
                fed.append(_on_mesh(mesh, rows, P(None, axis) if sharded else P()))
                phases.put_bytes.inc(rows.nbytes)
                del rows  # the transfer holds it until it is through
                jax.block_until_ready(fed[-2:-1])
    with phases.feed:
        jax.block_until_ready(fed)
    epoch = _Epoch(table.columns)
    epoch.table, epoch.rows, epoch.steps = table, fed, steps
    return epoch


def _take_holdout(predict, table: _Table, idx: np.ndarray, phases, *args) -> tuple:
    """``predict(*args, table, rows)`` over the table's rows ``idx``, as
    many a dispatch as a table slice holds, every dispatch of one shape
    (the last padded with row 0) and read back before the next is
    enqueued: the host arrays of what ``predict`` returns, row for row
    of ``idx``."""
    per = min(max(FEED_SLICE_BYTES // table.row_bytes, 1), len(idx))
    out = []
    for lo in range(0, len(idx), per):
        rows = np.zeros(per, np.int32)
        rows[: len(idx) - lo] = idx[lo : lo + per]
        phases.put_bytes.inc(rows.nbytes)
        out.append([np.asarray(a) for a in predict(*args, table, jnp.asarray(rows))])
    return tuple(np.concatenate(parts)[: len(idx)] for parts in zip(*out))


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
    ``_feed_slices`` returns. Each slice of row numbers is one dispatch
    of a jitted loop over its steps (as many as the epoch has left: a
    traced count, so the padded last slice runs on the same executable),
    each step's batch taken from the table by its row numbers, on the
    chip; the table is an argument of every dispatch (not donated, not
    closed over), and a slice is waited for before the next is enqueued.
    The carried state is not donated either: it is a quarter of a
    megabyte at most, and a carry aliased to the outputs has to live in
    HBM, where one left free XLA keeps in VMEM for the whole loop (a GRU
    slice of 503 steps over the table: 30.9 ms donated, 20.6 ms not;
    PERF.md, PR 29). The jitted slice
    takes its name from the loss's (``mlp_loss`` -> ``mlp_epoch``), so
    that a trace's host events (``PjitFunction(mlp_epoch)``), the XLA
    module and the scope of the step's ops say which leg they belong to;
    a leg's ``epoch_slice`` phase is open around each slice."""
    leg = getattr(loss_fn, "__name__", "loss").removesuffix("_loss")
    name = leg + "_epoch"
    slice_phase = LEG_PHASES[leg].epoch_slice if leg in LEG_PHASES else contextlib.nullcontext()

    def epoch_slice(params, opt_state, table, rows, steps):
        def body(i, carry):
            with jax.named_scope(name):
                params, opt_state, loss_sum = carry
                loss, grads = jax.value_and_grad(loss_fn)(params, table.take(rows[i]))
                updates, opt_state = optimizer.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
            return params, opt_state, loss_sum + loss

        return jax.lax.fori_loop(0, steps, body, (params, opt_state, jnp.zeros((), jnp.float32)))

    epoch_slice.__name__ = epoch_slice.__qualname__ = name
    run_slice = jax.jit(epoch_slice)

    def epoch(params, opt_state, batches):
        left, loss_sum = batches.steps, 0.0
        for rows in batches.rows:
            with slice_phase:
                steps = min(rows.shape[0], left)
                params, opt_state, part_sum = run_slice(params, opt_state, batches.table, rows, steps)
                loss_sum += float(part_sum)  # the wait: nothing is queued behind a running slice
                left -= steps
        return params, opt_state, loss_sum / batches.steps

    def lower(params, opt_state, batches):
        """The lowering of the epoch's slice, for a reader of its HLO."""
        return run_slice.lower(params, opt_state, batches.table, batches.rows[0], batches.steps)

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
    order: FitOrder | None = None,
) -> FitResult:
    """Fit the pair scorer: features [N, F] → label log piece cost [N].

    Evaluation metrics are MSE/MAE, matching what the manager stores with
    an MLP model upload (reference manager_server_v1.go:847-851).
    ``order`` is the fit's order where the caller began it before it had
    the arrays (``FitOrder``); handed none, or one that was drawn for
    other than these ``N`` rows and this ``config``, the fit begins its
    own here.
    """
    cfg = config or FitConfig()
    n, f = features.shape
    if order is not None and not order.is_for(n, cfg):
        # row numbers past the table would be clamped on the chip, not refused
        order.close()
        order = None
    with order or FitOrder(PH_MLP, n, cfg) as order:
        with PH_MLP.split:
            train_idx, eval_idx = order.split()
        steps, _, batch = _batch_steps(len(train_idx), cfg.batch_size)

        key = jax.random.PRNGKey(cfg.seed)
        params = mlp_mod.init_mlp(key, [f, *cfg.hidden_dims, 1])
        # warm-start the output bias at the label mean — the regression head
        # starts unbiased instead of spending its first epochs drifting there
        params["layers"][-1]["b"] = _head_bias(labels)
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
        table = _put_table(mesh, PH_MLP, features, labels)  # once a fit: every epoch and the holdout take from it

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
                batches = None  # the chip holds one epoch's row numbers: the last go before the next are fed
                host = _gather_slices(train_idx, partial(order.epoch, epoch), steps, batch, table.row_bytes)
                batches = _feed_slices(mesh, host, table, steps, PH_MLP)
                with PH_MLP.epoch_dispatch:
                    params, opt_state, mean_loss = epoch_fn(params, opt_state, batches)
                with PH_MLP.epoch_wait:
                    history.append(float(mean_loss))
                _maybe_save_tree(ckpt, cfg, epoch, {"params": params, "opt_state": opt_state})

            metrics = {}
            if len(eval_idx):
                with PH_MLP.holdout:
                    metrics = _mlp_error(params, table, eval_idx)
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


def _mlp_holdout(params, table: _Table, rows):
    x, y = table.take(rows)
    return mlp_mod.score_parents(params, x), y


def _mlp_error(params, table: _Table, idx: np.ndarray) -> dict[str, float]:
    """The error over the table's rows ``idx``: their features and
    labels taken on the chip, a slice a forward (``_take_holdout``)."""
    pred, y = _take_holdout(jit_once(_mlp_holdout), table, idx, PH_MLP, params)
    err = pred - y
    return {"mse": float(np.mean(err**2)), "mae": float(np.mean(np.abs(err)))}


def evaluate_mlp(params, features: np.ndarray, labels: np.ndarray) -> dict[str, float]:
    """The error of ``params`` on a set of its own: put as a fit's table
    is (``_put_table``), every row scored in order."""
    table = _put_table(None, PH_MLP, features, labels)
    return _mlp_error(params, table, np.arange(len(features)))


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
    params["head"]["layers"][-1]["b"] = _head_bias(graph.edge_rtt_log_ms)
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
    with FitOrder(PH_GNN, e, cfg) as order:
        with PH_GNN.split:
            train_idx, eval_idx = order.split()
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
        # never sharded: the edges and their batches are replicated beside the graph
        table = _put_table(None, PH_GNN, graph.edge_src, graph.edge_dst, graph.edge_rtt_log_ms)

        ckpt, start_epoch = _open_checkpoint(cfg)
        try:
            if ckpt is not None and start_epoch > 0:
                restored = ckpt.restore_latest({"params": params, "opt_state": opt_state})
                if restored is not None:
                    _, state = restored
                    params, opt_state = state["params"], state["opt_state"]

            history: list[float] = []
            for epoch in range(start_epoch, cfg.epochs):
                host = _gather_slices(train_idx, partial(order.epoch, epoch), steps, batch, table.row_bytes)
                batches = _feed_slices(None, host, table, steps, PH_GNN)
                with PH_GNN.epoch_dispatch:
                    params, opt_state, mean_loss = epoch_fn(params, opt_state, batches)
                with PH_GNN.epoch_wait:
                    history.append(float(mean_loss))
                _maybe_save_tree(ckpt, cfg, epoch, {"params": params, "opt_state": opt_state})

            metrics: dict[str, float] = {}
            if len(eval_idx):
                with PH_GNN.holdout:
                    metrics = _gnn_error(params, graph, (node_features, neighbors, neighbor_mask), table, eval_idx)
            _finish_checkpoint(ckpt)
            ckpt = None
            # the learned rows are one a host, in this graph's order: the
            # version names them, so that it can be served on another graph
            params = {**params, "node_ids": gnn_mod.NodeIds(graph.node_ids)}
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
    with FitOrder(PH_GNN, e, replace(cfg, epochs=0)) as order:  # full-batch steps: the holdout's draw alone
        _, eval_idx = order.split()
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
    out_params["node_ids"] = gnn_mod.NodeIds(graph.node_ids)
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


def _gnn_holdout(params, node_features, neighbors, neighbor_mask, table: _Table, rows):
    src, dst, y = table.take(rows)
    return gnn_mod.forward_edge_rtt(params, node_features, neighbors, neighbor_mask, src, dst), y


def _gnn_error(params, graph, on_chip: tuple, table: _Table, edge_idx: np.ndarray) -> dict[str, float]:
    """The evaluation over the edge table's rows ``edge_idx``, taken on
    the chip; ``on_chip`` is the graph's node arrays there."""
    pred, y = _take_holdout(jit_once(_gnn_holdout), table, edge_idx, PH_GNN, params, *on_chip)
    return _edge_metrics(pred, y, float(np.median(graph.edge_rtt_log_ms)))


def evaluate_gnn(params, graph, edge_idx: np.ndarray) -> dict[str, float]:
    on_chip = tuple(jnp.asarray(a) for a in (graph.node_features, graph.neighbors, graph.neighbor_mask))
    table = _put_table(None, PH_GNN, graph.edge_src, graph.edge_dst, graph.edge_rtt_log_ms)
    return _gnn_error(params, graph, on_chip, table, edge_idx)


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
    # one generator for the fit, carried from epoch to epoch: each draw waits for the one before
    with FitOrder(PH_GRU, n, cfg, carried=True) as order:
        with PH_GRU.split:
            train_idx, eval_idx = order.split()
        if lengths is None:
            lengths = np.full((n,), t, np.int32)

        key = jax.random.PRNGKey(cfg.seed)
        params = gru_mod.init_gru(key, f, cfg.hidden_dims[0])
        params["head"]["layers"][-1]["b"] = _head_bias(labels)
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
        table = _put_table(mesh, PH_GRU, sequences, labels, lengths)

        history: list[float] = []
        for epoch in range(cfg.epochs):
            batches = None  # the chip holds one epoch's row numbers: the last go before the next are fed
            host = _gather_slices(train_idx, partial(order.epoch, epoch), steps, batch, table.row_bytes)
            batches = _feed_slices(mesh, host, table, steps, PH_GRU)
            with PH_GRU.epoch_dispatch:
                params, opt_state, mean_loss = epoch_fn(params, opt_state, batches)
            with PH_GRU.epoch_wait:
                history.append(float(mean_loss))

        metrics: dict[str, float] = {}
        if len(eval_idx):
            with PH_GRU.holdout:
                pred, y = _take_holdout(jit_once(_gru_holdout), table, eval_idx, PH_GRU, params)
                err = pred - y
                metrics = {"mse": float(np.mean(err**2)), "mae": float(np.mean(np.abs(err)))}
        return FitResult(params=params, metrics=metrics, history=history)


def _gru_holdout(params, table: _Table, rows):
    x, y, lengths = table.take(rows)
    return gru_mod.predict_next_cost(params, x, lengths), y
