#!/usr/bin/env python3
"""A control of ``train-rounds-three-schedulers``: the program with one
piece replaced in the process, which has to come out as not correct by
the harness's own comparison.

    python3 benchmarks/tools/controls_three_schedulers.py --control exchanged --seed 7 --seconds 51
    python3 benchmarks/tools/controls_three_schedulers.py --control admission_off --chunks-per-upload 20 --rounds-held 2.5 --seed 7 --seconds 51
    python3 benchmarks/tools/controls_three_schedulers.py --control plain_mean --chunks-per-upload 20,20,10 --seed 7 --seconds 51

``exchanged``: schedulers 0 and 1 hold each other's uploads on the
trainer's disk while the reference keeps who sent what: their per-host
gaps fail, scheduler 2's hold. ``admission_off``: ``RoundAdmission.wait``
admits whoever arrives: ``rounds_admitted_beyond_limit`` by the
program's own gauges. At the cell's size three loads' pairs (13.8 GB)
beside 22.5 GiB of files do not fit the host's 40 GiB (a builder's chip run of the refused PR 44:
ended in the warm-up cadence, before a check was read), so the fault is
planted where the host holds it: smaller uploads and, with
``--rounds-held X``, a device limit handed to the trainer and to the
reference alike under which ``X`` of the largest upload's rounds fit (2.5:
the rule must refuse the third, as ``--control none`` at the same size
shows, and ``admission_off`` exceeds it). ``plain_mean``: the merge
forgets its weights; with uneven uploads (``--chunks-per-upload A,B,C``)
``merged_gap`` fails, and nothing else. ``--control none`` runs the
program as it is, at that size. The rest is ``benchmarks/run.py``'s own
run, argument for argument (the replaced pieces are those of
``benchmarks/tests/test_train_rounds_three_schedulers.py``).
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.normpath(os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))
sys.path.insert(0, ROOT)


def _take(flag: str) -> "str | None":
    if flag not in sys.argv:
        return None
    at = sys.argv.index(flag)
    value = sys.argv[at + 1]
    del sys.argv[at : at + 2]
    return value


def main() -> int:
    control, chunks, held = _take("--control"), _take("--chunks-per-upload"), _take("--rounds-held")

    from benchmarks import run as bench_run
    from benchmarks.generators import rounds_by_scheduler as gen
    from benchmarks.harness import admission as plain
    from benchmarks.harness import cells
    from benchmarks.tests import test_train_rounds_three_schedulers as pieces
    from dragonfly2_tpu.trainer import federation as federation_mod
    from dragonfly2_tpu.trainer import training as training_mod

    if chunks is not None:
        per = [int(c) for c in chunks.split(",")]
        load = cells.load_cell

        def load_cell(name, *a, **kw):
            cell = load(name, *a, **kw)
            cell.traffic["chunks_per_upload"] = per if len(per) > 1 else per[0]
            return cell

        cells.load_cell = load_cell
    if held is not None:
        cell = cells.load_cell(pieces.CELL)
        mix, stated = cell.traffic, cell.config["admission"]
        scale = cell.config["scale"]  # a record's pairs (its parents) are the deployment's: four
        pairs = scale["pairs_per_round"] // scale["records_per_round"] * mix["body_records"] * mix["body_repeats_per_chunk"] * max(gen.chunks_of(mix))
        limit = stated["reserve_bytes"] + int(float(held) * plain.round_bytes(pairs, stated))
        training_mod._device_bytes_limit = lambda mesh: limit
        gen.device_bytes_limit = lambda devices: limit
        print(f"device limit handed in: {limit} B ({held} rounds of {pairs} pairs over the reserve)", flush=True)

    if control == "exchanged":
        real = gen.stage_all
        gen.stage_all = lambda *a: pieces.exchange_uploads(real(*a), 0, 1)
    elif control == "admission_off":
        training_mod.RoundAdmission.wait = pieces.admit_all
    elif control == "plain_mean":
        federation_mod.fedavg_trees = pieces.plain_mean
    elif control != "none":
        raise SystemExit(f"no control {control!r}: exchanged, admission_off, plain_mean or none")
    print(f"control: {control}", flush=True)
    return bench_run.main(["--workload", pieces.CELL, *sys.argv[1:]])


if __name__ == "__main__":
    sys.exit(main())
