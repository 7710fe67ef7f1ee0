"""The merge of a cadence: the MLP versions the schedulers' rounds just
fitted → one example-weighted FedAvg model (SURVEY §7 stage 7).

The trainer's storage keys dataset files by uploading scheduler host
(reference trainer/storage/storage.go:141-148); each host's round fits
that host's records alone, and a merged model generalizes across them
without their raw records ever being pooled. Nothing is fitted here and
nothing is read from storage: a round clears the upload it consumed, and
what it fitted is what is averaged (parallel/fedavg.fedavg_trees; the
in-mesh psum variant rides a ``fed`` mesh axis, exercised in
__graft_entry__.dryrun_multichip). The merged model is scored on rows
of every host's holdout that each round keeps past its upload.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from dragonfly2_tpu.parallel.fedavg import fedavg_trees
from dragonfly2_tpu.trainer.train import evaluate_mlp


@dataclass
class FittedVersion:
    """A host's newest fitted MLP version, as its round registered it."""

    params: object  # host arrays
    pairs: int  # the pairs of the upload it was fitted on: its weight
    # rows of its own holdout, (features, labels), kept for the merge
    # (``holdout_sample``); None where the fit never had its pairs in hand
    holdout: "tuple[np.ndarray, np.ndarray] | None" = None


def holdout_sample(features: np.ndarray, labels: np.ndarray, held: np.ndarray) -> tuple:
    """The rows of a fit's holdout ``held`` (row numbers, in the drawn
    order) that outlive its upload: the first 64th of them, so that the
    hosts' samples pooled weigh each host by its pairs, and no fewer
    than 1,024 (all of a smaller holdout). A week's 5,505,024 held rows
    leave 86,016: 6.9 MB."""
    rows = np.sort(held[: max(len(held) // 64, min(len(held), 1024))])
    return features[rows], labels[rows]


def merge_versions(fitted: dict[str, FittedVersion]) -> tuple[object, dict[str, float]]:
    """-> (the pair-weighted mean of the hosts' parameters, its
    evaluation: the merged parameters' own error on the hosts' kept
    holdout rows, pooled, beside the hosts, pairs and rows behind it).
    Where no host kept a row the error is left out, not stood in for."""
    if not fitted:
        raise ValueError("no fitted version to merge")
    versions = [fitted[host_id] for host_id in sorted(fitted)]
    weights = [float(v.pairs) for v in versions]
    merged = fedavg_trees([v.params for v in versions], weights)
    held = [v.holdout for v in versions if v.holdout is not None and len(v.holdout[1])]
    evaluation: dict[str, float] = {}
    if held:
        evaluation = evaluate_mlp(merged, np.concatenate([x for x, _ in held]), np.concatenate([y for _, y in held]))
    evaluation.update(hosts=float(len(versions)), pairs=sum(weights), holdout_rows=float(sum(len(y) for _, y in held)))
    return merged, evaluation
