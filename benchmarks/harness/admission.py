"""The plain reckoning of which rounds share a chip: what a resident
round holds there, from its pair count alone, and who is admitted when,
from the rounds' sizes, the order they arrived in and the chip's limit.
Written from the deployment's statement (``configs/three-scheduler-cluster.json``
``admission``) and the table's layout, not from the trainer's code: the
same cadence has to come out the same way here and there.
"""

from __future__ import annotations

PAIR_WORDS = 20  # 19 features and a label, 32 bits each
ROW_BYTES = 512  # a table row: 128 lanes of 32 bits
PAIRS_A_ROW = ROW_BYTES // 4 // PAIR_WORDS  # 6: a pair never straddles a row


def round_bytes(pairs: int, stated: dict) -> int:
    """A resident round at its fullest: the table (laid a slice of
    ``slice_bytes`` of host columns at a time, the pairs spread evenly
    over the fewest slices in whole rows), 4 B a pair of row numbers,
    ``slices_in_flight`` slices passing through, and the two small fits'
    allowance."""
    most = stated["slice_bytes"] // (4 * PAIR_WORDS) // PAIRS_A_ROW * PAIRS_A_ROW  # pairs a slice at most
    slices = max(-(-pairs // most), 1)
    a_slice = -(-max(pairs, 1) // (slices * PAIRS_A_ROW)) * PAIRS_A_ROW
    table = slices * a_slice // PAIRS_A_ROW * ROW_BYTES
    return table + 4 * pairs + stated["slices_in_flight"] * stated["slice_bytes"] + stated["small_fits_bytes"]


def budget(bytes_limit: "int | None", stated: dict) -> "int | None":
    return None if bytes_limit is None else bytes_limit - stated["reserve_bytes"]


def replay(rounds: list, room: "int | None") -> list:
    """``rounds``: ``(key, arrived_at, returned_at, bytes)``. First come,
    first admitted: a round goes onto the chip when it stands first in
    line and fits ``room`` beside those running, and always when none
    runs. -> ``(key, waited)`` in the order of admission. A round that
    waits is admitted at a return: ``returned_at`` is the program's own
    clock, so the replay says who goes next, not when."""
    events = sorted(
        [(at, 1, key) for key, at, _, _ in rounds] + [(back, 0, key) for key, _, back, _ in rounds]
    )  # at one instant a return before an arrival
    size = {key: nbytes for key, _, _, nbytes in rounds}
    line, running, admitted, waited = [], [], [], set()
    for _, arrives, key in events:
        if arrives:
            line.append(key)
        elif key in running:
            running.remove(key)
        else:  # it returned without ever being admitted here: the replay and the program disagree
            admitted.append((key, True))
            line.remove(key)
        while line:
            head = line[0]
            if running and room is not None and sum(size[k] for k in running) + size[head] > room:
                waited.update(line)
                break
            running.append(line.pop(0))
            admitted.append((head, head in waited))
    return admitted
