"""The comparison that decides ``correct``: the timed path's numbers
against the plain reference's, each beside a limit of its own. Imports no
JAX."""

from __future__ import annotations

import statistics

# A leaf whose reference gradient is under this share of the median
# leaf's is nought to rounding (a key's bias under softmax): under Adam it
# moves by round-off alone, so its change is not compared.
NOUGHT_GRADIENT = 1e-3


def _worst_leaf(prog: dict, ref: dict, leaves) -> float:
    """Largest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger."""
    floor = statistics.median(ref.values())
    return max(abs(prog[k] - ref[k]) / max(ref[k], floor) for k in leaves)


def train_numbers(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref``: ``{"losses", "grad_norms", "change_norms"}``.
    Returns the numbers compared, by their short names."""
    if set(prog["grad_norms"]) != set(ref["grad_norms"]):
        raise ValueError("program and reference disagree on the leaves")
    median_grad = statistics.median(ref["grad_norms"].values())
    moved = [k for k, g in ref["grad_norms"].items()
             if g >= NOUGHT_GRADIENT * median_grad]
    return {
        "loss_gap": max(abs(p - r) / abs(r)
                        for p, r in zip(prog["losses"], ref["losses"])),
        "grad_gap": _worst_leaf(prog["grad_norms"], ref["grad_norms"],
                                ref["grad_norms"]),
        "update_gap": _worst_leaf(prog["change_norms"], ref["change_norms"],
                                  moved),
    }


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Every limit named in the cell's files has to be met by a number
    that is there and finite. Returns ``(correct, compared)`` where
    ``compared`` is ``{name: {"value", "limit"}}``."""
    compared = {}
    correct = bool(limits)
    for name, limit in limits.items():
        value = numbers.get(name)
        compared[name] = {"value": value, "limit": limit}
        if value is None or not value == value or not value <= limit:
            correct = False
    return correct, compared
