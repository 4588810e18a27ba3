"""The numbers ``correct`` compares.  Each is a gap between what the timed
path produced and what the plain reference gives for the same seed; its limit
sits in ``limits/<cell>.json`` with the readings it was set from in PERF.md.
"""

from __future__ import annotations

import statistics

ZERO_GRADIENT_SHARE = 1e-3  # of the median leaf's gradient norm


def leaf_gaps(program: dict, reference: dict, leaves) -> dict:
    """Per leaf: ``|program norm - reference norm|`` measured against the
    reference's norm of that leaf or of the median leaf, whichever is larger."""
    median = statistics.median(reference[k] for k in leaves)
    return {k: abs(program[k] - reference[k]) / max(reference[k], median) for k in leaves}


def _worst_leaf_gap(program: dict, reference: dict, leaves) -> tuple:
    """The largest of ``leaf_gaps`` and its leaf."""
    gaps = leaf_gaps(program, reference, leaves)
    where = max(gaps, key=gaps.get)
    return gaps[where], where


def train_numbers(program: dict, reference: dict) -> tuple:
    """``program`` and ``reference``: ``{"losses": [l1, l2, l3], "grad_norms":
    {leaf: norm of step 1's gradient}, "update_norms": {leaf: norm of the
    parameters' change over the steps}}``.

    Leaves whose reference gradient is nought to rounding (under a thousandth
    of the median leaf's) move under Adam by round-off alone and are left out
    of the change.  Returns (numbers, notes)."""
    leaves = sorted(reference["grad_norms"])
    if sorted(program["grad_norms"]) != leaves or sorted(program["update_norms"]) != leaves:
        raise ValueError("program and reference do not have the same parameter leaves")
    loss_gap = max(
        abs(p - r) / abs(r) for p, r in zip(program["losses"], reference["losses"])
    )
    grad_gap, grad_leaf = _worst_leaf_gap(program["grad_norms"], reference["grad_norms"], leaves)
    median_grad = statistics.median(reference["grad_norms"].values())
    moved = [k for k in leaves if reference["grad_norms"][k] >= ZERO_GRADIENT_SHARE * median_grad]
    upd_gap, upd_leaf = _worst_leaf_gap(program["update_norms"], reference["update_norms"], moved)
    numbers = {"loss_gap": loss_gap, "grad_norm_gap": grad_gap, "update_norm_gap": upd_gap}
    notes = {
        "grad_norm_gap_leaf": grad_leaf, "update_norm_gap_leaf": upd_leaf,
        "leaves": len(leaves), "leaves_left_out_of_update": len(leaves) - len(moved),
        "losses_program": program["losses"], "losses_reference": reference["losses"],
        "grad_leaf_gaps": leaf_gaps(program["grad_norms"], reference["grad_norms"], leaves),
        "update_leaf_gaps": leaf_gaps(program["update_norms"], reference["update_norms"], moved),
    }
    return numbers, notes


def serve_numbers(gaps_by_request: list, unfinished: int) -> dict:
    """``gaps_by_request``: for each sampled request, the vector of how far the
    reference's logit of each served token lies below the reference's best."""
    widest = max((float(g.max()) for g in gaps_by_request if len(g)), default=float("nan"))
    return {"served_logit_gap": widest, "unfinished_requests": float(unfinished)}
