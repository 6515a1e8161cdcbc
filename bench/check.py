"""The comparison that decides ``correct``.

Set-up drives the trainer through its first rounds -- the same object,
through the same ``run_round`` the window then drives -- and keeps the
weights after each, and each round's schedule. Once the window has
closed, the reference (``bench/reference.py``) follows the same rounds
from the same weights. A round is the program's step; its server update
is what the optimizer of the server would see. The numbers compared:

* ``schedule_mismatch``: mediators (client lists, in order) of the checked
  rounds that differ from the reference's Alg. 3 / cohort draw. Exact.
* ``loss_gap``: the largest gap, over the checked rounds, between the
  test loss of the program's weights and of the reference's, over the
  test loss of the initial weights; all three evaluated by the
  reference's forward in float32 at ``highest`` precision (the round
  program reports no loss of its own, and a loss that training drives
  towards 0 makes a gap relative to itself meaningless).
* ``update1_gap``: the first round's server update, per leaf: the gap
  between the norm of the program's change of that leaf and the norm of
  the reference's, over the larger of that reference norm and the median
  leaf's. Worst leaf.
* ``update3_gap``: the same for the change after the last checked round.

Leaves whose first reference update is under a thousandth of the median
leaf's are left out of both update numbers (none is, in these models).
"""
from __future__ import annotations

import math

import jax
import numpy as np

NUMBERS = ("schedule_mismatch", "loss_gap", "update1_gap", "update3_gap")


def _leaf_norms(a, b) -> np.ndarray:
    return np.array([float(np.linalg.norm((x - y).astype(np.float64)))
                     for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])


def _update_gap(prog, ref, p0, keep) -> float:
    pn, rn = _leaf_norms(prog, p0), _leaf_norms(ref, p0)
    denom = np.maximum(rn, np.median(rn))
    gaps = np.abs(pn - rn) / denom
    return float(gaps[keep].max())


def compare(prog_params: list, prog_sched: list, ref_params: list,
            ref_sched: list, params0, losses) -> dict:
    """The numbers above. ``losses(params) -> float`` is the reference's
    test loss; the three lists hold one entry per checked round."""
    mismatch = sum(int(a != b) for pa, pb in zip(prog_sched, ref_sched)
                   for a, b in zip(pa, pb))
    mismatch += sum(abs(len(pa) - len(pb))
                    for pa, pb in zip(prog_sched, ref_sched))
    first = _leaf_norms(ref_params[0], params0)
    keep = first >= 1e-3 * np.median(first)
    loss_gap = 0.0
    l0 = losses(params0)
    for p, r in zip(prog_params, ref_params):
        gap = abs(losses(p) - losses(r)) / max(abs(l0), 1e-12)
        loss_gap = max(loss_gap, gap if math.isfinite(gap) else math.inf)
    out = {"schedule_mismatch": float(mismatch),
           "loss_gap": loss_gap,
           "update1_gap": _update_gap(prog_params[0], ref_params[0],
                                      params0, keep),
           "update3_gap": _update_gap(prog_params[-1], ref_params[-1],
                                      params0, keep)}
    return {k: (v if math.isfinite(v) else math.inf) for k, v in out.items()}


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})``: correct when every
    number is finite and at or under its limit."""
    table = {k: {"value": numbers[k], "limit": limits[k]} for k in NUMBERS}
    ok = all(math.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in table.values())
    return ok, table
