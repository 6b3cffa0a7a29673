"""The numbers that decide ``correct``, and their limits.

Both sides are given in one form: per step the behaviour log-probs of the
generated tokens, the reference log-probs ExpPrep produced, the
advantages and the loss; the norms of the first clipped gradient, per
leaf; and the norms of the weights' change over the first steps, per
leaf. ``readings`` compares one side (the program, or the control) with
the float32 reference:

  behaviour_lp_gap  widest gap of a generated token's log-prob
  ref_lp_gap        widest gap of a reference log-prob at a generated token
  advantage_gap     widest gap of an advantage
  loss_gap          widest gap of a step's loss
  grad0_gap         worst leaf: |norm(program) - norm(reference)| of the
                    first gradient, over max(reference leaf, median leaf)
  change_gap        the same for the change of the weights over the steps

Leaves whose first reference gradient is under a thousandth of the median
leaf's are left out of both norms: Adam moves them by round-off alone.
"""
from __future__ import annotations

import numpy as np

NUMBERS = ("behaviour_lp_gap", "ref_lp_gap", "advantage_gap", "loss_gap",
           "grad0_gap", "change_gap")
LEAF_FLOOR = 1e-3


def _norm_gap(side: dict, ref: dict, keep) -> float:
    med = float(np.median([ref[k] for k in keep]))
    gap = 0.0
    for k in keep:
        d = abs(side[k] - ref[k])
        den = max(ref[k], med)
        gap = max(gap, d / den if den > 0 else (np.inf if d > 0 else 0.0))
    return gap


def readings(side_steps, side_g0, side_change, ref_steps, ref_g0,
             ref_change, gen_masks) -> dict:
    lp = rlp = adv = loss = 0.0
    for s, r, gen in zip(side_steps, ref_steps, gen_masks):
        mask = np.asarray(gen)[:, 1:].astype(bool)
        lp = max(lp, float(np.max(np.abs(s["lp"] - r["lp"])[mask])))
        rlp = max(rlp, float(np.max(np.abs(s["ref_lp"] - r["ref_lp"])[mask])))
        adv = max(adv, float(np.max(np.abs(s["adv"] - r["adv"]))))
        loss = max(loss, abs(s["loss"] - r["loss"]))
    med = float(np.median(list(ref_g0.values())))
    keep = [k for k, v in ref_g0.items() if v >= LEAF_FLOOR * med]
    return {"behaviour_lp_gap": lp, "ref_lp_gap": rlp, "advantage_gap": adv,
            "loss_gap": loss, "grad0_gap": _norm_gap(side_g0, ref_g0, keep),
            "change_gap": _norm_gap(side_change, ref_change, keep)}


def verdict(values: dict, limits: dict):
    """(correct, lines): every compared number at or under its limit. A
    number whose limit is None is printed and not compared."""
    lines = [f"{k} {values[k]!r} limit "
             + ("none (not compared)" if limits[k] is None
                else repr(limits[k])) for k in NUMBERS]
    ok = all(np.isfinite(values[k]) and values[k] <= limits[k]
             for k in NUMBERS if limits[k] is not None)
    return bool(ok), lines
