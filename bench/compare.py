"""The numbers that decide ``correct``: how far a call's outputs lie from
the plain reference's, round by round.

Per round ``k`` (named ``<number>.r<k>``) and as the worst over every round
(named ``<number>``):

- ``loss_gap``: every client's tapped train loss (the mean of its minibatch
  losses; the target's is its post-aggregation pass under pFedWN), as the
  gap to the reference's loss over the larger of that loss and the round's
  median loss; the worst client.
- ``loss_shift``: the same relative gaps, signed, and their median over
  the clients. Rounding moves single clients' trajectories either way; a
  lower precision, a lost update or half a batch moves them all one way.
  The median is not swayed by the few clients whose trajectories have come
  apart furthest.
- ``target_loss_gap``: the target's relative gap alone (under pFedWN its
  post-aggregation pass, which starts from the EM-weighted mix).
- ``mean_acc_gap``, ``target_acc_gap``: the eval's accuracies.
- ``pi_gap``: the largest gap of any EM weight π (pFedWN).
- ``link_gap``: the gap of the link success rate, which the erasure draws
  alone decide (pFedWN); an exact comparison.

A number the cell's limits file does not name is printed but not compared.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

FAR = 1e300


def _gap(v) -> float:
    """A gap, with anything not finite (a NaN loss, a missing answer) read
    as infinitely far."""
    v = float("inf") if v is None else float(v)
    return v if np.isfinite(v) else float("inf")


def round_numbers(p: Dict, r: Dict) -> Dict[str, float]:
    """The numbers of one round: the program's outputs ``p`` against the
    reference's ``r``."""
    lp, lr = np.asarray(p["train_loss"]), np.asarray(r["train_loss"])
    if lp.shape != lr.shape:
        return {"loss_gap": float("inf")}
    rel = (lp - lr) / np.maximum(np.abs(lr), np.median(np.abs(lr)))
    out = {"loss_gap": _gap(np.max(np.abs(rel))),
           "loss_shift": _gap(abs(np.median(rel))),
           "target_loss_gap": _gap(abs(rel[0]))}
    for key, name in (("target_acc", "target_acc_gap"),
                      ("mean_participant_acc", "mean_acc_gap")):
        v = p[key]
        out[name] = _gap(None if v is None else abs(v - r[key]))
    if r["pi"] is not None and r["pi"].size:
        pp = p["pi"]
        out["pi_gap"] = _gap(None if pp is None or pp.shape != r["pi"].shape
                             else np.max(np.abs(pp - r["pi"])))
        out["link_gap"] = _gap(abs(p["link_success_rate"]
                                   - r["link_success_rate"]))
    return out


def numbers(prog: List[Dict], ref: List[Dict]) -> Dict[str, float]:
    if len(prog) != len(ref):
        return {"rounds_missing": float(abs(len(ref) - len(prog)))}
    out: Dict[str, float] = {}
    for k, (p, r) in enumerate(zip(prog, ref), start=1):
        for name, v in round_numbers(p, r).items():
            out[f"{name}.r{k}"] = v
            out[name] = max(out.get(name, 0.0), v)
    return out


def worst(per_call: List[Dict[str, float]]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for nums in per_call:
        for k, v in nums.items():
            out[k] = max(out.get(k, 0.0), v)
    return out


def checks(nums: Dict[str, float], limits: Dict[str, float]
           ) -> Dict[str, Dict[str, float]]:
    """``{name: {"value", "limit"}}`` for every number the limits name; a
    number the run did not produce, or one not finite, reads as 1e300 (JSON
    has no infinity)."""
    return {k: {"value": min(float(nums.get(k, FAR)), FAR),
                "limit": float(lim)} for k, lim in limits.items()}


def passes(nums: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks(nums, limits).values())
