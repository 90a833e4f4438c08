"""The comparison that decides ``correct``: what the timed units produced
against the plain reference, number by number, each beside its limit.

Every number is a gap relative to the reference (0 = identical):

- ``fe_coef``: worst fixed-effect model of the sweep, |w - w_ref| / |w_ref|.
- ``re_coef``: worst random-effect coordinate, Frobenius gap of the [E, K]
  tables aligned by entity id (an entity the program gave no row counts as 0).
- ``re_entity``: the worst single entity of any random effect, its gap
  measured against its own reference norm or the median entity's, whichever is
  larger (some entities' coefficients are all but zero).
- ``fe_objective``: worst fixed-effect solve, the final objective the
  program's tracker reports against the reference's minimum at that update.
- ``train_loss``: mean log-loss of the SUM of the training scores the
  coordinates exchanged, as the last unit left them.
- ``heldout_loss`` / ``heldout_auc``: held-out log-loss of the unit's final
  model scored by the plain scorer; the last validation AUC the program
  reported against the reference's (absolute gap).
- ``reference_residual``: not a gap of the program's but the reference's own
  check: the worst estimated distance of any of its block solves from that
  block's exact minimiser, in coefficient units. A reference that has not
  converged decides nothing.
- ``unit_repeat``: worst gap between any unit's coefficients and the last
  unit's. Every unit does identical work, so it is compared exactly (limit 0).
"""

from __future__ import annotations

import numpy as np


def _rel(a, b) -> float:
    return float(np.linalg.norm(np.asarray(a) - np.asarray(b)) / max(np.linalg.norm(b), 1e-30))


def _aligned(answer, reference):
    """The program's [E', K] table laid on the reference's entity rows."""
    ids, rows = answer
    ref_ids, ref_rows = reference
    out = np.zeros_like(ref_rows)
    pos = np.searchsorted(ref_ids, np.asarray(ids, np.int64))
    out[pos] = rows[:, : ref_rows.shape[1]]
    return out


def numbers(answers: list, reference: list, program_losses: dict) -> dict:
    """``answers`` and ``reference``: one record per model of the sweep (same
    order); ``program_losses``: ``{"train_loss": [...], "heldout_loss": [...]}``
    per model, computed by the plain scorer from the program's scores and
    coefficients."""
    out = {k: 0.0 for k in ("fe_coef", "fe_objective", "train_loss", "heldout_loss", "heldout_auc")}
    out["reference_residual"] = max(d for r in reference for ds in r["distances"].values() for d in ds)
    has_random = any(a["random"] for a in answers)
    if has_random:
        out["re_coef"] = out["re_entity"] = 0.0
    if len(answers) != len(reference):
        raise ValueError(f"{len(answers)} models trained, the reference has {len(reference)}")
    for i, (a, r) in enumerate(zip(answers, reference)):
        if a["reg"] != r["reg"]:
            raise ValueError(f"model {i}: weights {a['reg']} against the reference's {r['reg']}")
        for cid, w in a["fixed"].items():
            out["fe_coef"] = max(out["fe_coef"], _rel(w, r["fixed"][cid]))
            got, want = np.asarray(a["fe_objectives"][cid]), np.asarray(r["fe_objectives"][cid])
            if got.shape != want.shape:
                raise ValueError(f"{cid}: {got.shape[0]} solves, the reference made {want.shape[0]}")
            out["fe_objective"] = max(out["fe_objective"], float(np.max(np.abs(got - want) / want)))
        for cid, table in a["random"].items():
            got, want = _aligned(table, r["random"][cid]), r["random"][cid][1]
            out["re_coef"] = max(out["re_coef"], _rel(got, want))
            norms = np.linalg.norm(want, axis=1)
            gaps = np.linalg.norm(got - want, axis=1) / np.maximum(norms, np.median(norms))
            out["re_entity"] = max(out["re_entity"], float(gaps.max()))
        for key in ("train_loss", "heldout_loss"):
            out[key] = max(out[key], abs(program_losses[key][i] - r[key]) / r[key])
        if a["validation_metric"] is not None:
            out["heldout_auc"] = max(
                out["heldout_auc"], abs(a["validation_metric"] - r["heldout_auc"])
            )
    return out


def unit_repeat(all_units: list) -> float:
    """Worst gap of any unit's coefficients from the last unit's."""
    last, worst = all_units[-1], 0.0
    for unit in all_units[:-1]:
        for a, b in zip(unit, last):
            for cid, w in a["fixed"].items():
                worst = max(worst, _rel(w, b["fixed"][cid]))
            for cid, (_ids, rows) in a["random"].items():
                worst = max(worst, _rel(rows, b["random"][cid][1]))
    return worst


def judge(values: dict, limits: dict) -> tuple:
    """``(correct, compared)``: ``compared`` maps each number to ``{"value",
    "limit"}`` (limit None = computed but not compared). A number that is not
    finite fails; a limit with no number fails."""
    compared, ok = {}, True
    for name in sorted(set(values) | set(limits)):
        value, limit = values.get(name), limits.get(name)
        compared[name] = {"value": value, "limit": limit}
        if limit is None:
            continue
        if value is None or not np.isfinite(value) or value > limit:
            ok = False
    return ok, compared
