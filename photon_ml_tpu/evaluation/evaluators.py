"""Evaluators: global metrics + per-group (multi) metrics.

Re-creates the reference evaluation stack (photon-lib evaluation/EvaluationSuite.scala:
33-173, evaluation/MultiEvaluator.scala:36-86; photon-api evaluation/* local
evaluators: AreaUnderROCCurveLocalEvaluator.scala:72, PrecisionAtKLocalEvaluator.scala:76,
RMSE/loss evaluators, EvaluatorFactory.scala:65).

TPU design: a metric is a pure function over (scores, labels, weights) arrays. AUC is
the rank-statistic form (sort once, tie-averaged ranks) — O(n log n) on device. The
MultiEvaluator (per-group AUC averaged over groups, e.g. per-user AUC) replaces the
reference's groupByKey with a host-side sort + segmented evaluation.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.function.losses import (
    logistic_loss,
    poisson_loss,
    smoothed_hinge_loss,
    squared_loss,
)

Array = jnp.ndarray


class EvaluatorType(str, enum.Enum):
    AUC = "AUC"  # area under ROC
    AUPR = "AUPR"  # area under precision-recall
    RMSE = "RMSE"
    LOGISTIC_LOSS = "LOGISTIC_LOSS"
    POISSON_LOSS = "POISSON_LOSS"
    SQUARED_LOSS = "SQUARED_LOSS"
    SMOOTHED_HINGE_LOSS = "SMOOTHED_HINGE_LOSS"
    PRECISION_AT_K = "PRECISION_AT_K"  # parameterized; see precision_at_k


# ------------------------------------------------------------------ metrics


def auc_roc(scores, labels, weights=None) -> float:
    """(Weighted) area under the ROC curve via the Mann-Whitney pair statistic:
    sum over (pos, neg) pairs of w_p * w_n * [s_p > s_n] (ties count half),
    computed in one descending sweep. NaN when only one class has mass (the
    reference's per-group filter drops such groups, MultiEvaluator.scala:49-66).
    """
    scores = np.asarray(scores, dtype=np.float64)
    labels = np.asarray(labels, dtype=np.float64) > 0.5
    w = np.ones(len(scores)) if weights is None else np.asarray(weights, dtype=np.float64)
    w_pos_total = float(w[labels].sum())
    w_neg_total = float(w[~labels].sum())
    if w_pos_total <= 0 or w_neg_total <= 0:
        return float("nan")
    order = np.argsort(scores, kind="mergesort")  # ascending
    s, l, ww = scores[order], labels[order], w[order]
    # group by distinct score: for each tie group, positives beat all lighter
    # negatives fully and tied negatives half (vectorized via reduceat).
    boundaries = np.flatnonzero(np.diff(s) != 0) + 1
    starts = np.concatenate([[0], boundaries])
    grp_pos = np.add.reduceat(ww * l, starts)
    grp_neg = np.add.reduceat(ww * ~l, starts)
    cum_neg_below = np.concatenate([[0.0], np.cumsum(grp_neg)[:-1]])
    num = float(np.sum(grp_pos * (cum_neg_below + 0.5 * grp_neg)))
    return float(num / (w_pos_total * w_neg_total))


def auc_pr(scores, labels, weights=None) -> float:
    """(Weighted) area under the precision-recall curve (trapezoidal, descending sweep)."""
    scores = np.asarray(scores, dtype=np.float64)
    labels = np.asarray(labels, dtype=np.float64) > 0.5
    w = np.ones(len(scores)) if weights is None else np.asarray(weights, dtype=np.float64)
    w_pos_total = float(w[labels].sum())
    if w_pos_total <= 0:
        return float("nan")
    order = np.argsort(-scores, kind="mergesort")
    tp = np.cumsum(w[order] * labels[order])
    fp = np.cumsum(w[order] * ~labels[order])
    # collapse ties: keep last index of each distinct score
    distinct = np.flatnonzero(np.diff(scores[order], append=np.nan))
    tp, fp = tp[distinct], fp[distinct]
    precision = tp / (tp + fp)
    recall = tp / w_pos_total
    # prepend (recall=0, precision=first)
    recall = np.concatenate([[0.0], recall])
    precision = np.concatenate([[precision[0]], precision])
    return float(np.trapezoid(precision, recall))


def rmse(scores, labels, weights=None) -> float:
    scores = np.asarray(scores, dtype=np.float64)
    labels = np.asarray(labels, dtype=np.float64)
    if weights is None:
        return float(np.sqrt(np.mean((scores - labels) ** 2)))
    w = np.asarray(weights, dtype=np.float64)
    return float(np.sqrt(np.sum(w * (scores - labels) ** 2) / np.sum(w)))


def _mean_pointwise_loss(loss):
    def fn(scores, labels, weights=None) -> float:
        z = jnp.asarray(scores)
        y = jnp.asarray(labels)
        l = loss.loss(z, y)
        if weights is None:
            return float(jnp.mean(l))
        w = jnp.asarray(weights)
        return float(jnp.sum(w * l) / jnp.sum(w))

    return fn


def precision_at_k(k: int):
    """(Weighted) fraction of positive mass among the k highest-scored samples."""

    def fn(scores, labels, weights=None) -> float:
        scores = np.asarray(scores, dtype=np.float64)
        labels = np.asarray(labels, dtype=np.float64)
        kk = min(k, len(scores))
        if kk == 0:
            return float("nan")
        top = np.argsort(-scores, kind="mergesort")[:kk]
        if weights is None:
            return float((labels[top] > 0.5).mean())
        w = np.asarray(weights, dtype=np.float64)[top]
        tot = w.sum()
        return float(np.sum(w * (labels[top] > 0.5)) / tot) if tot > 0 else float("nan")

    return fn


# ------------------------------------------------------------- evaluator API


@dataclasses.dataclass(frozen=True)
class Evaluator:
    """A named single metric; ``larger_is_better`` drives best-model selection
    (reference Evaluator.betterThan)."""

    name: str
    fn: Callable
    larger_is_better: bool

    def evaluate(self, scores, labels, weights=None) -> float:
        return self.fn(scores, labels, weights)

    def better_than(self, a: float, b: Optional[float]) -> bool:
        if b is None or np.isnan(b):
            return not np.isnan(a)
        if np.isnan(a):
            return False
        return a > b if self.larger_is_better else a < b


@dataclasses.dataclass(frozen=True)
class MultiEvaluator:
    """Per-group metric averaged over groups, e.g. per-user AUC
    (MultiEvaluator.scala:36-86: group scores by an id tag, evaluate each group,
    unweighted mean over groups that yield a defined metric)."""

    base: Evaluator
    id_tag: str  # grouping column, e.g. "userId"

    @property
    def name(self) -> str:
        return f"{self.base.name}@{self.id_tag}"

    @property
    def larger_is_better(self) -> bool:
        return self.base.larger_is_better

    def better_than(self, a, b):
        return self.base.better_than(a, b)

    def evaluate_grouped(self, scores, labels, weights, group_ids) -> float:
        scores = np.asarray(scores)
        labels = np.asarray(labels)
        weights = np.ones(len(scores)) if weights is None else np.asarray(weights)
        group_ids = np.asarray(group_ids)
        order = np.argsort(group_ids, kind="mergesort")
        sg = group_ids[order]
        boundaries = np.flatnonzero(np.diff(sg) != 0 if sg.dtype.kind in "if" else sg[1:] != sg[:-1]) + 1
        vals = []
        for start, stop in zip(np.concatenate([[0], boundaries]), np.concatenate([boundaries, [len(sg)]])):
            idx = order[start:stop]
            v = self.base.fn(scores[idx], labels[idx], weights[idx])
            if not np.isnan(v):
                vals.append(v)
        return float(np.mean(vals)) if vals else float("nan")


def evaluator_spec_name(spec) -> str:
    """A PROCESS-STABLE identity string for an evaluator spec, for run
    fingerprints (io/checkpoint.py). ``str()`` on Evaluator/MultiEvaluator
    dataclasses renders their ``fn`` field as ``<function ... at 0x...>`` —
    stable within one process (module-level functions) but different across
    processes, which would make a resumed run reject its own checkpoint."""
    name = getattr(spec, "name", None)
    return name if isinstance(name, str) else str(spec)


def resolve_evaluator(spec):
    """Accept EvaluatorType | Evaluator | MultiEvaluator | (EvaluatorType, id_tag)."""
    if isinstance(spec, (Evaluator, MultiEvaluator)):
        return spec
    if isinstance(spec, tuple):
        base, id_tag = spec
        return MultiEvaluator(evaluator_for_type(EvaluatorType(base)), id_tag)
    return evaluator_for_type(EvaluatorType(spec))


def evaluator_for_type(etype: EvaluatorType, k: int = 10) -> Evaluator:
    """EvaluatorFactory (photon-api evaluation/EvaluatorFactory.scala:65)."""
    etype = EvaluatorType(etype)
    table = {
        EvaluatorType.AUC: Evaluator("AUC", auc_roc, True),
        EvaluatorType.AUPR: Evaluator("AUPR", auc_pr, True),
        EvaluatorType.RMSE: Evaluator("RMSE", rmse, False),
        EvaluatorType.LOGISTIC_LOSS: Evaluator("LOGISTIC_LOSS", _mean_pointwise_loss(logistic_loss), False),
        EvaluatorType.POISSON_LOSS: Evaluator("POISSON_LOSS", _mean_pointwise_loss(poisson_loss), False),
        EvaluatorType.SQUARED_LOSS: Evaluator("SQUARED_LOSS", _mean_pointwise_loss(squared_loss), False),
        EvaluatorType.SMOOTHED_HINGE_LOSS: Evaluator(
            "SMOOTHED_HINGE_LOSS", _mean_pointwise_loss(smoothed_hinge_loss), False
        ),
        EvaluatorType.PRECISION_AT_K: Evaluator(f"PRECISION@{k}", precision_at_k(k), True),
    }
    return table[etype]


@dataclasses.dataclass
class EvaluationSuite:
    """Holds validation labels/offsets/weights once, runs all evaluators on a score
    array (EvaluationSuite.scala:33-173; the join the reference does is positional
    alignment here). ``primary`` drives best-model selection."""

    evaluators: Sequence[object]  # Evaluator | MultiEvaluator
    labels: np.ndarray
    offsets: np.ndarray
    weights: np.ndarray
    id_columns: Optional[dict] = None  # id_tag -> per-sample group ids

    @property
    def primary(self):
        return self.evaluators[0]

    def evaluate(self, raw_scores) -> dict[str, float]:
        """raw_scores are coordinate-score sums; offsets are added before metrics
        (reference: scores + offsets, EvaluationSuite.evaluate:56-81).

        Scores longer than the label array are sliced: mesh placement pads the
        sample axis to the device count and padded rows are metric-inert."""
        # the one device->host transfer of a validation round, named so that
        # runtime_guard.sync_discipline regions can hold a validating fit
        total = np.asarray(jax.device_get(raw_scores))[: len(self.labels)] + self.offsets
        results: dict[str, float] = {}
        for ev in self.evaluators:
            if isinstance(ev, MultiEvaluator):
                if not self.id_columns or ev.id_tag not in self.id_columns:
                    raise ValueError(f"Missing id column {ev.id_tag!r} for {ev.name}")
                results[ev.name] = ev.evaluate_grouped(
                    total, self.labels, self.weights, self.id_columns[ev.id_tag]
                )
            else:
                results[ev.name] = ev.evaluate(total, self.labels, self.weights)
        return results
