"""Evaluators: global metrics + per-group (multi) metrics.

Re-creates the reference evaluation stack (photon-lib evaluation/EvaluationSuite.scala:
33-173, evaluation/MultiEvaluator.scala:36-86; photon-api evaluation/* local
evaluators: AreaUnderROCCurveLocalEvaluator.scala:72, PrecisionAtKLocalEvaluator.scala:76,
RMSE/loss evaluators, EvaluatorFactory.scala:65).

TPU design: a metric is a pure function over (scores, labels, weights) arrays.

On the HOST (NumPy, float64): every free metric function here. ``auc_roc`` is the
rank-statistic form (one merge sort, tie groups by ``reduceat``) and stays the
reference; AUPR, RMSE and PRECISION@k likewise. The MultiEvaluator (per-group AUC
averaged over groups, e.g. per-user AUC) replaces the reference's groupByKey with
a host-side sort + segmented evaluation. The pointwise losses reduce in ``jnp``.

On the DEVICE: ``EvaluationSuite.evaluate`` computes the plain unweighted AUC where
the scores already are (``_auc_rank_sums``: one sort, three scans) and reads back
six integers, where ``EvaluationSuite._on_device`` admits the input; everything
else reads the scores to the host once and runs the functions above. The device
path is INTEGER arithmetic because its result has to be the host's bit for bit:
the benchmark compares the last validation metric under 3e-6 and best-model
selection compares successive metrics with ``>``. With unit weights every
quantity of ``auc_roc`` is an integer or a half-integer under 2^53, so its
float64 result does not depend on the order of its sums, and an exact integer
count followed by the same one division is the same float64. A float32 rank sum
would be a different number.
"""

from __future__ import annotations

import dataclasses
import enum
import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

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


# The device AUC's integer sums are proven for label counts to this bound: a
# positive's term (below) is at most 2 * (2^24 - 1) < 2^25, so four 7-bit limbs
# hold it, and a limb's sum over all rows is at most 2^24 * 127 < 2^31.
DEVICE_AUC_MAX_ROWS = 1 << 24
_LIMB_BITS = 7
_LIMBS = 4


def _ordered_keys(scores):
    """Scores as integers of their own width whose order and equality are those
    of ``auc_roc``'s float64 merge sort: ``-0.0 == 0.0``, every NaN last. Bit
    operations only, so no backend's treatment of subnormals can move a tie."""
    itype = {4: jnp.int32, 8: jnp.int64}[scores.dtype.itemsize]
    top = jnp.iinfo(itype).max
    inf = lax.bitcast_convert_type(jnp.asarray(jnp.inf, scores.dtype), itype)
    raw = lax.bitcast_convert_type(scores, itype)
    magnitude = raw & top  # the sign bit cleared
    return jnp.where(magnitude > inf, top, jnp.where(raw < 0, -magnitude, magnitude))


@jax.jit
def _auc_rank_sums(scores, positive):
    """``int32[_LIMBS + 2]``: the limb sums of ``2 * num`` of ``auc_roc`` with
    unit weights, then the two class counts.

    For every positive row the integer 2 * (negatives strictly below its tie
    group) + (negatives inside its tie group), i.e. (negatives below the group)
    + (negatives to the group's end); the sum of those reaches 2^47, so it
    leaves the device as sums of 7-bit limbs that cannot overflow an int32
    (``DEVICE_AUC_MAX_ROWS``). Tie groups are ``auc_roc``'s: a row starts one
    where ``diff != 0``, which also holds between equal infinities and between
    NaNs (their difference is NaN), so among those the merge sort's row order
    counts: the row index is the second sort key, with the label in its low
    bit. (Written so for the TPU compiler's sake: ``is_stable=True`` and a
    ``reverse=True`` running minimum each more than double the program's cold
    compile, ``PERF.md`` §6, PR 36.)"""
    n = positive.shape[0]
    keys = _ordered_keys(scores[:n])
    row_and_label = 2 * jnp.arange(n, dtype=jnp.int32) + positive.astype(jnp.int32)
    keys, row_and_label = lax.sort((keys, row_and_label), num_keys=2, is_stable=False)
    pos = row_and_label & 1
    neg = 1 - pos
    neg_to_here = jnp.cumsum(neg, dtype=jnp.int32)
    not_finite = jnp.abs(keys[1:]) >= _ordered_keys(jnp.asarray(jnp.inf, scores.dtype))
    differs = (keys[1:] != keys[:-1]) | not_finite
    edge = jnp.ones((1,), bool)
    starts = jnp.concatenate([edge, differs])
    ends = jnp.concatenate([differs, edge])
    # both counts are non-decreasing along the sort, so a running maximum
    # carries a group's first value forward and a running minimum from the
    # far end carries its last value back: no segment loop
    neg_below_group = lax.cummax(jnp.where(starts, neg_to_here - neg, 0))
    neg_to_group_end = lax.cummin(jnp.where(ends, neg_to_here, n)[::-1])[::-1]
    terms = pos * (neg_below_group + neg_to_group_end)
    shifts = _LIMB_BITS * jnp.arange(_LIMBS, dtype=jnp.int32)
    limbs = (terms[:, None] >> shifts) & ((1 << _LIMB_BITS) - 1)
    limb_sums = jnp.sum(limbs, axis=0, dtype=jnp.int32)
    n_pos = jnp.sum(pos, dtype=jnp.int32)
    return jnp.concatenate([limb_sums, jnp.stack([n_pos, n - n_pos])])


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

    @functools.cached_property
    def _unit_weights_zero_offsets(self) -> bool:
        return bool(np.all(np.asarray(self.weights) == 1) and np.all(np.asarray(self.offsets) == 0))

    @functools.cached_property
    def _positive(self):
        """The labels as ``auc_roc`` reads them, placed once per suite (at its
        first device evaluation, so a warm-up pays it): an explicit, uncommitted
        ``device_put``, so the program runs wherever the scores are."""
        return jax.device_put(np.asarray(self.labels, dtype=np.float64) > 0.5)

    def _on_device(self, evaluator, raw_scores) -> bool:
        """THE rule of the device path, read from the input alone (no option, no
        backend): the plain ``AUC`` evaluator, unit weights and zero offsets (f32
        + f64 zero is the f32 value, so order and ties are the host's), a float
        score vector on ONE device (a mesh-placed score keeps the host path),
        at least as long as the labels, whose count is one the integer sums are
        proven for. Both paths give the same bits, so nothing is chosen but time."""
        return (
            isinstance(evaluator, Evaluator)
            and evaluator.fn is auc_roc
            and isinstance(raw_scores, jax.Array)
            and raw_scores.ndim == 1
            and raw_scores.dtype in (jnp.float32, jnp.float64)
            and len(raw_scores.devices()) == 1
            and 0 < len(self.labels) <= min(raw_scores.shape[0], DEVICE_AUC_MAX_ROWS)
            and self._unit_weights_zero_offsets
        )

    def metric_path(self, raw_scores) -> str:
        """``device`` where ``evaluate(raw_scores)`` leaves the scores on the
        device (every evaluator is served there), else ``host``: the
        ``metric_path`` attribute of the ``descent.evaluate`` span."""
        on_device = all(self._on_device(ev, raw_scores) for ev in self.evaluators)
        return "device" if on_device else "host"

    def _device_auc(self, raw_scores) -> float:
        sums = jax.device_get(_auc_rank_sums(raw_scores, self._positive))
        *limbs, n_pos, n_neg = (int(v) for v in sums)
        if n_pos == 0 or n_neg == 0:
            return float("nan")
        twice_num = sum(limb << (_LIMB_BITS * i) for i, limb in enumerate(limbs))
        # auc_roc's own last line: num is a half-integer under 2^53, exact
        return (twice_num / 2) / (n_pos * n_neg)

    def evaluate(self, raw_scores) -> dict[str, float]:
        """raw_scores are coordinate-score sums; offsets are added before metrics
        (reference: scores + offsets, EvaluationSuite.evaluate:56-81).

        Scores longer than the label array are sliced: mesh placement pads the
        sample axis to the device count and padded rows are metric-inert."""
        on_device = [self._on_device(ev, raw_scores) for ev in self.evaluators]
        # the device->host transfers of a validation round, named so that
        # runtime_guard.sync_discipline regions can hold a validating fit: the
        # scores, ONCE, where some evaluator needs them on the host; six
        # integers for each evaluator served on the device (_device_auc)
        total = None
        if not all(on_device):
            total = np.asarray(jax.device_get(raw_scores))[: len(self.labels)] + self.offsets
        results: dict[str, float] = {}
        for ev, served in zip(self.evaluators, on_device):
            if served:
                results[ev.name] = self._device_auc(raw_scores)
            elif isinstance(ev, MultiEvaluator):
                if not self.id_columns or ev.id_tag not in self.id_columns:
                    raise ValueError(f"Missing id column {ev.id_tag!r} for {ev.name}")
                results[ev.name] = ev.evaluate_grouped(
                    total, self.labels, self.weights, self.id_columns[ev.id_tag]
                )
            else:
                results[ev.name] = ev.evaluate(total, self.labels, self.weights)
        return results
