"""SweepSpec: which hyperparameter axes a model-selection sweep explores.

Each axis is one scalar knob of one coordinate — the base L2 weight, the
elastic-net L1 weight, or the fixed-effect down-sampling rate — with a range
and an optional LOG/SQRT transform (hyperparameter/rescaling.py, the same
VectorRescaling algebra the reference's tuner uses). The Bayesian search
operates in transformed-[0,1]^d space; :meth:`SweepSpec.decode` maps its
candidate vectors back to raw per-coordinate values.

Validation against the estimator happens ONCE up front (:meth:`validate`):
every axis must name a real coordinate and a knob whose program treats it as
a TRACED argument — that is what makes the population axis possible at all
(optimization/solver_cache.py keeps static config in the cache key and
everything swept as traced arrays). Configurations the population programs
cannot carry (mesh sharding, box constraints, variance computation, partial
retrain) are rejected here with the reason, not deep in a trace.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import numpy as np

from photon_ml_tpu.estimators.config import RandomEffectDataConfiguration
from photon_ml_tpu.hyperparameter.rescaling import (
    LOG_TRANSFORM,
    SQRT_TRANSFORM,
    scale_backward,
    scale_forward,
    transform_backward,
    transform_forward,
)

_PARAMETERS = ("l2", "l1", "down_sampling_rate")


@dataclasses.dataclass(frozen=True)
class SweepAxis:
    """One swept scalar knob of one coordinate."""

    coordinate_id: str
    parameter: str  # "l2" | "l1" | "down_sampling_rate"
    min: float
    max: float
    transform: Optional[str] = None  # LOG | SQRT | None

    @property
    def name(self) -> str:
        return f"{self.coordinate_id}.{self.parameter}"

    def __post_init__(self):
        if self.parameter not in _PARAMETERS:
            raise ValueError(
                f"Unknown sweep parameter {self.parameter!r}; "
                f"supported: {_PARAMETERS}"
            )
        if not (self.min < self.max):
            raise ValueError(f"Axis {self.name}: min {self.min} must be < max {self.max}")
        if self.transform not in (None, LOG_TRANSFORM, SQRT_TRANSFORM):
            raise ValueError(f"Axis {self.name}: unknown transform {self.transform!r}")
        if self.transform == LOG_TRANSFORM and self.min <= 0.0:
            raise ValueError(f"Axis {self.name}: LOG transform requires min > 0")
        if self.transform == SQRT_TRANSFORM and self.min < 0.0:
            raise ValueError(f"Axis {self.name}: SQRT transform requires min >= 0")
        if self.parameter == "down_sampling_rate" and not (
            0.0 < self.min and self.max < 1.0
        ):
            raise ValueError(
                f"Axis {self.name}: down-sampling rates live strictly inside (0, 1)"
            )


@dataclasses.dataclass(frozen=True)
class SweepSpec:
    """The swept axes of one model-selection run."""

    axes: tuple

    def __post_init__(self):
        object.__setattr__(self, "axes", tuple(self.axes))
        if not self.axes:
            raise ValueError("A sweep needs at least one axis")
        names = [a.name for a in self.axes]
        if len(set(names)) != len(names):
            raise ValueError(f"Duplicate sweep axes: {sorted(names)}")

    @property
    def dimension(self) -> int:
        return len(self.axes)

    @property
    def axis_names(self) -> tuple:
        return tuple(a.name for a in self.axes)

    # ---------------------------------------------------------- validation

    def validate(self, estimator) -> None:
        """Reject axis/estimator combinations the population programs cannot
        express, with the reason. Raises ValueError."""
        from photon_ml_tpu.estimators.config import expand_game_configurations
        from photon_ml_tpu.types import VarianceComputationType

        configs = estimator.coordinate_configurations
        reasons = []
        if estimator.mesh is not None:
            reasons.append(
                "mesh-sharded estimators are not supported (the population "
                "programs do not re-place sharded tables)"
            )
        if (
            VarianceComputationType(estimator.variance_computation)
            != VarianceComputationType.NONE
        ):
            reasons.append(
                "variance computation is not part of model selection; compute "
                "variances on the winner with a normal fit"
            )
        if estimator.partial_retrain_locked_coordinates:
            reasons.append("partial retrain (locked coordinates) is not supported")
        if len(expand_game_configurations(configs)) != 1:
            reasons.append(
                "coordinate configurations expand to a reg-weight grid; the "
                "sweep OWNS the regularization axis (drop reg_weights)"
            )
        for axis in self.axes:
            cfg = configs.get(axis.coordinate_id)
            if cfg is None:
                reasons.append(f"axis {axis.name}: unknown coordinate")
                continue
            is_re = isinstance(cfg.data_config, RandomEffectDataConfiguration)
            if axis.parameter == "down_sampling_rate" and is_re:
                reasons.append(
                    f"axis {axis.name}: down-sampling is a fixed-effect knob"
                )
            if (
                axis.parameter == "down_sampling_rate"
                and not is_re
                and not (0.0 < getattr(cfg, "down_sampling_rate", 1.0) < 1.0)
            ):
                # the program's down-sampling support is a STATIC flag; the
                # base configuration decides whether the family carries it
                reasons.append(
                    f"axis {axis.name}: a down_sampling_rate axis needs a "
                    "down-sampling base configuration (set the coordinate's "
                    "down_sampling_rate inside (0, 1))"
                )
            if axis.parameter == "l1" and not cfg.optimization_config.l1_weight:
                # has_l1 is a STATIC program flag: a population cannot mix
                # L1-bearing and L1-free solves in one compiled family
                reasons.append(
                    f"axis {axis.name}: the base configuration has no L1 term "
                    "(configure ELASTIC_NET/L1 with a nonzero weight so the "
                    "compiled program family carries the L1 argument)"
                )
            if (
                axis.parameter == "l2"
                and cfg.per_entity_reg_weights is not None
                and not isinstance(cfg.per_entity_reg_weights, dict)
            ):
                reasons.append(
                    f"axis {axis.name}: an [E] per-entity weight array "
                    "overrides EVERY entity, so the swept base weight would "
                    "be dead"
                )
        for cid, cfg in configs.items():
            if cfg.box_constraints is not None:
                reasons.append(
                    f"coordinate {cid!r}: box constraints are not carried by "
                    "the population programs"
                )
        if reasons:
            raise ValueError(
                "SweepSpec is not valid for this estimator: " + "; ".join(reasons)
            )

    def vmappable(self, estimator) -> bool:
        """True when every swept knob can ride the population (lane) axis of
        one compiled program. Dict-valued per-entity L2 overrides resolve
        host-side (entity-id lookup) per setting, so an L2 axis over such a
        coordinate takes the sequential shared-program fallback instead."""
        for axis in self.axes:
            cfg = estimator.coordinate_configurations.get(axis.coordinate_id)
            if (
                cfg is not None
                and axis.parameter == "l2"
                and isinstance(cfg.per_entity_reg_weights, dict)
            ):
                return False
        return True

    # ------------------------------------------------------------ en/decode

    def _ranges_transformed(self):
        tmap = {
            i: a.transform for i, a in enumerate(self.axes) if a.transform is not None
        }
        lo = transform_forward(
            np.array([a.min for a in self.axes], dtype=np.float64), tmap
        )
        hi = transform_forward(
            np.array([a.max for a in self.axes], dtype=np.float64), tmap
        )
        return list(zip(lo, hi)), tmap

    def decode(self, candidates: np.ndarray) -> list[dict]:
        """[P, d] candidate matrix in [0,1]^d -> P settings dicts
        ``{axis_name: raw value}`` (scale back over the TRANSFORMED ranges,
        then invert the transform — the exact inverse of :meth:`encode`)."""
        candidates = np.atleast_2d(np.asarray(candidates, dtype=np.float64))
        if candidates.shape[1] != self.dimension:
            raise ValueError(
                f"candidates have {candidates.shape[1]} dims, spec has {self.dimension}"
            )
        ranges_t, tmap = self._ranges_transformed()
        out = []
        for row in candidates:
            raw = transform_backward(scale_backward(row, ranges_t), tmap)
            # numerical inverse drift must not escape the declared range
            raw = np.clip(raw, [a.min for a in self.axes], [a.max for a in self.axes])
            out.append({a.name: float(v) for a, v in zip(self.axes, raw)})
        return out

    def encode(self, settings: Sequence[dict]) -> np.ndarray:
        """Settings dicts -> [P, d] candidate matrix in [0,1]^d."""
        ranges_t, tmap = self._ranges_transformed()
        rows = []
        for s in settings:
            raw = np.array([s[a.name] for a in self.axes], dtype=np.float64)
            rows.append(scale_forward(transform_forward(raw, tmap), ranges_t))
        return np.stack(rows)

    def nearest_prior(
        self, settings: Sequence[dict], prior_settings: Sequence[dict]
    ) -> np.ndarray:
        """Index of each setting's nearest neighbor among ``prior_settings``,
        by Euclidean distance in the transformed-[0,1]^d search space — the
        warm-start seeding rule (SweepRunner's glmnet-style regularization
        paths across Bayesian rounds): 'nearest on the swept axes' is
        measured where those axes are commensurate, i.e. after the LOG/SQRT
        transforms and range scaling. np.argmin ties break to the lowest
        index, so the mapping is deterministic."""
        if not prior_settings:
            raise ValueError("nearest_prior needs at least one prior setting")
        a = self.encode(settings)
        b = self.encode(prior_settings)
        d = np.linalg.norm(a[:, None, :] - b[None, :, :], axis=-1)
        return np.argmin(d, axis=1)

    def describe(self) -> list[dict]:
        """JSON-friendly axis description (driver stats / checkpoint extra)."""
        return [
            {
                "coordinate": a.coordinate_id,
                "parameter": a.parameter,
                "min": a.min,
                "max": a.max,
                "transform": a.transform,
            }
            for a in self.axes
        ]


def setting_value(settings: dict, cid: str, parameter: str, default: float) -> float:
    """One coordinate knob out of a settings dict, falling back to the base
    configuration's value when the axis is not swept."""
    return float(settings.get(f"{cid}.{parameter}", default))
