"""Population coordinate descent: P hyperparameter settings trained at once.

The state of a normal descent run (one coefficient table and one [N] score per
coordinate) grows a LEADING POPULATION AXIS: ``[P, D]`` / ``[P, E, K]`` tables
and ``[P, N]`` scores, updated by the population programs in
``optimization/solver_cache.py`` (``re_population_update_program`` /
``fe_population_update_program``). Every update is ONE donated XLA dispatch
for the whole population; the datasets (bucket blocks, design matrix,
normalization tables, scoring views) stay device-resident and broadcast —
read once per update for all P settings.

Two execution paths, bitwise-interchangeable per setting:

- **vmapped** (default): all settings ride the lane axis of one dispatch.
- **sequential** fallback: one dispatch per setting through the SAME compiled
  program, every lane filled with that setting (duplicate-lane padding, the
  active-set trick) and lane 0 extracted. This exists for knobs the lane axis
  cannot carry — per-entity-L2 DICTS resolve entity ids host-side per setting
  — and as the parity reference. Bitwise parity holds BY CONSTRUCTION: a
  lane's output is a function of that lane's inputs alone (no cross-lane ops
  under vmap; converged while_loop lanes are select-frozen), and both paths
  execute the one compiled form. Comparing against programs of OTHER batch
  shapes (e.g. the unbatched single-model program) is NOT bitwise on real
  backends — XLA re-vectorizes reductions per shape — which is exactly the
  PR 4 lesson (models/game.random_effect_view_score) applied to the
  population axis; the parity gate in bench.py --sweep pins the contract.

A third path, ``fused``, collapses the whole train() call — all settings x
all coordinates x all iterations — into ONE jit
(``parallel/game.population_sweep_fn``), with per-lane EARLY EXIT
(convergence/domination freezing mid-descent), optional warm-started initial
tables, and an optional device MESH that shards the settings axis
(``P(settings, None, None)`` tables, broadcast data replicated — the
embarrassingly parallel axis crossing zero data collectives, audited by
``parallel/hlo_guards.assert_settings_axis_collective_free``).

Divergence: the per-lane reject is applied IN-PROGRAM (a diverged setting
keeps its previous coefficients/score bit for bit, exactly like the
single-model path) and surfaced as per-lane flags, materialized in ONE
batched transfer per ``train`` call and recorded as incidents.

Reduced-precision population tables: a ``re_precision`` policy on the
estimator (optimization/precision.py) stores the ``[P, E, K]`` random-effect
tables and their bucket/view feature arrays in bf16/f16 with f32
accumulation — the same storage/accumulation split the single-model update
program runs, inherited here because the population programs share its body.
The f32 reference policy keeps every cast an identity (the bitwise-gated
status quo); reduced sweeps are tolerance-gated on the winner's held-out
metric, never compared bitwise against f32.
"""

from __future__ import annotations

import dataclasses
import functools
import operator
from typing import Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.algorithm.random_effect import (
    build_l2_rows,
    precompute_norm_tables,
    update_program_data,
)
from photon_ml_tpu.data.dataset import FixedEffectDataset
from photon_ml_tpu.data.random_effect import RandomEffectDataset, _next_pow2
from photon_ml_tpu.estimators.config import RandomEffectDataConfiguration
from photon_ml_tpu.function.losses import loss_for_task
from photon_ml_tpu.models.game import FixedEffectModel, RandomEffectModel
from photon_ml_tpu.models.glm import Coefficients, model_class_for_task
from photon_ml_tpu.optimization.precision import resolve_precision
from photon_ml_tpu.optimization.solver_cache import (
    fe_population_update_program,
    re_population_update_program,
)
from photon_ml_tpu.parallel.game import (
    PopulationCoordinateSpec,
    make_population_sweep_program,
)
from photon_ml_tpu.resilience.incidents import Incident
from photon_ml_tpu.sampling.down_sampler import per_sample_uniform
from photon_ml_tpu.sweep.spec import setting_value
from photon_ml_tpu.types import OptimizerType, TaskType, VarianceComputationType

Array = jnp.ndarray

_MIN_POPULATION_PAD = 2


@dataclasses.dataclass(frozen=True)
class EarlyExitConfig:
    """Per-lane early-exit policy for the FUSED population path.

    - ``freeze_tol``: a lane whose total training score moved at most
      ``freeze_tol * (1 + max|score|)`` across a full coordinate-descent pass
      is select-frozen for the remaining passes (its committed state carried
      bitwise; its remaining solves run zero iterations). Negative disables
      convergence freezing while keeping the same compiled program.
    - ``min_iterations``: completed passes before any lane may freeze
      (STATIC — part of the program key).
    - ``domination_bound``: optional host-derived training-loss bound; a lane
      whose per-lane weighted mean training loss exceeds it freezes as
      dominated. Per-lane vs a broadcast scalar — deliberately never a
      cross-lane reduction, which would put a collective on the settings
      axis. None disables (and keeps labels/weights out of the program).
    """

    freeze_tol: float = 1e-6
    min_iterations: int = 1
    domination_bound: Optional[float] = None


@dataclasses.dataclass
class _CoordStatic:
    """Descent-invariant pieces of one coordinate, built once per trainer."""

    cid: str
    kind: str  # "fe" | "re"
    dataset: object
    opt_config: object  # the base GLMOptimizationConfiguration
    norm: object  # NormalizationContext (FE) | Optional[NormalizationContext] (RE)
    has_l1: bool
    # RE only
    buckets: Optional[tuple] = None
    norm_tables: Optional[tuple] = None
    view: Optional[tuple] = None
    # the dataset's [N] slot index where the per-update program scores from
    # its bucket blocks (algorithm/random_effect.bucket_score_slots), else
    # None; the fused sweep always scores through ``view``
    sample_slots: Optional[object] = None
    per_entity: Optional[object] = None  # None | [E] array | {entity_id: l2} dict
    # FE only
    down_sampling: bool = False
    base_rate: float = 1.0


@dataclasses.dataclass
class PopulationResult:
    """One population training run: per-setting tables, scores and rejects."""

    settings: list
    coeffs: dict  # cid -> [P, D] (FE) | [P, E, K] (RE)
    train_scores: dict  # cid -> [P, N]
    incidents: list  # per-lane divergence Incidents (setting index attached)
    rejected: np.ndarray  # [P] bool: lane absorbed >= 1 rejected update
    path: str  # "vmapped" | "sequential" | "fused"
    # per-lane observability (every path): total solver iterations the lane's
    # updates actually executed (RE: summed over entities and buckets)
    lane_iterations: Optional[np.ndarray] = None  # [P] int
    # fused path with early exit: completed CD passes at freeze time, -1 =
    # the lane ran every pass
    frozen_at: Optional[np.ndarray] = None  # [P] int
    # fused path with capture_pass_states: per-pass state snapshots (tests)
    pass_states: Optional[list] = None

    @property
    def population(self) -> int:
        return len(self.settings)

    @property
    def freeze_fraction(self) -> float:
        """Fraction of lanes frozen before the final pass (0.0 when early
        exit is off or on the per-update paths)."""
        if self.frozen_at is None or self.frozen_at.size == 0:
            return 0.0
        return float(np.mean(self.frozen_at >= 0))


class PopulationTrainer:
    """Full coordinate-descent passes for a population of settings over ONE
    set of shared device-resident datasets (built once by the caller via
    ``GameEstimator.prepare_training_datasets``)."""

    def __init__(
        self,
        estimator,
        datasets: Mapping[str, object],
        base_offsets: Array,
        seed: int = 0,
        mesh=None,
    ):
        self.estimator = estimator
        self.task = TaskType(estimator.task)
        self.dtype = estimator.dtype
        self.base_offsets = jnp.asarray(base_offsets, dtype=self.dtype)
        self.seed = seed
        # the population programs inherit the estimator's random-effect inner
        # solver (optimization/normal_equations.py); both the vmapped path
        # and the sequential fallback run the SAME program, so the bitwise
        # per-lane parity contract holds for direct solves too
        self.re_solver = getattr(estimator, "re_solver", "lbfgs")
        # storage/accumulation precision for the [P, E, K] random-effect
        # population tables and their feature arrays — the estimator's
        # re_precision, inherited the way the single-model update program
        # inherits it (the population bodies ARE that program's body)
        self.precision = resolve_precision(getattr(estimator, "re_precision", None))
        # optional 1-D device mesh the FUSED path shards the SETTINGS axis
        # over: population state P(settings, ...), broadcast data replicated
        self.mesh = mesh
        if mesh is not None and len(mesh.axis_names) != 1:
            raise ValueError(
                f"population mesh must be 1-D (settings axis); got axes "
                f"{mesh.axis_names}"
            )
        loss = loss_for_task(self.task)
        self._static: dict[str, _CoordStatic] = {}
        for cid, cfg in estimator.coordinate_configurations.items():
            ds = datasets[cid]
            opt = cfg.optimization_config
            opt_type = OptimizerType(opt.optimizer_config.optimizer_type)
            if (
                opt_type in (OptimizerType.TRON, OptimizerType.NEWTON)
                and not loss.has_hessian
            ):
                raise ValueError(
                    f"{opt_type.value} requires a twice-differentiable loss"
                )
            if isinstance(cfg.data_config, RandomEffectDataConfiguration):
                if not isinstance(ds, RandomEffectDataset):
                    raise TypeError(f"coordinate {cid!r}: expected a RandomEffectDataset")
                if getattr(ds, "coeffs_sharding", None) is not None:
                    raise ValueError(
                        f"coordinate {cid!r}: mesh-sharded datasets are not "
                        "supported by the population programs"
                    )
                norm = estimator._normalization_for(cfg.data_config.feature_shard_id)
                norm = None if norm.is_identity or ds.projector is not None else norm
                buckets, view, sample_slots = update_program_data(
                    ds, self.precision, norm
                )
                self._static[cid] = _CoordStatic(
                    cid=cid,
                    kind="re",
                    dataset=ds,
                    opt_config=opt,
                    norm=norm,
                    has_l1=bool(opt.l1_weight),
                    buckets=buckets,
                    norm_tables=precompute_norm_tables(ds, norm, self.dtype),
                    view=view,
                    sample_slots=sample_slots,
                    per_entity=cfg.per_entity_reg_weights,
                )
            else:
                if not isinstance(ds, FixedEffectDataset):
                    raise TypeError(f"coordinate {cid!r}: expected a FixedEffectDataset")
                rate = float(getattr(cfg, "down_sampling_rate", 1.0))
                self._static[cid] = _CoordStatic(
                    cid=cid,
                    kind="fe",
                    dataset=ds,
                    opt_config=opt,
                    norm=estimator._normalization_for(cfg.data_config.feature_shard_id),
                    has_l1=bool(opt.l1_weight),
                    down_sampling=0.0 < rate < 1.0,
                    base_rate=rate,
                )
        # stable per-coordinate seed offsets for the down-sampling draws
        self._coord_index = {cid: i for i, cid in enumerate(self._static)}
        self.n_samples = int(self.base_offsets.shape[0])
        # population validation-scoring caches: alignment gather maps (host,
        # computed once per scoring dataset) and per-coordinate jitted
        # scorers, keyed by (cid, id(scoring_ds)). The keyed datasets are
        # RETAINED (_scoring_refs): a recycled address from a collected
        # dataset must not alias a cache entry built for a different one
        self._align_maps: dict = {}
        self._pop_scorers: dict = {}
        self._scoring_refs: dict = {}

    # ------------------------------------------------------------- settings

    def _lane_values(self, st: _CoordStatic, settings: Sequence[dict]) -> dict:
        """Per-lane hyperparameter arrays for one coordinate (live lanes only;
        the caller pads). RE l2 arrives as full per-entity rows so the lane
        axis carries per-entity overrides uniformly."""
        cid = st.cid
        l2 = np.array(
            [setting_value(s, cid, "l2", st.opt_config.l2_weight) for s in settings]
        )
        l1 = np.array(
            [setting_value(s, cid, "l1", st.opt_config.l1_weight or 0.0) for s in settings]
        )
        out = {"l1": l1}
        if st.kind == "re":
            E = st.dataset.n_entities
            per_entity = st.per_entity
            if isinstance(per_entity, dict) and not any(
                f"{cid}.l2" in s for s in settings
            ):
                # unswept dict overrides are setting-invariant: resolve once.
                # build_l2_rows pads its table to E+1 rows; slice back to the
                # [E] per-entity override array its own validation expects
                per_entity = np.asarray(
                    build_l2_rows(st.dataset, l2[0], per_entity, self.dtype, E)
                )[:E]
            if isinstance(per_entity, dict):
                raise ValueError(
                    f"coordinate {cid!r}: dict per-entity L2 overrides under a "
                    "swept l2 axis take the sequential path (host-side "
                    "entity-id resolution per setting)"
                )
            out["l2_rows"] = np.stack(
                [
                    np.asarray(build_l2_rows(st.dataset, v, per_entity, self.dtype, E))
                    for v in l2
                ]
            )
        else:
            out["l2"] = l2
            out["rates"] = np.array(
                [
                    setting_value(s, cid, "down_sampling_rate", st.base_rate)
                    for s in settings
                ]
            )
        return out

    def _sequential_lane_values(self, st: _CoordStatic, setting: dict) -> dict:
        """One setting's values for a sequential dispatch — the path where a
        dict per-entity override IS expressible (resolved host-side here)."""
        cid = st.cid
        l2 = setting_value(setting, cid, "l2", st.opt_config.l2_weight)
        out = {
            "l1": np.array([setting_value(setting, cid, "l1", st.opt_config.l1_weight or 0.0)])
        }
        if st.kind == "re":
            out["l2_rows"] = np.asarray(
                build_l2_rows(
                    st.dataset, l2, st.per_entity, self.dtype, st.dataset.n_entities
                )
            )[None]
        else:
            out["l2"] = np.array([l2])
            out["rates"] = np.array(
                [setting_value(setting, cid, "down_sampling_rate", st.base_rate)]
            )
        return out

    # --------------------------------------------------------------- train

    def train(
        self,
        settings: Sequence[dict],
        n_iterations: int = 1,
        vmapped: bool = True,
        *,
        fused: bool = False,
        early_exit: Optional[EarlyExitConfig] = None,
        warm_start: Optional[Mapping[str, Array]] = None,
        capture_pass_states: bool = False,
    ) -> PopulationResult:
        """Run ``n_iterations`` full coordinate-descent passes for every
        setting. By default each setting solves from a zero initialization
        (candidates are independent — model selection compares settings, it
        does not chain them); ``warm_start`` (cid -> ``[P, ...]``
        original-space tables, the FUSED path only) seeds each lane instead —
        the runner's cross-round glmnet-style paths. Returns live-lane
        tables, scores and per-lane divergence/iteration records.

        ``fused=True`` takes the one-jit whole-sweep path
        (``parallel/game.population_sweep_fn``): required for ``early_exit``,
        ``warm_start`` and a trainer ``mesh``; ``vmapped`` is ignored there.
        """
        if n_iterations < 1:
            raise ValueError(f"n_iterations must be >= 1, got {n_iterations}")
        settings = list(settings)
        if not settings:
            raise ValueError("empty population")
        if not fused:
            for name, value in (
                ("early_exit", early_exit),
                ("warm_start", warm_start),
                ("capture_pass_states", capture_pass_states or None),
            ):
                if value is not None:
                    raise ValueError(f"{name} requires the fused path (fused=True)")
            if self.mesh is not None:
                raise ValueError(
                    "a population mesh shards the settings axis of the FUSED "
                    "program; call train(..., fused=True)"
                )
            if vmapped:
                return self._train_vmapped(settings, n_iterations)
            return self._train_sequential(settings, n_iterations)
        return self._train_fused(
            settings, n_iterations, early_exit, warm_start, capture_pass_states
        )

    def _pad(self, arr: np.ndarray, p_pad: int) -> jnp.ndarray:
        """Pad the lane axis to ``p_pad`` with DUPLICATES of lane 0 (a twin
        solve converges like its sibling; its output is sliced away)."""
        live = arr.shape[0]
        if live < p_pad:
            arr = np.concatenate([arr, np.repeat(arr[:1], p_pad - live, axis=0)])
        return jnp.asarray(arr, dtype=self.dtype)

    def _keep_u(self, cid: str, iteration: int) -> Array:
        """The shared down-sampling draw for (coordinate, iteration): a pure
        function of (seed, coordinate index, iteration, sample position), so
        the vmapped and sequential paths — and a crash-replayed sweep — see
        the identical mask (sampling/down_sampler.per_sample_uniform)."""
        return per_sample_uniform(
            self.seed + self._coord_index[cid],
            iteration,
            jnp.arange(self.n_samples, dtype=jnp.uint32),
        )

    def _dispatch_update(
        self, st: _CoordStatic, state: dict, lane: dict, offsets_pop: Array,
        iteration: int,
    ):
        """One population update for one coordinate: returns (new coeffs,
        new score, guard, lane_iters) with guard = (coefs_ok [P], value_ok
        [P] or None, values [P] or None) and lane_iters [P] (total solver
        iterations per lane, RE summed over entities) device arrays."""
        if st.kind == "re":
            program = re_population_update_program(
                self.task,
                st.opt_config.optimizer_config,
                st.has_l1,
                VarianceComputationType.NONE,
                st.dataset.n_entities,
                self.re_solver,
                self.precision,
            )
            coeffs, score, _var, ok, _reasons, iters = program(
                state["coeffs"],
                state["score"],
                None,
                offsets_pop,
                lane["l2_rows"],
                lane["l1"],
                st.buckets,
                st.norm_tables,
                st.view,
                sample_slots=st.sample_slots,
            )
            lane_iters = functools.reduce(
                operator.add,
                (jnp.sum(b, axis=-1).astype(jnp.int32) for b in iters),
            )
            return coeffs, score, (ok, None, None), lane_iters
        program = fe_population_update_program(
            self.task,
            st.opt_config.optimizer_config,
            st.has_l1,
            st.down_sampling,
        )
        keep_u = (
            self._keep_u(st.cid, iteration)
            if st.down_sampling
            else jnp.zeros((0,), dtype=jnp.float32)
        )
        coeffs, score, coefs_ok, value_ok, values, iters, _reasons = program(
            state["coeffs"],
            state["score"],
            offsets_pop,
            lane["l2"],
            lane["l1"],
            lane["rates"],
            keep_u,
            st.dataset.data,
            st.norm,
        )
        return (
            coeffs, score, (coefs_ok, value_ok, values),
            iters.astype(jnp.int32),
        )

    def _table_dtype(self, st: _CoordStatic):
        """Random-effect population tables live at the precision policy's
        storage dtype; fixed-effect tables (and the reference policy) keep
        the compute dtype — mirroring the single-model update program."""
        if st.kind == "re" and not self.precision.is_reference:
            return self.precision.storage_dtype
        return self.dtype

    def _score_dtype(self, st: _CoordStatic):
        if st.kind == "re" and not self.precision.is_reference:
            return self.precision.accum_dtype
        return self.dtype

    def _init_state(self, p_pad: int) -> dict:
        states = {}
        for cid, st in self._static.items():
            if st.kind == "re":
                shape = (p_pad, st.dataset.n_entities, st.dataset.max_k)
            else:
                shape = (p_pad, st.dataset.dim)
            states[cid] = {
                "coeffs": jnp.zeros(shape, dtype=self._table_dtype(st)),
                # a zero model scores exactly zero everywhere
                "score": jnp.zeros(
                    (p_pad, self.n_samples), dtype=self._score_dtype(st)
                ),
            }
        return states

    def _train_vmapped(self, settings: list, n_iterations: int) -> PopulationResult:
        p_live = len(settings)
        p_pad = _next_pow2(p_live, _MIN_POPULATION_PAD)
        lanes = {
            cid: {
                k: self._pad(v, p_pad)
                for k, v in self._lane_values(st, settings).items()
            }
            for cid, st in self._static.items()
        }
        states = self._init_state(p_pad)
        guards: list[tuple] = []
        for iteration in range(n_iterations):
            # iteration-boundary recompute keeps the total a pure function of
            # the per-coordinate scores (the descent loop's determinism rule)
            total = functools.reduce(
                operator.add, (s["score"] for s in states.values())
            )
            for cid, st in self._static.items():
                partial = total - states[cid]["score"]
                offsets_pop = self.base_offsets[None, :] + partial
                coeffs, score, guard, iters = self._dispatch_update(
                    st, states[cid], lanes[cid], offsets_pop, iteration
                )
                states[cid] = {"coeffs": coeffs, "score": score}
                total = partial + score
                # lane index IS the setting index on the vmapped path
                guards.append((iteration, cid, guard, iters, None))
        incidents, rejected, lane_iters = self._materialize_guards(guards, p_live)
        return PopulationResult(
            settings=settings,
            coeffs={cid: s["coeffs"][:p_live] for cid, s in states.items()},
            train_scores={cid: s["score"][:p_live] for cid, s in states.items()},
            incidents=incidents,
            rejected=rejected,
            path="vmapped",
            lane_iterations=lane_iters,
        )

    def _train_sequential(self, settings: list, n_iterations: int) -> PopulationResult:
        """The shared-program fallback: one dispatch per setting per update,
        every lane of the SAME compiled population program filled with that
        setting, lane 0 extracted — bitwise-identical per setting to the
        vmapped path (lane-content independence), at the honest cost of
        p_pad duplicate lanes per dispatch plus per-setting dispatch
        overhead. Expressible here and not on the lane axis: dict-keyed
        per-entity L2 overrides (resolved host-side per setting)."""
        p_live = len(settings)
        p_pad = _next_pow2(p_live, _MIN_POPULATION_PAD)
        guards: list[tuple] = []
        final_coeffs: dict[str, list] = {cid: [] for cid in self._static}
        final_scores: dict[str, list] = {cid: [] for cid in self._static}
        for p, setting in enumerate(settings):
            lanes = {}
            for cid, st in self._static.items():
                vals = self._sequential_lane_values(st, setting)
                lanes[cid] = {
                    k: jnp.asarray(
                        np.repeat(v, p_pad, axis=0), dtype=self.dtype
                    )
                    for k, v in vals.items()
                }
            states = self._init_state(p_pad)
            for iteration in range(n_iterations):
                total = functools.reduce(
                    operator.add, (s["score"] for s in states.values())
                )
                for cid, st in self._static.items():
                    partial = total - states[cid]["score"]
                    offsets_pop = self.base_offsets[None, :] + partial
                    coeffs, score, guard, iters = self._dispatch_update(
                        st, states[cid], lanes[cid], offsets_pop, iteration
                    )
                    states[cid] = {"coeffs": coeffs, "score": score}
                    total = partial + score
                    # every lane is this setting; record lane 0's flags for it
                    guards.append(
                        (
                            iteration,
                            cid,
                            tuple(None if g is None else g[:1] for g in guard),
                            iters[:1],
                            p,
                        )
                    )
            for cid, s in states.items():
                final_coeffs[cid].append(s["coeffs"][0])
                final_scores[cid].append(s["score"][0])
        incidents, rejected, lane_iters = self._materialize_guards(guards, p_live)
        return PopulationResult(
            settings=settings,
            coeffs={cid: jnp.stack(v) for cid, v in final_coeffs.items()},
            train_scores={cid: jnp.stack(v) for cid, v in final_scores.items()},
            incidents=incidents,
            rejected=rejected,
            path="sequential",
            lane_iterations=lane_iters,
        )

    # ---------------------------------------------------------- fused path

    def _settings_sharding(self, ndim: int):
        from jax.sharding import NamedSharding, PartitionSpec

        axis = self.mesh.axis_names[0]
        return NamedSharding(
            self.mesh, PartitionSpec(axis, *([None] * (ndim - 1)))
        )

    def _replicated(self):
        from jax.sharding import NamedSharding, PartitionSpec

        return NamedSharding(self.mesh, PartitionSpec())

    def _fused_coord_data(self) -> dict:
        """The fused program's broadcast per-coordinate data pytrees. Under a
        mesh, device_put REPLICATED once and cached (every device reads its
        own copy of the shared datasets — the settings axis exchanges
        nothing)."""
        cached = getattr(self, "_fused_data_cache", None)
        if cached is not None:
            return cached
        datas = {}
        for cid, st in self._static.items():
            if st.kind == "re":
                datas[cid] = {
                    "buckets": st.buckets,
                    "norm_tables": st.norm_tables,
                    "view": st.view,
                }
            else:
                datas[cid] = {"data": st.dataset.data, "norm": st.norm}
        if self.mesh is not None:
            rep = self._replicated()
            datas = jax.tree_util.tree_map(
                lambda a: jax.device_put(a, rep), datas
            )
            self._fused_offsets = jax.device_put(self.base_offsets, rep)
        else:
            self._fused_offsets = self.base_offsets
        self._fused_data_cache = datas
        return datas

    def _fused_program(
        self, n_iterations: int, min_freeze_iterations: int,
        with_domination: bool, warm: bool, capture: bool,
    ):
        key = (
            n_iterations, min_freeze_iterations, with_domination, warm, capture,
        )
        cache = getattr(self, "_fused_programs", None)
        if cache is None:
            cache = self._fused_programs = {}
        program = cache.get(key)
        if program is None:
            specs = []
            for cid, st in self._static.items():
                specs.append(
                    PopulationCoordinateSpec(
                        cid=cid,
                        kind=st.kind,
                        opt_config=st.opt_config.optimizer_config,
                        has_l1=st.has_l1,
                        n_entities=(
                            st.dataset.n_entities if st.kind == "re" else 0
                        ),
                        down_sampling=st.down_sampling,
                    )
                )
            program = make_population_sweep_program(
                self.task,
                tuple(specs),
                n_iterations,
                re_solver=self.re_solver,
                precision=self.precision,
                min_freeze_iterations=min_freeze_iterations,
                with_domination=with_domination,
                warm_start=warm,
                capture_pass_states=capture,
                mesh=self.mesh,
            )
            cache[key] = program
        return program

    def _domination_data(self):
        """[N] labels/weights for the per-lane training-loss domination
        check, from a fixed-effect coordinate's LabeledData (every
        coordinate scores the same samples)."""
        for st in self._static.values():
            if st.kind == "fe":
                return st.dataset.data.labels, st.dataset.data.weights
        raise ValueError(
            "domination_bound needs training labels; this estimator has no "
            "fixed-effect coordinate to take them from"
        )

    def _fused_args(
        self, settings: list, n_iterations: int,
        early_exit: Optional[EarlyExitConfig],
        warm_start: Optional[Mapping[str, Array]],
        capture_pass_states: bool,
    ):
        """(program, args, guard_labels, p_live): everything a fused dispatch
        — or a compile-only lowering of the identical program on identical
        arguments (``lower_fused_sweep``) — needs."""
        p_live = len(settings)
        m = self.mesh.devices.size if self.mesh is not None else 1
        p_pad = _next_pow2(p_live, _MIN_POPULATION_PAD)
        if p_pad % m:
            p_pad = ((p_pad + m - 1) // m) * m
        lanes = {
            cid: {
                k: self._pad(v, p_pad)
                for k, v in self._lane_values(st, settings).items()
            }
            for cid, st in self._static.items()
        }
        coeffs0 = {}
        for cid, st in self._static.items():
            dtype = self._table_dtype(st)
            if warm_start is not None:
                if cid not in warm_start:
                    raise ValueError(f"warm_start is missing coordinate {cid!r}")
                warm = jnp.asarray(warm_start[cid], dtype=dtype)
                if warm.shape[0] != p_live:
                    raise ValueError(
                        f"warm_start[{cid!r}] has {warm.shape[0]} lanes, "
                        f"population has {p_live}"
                    )
                if p_pad > p_live:
                    warm = jnp.concatenate(
                        [warm, jnp.repeat(warm[:1], p_pad - p_live, axis=0)]
                    )
                coeffs0[cid] = warm
            elif st.kind == "re":
                coeffs0[cid] = jnp.zeros(
                    (p_pad, st.dataset.n_entities, st.dataset.max_k), dtype=dtype
                )
            else:
                coeffs0[cid] = jnp.zeros((p_pad, st.dataset.dim), dtype=dtype)
        active0 = jnp.ones((p_pad,), dtype=bool)
        keep_us = {
            cid: jnp.stack(
                [self._keep_u(cid, it) for it in range(n_iterations)]
            )
            for cid, st in self._static.items()
            if st.kind == "fe" and st.down_sampling
        }
        with_domination = (
            early_exit is not None and early_exit.domination_bound is not None
        )
        if with_domination:
            labels, weights = self._domination_data()
            domination_bound = float(early_exit.domination_bound)
        else:
            labels = weights = jnp.zeros((0,), dtype=self.dtype)
            domination_bound = float("inf")
        freeze_tol = float(early_exit.freeze_tol) if early_exit is not None else -1.0
        min_iters = early_exit.min_iterations if early_exit is not None else 1
        datas = self._fused_coord_data()
        if self.mesh is not None:
            coeffs0 = {
                cid: jax.device_put(a, self._settings_sharding(a.ndim))
                for cid, a in coeffs0.items()
            }
            lanes = {
                cid: {
                    k: jax.device_put(a, self._settings_sharding(a.ndim))
                    for k, a in lane.items()
                }
                for cid, lane in lanes.items()
            }
            active0 = jax.device_put(active0, self._settings_sharding(1))
            rep = self._replicated()
            keep_us = {k: jax.device_put(v, rep) for k, v in keep_us.items()}
            if with_domination:
                labels = jax.device_put(labels, rep)
                weights = jax.device_put(weights, rep)
        program = self._fused_program(
            n_iterations, min_iters, with_domination,
            warm_start is not None, capture_pass_states,
        )
        guard_labels = [
            (it, cid)
            for it in range(n_iterations)
            for cid in self._static
        ]
        args = (
            coeffs0, lanes, active0, self._fused_offsets, keep_us,
            freeze_tol, domination_bound, labels, weights, datas,
        )
        return program, args, guard_labels, p_live

    def _train_fused(
        self, settings: list, n_iterations: int,
        early_exit: Optional[EarlyExitConfig],
        warm_start: Optional[Mapping[str, Array]],
        capture_pass_states: bool,
    ) -> PopulationResult:
        program, args, guard_labels, p_live = self._fused_args(
            settings, n_iterations, early_exit, warm_start, capture_pass_states
        )
        states, stats, guards_dev, snapshots = program(*args)
        guards = [
            (it, cid, guard, None, None)
            for (it, cid), guard in zip(guard_labels, guards_dev)
        ]
        incidents, rejected, _ = self._materialize_guards(guards, p_live)
        host_stats = jax.device_get(stats)
        lane_iterations = np.asarray(host_stats["lane_iterations"][:p_live])
        frozen_at = np.asarray(host_stats["frozen_at"][:p_live])
        return PopulationResult(
            settings=settings,
            coeffs={cid: s["coeffs"][:p_live] for cid, s in states.items()},
            train_scores={cid: s["score"][:p_live] for cid, s in states.items()},
            incidents=incidents,
            rejected=rejected,
            path="fused",
            lane_iterations=lane_iterations,
            frozen_at=frozen_at,
            pass_states=(
                [
                    {
                        cid: {k: v[:p_live] for k, v in s.items()}
                        for cid, s in snap.items()
                    }
                    for snap in snapshots
                ]
                if capture_pass_states
                else None
            ),
        )

    def lower_fused_sweep(
        self,
        settings: Sequence[dict],
        n_iterations: int = 1,
        early_exit: Optional[EarlyExitConfig] = None,
        warm_start: Optional[Mapping[str, Array]] = None,
    ) -> str:
        """Compiled-module text of EXACTLY the fused program a
        ``train(..., fused=True)`` call with these arguments dispatches —
        the input ``hlo_guards.assert_settings_axis_collective_free``
        audits (the mesh x population zero-data-collective contract)."""
        program, args, _, _ = self._fused_args(
            list(settings), n_iterations, early_exit, warm_start, False
        )
        return program.lower(*args).compile().as_text()

    def _materialize_guards(
        self, guards: list, p_live: int
    ) -> tuple[list, np.ndarray, np.ndarray]:
        """ONE batched transfer for every update's per-lane guard flags AND
        per-lane solver iteration counts, then incident records for the
        rejects (the reject itself already happened in-program — this is the
        paper trail, coordinate_descent._flush_guards style). Guard entries
        carry an explicit setting index for sequential dispatches (every lane
        is one setting there); vmapped entries map lane index -> setting
        index directly. Returns (incidents, rejected [P], lane_iterations
        [P])."""
        incidents: list[Incident] = []
        rejected = np.zeros(p_live, dtype=bool)
        lane_iterations = np.zeros(p_live, dtype=np.int64)
        if not guards:
            return incidents, rejected, lane_iterations
        host = jax.device_get([(g, it) for _, _, g, it, _ in guards])
        for (iteration, cid, _, _, setting_idx), (
            (coefs_ok, value_ok, values), iters
        ) in zip(guards, host):
            if iters is not None:
                # the fused path's iteration counts arrive via its stats
                # output instead; per-update entries accumulate here
                iters = np.atleast_1d(np.asarray(iters))
                if setting_idx is not None:
                    lane_iterations[setting_idx] += int(iters[0])
                else:
                    lane_iterations += iters[:p_live].astype(np.int64)
            coefs_ok = np.atleast_1d(np.asarray(coefs_ok))
            value_ok = None if value_ok is None else np.atleast_1d(np.asarray(value_ok))
            for lane in range(coefs_ok.shape[0]):
                p = setting_idx if setting_idx is not None else lane
                if p >= p_live:
                    continue  # padding lane: a duplicate of lane 0, not a setting
                if value_ok is not None and not bool(value_ok[lane]):
                    v = float(np.asarray(values)[lane])
                    cause = f"training objective is non-finite ({v})"
                elif not bool(coefs_ok[lane]):
                    cause = "solver emitted non-finite coefficients"
                else:
                    continue
                rejected[p] = True
                incidents.append(
                    Incident(
                        kind="divergence",
                        cause=cause,
                        action="update rejected; previous setting state kept",
                        coordinate_id=cid,
                        iteration=iteration,
                        detail=f"setting={p}",
                    )
                )
        return incidents, rejected, lane_iterations

    # ---------------------------------------------------- population scoring

    def _scoring_align_map(self, st: _CoordStatic, scoring_ds):
        """Train-layout -> scoring-layout gather map, computed ONCE per
        (coordinate, scoring dataset): the same re-layout
        ``RandomEffectModel.aligned_to`` performs per model, but as index
        arrays the whole POPULATION gathers through in one device op — P
        per-lane host alignments collapse into one [P, E_val, K_val] gather."""
        key = (st.cid, id(scoring_ds))
        cached = self._align_maps.get(key)
        if cached is not None:
            return cached
        train_ds = st.dataset
        if (train_ds.projector is None) != (scoring_ds.projector is None):
            # mirrors RandomEffectModel.score_dataset's refusal: coefficients
            # in one space dotted with features in another are garbage
            raise ValueError(
                f"coordinate {st.cid!r}: training and scoring datasets "
                "disagree on projection; rebuild the scoring dataset with "
                "the training projector"
            )
        src_proj = np.asarray(train_ds.proj_indices)
        dst_proj = np.asarray(scoring_ds.proj_indices)
        row_by_entity = {e: i for i, e in enumerate(train_ds.entity_ids)}
        E_val, K_val = dst_proj.shape
        rows = np.zeros((E_val, K_val), dtype=np.int32)
        cols = np.zeros((E_val, K_val), dtype=np.int32)
        mask = np.zeros((E_val, K_val), dtype=bool)
        for i, e in enumerate(scoring_ds.entity_ids):
            r = row_by_entity.get(e, -1)
            if r < 0:
                continue  # unseen entity: scores 0, like the eager path
            col_slot = {int(c): k for k, c in enumerate(src_proj[r]) if c >= 0}
            for k, c in enumerate(dst_proj[i]):
                if c < 0:
                    continue
                kk = col_slot.get(int(c), -1)
                if kk >= 0:
                    rows[i, k], cols[i, k], mask[i, k] = r, kk, True
        out = (jnp.asarray(rows), jnp.asarray(cols), jnp.asarray(mask))
        self._align_maps[key] = out
        self._scoring_refs[id(scoring_ds)] = scoring_ds
        return out

    def _population_scorer(self, st: _CoordStatic, scoring_ds):
        """Jitted population scorer for one (coordinate, scoring dataset),
        cached so repeated rounds reuse one compiled program."""
        key = (st.cid, id(scoring_ds))
        scorer = self._pop_scorers.get(key)
        if scorer is not None:
            return scorer
        if st.kind == "fe":
            X = scoring_ds.data.X

            scorer = jax.jit(jax.vmap(lambda w: X.matvec(w)))
        else:
            from photon_ml_tpu.models.game import random_effect_view_score

            rows, cols, mask = self._scoring_align_map(st, scoring_ds)
            entity_rows, local_cols, vals = scoring_ds.scoring_view()

            def score_all(tables):
                aligned = jnp.where(mask, tables[:, rows, cols], 0.0)
                return jax.vmap(
                    random_effect_view_score, in_axes=(0, None, None, None)
                )(aligned, entity_rows, local_cols, vals)

            scorer = jax.jit(score_all)
        self._pop_scorers[key] = scorer
        self._scoring_refs[id(scoring_ds)] = scoring_ds
        return scorer

    def score_population(
        self, result: PopulationResult, scoring_datasets: Mapping[str, object]
    ) -> Array:
        """Every setting's total [P, N_val] validation score in a handful of
        batched dispatches (one per coordinate) — the per-lane equivalent of
        summing ``score_model_on_dataset`` over coordinates, with the model
        re-alignment hoisted into a cached gather map instead of P host-side
        ``aligned_to`` calls per round."""
        total = None
        for cid, st in self._static.items():
            s = self._population_scorer(st, scoring_datasets[cid])(result.coeffs[cid])
            total = s if total is None else total + s
        return total

    # --------------------------------------------------------------- models

    def build_models(self, result: PopulationResult, lane: int) -> dict:
        """Materialize one setting's GAME models from the population tables
        (the winner-export path; also validation scoring per lane)."""
        models: dict[str, object] = {}
        for cid, st in self._static.items():
            table = result.coeffs[cid][lane]
            if st.kind == "fe":
                glm = model_class_for_task(self.task)(Coefficients(means=table))
                models[cid] = FixedEffectModel(
                    model=glm, feature_shard_id=st.dataset.feature_shard_id
                )
            else:
                ds = st.dataset
                models[cid] = RandomEffectModel(
                    re_type=ds.re_type,
                    feature_shard_id=ds.feature_shard_id,
                    task=self.task,
                    entity_ids=ds.entity_ids,
                    coeffs=table,
                    proj_indices=ds.proj_indices,
                    projector=ds.projector,
                )
        return models
