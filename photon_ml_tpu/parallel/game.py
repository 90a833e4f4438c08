"""One full GLMix coordinate-descent pass as a single jitted SPMD program.

This is the multi-chip production path for the flagship model (fixed effect +
per-entity random effects, BASELINE.json config #3). The reference runs the same
pass as a driver-orchestrated sequence of Spark jobs (CoordinateDescent.scala:
119-346: per-coordinate broadcast/treeAggregate solves + score-exchange joins).
Here the ENTIRE pass — fixed-effect L-BFGS solve, per-entity vmap-ed solves for
every random-effect coordinate, and the residual score exchange — is one XLA
program over a device mesh:

- fixed-effect samples: sharded over the mesh axis (data parallel; gradient psum);
- random-effect entity blocks: sharded over the same axis (expert-parallel-like;
  zero comm inside the vmap-ed solves);
- the [N] score axis: sharded; `partial = total - own` residual updates
  (CoordinateDescent.scala:197-204) are elementwise, not joins.

Padding discipline: padded samples carry weight 0; padded bucket entities scatter
into a junk coefficient row (index E) that no scoring gather ever reads.
"""

from __future__ import annotations

import dataclasses
import functools
import operator
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.data.dataset import LabeledData
from photon_ml_tpu.data.random_effect import RandomEffectDataset
from photon_ml_tpu.normalization import NO_NORMALIZATION
from photon_ml_tpu.optimization.config import GLMOptimizationConfiguration
from photon_ml_tpu.parallel.mesh import (
    batch_sharding,
    pad_put,
    replicated_sharding,
)
from photon_ml_tpu.types import TaskType

Array = jnp.ndarray


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedREBucket:
    """One padded entity block, leading (entity) axis sharded over the mesh."""

    entity_rows: Array  # [E_b] int32 into the coordinate's [E+1] coeff table (E = junk)
    X: Array  # [E_b, S, K]
    labels: Array  # [E_b, S]
    weights: Array  # [E_b, S] (0 = padding)
    sample_ids: Array  # [E_b, S] int32 global sample ids, -1 pad


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedRECoordinate:
    """One random-effect coordinate: training buckets + per-sample scoring view."""

    buckets: tuple  # tuple[ShardedREBucket, ...]
    sample_entity_rows: Array  # [N] int32, -1 = no model
    sample_local_cols: Array  # [N, nnz] int32, -1 pad
    sample_vals: Array  # [N, nnz]
    n_entities: int = dataclasses.field(metadata=dict(static=True))
    max_k: int = dataclasses.field(metadata=dict(static=True))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedGameData:
    """Flagship GLMix training data placed on a mesh: fixed-effect design matrix
    (dense [N, D] blocks samples-sharded, or padded-COO sparse with the nnz axis
    sharded — the billion-feature regime) + one ShardedRECoordinate per random
    effect."""

    fe_X: object  # DenseDesignMatrix | SparseDesignMatrix, samples/nnz sharded
    labels: Array  # [N]
    offsets: Array  # [N]
    weights: Array  # [N] (0 = sample padding)
    re: tuple  # tuple[ShardedRECoordinate, ...]

    @property
    def n(self) -> int:
        return self.labels.shape[0]


def build_sharded_game_data(
    fe_X,
    labels: np.ndarray,
    re_datasets: Sequence[RandomEffectDataset],
    mesh,
    *,
    offsets: Optional[np.ndarray] = None,
    weights: Optional[np.ndarray] = None,
    dtype=jnp.float32,
    fe_storage_dtype=None,
    re_storage_dtype=None,
) -> ShardedGameData:
    """Host-side placement: pad the sample axis and every bucket's entity axis to
    the mesh size, then device_put with batch/entity sharding.

    ``fe_X`` may be a dense [N, D] array (samples sharded as [N', D] blocks) or a
    scipy sparse / SparseDesignMatrix (COO nnz axis sharded; scatter-adds psum —
    the sparse billion-feature path of parallel/glm.py).

    ``fe_storage_dtype=jnp.bfloat16`` stores the dense fixed-effect design
    matrix in bf16 (matvecs read half the HBM bytes and hit the MXU natively;
    accumulation stays f32 — see DenseDesignMatrix._mxu_dot).
    ``re_storage_dtype=jnp.bfloat16`` does the same for the random-effect
    bucket blocks and the per-sample scoring values — the hot loops of the
    2026-07-31 on-chip trace (ROADMAP.md S2) read exactly those arrays every
    solver iteration. Labels, weights, scores and coefficients keep ``dtype``."""
    from photon_ml_tpu.data.matrix import as_design_matrix_with_storage
    from photon_ml_tpu.parallel.glm import shard_labeled_data

    m = mesh.devices.size
    bs1, bs2, bs3 = (batch_sharding(mesh, ndim=k) for k in (1, 2, 3))
    n = np.asarray(labels).shape[0]
    offsets = np.zeros(n) if offsets is None else np.asarray(offsets)
    weights = np.ones(n) if weights is None else np.asarray(weights)

    def put(arr, sharding, *, fill=0, to_dtype=None):
        placed, _ = pad_put(arr, m, sharding, fill=fill, to_dtype=to_dtype)
        return placed

    fe_mat = as_design_matrix_with_storage(fe_X, fe_storage_dtype, dtype)
    fe_data, _ = shard_labeled_data(
        LabeledData.build(
            fe_mat, labels, offsets=offsets, weights=weights, dtype=dtype,
        ),
        mesh,
    )
    yp, op, wp = fe_data.labels, fe_data.offsets, fe_data.weights

    re_store = re_storage_dtype or dtype
    coords = []
    for ds in re_datasets:
        E = ds.n_entities
        buckets = []
        for b in ds.buckets:
            buckets.append(
                ShardedREBucket(
                    entity_rows=put(b.entity_rows, bs1, fill=E),
                    X=put(b.X, bs3, to_dtype=re_store),
                    labels=put(b.labels, bs2, to_dtype=dtype),
                    weights=put(b.weights, bs2, to_dtype=dtype),
                    sample_ids=put(b.sample_ids, bs2, fill=-1),
                )
            )
        coords.append(
            ShardedRECoordinate(
                buckets=tuple(buckets),
                sample_entity_rows=put(ds.sample_entity_rows, bs1, fill=-1),
                sample_local_cols=put(ds.sample_local_cols, bs2, fill=-1),
                sample_vals=put(ds.sample_vals, bs2, to_dtype=re_store),
                n_entities=E,
                max_k=ds.max_k,
            )
        )

    return ShardedGameData(
        fe_X=fe_data.X,
        labels=yp,
        offsets=op,
        weights=wp,
        re=tuple(coords),
    )


def init_game_params(data: ShardedGameData, mesh) -> dict:
    """Zero-initialized flagship parameters: replicated fixed-effect coefficients +
    one [E_pad+pad, K] ENTITY-SHARDED table per random effect. The table height is
    padded to a mesh multiple past E+1 (row E is the junk row for bucket padding;
    rows above are sharding padding, both kept zero by game_train_step)."""
    m = mesh.devices.size
    rep = replicated_sharding(mesh)
    es = batch_sharding(mesh, ndim=2)
    # labels carry the COMPUTE dtype; fe_X may hold a lower STORAGE dtype (bf16)
    dtype = data.labels.dtype
    fe = jax.device_put(jnp.zeros((data.fe_X.n_cols,), dtype=dtype), rep)
    re = tuple(
        jax.device_put(
            jnp.zeros((-(-(rc.n_entities + 1) // m) * m, rc.max_k), dtype=dtype), es
        )
        for rc in data.re
    )
    return {"fixed": fe, "re": re}


def _re_score(rc: ShardedRECoordinate, coeffs: Array) -> Array:
    """[N] scores via the per-sample gathered view (RandomEffectModel.score
    semantics: entities without a model score 0)."""
    has_model = rc.sample_entity_rows >= 0
    w = coeffs[jnp.maximum(rc.sample_entity_rows, 0)]  # [N, K]
    gathered = jnp.take_along_axis(w, jnp.maximum(rc.sample_local_cols, 0), axis=1)
    gathered = jnp.where(rc.sample_local_cols >= 0, gathered, 0.0)
    return jnp.where(has_model, jnp.sum(gathered * rc.sample_vals, axis=1), 0.0)


def game_train_step(
    data: ShardedGameData,
    params: dict,
    task: TaskType,
    fe_config: GLMOptimizationConfiguration,
    re_configs: Sequence[GLMOptimizationConfiguration],
    fuse_fe: bool = False,
    shard_mesh=None,
    fe_l2=None,
    re_l2=None,
    re_solver: str = "lbfgs",
) -> tuple[dict, dict]:
    """One pure (jittable) coordinate-descent pass over [fixed, re_0, re_1, ...].

    ``fe_l2``/``re_l2`` (scalar / sequence of scalars) override the configs'
    L2 weights as TRACED values: a caller sweeping regularization weights can
    then reuse one compiled program across the whole sweep instead of
    baking each weight in as a trace-time constant.

    ``re_solver`` selects the random-effect inner bucket solver
    (optimization/normal_equations.py — "lbfgs" | "direct" | "auto"); the
    fixed-effect solve always runs the configured optimizer.

    Returns (new params, diagnostics {fe_value, fe_iterations, total_scores}).
    """
    from photon_ml_tpu.optimization.solver_cache import (
        glm_solver,
        re_bucket_solver,
        shard_mapped_glm_solver,
    )
    from photon_ml_tpu.types import VarianceComputationType

    task = TaskType(task)
    no_var = VarianceComputationType.NONE

    fe_coef = params["fixed"]
    re_coeffs = list(params["re"])
    dtype = fe_coef.dtype
    fe_l2 = jnp.asarray(
        fe_config.l2_weight if fe_l2 is None else fe_l2, dtype=dtype
    )
    re_l2 = [
        jnp.asarray(cfg.l2_weight if re_l2 is None else re_l2[i], dtype=dtype)
        for i, cfg in enumerate(re_configs)
    ]

    fe_score = data.fe_X.matvec(fe_coef)
    re_scores = [_re_score(rc, w) for rc, w in zip(data.re, re_coeffs)]
    total = fe_score + sum(re_scores) if re_scores else fe_score

    # ---- fixed-effect coordinate (partial = total - own) ------------------------
    # Shares the cached solver with GLMOptimizationProblem.run: one update logic,
    # two drivers (this fused pass and the host coordinate-descent loop).
    d = LabeledData(
        X=data.fe_X,
        labels=data.labels,
        offsets=data.offsets + (total - fe_score),
        weights=data.weights,
    )
    empty = jnp.zeros((0,), dtype=dtype)
    # Pallas routing: on a single chip the opt-in fused kernel rides the
    # stock GSPMD-free solve (fuse_fe). On a MULTI-chip mesh GSPMD cannot
    # partition an opaque pallas_call, so when the kernels are enabled the
    # fixed-effect solve switches to the shard_map form — per-device fused
    # blocks + explicit psum (shard_mapped_glm_solver) — instead of silently
    # dropping the fusion.
    from photon_ml_tpu.data.matrix import DenseDesignMatrix
    from photon_ml_tpu.ops import pallas_glm

    use_shard_map = (
        shard_mesh is not None
        and isinstance(data.fe_X, DenseDesignMatrix)
        and pallas_glm.pallas_enabled()
    )
    if use_shard_map:
        fe_solve_sm = shard_mapped_glm_solver(
            task, fe_config.optimizer_config, bool(fe_config.l1_weight), shard_mesh
        )
        fe_res = fe_solve_sm(
            d,
            fe_coef,
            fe_l2,
            jnp.asarray(fe_config.l1_weight or 0.0, dtype=dtype),
        )
    else:
        fe_solve = glm_solver(
            task, fe_config.optimizer_config, bool(fe_config.l1_weight), False, False,
            no_var, allow_fused=fuse_fe,
        )
        fe_res, _ = fe_solve(
            d,
            fe_coef,
            fe_l2,
            jnp.asarray(fe_config.l1_weight or 0.0, dtype=dtype),
            empty,
            empty,
            NO_NORMALIZATION,
        )
    fe_coef = fe_res.coefficients
    fe_score = data.fe_X.matvec(fe_coef)
    total = fe_score + sum(re_scores) if re_scores else fe_score

    # ---- random-effect coordinates ----------------------------------------------
    re_iter_maxes = []
    for i, (rc, cfg) in enumerate(zip(data.re, re_configs)):
        solve = re_bucket_solver(
            task, cfg.optimizer_config, bool(cfg.l1_weight), no_var, re_solver
        )
        offsets_plus = data.offsets + (total - re_scores[i])
        coeffs = re_coeffs[i]
        bucket_iters = []
        for b in rc.buckets:
            K = b.X.shape[2]
            off_b = jnp.take(offsets_plus, jnp.maximum(b.sample_ids, 0), axis=0)
            off_b = jnp.where(b.sample_ids >= 0, off_b, 0.0)
            w0_b = coeffs[b.entity_rows, :K]
            w_b, _, it_b, _, _ = solve(
                b.X,
                b.labels,
                b.weights,
                off_b,
                w0_b,
                jnp.full((b.entity_rows.shape[0],), 1.0, dtype=dtype) * re_l2[i],
                jnp.asarray(cfg.l1_weight or 0.0, dtype=dtype),
            )
            coeffs = coeffs.at[b.entity_rows, :K].set(w_b)
            # a vmapped while_loop runs until EVERY lane converges, so the
            # bucket's executed iteration count is the max over entities —
            # the measured input to bench.py's roofline cost model
            bucket_iters.append(jnp.max(it_b))
        # junk + sharding-padding rows must stay zero: bucket padding scattered
        # garbage into row E (rows above are device_put padding)
        coeffs = coeffs.at[rc.n_entities :].set(0.0)
        re_coeffs[i] = coeffs
        re_scores[i] = _re_score(rc, coeffs)
        total = fe_score + sum(re_scores)
        re_iter_maxes.append(tuple(bucket_iters))

    new_params = {"fixed": fe_coef, "re": tuple(re_coeffs)}
    diagnostics = {
        "fe_value": fe_res.value,
        "fe_iterations": fe_res.iterations,
        "total_scores": total,
        "re_iterations_max": tuple(re_iter_maxes),
    }
    return new_params, diagnostics


@dataclasses.dataclass(frozen=True)
class PopulationCoordinateSpec:
    """Static description of one coordinate inside the fused population
    sweep program (hashable — part of the program-builder key). The traced
    data rides separately (``population_sweep_fn``'s ``datas`` argument)."""

    cid: str
    kind: str  # "fe" | "re"
    opt_config: object  # OptimizerConfig (frozen dataclass, hashable)
    has_l1: bool
    n_entities: int = 0  # RE only
    down_sampling: bool = False  # FE only


def population_sweep_fn(
    task: TaskType,
    coord_specs: tuple,
    n_iterations: int,
    *,
    re_solver: str = "lbfgs",
    precision=None,
    min_freeze_iterations: int = 1,
    with_domination: bool = False,
    warm_start: bool = False,
    capture_pass_states: bool = False,
    lane_constraint=None,
):
    """The settings axis on the fused GAME pass: ONE trace covers ALL
    settings x ALL coordinates x ALL descent iterations — model selection
    collapsed into a single program the way ``game_train_step`` collapsed the
    per-coordinate Spark jobs of one pass. The per-lane per-coordinate bodies
    are EXACTLY the population update bodies
    (``optimization/solver_cache._re_coordinate_update_fn`` /
    ``_fe_population_update_fn`` with ``with_active=True``), so a fused lane
    and a per-update-dispatch lane run the same update logic.

    The settings axis is embarrassingly parallel BY CONSTRUCTION: a lane's
    offsets come from its own coordinates' scores only, so no cross-lane op
    exists anywhere in the trace — which is what lets a mesh shard the lane
    axis (``P(settings, None, ...)`` tables, data replicated) with ZERO data
    collectives in the compiled module
    (``parallel/hlo_guards.assert_settings_axis_collective_free``; the one
    tolerated op is the batched while_loops' single-element
    convergence-consensus all-reduce).

    Per-lane EARLY EXIT runs at pass boundaries, inside the trace:

    - **convergence**: a lane whose total training score moved at most
      ``freeze_tol * (1 + max|score|)`` since the previous pass freezes —
      its remaining solves run ZERO iterations (masked stationary objective,
      ``solver_cache._masked_value_and_grad``), so the batched while_loops'
      trip counts track the slowest SURVIVING lane and the population's
      wall-clock tracks the median lane, not the slowest. ``freeze_tol`` is
      a TRACED scalar: a negative value never freezes, so the same compiled
      program measures early-exit on vs off (the bench's winner-unchanged
      gate compares within one program).
    - **domination** (``with_domination=True``): a lane whose per-lane
      weighted mean training loss exceeds the TRACED ``domination_bound``
      (a host-derived scalar, e.g. from the previous round's best — never a
      cross-lane reduction, which would put a collective on the settings
      axis) freezes the same way. ``+inf`` disables it per dispatch.

    Frozen lanes carry their committed state bitwise (the update bodies
    select-freeze outputs to the previous tables/scores), report no rejects,
    and contribute zero solver iterations; ``frozen_at`` records the number
    of completed passes at freeze time (-1 = ran every pass).

    ``sweep(coeffs0, lanes, active0, base_offsets, keep_us, freeze_tol,
    domination_bound, labels, weights, datas) ->
    (states, stats, guards, snapshots)`` where

    - ``coeffs0``: dict cid -> ``[P, ...]`` initial tables. With the static
      ``warm_start=False`` (the cold-start family) initial scores are literal
      zeros — bitwise the per-update path's init; with ``warm_start=True``
      they are computed in-trace from ``coeffs0`` with the same scoring
      kernels the updates use (glmnet-style path seeding,
      ``SweepRunner``'s cross-round warm starts).
    - ``lanes``: dict cid -> per-lane hyperparameter arrays (``l2_rows``/
      ``l1`` for RE, ``l2``/``l1``/``rates`` for FE).
    - ``keep_us``: dict cid -> ``[n_iterations, N]`` shared down-sampling
      draws (down-sampling FE coordinates only), indexed statically per
      unrolled pass.
    - ``labels``/``weights``: ``[N]`` training labels/weights, read only
      under ``with_domination`` (pass empty arrays otherwise).
    - ``datas``: dict cid -> the coordinate's broadcast device data
      (RE: ``{"buckets", "norm_tables", "view"}``; FE: ``{"data", "norm"}``).
    - ``states``: dict cid -> ``{"coeffs", "score"}`` final per-lane state;
      ``stats``: ``{"active", "frozen_at", "lane_iterations"}`` (all [P]);
      ``guards``: one ``(coefs_ok, value_ok, values)`` triple per update in
      (iteration, coordinate) order — the caller holds the static labels;
      ``snapshots``: per-pass state copies when ``capture_pass_states``
      (the freeze-contract tests' reference), else ``()``.
    """
    from photon_ml_tpu.function.losses import loss_for_task
    from photon_ml_tpu.models.game import random_effect_view_score
    from photon_ml_tpu.optimization.precision import FLOAT32
    from photon_ml_tpu.optimization.solver_cache import (
        _fe_population_update_fn,
        _re_coordinate_update_fn,
    )
    from photon_ml_tpu.types import VarianceComputationType

    task = TaskType(task)
    precision = FLOAT32 if precision is None else precision
    reduced = not precision.is_reference
    loss = loss_for_task(task) if with_domination else None

    # ``lane_constraint`` (mesh runs): pin every per-lane intermediate the
    # pass hands forward — updated states and the freeze flags — to the
    # settings sharding. Output constraints alone leave GSPMD free to
    # REPLICATE small per-lane chains mid-trace (observed: [P]-sized
    # all-gathers around the freeze selects at some shapes), which violates
    # the zero-data-collective contract the sharded program exists for.
    pin = lane_constraint if lane_constraint is not None else (lambda t: t)

    bodies = {}
    for spec in coord_specs:
        if spec.kind == "re":
            update = _re_coordinate_update_fn(
                task,
                spec.opt_config,
                spec.has_l1,
                VarianceComputationType.NONE,
                spec.n_entities,
                re_solver,
                precision,
                with_active=True,
            )
            bodies[spec.cid] = jax.vmap(
                update, in_axes=(0, 0, 0, 0, 0, 0, 0, None, None, None)
            )
        else:
            bodies[spec.cid] = _fe_population_update_fn(
                task, spec.opt_config, spec.has_l1, spec.down_sampling,
                with_active=True,
            )

    def _initial_score(spec, coeffs, data):
        if not warm_start:
            # cold start: a zero model scores EXACTLY zero — keep the literal
            # (hostile NaN features must not poison the init, matching the
            # per-update path's zeros init bitwise)
            n = (
                data["view"][0].shape[0]
                if spec.kind == "re"
                else data["data"].labels.shape[0]
            )
            return jnp.zeros((coeffs.shape[0], n), dtype=jnp.result_type(coeffs, jnp.float32))
        if spec.kind == "re":
            entity_rows, local_cols, vals = data["view"]
            if reduced:
                score_fn = lambda w: random_effect_view_score(
                    w.astype(precision.accum_dtype),
                    entity_rows,
                    local_cols,
                    vals.astype(precision.accum_dtype),
                )
            else:
                score_fn = lambda w: random_effect_view_score(
                    w, entity_rows, local_cols, vals
                )
            return jax.vmap(score_fn)(coeffs)
        return jax.vmap(data["data"].X.matvec)(coeffs)

    def sweep(
        coeffs0, lanes, active0, base_offsets, keep_us, freeze_tol,
        domination_bound, labels, weights, datas,
    ):
        specs = {s.cid: s for s in coord_specs}
        states = {}
        for cid, spec in specs.items():
            states[cid] = {
                "coeffs": coeffs0[cid],
                "score": _initial_score(spec, coeffs0[cid], datas[cid]),
            }
        active = active0
        p = active.shape[0]
        frozen_at = jnp.full((p,), -1, dtype=jnp.int32)
        lane_iters = jnp.zeros((p,), dtype=jnp.int32)
        guards = []
        snapshots = []
        prev_total = functools.reduce(
            operator.add, (s["score"] for s in states.values())
        )
        for it in range(n_iterations):
            total = functools.reduce(
                operator.add, (s["score"] for s in states.values())
            )
            for cid, spec in specs.items():
                st, lane, data = states[cid], lanes[cid], datas[cid]
                partial = total - st["score"]
                offsets_pop = base_offsets[None, :] + partial
                if spec.kind == "re":
                    coeffs, score, _var, ok, _reasons, iters, _evals = bodies[cid](
                        st["coeffs"], st["score"], None, offsets_pop,
                        lane["l2_rows"], lane["l1"], active,
                        data["buckets"], data["norm_tables"], data["view"],
                    )
                    lane_iters = lane_iters + functools.reduce(
                        operator.add,
                        (jnp.sum(b, axis=-1).astype(jnp.int32) for b in iters),
                    )
                    guards.append((ok, None, None))
                else:
                    keep_u = (
                        keep_us[cid][it]
                        if spec.down_sampling
                        else jnp.zeros((0,), dtype=jnp.float32)
                    )
                    coeffs, score, coefs_ok, value_ok, values, iters, _r = bodies[
                        cid
                    ](
                        st["coeffs"], st["score"], offsets_pop, lane["l2"],
                        lane["l1"], lane["rates"], keep_u, active,
                        data["data"], data["norm"],
                    )
                    lane_iters = lane_iters + iters.astype(jnp.int32)
                    guards.append((coefs_ok, value_ok, values))
                states[cid] = pin({"coeffs": coeffs, "score": score})
                total = partial + states[cid]["score"]
            if capture_pass_states:
                snapshots.append(
                    {cid: dict(s) for cid, s in states.items()}
                )
            if it < n_iterations - 1:
                # pass-boundary freeze check (skipped after the final pass:
                # a lane converging there skipped no work, and counting it
                # would overstate the early-exit win)
                delta = jnp.max(jnp.abs(total - prev_total), axis=-1)
                scale = 1.0 + jnp.max(jnp.abs(total), axis=-1)
                finished = delta <= freeze_tol * scale
                if with_domination:
                    margins = base_offsets[None, :] + total
                    per_sample = loss.loss(margins, labels[None, :])
                    lane_loss = jnp.sum(
                        per_sample * weights[None, :], axis=-1
                    ) / jnp.sum(weights)
                    finished = jnp.logical_or(
                        finished, lane_loss > domination_bound
                    )
                if (it + 1) >= min_freeze_iterations:
                    newly = jnp.logical_and(active, finished)
                    frozen_at = pin(jnp.where(
                        newly, jnp.int32(it + 1), frozen_at
                    ))
                    active = pin(
                        jnp.logical_and(active, jnp.logical_not(newly))
                    )
            prev_total = total
        stats = {
            "active": active,
            "frozen_at": frozen_at,
            "lane_iterations": lane_iters,
        }
        return states, stats, tuple(guards), tuple(snapshots)

    return sweep


def make_population_sweep_program(
    task: TaskType,
    coord_specs: tuple,
    n_iterations: int,
    *,
    re_solver: str = "lbfgs",
    precision=None,
    min_freeze_iterations: int = 1,
    with_domination: bool = False,
    warm_start: bool = False,
    capture_pass_states: bool = False,
    mesh=None,
):
    """jit(population_sweep_fn) with the initial tables donated. On a
    ``mesh`` every output leaf (all lead with the population axis) is pinned
    to ``P(settings, None, ...)`` via sharding constraints, so the program
    never gathers lane-axis tensors: the caller places the population state
    and lane arrays settings-sharded and the broadcast data replicated, and
    the compiled module stays free of data collectives
    (``hlo_guards.assert_settings_axis_collective_free`` audits exactly
    this). Callers cache the returned function per static key; jit adds its
    shape cache underneath."""
    lane_constraint = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        axis = mesh.axis_names[0]

        def lane_constraint(tree):
            def pin(a):
                spec = PartitionSpec(axis, *([None] * (a.ndim - 1)))
                return jax.lax.with_sharding_constraint(
                    a, NamedSharding(mesh, spec)
                )

            return jax.tree_util.tree_map(pin, tree)

    fn = population_sweep_fn(
        task,
        coord_specs,
        n_iterations,
        re_solver=re_solver,
        precision=precision,
        min_freeze_iterations=min_freeze_iterations,
        with_domination=with_domination,
        warm_start=warm_start,
        capture_pass_states=capture_pass_states,
        lane_constraint=lane_constraint,
    )
    if mesh is None:
        return jax.jit(fn, donate_argnums=(0,))

    def constrained(*args):
        return lane_constraint(fn(*args))

    return jax.jit(constrained, donate_argnums=(0,))


def make_jitted_game_step(
    data: ShardedGameData,
    task: TaskType,
    fe_config: GLMOptimizationConfiguration,
    re_configs: Sequence[GLMOptimizationConfiguration],
    mesh,
    re_solver: str = "lbfgs",
):
    """jit(game_train_step) with params donated — call as
    ``step(params) -> (params, diagnostics)``. One compiled XLA program per pass.

    ``data`` is passed as a jit ARGUMENT, never closed over. Closed-over
    arrays become jaxpr constants: on a multi-device mesh GSPMD ignores their
    committed shardings (it replicates constants), silently turning the whole
    pass into per-device full-data recomputation — measured as a clean 1/m
    throughput collapse on an m-device mesh (benchmarks/device_scaling.py
    caught it); on one device the whole dataset is embedded in the module as
    dense HLO constants (~0.5 KB of module text per f32 design-matrix row at
    D=64), which makes the compile and its persistent-cache entry scale with
    the dataset and stops working near the 2 GB module limit. As an argument,
    the ShardedGameData pytree's shardings bind the partitioning."""

    fuse_fe = mesh.devices.size == 1
    shard_mesh = mesh if mesh.devices.size > 1 else None

    @functools.partial(jax.jit, donate_argnums=(1,))
    def _step(d, params):
        return game_train_step(
            d, params, task, fe_config, tuple(re_configs),
            fuse_fe=fuse_fe, shard_mesh=shard_mesh, re_solver=re_solver,
        )

    def step(params):
        return _step(data, params)

    # the raw jitted (data, params) function, for compile-time inspection
    # (tests lower it to assert the per-device module is actually partitioned)
    step.jitted = _step
    step.data = data
    return step
