"""Host-loop benchmark: featureful coordinate-descent pass throughput.

Metric: ``glmix_host_cd_pass_samples_per_sec`` — samples x passes / wall-clock
through ``run_coordinate_descent`` with a featureful configuration
(normalization + per-entity L2 + coefficient variances). This is the regime
the single-program random-effect coordinate update
(optimization/solver_cache.re_coordinate_update_program) exists for:
one donated XLA dispatch per coordinate update instead of one program per
bucket with eager glue, per-bucket normalization gathers, and blocking
divergence-guard/tracker reads between updates.

Reported, per the honest-ratio rules (docs/PERFORMANCE.md):

- ``value`` — the single-program path (LBFGS, f32: the metric-continuity
  headline), measured AFTER a full warmup descent compiled every program,
  with the region under ``runtime_guard.sync_discipline``: any jaxpr retrace
  aborts the run (``retraces_after_warmup`` MUST be 0) and implicit
  device->host transfers raise on accelerator backends;
- ``per_bucket_samples_per_sec`` / ``vs_per_bucket`` — the SAME workload
  through the pre-PR per-bucket loop (``use_update_program=False`` +
  ``defer_guard=False``: one jitted program per bucket, blocking per-update
  guard), warmed symmetrically — the denominator for the speedup claim;
- ``parity_bitwise`` — quality gate: both paths must produce bitwise-equal
  coefficients, variances AND training scores after the measured passes. A
  fast update program that trains a different model is a bug, not a speedup.

SOLVER x PRECISION MATRIX (``solver_matrix`` in the JSON; disable with
``--no-solver-matrix``): the two roofline levers of docs/PERFORMANCE.md
"Roofline: solver and precision levers" measured against the LBFGS/f32
headline on the identical workload —

- ``direct_f32``  — ``re_solver="direct"`` (optimization/normal_equations.py):
  batched Gram/Cholesky Newton solves replace the LBFGS inner loop. GATED on
  cross-run bitwise determinism (two fresh runs must produce identical
  coefficient/variance/score bytes) and zero steady-state retraces.
- ``direct_bf16`` — direct solves + ``precision="bf16"``
  (optimization/precision.py): coefficient tables and feature blocks stored
  bfloat16, f32 accumulation. GATED on held-out quality: the bf16 model's
  held-out log-loss may differ from the f32 direct model's by at most
  ``BF16_HELDOUT_LOGLOSS_TOL`` (an explicit tolerance gate — reduced
  precision is NEVER bitwise-compared against f32), plus zero retraces.

Each variant carries modeled roofline columns, ``achieved_gb_per_sec`` and
``flops_per_byte``, computed
from the MEASURED per-entity solver iteration counts and the design-matrix
byte/flop model documented in docs/PERFORMANCE.md (bytes = design-block reads
per evaluation x evaluations; a model, not a hardware counter — its value is
the TREND: direct cuts evaluations, bf16 halves bytes per evaluation, and the
flop/byte column shows the loop climbing away from the ~0.5 flop/byte
bandwidth wall).

``--min-direct-speedup R`` gates ``best_direct_vs_lbfgs`` — the best DIRECT
variant's ratio over the LBFGS/f32 headline (the CI smoke shape leaves it
informational; the featureful default shape is where the >= 1.5x claim is
checked). The best variant carries the claim because the roofline thesis is
the two levers COMBINED: on the CPU host the f32 direct path's iteration
collapse (``re_iterations_mean`` in the matrix) is offset by each Newton
iteration's Gram-assembly FLOPs (~K gradient passes), a compute cost the
bandwidth-bound TPU regime does not pay — ``direct_f32_vs_lbfgs`` is
reported separately so that asymmetry stays visible.

MESH MODE (``--mesh-devices N``): the same featureful workload through the
SHARDED single-program coordinate update — datasets placed over an N-device
mesh (``parallel/placement``), each RE update ONE donated SPMD module with
entity-sharded tables/solves and sample-sharded scores. Emits
``glmix_mesh_cd_pass_samples_per_sec`` + per-device efficiency columns and
gates: bitwise fused-vs-per-bucket parity ON the mesh, run-to-run
determinism, ZERO DATA collectives inside the RE solver loops (only the
scalar convergence-predicate consensus a global batched while_loop needs,
measured and reported) + bounded gather/scatter collectives
(parallel/hlo_guards), held-out quality within
``MESH_HELDOUT_LOGLOSS_TOL`` of the 1-device program (cross-layout
comparisons are tolerance-only — XLA re-vectorizes per local shape, the
PR 8 lesson), and zero steady-state retraces. See ``run_mesh``.

Run directly (``python benchmarks/host_loop_bench.py``; needs the package
installed, as in CI) or as ``python bench.py --host-loop``. Flags:
``--passes P`` (default 6), ``--samples N`` / ``--users U`` / ``--items I`` /
``--features D`` (default 6000 / 2500 / 1000 / 32 — 3.5k entities over 6k
samples with power-law counts: per-entity data is SPARSE, each coordinate
spans ~10 bucket shape classes, and the per-bucket loop's dispatch + host
syncs dominate its solves — the many-small-entities regime random effects
live in). ``--working-set`` adds the streamed-vs-resident column: the same
featureful workload with each RE coordinate's tables tiered at 50% residency
through the device-resident working set (data/working_set.py) —
``working_set_vs_resident`` is informational (benchmarks/working_set_bench.py
owns the enforced residency ladder), while its bitwise coefficient/score
parity, measured peak-within-budget and zero-retrace gates are hard. Prints
ONE JSON line; exits nonzero when a gate fails.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np
import scipy.sparse as sp

N_SAMPLES = 6_000
N_USERS = 2_500
N_ITEMS = 1_000
N_FEATURES = 32
D_RE = 8  # intercept + 7 feature columns, the flagship RE shard shape
FE_ITERS = 30
RE_ITERS = 30
HELDOUT_FRACTION = 0.25  # held-out rows generated on top of --samples

# Explicit tolerance gate for the reduced-precision variant: the bf16 model's
# held-out mean log-loss may drift from the f32 direct model's by at most this
# much. bf16 carries ~8 mantissa bits (~2-3 decimal digits) on the stored
# coefficients; the measured drift at the featureful shape is recorded next to
# the gate in docs/PERFORMANCE.md.
BF16_HELDOUT_LOGLOSS_TOL = 0.02


def _powerlaw_ids(rng, n: int, n_entities: int) -> np.ndarray:
    """Entity ids with zipf-ish frequencies: entity sizes then span many pow2
    shape classes (real id-type skew), unlike the uniform assignment of
    bench.py's flagship workload which collapses into 1-2 buckets."""
    ranks = np.arange(1, n_entities + 1, dtype=np.float64)
    p = 1.0 / ranks
    p /= p.sum()
    return rng.choice(n_entities, size=n, p=p)


def build_workload(n: int, n_users: int, n_items: int, d: int, seed: int = 42):
    from photon_ml_tpu.normalization import FeatureDataStatistics, NormalizationContext
    from photon_ml_tpu.types import NormalizationType

    rng = np.random.default_rng(seed)
    n_ho = max(1, int(n * HELDOUT_FRACTION))
    n_all = n + n_ho
    fe_X_all = rng.normal(size=(n_all, d)).astype(np.float32)
    users_all = _powerlaw_ids(rng, n_all, n_users)
    items_all = _powerlaw_ids(rng, n_all, n_items)
    w = rng.normal(size=d) * 0.3
    z_all = (
        fe_X_all @ w
        + 0.4 * rng.normal(size=n_users)[users_all]
        + 0.4 * rng.normal(size=n_items)[items_all]
    )
    y_all = (rng.random(n_all) < 1.0 / (1.0 + np.exp(-z_all))).astype(np.float64)
    re_dense_all = np.concatenate(
        [np.ones((n_all, 1), dtype=np.float32), 3.0 * fe_X_all[:, : D_RE - 1] + 1.0],
        axis=1,
    )
    # training slice (the measured workload) + held-out slice (quality gates)
    fe_X, y, users, items = fe_X_all[:n], y_all[:n], users_all[:n], items_all[:n]
    re_feat = sp.csr_matrix(re_dense_all[:n])
    heldout = dict(
        fe_X=fe_X_all[n:],
        re_X=re_dense_all[n:],
        users=users_all[n:],
        items=items_all[n:],
        y=y_all[n:],
    )
    stats = FeatureDataStatistics.compute(
        re_dense_all[:n].astype(np.float64), intercept_index=0
    )
    norm = NormalizationContext.build(NormalizationType.STANDARDIZATION, stats)
    # dict form: power-law sampling can drop tail entities entirely, and the
    # dict override skips absent ids instead of demanding an exact [E] array
    pe_users = {int(e): float(w_e) for e, w_e in enumerate(rng.uniform(0.5, 2.0, size=n_users))}
    pe_items = {int(e): float(w_e) for e, w_e in enumerate(rng.uniform(0.5, 2.0, size=n_items))}
    return fe_X, y, users, items, re_feat, norm, pe_users, pe_items, heldout


def build_coordinates(
    workload,
    use_update_program: bool,
    re_solver: str = "lbfgs",
    precision=None,
    mesh=None,
    working_set: bool = False,
):
    """FE + per-user + per-item coordinates in the featureful (fused-pass-
    ineligible) configuration: RE normalization, per-entity L2 overrides,
    SIMPLE variances. ``mesh``: place every dataset (and the base offsets)
    over the device mesh — the sharded single-program regime of
    ``run_mesh``; None keeps the host placement. ``working_set``: engage the
    device-resident working set on each RE coordinate at 50%% residency
    (``working_set_rows`` = half its entity count) — the ``--working-set``
    column's streamed variant."""
    import jax.numpy as jnp

    from photon_ml_tpu.algorithm import FixedEffectCoordinate, RandomEffectCoordinate
    from photon_ml_tpu.data.dataset import FixedEffectDataset, LabeledData
    from photon_ml_tpu.data.random_effect import build_random_effect_dataset
    from photon_ml_tpu.optimization.common import OptimizerConfig
    from photon_ml_tpu.optimization.config import (
        GLMOptimizationConfiguration,
        RegularizationContext,
    )
    from photon_ml_tpu.types import RegularizationType, TaskType, VarianceComputationType

    fe_X, y, users, items, re_feat, norm, pe_users, pe_items, _ = workload
    n = len(y)

    def cfg(iters):
        return GLMOptimizationConfiguration(
            optimizer_config=OptimizerConfig(max_iterations=iters),
            regularization_context=RegularizationContext(RegularizationType.L2),
            regularization_weight=1.0,
        )

    fe_ds = FixedEffectDataset(LabeledData.build(fe_X, y), feature_shard_id="global")
    datasets = {"fixed": fe_ds}
    re_datasets = {}
    for cid, ids, re_type in (
        ("per-user", users, "userId"),
        ("per-item", items, "itemId"),
    ):
        re_datasets[cid] = datasets[cid] = build_random_effect_dataset(
            re_feat, ids, re_type, feature_shard_id="re_shard", labels=y,
            normalization=norm, intercept_index=0,
        )
    if mesh is not None:
        from photon_ml_tpu.parallel.placement import (
            pad_and_shard_vector,
            place_game_datasets,
        )

        datasets = place_game_datasets(datasets, mesh)
        re_datasets = {cid: datasets[cid] for cid in re_datasets}
        base_offsets = pad_and_shard_vector(
            np.zeros(n), mesh, dtype=datasets["per-user"].sample_vals.dtype
        )
    else:
        base_offsets = jnp.zeros(
            n, dtype=re_datasets["per-user"].sample_vals.dtype
        )
    coords = {
        "fixed": FixedEffectCoordinate(
            coordinate_id="fixed",
            dataset=datasets["fixed"],
            task=TaskType.LOGISTIC_REGRESSION,
            configuration=cfg(FE_ITERS),
        )
    }
    for cid, pe in (("per-user", pe_users), ("per-item", pe_items)):
        coords[cid] = RandomEffectCoordinate(
            coordinate_id=cid,
            dataset=datasets[cid],
            task=TaskType.LOGISTIC_REGRESSION,
            configuration=cfg(RE_ITERS),
            base_offsets=base_offsets,
            normalization=norm,
            variance_computation=VarianceComputationType.SIMPLE,
            per_entity_reg_weights=pe,
            use_update_program=use_update_program,
            re_solver=re_solver,
            precision=precision,
            working_set_rows=(
                max(datasets[cid].n_entities // 2, 1) if working_set else None
            ),
        )
    return coords


def _coefficient_state(result) -> list:
    """Every trained array of a descent result, for the bitwise parity gate."""
    out = []
    for cid in sorted(result.model.models):
        m = result.model.get_model(cid)
        if hasattr(m, "coeffs"):
            out.append(np.asarray(m.coeffs))
            if m.variances is not None:
                out.append(np.asarray(m.variances))
        else:
            out.append(np.asarray(m.model.coefficients.means))
        out.append(np.asarray(result.training_scores[cid]))
    return out


def _states_equal(a: list, b: list) -> bool:
    return len(a) == len(b) and all(
        x.dtype == y.dtype and np.array_equal(x, y) for x, y in zip(a, b)
    )


def _peak_device_table_bytes(result) -> tuple[int, str]:
    """MEASURED device table footprint, never a modeled byte count: the
    backend allocator's peak where the platform exposes ``memory_stats()``
    (TPU/GPU), else the live coefficient/variance/score buffers' actual
    ``nbytes`` (the CPU backend's honest fallback — real buffer sizes, but a
    live sample rather than an allocator peak). Returns (bytes, source)."""
    from photon_ml_tpu.data.working_set import backend_peak_bytes

    peak = backend_peak_bytes()
    if peak is not None:
        return int(peak), "backend_memory_stats"
    live = 0
    for cid in result.model.models:
        m = result.model.get_model(cid)
        if hasattr(m, "coeffs"):
            live += int(np.asarray(m.coeffs).nbytes)
            if m.variances is not None:
                live += int(np.asarray(m.variances).nbytes)
        else:
            live += int(np.asarray(m.model.coefficients.means).nbytes)
        live += int(np.asarray(result.training_scores[cid]).nbytes)
    return live, "live_buffer_nbytes"


def _heldout_logloss(result, workload) -> float:
    """Mean logistic log-loss of the trained GAME model on the held-out rows
    (host numpy: a quality metric, not a throughput path). Random-effect
    scoring reproduces RandomEffectModel semantics — unseen entities and
    columns the model never saw score 0."""
    _, _, _, _, _, _, _, _, ho = workload
    z = ho["fe_X"].astype(np.float64) @ np.asarray(
        result.model.get_model("fixed").model.coefficients.means, dtype=np.float64
    )
    for cid, ids in (("per-user", ho["users"]), ("per-item", ho["items"])):
        m = result.model.get_model(cid)
        coeffs = np.asarray(m.coeffs, dtype=np.float64)
        proj = np.asarray(m.proj_indices)
        row_by_entity = {e: i for i, e in enumerate(m.entity_ids)}
        X = ho["re_X"].astype(np.float64)
        for i, e in enumerate(ids):
            r = row_by_entity.get(e, -1)
            if r < 0:
                continue
            cols = proj[r]
            valid = cols >= 0
            z[i] += float(coeffs[r, valid] @ X[i, cols[valid]])
    y = ho["y"]
    # stable log(1 + exp(z)) - y z
    return float(np.mean(np.logaddexp(0.0, z) - y * z))


def _mean_re_iterations(result) -> float:
    """Mean per-entity solver iteration count over all RE updates — the
    measured input of the roofline byte/flop model."""
    vals = []
    for cid, trackers in result.trackers.items():
        for t in trackers:
            im = getattr(t, "iterations_mean", None)
            if im is not None:
                vals.append(float(im))
    return float(np.mean(vals)) if vals else 0.0


def _roofline(coords, result, elapsed: float, passes: int, itemsize: int) -> dict:
    """Modeled achieved bandwidth + arithmetic intensity for one variant.

    The model (docs/PERFORMANCE.md "Roofline: solver and precision levers"):
    per solver iteration each entity's [S, K] design block is read twice for
    the value+gradient evaluation (matvec + rmatvec in the stock lowering);
    a direct-solve iteration reads it once more for the Gram/Hessian
    assembly — folded in via the measured mean iteration count, which for
    direct variants COUNTS those assemblies. Flops per read: 2 per element
    per matvec pass. Fixed-effect reads are modeled the same way from its
    [N, D] matrix. This is a trend model from measured iteration counts, not
    a hardware counter."""
    re_cells = 0
    for c in coords.values():
        ds = getattr(c, "dataset", None)
        for b in getattr(ds, "buckets", []) or []:
            E, (S, K) = b.n_entities, b.shape
            re_cells += E * S * K
    fe_ds = coords["fixed"].dataset
    fe_cells = int(fe_ds.data.X.n_rows) * int(fe_ds.data.X.n_cols)
    re_iters = _mean_re_iterations(result)
    fe_tr = result.trackers.get("fixed", [])
    fe_iters = float(np.mean([t.iterations for t in fe_tr])) if fe_tr else 0.0
    # 2 design-block reads per evaluation, (iters + 1) evaluations per update
    re_reads = 2.0 * (re_iters + 1.0) * re_cells * passes
    fe_reads = 2.0 * (fe_iters + 1.0) * fe_cells * passes
    bytes_total = re_reads * itemsize + fe_reads * 4  # FE matrix stays f32
    flops_total = 2.0 * (re_reads + fe_reads)
    return {
        "achieved_gb_per_sec": round(bytes_total / elapsed / 1e9, 3),
        "flops_per_byte": round(flops_total / bytes_total, 3),
        "re_iterations_mean": round(re_iters, 2),
    }


def run(
    passes: int,
    n: int,
    n_users: int,
    n_items: int,
    d: int,
    reps: int = 3,
    solver_matrix: bool = True,
    min_direct_speedup: float = 0.0,
    working_set: bool = False,
) -> dict:
    import jax

    from photon_ml_tpu.algorithm import run_coordinate_descent
    from photon_ml_tpu.analysis.runtime_guard import sync_discipline

    workload = build_workload(n, n_users, n_items, d)

    coords_new = build_coordinates(workload, use_update_program=True)
    coords_old = build_coordinates(workload, use_update_program=False)
    bucket_counts = {
        cid: len(c.dataset.buckets)
        for cid, c in coords_new.items()
        if hasattr(c.dataset, "buckets")
    }

    def block(result):
        # the descent queue is async: the clock stops when results exist
        jax.block_until_ready(
            [m.coeffs if hasattr(m, "coeffs") else m.model.coefficients.means
             for m in result.model.models.values()]
        )
        return result

    # warmup: compile every program of BOTH paths outside the timed regions
    block(run_coordinate_descent(coords_new, n_iterations=1))
    block(run_coordinate_descent(coords_old, n_iterations=1, defer_guard=False))

    # interleaved best-of-k: both paths see the same machine-noise profile
    # (CPU scheduling jitter lands on each rep pair, and min-of-k is the
    # standard low-variance estimator for a deterministic workload)
    elapsed_new = elapsed_old = float("inf")
    result_new = result_old = None
    retraces = 0
    for _ in range(max(1, reps)):
        with sync_discipline(what="host_loop_bench measured region") as region:
            t0 = time.perf_counter()
            result_new = block(run_coordinate_descent(coords_new, n_iterations=passes))
            elapsed_new = min(elapsed_new, time.perf_counter() - t0)
        retraces += region.traces

        t0 = time.perf_counter()
        result_old = block(
            run_coordinate_descent(coords_old, n_iterations=passes, defer_guard=False)
        )
        elapsed_old = min(elapsed_old, time.perf_counter() - t0)

    # --- gates --------------------------------------------------------------
    state_new = _coefficient_state(result_new)
    state_old = _coefficient_state(result_old)
    parity = _states_equal(state_new, state_old)

    value = n * passes / elapsed_new
    per_bucket = n * passes / elapsed_old
    lbfgs_roof = _roofline(coords_new, result_new, elapsed_new, passes, itemsize=4)
    peak_bytes, peak_source = _peak_device_table_bytes(result_new)
    result = {
        "metric": "glmix_host_cd_pass_samples_per_sec",
        "value": round(value, 2),
        "unit": "samples/sec",
        "per_bucket_samples_per_sec": round(per_bucket, 2),
        "vs_per_bucket": round(value / per_bucket, 2),
        "parity_bitwise": bool(parity),
        "retraces_after_warmup": int(retraces),
        # measured from the live backend (allocator peak where the platform
        # exposes memory_stats(); live buffer nbytes otherwise) — never modeled
        "peak_device_table_bytes": int(peak_bytes),
        "device_memory_source": peak_source,
        # modeled roofline columns
        "achieved_gb_per_sec": lbfgs_roof["achieved_gb_per_sec"],
        "flops_per_byte": lbfgs_roof["flops_per_byte"],
        "passes": passes,
        "reps": reps,
        "n_samples": n,
        "buckets": bucket_counts,
        "platform": jax.default_backend(),
    }
    gates_ok = parity and retraces == 0

    # --- working-set column (--working-set) ----------------------------------
    # the SAME featureful workload with each RE coordinate's tables tiered at
    # 50% residency: throughput ratio vs the all-resident headline, bitwise
    # coefficient/score parity (variances allclose — the split-bucket batched-
    # GEMM scope, see benchmarks/working_set_bench.py), measured peak device
    # table bytes within budget, zero steady-state retraces. The ratio itself
    # is informational here (working_set_bench owns the enforced ladder); the
    # parity/peak/retrace gates are hard.
    if working_set:
        from photon_ml_tpu.analysis.runtime_guard import no_retrace

        coords_ws = build_coordinates(
            workload, use_update_program=True, working_set=True
        )
        for cid in ("per-user", "per-item"):
            assert coords_ws[cid]._working_set() is not None, (
                f"{cid}: working set demoted — the --working-set column would "
                "silently re-measure the all-resident path"
            )
        block(run_coordinate_descent(coords_ws, n_iterations=1))
        elapsed_ws = float("inf")
        result_ws = None
        retraces_ws = 0
        for _ in range(max(1, reps)):
            # counter-only region: the per-chunk D2H harvests are real,
            # intended transfers, so sync_discipline does not apply
            with no_retrace(allow_retraces=10**6,
                            what="host_loop_bench --working-set") as region:
                t0 = time.perf_counter()
                result_ws = block(
                    run_coordinate_descent(coords_ws, n_iterations=passes)
                )
                elapsed_ws = min(elapsed_ws, time.perf_counter() - t0)
            retraces_ws += region.traces
        sps_ws = n * passes / elapsed_ws

        ws_parity = True
        ws_var_ok = True
        ws_var_maxdiff = 0.0
        for cid in sorted(result_new.model.models):
            ma = result_ws.model.get_model(cid)
            mb = result_new.model.get_model(cid)
            if hasattr(mb, "coeffs"):
                ca, cb = np.asarray(ma.coeffs), np.asarray(mb.coeffs)
                ws_parity = ws_parity and ca.dtype == cb.dtype and np.array_equal(ca, cb)
                if mb.variances is not None:
                    va = np.asarray(ma.variances)
                    vb = np.asarray(mb.variances)
                    ws_var_maxdiff = max(ws_var_maxdiff, float(np.abs(va - vb).max()))
                    ws_var_ok = ws_var_ok and np.allclose(va, vb, rtol=1e-5, atol=1e-7)
            else:
                ws_parity = ws_parity and np.array_equal(
                    np.asarray(ma.model.coefficients.means),
                    np.asarray(mb.model.coefficients.means),
                )
            ws_parity = ws_parity and np.array_equal(
                np.asarray(result_ws.training_scores[cid]),
                np.asarray(result_new.training_scores[cid]),
            )
        ws_stats = {
            cid: coords_ws[cid].working_set_stats()
            for cid in ("per-user", "per-item")
        }
        ws_peak_ok = all(
            st["peak_device_table_bytes"] <= st["budget_bytes"]
            for st in ws_stats.values()
        )
        result["working_set"] = {
            "samples_per_sec": round(sps_ws, 2),
            "vs_resident": round(sps_ws / value, 4),
            "residency": 0.5,
            "parity_bitwise": bool(ws_parity),
            "variance_parity": bool(ws_var_ok),
            "variance_max_diff": ws_var_maxdiff,
            "peak_device_table_bytes": {
                cid: st["peak_device_table_bytes"] for cid, st in ws_stats.items()
            },
            "budget_bytes": {
                cid: st["budget_bytes"] for cid, st in ws_stats.items()
            },
            "peak_within_budget": bool(ws_peak_ok),
            "overlap_efficiency": {
                cid: st["overlap_efficiency"] for cid, st in ws_stats.items()
            },
            "retraces_after_warmup": int(retraces_ws),
        }
        result["working_set_vs_resident"] = round(sps_ws / value, 4)
        gates_ok = (
            gates_ok and ws_parity and ws_var_ok and ws_peak_ok
            and retraces_ws == 0
        )

    if not solver_matrix:
        result["gates_ok"] = bool(gates_ok)
        return result

    # --- solver x precision matrix ------------------------------------------
    matrix = {
        "lbfgs_f32": {
            "samples_per_sec": round(value, 2),
            "vs_lbfgs": 1.0,
            "heldout_logloss": round(_heldout_logloss(result_new, workload), 6),
            **lbfgs_roof,
        }
    }
    variant_specs = [
        ("direct_f32", dict(re_solver="direct"), 4),
        ("direct_bf16", dict(re_solver="direct", precision="bf16"), 2),
    ]
    variant_results = {}
    variant_ratios = {}
    for name, kw, itemsize in variant_specs:
        coords_v = build_coordinates(workload, use_update_program=True, **kw)
        block(run_coordinate_descent(coords_v, n_iterations=1))  # warmup
        elapsed_v = float("inf")
        res_v = None
        retraces_v = 0
        for _ in range(max(1, reps)):
            with sync_discipline(what=f"host_loop_bench {name} region") as region:
                t0 = time.perf_counter()
                res_v = block(run_coordinate_descent(coords_v, n_iterations=passes))
                elapsed_v = min(elapsed_v, time.perf_counter() - t0)
            retraces_v += region.traces
        sps = n * passes / elapsed_v
        variant_results[name] = res_v
        variant_ratios[name] = sps / value  # unrounded: the gate's input
        matrix[name] = {
            "samples_per_sec": round(sps, 2),
            "vs_lbfgs": round(sps / value, 2),
            "retraces_after_warmup": int(retraces_v),
            "heldout_logloss": round(_heldout_logloss(res_v, workload), 6),
            **_roofline(coords_v, res_v, elapsed_v, passes, itemsize=itemsize),
        }
        gates_ok = gates_ok and retraces_v == 0

    # f32 direct path: cross-run bitwise determinism (fresh coordinates, same
    # inputs -> identical coefficient/variance/score bytes)
    coords_det = build_coordinates(workload, use_update_program=True, re_solver="direct")
    block(run_coordinate_descent(coords_det, n_iterations=1))
    res_det = block(run_coordinate_descent(coords_det, n_iterations=passes))
    direct_deterministic = _states_equal(
        _coefficient_state(variant_results["direct_f32"]), _coefficient_state(res_det)
    )
    gates_ok = gates_ok and direct_deterministic

    # bf16 variant: EXPLICIT tolerance gate on held-out quality drift vs the
    # f32 direct model (never a bitwise comparison)
    bf16_drift = abs(
        matrix["direct_bf16"]["heldout_logloss"] - matrix["direct_f32"]["heldout_logloss"]
    )
    drift_ok = bf16_drift <= BF16_HELDOUT_LOGLOSS_TOL
    gates_ok = gates_ok and drift_ok

    # The speedup gate checks the BEST direct variant: the roofline thesis is
    # the two levers COMBINED (fewer passes over the data x fewer bytes per
    # pass). On a CPU host the f32 direct path's iteration collapse is offset
    # by the Newton iteration's FLOP cost (the Gram/Hessian assembly is ~K
    # gradient passes — a compute cost the bandwidth-bound TPU regime does
    # not pay, see docs/PERFORMANCE.md), so its ratio is reported separately
    # and the quality-gated direct_bf16 variant carries the combined claim.
    best_direct = max(variant_ratios.values())  # unrounded for the gate
    speedup_ok = best_direct >= min_direct_speedup
    gates_ok = gates_ok and speedup_ok

    result.update(
        solver_matrix=matrix,
        direct_f32_vs_lbfgs=matrix["direct_f32"]["vs_lbfgs"],
        best_direct_vs_lbfgs=round(best_direct, 3),
        direct_deterministic=bool(direct_deterministic),
        bf16_heldout_drift=round(bf16_drift, 6),
        bf16_drift_tol=BF16_HELDOUT_LOGLOSS_TOL,
        min_direct_speedup=min_direct_speedup,
        gates_ok=bool(gates_ok),
    )
    return result


# Cross-LAYOUT tolerance gate for the mesh mode: the sharded program and the
# 1-device (host-placed) program compile DIFFERENT local shapes, and XLA
# re-vectorizes per shape (the PR 8 lesson), so their converged models agree
# only to solver-convergence tolerance — never bitwise. The held-out log-loss
# gap is the honest cross-layout quality gate; bitwise gates apply WITHIN a
# layout (fused vs per-bucket on the same mesh, and run-to-run).
MESH_HELDOUT_LOGLOSS_TOL = 0.01


def run_mesh(
    passes: int,
    n: int,
    n_users: int,
    n_items: int,
    d: int,
    devices: int,
    reps: int = 3,
) -> dict:
    """``--mesh-devices N``: the featureful workload through the SHARDED
    single-program coordinate update — one donated SPMD module per RE update
    over an N-device mesh (entity-sharded tables/solves, sample-sharded
    scores), with no host round trips between updates.

    Metric: ``glmix_mesh_cd_pass_samples_per_sec`` + per-device efficiency
    columns vs the 1-device (host-placed) program. Gates (nonzero exit):

    - BITWISE coefficient/variance/score parity between the sharded update
      program and the per-bucket loop ON THE SAME MESH (the PR 4 parity
      contract, lifted onto the mesh), and across two fresh sharded runs;
    - held-out log-loss within ``MESH_HELDOUT_LOGLOSS_TOL`` of the 1-device
      program (cross-layout comparisons are tolerance-only — PR 8 lesson);
    - ZERO DATA collectives inside the RE solver loops
      (``hlo_guards.assert_entity_solves_collective_free`` over each RE
      coordinate's compiled update program; the scalar convergence-predicate
      all-reduces a global batched while_loop needs are counted and must be
      NONZERO — proof the scan actually sees the loops) and every remaining
      collective within the gather/scatter payload bounds;
    - zero steady-state retraces under ``sync_discipline``.

    Scaling-efficiency columns are INFORMATIONAL under emulated host devices
    (they share the physical cores — docs/PERFORMANCE.md "Honest measurement
    under emulated devices"); record real scaling only from real-device
    windows.
    """
    import jax

    from photon_ml_tpu.algorithm import run_coordinate_descent
    from photon_ml_tpu.analysis.runtime_guard import sync_discipline
    from photon_ml_tpu.parallel import hlo_guards
    from photon_ml_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(devices)
    workload = build_workload(n, n_users, n_items, d)

    def block(result):
        jax.block_until_ready(
            [m.coeffs if hasattr(m, "coeffs") else m.model.coefficients.means
             for m in result.model.models.values()]
        )
        return result

    coords_mesh = build_coordinates(workload, use_update_program=True, mesh=mesh)
    coords_pb = build_coordinates(workload, use_update_program=False, mesh=mesh)
    coords_host = build_coordinates(workload, use_update_program=True)

    # collective audit BEFORE the timed runs: the compiled update program of
    # each RE coordinate must keep its entity-sharded bucket solves free of
    # DATA collectives (the only tolerated in-loop op is the scalar
    # convergence-predicate all-reduce a globally batched while_loop needs
    # for termination consensus), with the surrounding gathers/scatters
    # bounded. Both counts are MEASURED, and the predicate count must be
    # nonzero — a zero would mean the scan no longer sees the solver loops
    # (the vacuity failure mode the guard itself once had).
    loop_data_collectives = 0
    loop_predicate_collectives = 0
    collective_kinds: dict = {}
    for cid in ("per-user", "per-item"):
        coord = coords_mesh[cid]
        hlo = coord.compiled_update_hlo()
        in_loop = hlo_guards.loop_collectives(hlo)
        preds = hlo_guards.assert_entity_solves_collective_free(hlo)
        loop_predicate_collectives += preds
        loop_data_collectives += len(in_loop) - preds
        ds = coord.dataset
        table_elements = (ds.coeffs_rows + 1) * ds.max_k
        bucket_block = max(
            b.n_entities * b.shape[0] for b in ds.buckets
        )
        cols = hlo_guards.assert_collective_profile(
            hlo,
            grad_elements=ds.max_k,
            table_elements=table_elements,
            n_samples=int(ds.sample_entity_rows.shape[0]),
            bucket_block_elements=bucket_block,
            max_collectives=16 * len(ds.buckets),
        )
        for c in cols:
            collective_kinds[c.kind] = collective_kinds.get(c.kind, 0) + 1

    # warmup compiles every program of all three variants
    block(run_coordinate_descent(coords_mesh, n_iterations=1))
    block(run_coordinate_descent(coords_pb, n_iterations=1, defer_guard=False))
    block(run_coordinate_descent(coords_host, n_iterations=1))

    elapsed_mesh = elapsed_pb = elapsed_host = float("inf")
    result_mesh = result_pb = result_host = None
    retraces = 0
    for _ in range(max(1, reps)):
        with sync_discipline(what="mesh_cd_bench measured region") as region:
            t0 = time.perf_counter()
            result_mesh = block(run_coordinate_descent(coords_mesh, n_iterations=passes))
            elapsed_mesh = min(elapsed_mesh, time.perf_counter() - t0)
        retraces += region.traces

        t0 = time.perf_counter()
        result_pb = block(
            run_coordinate_descent(coords_pb, n_iterations=passes, defer_guard=False)
        )
        elapsed_pb = min(elapsed_pb, time.perf_counter() - t0)

        t0 = time.perf_counter()
        result_host = block(run_coordinate_descent(coords_host, n_iterations=passes))
        elapsed_host = min(elapsed_host, time.perf_counter() - t0)

    # --- gates ---------------------------------------------------------------
    parity = _states_equal(
        _coefficient_state(result_mesh), _coefficient_state(result_pb)
    )
    coords_det = build_coordinates(workload, use_update_program=True, mesh=mesh)
    block(run_coordinate_descent(coords_det, n_iterations=1))
    result_det = block(run_coordinate_descent(coords_det, n_iterations=passes))
    deterministic = _states_equal(
        _coefficient_state(result_mesh), _coefficient_state(result_det)
    )
    ll_mesh = _heldout_logloss(result_mesh, workload)
    ll_host = _heldout_logloss(result_host, workload)
    drift = abs(ll_mesh - ll_host)
    drift_ok = drift <= MESH_HELDOUT_LOGLOSS_TOL
    coeff_maxdiff = 0.0
    for cid in ("per-user", "per-item"):
        a = np.asarray(result_mesh.model.get_model(cid).coeffs, dtype=np.float64)
        b = np.asarray(result_host.model.get_model(cid).coeffs, dtype=np.float64)
        coeff_maxdiff = max(coeff_maxdiff, float(np.abs(a[: b.shape[0]] - b).max()))

    value = n * passes / elapsed_mesh
    host_sps = n * passes / elapsed_host
    gates_ok = (
        parity
        and deterministic
        and drift_ok
        and retraces == 0
        and loop_data_collectives == 0
        # a 1-partition module legitimately compiles with NO collectives at
        # all, so the scan-sees-the-loops proof only applies at devices > 1
        and (devices == 1 or loop_predicate_collectives > 0)
    )
    return {
        "metric": "glmix_mesh_cd_pass_samples_per_sec",
        "value": round(value, 2),
        "unit": "samples/sec",
        "mesh_devices": devices,
        "emulated_devices": jax.default_backend() == "cpu",
        "samples_per_sec_per_device": round(value / devices, 2),
        "one_device_samples_per_sec": round(host_sps, 2),
        "scaling_efficiency_vs_1dev": round(value / devices / host_sps, 3),
        "per_bucket_mesh_samples_per_sec": round(n * passes / elapsed_pb, 2),
        "vs_per_bucket_mesh": round(value / (n * passes / elapsed_pb), 2),
        "parity_bitwise_vs_per_bucket": bool(parity),
        "deterministic_across_runs": bool(deterministic),
        "retraces_after_warmup": int(retraces),
        "loop_data_collectives": int(loop_data_collectives),
        "loop_predicate_collectives": int(loop_predicate_collectives),
        "collective_profile": collective_kinds,
        "heldout_logloss_mesh": round(ll_mesh, 6),
        "heldout_logloss_1dev": round(ll_host, 6),
        "vs_1dev_heldout_drift": round(drift, 6),
        "vs_1dev_drift_tol": MESH_HELDOUT_LOGLOSS_TOL,
        "vs_1dev_coeff_maxdiff": float(coeff_maxdiff),
        "passes": passes,
        "reps": reps,
        "n_samples": n,
        "platform": jax.default_backend(),
        "gates_ok": bool(gates_ok),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--passes", type=int, default=6)
    p.add_argument("--samples", type=int, default=N_SAMPLES)
    p.add_argument("--users", type=int, default=N_USERS)
    p.add_argument("--items", type=int, default=N_ITEMS)
    p.add_argument("--features", type=int, default=N_FEATURES)
    p.add_argument("--reps", type=int, default=3)
    p.add_argument(
        "--no-solver-matrix", dest="solver_matrix", action="store_false",
        help="skip the solver x precision variant matrix (parity/retrace "
        "gates on the LBFGS paths only)",
    )
    p.add_argument(
        "--min-direct-speedup", type=float, default=0.0,
        help="gate: the BEST direct variant (best_direct_vs_lbfgs — "
        "direct_f32 or direct_bf16, the combined-levers claim) must be at "
        "least this many times faster than the LBFGS update program "
        "(0 = informational; the featureful default shape is where the "
        ">=1.5x claim is checked; direct_f32_vs_lbfgs is reported "
        "separately)",
    )
    p.add_argument(
        "--working-set", dest="working_set", action="store_true",
        help="add the working_set column: the same featureful workload with "
        "each RE coordinate's tables tiered at 50%% residency "
        "(working_set_rows = half its entity count). Reports streamed-vs-"
        "resident throughput (working_set_vs_resident, informational) and "
        "hard-gates bitwise coefficient/score parity, peak device table "
        "bytes within budget, and zero steady-state retraces",
    )
    p.add_argument(
        "--mesh-devices", type=int, default=0,
        help="run the SHARDED single-program coordinate update over this "
        "many devices instead of the host-loop matrix: emits "
        "glmix_mesh_cd_pass_samples_per_sec with per-device efficiency "
        "columns and gates bitwise fused-vs-per-bucket parity on the mesh, "
        "run-to-run determinism, zero RE-solve DATA collectives, bounded "
        "gather/scatter collectives, tolerance vs the 1-device program, "
        "and zero steady-state retraces. CPU-ONLY SURFACE for now: the "
        "devices are always EMULATED host devices (JAX_PLATFORMS is forced "
        "to cpu), the gates are program-shape counts and the efficiency "
        "columns informational; the four-chip check is "
        "`chip_smoke.py --devices 4`",
    )
    args = p.parse_args(argv)
    if args.mesh_devices:
        if args.mesh_devices < 1:
            p.error("--mesh-devices must be >= 1")
        # must happen before the first jax import (all jax imports in this
        # module are function-local for exactly this reason): the mesh mode
        # runs on emulated host devices, whatever the machine offers
        import os

        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags
                + f" --xla_force_host_platform_device_count={args.mesh_devices}"
            )
        result = run_mesh(
            args.passes, args.samples, args.users, args.items, args.features,
            args.mesh_devices, args.reps,
        )
        print(json.dumps(result))
        return 0 if result["gates_ok"] else 1
    result = run(
        args.passes, args.samples, args.users, args.items, args.features,
        args.reps, solver_matrix=args.solver_matrix,
        min_direct_speedup=args.min_direct_speedup,
        working_set=args.working_set,
    )
    print(json.dumps(result))
    # every gate is load-bearing: a retrace voids the steady-state reading, a
    # parity failure means the update program trains a different model, a
    # non-deterministic direct solve voids its exactness contract, and a
    # bf16 drift beyond tolerance means the reduced variant ships worse models
    return 0 if result["gates_ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
