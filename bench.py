"""Flagship benchmark: GLMix coordinate-descent pass throughput.

Workload = the BASELINE.json north-star shape (config #3): 3-coordinate GLMix
logistic — one dense fixed effect + per-user + per-item random effects — trained
by the single-jit SPMD coordinate-descent pass (photon_ml_tpu.parallel.game).

The flagship runs in ONE process on a TPU and nowhere else: with no TPU it
exits non-zero and prints no metric (a CPU timing is not a speed result —
ROADMAP.md north star). Run it on the chip through the chip tool; the other
``--<mode>`` flags delegate to benchmarks/ scripts with their own gates.

Prints ONE JSON line: {"metric", "value", "unit", "platform", "device_kind",
"device_count", ...variant detail}. A variant that errored is recorded in
that line and the process then exits non-zero.
"""

from __future__ import annotations

import json
import os
import sys
import time

# Per-chip peaks for the roofline denominator: (dense bf16/f32-accum MXU
# FLOP/s, HBM bytes/s), public spec-sheet numbers (Google Cloud TPU docs). MFU
# is reported against the bf16 MXU peak by convention (an f32 variant's MFU is
# therefore conservative). Keyed by a substring of ``device_kind``; a device
# that is not in the table is an error, never a default (``peaks_for``).
_TPU_PEAKS = {
    "v5 lite": (197e12, 819e9),  # v5e device_kind string
    "v5e": (197e12, 819e9),
    "v5p": (459e12, 2765e9),
    "v6": (918e12, 1640e9),  # Trillium / v6e
    "v4": (275e12, 1228e9),
    "v3": (123e12, 900e9),
    "v2": (45e12, 700e9),
}


def peaks_for(device_kind: str) -> tuple:
    """(peak FLOP/s, peak HBM bytes/s) of ``device_kind``; KeyError when the
    device is not in ``_TPU_PEAKS`` — a roofline against a guessed peak is
    worse than none."""
    kind = (device_kind or "").lower()
    for key, peaks in _TPU_PEAKS.items():
        if key in kind:
            return peaks
    raise KeyError(
        f"device_kind {device_kind!r} is not in bench._TPU_PEAKS "
        f"({sorted(_TPU_PEAKS)}); add its published peaks with their source"
    )


def device_record() -> dict:
    """The device every printed result names, as JAX reports it."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


def require_tpu() -> dict:
    """``device_record()`` on a TPU whose peaks are known; otherwise exit
    non-zero before any work — a measurement path that finds no chip fails,
    it does not fall back."""
    dev = device_record()
    if dev["platform"] != "tpu":
        print(
            f"no TPU: jax reports platform={dev['platform']!r} "
            f"({dev['device_kind']}); refusing to measure",
            file=sys.stderr,
        )
        sys.exit(1)
    peaks_for(dev["device_kind"])
    return dev


def _xla_cost(step, params):
    """FLOPs + bytes from XLA's static cost model for the compiled step.
    CAVEAT: HLO cost analysis visits each while-loop body ONCE (trip counts
    are dynamic), so for an iterative solver these numbers are per-iteration-
    family, not per-pass — they are reported as labeled secondaries next to
    the analytic per-pass model, never used for MFU."""
    try:
        ca = step.jitted.lower(step.data, params).compile().cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        return {
            "xla_flops_loop_bodies_once": float(ca.get("flops", 0.0)),
            "xla_bytes_loop_bodies_once": float(ca.get("bytes accessed", 0.0)),
        }
    except Exception as e:  # measurement metadata, never a failure mode
        return {"cost_analysis_error": f"{type(e).__name__}: {e}"[:160]}


def _analytic_cost(data, fe_iters, re_iters, *, newton, storage_bytes):
    """Per-pass FLOPs and HBM-traffic model for the GLMix CD pass, from the
    actual tensor shapes (fixed-effect [n,d] + every RE bucket's [E,S,K]
    block) and iteration counts.

    Model, per value+gradient evaluation of a GLM objective on an [n,d]
    design matrix: 4nd FLOPs (forward matvec 2nd + gradient matvec 2nd) and
    two passes over the matrix (2·n·d·storage_bytes) — the stock XLA lowering
    reads X once forward, once transposed; the fused Pallas kernel's single
    pass makes this a ≤2x-conservative bytes model. NEWTON adds the Gauss-
    Newton Hessian build (2nd² FLOPs, one more X pass) and a d³/3 Cholesky
    per iteration. L-BFGS line search evaluates the objective ≥1 time per
    accepted iteration; evals == iterations is assumed, making the FLOPs
    model (and MFU) a LOWER bound there.

    ``fe_iters`` is the measured iteration count from the pass diagnostics.
    ``re_iters`` is EITHER the measured per-coordinate, per-bucket MAX
    iteration counts from the diagnostics (``re_iterations_max`` — a vmapped
    bucket while_loop executes max-lane iterations for EVERY lane, so the
    bucket's real compute is max x E·S·K) OR, as a fallback, the configured
    solver cap (int), which makes the RE term an upper bound — whichever was
    used is labeled in the emitted record."""
    n, d = data.fe_X.n_rows, data.fe_X.n_cols
    def solve_cost(rows, cols, iters):
        flops = iters * 4.0 * rows * cols
        bytes_ = iters * 2.0 * rows * cols * storage_bytes
        if newton:
            flops += iters * (2.0 * rows * cols * cols + cols**3 / 3.0)
            bytes_ += iters * rows * cols * storage_bytes
        return flops, bytes_

    re_measured = not isinstance(re_iters, int)
    flops, bytes_ = solve_cost(n, d, max(float(fe_iters), 1.0))
    for ci, rc in enumerate(data.re):
        for bi, b in enumerate(rc.buckets):
            E, S, K = b.X.shape
            it = float(re_iters[ci][bi]) if re_measured else float(re_iters)
            f, by = solve_cost(E * S, K, max(it, 1.0))
            flops += f
            bytes_ += by
        # scoring gathers: one pass over the per-sample RE values per coordinate
        ns, k = rc.sample_vals.shape
        flops += 2.0 * ns * k
        bytes_ += ns * k * storage_bytes
    out = {
        "flops_per_pass": float(flops),
        "hbm_bytes_per_pass": float(bytes_),
        "cost_model": (
            "analytic (fe + re iters measured, mean over timed passes)"
            if re_measured
            else "analytic (fe iters measured; re iters = config cap)"
        ),
        "fe_iterations_measured": round(float(fe_iters), 2),
    }
    if re_measured:
        out["re_iterations_measured"] = [
            [round(float(x), 2) for x in coord] for coord in re_iters
        ]
    else:
        out["re_iterations_assumed"] = int(re_iters)
    return out


def _xla_model_check(data, task):
    """Cross-check of the analytic cost model against XLA's static cost
    analysis, on a jit of ONE fixed-effect value+gradient evaluation. Loop
    trip counts divide out: the analytic per-pass model is literally
    iterations x this per-eval model, so the per-eval ratio validates the
    whole model. Emitted fields:
    ``xla_cost_ratio`` (XLA flops / analytic 4nd) — the load-bearing check,
    within ~20% of 1 for a trustworthy model (measured 1.13 on XLA:CPU at
    the flagship shape) — and ``xla_bytes_ratio`` (XLA bytes-accessed /
    analytic 2·n·d·storage), which runs ~2x high by construction: cost
    analysis charges every op's operands, including [n]-vector traffic that
    real fusion keeps on-chip, so it bounds the analytic bytes model from
    above rather than pinning it. Fail-soft metadata."""
    try:
        import jax
        import jax.numpy as jnp

        from photon_ml_tpu.data.dataset import LabeledData
        from photon_ml_tpu.function.losses import loss_for_task
        from photon_ml_tpu.function.objective import GLMObjective
        from photon_ml_tpu.types import TaskType

        d = LabeledData(
            X=data.fe_X, labels=data.labels,
            offsets=data.offsets, weights=data.weights,
        )
        cdtype = data.labels.dtype
        loss = loss_for_task(TaskType(task))

        def vg(dd, w):
            obj = GLMObjective(loss, allow_fused=False)
            return obj.value_and_gradient(dd, w, jnp.asarray(1.0, cdtype))

        w0 = jnp.zeros((data.fe_X.n_cols,), cdtype)
        ca = jax.jit(vg).lower(d, w0).compile().cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        n, cols = data.fe_X.n_rows, data.fe_X.n_cols
        sb = jnp.dtype(data.fe_X.dtype).itemsize
        analytic_flops = 4.0 * n * cols
        analytic_bytes = 2.0 * n * cols * sb
        xla_flops = float(ca.get("flops", 0.0))
        xla_bytes = float(ca.get("bytes accessed", 0.0))
        out = {
            "xla_eval_flops": xla_flops,
            "analytic_eval_flops": analytic_flops,
        }
        if xla_flops and analytic_flops:
            out["xla_cost_ratio"] = round(xla_flops / analytic_flops, 4)
        if xla_bytes and analytic_bytes:
            out["xla_bytes_ratio"] = round(xla_bytes / analytic_bytes, 4)
        return out
    except Exception as e:  # validation metadata, never a failure mode
        return {"xla_model_check_error": f"{type(e).__name__}: {e}"[:160]}


def _roofline(cost, samples_per_sec, n_samples):
    """Utilization accounting for one measured variant: achieved FLOP/s and
    HBM GB/s vs the chip's peaks, and which roofline regime the pass sits in.
    The regime call: arithmetic intensity above the ridge point means the
    ceiling is the MXU, below it the ceiling is HBM bandwidth — and if the
    pass is far from BOTH ceilings it is latency-bound (sequential dispatch,
    small ops), which no per-kernel tuning fixes."""
    import jax

    flops = cost.get("flops_per_pass")
    hbm = cost.get("hbm_bytes_per_pass")
    if not flops or not hbm or samples_per_sec <= 0:
        return dict(cost)
    sec_per_pass = n_samples / samples_per_sec
    out = dict(cost)
    out["achieved_flops_per_sec"] = round(flops / sec_per_pass, 2)
    out["achieved_hbm_bytes_per_sec"] = round(hbm / sec_per_pass, 2)
    out["arithmetic_intensity"] = round(flops / hbm, 3)
    kind = jax.devices()[0].device_kind
    out["device_kind"] = kind
    peak_flops, peak_bw = peaks_for(kind)
    out["mfu"] = round(flops / sec_per_pass / peak_flops, 5)
    out["hbm_util"] = round(hbm / sec_per_pass / peak_bw, 5)
    ridge = peak_flops / peak_bw
    if max(out["mfu"], out["hbm_util"]) < 0.05:
        out["regime"] = "latency"
    elif flops / hbm >= ridge:
        out["regime"] = "compute"
    else:
        out["regime"] = "bandwidth"
    return out

N_SAMPLES = 100_000
N_FEATURES = 64
N_USERS = 2_000
N_ITEMS = 500
N_PASSES = 3
FE_ITERS = 50
RE_ITERS = 30


def _apply_scale(scale: float) -> None:
    """--scale multiplies the workload shape; --scale 200 is the MovieLens-20M
    north star (20M samples / 400k users / 100k items — BASELINE.md config #3).
    At the default toy shape the pass is dispatch-latency-bound and
    systematically understates an accelerator's advantage; at-scale numbers
    are the ones that answer the reference's scale claim (README.md:56)."""
    global N_SAMPLES, N_USERS, N_ITEMS
    N_SAMPLES = int(N_SAMPLES * scale)
    N_USERS = max(1, int(N_USERS * scale))
    N_ITEMS = max(1, int(N_ITEMS * scale))


def _generate_workload(n_samples=None, n_users=None, n_items=None):
    """The flagship workload's generative process as host arrays:
    (fe_X [n, 64] f32, users [n], items [n], y [n], re_feat csr [n, 8]) from
    seed 42 — a planted logistic GLMix (fixed effect + per-user + per-item
    intercepts; the RE feature shard is an intercept + the first 7 fixed
    columns). chip_smoke.py feeds the same arrays to GameEstimator."""
    import numpy as np
    import scipy.sparse as sp

    n = N_SAMPLES if n_samples is None else n_samples
    nu = N_USERS if n_users is None else n_users
    ni = N_ITEMS if n_items is None else n_items
    rng = np.random.default_rng(42)
    fe_X = rng.normal(size=(n, N_FEATURES)).astype(np.float32)
    users = rng.integers(0, nu, size=n)
    items = rng.integers(0, ni, size=n)
    w = rng.normal(size=N_FEATURES) * 0.3
    z = fe_X @ w + 0.4 * rng.normal(size=nu)[users] + 0.4 * rng.normal(size=ni)[items]
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-z))).astype(np.float64)
    re_feat = sp.csr_matrix(
        np.concatenate([np.ones((n, 1), dtype=np.float32), fe_X[:, :7]], axis=1)
    )
    return fe_X, users, items, y, re_feat


def _build_workload(dtype, n_samples=None, n_users=None, n_items=None):
    """THE flagship GLMix workload (BASELINE config #3 shape by default).

    Shape parameters exist so other harnesses measuring the same program
    (benchmarks/device_scaling.py) share this one definition instead of
    re-implementing a drift-prone copy."""
    from photon_ml_tpu.data.random_effect import build_random_effect_dataset

    fe_X, users, items, y, re_feat = _generate_workload(n_samples, n_users, n_items)
    ds_u = build_random_effect_dataset(
        re_feat, users, "userId", labels=y, intercept_index=0, dtype=dtype
    )
    ds_i = build_random_effect_dataset(
        re_feat, items, "itemId", labels=y, intercept_index=0, dtype=dtype
    )
    return fe_X, y, ds_u, ds_i


def _build_workload_device(fe_storage_dtype=None):
    """Device-native at-scale workload: the same generative process as
    ``_build_workload`` synthesized ON the accelerator with jax.random
    (threefry is backend-deterministic, so CPU and TPU see identical bytes).

    Exists for the at-scale shapes: at --scale 200 the host builder holds the
    ~11 GB workload in host memory once per storage dtype and ships all of it
    to the device. Here the only host↔device traffic is the per-entity count
    vector (~E*8 bytes down) and the bucket membership lists (~E*4 bytes up) —
    everything else (design matrix, labels, RE blocks) is generated and
    gathered in HBM.

    The tradeoff: this path does NOT exercise the production ingest
    (build_random_effect_dataset); the default host builder remains the
    flagship path. The bench workload's RE features are dense (intercept +
    7 fe columns), so every entity's projection is the identity [0..7] and the
    per-sample scoring view is shared between coordinates. Bucketing mirrors
    production: pow2 sample-axis classes (min 8), rare classes (<5% of
    entities) folded upward into the next class on accelerators.

    Returns a ShardedGameData (single-device placement; callers needing a
    multi-device mesh take the host builder)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from photon_ml_tpu.data.matrix import DenseDesignMatrix
    from photon_ml_tpu.parallel.game import (
        ShardedGameData,
        ShardedREBucket,
        ShardedRECoordinate,
    )

    n, d, nu, ni = N_SAMPLES, N_FEATURES, N_USERS, N_ITEMS
    f32 = jnp.float32
    keys = jax.random.split(jax.random.PRNGKey(42), 7)

    @jax.jit
    def gen():
        fe_X = jax.random.normal(keys[0], (n, d), f32)
        users = jax.random.randint(keys[1], (n,), 0, nu)
        items = jax.random.randint(keys[2], (n,), 0, ni)
        w = jax.random.normal(keys[3], (d,), f32) * 0.3
        z = (
            fe_X @ w
            + 0.4 * jax.random.normal(keys[4], (nu,), f32)[users]
            + 0.4 * jax.random.normal(keys[5], (ni,), f32)[items]
        )
        y = (jax.random.uniform(keys[6], (n,), f32) < jax.nn.sigmoid(z)).astype(f32)
        re_vals = jnp.concatenate([jnp.ones((n, 1), f32), fe_X[:, :7]], axis=1)
        return fe_X, users, items, y, re_vals

    fe_X, users, items, y, re_vals = gen()
    if fe_storage_dtype is not None:
        # storage dtype covers the RE arrays too (the profiled hot loops)
        re_vals = re_vals.astype(fe_storage_dtype)
    K = 8
    local_cols = jnp.broadcast_to(jnp.arange(K, dtype=jnp.int32), (n, K))

    def build_coord(entities, E):
        counts = jnp.bincount(entities, length=E)
        order = jnp.argsort(entities).astype(jnp.int32)
        starts = jnp.concatenate(
            [jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1].astype(jnp.int32)]
        )
        counts_h = np.asarray(counts)  # the one device->host hop, [E] int
        # the ingest path's own layout rule and bucket cost (data/random_effect.py),
        # so the bench workload tracks its bucketing decisions
        from photon_ml_tpu.data.random_effect import _bucket_policy, bucket_layout

        live = np.flatnonzero(counts_h >= 1)  # empty entities train no model
        cost, pow2_heights = _bucket_policy(None)
        layout = bucket_layout(
            counts_h[live], np.full(len(live), K), cost, pow2_heights=pow2_heights
        )
        buckets = []
        for (S, _k), inside in sorted(layout.items()):
            members = live[inside]
            ents_d = jnp.asarray(members.astype(np.int32))
            idx = starts[ents_d][:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
            valid = jnp.arange(S)[None, :] < counts[ents_d][:, None]
            ids = jnp.where(valid, order[jnp.clip(idx, 0, n - 1)], -1)
            Xb = jnp.where(valid[..., None], re_vals[jnp.clip(ids, 0)], 0.0)
            yb = jnp.where(valid, y[jnp.clip(ids, 0)], 0.0)
            buckets.append(
                ShardedREBucket(
                    entity_rows=ents_d,
                    X=Xb,
                    labels=yb,
                    weights=valid.astype(f32),
                    sample_ids=ids,
                )
            )
        return ShardedRECoordinate(
            buckets=tuple(buckets),
            sample_entity_rows=entities.astype(jnp.int32),
            sample_local_cols=local_cols,
            sample_vals=re_vals,
            n_entities=E,
            max_k=K,
        )

    fe_vals = fe_X if fe_storage_dtype is None else fe_X.astype(fe_storage_dtype)
    return ShardedGameData(
        fe_X=DenseDesignMatrix(values=fe_vals),
        labels=y,
        offsets=jnp.zeros(n, f32),
        weights=jnp.ones(n, f32),
        re=(build_coord(users, nu), build_coord(items, ni)),
    )


def run_benchmark(device_data: bool = False) -> tuple:
    """Returns (samples/sec, variant-info dict) through full GLMix
    coordinate-descent passes.

    The reference-parity configuration (L-BFGS, f32) is always measured and is
    the quality anchor. Two tuned variants are then measured
    and gated on the converged fixed-effect objective staying within 1% of the
    anchor: direct Newton-Cholesky solves (optimization/newton.py — same convex
    optimum, quadratic convergence, so far fewer while_loop iterations per
    pass) and bf16 feature storage on top (half the HBM bytes on the
    matvec-bound solves, f32 accumulation on the MXU). The headline number is
    the best gated variant; per-variant detail lands in bench's JSON line."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from photon_ml_tpu.optimization.common import OptimizerConfig
    from photon_ml_tpu.optimization.config import (
        GLMOptimizationConfiguration,
        RegularizationContext,
    )
    from photon_ml_tpu.parallel import build_sharded_game_data, make_mesh, make_jitted_game_step
    from photon_ml_tpu.parallel.game import init_game_params
    from photon_ml_tpu.types import RegularizationType, TaskType

    mesh = make_mesh(len(jax.devices()))
    demoted = False
    if device_data and mesh.devices.size > 1:
        # the device builder places single-device arrays; a mesh needs the
        # host builder's explicit shardings
        device_data, demoted = False, True
        print(
            "--device-data demoted to the host builder: multi-device mesh "
            "needs explicit shardings (expect full dataset transfers)",
            file=sys.stderr,
        )
    if not device_data:
        fe_X, y, ds_u, ds_i = _build_workload(jnp.float32)

    def glm_cfg(opt, iters, ls=None):
        import dataclasses as _dc

        oc = OptimizerConfig(optimizer_type=opt, max_iterations=iters)
        if ls is not None:
            oc = _dc.replace(oc, max_line_search_iterations=ls)
        return GLMOptimizationConfiguration(
            optimizer_config=oc,
            regularization_context=RegularizationContext(RegularizationType.L2),
            regularization_weight=1.0,
        )

    # One device placement per distinct storage dtype, shared across variants
    # (at --scale 200 the sharded dataset is ~10 GB; measure timings exclude
    # builds either way) ...
    # ... but hold ONE placement at a time: f32+bf16 copies of the at-scale
    # dataset together would overflow a v5e chip's 16 GB HBM. The sweep
    # orders same-storage variants adjacently, so single-entry caching still
    # coalesces lbfgs/newton pairs into one transfer each.
    built = {}

    def get_data(fe_storage_dtype):
        key = jnp.dtype(fe_storage_dtype).name if fe_storage_dtype else None
        if key not in built:
            built.clear()
            if device_data:
                built[key] = _build_workload_device(fe_storage_dtype)
            else:
                # one storage knob drives both: the RE bucket blocks are the
                # profiled hot loops, so bf16 storage must cover them too
                built[key] = build_sharded_game_data(
                    fe_X, y, [ds_u, ds_i], mesh, dtype=jnp.float32,
                    fe_storage_dtype=fe_storage_dtype,
                    re_storage_dtype=fe_storage_dtype,
                )
        return built[key]

    # XLA-model FLOPs/bytes per measured configuration, keyed the same way
    # the sweep names its variants, so the winner's roofline can be attached
    # to the result after selection (_winner_roofline).
    costs = {}

    def measure(opt_type, fe_storage_dtype, ls=None):
        from photon_ml_tpu.ops import pallas_glm

        data = get_data(fe_storage_dtype)
        fe_cfg = glm_cfg(opt_type, FE_ITERS, ls)
        re_cfg = glm_cfg(opt_type, RE_ITERS, ls)
        step = make_jitted_game_step(
            data, TaskType.LOGISTIC_REGRESSION, fe_cfg, [re_cfg, re_cfg], mesh
        )
        params = init_game_params(data, mesh)
        params, diag = step(params)  # compile + warm-up pass
        jax.block_until_ready(params)
        t0 = time.perf_counter()
        # per-pass diagnostics are SMALL device scalars: collect lazily and
        # convert only after the clock stops (a host sync inside the timed
        # loop would serialize the passes)
        pass_diags = []
        for _ in range(N_PASSES):
            params, diag = step(params)
            pass_diags.append(diag)
        jax.block_until_ready(params)
        elapsed = time.perf_counter() - t0
        value = float(diag["fe_value"])
        assert value > 0.0
        key = (
            opt_type.name,
            jnp.dtype(fe_storage_dtype).name if fe_storage_dtype else None,
            pallas_glm.pallas_enabled(),
            ls,
        )
        # MEAN over the timed passes, matching the mean the throughput is:
        # warm-started later passes run fewer solver iterations than pass 1,
        # so the last pass alone would bias flops_per_pass (and MFU) low
        fe_iters_mean = float(
            np.mean([int(dg["fe_iterations"]) for dg in pass_diags])
        )
        re_meas = None
        if pass_diags[0].get("re_iterations_max") is not None:
            per_pass = [
                [[int(x) for x in coord] for coord in dg["re_iterations_max"]]
                for dg in pass_diags
            ]
            re_meas = tuple(
                tuple(
                    float(np.mean([p[ci][bi] for p in per_pass]))
                    for bi in range(len(per_pass[0][ci]))
                )
                for ci in range(len(per_pass[0]))
            )
        costs[key] = {
            **_analytic_cost(
                data,
                fe_iters_mean,
                # measured per-bucket max iteration counts, averaged over the
                # timed passes; the config cap only as fallback
                re_meas if re_meas is not None else RE_ITERS,
                newton=opt_type.name == "NEWTON",
                storage_bytes=jnp.dtype(fe_storage_dtype or jnp.float32).itemsize,
            ),
            **_xla_cost(step, params),
        }
        return N_SAMPLES * N_PASSES / elapsed, value

    # analytic-model validation BEFORE the sweep, while the cache is empty:
    # the f32 data built here is exactly what the anchor variant reuses (no
    # second at-scale build/transfer)
    model_check = _xla_model_check(get_data(None), TaskType.LOGISTIC_REGRESSION)

    # the Pallas variant: a single chip fuses inside the stock solve,
    # multi-chip meshes route the fixed-effect solve through shard_map
    # (per-device kernels + psum)
    value, info = run_variant_sweep(measure, bf16=jnp.bfloat16)
    info.update(model_check)
    info.update(_winner_roofline(info, costs, value))
    if device_data:
        info["data_builder"] = "device"
    elif demoted:
        info["data_builder"] = "host (device demoted: multi-device mesh)"
    return value, info


def _winner_roofline(info, costs, samples_per_sec, n_samples=None):
    """Attach the winning variant's roofline accounting to the bench record.

    Variant names encode their configuration (``lbfgs_bf16_pallas`` →
    LBFGS + bfloat16 storage + fused kernels), which is exactly the key
    ``measure`` stored its XLA cost model under — so the lookup needs no
    side channel through the sweep logic (unit-tested in
    tests/test_bench_logic.py)."""
    name = info.get("variant", "")
    key = (
        "NEWTON" if name.startswith("newton") else "LBFGS",
        "bfloat16" if "bf16" in name else None,
        name.endswith("_pallas"),
        15 if "_ls15" in name else None,
    )
    cost = costs.get(key)
    if cost is None:
        return {}
    return {
        "roofline": _roofline(
            cost, samples_per_sec, N_SAMPLES if n_samples is None else n_samples
        )
    }


def run_variant_sweep(measure, *, bf16):
    """The tuned-variant selection logic, separated from jax/workload state so
    it is unit-testable (tests/test_bench_logic.py).

    ``measure(opt_type, storage_dtype) -> (throughput, converged_value)`` is
    called once per variant; variants count only when their converged
    objective stays within 1% of the L-BFGS f32 anchor. A variant's failure is
    recorded under ``<name>_error`` so the remaining variants still run; the
    caller (``main``) then exits non-zero."""
    from photon_ml_tpu.ops import pallas_glm

    # Force pallas OFF for the anchor and the non-pallas variants so every
    # throughput comparison runs the same lowering family regardless of an
    # ambient PHOTON_PALLAS=1; the dedicated pallas variant turns it on.
    with pallas_glm.pallas_override(False):
        return _variant_sweep_body(measure, bf16)


def _variant_sweep_body(measure, bf16):
    from photon_ml_tpu.ops import pallas_glm
    from photon_ml_tpu.types import OptimizerType

    tp_anchor, val_anchor = measure(OptimizerType.LBFGS, None)
    info = {"variant": "lbfgs_f32", "lbfgs_f32_samples_per_sec": round(tp_anchor, 2)}
    best = tp_anchor

    configs = {"lbfgs_f32": (OptimizerType.LBFGS, None, None)}

    def try_variant(name, opt_type, storage, pallas=False, ls=None):
        nonlocal best
        # enable_pallas drops the traced solver caches on a state change, so
        # the trace-time fuse decision is re-made for this variant.
        pallas_glm.enable_pallas(pallas)
        try:
            tp, val = (
                measure(opt_type, storage, ls)
                if ls is not None
                else measure(opt_type, storage)
            )
        except Exception as e:  # recorded; main() exits non-zero on any *_error
            info[f"{name}_error"] = f"{type(e).__name__}: {e}"[:200]
            print(f"{name} variant failed: {e}", file=sys.stderr)
            return
        info[f"{name}_samples_per_sec"] = round(tp, 2)
        gate_ok = abs(val - val_anchor) <= 0.01 * abs(val_anchor)
        info[f"{name}_quality_gate"] = bool(gate_ok)
        configs[name] = (opt_type, storage, ls)
        if gate_ok and tp > best:
            best = tp
            info["variant"] = name

    try_variant("newton_f32", OptimizerType.NEWTON, None)
    try_variant("newton_bf16", OptimizerType.NEWTON, bf16)
    if info["variant"] == "lbfgs_f32":
        # Newton didn't win or didn't gate: still try the storage win alone.
        try_variant("lbfgs_bf16", OptimizerType.LBFGS, bf16)
    # The line-search budget trade is SHAPE-dependent (the default 10 wins
    # the latency-bound toy shape, a longer budget saves outer iterations
    # when the pass is bandwidth-bound at scale — docs/PERFORMANCE.md):
    # measure the winner with Breeze's combined budget and keep the faster.
    win_opt, win_storage, _ = configs[info["variant"]]
    try_variant(f"{info['variant']}_ls15", win_opt, win_storage, ls=15)
    # Fused Pallas value+gradient kernel on top of the winning configuration.
    win_opt, win_storage, win_ls = configs[info["variant"]]
    try_variant(
        f"{info['variant']}_pallas", win_opt, win_storage,
        pallas=True, ls=win_ls,
    )
    return best, info


def variant_errors(info: dict) -> list:
    """Names of the ``*_error`` records a sweep left in ``info``."""
    return sorted(k for k in info if k.endswith("_error"))


def _delegate_benchmark(flag: str, module_name: str) -> None:
    """Hand the run to a benchmarks/ module's main(): it prints its own JSON
    line and exits nonzero when one of its quality gates fails."""
    import importlib

    sys.path.insert(
        0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmarks")
    )
    module = importlib.import_module(module_name)
    sys.exit(module.main([a for a in sys.argv[1:] if a != flag]))


def main():
    if "--scoring" in sys.argv:
        # serving-path benchmark (fused engine steady state, retrace +
        # bitwise-parity gates)
        _delegate_benchmark("--scoring", "scoring_bench")

    if "--host-loop" in sys.argv:
        # host-backend featureful CD pass: single-program random-effect
        # updates vs the per-bucket loop (bitwise-parity + zero-retrace gates)
        _delegate_benchmark("--host-loop", "host_loop_bench")

    if "--ingest" in sys.argv:
        # parallel streaming Avro ingest vs the sequential path (bitwise
        # parity + determinism + bounded-RSS gates, time-to-first-update)
        _delegate_benchmark("--ingest", "ingest_bench")

    if "--serving-load" in sys.argv:
        # closed-loop load through the micro-batching serving frontend
        # (p50/p99/p999 + peak sustainable QPS; bitwise-parity, zero-retrace,
        # zero-shed-below-knee, hot-swap-no-drop and rollback gates)
        _delegate_benchmark("--serving-load", "serving_load_bench")

    if "--fleet" in sys.argv:
        # OPEN-LOOP load through the multi-replica fleet tier (router +
        # replica set + HTTP transport): fleet_sustained_qps_at_p999 with
        # bitwise-parity, zero-retrace, rolling-rollout-no-drop,
        # canary-reject and quota-distinctness gates
        _delegate_benchmark("--fleet", "fleet_bench")

    if "--fleet-proc" in sys.argv:
        # CROSS-PROCESS fleet: N replica processes behind the front router
        # (serving/router.py), SIGKILLed mid-load and restarted:
        # fleet_proc_sustained_qps_at_p999 with bitwise-parity,
        # zero-silent-drop, reconverge-within-probe-budget and
        # readmitted-replica-serves gates
        _delegate_benchmark("--fleet-proc", "fleet_proc_bench")

    if "--continuous" in sys.argv:
        # continuous-training delta pass vs full retrain (active-set-fraction,
        # delta-proportionality, quality-parity and bounded-retrace gates)
        _delegate_benchmark("--continuous", "continuous_bench")

    if "--sweep" in sys.argv:
        # batched model selection: vmapped population training vs N sequential
        # runs (bitwise vmapped-vs-fallback parity, zero-retrace, >=3x over
        # the native sequential baseline, per-family winner-serves gates)
        _delegate_benchmark("--sweep", "sweep_bench")

    if "--wide-fe" in sys.argv:
        # wide fixed-effect training: sparse-aware fused FE update at
        # k-scale x the feature count at fixed nnz/row vs the dense column
        # (bitwise sparse-vs-dense parity, zero-retrace, throughput-holds
        # and 2-D feature-axis collective-profile gates)
        _delegate_benchmark("--wide-fe", "wide_fe_bench")

    if "--working-set" in sys.argv:
        # hierarchical entity-table training: streamed working-set CD pass vs
        # all-resident across an oversubscription ladder (bitwise-parity,
        # bounded measured device-table-bytes, zero-retrace and overlap gates)
        _delegate_benchmark("--working-set", "working_set_bench")

    scale = None
    if "--scale" in sys.argv:
        try:
            scale = float(sys.argv[sys.argv.index("--scale") + 1])
        except (IndexError, ValueError):
            print("--scale requires a numeric factor (e.g. --scale 200)", file=sys.stderr)
            sys.exit(2)
    trace_dir = None
    if "--profile" in sys.argv:
        # jax.profiler trace of the measured passes (open with xprof /
        # tensorboard): attributes the pass's time op by op on the chip
        idx = sys.argv.index("--profile") + 1
        if idx >= len(sys.argv):
            print("--profile requires a trace directory argument", file=sys.stderr)
            sys.exit(2)
        trace_dir = sys.argv[idx]
    device_data = "--device-data" in sys.argv

    dev = require_tpu()  # exits non-zero, metric-less, without a chip

    from photon_ml_tpu.cli.runtime import configure_compilation_cache

    configure_compilation_cache()
    if scale is not None:
        _apply_scale(scale)
    if trace_dir:
        import jax

        with jax.profiler.trace(trace_dir):
            value, info = run_benchmark(device_data=device_data)
        info["trace_dir"] = trace_dir
    else:
        value, info = run_benchmark(device_data=device_data)
    result = {
        "metric": "glmix_cd_pass_samples_per_sec",
        "value": round(value, 2),
        "unit": "samples/sec",
        **dev,
    }
    if scale is not None:
        result["scale"] = scale  # non-standard shape, labeled
    result.update(info)
    print(json.dumps(result))
    errors = variant_errors(info)
    if errors:
        print(f"variants failed: {errors}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
