#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the GLMix train→serve path still
starts on the chip.

One process, x64 off, through the entry points a user calls, at the full
widths of BASELINE.json config #3 (logistic GLMix: dense fixed effect d=64 f32,
per-user and per-item random effects K=8, L-BFGS + L2):

  device   jax version, platform, device_kind, count; not a TPU -> exit 1
           before any work; device_kind must be in bench._TPU_PEAKS
  train    GameEstimator.fit, default options (host loop, donated update
           programs), two passes, AUC validation, checkpoints; then the same
           fit again inside runtime_guard.sync_discipline() (zero retraces,
           no implicit device->host transfer)
  serve    serve_from_checkpoint(<ckpt>/config_0) -> ServingFrontend, mixed
           request sizes against a host NumPy float64 scoring of the same
           coefficients; the repeat round runs under the guards
  kernels  the three Pallas kernels compiled (interpret=False) at the train
           leg's FE shape in f32 and bf16, at 8192x512 and at the widest D
           each gate admits, against a float64 host reference; then one
           fixed-effect solve under enable_pallas(True) whose lowered text
           holds a tpu_custom_call
  cli      a seeded Avro corpus through game_training_driver.main and
           game_scoring_driver.main in-process (never a child: this process
           holds the chip), scores read back against the library's

``--devices N`` runs the train and serve legs over ``make_mesh(N)`` and
checks the placement (per-device shards, bytes in use, collective profile of
the compiled fused step).

Every leg's failure is a non-zero exit: nothing here catches a failed leg and
carries on. ``--rehearsal`` (never the default) runs the same legs tiny on the
CPU with the kernels interpreted, so chip time is not spent finding typos;
every line it prints then starts with ``REHEARSAL platform=cpu`` and it prints
no result line a chip run could be mistaken for.

Last line of stdout on success: one JSON object with exactly these keys,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``. The
line before it is ``summary: {...}``, one JSON object with the per-leg first-call
and repeat seconds (set-up facts, not performance metrics), the compile-cache
directory and what each leg observed, ending ``"claim": null``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# ---------------------------------------------------------------- tolerances
# Stated after seeing the chip (TPU v5 lite, JAX 0.9.0; PERF.md "Bring-up"
# holds the observed values). Device math is f32 with f32 matmul operands at
# the XLA default precision unless the code pins it.
AUC_MIN = 0.75  # planted model; 0.5 is chance
SCORE_ATOL = 1e-4  # device [N] training/serving scores vs host float64
LOGLOSS_RTOL = 1e-4  # device mean log-loss vs host float64 recomputation
# fused kernel sums vs float64 reference, relative to the largest reference
# entry. bf16 storage rounds the coefficient and the per-row factor to bf16
# before the MXU (the documented mixed-precision contract), f32 does not.
KERNEL_RTOL = {"float32": 5e-4, "bfloat16": 5e-2}
CLI_SCORE_ATOL = 1e-5  # scoring driver's avro scores vs GameTransformer


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(condition, message: str) -> None:
    if not condition:
        raise SmokeFailure(message)


_PREFIX = ""


def say(*parts) -> None:
    print(_PREFIX + " ".join(str(p) for p in parts), flush=True)


@dataclasses.dataclass(frozen=True)
class Sizes:
    n_train: int
    n_val: int
    n_users: int
    n_items: int
    serve_rows: tuple  # request row counts; [4] and [5] share one batch bucket
    hess_rows: int  # rows at the Hessian kernel gate's widest D (8192x512)
    widest_rows: int  # rows at the widest D the other two gates admit
    cli_train: int
    cli_val: int


# bench._build_workload at --scale 10, plus a held-out tenth for validation
FULL = Sizes(
    n_train=1_000_000, n_val=100_000, n_users=20_000, n_items=5_000,
    serve_rows=(1, 5, 8, 33, 200, 180, 1000, 3000),
    hess_rows=8192, widest_rows=4096,
    cli_train=4000, cli_val=1000,
)
TINY = Sizes(
    n_train=3000, n_val=600, n_users=40, n_items=10,
    serve_rows=(1, 5, 8, 33, 20, 18, 100, 70),
    hess_rows=700, widest_rows=520,
    cli_train=300, cli_val=120,
)

TASK = "LOGISTIC_REGRESSION"
FE_ITERS, RE_ITERS = 50, 30  # bench.py's flagship solver budgets


# ------------------------------------------------------------------ helpers


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


class CompileMeter:
    """What the first call of a leg is made of: XLA backend compiles (count,
    seconds) and persistent-cache hits and misses, from jax.monitoring — the
    same event stream runtime_guard counts traces on."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax

        self.totals = {"compile_seconds": 0.0, "compiles": 0, "cache_hits": 0,
                       "cache_misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_kw):
        if event == self._COMPILE:
            self.totals["compile_seconds"] += duration
            self.totals["compiles"] += 1

    def _on_event(self, event, **_kw):
        if event == self._HIT:
            self.totals["cache_hits"] += 1
        elif event == self._MISS:
            self.totals["cache_misses"] += 1

    def measure(self, seconds: dict, leg: str, fn):
        """Run ``fn`` (which files its own first/repeat times under
        ``seconds[leg]``) and add what it compiled."""
        before = dict(self.totals)
        out = fn()
        seconds[leg].update({
            k: round(self.totals[k] - before[k], 2) for k in self.totals
        })
        return out


def _coordinate_configs():
    from photon_ml_tpu.estimators import (
        CoordinateConfiguration,
        FixedEffectDataConfiguration,
        RandomEffectDataConfiguration,
    )
    from photon_ml_tpu.optimization.common import OptimizerConfig
    from photon_ml_tpu.optimization.config import (
        GLMOptimizationConfiguration,
        RegularizationContext,
    )
    from photon_ml_tpu.types import OptimizerType, RegularizationType

    def opt(iters):
        return GLMOptimizationConfiguration(
            optimizer_config=OptimizerConfig(
                optimizer_type=OptimizerType.LBFGS, max_iterations=iters
            ),
            regularization_context=RegularizationContext(RegularizationType.L2),
            regularization_weight=1.0,
        )

    return {
        "fixed": CoordinateConfiguration(
            data_config=FixedEffectDataConfiguration("global"),
            optimization_config=opt(FE_ITERS),
        ),
        "per-user": CoordinateConfiguration(
            data_config=RandomEffectDataConfiguration("userId", "re"),
            optimization_config=opt(RE_ITERS),
        ),
        "per-item": CoordinateConfiguration(
            data_config=RandomEffectDataConfiguration("itemId", "re"),
            optimization_config=opt(RE_ITERS),
        ),
    }


def _game_input(fe_X, users, items, y, re_feat, rows):
    from photon_ml_tpu.data.game_data import GameInput

    return GameInput(
        features={"global": fe_X[rows], "re": re_feat[rows]},
        labels=None if y is None else y[rows],
        id_columns={"userId": users[rows], "itemId": items[rows]},
    )


def host_game_scores(model, data):
    """[n] float64 scores of ``model`` on a GameInput in plain NumPy: the
    reference the device's scores are held against. Entities without a model
    score 0 from that coordinate."""
    import jax
    import numpy as np

    from photon_ml_tpu.models.game import FixedEffectModel

    total = np.zeros(data.n, dtype=np.float64)
    for _cid, m in model:
        X = data.shard(m.feature_shard_id)
        if isinstance(m, FixedEffectModel):
            w = np.asarray(jax.device_get(m.model.coefficients.means), np.float64)
            total += np.asarray(X, dtype=np.float64) @ w
            continue
        coeffs = np.asarray(jax.device_get(m.coeffs), np.float64)
        proj = np.asarray(jax.device_get(m.proj_indices))
        n_ent = len(m.entity_ids)
        # global-column layout per entity, plus a zero row for unseen ids
        table = np.zeros((n_ent + 1, X.shape[1]), dtype=np.float64)
        ent, slot = np.nonzero(proj[:n_ent] >= 0)
        table[ent, proj[ent, slot]] = coeffs[ent, slot]
        row_of = {e: i for i, e in enumerate(m.entity_ids)}
        rows = np.fromiter(
            (row_of.get(e, n_ent) for e in np.asarray(data.ids(m.re_type)).tolist()),
            dtype=np.int64, count=data.n,
        )
        total += np.einsum("ij,ij->i", X.toarray().astype(np.float64), table[rows])
    return total


def host_logloss(z, y):
    import numpy as np

    return float(np.mean(np.logaddexp(0.0, z) - y * z))


def _model_arrays(model):
    from photon_ml_tpu.algorithm.coordinate import coefficient_arrays

    return [a for _cid, m in model for a in coefficient_arrays(m)]


def _even_shards(arr, m: int, what: str) -> list:
    """Rows of ``arr`` held by each of ``m`` devices; fails unless every
    device holds about 1/m of them (a replicated array holds all of them
    everywhere)."""
    rows = [s.data.shape[0] for s in arr.addressable_shards]
    check(len(rows) == m, f"{what}: {len(rows)} shards on {m} devices")
    check(
        max(rows) * m <= arr.shape[0] + m and sum(rows) == arr.shape[0],
        f"{what}: per-device rows {rows} of {arr.shape[0]} — replicated, not placed",
    )
    return rows


def _describe_trackers(descent) -> dict:
    """Solver iteration counts and convergence reasons per coordinate, per
    pass."""
    out = {}
    for cid, trackers in descent.trackers.items():
        rows = []
        for t in trackers:
            if getattr(t, "final_value", None) is None:  # per-entity solves
                rows.append(
                    {
                        "reasons": dict(t.convergence_reason_counts),
                        "iterations_mean": round(float(t.iterations_mean), 2),
                        "iterations_max": int(t.iterations_max),
                    }
                )
            else:
                rows.append(
                    {
                        "reason": str(t.convergence_reason),
                        "iterations": int(t.iterations),
                        "value": float(t.final_value),
                    }
                )
        out[cid] = rows
    return out


# --------------------------------------------------------------------- legs


def leg_device(args, rehearsal: bool) -> dict:
    import jax

    import bench

    dev = bench.device_record()
    say(
        f"device: jax {jax.__version__} platform={dev['platform']} "
        f"device_kind={dev['device_kind']!r} count={dev['device_count']} "
        f"x64={jax.config.jax_enable_x64}"
    )
    check(not jax.config.jax_enable_x64, "the smoke runs x64 off")
    if not rehearsal:
        bench.require_tpu()  # exits 1 here, before any work, without a TPU
        stats = jax.devices()[0].memory_stats() or {}
        check("bytes_limit" in stats, f"memory_stats() has no bytes_limit: {stats}")
        say(f"device: bytes_limit={stats['bytes_limit']}")
    check(
        dev["device_count"] >= args.devices,
        f"--devices {args.devices} but jax sees {dev['device_count']}",
    )
    return dev


def _fit(workload, mesh, ckpt_dir):
    from photon_ml_tpu.estimators import GameEstimator
    from photon_ml_tpu.evaluation import EvaluatorType

    train, val = workload
    estimator = GameEstimator(
        task=TASK,
        coordinate_configurations=_coordinate_configs(),
        n_iterations=2,
        validation_evaluators=[EvaluatorType.AUC],
        checkpoint_directory=ckpt_dir,
        mesh=mesh,
    )
    result = estimator.fit(train, validation_data=val)[0]
    import jax

    jax.block_until_ready(_model_arrays(result.model))
    return result


def leg_train(sizes, workload, mesh, workdir, seconds) -> dict:
    """Host-loop fit (default options) + checkpoints; the repeat fit runs
    under the sync/retrace guards."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from photon_ml_tpu.analysis.runtime_guard import sync_discipline
    from photon_ml_tpu.function.losses import loss_for_task
    from photon_ml_tpu.types import TaskType

    train, _val = workload
    ckpt = os.path.join(workdir, "ckpt")
    result, first = _timed(lambda: _fit(workload, mesh, ckpt))
    # the same fit again (fresh checkpoint root, or it would resume a finished
    # run): every program is compiled, so any trace is a jit cache miss, and
    # every device->host read on the path must be a named jax.device_get
    with sync_discipline(what="chip_smoke repeat fit") as region:
        again, repeat = _timed(lambda: _fit(workload, mesh, ckpt + "_repeat"))
    retraces = region.traces  # a live counter: read it at the region's end
    seconds["train"] = {"first": round(first, 2), "repeat": round(repeat, 2)}
    say(f"train: first fit {first:.1f}s, repeat fit {repeat:.1f}s, "
        f"retraces in repeat = {retraces}")

    descent = result.descent
    solver = _describe_trackers(descent)
    say("train: solver", json.dumps(solver))
    finite = jax.device_get(
        [jnp.all(jnp.isfinite(a)) for a in _model_arrays(result.model)]
    )
    check(all(bool(f) for f in finite), "non-finite coefficients out of the fit")
    fe_values = [row["value"] for row in solver["fixed"]]
    check(len(fe_values) == 2, f"expected two fixed-effect updates, got {fe_values}")
    check(
        np.isfinite(fe_values).all() and fe_values[1] < fe_values[0],
        f"training objective did not fall pass over pass: {fe_values}",
    )
    again_values = [float(t.final_value) for t in again.descent.trackers["fixed"]]
    check(
        again_values == fe_values,
        f"the repeat fit diverged from the first: {again_values} vs {fe_values}",
    )
    auc = result.best_metric
    say(f"train: objective per pass {fe_values}, validation AUC {auc:.4f}")
    check(auc is not None and auc > AUC_MIN, f"AUC {auc} not above {AUC_MIN}")

    # device scores/log-loss against a host float64 recomputation from the
    # final coefficients
    n = train.n
    total_dev = sum(descent.training_scores.values())[:n]
    y_dev = jnp.asarray(train.labels, dtype=total_dev.dtype)
    loss = loss_for_task(TaskType(TASK)).loss
    dev_ll = float(jax.device_get(jnp.mean(loss(total_dev, y_dev))))
    z_host = host_game_scores(result.model, train)
    ll_host = host_logloss(z_host, np.asarray(train.labels, np.float64))
    score_err = float(
        np.max(np.abs(np.asarray(jax.device_get(total_dev), np.float64) - z_host))
    )
    ll_rel = abs(dev_ll - ll_host) / ll_host
    say(
        f"train: log-loss device={dev_ll:.6f} host_f64={ll_host:.6f} "
        f"rel_diff={ll_rel:.2e} (tol {LOGLOSS_RTOL}); max |score_dev - "
        f"score_host|={score_err:.2e} (tol {SCORE_ATOL})"
    )
    check(ll_rel <= LOGLOSS_RTOL, f"log-loss rel diff {ll_rel:.3e} > {LOGLOSS_RTOL}")
    check(score_err <= SCORE_ATOL, f"score abs diff {score_err:.3e} > {SCORE_ATOL}")
    shards = {}
    if mesh is not None:
        # the fit's [N] scores and [E, K] tables come back sample-/entity-sharded
        from photon_ml_tpu.models.game import RandomEffectModel

        m = mesh.devices.size
        for cid, score in descent.training_scores.items():
            shards[f"score.{cid}"] = _even_shards(score, m, f"fit score {cid}")
        for cid, mdl in result.model:
            if isinstance(mdl, RandomEffectModel):
                shards[f"table.{cid}"] = _even_shards(mdl.coeffs, m, f"fit table {cid}")
        say("train: per-device rows", json.dumps(shards))
    return {
        "result": result,
        "shards": shards,
        "checkpoint_root": os.path.join(ckpt, "config_0"),
        "objective": fe_values[-1],
        "auc": auc,
        "solver": solver,
        "logloss_rel_diff": ll_rel,
        "score_max_abs_diff": score_err,
        "retraces_in_repeat": retraces,
    }


def leg_serve(sizes, arrays, checkpoint_root, mesh, seconds) -> dict:
    """serve_from_checkpoint -> ServingFrontend; scores against host NumPy."""
    import jax
    import numpy as np

    from photon_ml_tpu.analysis.runtime_guard import sync_discipline
    from photon_ml_tpu.serving import get_engine, serve_from_checkpoint
    from photon_ml_tpu.serving.frontend import ServingFrontend

    fe_X, users, items, _y, re_feat = arrays
    n = fe_X.shape[0]
    rng = np.random.default_rng(7)
    requests = []
    for rows in sizes.serve_rows:
        idx = rng.integers(0, n, size=rows)
        requests.append(_game_input(fe_X, users, items, None, re_feat, idx))
    # one request whose entity ids were never seen in training: both random
    # effects score 0, the fixed effect alone answers
    unseen = _game_input(fe_X, users + sizes.n_users, items + sizes.n_items, None,
                         re_feat, rng.integers(0, n, size=16))
    requests.append(unseen)

    frontend, _manager = serve_from_checkpoint(checkpoint_root)
    if mesh is not None:
        # serve_from_checkpoint builds a single-device engine; the mesh engine
        # of the same generation is what a four-chip host serves from
        engine = get_engine(frontend.engine.model, mesh=mesh)
        frontend.close()
        frontend = ServingFrontend(engine, generation=frontend.generation)
    try:
        engine = frontend.engine
        model = engine.model
        buckets = [engine.bucket(r.n) for r in requests]
        check(
            buckets[4] == buckets[5] and sizes.serve_rows[4] != sizes.serve_rows[5],
            f"requests 4 and 5 must share a bucket at different sizes: {buckets}",
        )

        def round_trip():
            return [frontend.score(r, timeout=600.0) for r in requests]

        got, first = _timed(round_trip)
        traces_after_first = engine.trace_count
        # The dispatcher thread owns every engine call, and jax's transfer
        # guard context is thread-local, so sync_discipline() on this thread
        # alone would not see the dispatch: hold the process-wide flag for
        # the guarded round as well.
        jax.config.update("jax_transfer_guard_device_to_host", "disallow")
        try:
            with sync_discipline(what="chip_smoke repeat serving round") as region:
                again, repeat = _timed(round_trip)
            retraces = region.traces
        finally:
            jax.config.update("jax_transfer_guard_device_to_host", "allow")
        seconds["serve"] = {"first": round(first, 2), "repeat": round(repeat, 2)}
        check(
            engine.trace_count == traces_after_first,
            "the serving engine retraced on the repeat round",
        )
        worst = 0.0
        for r, a, b in zip(requests, got, again):
            check(a.shape == (r.n,) and np.isfinite(a).all(), "bad serving scores")
            check(np.array_equal(a, b), "repeat round changed a request's scores")
            worst = max(worst, float(np.max(np.abs(a - host_game_scores(model, r)))))
        fe_only = host_game_scores(
            type(model)(models={"fixed": model.get_model("fixed")}), unseen
        )
        check(
            float(np.max(np.abs(got[-1] - fe_only))) <= SCORE_ATOL,
            "unseen-entity request did not score the fixed effect alone",
        )
        stats = frontend.stats()
        say(
            f"serve: {len(requests)} requests x2 (rows {[r.n for r in requests]}, "
            f"buckets {buckets}); first round {first:.2f}s, repeat {repeat:.2f}s; "
            f"engine traces {traces_after_first}, retraces in repeat = "
            f"{retraces}; max |score - host_f64| = {worst:.2e} (tol "
            f"{SCORE_ATOL}); generation {stats['generation']}, served "
            f"{stats.get('served', 0)}"
        )
        check(worst <= SCORE_ATOL, f"serving scores off by {worst:.3e} > {SCORE_ATOL}")
        check(stats.get("served", 0) == 2 * len(requests), f"served count: {stats}")
        check(stats.get("dispatch_failures", 0) == 0, f"dispatch failures: {stats}")
    finally:
        frontend.close()
    check(not frontend._dispatcher.is_alive(), "dispatcher thread outlived close()")
    return {"score_max_abs_diff": worst, "retraces_in_repeat": retraces}


def leg_kernels(sizes, fe_X, rehearsal: bool, seconds) -> dict:
    """Compile and run the three fused kernels at every shape class their
    gates admit; then a fixed-effect solve that must have engaged one."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    from pallas_microbench import float64_reference

    from photon_ml_tpu.function.losses import loss_for_task
    from photon_ml_tpu.ops import pallas_glm
    from photon_ml_tpu.types import TaskType

    loss = loss_for_task(TaskType(TASK))
    interpret = rehearsal
    rng = np.random.default_rng(3)
    cases = [("train-fe", fe_X, jnp.float32), ("train-fe", fe_X, jnp.bfloat16)]
    n, d = sizes.hess_rows, pallas_glm.MAX_HESS_DIM  # the Hessian gate's widest D
    X_wide = rng.normal(size=(n, d)).astype(np.float32)
    cases += [(f"{n}x{d}", X_wide, jnp.float32), (f"{n}x{d}", X_wide, jnp.bfloat16)]
    for dtype in (jnp.float32, jnp.bfloat16):  # the widest D each gate admits
        d = pallas_glm.MAX_FUSED_DIM[jnp.dtype(dtype).name]
        cases.append((
            f"{sizes.widest_rows}x{d}",
            rng.normal(size=(sizes.widest_rows, d)).astype(np.float32),
            dtype,
        ))
    worst = {}
    t_first = 0.0
    for label, X_host, dtype in cases:
        n, d = X_host.shape
        X = jnp.asarray(X_host, dtype=dtype)
        # the reference sees the values the device holds (bf16 storage rounds)
        X64 = np.asarray(jax.device_get(X.astype(jnp.float32)), np.float64)
        y = (rng.random(n) < 0.5).astype(np.float64)
        off = rng.normal(size=n) * 0.1
        w = np.where(rng.random(n) < 0.1, 0.0, 1.0 + rng.random(n))
        coef = rng.normal(size=d) * (0.5 / np.sqrt(d))
        v = rng.normal(size=d) * (0.5 / np.sqrt(d))
        shifts = rng.normal(size=d) * 0.1
        factors = 1.0 + 0.1 * rng.random(d)
        ref = float64_reference(X64, y, off, w, coef, v, shifts, factors)
        f32 = lambda a: jnp.asarray(a, dtype=jnp.float32)  # noqa: E731
        zero = jnp.zeros((), jnp.float32)
        calls = {
            "value_grad": lambda: pallas_glm.fused_loss_grad_sums(
                X, f32(y), f32(off), f32(w), f32(coef), zero,
                loss_and_dz=loss.loss_and_dz, interpret=interpret,
            ),
            "hvp": lambda: pallas_glm.fused_hessian_vector_sums(
                X, f32(y), f32(off), f32(w), f32(coef), zero, f32(v), zero,
                dzz=loss.dzz, interpret=interpret,
            ),
        }
        if d <= pallas_glm.MAX_HESS_DIM:
            calls["hessian"] = lambda: (pallas_glm.fused_hessian_matrix(
                X, f32(y), f32(off), f32(w), f32(coef), zero, f32(shifts),
                f32(factors), dzz=loss.dzz, interpret=interpret,
            ),)
        for name, call in calls.items():
            out, dt = _timed(lambda: jax.device_get(call()))
            t_first += dt
            err = 0.0
            for got, want in zip(out, ref[name]):
                got = np.asarray(got, np.float64)
                check(np.isfinite(got).all(), f"{name} {label}: non-finite output")
                scale = max(float(np.max(np.abs(want))), 1e-6)
                err = max(err, float(np.max(np.abs(got - want))) / scale)
            key = f"{name}:{label}:{jnp.dtype(dtype).name}"
            worst[key] = err
            tol = KERNEL_RTOL[jnp.dtype(dtype).name]
            say(f"kernels: {key} [{n}x{d}] rel err vs f64 = {err:.2e} "
                f"(tol {tol}, {dt:.2f}s)")
            check(err <= tol, f"{key}: rel err {err:.3e} > {tol}")

    # one fixed-effect solve with the kernels switched on: the lowered
    # program must hold the Mosaic custom call, and the optimum must be the
    # stock lowering's
    from photon_ml_tpu.data.dataset import LabeledData
    from photon_ml_tpu.normalization import NO_NORMALIZATION
    from photon_ml_tpu.optimization.common import OptimizerConfig
    from photon_ml_tpu.optimization.solver_cache import glm_solver
    from photon_ml_tpu.types import OptimizerType, VarianceComputationType

    n, d = fe_X.shape
    z = fe_X @ (rng.normal(size=d) * 0.3)
    labels = (rng.random(n) < 1.0 / (1.0 + np.exp(-z))).astype(np.float64)
    data = LabeledData.build(fe_X, labels, dtype=jnp.float32)
    config = OptimizerConfig(optimizer_type=OptimizerType.LBFGS, max_iterations=FE_ITERS)
    empty = jnp.zeros((0,), jnp.float32)
    solve_args = (
        data, jnp.zeros((d,), jnp.float32), jnp.asarray(1.0, jnp.float32),
        jnp.asarray(0.0, jnp.float32), empty, empty, NO_NORMALIZATION,
    )

    def solve():
        solver = glm_solver(
            TaskType(TASK), config, False, False, False,
            VarianceComputationType.NONE, allow_fused=True,
        )
        text = solver.lower(*solve_args).as_text()
        result, _ = solver(*solve_args)
        return text, float(jax.device_get(result.value)), int(jax.device_get(result.iterations))

    with pallas_glm.pallas_override(False):
        stock_text, stock_value, stock_iters = solve()
    with pallas_glm.pallas_override(True):
        (fused_text, fused_value, fused_iters), dt = _timed(solve)
    t_first += dt
    seconds["kernels"] = {"first": round(t_first, 2), "repeat": None}
    rel = abs(fused_value - stock_value) / abs(stock_value)
    engaged = "tpu_custom_call" in fused_text
    say(
        f"kernels: PHOTON_PALLAS solve value {fused_value:.6g} in {fused_iters} "
        f"iterations vs stock {stock_value:.6g} in {stock_iters} (rel {rel:.2e}); "
        f"tpu_custom_call in lowered text: {engaged}"
        + (" (interpreted kernels lower to plain HLO)" if rehearsal else "")
    )
    check("tpu_custom_call" not in stock_text, "stock solve holds a custom call")
    if not rehearsal:
        check(engaged, "enable_pallas(True) solve did not lower to a tpu_custom_call")
    check(rel <= 1e-3, f"fused solve objective off the stock one by {rel:.3e}")
    return {"worst_rel_err": max(worst.values()), "by_case": worst,
            "fused_solve_iterations": fused_iters, "stock_solve_iterations": stock_iters}


def _write_avro_corpus(path, rng, n, d, n_users, w, bias):
    import numpy as np

    from photon_ml_tpu.data import avro_io

    X = rng.normal(size=(n, d))
    users = rng.integers(0, n_users, size=n)
    z = X @ w + bias[users]
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-z))).astype(np.float64)

    def records():
        for i in range(n):
            yield {
                "uid": f"s{i}",
                "label": float(y[i]),
                "features": [
                    {"name": f"f{j}", "term": "", "value": float(X[i, j])}
                    for j in range(d)
                ],
                "metadataMap": {"userId": f"u{users[i]}"},
                "weight": 1.0,
                "offset": 0.0,
            }

    avro_io.write_container(path, avro_io.TRAINING_EXAMPLE_SCHEMA, records())


def leg_cli(sizes, workdir, seconds) -> dict:
    """The CLI drivers in-process over a seeded Avro corpus."""
    import numpy as np

    from photon_ml_tpu.cli import game_scoring_driver, game_training_driver
    from photon_ml_tpu.cli.game_training_driver import _load_index_maps
    from photon_ml_tpu.cli.parsers import parse_feature_shard_configuration
    from photon_ml_tpu.data import avro_io, native_avro
    from photon_ml_tpu.data.readers import read_merged_avro
    from photon_ml_tpu.io.model_io import load_game_model
    from photon_ml_tpu.transformers import GameTransformer

    say(f"cli: native_avro.available() = {native_avro.available()}")
    base = os.path.join(workdir, "cli")
    os.makedirs(base)
    rng = np.random.default_rng(11)
    d, n_users = 16, 24
    w, bias = rng.normal(size=d) * 0.5, rng.normal(size=n_users)
    train_path = os.path.join(base, "train.avro")
    val_path = os.path.join(base, "validate.avro")
    _write_avro_corpus(train_path, rng, sizes.cli_train, d, n_users, w, bias)
    _write_avro_corpus(val_path, rng, sizes.cli_val, d, n_users, w, bias)
    shard = "name=shardA,feature.bags=features"
    solver = "optimizer=LBFGS,max.iter=50,tolerance=1e-7,regularization=L2,reg.weights=1.0"
    out = os.path.join(base, "train-out")

    def run_drivers():
        rc = game_training_driver.main([
            "--training-task", TASK,
            "--input-data-directories", train_path,
            "--validation-data-directories", val_path,
            "--root-output-directory", out,
            "--override-output-directory",
            "--feature-shard-configurations", shard,
            "--coordinate-configurations", f"name=global,feature.shard=shardA,{solver}",
            "--coordinate-configurations",
            f"name=per-user,random.effect.type=userId,feature.shard=shardA,{solver}",
            "--coordinate-update-sequence", "global,per-user",
            "--coordinate-descent-iterations", "2",
            "--evaluators", "AUC",
            "--log-level", "WARNING",
        ])
        check(rc == 0, f"game_training_driver.main returned {rc}")
        rc = game_scoring_driver.main([
            "--input-data-directories", val_path,
            "--model-input-directory", os.path.join(out, "best"),
            "--root-output-directory", os.path.join(base, "score-out"),
            "--override-output-directory",
            "--feature-shard-configurations", shard,
            "--log-level", "WARNING",
        ])
        check(rc == 0, f"game_scoring_driver.main returned {rc}")

    _, first = _timed(run_drivers)
    seconds["cli"] = {"first": round(first, 2), "repeat": None}
    with open(os.path.join(out, "best", "model-metadata.json")) as f:
        auc = json.load(f)["bestMetric"]
    records = list(
        avro_io.read_container(os.path.join(base, "score-out", "scores", "part-00000.avro"))
    )
    check(len(records) == sizes.cli_val, f"{len(records)} score records")
    by_uid = {r["uid"]: r["predictionScore"] for r in records}

    shard_cfg = dict([parse_feature_shard_configuration(shard)])
    index_maps = _load_index_maps(os.path.join(out, "index-maps"), shard_cfg)
    model = load_game_model(
        os.path.join(out, "best"), {"global": index_maps["shardA"], "per-user": index_maps["shardA"]}
    )
    data, _, uids = read_merged_avro([val_path], shard_cfg, index_maps, ["userId"])
    library = GameTransformer(model=model).score(data)
    driver = np.asarray([by_uid[str(u)] for u in uids])
    err = float(np.max(np.abs(driver - np.asarray(library, np.float64))))
    say(
        f"cli: train+score drivers {first:.1f}s, validation AUC {auc:.4f}, "
        f"{len(records)} scores read back, max |driver - library| = {err:.2e} "
        f"(tol {CLI_SCORE_ATOL})"
    )
    check(auc > AUC_MIN, f"CLI AUC {auc} <= {AUC_MIN}")
    check(np.isfinite(driver).all(), "non-finite scores from the scoring driver")
    check(err <= CLI_SCORE_ATOL, f"driver scores differ from the library's by {err:.3e}")
    return {"auc": auc, "score_max_abs_diff": err,
            "native_avro": bool(native_avro.available())}


def leg_placement(sizes, mesh, seconds) -> dict:
    """--devices N: where build_sharded_game_data puts the data and the
    tables, and what the compiled fused step sends between chips. Runs first:
    it is the leg only a mesh exercises."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import bench
    from photon_ml_tpu.parallel import build_sharded_game_data, make_jitted_game_step
    from photon_ml_tpu.parallel.game import init_game_params

    m = mesh.devices.size
    report = {}
    # build_sharded_game_data must place, not replicate
    def build():
        fe_X, y, ds_u, ds_i = bench._build_workload(
            jnp.float32, sizes.n_train, sizes.n_users, sizes.n_items
        )
        data = build_sharded_game_data(fe_X, y, [ds_u, ds_i], mesh)
        return jax.block_until_ready(data)

    data, t_build = _timed(build)
    report["fe_X"] = _even_shards(data.fe_X.values, m, "fused fe_X")
    for ci, rc in enumerate(data.re):
        for bi, b in enumerate(rc.buckets):
            report[f"re{ci}.bucket{bi}"] = _even_shards(b.X, m, f"re{ci} bucket{bi}")
    params = init_game_params(data, mesh)
    for ci, table in enumerate(params["re"]):
        report[f"re{ci}.table"] = _even_shards(table, m, f"re{ci} table")
    say("placement: per-device rows", json.dumps(report))

    cfgs = _coordinate_configs()
    from photon_ml_tpu.types import TaskType

    step = make_jitted_game_step(
        data, TaskType(TASK), cfgs["fixed"].optimization_config,
        [cfgs["per-user"].optimization_config, cfgs["per-item"].optimization_config],
        mesh,
    )
    (compiled_text, collectives), t_compile = _timed(
        lambda: _profile(step, data, params, m)
    )
    params, diag = step(params)
    jax.block_until_ready(params)
    total = np.asarray(jax.device_get(diag["total_scores"]))
    check(np.isfinite(total).all(), "non-finite scores out of the fused step")
    full = f"f32[{data.fe_X.n_rows},{data.fe_X.n_cols}]"
    check(
        full not in compiled_text,
        f"{full} appears in the per-device module: the pass is replicated",
    )
    from collections import Counter

    kinds = dict(Counter(c.kind for c in collectives))
    in_use = [int((d.memory_stats() or {}).get("bytes_in_use", -1)) for d in mesh.devices.flat]
    say(
        f"placement: build+place {t_build:.1f}s, fused step compile {t_compile:.1f}s, "
        f"collective profile within bounds {kinds}; per-device bytes_in_use {in_use}"
    )
    seconds["placement"] = {"first": round(t_build + t_compile, 2), "repeat": None}
    return {"rows": report, "collectives": kinds, "bytes_in_use": in_use}


def _profile(step, data, params, m):
    """Compile the fused step and hold its collectives to the healthy GLMix
    profile (as __graft_entry__.dryrun_multichip does). GSPMD may lower a
    small bucket's once-per-update [E, S] offset gather as a masked local
    gather + all-reduce, so that class is admitted up to the largest bucket
    block — but only OUTSIDE the solver loops, where nothing larger than the
    fixed effect's (value, gradient) tuple may ride the wire."""
    from photon_ml_tpu.parallel.hlo_guards import (
        assert_collective_profile,
        loop_collectives,
    )

    text = step.jitted.lower(data, params).compile().as_text()
    d = data.fe_X.n_cols
    collectives = assert_collective_profile(
        text,
        grad_elements=d,
        table_elements=max((rc.n_entities + 1 + m) * rc.max_k for rc in data.re),
        n_samples=data.n,
        bucket_block_elements=max(
            b.X.shape[0] * b.X.shape[1] for rc in data.re for b in rc.buckets
        ),
    )
    in_loop = loop_collectives(text)
    check(in_loop, "no collective found inside the solver loops: vacuous scan")
    for name, line, elements in in_loop:
        check(
            elements <= d + 1,
            f"{elements}-element collective inside a solver loop ({name}): {line[:160]}",
        )
    return text, collectives


# --------------------------------------------------------------------- main


def main(argv=None) -> int:
    global _PREFIX
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--devices", type=int, default=1,
                    help="run the train and serve legs over make_mesh(N) "
                         "and check the placement (default 1: no mesh, all legs)")
    ap.add_argument("--samples", type=int, default=None,
                    help="training samples (default 1,000,000); shrink N, never "
                         "the widths, when the time limit demands it")
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU, tiny sizes, interpreted kernels: finds typos, "
                         "proves nothing about the chip")
    args = ap.parse_args(argv)
    rehearsal = args.rehearsal
    sizes = TINY if rehearsal else FULL
    if args.samples is not None:
        sizes = dataclasses.replace(sizes, n_train=args.samples)
    if rehearsal:
        _PREFIX = "REHEARSAL platform=cpu "
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["PHOTON_PALLAS_INTERPRET"] = "1"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}"
        )
    sys.path.insert(0, REPO)
    t_start = time.perf_counter()

    import jax

    import bench
    from photon_ml_tpu.cli.runtime import configure_compilation_cache

    dev = leg_device(args, rehearsal)
    cache_dir = configure_compilation_cache()
    say(f"compile cache: {cache_dir}")
    meter = CompileMeter()
    if sizes.n_train != FULL.n_train and not rehearsal:
        say(f"NOTE: N shrunk to {sizes.n_train} samples (widths unchanged)")

    mesh = None
    if args.devices > 1:
        from photon_ml_tpu.parallel import make_mesh

        mesh = make_mesh(args.devices)

    seconds: dict = {}
    summary: dict = {}
    arrays, t_gen = _timed(lambda: bench._generate_workload(
        sizes.n_train + sizes.n_val, sizes.n_users, sizes.n_items
    ))
    fe_X, users, items, y, re_feat = arrays
    re_feat = re_feat.tocsr()
    import numpy as np

    train_rows = np.arange(sizes.n_train)
    val_rows = np.arange(sizes.n_train, sizes.n_train + sizes.n_val)
    workload = (
        _game_input(fe_X, users, items, y, re_feat, train_rows),
        _game_input(fe_X, users, items, y, re_feat, val_rows),
    )
    say(
        f"workload: {sizes.n_train} train + {sizes.n_val} validation samples x 64, "
        f"{sizes.n_users} users, {sizes.n_items} items, K=8, seed 42 "
        f"({t_gen:.1f}s on the host)"
    )

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        if mesh is not None:
            summary["placement"] = meter.measure(seconds, "placement", lambda: leg_placement(
                sizes, mesh, seconds))
        train = meter.measure(seconds, "train", lambda: leg_train(
            sizes, workload, mesh, workdir, seconds))
        summary["train"] = {k: train[k] for k in (
            "objective", "auc", "solver", "logloss_rel_diff",
            "score_max_abs_diff", "retraces_in_repeat", "shards")}
        summary["serve"] = meter.measure(seconds, "serve", lambda: leg_serve(
            sizes, (fe_X, users, items, y, re_feat), train["checkpoint_root"],
            mesh, seconds))
        if mesh is None:
            summary["kernels"] = meter.measure(seconds, "kernels", lambda: leg_kernels(
                sizes, fe_X[: sizes.n_train], rehearsal, seconds))
            summary["cli"] = meter.measure(seconds, "cli", lambda: leg_cli(
                sizes, workdir, seconds))
    for leg, rec in seconds.items():
        say(f"set-up, not a metric: {leg} {json.dumps(rec)}")

    peak = None
    if not rehearsal:
        peak = [int(d.memory_stats()["peak_bytes_in_use"]) for d in jax.devices()[: args.devices]]
        say(f"device: peak_bytes_in_use {peak}")
    device = {
        "platform": dev["platform"],
        "kind": dev["device_kind"],
        "count": dev["device_count"],
    }
    summary_record = {
        "device": device,
        "jax": jax.__version__,
        "mesh_devices": args.devices,
        "samples": sizes.n_train,
        "seconds": seconds,  # first call vs repeat: set-up facts, not metrics
        "total_seconds": round(time.perf_counter() - t_start, 1),
        "compile_cache_dir": cache_dir,
        "peak_bytes_in_use": peak,
        "legs": summary,
        "claim": None,
    }
    say("summary:", json.dumps(summary_record))
    # The result line is the contract's and nothing more: exactly "ok" and
    # "device". A rehearsal's carries the prefix too: it is not a result.
    say(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
