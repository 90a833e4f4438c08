"""Mesh-parallel paths on the simulated 8-device CPU platform (conftest).

Mirrors the reference's test strategy: "distributed" behavior exercised on a
multi-core local context (SURVEY §4); here an 8-device mesh stands in for v5e-8.
Correctness bar: sharded solves must match the single-device solves bit-for-near.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from photon_ml_tpu.data.dataset import LabeledData
from photon_ml_tpu.data.random_effect import build_random_effect_dataset
from photon_ml_tpu.optimization.config import (
    GLMOptimizationConfiguration,
    RegularizationContext,
)
from photon_ml_tpu.optimization.common import OptimizerConfig
from photon_ml_tpu.parallel import (
    build_sharded_game_data,
    make_mesh,
    make_jitted_game_step,
    shard_labeled_data,
    train_glm_sharded,
)
from photon_ml_tpu.parallel.game import init_game_params, game_train_step
from photon_ml_tpu.types import OptimizerType, RegularizationType, TaskType


def _logistic_data(rng, n=640, d=12):
    X = rng.normal(size=(n, d))
    w_true = rng.normal(size=d)
    p = 1.0 / (1.0 + np.exp(-(X @ w_true)))
    y = (rng.random(n) < p).astype(np.float64)
    return X, y


def _config(opt=OptimizerType.LBFGS, l2=1.0, max_iterations=100):
    return GLMOptimizationConfiguration(
        optimizer_config=OptimizerConfig(optimizer_type=opt, max_iterations=max_iterations),
        regularization_context=RegularizationContext(
            RegularizationType.L2 if l2 else RegularizationType.NONE
        ),
        regularization_weight=l2,
    )


class TestShardedGLM:
    def test_sharded_matches_single_device_dense(self, rng):
        X, y = _logistic_data(rng)
        mesh = make_mesh(8)
        cfg = _config()
        data = LabeledData.build(X, y, dtype=jnp.float64)
        sharded, n = shard_labeled_data(data, mesh)
        assert n == len(y)
        w_sharded, res = train_glm_sharded(sharded, TaskType.LOGISTIC_REGRESSION, cfg, mesh)

        w_single, _ = train_glm_sharded(data, TaskType.LOGISTIC_REGRESSION, cfg, make_mesh(1))
        np.testing.assert_allclose(np.asarray(w_sharded), np.asarray(w_single), atol=1e-6)

    def test_sharded_handles_padding(self, rng):
        # n = 637 is not divisible by 8: padded rows must be inert (weight 0)
        X, y = _logistic_data(rng, n=637)
        mesh = make_mesh(8)
        cfg = _config()
        sharded, n = shard_labeled_data(LabeledData.build(X, y, dtype=jnp.float64), mesh)
        assert sharded.labels.shape[0] % 8 == 0 and n == 637
        w_pad, _ = train_glm_sharded(sharded, TaskType.LOGISTIC_REGRESSION, cfg, mesh)
        w_ref, _ = train_glm_sharded(
            LabeledData.build(X, y, dtype=jnp.float64),
            TaskType.LOGISTIC_REGRESSION,
            cfg,
            make_mesh(1),
        )
        np.testing.assert_allclose(np.asarray(w_pad), np.asarray(w_ref), atol=1e-6)

    def test_sharded_sparse_tron(self, rng):
        X, y = _logistic_data(rng, n=320, d=20)
        Xs = sp.csr_matrix(np.where(np.abs(X) > 0.8, X, 0.0))
        mesh = make_mesh(8)
        cfg = _config(opt=OptimizerType.TRON)
        sharded, _ = shard_labeled_data(LabeledData.build(Xs, y, dtype=jnp.float64), mesh)
        w, res = train_glm_sharded(sharded, TaskType.LOGISTIC_REGRESSION, cfg, mesh)
        w_ref, _ = train_glm_sharded(
            LabeledData.build(Xs, y, dtype=jnp.float64),
            TaskType.LOGISTIC_REGRESSION,
            cfg,
            make_mesh(1),
        )
        np.testing.assert_allclose(np.asarray(w), np.asarray(w_ref), atol=1e-6)


class TestShardedGameStep:
    # ONE workload (fixed seed, class-scoped) shared by every test in the class:
    # identical array shapes + identical static solver configs mean the fused
    # GAME program compiles once and the jit/solver caches serve the rest.
    @pytest.fixture(scope="class")
    def glmix(self):
        rng = np.random.default_rng(271828)
        n, d, n_users, n_items = 200, 8, 13, 7
        fe_X = rng.normal(size=(n, d))
        users = rng.integers(0, n_users, size=n)
        items = rng.integers(0, n_items, size=n)
        w = rng.normal(size=d)
        u_eff = rng.normal(size=n_users) * 0.5
        i_eff = rng.normal(size=n_items) * 0.5
        z = fe_X @ w + u_eff[users] + i_eff[items]
        y = (rng.random(n) < 1.0 / (1.0 + np.exp(-z))).astype(np.float64)

        # per-entity features: intercept + one covariate
        re_feat = sp.csr_matrix(
            np.concatenate([np.ones((n, 1)), fe_X[:, :1]], axis=1)
        )
        ds_u = build_random_effect_dataset(
            re_feat, users, "userId", dtype=jnp.float64, intercept_index=0, labels=y
        )
        ds_i = build_random_effect_dataset(
            re_feat, items, "itemId", dtype=jnp.float64, intercept_index=0, labels=y
        )
        return fe_X, y, ds_u, ds_i

    def test_game_step_runs_and_improves(self, glmix):
        fe_X, y, ds_u, ds_i = glmix
        mesh = make_mesh(8)
        data = build_sharded_game_data(fe_X, y, [ds_u, ds_i], mesh, dtype=jnp.float64)
        cfg = _config(max_iterations=40)
        step = make_jitted_game_step(
            data, TaskType.LOGISTIC_REGRESSION, cfg, [cfg, cfg], mesh
        )
        params = init_game_params(data, mesh)
        params, diag = step(params)
        # total log-loss with the trained scores beats the zero model
        total = np.asarray(diag["total_scores"])
        yv = np.asarray(data.labels)
        wv = np.asarray(data.weights)
        ll = np.sum(wv * (np.log1p(np.exp(-np.abs(total))) + np.maximum(total, 0) - yv * total))
        ll0 = np.sum(wv * np.log(2.0))
        assert ll < ll0

        # junk coefficient rows stay zero
        for rc, coeffs in zip(data.re, params["re"]):
            assert float(jnp.abs(coeffs[rc.n_entities]).max()) == 0.0

    def test_game_step_matches_unsharded(self, glmix):
        fe_X, y, ds_u, ds_i = glmix
        cfg = _config(max_iterations=40)
        out = {}
        for nd in (1, 8):
            mesh = make_mesh(nd)
            data = build_sharded_game_data(fe_X, y, [ds_u, ds_i], mesh, dtype=jnp.float64)
            params = init_game_params(data, mesh)
            params, diag = game_train_step(
                data, params, TaskType.LOGISTIC_REGRESSION, cfg, [cfg, cfg]
            )
            out[nd] = np.asarray(params["fixed"])
        np.testing.assert_allclose(out[1], out[8], atol=1e-6)

    def test_game_step_sparse_fixed_effect_parity(self, glmix):
        """A scipy-sparse fixed-effect design rides the COO-sharded path
        (parallel/glm.py) through the fused pass; results match dense on the
        8-device mesh (VERDICT item 5: PalDBIndexMap billion-feature regime)."""
        fe_X, y, ds_u, ds_i = glmix
        cfg = _config(max_iterations=40)
        mesh = make_mesh(8)
        out = {}
        for kind in ("dense", "sparse"):
            X = sp.csr_matrix(fe_X) if kind == "sparse" else fe_X
            data = build_sharded_game_data(X, y, [ds_u, ds_i], mesh, dtype=jnp.float64)
            params = init_game_params(data, mesh)
            params, _ = game_train_step(
                data, params, TaskType.LOGISTIC_REGRESSION, cfg, [cfg, cfg]
            )
            out[kind] = np.asarray(params["fixed"])
        np.testing.assert_allclose(out["dense"], out["sparse"], atol=1e-6)


def test_bf16_fe_storage_game_step_close_to_f32(rng):
    """fe_storage_dtype=bf16 through the fused pass: coefficients/scores stay
    f32 and the converged objective lands within 1% of full-precision (the
    bench quality gate)."""
    from photon_ml_tpu.parallel.game import (
        build_sharded_game_data,
        game_train_step,
        init_game_params,
    )

    n, d = 256, 8
    fe_X = rng.normal(size=(n, d))
    w = rng.normal(size=d)
    y = (rng.random(n) < 1 / (1 + np.exp(-(fe_X @ w)))).astype(np.float64)
    users = np.arange(n) % 9
    re_feat = sp.csr_matrix(np.ones((n, 1)))
    ds = build_random_effect_dataset(re_feat, users, "userId", labels=y)
    mesh = make_mesh(8)
    cfg = _config(max_iterations=40)
    vals = {}
    for storage in (None, jnp.bfloat16):
        data = build_sharded_game_data(
            fe_X, y, [ds], mesh, dtype=jnp.float32, fe_storage_dtype=storage
        )
        params = init_game_params(data, mesh)
        assert params["fixed"].dtype == jnp.float32
        params, diag = game_train_step(
            data, params, TaskType.LOGISTIC_REGRESSION, cfg, [cfg]
        )
        assert params["fixed"].dtype == jnp.float32
        vals[storage] = float(diag["fe_value"])
    assert abs(vals[jnp.bfloat16] - vals[None]) <= 0.01 * abs(vals[None])


def test_bf16_re_storage_game_step_close_to_f32(rng):
    """re_storage_dtype=bf16: bucket blocks and scoring values store half the
    HBM bytes (the profiled hot loops, ROADMAP.md S2); coefficients
    and the converged objective stay within the bench quality gate of f32."""
    from photon_ml_tpu.parallel.game import (
        build_sharded_game_data,
        game_train_step,
        init_game_params,
    )

    n, d = 256, 8
    fe_X = rng.normal(size=(n, d))
    w = rng.normal(size=d)
    y = (rng.random(n) < 1 / (1 + np.exp(-(fe_X @ w)))).astype(np.float64)
    users = np.arange(n) % 9
    re_feat = sp.csr_matrix(
        np.concatenate([np.ones((n, 1)), fe_X[:, :3]], axis=1)
    )
    ds = build_random_effect_dataset(
        re_feat, users, "userId", labels=y, intercept_index=0
    )
    mesh = make_mesh(8)
    cfg = _config(max_iterations=40)
    vals = {}
    for storage in (None, jnp.bfloat16):
        data = build_sharded_game_data(
            fe_X, y, [ds], mesh, dtype=jnp.float32,
            fe_storage_dtype=storage, re_storage_dtype=storage,
        )
        if storage is not None:
            assert data.re[0].buckets[0].X.dtype == jnp.bfloat16
            assert data.re[0].sample_vals.dtype == jnp.bfloat16
        params = init_game_params(data, mesh)
        params, diag = game_train_step(
            data, params, TaskType.LOGISTIC_REGRESSION, cfg, [cfg]
        )
        assert params["fixed"].dtype == jnp.float32
        assert params["re"][0].dtype == jnp.float32
        vals[storage] = float(diag["fe_value"])
    assert abs(vals[jnp.bfloat16] - vals[None]) <= 0.01 * abs(vals[None])


def _import_bench_module(name):
    """Import a benchmarks/ script by name (they are not a package)."""
    import importlib
    import os
    import sys

    bench_dir = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
    sys.path.insert(0, bench_dir)
    try:
        return importlib.import_module(name)
    finally:
        sys.path.remove(bench_dir)


def test_scale_bench_tiny_smoke(capsys):
    """benchmarks/scale_bench.py --tiny runs both configs end to end and
    reports ~1/m per-device shard scaling."""
    import json

    scale_bench = _import_bench_module("scale_bench")
    assert scale_bench.main(["--tiny"]) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.strip().splitlines()]
    by_config = {rec["config"]: rec for rec in lines}
    sparse = by_config["sparse_fixed_effect"]
    assert sparse["devices"] >= 8
    # nnz shards within one padding row of nnz / m
    assert max(sparse["per_device_nnz_shards"]) <= sparse["nnz"] // sparse["devices"] + 1
    entity = by_config["entity_scale"]
    # table height = ceil((E+1)/m)*m entity-sharded -> at most E//m + 1 rows/device
    assert len(entity["per_device_table_rows"]) == entity["devices"]
    assert max(entity["per_device_table_rows"]) <= (
        entity["n_entities"] // entity["devices"] + 1
    )


def test_run_benchmarks_smoke(capsys):
    """The five-config benchmark runner works end to end: config 3 at tiny
    scale through the main() entry point (plumbing, JSON shape, parity
    fields), plus config 1 called directly at reduced sizes (its --scale-less
    a1a defaults are too heavy for a unit suite)."""
    import json

    run_benchmarks = _import_bench_module("run_benchmarks")
    rc = run_benchmarks.main(["--configs", "3", "--scale", "0.02", "--no-strict"])
    assert rc in (0, None)
    lines = [json.loads(l) for l in capsys.readouterr().out.strip().splitlines()]
    recs = {k: v for rec in lines for k, v in rec.items()}
    assert recs["glmix_movielens_like"]["auc"] > 0.8
    for rec in recs.values():
        assert rec["value"] > 0 and rec["platform"] == "cpu"

    small = run_benchmarks.config1_a1a_avro_lbfgs_l2(n_train=400, n_test=800)
    assert small["auc"] > 0.7 and small["value"] > 0


def test_game_step_partitions_data_not_replicates():
    """Compile-time guard for the closure-constant trap: arrays CLOSED OVER by
    a jitted step become jaxpr constants, and GSPMD replicates constants
    regardless of their committed sharding — every device then recomputes the
    FULL pass (a clean 1/m throughput collapse; zero multi-chip scaling).
    make_jitted_game_step must pass ShardedGameData as a jit argument, so the
    per-device module works on [N/m]-row blocks of the fixed-effect matrix."""
    rng = np.random.default_rng(3)
    n, d = 1024, 16
    fe_X = rng.normal(size=(n, d)).astype(np.float32)
    users = rng.integers(0, 32, size=n)
    y = (rng.random(n) < 0.5).astype(np.float64)
    re_feat = sp.csr_matrix(np.ones((n, 1), dtype=np.float32))
    ds_u = build_random_effect_dataset(
        re_feat, users, "userId", labels=y, intercept_index=0, dtype=jnp.float64
    )
    mesh = make_mesh(8)
    data = build_sharded_game_data(fe_X, y, [ds_u], mesh, dtype=jnp.float64)
    cfg = _config(max_iterations=3)
    step = make_jitted_game_step(
        data, TaskType.LOGISTIC_REGRESSION, cfg, [cfg], mesh
    )
    params = init_game_params(data, mesh)
    txt = step.jitted.lower(data, params).compile().as_text()
    full = f"{n},{d}"          # unpartitioned fixed-effect block
    part = f"{n // 8},{d}"     # correctly partitioned per-device block
    assert txt.count(full) == 0, "fixed-effect matrix is replicated per device"
    assert txt.count(part) > 0

    # Comm-volume guard on the same compiled module (the shape guard's
    # companion): all-reduces stay gradient-sized, all-gathers stay
    # entity-table/score-sized, nothing dataset-shaped rides the wire.
    from photon_ml_tpu.parallel.hlo_guards import assert_collective_profile

    table_elements = max((rc.n_entities + 1 + 8) * rc.max_k for rc in data.re)
    collectives = assert_collective_profile(
        txt, grad_elements=d, table_elements=table_elements, n_samples=n
    )
    assert any(c.kind == "all-reduce" for c in collectives)  # psum is present


def test_collective_profile_guard_rejects_bad_profiles():
    """assert_collective_profile parses real HLO shapes and fails on each
    regression class: dataset-sized reduction, dataset-sized gather,
    unexpected collective kinds, and collective-count blow-up."""
    import pytest

    from photon_ml_tpu.parallel.hlo_guards import (
        Collective,
        assert_collective_profile,
    )

    healthy = """
  %all-reduce.42 = (f32[], f32[24]{0}) all-reduce(%a, %b), channel_id=1
  ROOT %all-reduce.36 = pred[] all-reduce(%c), channel_id=5
  %all-gather = f32[24,4]{1,0} all-gather(%p), channel_id=14, dimensions={0}
  %all-gather.2 = f32[64]{0} all-gather(%q), channel_id=27, dimensions={0}
"""
    parsed = assert_collective_profile(
        healthy, grad_elements=24, table_elements=96, n_samples=64
    )
    assert [c.kind for c in parsed].count("all-reduce") == 2
    assert parsed[0].elements == 25  # tuple (f32[], f32[24])

    with pytest.raises(AssertionError, match="all-reduce payload"):
        assert_collective_profile(
            healthy + "  %all-reduce.9 = f32[1024,24]{1,0} all-reduce(%x)\n",
            grad_elements=24, table_elements=96, n_samples=64,
        )
    with pytest.raises(AssertionError, match="all-gather result"):
        assert_collective_profile(
            healthy + "  %all-gather.9 = f32[1024,24]{1,0} all-gather(%x)\n",
            grad_elements=24, table_elements=96, n_samples=64,
        )
    with pytest.raises(AssertionError, match="unexpected all-to-all"):
        assert_collective_profile(
            healthy + "  %all-to-all.1 = f32[8]{0} all-to-all(%x)\n",
            grad_elements=24, table_elements=96, n_samples=64,
        )
    many = healthy + "".join(
        f"  %all-reduce.x{i} = pred[] all-reduce(%c)\n" for i in range(60)
    )
    with pytest.raises(AssertionError, match="collectives in one pass"):
        assert_collective_profile(
            many, grad_elements=24, table_elements=96, n_samples=64
        )
    # async -start form parses too
    assert Collective.parse_all(
        "  %ar = (f32[24]{0}) all-reduce-start(%x)\n"
    )[0].elements == 24
