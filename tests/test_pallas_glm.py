"""Fused Pallas GLM kernel vs the stock XLA objective (interpret mode on CPU).

The kernel itself is exercised interpreted (pl.pallas_call(interpret=True)) so
its numerics are validated without a TPU; the integration gate is exercised
through GLMObjective with the PHOTON_PALLAS_INTERPRET test hook.
"""

import contextlib
import os

import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.data.dataset import LabeledData
from photon_ml_tpu.function.losses import (
    logistic_loss,
    poisson_loss,
    smoothed_hinge_loss,
    squared_loss,
)
from photon_ml_tpu.function.objective import GLMObjective
from photon_ml_tpu.normalization import NormalizationContext
from photon_ml_tpu.ops import pallas_glm

LOSSES = [logistic_loss, squared_loss, poisson_loss, smoothed_hinge_loss]


@contextlib.contextmanager
def pallas_interpret():
    """Enable the fused kernels in interpret mode, restoring prior state."""
    prev_env = os.environ.get("PHOTON_PALLAS_INTERPRET")
    pallas_glm.enable_pallas(True)
    os.environ["PHOTON_PALLAS_INTERPRET"] = "1"
    try:
        yield
    finally:
        pallas_glm.enable_pallas(None)
        if prev_env is None:
            del os.environ["PHOTON_PALLAS_INTERPRET"]
        else:
            os.environ["PHOTON_PALLAS_INTERPRET"] = prev_env


def _problem(rng, n=700, d=5, weights=None):
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (rng.uniform(size=n) > 0.5).astype(np.float32)
    off = rng.normal(size=n).astype(np.float32) * 0.1
    w = np.ones(n, dtype=np.float32) if weights is None else weights
    coef = rng.normal(size=d).astype(np.float32) * 0.5
    return X, y, off, w, coef


def _reference_sums(loss, X, y, off, w, coef):
    z = X.astype(np.float64) @ coef.astype(np.float64) + off
    l, dz = loss.loss_and_dz(jnp.asarray(z), jnp.asarray(y.astype(np.float64)))
    with np.errstate(invalid="ignore"):  # 0 * inf rows are masked by the where
        wl = np.where(w != 0, w * np.asarray(l), 0.0)
        wdz = np.where(w != 0, w * np.asarray(dz), 0.0)
    return wl.sum(), X.T.astype(np.float64) @ wdz, wdz.sum()


@pytest.mark.parametrize("loss", LOSSES, ids=lambda l: l.name)
def test_fused_sums_match_reference(rng, loss):
    X, y, off, w, coef = _problem(rng)
    val, grad, wsum = pallas_glm.fused_loss_grad_sums(
        jnp.asarray(X), jnp.asarray(y), jnp.asarray(off), jnp.asarray(w),
        jnp.asarray(coef), jnp.float32(0.0),
        loss_and_dz=loss.loss_and_dz, interpret=True,
    )
    ref_val, ref_grad, ref_wsum = _reference_sums(loss, X, y, off, w, coef)
    np.testing.assert_allclose(float(val), ref_val, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(grad), ref_grad, rtol=2e-4, atol=1e-3)
    np.testing.assert_allclose(float(wsum), ref_wsum, rtol=2e-4, atol=1e-4)


def test_block_boundary_and_weight_masking(rng):
    """N not a multiple of the block size; weight-0 rows with overflowing
    margins must stay inert (the _weighted contract)."""
    n = pallas_glm.BLOCK_ROWS + 37
    X, y, off, w, coef = _problem(rng, n=n, d=3)
    w[::5] = 0.0
    off[::5] = 1e30  # exp overflows in the Poisson loss — must not poison sums
    val, grad, wsum = pallas_glm.fused_loss_grad_sums(
        jnp.asarray(X), jnp.asarray(y), jnp.asarray(off), jnp.asarray(w),
        jnp.asarray(coef), jnp.float32(0.0),
        loss_and_dz=poisson_loss.loss_and_dz, interpret=True,
    )
    ref_val, ref_grad, ref_wsum = _reference_sums(poisson_loss, X, y, off, w, coef)
    assert np.isfinite(float(val)) and np.isfinite(np.asarray(grad)).all()
    np.testing.assert_allclose(float(val), ref_val, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(grad), ref_grad, rtol=2e-4, atol=1e-3)


def test_objective_integration_matches_stock_path(rng):
    """GLMObjective.value_and_gradient via the fused gate == stock XLA path,
    including the normalization shift/factor algebra and the L2 term."""
    from photon_ml_tpu.data.matrix import DenseDesignMatrix

    X, y, off, w, coef = _problem(rng, n=300, d=4)
    X[:, -1] = 1.0  # intercept column (required for shift normalization)
    data = LabeledData(
        X=DenseDesignMatrix(jnp.asarray(X)),
        labels=jnp.asarray(y),
        offsets=jnp.asarray(off),
        weights=jnp.asarray(w),
    )
    shifts = rng.normal(size=4) * 0.1
    shifts[-1] = 0.0
    norm = NormalizationContext(
        factors=np.abs(rng.normal(size=4)) + 0.5, shifts=shifts, intercept_index=3
    )
    obj = GLMObjective(logistic_loss, norm)
    stock_v, stock_g = obj.value_and_gradient(data, jnp.asarray(coef), 0.7)

    with pallas_interpret():
        assert obj._fused_value_and_gradient(data, jnp.asarray(coef), 0.7) is not None
        fused_v, fused_g = obj.value_and_gradient(data, jnp.asarray(coef), 0.7)
    np.testing.assert_allclose(float(fused_v), float(stock_v), rtol=2e-5)
    np.testing.assert_allclose(np.asarray(fused_g), np.asarray(stock_g), rtol=2e-4, atol=1e-4)


def test_gate_closed_by_default_and_for_wrong_dtypes(rng):
    X, y, off, w, coef = _problem(rng, n=64, d=3)
    from photon_ml_tpu.data.matrix import DenseDesignMatrix

    data = LabeledData(
        X=DenseDesignMatrix(jnp.asarray(X)), labels=jnp.asarray(y),
        offsets=jnp.asarray(off), weights=jnp.asarray(w),
    )
    obj = GLMObjective(logistic_loss)
    assert obj._fused_value_and_gradient(data, jnp.asarray(coef), 0.0) is None  # off

    with pallas_interpret():
        # f64 coefficients: precision contract keeps the stock path
        data64 = LabeledData(
            X=DenseDesignMatrix(jnp.asarray(X, dtype=jnp.float64)),
            labels=jnp.asarray(y), offsets=jnp.asarray(off), weights=jnp.asarray(w),
        )
        assert (
            obj._fused_value_and_gradient(data64, jnp.asarray(coef, jnp.float64), 0.0)
            is None
        )
        # vmapped-construction objects opt out
        no_fuse = GLMObjective(logistic_loss, allow_fused=False)
        assert no_fuse._fused_value_and_gradient(data, jnp.asarray(coef), 0.0) is None


def test_solver_convergence_through_fused_path(rng):
    """An L-BFGS solve with the fused evaluations reaches the stock optimum."""
    from photon_ml_tpu.function.objective import make_value_and_grad
    from photon_ml_tpu.optimization import minimize_lbfgs
    from photon_ml_tpu.data.matrix import DenseDesignMatrix

    X, y, off, w, coef = _problem(rng, n=400, d=6)
    data = LabeledData(
        X=DenseDesignMatrix(jnp.asarray(X)), labels=jnp.asarray(y),
        offsets=jnp.asarray(off), weights=jnp.asarray(w),
    )
    obj = GLMObjective(logistic_loss)
    vg = make_value_and_grad(obj, data, l2_weight=1.0)
    stock = minimize_lbfgs(vg, jnp.zeros(6, jnp.float32), tolerance=1e-10, max_iterations=100)

    with pallas_interpret():
        fused = minimize_lbfgs(
            vg, jnp.zeros(6, jnp.float32), tolerance=1e-10, max_iterations=100
        )
    np.testing.assert_allclose(
        np.asarray(fused.coefficients), np.asarray(stock.coefficients), atol=5e-4
    )


@pytest.mark.parametrize("loss", [logistic_loss, squared_loss, poisson_loss], ids=lambda l: l.name)
def test_fused_hvp_matches_reference(rng, loss):
    X, y, off, w, coef = _problem(rng, n=pallas_glm.BLOCK_ROWS + 51, d=6)
    w[::7] = 0.0
    v = rng.normal(size=6).astype(np.float32)
    vec, usum = pallas_glm.fused_hessian_vector_sums(
        jnp.asarray(X), jnp.asarray(y), jnp.asarray(off), jnp.asarray(w),
        jnp.asarray(coef), jnp.float32(0.0), jnp.asarray(v), jnp.float32(0.0),
        dzz=loss.dzz, interpret=True,
    )
    z = X.astype(np.float64) @ coef.astype(np.float64) + off
    d2 = np.asarray(loss.dzz(jnp.asarray(z), jnp.asarray(y.astype(np.float64))))
    dv = X.astype(np.float64) @ v.astype(np.float64)
    u = np.where(w != 0, w * d2 * dv, 0.0)
    np.testing.assert_allclose(np.asarray(vec), X.T.astype(np.float64) @ u, rtol=2e-4, atol=1e-3)
    np.testing.assert_allclose(float(usum), u.sum(), rtol=2e-4, atol=1e-4)


def test_tron_solve_through_fused_hvp(rng):
    """A TRON solve with fused evaluations (value+grad AND HVP) matches stock."""
    from photon_ml_tpu.function.objective import make_value_and_grad
    from photon_ml_tpu.optimization import minimize_tron
    from photon_ml_tpu.data.matrix import DenseDesignMatrix

    X, y, off, w, coef = _problem(rng, n=400, d=5)
    data = LabeledData(
        X=DenseDesignMatrix(jnp.asarray(X)), labels=jnp.asarray(y),
        offsets=jnp.asarray(off), weights=jnp.asarray(w),
    )
    obj = GLMObjective(logistic_loss)
    vg = make_value_and_grad(obj, data, l2_weight=0.5)
    hvp = lambda x, v: obj.hessian_vector(data, x, v, 0.5)
    stock = minimize_tron(vg, hvp, jnp.zeros(5, jnp.float32), tolerance=1e-10, max_iterations=60)

    with pallas_interpret():
        assert obj._fused_hessian_vector(
            data, jnp.zeros(5, jnp.float32), jnp.ones(5, jnp.float32), 0.5
        ) is not None
        fused = minimize_tron(
            vg, hvp, jnp.zeros(5, jnp.float32), tolerance=1e-10, max_iterations=60
        )
    np.testing.assert_allclose(
        np.asarray(fused.coefficients), np.asarray(stock.coefficients), atol=5e-4
    )


def test_fused_hvp_with_normalization(rng):
    from photon_ml_tpu.data.matrix import DenseDesignMatrix

    X, y, off, w, coef = _problem(rng, n=250, d=4)
    X[:, -1] = 1.0
    shifts = rng.normal(size=4) * 0.1
    shifts[-1] = 0.0
    norm = NormalizationContext(
        factors=np.abs(rng.normal(size=4)) + 0.5, shifts=shifts, intercept_index=3
    )
    data = LabeledData(
        X=DenseDesignMatrix(jnp.asarray(X)), labels=jnp.asarray(y),
        offsets=jnp.asarray(off), weights=jnp.asarray(w),
    )
    obj = GLMObjective(logistic_loss, norm)
    v = jnp.asarray(rng.normal(size=4).astype(np.float32))
    stock = obj.hessian_vector(data, jnp.asarray(coef), v, 0.3)

    with pallas_interpret():
        fused = obj.hessian_vector(data, jnp.asarray(coef), v, 0.3)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(stock), rtol=2e-4, atol=1e-4)


def test_fused_kernels_bf16_storage(rng):
    """bf16 design-matrix storage: both kernels run the bf16 MXU branch and
    stay within bf16 rounding of the f64 reference (the _mxu_dot contract)."""
    X, y, off, w, coef = _problem(rng, n=300, d=4)
    Xb = jnp.asarray(X, dtype=jnp.bfloat16)
    val, grad, wsum = pallas_glm.fused_loss_grad_sums(
        Xb, jnp.asarray(y), jnp.asarray(off), jnp.asarray(w),
        jnp.asarray(coef), jnp.float32(0.0),
        loss_and_dz=logistic_loss.loss_and_dz, interpret=True,
    )
    Xr = np.asarray(Xb).astype(np.float64)  # the rounded values ARE the data
    ref_val, ref_grad, ref_wsum = _reference_sums(logistic_loss, Xr, y, off, w, coef)
    np.testing.assert_allclose(float(val), ref_val, rtol=3e-2)
    np.testing.assert_allclose(np.asarray(grad), ref_grad, rtol=4e-2, atol=0.5)
    np.testing.assert_allclose(float(wsum), ref_wsum, rtol=4e-2, atol=0.1)
    zr = Xr @ np.asarray(coef, np.float64) + off

    v = rng.normal(size=4).astype(np.float32)
    vec, usum = pallas_glm.fused_hessian_vector_sums(
        Xb, jnp.asarray(y), jnp.asarray(off), jnp.asarray(w),
        jnp.asarray(coef), jnp.float32(0.0), jnp.asarray(v), jnp.float32(0.0),
        dzz=logistic_loss.dzz, interpret=True,
    )
    d2 = np.asarray(logistic_loss.dzz(jnp.asarray(zr), jnp.asarray(y.astype(np.float64))))
    u = w * d2 * (Xr @ v.astype(np.float64))
    np.testing.assert_allclose(np.asarray(vec), Xr.T @ u, rtol=4e-2, atol=0.5)
    np.testing.assert_allclose(float(usum), u.sum(), rtol=4e-2, atol=0.1)


@pytest.mark.parametrize("loss", [logistic_loss, squared_loss, poisson_loss], ids=lambda l: l.name)
def test_fused_hessian_matrix_matches_reference(rng, loss):
    X, y, off, w, coef = _problem(rng, n=pallas_glm.HESS_BLOCK_ROWS + 33, d=5)
    w[::6] = 0.0
    H = pallas_glm.fused_hessian_matrix(
        jnp.asarray(X), jnp.asarray(y), jnp.asarray(off), jnp.asarray(w),
        jnp.asarray(coef), jnp.float32(0.0),
        jnp.zeros(5, jnp.float32), jnp.ones(5, jnp.float32),
        dzz=loss.dzz, interpret=True,
    )
    z = X.astype(np.float64) @ coef.astype(np.float64) + off
    d2 = np.where(w != 0, w * np.asarray(
        loss.dzz(jnp.asarray(z), jnp.asarray(y.astype(np.float64)))
    ), 0.0)
    ref = X.T.astype(np.float64) @ (X.astype(np.float64) * d2[:, None])
    np.testing.assert_allclose(np.asarray(H), ref, rtol=2e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(H), np.asarray(H).T, atol=1e-5)  # symmetric


def test_fused_hessian_matrix_bf16_storage(rng):
    """bf16 storage upcasts the block to f32 BEFORE normalization (the stock
    path's reduction-dtype contract)."""
    X, y, off, w, coef = _problem(rng, n=200, d=4)
    Xb = jnp.asarray(X, dtype=jnp.bfloat16)
    H = pallas_glm.fused_hessian_matrix(
        Xb, jnp.asarray(y), jnp.asarray(off), jnp.asarray(w),
        jnp.asarray(coef), jnp.float32(0.0),
        jnp.zeros(4, jnp.float32), jnp.ones(4, jnp.float32),
        dzz=logistic_loss.dzz, interpret=True,
    )
    Xr = np.asarray(Xb).astype(np.float64)  # the rounded values ARE the data
    z = Xr @ np.asarray(coef, np.float64) + off
    d2 = w * np.asarray(logistic_loss.dzz(jnp.asarray(z), jnp.asarray(y.astype(np.float64))))
    ref = Xr.T @ (Xr * d2[:, None])
    np.testing.assert_allclose(np.asarray(H), ref, rtol=4e-2, atol=0.5)


def test_fused_hessian_matrix_through_objective_with_normalization(rng):
    from photon_ml_tpu.data.matrix import DenseDesignMatrix

    X, y, off, w, coef = _problem(rng, n=200, d=4)
    X[:, -1] = 1.0
    shifts = rng.normal(size=4) * 0.1
    shifts[-1] = 0.0
    norm = NormalizationContext(
        factors=np.abs(rng.normal(size=4)) + 0.5, shifts=shifts, intercept_index=3
    )
    data = LabeledData(
        X=DenseDesignMatrix(jnp.asarray(X)), labels=jnp.asarray(y),
        offsets=jnp.asarray(off), weights=jnp.asarray(w),
    )
    obj = GLMObjective(logistic_loss, norm)
    stock = obj.hessian_matrix(data, jnp.asarray(coef), 0.4)
    with pallas_interpret():
        assert obj._fused_hessian_matrix(data, jnp.asarray(coef), 0.4) is not None
        fused = obj.hessian_matrix(data, jnp.asarray(coef), 0.4)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(stock), rtol=2e-4, atol=1e-4)


def test_newton_solve_through_fused_hessian(rng):
    """A NEWTON solve with all three fused kernels matches the stock optimum."""
    from photon_ml_tpu.optimization import minimize_newton
    from photon_ml_tpu.function.objective import make_value_and_grad
    from photon_ml_tpu.data.matrix import DenseDesignMatrix

    X, y, off, w, coef = _problem(rng, n=400, d=5)
    data = LabeledData(
        X=DenseDesignMatrix(jnp.asarray(X)), labels=jnp.asarray(y),
        offsets=jnp.asarray(off), weights=jnp.asarray(w),
    )
    obj = GLMObjective(logistic_loss)
    vg = make_value_and_grad(obj, data, l2_weight=0.8)
    hess = lambda x: obj.hessian_matrix(data, x, 0.8)
    stock = minimize_newton(vg, hess, jnp.zeros(5, jnp.float32), tolerance=1e-10)
    with pallas_interpret():
        fused = minimize_newton(vg, hess, jnp.zeros(5, jnp.float32), tolerance=1e-10)
    np.testing.assert_allclose(
        np.asarray(fused.coefficients), np.asarray(stock.coefficients), atol=5e-4
    )


def test_full_game_step_with_fused_fe(rng):
    """The single-device GAME step traces and matches stock with the fused
    kernels engaged — the exact lowering the TPU bench's pallas variant runs."""
    import scipy.sparse as sp

    from photon_ml_tpu.data.random_effect import build_random_effect_dataset
    from photon_ml_tpu.optimization.common import OptimizerConfig
    from photon_ml_tpu.optimization.config import (
        GLMOptimizationConfiguration,
        RegularizationContext,
    )
    from photon_ml_tpu.parallel import (
        build_sharded_game_data,
        make_jitted_game_step,
        make_mesh,
    )
    from photon_ml_tpu.parallel.game import init_game_params
    from photon_ml_tpu.types import OptimizerType, RegularizationType, TaskType

    n, d, n_users = 400, 6, 10
    X = rng.normal(size=(n, d)).astype(np.float32)
    users = np.arange(n) % n_users
    y = ((X @ rng.normal(size=d)) + rng.normal(size=n_users)[users] > 0).astype(
        np.float64
    )
    re_feat = sp.csr_matrix(np.ones((n, 1), np.float32))
    ds = build_random_effect_dataset(
        re_feat, users, "u", labels=y, intercept_index=0, dtype=jnp.float32
    )
    mesh = make_mesh(1)
    data = build_sharded_game_data(X, y, [ds], mesh, dtype=jnp.float32)
    cfg = GLMOptimizationConfiguration(
        optimizer_config=OptimizerConfig(
            optimizer_type=OptimizerType.NEWTON, max_iterations=10
        ),
        regularization_context=RegularizationContext(RegularizationType.L2),
        regularization_weight=1.0,
    )

    def run():
        step = make_jitted_game_step(
            data, TaskType.LOGISTIC_REGRESSION, cfg, [cfg], mesh
        )
        params, diag = step(init_game_params(data, mesh))
        return np.asarray(params["fixed"]), float(diag["fe_value"])

    stock_coef, stock_val = run()
    with pallas_interpret():
        # guard: the fused path must actually be eligible for this setup,
        # otherwise the parity below silently compares stock against stock
        assert pallas_glm.should_fuse(d, jnp.float32)
        from photon_ml_tpu.data.matrix import DenseDesignMatrix
        from photon_ml_tpu.function.objective import GLMObjective
        from photon_ml_tpu.function.losses import logistic_loss

        assert GLMObjective(logistic_loss)._fused_eligible(
            data.fe_X if isinstance(data.fe_X, DenseDesignMatrix) else None,
            jnp.zeros((d,), jnp.float32),
        )
        fused_coef, fused_val = run()
    np.testing.assert_allclose(fused_coef, stock_coef, atol=5e-4)
    np.testing.assert_allclose(fused_val, stock_val, rtol=1e-4)


def test_shard_mapped_solver_matches_gspmd(rng):
    """shard_mapped_glm_solver (explicit shard_map + psum) must reach the same
    optimum as the stock GSPMD solve on the 8-device mesh — with the kernels
    OFF it is purely the explicit-collective form of the same math."""
    from photon_ml_tpu.data.dataset import LabeledData
    from photon_ml_tpu.data.matrix import DenseDesignMatrix
    from photon_ml_tpu.optimization.common import OptimizerConfig
    from photon_ml_tpu.optimization.solver_cache import (
        glm_solver,
        shard_mapped_glm_solver,
    )
    from photon_ml_tpu.parallel import make_mesh
    from photon_ml_tpu.parallel.glm import shard_labeled_data
    from photon_ml_tpu.types import TaskType, VarianceComputationType

    n, d = 512, 6
    X = rng.normal(size=(n, d))
    y = ((X @ rng.normal(size=d)) > 0).astype(np.float64)
    data = LabeledData.build(DenseDesignMatrix(jnp.asarray(X)), y, dtype=jnp.float64)
    mesh = make_mesh(8)
    data_m, _ = shard_labeled_data(data, mesh)

    cfg = OptimizerConfig(max_iterations=60, tolerance=1e-10)
    l2 = jnp.asarray(1.0, jnp.float64)
    l1 = jnp.asarray(0.0, jnp.float64)
    x0 = jnp.zeros((d,), jnp.float64)
    empty = jnp.zeros((0,), jnp.float64)

    from photon_ml_tpu.normalization import NO_NORMALIZATION

    ref, _ = glm_solver(
        TaskType.LOGISTIC_REGRESSION, cfg, False, False, False,
        VarianceComputationType.NONE,
    )(data, x0, l2, l1, empty, empty, NO_NORMALIZATION)
    got = shard_mapped_glm_solver(TaskType.LOGISTIC_REGRESSION, cfg, False, mesh)(
        data_m, x0, l2, l1
    )
    np.testing.assert_allclose(
        np.asarray(got.coefficients), np.asarray(ref.coefficients), atol=1e-8
    )
    assert float(got.value) == pytest.approx(float(ref.value), rel=1e-10)


def test_full_game_step_shard_map_multichip(rng):
    """With the kernels enabled on a MULTI-device mesh, the fixed-effect solve
    takes the shard_map route (per-device fused blocks + explicit psum) and
    matches the stock GSPMD result — the single-chip-only restriction on the
    Pallas path is lifted."""
    import scipy.sparse as sp

    from photon_ml_tpu.data.random_effect import build_random_effect_dataset
    from photon_ml_tpu.optimization.common import OptimizerConfig
    from photon_ml_tpu.optimization.config import (
        GLMOptimizationConfiguration,
        RegularizationContext,
    )
    from photon_ml_tpu.parallel import (
        build_sharded_game_data,
        make_jitted_game_step,
        make_mesh,
    )
    from photon_ml_tpu.parallel.game import init_game_params
    from photon_ml_tpu.types import RegularizationType, TaskType

    n, d, n_users = 400, 6, 10
    X = rng.normal(size=(n, d)).astype(np.float32)
    users = np.arange(n) % n_users
    y = ((X @ rng.normal(size=d)) + rng.normal(size=n_users)[users] > 0).astype(
        np.float64
    )
    re_feat = sp.csr_matrix(np.ones((n, 1), np.float32))
    ds = build_random_effect_dataset(
        re_feat, users, "u", labels=y, intercept_index=0, dtype=jnp.float32
    )
    mesh = make_mesh(8)
    data = build_sharded_game_data(X, y, [ds], mesh, dtype=jnp.float32)
    cfg = GLMOptimizationConfiguration(
        optimizer_config=OptimizerConfig(max_iterations=40),
        regularization_context=RegularizationContext(RegularizationType.L2),
        regularization_weight=1.0,
    )

    def run():
        step = make_jitted_game_step(
            data, TaskType.LOGISTIC_REGRESSION, cfg, [cfg], mesh
        )
        params, diag = step(init_game_params(data, mesh))
        return np.asarray(params["fixed"]), float(diag["fe_value"])

    stock_coef, stock_val = run()
    with pallas_interpret():
        assert pallas_glm.should_fuse(d, jnp.float32, per_device=True)
        fused_coef, fused_val = run()
    np.testing.assert_allclose(fused_coef, stock_coef, atol=5e-4)
    np.testing.assert_allclose(fused_val, stock_val, rtol=1e-4)


@pytest.mark.parametrize("opt", ["TRON", "NEWTON"])
def test_shard_mapped_solver_second_order_parity(rng, opt):
    """The psum'd objective must serve the second-order paths too: TRON's
    per-CG-step HVP and NEWTON's per-iteration full Hessian are data sums
    with replicated algebra on top — shard_map must reach the stock optimum."""
    from photon_ml_tpu.data.dataset import LabeledData
    from photon_ml_tpu.data.matrix import DenseDesignMatrix
    from photon_ml_tpu.normalization import NO_NORMALIZATION
    from photon_ml_tpu.optimization.common import OptimizerConfig
    from photon_ml_tpu.optimization.solver_cache import (
        glm_solver,
        shard_mapped_glm_solver,
    )
    from photon_ml_tpu.parallel import make_mesh
    from photon_ml_tpu.parallel.glm import shard_labeled_data
    from photon_ml_tpu.types import OptimizerType, TaskType, VarianceComputationType

    n, d = 512, 6
    X = rng.normal(size=(n, d))
    y = ((X @ rng.normal(size=d)) > 0).astype(np.float64)
    data = LabeledData.build(DenseDesignMatrix(jnp.asarray(X)), y, dtype=jnp.float64)
    mesh = make_mesh(8)
    data_m, _ = shard_labeled_data(data, mesh)

    cfg = OptimizerConfig(
        optimizer_type=OptimizerType[opt], max_iterations=30, tolerance=1e-10
    )
    l2 = jnp.asarray(1.0, jnp.float64)
    l1 = jnp.asarray(0.0, jnp.float64)
    x0 = jnp.zeros((d,), jnp.float64)
    empty = jnp.zeros((0,), jnp.float64)

    ref, _ = glm_solver(
        TaskType.LOGISTIC_REGRESSION, cfg, False, False, False,
        VarianceComputationType.NONE,
    )(data, x0, l2, l1, empty, empty, NO_NORMALIZATION)
    got = shard_mapped_glm_solver(TaskType.LOGISTIC_REGRESSION, cfg, False, mesh)(
        data_m, x0, l2, l1
    )
    np.testing.assert_allclose(
        np.asarray(got.coefficients), np.asarray(ref.coefficients), atol=1e-7
    )


def test_shard_mapped_solver_rejects_sparse(rng):
    """nnz-sharded COO inside shard_map would psum partial-margin losses —
    reject it loudly; sparse problems take the GSPMD lowering."""
    import scipy.sparse as sp

    from photon_ml_tpu.data.dataset import LabeledData
    from photon_ml_tpu.data.matrix import as_design_matrix
    from photon_ml_tpu.optimization.common import OptimizerConfig
    from photon_ml_tpu.optimization.solver_cache import shard_mapped_glm_solver
    from photon_ml_tpu.parallel import make_mesh
    from photon_ml_tpu.types import TaskType

    n, d = 64, 4
    X = sp.random(n, d, density=0.3, random_state=0, format="csr")
    y = (rng.random(n) < 0.5).astype(np.float64)
    data = LabeledData.build(as_design_matrix(X), y, dtype=jnp.float64)
    mesh = make_mesh(8)
    solve = shard_mapped_glm_solver(
        TaskType.LOGISTIC_REGRESSION, OptimizerConfig(max_iterations=5), False, mesh
    )
    with pytest.raises(TypeError, match="dense sample-sharded"):
        solve(
            data,
            jnp.zeros((d,), jnp.float64),
            jnp.asarray(1.0, jnp.float64),
            jnp.asarray(0.0, jnp.float64),
        )
