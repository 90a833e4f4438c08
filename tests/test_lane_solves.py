"""The random-effect L-BFGS written over the lane axis.

``lbfgs.minimize_lbfgs_lanes`` and ``linesearch.strong_wolfe_lanes`` run one
``while_loop`` each over a leading lane axis; lane ``e`` of their results
must be ``jax.vmap`` of the one-problem ``minimize_lbfgs`` / ``strong_wolfe``
bit for bit, in every field. The bucket solves of plain L-BFGS coordinates
reach them through ``solver_cache._re_bucket_solve_fn`` (so tracing such an
update batches no while loop), every other bucket solve keeps ``jax.vmap``,
the fixed-effect solves never reach them, and ``descent.update`` says which
form ran (``loop``).
"""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp
from jax._src.interpreters import batching
from jax._src.lax.control_flow.loops import while_p

from photon_ml_tpu.data.dataset import LabeledData
from photon_ml_tpu.data.matrix import DenseDesignMatrix
from photon_ml_tpu.function.losses import loss_for_task
from photon_ml_tpu.function.objective import GLMObjective
from photon_ml_tpu.optimization import factory, lbfgs, linesearch, solver_cache
from photon_ml_tpu.optimization.common import OptimizerConfig
from photon_ml_tpu.types import ConvergenceReason, OptimizerType, TaskType, VarianceComputationType
from photon_ml_tpu.util.timed import records

LOGISTIC, LINEAR, POISSON = (
    TaskType.LOGISTIC_REGRESSION, TaskType.LINEAR_REGRESSION, TaskType.POISSON_REGRESSION,
)
S, K = 12, 4
# a lane's kind: a logistic GLM, or a quadratic whose "gradient" points
# uphill (every trial step raises the value: the line search fails)
GLM, UPHILL = 0, 1


def _lanes(rng, kinds, dtype=jnp.float32):
    """One lane per entry of ``kinds``: rows of features, labels, weights
    and an L2 weight, spread in scale so the lanes stop at different
    iterations."""
    E = len(kinds)
    scale = np.geomspace(0.3, 6.0, E)[:, None, None]
    X = rng.normal(size=(E, S, K)) * scale
    y = rng.integers(0, 2, size=(E, S)).astype(float)
    return {
        "X": jnp.asarray(X, dtype),
        "y": jnp.asarray(y, dtype),
        "w": jnp.ones((E, S), dtype),
        "l2": jnp.asarray(rng.uniform(0.05, 1.0, size=E), dtype),
        "kind": jnp.asarray(kinds, jnp.int32),
    }


def _value_and_grad(lane, x):
    """One lane's objective at ``x`` (``[K]``)."""
    data = LabeledData(
        X=DenseDesignMatrix(lane["X"]), labels=lane["y"],
        offsets=jnp.zeros_like(lane["y"]), weights=lane["w"],
    )
    f, g = GLMObjective(loss_for_task(LOGISTIC), allow_fused=False).value_and_gradient(
        data, x, lane["l2"]
    )
    uphill = lane["kind"] == UPHILL
    return (
        jnp.where(uphill, jnp.sum(x * x), f),
        jnp.where(uphill, -2.0 * x, g),
    )


def _x0(rng, lanes, dtype=jnp.float32):
    return jnp.asarray(rng.normal(size=(lanes["X"].shape[0], K)), dtype)


def _padding(lanes, x0, e):
    """Lane ``e`` as bucket padding: no rows, zero warm start."""
    lanes = {k: v for k, v in lanes.items()}
    for k in ("X", "y", "w"):
        lanes[k] = lanes[k].at[e].set(0.0)
    return lanes, x0.at[e].set(0.0)


def _stationary(lanes, x0, e):
    """Lane ``e`` with real rows at a point of exactly zero gradient: at
    zero coefficients every row comes twice, once of each label, and small
    integer features keep every partial sum of the gradient exact."""
    dtype = lanes["X"].dtype
    rows = np.repeat(np.arange(-2.0, S // 2 - 2.0)[:, None] * np.ones(K), 2, axis=0)
    lanes = dict(
        lanes,
        X=lanes["X"].at[e].set(jnp.asarray(rows, dtype)),
        y=lanes["y"].at[e].set(jnp.asarray(np.tile([1.0, 0.0], S // 2), dtype)),
    )
    return lanes, x0.at[e].set(0.0)


def _scenario(name, rng):
    """``(lanes, x0, settings, {lane: what it must show})``."""
    settings = dict(max_iterations=60, tolerance=1e-7, history_length=5, max_line_search_iterations=10)
    if name == "staggered":
        lanes = _lanes(rng, [GLM] * 6)
        return lanes, _x0(rng, lanes), settings, {}
    if name == "zero_gradient":
        lanes = _lanes(rng, [GLM] * 5)
        lanes, x0 = _stationary(lanes, _x0(rng, lanes), 2)
        return lanes, x0, settings, {2: "no_iterations"}
    if name == "all_padding":
        lanes = _lanes(rng, [GLM] * 5)
        lanes, x0 = _padding(lanes, _x0(rng, lanes), 0)
        return lanes, x0, settings, {0: "no_iterations"}
    if name == "max_iterations":
        lanes = _lanes(rng, [GLM] * 5)
        lanes, x0 = _stationary(lanes, _x0(rng, lanes), 1)
        return lanes, x0, dict(settings, max_iterations=3, tolerance=1e-12), {
            0: "max_iterations", 1: "no_iterations",
        }
    if name == "line_search_fails":
        lanes = _lanes(rng, [GLM, GLM, UPHILL, GLM, GLM])
        return lanes, _x0(rng, lanes), settings, {2: "search_failed"}
    if name == "mixed":
        lanes = _lanes(rng, [GLM, GLM, UPHILL, GLM, GLM, GLM, GLM])
        x0 = _x0(rng, lanes)
        lanes, x0 = _padding(lanes, x0, 0)
        lanes, x0 = _stationary(lanes, x0, 4)
        return lanes, x0, settings, {
            0: "no_iterations", 2: "search_failed", 4: "no_iterations",
        }
    raise AssertionError(name)


SCENARIOS = ["staggered", "zero_gradient", "all_padding", "max_iterations", "line_search_fails", "mixed"]


def _assert_bitwise(got, want):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a.view(np.uint8), b.view(np.uint8))


def _assert_shows(res, expect):
    reasons, iters = np.asarray(res.convergence_reason), np.asarray(res.iterations)
    assert (reasons != ConvergenceReason.NOT_CONVERGED).all()
    for e, what in expect.items():
        if what == "no_iterations":
            assert iters[e] == 0 and reasons[e] == ConvergenceReason.GRADIENT_CONVERGED
        elif what == "max_iterations":
            assert reasons[e] == ConvergenceReason.MAX_ITERATIONS
        elif what == "search_failed":
            assert reasons[e] == ConvergenceReason.OBJECTIVE_NOT_IMPROVING
    # the lanes iterate for different counts, so frozen lanes ride the loop
    assert len(set(iters.tolist())) > 1


@pytest.mark.parametrize("track_states", [False, True])
@pytest.mark.parametrize("scenario", SCENARIOS)
def test_lane_lbfgs_is_vmap_of_the_one_problem_solve_bit_for_bit(rng, scenario, track_states):
    lanes, x0, settings, expect = _scenario(scenario, rng)
    settings = dict(settings, track_states=track_states)

    got = jax.jit(
        lambda lanes, x0: lbfgs.minimize_lbfgs_lanes(_value_and_grad, lanes, x0, **settings)
    )(lanes, x0)
    want = jax.jit(jax.vmap(
        lambda lane, x0: lbfgs.minimize_lbfgs(functools.partial(_value_and_grad, lane), x0, **settings)
    ))(lanes, x0)

    _assert_bitwise(got, want)
    _assert_shows(got, expect)
    assert (got.tracked_values is not None) == track_states


@pytest.mark.parametrize("scenario", SCENARIOS)
def test_lane_strong_wolfe_is_vmap_of_the_one_problem_search_bit_for_bit(rng, scenario):
    """One search from each lane's start along its steepest-descent
    direction, with every other lane masked inactive."""
    lanes, x0, settings, _expect = _scenario(scenario, rng)
    E = x0.shape[0]
    f0, g0 = jax.vmap(_value_and_grad)(lanes, x0)
    direction = -g0
    dphi0 = jnp.sum(g0 * direction, axis=-1)
    init_alpha = jnp.ones((E,), x0.dtype)
    active = jnp.arange(E) % 3 != 1

    def phi(lane, a):
        data, x, d = lane
        ft, gt = _value_and_grad(data, x + a * d)
        return ft, gt, jnp.dot(gt, d)

    got = jax.jit(lambda *a: linesearch.strong_wolfe_lanes(
        phi, a[:3], *a[3:7], max_iters=settings["max_line_search_iterations"], active=a[7]
    ))(lanes, x0, direction, f0, g0, dphi0, init_alpha, active)
    want = jax.jit(jax.vmap(
        lambda lane, x, d, f0, g0, dphi0, a0, act: linesearch.strong_wolfe(
            functools.partial(phi, (lane, x, d)), f0, g0, dphi0, a0,
            max_iters=settings["max_line_search_iterations"], active=act,
        )
    ))(lanes, x0, direction, f0, g0, dphi0, init_alpha, active)

    _assert_bitwise(got, want)
    evals = np.asarray(got.evals)
    assert (evals[~np.asarray(active)] == 1).all()  # masked lanes return the no-op
    assert evals[np.asarray(active)].max() > 1


def _bucket_inputs(rng, task, E=6, s=10, k=3):
    X = rng.normal(size=(E, s, k)) * np.geomspace(0.3, 4.0, E)[:, None, None]
    if task == POISSON:
        y = rng.poisson(1.5, size=(E, s)).astype(float)
    else:
        y = rng.integers(0, 2, size=(E, s)).astype(float)
    w = np.ones((E, s))
    X[0], y[0], w[0] = 0.0, 0.0, 0.0  # a padding lane
    f32 = jnp.float32
    return (
        jnp.asarray(X, f32), jnp.asarray(y, f32), jnp.asarray(w, f32),
        jnp.asarray(rng.normal(size=(E, s)) * 0.1, f32),
        jnp.asarray(rng.normal(size=(E, k)) * 0.1, f32).at[0].set(0.0),
        jnp.asarray(rng.uniform(0.1, 1.0, size=E), f32), f32(0.0),
    )


def _vmapped(monkeypatch):
    """Route every bucket through ``jax.vmap`` of the one-lane solve: the
    reference the lane form must equal."""
    monkeypatch.setattr(solver_cache, "lane_loop", lambda *a: False)


@pytest.mark.parametrize("active", [None, True, False])
@pytest.mark.parametrize("variance", [VarianceComputationType.NONE, VarianceComputationType.SIMPLE])
def test_bucket_solve_lanes_match_vmap_bit_for_bit(rng, monkeypatch, variance, active):
    """The bucket solve ``_re_bucket_solve_fn`` builds, lane form against
    ``jax.vmap`` of its one-lane solve; under ``with_active`` a masked
    bucket converges every lane in zero iterations and reports zero."""
    args = _bucket_inputs(rng, LOGISTIC)
    cfg = OptimizerConfig(max_iterations=40, tolerance=1e-7)
    with_active = active is not None
    if with_active:
        args = args + (jnp.asarray(active),)
    got = jax.jit(solver_cache._re_bucket_solve_fn(LOGISTIC, cfg, False, variance, "lbfgs", with_active))(*args)
    _vmapped(monkeypatch)
    want = jax.jit(solver_cache._re_bucket_solve_fn(LOGISTIC, cfg, False, variance, "lbfgs", with_active))(*args)

    _assert_bitwise(got, want)
    coefs, reasons, iters, evals, _var = (np.asarray(a) for a in got)
    if active is False:
        assert (iters == 0).all() and (evals == 0).all()
        np.testing.assert_array_equal(coefs, np.asarray(args[4]))
    else:
        assert iters[0] == 0 and iters[1:].min() > 0 and len(set(iters[1:].tolist())) > 1


def _coordinate_inputs(rng, task):
    from photon_ml_tpu.algorithm.random_effect import build_l2_rows, update_program_data
    from photon_ml_tpu.data.random_effect import build_random_effect_dataset
    from photon_ml_tpu.optimization.precision import FLOAT32

    n, n_users = 360, 10
    shares = np.repeat(np.arange(n_users), np.arange(1, n_users + 1))
    users = shares[np.arange(n) % len(shares)]
    X = np.concatenate([np.ones((n, 1)), rng.normal(size=(n, 2))], axis=1)
    eta = 0.5 * X[:, 1] + 0.3 * rng.normal(size=n_users)[users]
    y = rng.poisson(np.exp(eta)) * 1.0 if task == POISSON else (eta + 0.5 * rng.normal(size=n) > 0) * 1.0
    ds = build_random_effect_dataset(sp.csr_matrix(X), users, "userId", feature_shard_id="per-user", labels=y)
    buckets, view, slots = update_program_data(ds, FLOAT32, None)
    l2_rows = build_l2_rows(ds, 1.0, None, jnp.float32, ds.n_entities)
    offsets = jnp.asarray(rng.normal(size=n) * 0.1, jnp.float32)
    fresh = lambda: (  # noqa: E731 - donated: new buffers per call
        jnp.zeros((ds.n_entities, ds.max_k), jnp.float32), jnp.zeros((n,), jnp.float32), None,
    )
    rest = (offsets, l2_rows, jnp.float32(0.0), buckets, (None,) * len(buckets), view, slots)
    return ds, fresh, rest


@pytest.mark.parametrize("task", [LOGISTIC, POISSON])
def test_coordinate_update_program_matches_a_vmapped_reference_bit_for_bit(rng, monkeypatch, task):
    ds, fresh, rest = _coordinate_inputs(rng, task)
    cfg = OptimizerConfig(max_iterations=50, tolerance=1e-7)
    program = solver_cache.re_coordinate_update_program(
        task, cfg, False, VarianceComputationType.NONE, ds.n_entities, "lbfgs"
    )
    got = program(*fresh(), *rest)
    _vmapped(monkeypatch)
    reference = jax.jit(solver_cache._re_coordinate_update_fn(
        task, cfg, False, VarianceComputationType.NONE, ds.n_entities, "lbfgs"
    ))
    want = reference(*fresh(), *rest)

    _assert_bitwise(got, want)
    coeffs, _score, _var, ok, _reasons, iters, _evals = got
    assert bool(ok) and np.abs(np.asarray(coeffs)).max() > 1e-3
    assert min(int(np.asarray(i).max()) for i in iters) > 0


def _count(monkeypatch, module, name):
    calls = []
    inner = getattr(module, name)

    def counting(*a, **k):
        calls.append(name)
        return inner(*a, **k)

    monkeypatch.setattr(module, name, counting)
    return calls


@pytest.fixture()
def while_batchings(monkeypatch):
    """Calls of JAX's while-loop batching rule from now on."""
    calls = []
    rule = batching.fancy_primitive_batchers[while_p]

    def counting(*a, **k):
        calls.append(1)
        return rule(*a, **k)

    monkeypatch.setitem(batching.fancy_primitive_batchers, while_p, counting)
    return calls


@pytest.mark.parametrize(
    "optimizer, has_l1, re_solver, lanes",
    [
        (OptimizerType.LBFGS, False, "lbfgs", True),
        (OptimizerType.OWLQN, True, "lbfgs", False),
        (OptimizerType.LBFGS, False, "direct", False),
        (OptimizerType.TRON, False, "lbfgs", False),
    ],
)
def test_tracing_an_lbfgs_update_batches_no_while_loop(
    rng, monkeypatch, while_batchings, optimizer, has_l1, re_solver, lanes
):
    """An L-BFGS coordinate's update traces with zero calls of the
    while-loop batching rule; OWLQN, TRON and direct coordinates keep the
    vmapped form (the rule runs for each bucket)."""
    ds, fresh, rest = _coordinate_inputs(rng, LOGISTIC)
    lane_solves = _count(monkeypatch, lbfgs, "minimize_lbfgs_lanes")
    cfg = OptimizerConfig(optimizer_type=optimizer, max_iterations=50)
    update = solver_cache._re_coordinate_update_fn(
        LOGISTIC, cfg, has_l1, VarianceComputationType.NONE, ds.n_entities, re_solver
    )
    jax.make_jaxpr(update)(*fresh(), *rest)
    n_buckets = len(rest[3])
    assert len(lane_solves) == (n_buckets if lanes else 0)
    assert (len(while_batchings) == 0) == lanes
    if not lanes:
        assert len(while_batchings) >= n_buckets


def test_fixed_effect_solves_never_reach_the_lane_form(rng, monkeypatch):
    from photon_ml_tpu.normalization import NO_NORMALIZATION

    lane_solves = _count(monkeypatch, lbfgs, "minimize_lbfgs_lanes")
    one_problem = _count(monkeypatch, factory, "minimize_lbfgs"), _count(monkeypatch, factory, "minimize_newton")
    n, d = 64, 5
    data = LabeledData(
        X=DenseDesignMatrix(jnp.asarray(rng.normal(size=(n, d)), jnp.float32)),
        labels=jnp.asarray(rng.integers(0, 2, size=n), jnp.float32),
        offsets=jnp.zeros(n, jnp.float32), weights=jnp.ones(n, jnp.float32),
    )
    z = jnp.zeros((0,), jnp.float32)
    for task, optimizer in ((LOGISTIC, OptimizerType.LBFGS), (LINEAR, OptimizerType.NEWTON)):
        # a fresh jit past glm_solver's lru_cache: this trace runs the Python
        solve = solver_cache.glm_solver.__wrapped__(
            task, OptimizerConfig(optimizer_type=optimizer), False, False, False, VarianceComputationType.NONE
        )
        jax.make_jaxpr(solve)(data, jnp.zeros(d, jnp.float32), jnp.float32(1.0), jnp.float32(0.0), z, z,
                              NO_NORMALIZATION)
    assert [len(calls) for calls in one_problem] == [1, 1] and lane_solves == []


def _game_data(rng, task, n=400):
    from photon_ml_tpu.data.game_data import GameInput

    users = rng.integers(0, 20, size=n)
    X = rng.normal(size=(n, 3))
    V = np.column_stack([np.ones(n), X[:, 0]])
    eta = X @ np.array([0.4, -0.2, 0.1]) + 0.5 * rng.normal(size=20)[users]
    y = (eta + 0.5 * rng.normal(size=n) > 0) * 1.0 if task == LOGISTIC else eta + 0.3 * rng.normal(size=n)
    return GameInput(
        features={"global": X, "per-user": sp.csr_matrix(V)}, labels=y,
        id_columns={"userId": users.astype(np.int64)},
    )


@pytest.mark.parametrize(
    "task, re_optimizer, want",
    [(LOGISTIC, OptimizerType.LBFGS, "lanes"), (LINEAR, OptimizerType.LBFGS, "vmap"),
     (LOGISTIC, OptimizerType.TRON, "vmap")],
)
def test_descent_update_spans_say_which_loop_ran(rng, task, re_optimizer, want):
    """``loop`` on kind-``re`` updates: ``lanes`` for plain L-BFGS, ``vmap``
    for the direct solve (a linear task's default) and for TRON."""
    from photon_ml_tpu.estimators import (
        CoordinateConfiguration,
        FixedEffectDataConfiguration,
        GameEstimator,
        RandomEffectDataConfiguration,
    )
    from photon_ml_tpu.optimization.config import GLMOptimizationConfiguration, RegularizationContext
    from photon_ml_tpu.types import RegularizationType

    def opt(optimizer):
        return GLMOptimizationConfiguration(
            optimizer_config=OptimizerConfig(optimizer_type=optimizer, max_iterations=30),
            regularization_context=RegularizationContext(RegularizationType.L2),
            regularization_weight=1.0,
        )

    configs = {
        "fixed": CoordinateConfiguration(
            data_config=FixedEffectDataConfiguration("global"), optimization_config=opt(OptimizerType.LBFGS),
        ),
        "per-user": CoordinateConfiguration(
            data_config=RandomEffectDataConfiguration("userId", "per-user"), optimization_config=opt(re_optimizer),
        ),
    }
    since = time.time_ns()
    GameEstimator(task=task, coordinate_configurations=configs, n_iterations=2).fit(_game_data(rng, task))
    updates = records(since_ns=since, name="descent.update")
    assert [r.attrs["loop"] for r in updates if r.attrs["kind"] == "re"] == [want, want]
    assert all("loop" not in r.attrs for r in updates if r.attrs["kind"] != "re")
