#!/usr/bin/env python3
"""Compiled-program inventory ratchet: lower every registered program family
at smoke shapes and diff the structural facts against a committed inventory.

The facts that matter about a compiled module are not its text (op ids churn
with every compiler bump) but its CONTRACT surface, which this tool extracts
per program:

- the donated/aliased buffer set (the ``input_output_alias`` header) — a
  dropped ``donate_argnums`` doubles steady-state HBM for that update and
  no runtime test notices;
- data vs predicate collective counts, whole-module and inside solver
  ``while`` loops (via ``parallel/hlo_guards``) — a new in-loop DATA
  collective runs per solver iteration, not per update;
- the widest float dtype in the module — an f64 leak into an f32 program
  doubles every buffer it touches.

Usage (from the repo root)::

    python tools/program_audit.py --check         # CI gate (default)
    python tools/program_audit.py --update        # regenerate + commit
    python tools/program_audit.py --self-check    # prove the gate fires
    python tools/program_audit.py --check --only serving_score

Exit codes: 0 clean; 1 regression (dropped donation, new in-loop data
collective, widened float dtype, new collective kind, missing program);
2 stale inventory (the program IMPROVED — fewer collectives, more donation,
narrower dtype — regenerate with ``--update`` and commit so the ratchet
tightens); 3 a program family failed to build.

One-command regenerate workflow (after a deliberate program change)::

    python tools/program_audit.py --update && git add tools/program_inventory.json

Program families audited (same smoke shapes as the tier-1 suites, so the
persistent XLA cache makes repeat runs cheap): the mesh-sharded random-effect
coordinate update (``RandomEffectCoordinate.compiled_update_hlo``), the
streamed working-set chunk update (``solver_cache.re_chunk_update_program``
lowered on a real staged chunk — its donated init/score-partial pair is the
two-tables-in-flight memory contract), the 2-D feature-sharded fixed-effect
update in both storage classes (``FixedEffectCoordinate.compiled_update_hlo``
— ``fe_sparse_update`` lowers from a real CSR batch and ratchets the donation
pair plus the feature-axis collective counts; ``fe_update_2d`` is the dense
baseline profile), the fused population/game step
(``parallel.make_jitted_game_step``), the one-program population sweep
(``PopulationTrainer.lower_fused_sweep`` on a settings mesh), and the
serving engine's fused program at its two static buckets.

jax is imported lazily INSIDE the builders: importing this module stays
cheap and env setup (8 emulated CPU devices, x64) can happen first.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import re
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
DEFAULT_INVENTORY = Path(__file__).resolve().parent / "program_inventory.json"

# ---------------------------------------------------------------------------
# HLO fact extraction (pure text -> record; no jax needed)
# ---------------------------------------------------------------------------

_FLOAT_RANK = {"f16": 1, "bf16": 1, "f32": 2, "f64": 3}
_FLOAT_RE = re.compile(r"\b(bf16|f16|f32|f64)\[")
_ALIAS_ENTRY_RE = re.compile(r"\{([\d,\s]*)\}:\s*\((\d+)")


def parse_aliases(hlo_text: str) -> list:
    """Donated/aliased buffers from the module header's
    ``input_output_alias={ {out_index}: (param, {param_index}, kind), ... }``
    as sorted ``"out{i}<-arg{p}"`` strings. Brace-balanced scan: the entry
    values nest ``{}`` so a regex over the whole group would misparse."""
    key = "input_output_alias={"
    start = hlo_text.find(key)
    if start < 0:
        return []
    j = start + len(key) - 1
    depth = 0
    body = ""
    for k in range(j, len(hlo_text)):
        ch = hlo_text[k]
        if ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
            if depth == 0:
                body = hlo_text[j + 1 : k]
                break
    return sorted(
        f"out{{{m.group(1).strip()}}}<-arg{m.group(2)}"
        for m in _ALIAS_ENTRY_RE.finditer(body)
    )


def widest_float(hlo_text: str) -> str:
    found = set(_FLOAT_RE.findall(hlo_text))
    if not found:
        return "none"
    return max(found, key=lambda t: _FLOAT_RANK[t])


def summarize(hlo_text: str) -> dict:
    """Structural record of one compiled module. Pure text analysis on top of
    ``parallel/hlo_guards`` — a predicate collective is the single-element
    all-reduce (loop convergence consensus); everything else is DATA."""
    from photon_ml_tpu.parallel.hlo_guards import Collective, loop_collectives

    data_counts: dict = {}
    pred = 0
    for c in Collective.parse_all(hlo_text):
        if c.kind == "all-reduce" and c.elements == 1:
            pred += 1
        else:
            data_counts[c.kind] = data_counts.get(c.kind, 0) + 1
    in_loop = loop_collectives(hlo_text)
    in_loop_data = sum(
        1 for _, line, elements in in_loop
        if elements != 1 or "all-reduce" not in line
    )
    return {
        "donated": parse_aliases(hlo_text),
        "data_collectives": dict(sorted(data_counts.items())),
        "pred_all_reduce": pred,
        "in_loop_data": in_loop_data,
        "in_loop_pred": len(in_loop) - in_loop_data,
        "widest_float": widest_float(hlo_text),
    }


# ---------------------------------------------------------------------------
# Ratchet diff (pure record -> record comparison)
# ---------------------------------------------------------------------------


def diff_inventories(current: dict, committed: dict) -> tuple:
    """(regressions, stale): regressions fail the build; stale entries mean
    the program IMPROVED past the committed record — regenerate so the
    ratchet captures the better state, exactly like the lint baseline."""
    regressions, stale = [], []
    for name in sorted(committed):
        want, have = committed[name], current.get(name)
        if have is None:
            regressions.append(
                f"{name}: program family missing — it no longer lowers, or was "
                f"dropped from the audit without updating the inventory"
            )
            continue
        dropped = sorted(set(want["donated"]) - set(have["donated"]))
        gained = sorted(set(have["donated"]) - set(want["donated"]))
        if dropped:
            regressions.append(
                f"{name}: donation dropped ({', '.join(dropped)}) — the "
                f"program no longer consumes those input buffers; steady-state "
                f"HBM doubles for each"
            )
        if gained:
            stale.append(f"{name}: newly donated buffer(s): {', '.join(gained)}")
        d = have["in_loop_data"] - want["in_loop_data"]
        if d > 0:
            regressions.append(
                f"{name}: {d} new DATA collective(s) inside solver while-loops "
                f"(runs per solver ITERATION, not per update)"
            )
        elif d < 0:
            stale.append(f"{name}: {-d} fewer in-loop data collective(s)")
        rh = _FLOAT_RANK.get(have["widest_float"], 0)
        rw = _FLOAT_RANK.get(want["widest_float"], 0)
        if rh > rw:
            regressions.append(
                f"{name}: widest float widened {want['widest_float']} -> "
                f"{have['widest_float']} — a precision leak doubles every "
                f"buffer it touches"
            )
        elif rh < rw:
            stale.append(
                f"{name}: widest float narrowed {want['widest_float']} -> "
                f"{have['widest_float']}"
            )
        kinds = set(want["data_collectives"]) | set(have["data_collectives"])
        for kind in sorted(kinds):
            ch = have["data_collectives"].get(kind, 0)
            cw = want["data_collectives"].get(kind, 0)
            if ch > cw:
                regressions.append(
                    f"{name}: data {kind} count grew {cw} -> {ch}"
                    + ("" if cw else " (new collective kind)")
                )
            elif ch < cw:
                stale.append(f"{name}: data {kind} count shrank {cw} -> {ch}")
        if (
            have["pred_all_reduce"] != want["pred_all_reduce"]
            or have["in_loop_pred"] != want["in_loop_pred"]
        ):
            # predicate consensus is payload-free; count drift is worth
            # re-recording but is not a perf regression by itself
            stale.append(
                f"{name}: predicate all-reduce counts changed "
                f"({want['pred_all_reduce']}/{want['in_loop_pred']} -> "
                f"{have['pred_all_reduce']}/{have['in_loop_pred']})"
            )
    for name in sorted(set(current) - set(committed)):
        stale.append(f"{name}: new program family not in the inventory")
    return regressions, stale


# ---------------------------------------------------------------------------
# Program family builders (each lowers + compiles one registered program and
# returns the post-SPMD HLO text; jax/photon_ml_tpu imported lazily)
# ---------------------------------------------------------------------------


def _glm_config(max_iterations=50):
    from photon_ml_tpu.optimization.common import OptimizerConfig
    from photon_ml_tpu.optimization.config import (
        GLMOptimizationConfiguration,
        RegularizationContext,
    )
    from photon_ml_tpu.types import RegularizationType

    return GLMOptimizationConfiguration(
        optimizer_config=OptimizerConfig(
            max_iterations=max_iterations, tolerance=1e-9
        ),
        regularization_context=RegularizationContext(RegularizationType.L2),
        regularization_weight=1.0,
    )


def build_re_update() -> str:
    """Mesh-sharded random-effect coordinate update at the
    tests/test_update_program.py smoke workload (N=420, D=3, 12 entities,
    8 emulated devices) — the donated single-program bucket solve."""
    import numpy as np
    import scipy.sparse as sp
    import jax.numpy as jnp  # noqa: F401  (x64 side effects via conftest-equivalent setup)

    from photon_ml_tpu.algorithm import RandomEffectCoordinate
    from photon_ml_tpu.data.random_effect import build_random_effect_dataset
    from photon_ml_tpu.parallel.mesh import make_mesh
    from photon_ml_tpu.parallel.placement import (
        pad_and_shard_vector,
        place_random_effect_dataset,
    )
    from photon_ml_tpu.types import TaskType

    rng = np.random.default_rng(0)
    N, D, N_USERS = 420, 3, 12
    X = rng.normal(size=(N, D))
    shares = np.repeat(np.arange(N_USERS), np.arange(1, N_USERS + 1))
    users = shares[np.arange(N) % len(shares)]
    w = rng.normal(size=D)
    y = (X @ w + 0.7 * rng.normal(size=N_USERS)[users] > 0).astype(np.float64)
    re_dense = np.concatenate([np.ones((N, 1)), 2.0 * X[:, :2] + 0.5], axis=1)
    re_ds = build_random_effect_dataset(
        sp.csr_matrix(re_dense), users, "userId",
        feature_shard_id="per-user", labels=y,
    )
    mesh = make_mesh(8)
    ds_m = place_random_effect_dataset(re_ds, mesh)
    base = pad_and_shard_vector(np.zeros(N), mesh, dtype=ds_m.sample_vals.dtype)
    coord = RandomEffectCoordinate(
        coordinate_id="per-user", dataset=ds_m,
        task=TaskType.LOGISTIC_REGRESSION, configuration=_glm_config(),
        base_offsets=base, use_update_program=True,
    )
    return coord.compiled_update_hlo()


def build_re_chunk_update() -> str:
    """Streamed working-set chunk update (the per-chunk program
    ``_update_and_score_streamed`` dispatches) lowered on a REAL staged cold
    chunk at the tests/test_working_set.py skewed smoke shape (N=420, 20
    entities, budget 17). The donated pair — the chunk's init rows (arg0)
    and the running score partial (arg1) — IS the at-most-two-chunk-tables
    device-memory contract; dropping either silently doubles the streamed
    footprint."""
    import numpy as np
    import scipy.sparse as sp
    import jax.numpy as jnp

    from photon_ml_tpu.algorithm import RandomEffectCoordinate
    from photon_ml_tpu.data.random_effect import build_random_effect_dataset
    from photon_ml_tpu.optimization.solver_cache import re_chunk_update_program
    from photon_ml_tpu.types import TaskType, VarianceComputationType

    rng = np.random.default_rng(0)
    n, n_users = 420, 20
    X = rng.normal(size=(n, 3))
    shares = np.repeat(np.arange(n_users), np.arange(1, n_users + 1))
    users = shares[np.arange(n) % len(shares)]
    w = rng.normal(size=3)
    y = (X @ w + 0.7 * rng.normal(size=n_users)[users] > 0).astype(np.float64)
    re_dense = np.concatenate([np.ones((n, 1)), 2.0 * X[:, :2] + 0.5], axis=1)
    ds = build_random_effect_dataset(
        sp.csr_matrix(re_dense), users, "userId",
        feature_shard_id="per-user", labels=y,
    )
    coord = RandomEffectCoordinate(
        coordinate_id="per-user", dataset=ds,
        task=TaskType.LOGISTIC_REGRESSION, configuration=_glm_config(),
        base_offsets=jnp.zeros(n, dtype=ds.sample_vals.dtype),
        working_set_rows=17,
    )
    ws = coord._working_set()
    if ws is None:
        raise RuntimeError("working set demoted at the audit smoke shape")
    chunk = next(c for c in ws.chunks if not c.hot)
    staged, _, _ = ws._stage(chunk)
    init = ws._stage_init(chunk)
    program = re_chunk_update_program(
        coord.task,
        coord.configuration.optimizer_config,
        bool(coord.configuration.l1_weight),
        VarianceComputationType(coord.variance_computation),
        ds.max_k,
        "lbfgs",
    )
    score0 = jnp.zeros((ds.n_samples,), dtype=ds.sample_vals.dtype)
    return program.lower(
        init, score0, *staged["data"], staged["l2"], coord._ws_l1,
        staged["norm"], coord.base_offsets, ds.sample_local_cols,
        ds.sample_vals,
    ).compile().as_text()


def build_population_update() -> str:
    """Fused population/game step (one jitted program per descent pass) on an
    8-device mesh at a reduced smoke shape — the donated params carrier."""
    import numpy as np
    import scipy.sparse as sp
    import jax.numpy as jnp

    from photon_ml_tpu.data.random_effect import build_random_effect_dataset
    from photon_ml_tpu.parallel import (
        build_sharded_game_data,
        make_jitted_game_step,
        make_mesh,
    )
    from photon_ml_tpu.parallel.game import init_game_params
    from photon_ml_tpu.types import TaskType

    rng = np.random.default_rng(0)
    n, d = 256, 8
    fe_X = rng.normal(size=(n, d)).astype(np.float32)
    users = rng.integers(0, 16, size=n)
    y = (rng.random(n) < 0.5).astype(np.float64)
    re_feat = sp.csr_matrix(np.ones((n, 1), dtype=np.float32))
    ds_u = build_random_effect_dataset(
        re_feat, users, "userId", labels=y, intercept_index=0,
        dtype=jnp.float64,
    )
    mesh = make_mesh(8)
    data = build_sharded_game_data(fe_X, y, [ds_u], mesh, dtype=jnp.float64)
    cfg = _glm_config(max_iterations=3)
    step = make_jitted_game_step(
        data, TaskType.LOGISTIC_REGRESSION, cfg, [cfg], mesh
    )
    params = init_game_params(data, mesh)
    return step.jitted.lower(data, params).compile().as_text()


def build_fused_sweep() -> str:
    """One-program population sweep with the settings axis sharded over the
    8-device mesh (the zero-data-collective contract's module)."""
    import numpy as np
    import scipy.sparse as sp

    from photon_ml_tpu.data.game_data import GameInput
    from photon_ml_tpu.estimators.config import (
        CoordinateConfiguration,
        FixedEffectDataConfiguration,
        RandomEffectDataConfiguration,
    )
    from photon_ml_tpu.estimators.game_estimator import GameEstimator
    from photon_ml_tpu.parallel.mesh import make_mesh
    from photon_ml_tpu.sweep import PopulationTrainer
    from photon_ml_tpu.types import TaskType

    rng = np.random.default_rng(0)
    n, d, n_users = 260, 4, 9
    X = rng.normal(size=(n, d)).astype(np.float32)
    users = np.arange(n) % n_users
    w = rng.normal(size=d) * 0.6
    z = X @ w + 0.5 * rng.normal(size=n_users)[users]
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-z))).astype(np.float64)
    train = GameInput(
        features={"shardA": sp.csr_matrix(X)},
        labels=y,
        id_columns={"userId": users},
    )
    cfg = _glm_config(max_iterations=25)
    est = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_configurations={
            "global": CoordinateConfiguration(
                FixedEffectDataConfiguration("shardA"), cfg
            ),
            "per-user": CoordinateConfiguration(
                RandomEffectDataConfiguration("userId", "shardA"), cfg
            ),
        },
        n_iterations=1,
    )
    mesh = make_mesh(8, axis_name="settings")
    datasets = est.prepare_training_datasets(train)
    trainer = PopulationTrainer(
        est, datasets, np.asarray(train.offsets), seed=0, mesh=mesh
    )
    settings = [
        {"global.l2": 0.5, "per-user.l2": 8.0},
        {"global.l2": 20.0, "per-user.l2": 0.05},
        {"global.l2": 1.0, "per-user.l2": 1.0},
    ]
    return trainer.lower_fused_sweep(settings, n_iterations=1)


def _fe_coordinate_2d(storage: str):
    """Feature-sharded (2-D data x model mesh) fixed-effect coordinate at the
    tests/test_feature_sharded.py smoke shape, with the requested storage
    class — the fused ``fe_coordinate_update_program`` engages because
    placement stamps ``coef_sharding``."""
    import numpy as np
    import scipy.sparse as sp
    import jax.numpy as jnp

    from photon_ml_tpu.algorithm.coordinate import FixedEffectCoordinate
    from photon_ml_tpu.data.dataset import FixedEffectDataset, LabeledData
    from photon_ml_tpu.data.matrix import SparseDesignMatrix
    from photon_ml_tpu.parallel.feature_sharded import make_mesh2
    from photon_ml_tpu.parallel.placement import place_fixed_effect_dataset
    from photon_ml_tpu.types import TaskType

    rng = np.random.default_rng(0)
    n, d = 256, 24
    dense = (rng.random((n, d)) < 0.3) * rng.standard_normal((n, d))
    y = (rng.random(n) < 0.5).astype(np.float64)
    if storage == "sparse":
        mat = SparseDesignMatrix.from_scipy(sp.csr_matrix(dense), dtype=jnp.float64)
    else:
        mat = dense
    ds = place_fixed_effect_dataset(
        FixedEffectDataset(data=LabeledData.build(mat, y, dtype=jnp.float64)),
        make_mesh2(4, 2),
    )
    return FixedEffectCoordinate(
        coordinate_id="fe", dataset=ds,
        task=TaskType.LOGISTIC_REGRESSION, configuration=_glm_config(),
    )


def build_fe_sparse_update() -> str:
    """Fused fixed-effect update, SPARSE (padded-COO from a real CSR batch)
    storage on the 2-D feature-sharded mesh — the wide-FE program. The
    ratchet pins its donation pair (coeffs_prev/score_prev, the steady-state
    one-copy contract) and its feature-axis collective counts: the sparse
    path's in-loop data collectives are the per-iteration margin/gradient
    all-reduces plus the [D] coefficient-rebuild / [N] margin all-gathers
    that ``hlo_guards.assert_feature_axis_profile`` bounds — one more
    in-loop data collective means a new per-iteration cross-device exchange
    crossing the feature axis."""
    return _fe_coordinate_2d("sparse").compiled_update_hlo()


def build_fe_update_2d() -> str:
    """Fused fixed-effect update, DENSE block-sharded storage on the same
    2-D mesh — the feature-axis baseline profile (in-loop data collectives =
    the margin/gradient all-reduce pair only, 1411.6520's pattern)."""
    return _fe_coordinate_2d("dense").compiled_update_hlo()


def _serving_engine_and_batch():
    import numpy as np
    import scipy.sparse as sp
    import jax.numpy as jnp

    from photon_ml_tpu.data.game_data import GameInput
    from photon_ml_tpu.models.game import (
        FixedEffectModel,
        GameModel,
        RandomEffectModel,
    )
    from photon_ml_tpu.models.glm import Coefficients, LogisticRegressionModel
    from photon_ml_tpu.serving import GameServingEngine
    from photon_ml_tpu.types import TaskType

    rng = np.random.default_rng(0)
    n, d, d_re, n_users, n_items, k_max = 137, 6, 5, 10, 4, 3
    fixed = FixedEffectModel(
        model=LogisticRegressionModel(
            Coefficients(means=jnp.asarray(rng.normal(size=d)))
        ),
        feature_shard_id="global",
    )

    def random_model(re_type, n_entities):
        proj = np.full((n_entities, k_max), -1, dtype=np.int32)
        coeffs = np.zeros((n_entities, k_max))
        for i in range(n_entities):
            k = int(rng.integers(1, k_max + 1))
            cols = np.sort(rng.choice(d_re, size=k, replace=False))
            proj[i, :k] = cols
            coeffs[i, :k] = rng.normal(size=k)
        return RandomEffectModel(
            re_type=re_type, feature_shard_id="re_shard",
            task=TaskType.LOGISTIC_REGRESSION,
            entity_ids=tuple(f"e{i}" for i in range(n_entities)),
            coeffs=jnp.asarray(coeffs), proj_indices=jnp.asarray(proj),
        )

    model = GameModel(models={
        "fixed": fixed,
        "per-user": random_model("userId", n_users),
        "per-item": random_model("itemId", n_items),
    })
    re_dense = rng.normal(size=(n, d_re))
    re_dense[rng.random(size=re_dense.shape) < 0.4] = 0.0
    data = GameInput(
        features={
            "global": rng.normal(size=(n, d)),
            "re_shard": sp.csr_matrix(re_dense),
        },
        labels=(rng.random(n) > 0.5).astype(np.float64),
        offsets=rng.normal(size=n),
        id_columns={
            "userId": np.asarray(
                [f"e{i}" for i in rng.integers(0, n_users + 3, size=n)],
                dtype=object,
            ),
            "itemId": np.asarray(
                [f"e{i}" for i in rng.integers(0, n_items + 2, size=n)],
                dtype=object,
            ),
        },
    )
    engine = GameServingEngine(model)
    batch, _ = engine._prepare(data)
    return engine, batch


def build_serving_score() -> str:
    """Serving engine fused program, total-score bucket (the hot request
    path: per_coordinate=False, include_offsets=True, apply_link=False)."""
    engine, batch = _serving_engine_and_batch()
    return engine._jitted.lower(
        batch, per_coordinate=False, include_offsets=True, apply_link=False
    ).compile().as_text()


def build_serving_per_coordinate() -> str:
    """Serving engine fused program, per-coordinate bucket (the explain/debug
    surface: one score vector per coordinate, links applied)."""
    engine, batch = _serving_engine_and_batch()
    return engine._jitted.lower(
        batch, per_coordinate=True, include_offsets=False, apply_link=True
    ).compile().as_text()


PROGRAM_BUILDERS = {
    "re_update": build_re_update,
    "re_chunk_update": build_re_chunk_update,
    "fe_sparse_update": build_fe_sparse_update,
    "fe_update_2d": build_fe_update_2d,
    "population_update": build_population_update,
    "fused_sweep": build_fused_sweep,
    "serving_score": build_serving_score,
    "serving_per_coordinate": build_serving_per_coordinate,
}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def _setup_env():
    """8 emulated CPU devices + x64, BEFORE the first jax import (same
    platform the tier-1 suites compile on, so records and the persistent XLA
    cache line up)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        )
    if str(REPO_ROOT) not in sys.path:
        sys.path.insert(0, str(REPO_ROOT))
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)
    jax.config.update("jax_enable_x64", True)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # the test suite's out-of-tree cache (tests/conftest.py)
        jax.config.update(
            "jax_compilation_cache_dir", os.path.expanduser("~/.cache/photon_xla")
        )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)


def build_current(only=None) -> tuple:
    """(records, errors): lower every selected family and summarize it.
    A family that fails to build is an audit hole, not a pass."""
    records, errors = {}, []
    for name, builder in PROGRAM_BUILDERS.items():
        if only and name not in only:
            continue
        try:
            records[name] = summarize(builder())
        except Exception as e:  # noqa: BLE001 — report, don't mask, per family
            errors.append((name, f"{type(e).__name__}: {e}"))
    return records, errors


def self_check(current: dict) -> list:
    """Seed each regression class into a copy of the real records and assert
    the diff catches it — proof the gate fires, against today's programs."""
    failures = []
    regs, stale = diff_inventories(current, current)
    if regs or stale:
        failures.append(f"control: fresh-vs-fresh not clean: {regs + stale}")

    donors = [n for n, r in current.items() if r["donated"]]
    if not donors:
        failures.append("no audited program donates buffers — the dropped-"
                        "donation gate has nothing to protect")
    else:
        mutated = copy.deepcopy(current)
        mutated[donors[0]]["donated"] = mutated[donors[0]]["donated"][1:]
        regs, _ = diff_inventories(mutated, current)
        if not any("donation dropped" in r for r in regs):
            failures.append(f"seeded donation drop in {donors[0]} not caught")

    name = sorted(current)[0]
    mutated = copy.deepcopy(current)
    mutated[name]["in_loop_data"] += 1
    regs, _ = diff_inventories(mutated, current)
    if not any("inside solver while-loops" in r for r in regs):
        failures.append(f"seeded in-loop data collective in {name} not caught")

    mutated = copy.deepcopy(current)
    committed = copy.deepcopy(current)
    committed[name]["widest_float"] = "f32"
    mutated[name]["widest_float"] = "f64"
    regs, _ = diff_inventories(mutated, committed)
    if not any("widest float widened" in r for r in regs):
        failures.append(f"seeded f64 leak in {name} not caught")

    mutated = copy.deepcopy(current)
    del mutated[name]
    regs, _ = diff_inventories(mutated, current)
    if not any("missing" in r for r in regs):
        failures.append(f"seeded missing program family {name} not caught")
    return failures


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="program_audit",
        description="compiled-program inventory ratchet (donation, "
                    "collectives, dtypes) over the registered program families",
    )
    p.add_argument("--check", action="store_true",
                   help="diff fresh records against the committed inventory "
                        "(the default action)")
    p.add_argument("--update", action="store_true",
                   help="rewrite the inventory from fresh records and exit 0")
    p.add_argument("--self-check", action="store_true",
                   help="seed a violation of each regression class and prove "
                        "the diff catches it")
    p.add_argument("--inventory", default=str(DEFAULT_INVENTORY),
                   help=f"inventory file (default: {DEFAULT_INVENTORY.name})")
    p.add_argument("--only", action="append", default=[], metavar="NAME",
                   choices=sorted(PROGRAM_BUILDERS),
                   help="audit only this program family (repeatable)")
    args = p.parse_args(argv)

    _setup_env()
    current, errors = build_current(only=set(args.only) or None)
    for name, msg in errors:
        print(f"program_audit: {name}: BUILD FAILED: {msg}", file=sys.stderr)

    if args.update:
        doc = {
            "comment": "compiled-program inventory — regenerate with: "
                       "python tools/program_audit.py --update",
            "programs": current,
        }
        Path(args.inventory).write_text(json.dumps(doc, indent=2) + "\n")
        print(f"program_audit: wrote {args.inventory}: "
              f"{len(current)} program record(s)")
        return 3 if errors else 0

    if args.self_check:
        failures = self_check(current)
        for f in failures:
            print(f"program_audit: self-check FAILED: {f}", file=sys.stderr)
        if not failures:
            print(f"program_audit: self-check OK — all seeded regression "
                  f"classes caught across {len(current)} program(s)")
        return 3 if errors else (1 if failures else 0)

    inv_path = Path(args.inventory)
    if not inv_path.exists():
        print(f"program_audit: no inventory at {inv_path} — generate one "
              f"with --update and commit it", file=sys.stderr)
        return 1
    committed = json.loads(inv_path.read_text())["programs"]
    if args.only:
        committed = {k: v for k, v in committed.items() if k in set(args.only)}
    regressions, stale = diff_inventories(current, committed)
    for r in regressions:
        print(f"program_audit: REGRESSION: {r}")
    for s in stale:
        print(f"program_audit: stale inventory: {s}")
    print(f"program_audit: {len(current)} program(s) audited, "
          f"{len(regressions)} regression(s), {len(stale)} stale entr(y/ies)"
          + (f", {len(errors)} build failure(s)" if errors else ""))
    if stale and not regressions:
        print("program_audit: the programs improved past the committed "
              "inventory — regenerate with --update and commit")
    if errors:
        return 3
    if regressions:
        return 1
    return 2 if stale else 0


if __name__ == "__main__":
    sys.exit(main())
