"""Real-size compiles for the chip that is described, not attached: the
fixed-effect solve each cell's unit drives (the fixed coordinate of
glmix-ml20m's), as the program's own solver cache
builds it, at the configuration's published size against a v5e:2x2 topology.
What the TPU compiler refuses here it would refuse on the chip. The
random-effect update program needs placed datasets to build and is not covered
(PERF.md, open questions). Nothing runs: a compile that passes is not a chip
run.
"""

import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HBM_BYTES = 16e9
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    CONFIGS = [c["name"] for c in json.load(_f)["configs"]]  # every configuration, also later PRs'


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever stops the description skips the file
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("config", CONFIGS)
def test_fixed_effect_solve_compiles_at_real_size(config, one_chip):
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.data.dataset import LabeledData
    from photon_ml_tpu.data.matrix import DenseDesignMatrix
    from photon_ml_tpu.normalization import NO_NORMALIZATION
    from photon_ml_tpu.optimization.common import OptimizerConfig
    from photon_ml_tpu.optimization.solver_cache import glm_solver
    from photon_ml_tpu.types import OptimizerType, TaskType, VarianceComputationType

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        path = next(c["file"] for c in json.load(f)["configs"] if c["name"] == config)
    with open(os.path.join(ROOT, path)) as f:
        cfg = json.load(f)
    fixed = next(c for c in cfg["coordinates"] if c["kind"] == "fixed")
    n, d = int(cfg["n_train_rows"]), int(cfg["fixed_effect_dim"])

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    data = LabeledData(
        X=DenseDesignMatrix(values=shape(n, d)),
        labels=shape(n), offsets=shape(n), weights=shape(n),
    )
    solve = glm_solver(
        TaskType[cfg["task"]],
        OptimizerConfig(
            optimizer_type=OptimizerType[fixed["optimizer"]],
            max_iterations=int(fixed["max_iterations"]),
        ),
        False, False, False, VarianceComputationType.NONE,
    )
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = solve.lower(
            data, shape(d), shape(), shape(), shape(0), shape(0), NO_NORMALIZATION
        ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes
    assert mem.argument_size_in_bytes >= n * d * 4
    assert total < HBM_BYTES, f"{config}: {total / 1e9:.1f} GB does not fit one v5e chip"


def test_published_glmix_size_is_refused_today(one_chip):
    """PERF.md open question 1: at MovieLens-20M's 20,000,263 rows XLA:TPU
    refuses the program's random-effect scoring program (its [N, 8]
    temporaries are padded to 128 lanes), which is why the cell runs 6M rows.
    When this test starts to fail the program has been repaired: restore the
    published size in configs/glmix-ml20m.json."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.models.game import random_effect_view_score

    with open(os.path.join(ROOT, "chipbench", "configs", "glmix-ml20m.json")) as f:
        cfg = json.load(f)
    n = int(cfg["published"]["n_train_rows"])
    users = int(cfg["published"]["entities"]["userId"])
    k = int(cfg["random_effect_dim"])

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    jax.config.update("jax_enable_compilation_cache", False)
    try:
        with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
            random_effect_view_score.lower(
                shape((users, k)), shape((n,), jnp.int32), shape((n, k), jnp.int32), shape((n, k))
            ).compile()
        run_size = int(cfg["n_train_rows"])
        compiled = random_effect_view_score.lower(
            shape((int(cfg["entities"]["userId"]), k)), shape((run_size,), jnp.int32),
            shape((run_size, k), jnp.int32), shape((run_size, k)),
        ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    assert compiled.memory_analysis().temp_size_in_bytes < HBM_BYTES
