"""Test harness: force an 8-device CPU platform + float64.

Mirrors the reference's test strategy (SURVEY.md §4): the reference exercises
"distributed" behavior on a multi-core local[*] Spark; we exercise sharded jit /
shard_map code on a simulated 8-device CPU mesh via
--xla_force_host_platform_device_count. float64 gives numerical parity headroom for
optimizer convergence assertions (TPU production runs use f32/bf16).
"""

import os

# Force CPU: unit tests run on the simulated 8-device CPU platform whatever the
# ambient environment offers (on the chip machine JAX defaults to the TPU). jax
# may already be imported by a pytest plugin before this conftest, so set it
# through jax.config (effective until backends initialize) as well as the
# environment.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

# Persistent XLA compilation cache: the suite is compile-dominated (hundreds of
# lax.while_loop optimizer programs), and programs are identical across runs —
# the second and later suite runs skip nearly all compiles. Safe to share: the
# cache key includes program, flags, and compiler version. Set through the
# variable JAX itself reads, BEFORE jax is imported: in-process driver runs
# (cli/runtime.configure_compilation_cache) and every child process the tests
# spawn then keep to the same out-of-tree directory instead of growing
# <checkout>/.jax_cache (the chip tool copies the tree; 84 tests write ~50 MB).
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR", os.path.expanduser("~/.cache/photon_xla")
)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_x64", True)
# for a jax imported before the variable was set above
jax.config.update("jax_compilation_cache_dir", os.environ["JAX_COMPILATION_CACHE_DIR"])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture()
def rng(request):
    # Function-scoped and seeded per test: a session-scoped generator makes
    # every test's data depend on how many draws ran before it, so tests pass
    # or fail depending on execution order. Stable per-test seeding makes each
    # test reproducible in isolation and in any suite ordering.
    import zlib

    seed = zlib.crc32(request.node.nodeid.encode()) ^ 271828
    return np.random.default_rng(seed)


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 simulated devices, got {len(devs)}"
    return devs[:8]


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "chaos: crash-at-every-fault-point recovery sweeps (tier-1 adjacent; "
        "also run standalone via `pytest -m chaos`)",
    )
    config.addinivalue_line("markers", "slow: excluded from the tier-1 run")


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_programs_between_modules():
    """Free every compiled program when a test module is done.

    On this installation (JAX 0.9.0, XLA:CPU, 8 emulated devices) the suite
    otherwise dies of a segmentation fault inside an XLA:CPU compile once a few
    hundred tests' executables have accumulated in one process — at the seed
    always at test 289 of ~1040, with a cold or a warm persistent cache, while
    every module passes on its own. Modules share
    no compiled state by design, so dropping it costs only re-tracing."""
    yield
    jax.clear_caches()
