"""The two tiers of the update-program parity gates.

The random-effect update program and the per-bucket loop it replaces run the
same solve body in two differently fused XLA programs. Whether they agree to
the last bit is a property of the backend, not of the code: where fusion
decides which multiply-adds contract to an FMA (XLA:CPU of JAX 0.9), a solve's
result moves in its last bit, and a float32 L-BFGS that ends at the noise
floor of its objective turns that into another line-search branch and a
coefficient 1e-5 away. On the v5e the two sides differ in 0 of 6,000,000
elements (PERF.md, PR 30).

So a gate asks the backend first (``order_exact``: 985 of 4,096 float32
``a * b + c`` change with fusion on XLA:CPU of JAX 0.9.0) and then holds the
program to what that backend can show:

- order-exact: every array of the full descent bit for bit;
- elsewhere, both of
  1. the FIRST update, from identical inputs and before any score feedback, in
     the gate's own float32: within ``FIRST_UPDATE_ULPS`` float32 ulps of the
     array's largest magnitude;
  2. the full descent with float64 blocks: within ``FLOAT64_DESCENT_TOL`` of
     the array's largest magnitude. In float64 the solves converge (tolerance
     1e-9) and stop amplifying, so reassociation stays near the last bit
     while a wrong trip count, bucket order or padding row does not.

Readings (XLA:CPU, JAX 0.9.0, this repo's eight parity cases and the mesh
gate, 2026-10-03; differences over the reference array's largest magnitude):
first float32 update at most 0.50 float32 ulps (simplevar-uniform-raw
variances), so ``FIRST_UPDATE_ULPS`` = 8x that; float64 descent at most 0.9
float64 ulps on one device and 2.3e-10 on the 8-device mesh (one lane of 12
took another line-search branch in pass 3), so ``FLOAT64_DESCENT_TOL`` = 43x
that. The float32 descent itself ends up to 2.2e-4 apart (scores) and 8e-3
elementwise, which no tolerance could tell from a 1e-3 fault; that is why the
descent is held in float64.
"""

import jax
import jax.numpy as jnp
import numpy as np

FIRST_UPDATE_ULPS = 4
FLOAT64_DESCENT_TOL = 1e-8
N_ITERATIONS = 3


def order_exact(fused=None, apart=None) -> bool:
    """The probe: does fusing a multiply into an add change its bits here?
    ``a * b + c`` as one jitted fusion (free to contract to an FMA) against
    the same two operations as two programs (which cannot). This is the cause
    itself and not a sample of it: a single update of one tiny bucket (16
    lanes of 8 rows, the featureful configuration) came out bit-equal on the
    XLA:CPU that fails five of the eight cases (there the first update moves
    at most 3 lanes of 12, and none in five cases), and a probe that reads
    "exact" wrongly turns every gate red. ``fused``/``apart`` take
    ``(a, b, c)``; a test hands in stubs."""
    rng = np.random.default_rng(0)
    a, b, c = (jnp.asarray(rng.normal(size=4096), jnp.float32) for _ in range(3))
    if fused is None:
        fused = jax.jit(lambda a, b, c: a * b + c)
    if apart is None:
        multiply, add = jax.jit(jnp.multiply), jax.jit(jnp.add)

        def apart(a, b, c):
            return add(multiply(a, b), c)

    return bool(np.array_equal(np.asarray(fused(a, b, c)), np.asarray(apart(a, b, c))))


def _assert_within(new: dict, old: dict, bound: float, what: str) -> None:
    assert set(new) == set(old)
    for key in sorted(old):
        assert new[key].dtype == old[key].dtype, key
        assert new[key].shape == old[key].shape, key
        a = np.asarray(new[key], np.float64)
        b = np.asarray(old[key], np.float64)
        scale = np.abs(b).max()
        gap = np.abs(a - b).max()
        assert gap <= bound * scale, (
            f"{what}: {key} differs by {gap:.3e} = {gap / scale:.3e} of its "
            f"largest magnitude (limit {bound:.3e})"
        )


def assert_program_matches_loop(descend, exact: bool) -> str:
    """``descend(use_program, n_iterations, dtype)`` is a descent's state as
    host arrays by name, random-effect blocks at ``dtype``. Returns the tier
    that was held."""
    if exact:
        # a bound of zero is equality, element for element
        _assert_within(
            descend(True, N_ITERATIONS, np.float32),
            descend(False, N_ITERATIONS, np.float32),
            0.0,
            "float32 descent, bit for bit",
        )
        return "bitwise"
    _assert_within(
        descend(True, 1, np.float32),
        descend(False, 1, np.float32),
        FIRST_UPDATE_ULPS * float(np.finfo(np.float32).eps),
        "first float32 update",
    )
    _assert_within(
        descend(True, N_ITERATIONS, np.float64),
        descend(False, N_ITERATIONS, np.float64),
        FLOAT64_DESCENT_TOL,
        "float64 descent",
    )
    return "tolerance"
