"""On-chip microbenchmark: fused Pallas GLM kernels vs the stock XLA lowering.

Times the three fused kernels (ops/pallas_glm.py) against the equivalent
two/three-matmul XLA programs at the flagship bench shape and at larger
HBM-bound shapes. The kernels exist to cut HBM reads of X (the stock
value+gradient lowering reads X twice, the fused kernel once; TRON's HVP
three times vs once), so the expected win grows with rows x cols.

This is the keep-or-retire evidence (ROADMAP D8): either the kernels win
on-chip and become the default, or this prints the negative result that
retires them. On CPU the kernels run in interpret mode (slow) — timing there
is meaningless, so the script requires an accelerator unless --interpret is
passed for a smoke run.

Usage: python benchmarks/pallas_microbench.py [--interpret] [--repeats 20]
Prints one JSON line per (kernel, shape).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _time(fn, repeats):
    import jax

    jax.block_until_ready(fn())  # compile + warm, fully drained before t0
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats


def float64_reference(X64, y, off, w, coef, v, shifts, factors):
    """float64 host sums of the three fused kernels (logistic loss) — THE
    reference both this microbench and chip_smoke.py's kernel leg hold the
    kernels against: {"value_grad": (loss sum, X^T(w dz), sum w dz), "hvp":
    (X^T u, sum u), "hessian": (A^T diag(w dzz) A,)} with weight-0 rows
    excluded and A = (X - shifts) * factors."""
    import numpy as np

    z = X64 @ coef + off
    ez = np.exp(-np.abs(z))
    loss = np.log1p(ez) + np.maximum(z, 0.0) - y * z
    dz = np.where(z >= 0, 1.0 / (1.0 + ez), ez / (1.0 + ez)) - y
    dzz = 1.0 / (2.0 + ez + 1.0 / ez)
    live = w != 0
    wdz = np.where(live, w * dz, 0.0)
    u = np.where(live, w * dzz * (X64 @ v), 0.0)
    A = np.where(live[:, None], (X64 - shifts[None, :]) * factors[None, :], 0.0)
    return {
        "value_grad": (np.sum(np.where(live, w * loss, 0.0)), X64.T @ wdz, np.sum(wdz)),
        "hvp": (X64.T @ u, np.sum(u)),
        "hessian": (A.T @ (A * np.where(live, w * dzz, 0.0)[:, None]),),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--interpret", action="store_true",
                    help="CPU smoke run (interpret-mode kernels; no timing value)")
    ap.add_argument("--repeats", type=int, default=20)
    ap.add_argument("--shapes", default="100000x64,100000x512,1000000x64",
                    help="comma-separated NxD shapes")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from photon_ml_tpu.function.losses import loss_for_task
    from photon_ml_tpu.ops import pallas_glm
    from photon_ml_tpu.types import TaskType

    backend = jax.default_backend()
    if backend == "cpu" and not args.interpret:
        print(json.dumps({"error": "no accelerator; rerun with --interpret for a smoke run"}))
        return 1
    interpret = args.interpret

    loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
    dzz = loss.dzz

    shapes = []
    for tok in args.shapes.split(","):
        n, d = tok.lower().split("x")
        shapes.append((int(n), int(d)))
    if interpret:
        shapes = [(2048, 64)]  # interpret mode is ~1000x slower; smoke only
        args.repeats = 2

    rng = np.random.default_rng(0)
    results = []
    for n, d in shapes:
        if d > pallas_glm.MAX_FUSED_DIM["float32"]:
            continue
        X = jnp.asarray(rng.normal(size=(n, d)), dtype=jnp.float32)
        y = jnp.asarray((rng.random(n) < 0.5), dtype=jnp.float32)
        off = jnp.zeros(n, dtype=jnp.float32)
        w = jnp.ones(n, dtype=jnp.float32)
        coef = jnp.asarray(rng.normal(size=d) * 0.1, dtype=jnp.float32)
        v = jnp.asarray(rng.normal(size=d) * 0.1, dtype=jnp.float32)
        zero = jnp.zeros((), dtype=jnp.float32)

        @jax.jit
        def stock_value_grad(X=X, y=y, off=off, w=w, coef=coef):
            z = X @ coef + off
            l, dz = loss.loss_and_dz(z, y)
            wdz = jnp.where(w != 0, w * dz, 0.0)
            return jnp.sum(jnp.where(w != 0, w * l, 0.0)), X.T @ wdz, jnp.sum(wdz)

        def fused_value_grad():
            return pallas_glm.fused_loss_grad_sums(
                X, y, off, w, coef, zero,
                loss_and_dz=loss.loss_and_dz, interpret=interpret,
            )

        @jax.jit
        def stock_hvp(X=X, y=y, off=off, w=w, coef=coef, v=v):
            z = X @ coef + off
            u = jnp.where(w != 0, w * dzz(z, y) * (X @ v), 0.0)
            return X.T @ u, jnp.sum(u)

        def fused_hvp():
            return pallas_glm.fused_hessian_vector_sums(
                X, y, off, w, coef, zero, v, zero,
                dzz=dzz, interpret=interpret,
            )

        # float64 host references: stock-vs-fused allclose at tight rtol
        # would conflate precision-mode differences with kernel bugs. On a
        # v5e the stock matrix-VECTOR lowering sits 1e-7..2e-6 off this
        # reference and the kernels' f32-precision MXU contractions
        # 2e-7..5e-5 (PR 21, chip_smoke.py's kernel leg) — hence the floor.
        host = [np.asarray(a, dtype=np.float64) for a in jax.device_get((X, y, off, w, coef, v))]  # jaxlint: disable=HS001 f64 host reference build, outside the timed region
        ref = float64_reference(*host, np.zeros(d), np.ones(d))
        ref_vg, ref_hvp = ref["value_grad"], ref["hvp"]

        def assert_no_less_accurate(name, ref, a_stock, a_fused):
            for r, x_s, x_f in zip(
                ref,
                jax.tree_util.tree_leaves(a_stock),
                jax.tree_util.tree_leaves(a_fused),
            ):
                scale = np.maximum(np.abs(r), 1e-6)
                err_s = float(np.max(np.abs(np.asarray(x_s, np.float64) - r) / scale))
                err_f = float(np.max(np.abs(np.asarray(x_f, np.float64) - r) / scale))
                # floor: sequential per-block accumulation legitimately loses
                # ~sqrt(n_blocks) f32 ulps vs XLA's tree reduction — a few
                # 1e-5 relative at these shapes, far below fitting tolerances
                assert err_f <= max(2.0 * err_s, 5e-4), (
                    f"{name}: fused rel err {err_f:.2e} vs stock {err_s:.2e}"
                )

        pairs = [
            ("value_grad", stock_value_grad, fused_value_grad, ref_vg),
            ("hvp", stock_hvp, fused_hvp, ref_hvp),
        ]
        for name, stock, fused, ref in pairs:
            # numerical parity first: the speed question is moot if wrong
            assert_no_less_accurate(name, ref, stock(), fused())
            t_stock = _time(stock, args.repeats)
            t_fused = _time(fused, args.repeats)
            rec = {
                "kernel": name,
                "shape": f"{n}x{d}",
                "backend": backend,
                "interpret": interpret,
                "stock_ms": round(t_stock * 1e3, 4),
                "fused_ms": round(t_fused * 1e3, 4),
                "speedup": round(t_stock / t_fused, 4),
            }
            results.append(rec)
            print(json.dumps(rec))
    if not results:
        print(json.dumps({"error": "no eligible shapes"}))
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
