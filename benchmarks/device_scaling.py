"""Device-count scaling curve for the sharded GAME coordinate-descent pass.

Runs the flagship GLMix workload at 1/2/4/8 simulated devices (virtual CPU
mesh via ``--xla_force_host_platform_device_count``) and records samples/sec
per device count. This is the analog of the reference tuning its
treeAggregate depth (ValueAndGradientAggregator.scala:240-255): what is being
checked is the COLLECTIVE LAYOUT — per-device partial gradients psum'd over
the mesh, entity-sharded bucket solves with zero cross-device traffic inside
the solve. On one physical core the virtual devices add partition overhead
rather than real parallelism, so the curve's job is to catch *pathological*
behavior (a collective that serializes the pass or replicates work
device-count times), not to demonstrate speedup; on real multi-chip ICI the
same program scales because the partitions run concurrently.

Each device count runs in its own subprocess (device count is fixed at
backend init). Usage:

  python benchmarks/device_scaling.py [--devices 1,2,4,8] [--samples 200000]
      [--tiny] [--output benchmarks/device_scaling.json]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child(n_samples: int, n_users: int, n_items: int, passes: int) -> float:
    """Measure samples/sec of the sharded GAME pass on the ambient mesh.

    The workload is bench.py's ``_build_workload`` — the SAME program as the
    flagship bench, just parameterized by shape, so this curve is scaling
    evidence for the measured program, not for a drifting copy of it."""
    import time

    import jax
    import jax.numpy as jnp

    import bench
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

    fe_X, y, ds_u, ds_i = bench._build_workload(
        jnp.float32, n_samples=n_samples, n_users=n_users, n_items=n_items
    )

    mesh = make_mesh(len(jax.devices()))
    data = build_sharded_game_data(fe_X, y, [ds_u, ds_i], mesh, dtype=jnp.float32)

    def cfg(iters):
        return GLMOptimizationConfiguration(
            optimizer_config=OptimizerConfig(max_iterations=iters),
            regularization_context=RegularizationContext(RegularizationType.L2),
            regularization_weight=1.0,
        )

    step = make_jitted_game_step(
        data, TaskType.LOGISTIC_REGRESSION, cfg(50), [cfg(30), cfg(30)], mesh
    )
    params = init_game_params(data, mesh)
    params, diag = step(params)  # compile + warm-up
    jax.block_until_ready(params)
    t0 = time.perf_counter()
    for _ in range(passes):
        params, diag = step(params)
    jax.block_until_ready(params)
    elapsed = time.perf_counter() - t0
    assert float(diag["fe_value"]) > 0.0
    return n_samples * passes / elapsed


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", default="1,2,4,8")
    ap.add_argument("--samples", type=int, default=200_000)
    ap.add_argument("--users", type=int, default=4_000)
    ap.add_argument("--items", type=int, default=1_000)
    ap.add_argument("--passes", type=int, default=3)
    ap.add_argument("--tiny", action="store_true", help="CI shape (fast compile)")
    ap.add_argument("--output", default=None)
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.tiny:
        args.samples, args.users, args.items, args.passes = 8_192, 64, 16, 2

    if args.child:
        tp = _child(args.samples, args.users, args.items, args.passes)
        print(json.dumps({"samples_per_sec": tp}))
        return 0

    results = {}
    for n_dev in [int(x) for x in args.devices.split(",")]:
        import re

        # strip ANY ambient device-count flag: XLA takes the LAST duplicate,
        # so an ambient value appended after ours would silently win and run
        # every child at the same device count (a flat fake curve)
        ambient = re.sub(
            r"--xla_force_host_platform_device_count=\d+",
            "",
            os.environ.get("XLA_FLAGS", ""),
        ).strip()
        env = dict(os.environ)
        env.update(
            {
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": (
                    f"{ambient} --xla_force_host_platform_device_count={n_dev}"
                ).strip(),
            }
        )
        cmd = [
            sys.executable, os.path.abspath(__file__), "--child",
            "--samples", str(args.samples), "--users", str(args.users),
            "--items", str(args.items), "--passes", str(args.passes),
        ]
        proc = subprocess.run(
            cmd, capture_output=True, text=True, env=env, cwd=REPO, timeout=1800
        )
        if proc.returncode != 0:
            tail = (proc.stderr or "").strip().splitlines()[-1:]
            raise RuntimeError(f"{n_dev}-device child failed: {tail}")
        tp = json.loads(proc.stdout.strip().splitlines()[-1])["samples_per_sec"]
        results[n_dev] = tp
        print(f"{n_dev} devices: {tp:,.0f} samples/sec", file=sys.stderr)

    base = results[min(results)]
    record = {
        "metric": "glmix_cd_pass_samples_per_sec_by_device_count",
        "shape": {
            "samples": args.samples, "users": args.users, "items": args.items
        },
        "results": {str(k): round(v, 2) for k, v in sorted(results.items())},
        "relative": {str(k): round(v / base, 4) for k, v in sorted(results.items())},
        "note": "virtual CPU devices on one host: checks collective layout "
        "overhead, not real parallel speedup",
    }
    print(json.dumps(record))
    if args.output:
        with open(args.output, "w") as f:
            json.dump(record, f, indent=2)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, REPO)
    sys.exit(main())
