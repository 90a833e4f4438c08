"""Shared runtime configuration for the CLI drivers."""

from __future__ import annotations

import os

# photon_ml_tpu/cli/runtime.py -> the directory that holds the package
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def configure_compilation_cache() -> str:
    """THE compile-cache policy of every entry point that runs on a device
    (the four CLI drivers, bench.py, chip_smoke.py); returns the directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and no
    code sets a directory. Otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache`` (git-ignored): a directory that moves between
    runs never hits, so never the home directory, a temp name, a pid or a
    time. Every program is cached, however quick its compile: a threshold
    makes "did the second run add entries?" depend on timing jitter."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


def add_ingest_arguments(parser) -> None:
    """The shared --ingest-* runtime flag of the training and scoring drivers
    (one definition so the drivers cannot drift)."""
    parser.add_argument(
        "--ingest-workers", type=int, default=None,
        help="Avro ingest decode threads: container framing stays sequential "
             "(deterministic row order) while inflate + native block decode + "
             "columnar extraction fan out over this many workers with a "
             "bounded in-flight window — results are bitwise identical "
             "across worker counts. 1 = the sequential legacy path; default "
             "auto = min(cores, 8). See docs/PERFORMANCE.md 'Ingest & "
             "time-to-first-update'",
    )


def add_serving_arguments(parser) -> None:
    """The shared --serving-* knob block (serving driver; any future online
    endpoint reuses the same contract — docs/ARCHITECTURE.md 'Serving
    front-end & SLOs')."""
    parser.add_argument(
        "--serving-max-batch", type=int, default=4096,
        help="Micro-batching cap: coalesced samples per engine dispatch "
             "(align with the engine bucket you want to saturate)",
    )
    parser.add_argument(
        "--serving-max-wait-ms", type=float, default=2.0,
        help="Longest the oldest queued request waits for coalescing company "
             "before dispatch (the latency cost of batching)",
    )
    parser.add_argument(
        "--serving-queue-depth", type=int, default=256,
        help="Bounded request queue; submissions beyond it shed with an "
             "explicit Overloaded instead of growing a latency tail",
    )
    parser.add_argument(
        "--serving-deadline-ms", type=float, default=None,
        help="Per-request deadline: requests that cannot meet it are shed "
             "BEFORE dispatch with an explicit DeadlineExceeded (default: "
             "no deadline)",
    )
    parser.add_argument(
        "--serving-request-batch", type=int, default=512,
        help="Replay chunk size: input rows per request submitted through "
             "the frontend",
    )
    parser.add_argument(
        "--hot-swap-watch", action="store_true",
        help="Poll the checkpoint root for new generations while serving and "
             "hot-swap to them with zero downtime (integrity-verified, "
             "warmed before the flip, automatic rollback)",
    )
    parser.add_argument(
        "--hot-swap-poll-seconds", type=float, default=2.0,
        help="Generation watcher poll interval for --hot-swap-watch",
    )
    parser.add_argument(
        "--fleet-replicas", type=int, default=0,
        help="Serve through a ReplicaSet of this many replicas behind the "
             "ModelRouter instead of one frontend (serving/fleet.py): "
             "round-robin routing with overload failover, and hot-swap "
             "becomes replica-at-a-time with a canary gate (0 = single-"
             "frontend mode, the default)",
    )
    parser.add_argument(
        "--fleet-http-port", type=int, default=None,
        help="With --fleet-replicas: also expose the fleet over HTTP on this "
             "port while replaying (serving/transport.py; 0 = an ephemeral "
             "port, reported in the stats JSON as http_endpoint)",
    )


def add_distributed_arguments(parser, purpose: str) -> None:
    """The shared --distributed-* flag contract of the training and scoring
    drivers (one definition so the two cannot drift)."""
    parser.add_argument(
        "--distributed-coordinator", default=None,
        help=f"host:port of process 0 (or 'auto') for {purpose}. CPU-only "
             "surface for now: a chip belongs to one process at a time, so "
             "several processes on one host run on the CPU backend; one "
             "process drives all chips of a host through --compute-backend "
             "mesh",
    )
    parser.add_argument("--distributed-num-processes", type=int, default=None)
    parser.add_argument("--distributed-process-id", type=int, default=None)
    parser.add_argument(
        "--distributed-init-timeout", type=float, default=None,
        help="Seconds each jax.distributed.initialize attempt may wait for "
             "the coordinator (default: jax's own, 300s). See "
             "docs/ARCHITECTURE.md 'Failure model & recovery'",
    )
    parser.add_argument(
        "--distributed-init-retries", type=int, default=2,
        help="Retries (exponential backoff + jitter) when joining the "
             "distributed runtime fails — a coordinator that is still "
             "starting is an incident, not a crash. 0 = fail fast",
    )


def prepare_output_root(root: str, override: bool, rank: int, nproc: int) -> None:
    """Single-writer output-root preparation shared by the CLI drivers.

    Process 0 owns the override/exists decision. Multi-process runs exchange
    a success flag through the distributed runtime (the collective doubles as
    the ordering barrier before any peer's first write — no marker files,
    which would go stale across runs), so a rank-0 failure fails EVERY rank
    promptly instead of leaving peers blocked until the peer-loss timeout."""
    import shutil

    failure = None
    if rank == 0:
        try:
            if os.path.exists(root):
                if override:
                    shutil.rmtree(root)
                elif os.listdir(root):
                    raise FileExistsError(
                        f"Output directory {root!r} exists; "
                        f"pass --override-output-directory"
                    )
            os.makedirs(root, exist_ok=True)
        except Exception as e:  # report through the collective before raising
            failure = e
    if nproc > 1:
        import numpy as np
        from jax.experimental import multihost_utils

        flags = multihost_utils.process_allgather(
            np.asarray([0 if (rank != 0 or failure is None) else 1])
        )
        if int(np.asarray(flags).sum()) > 0:
            if failure is not None:
                raise failure
            raise RuntimeError(
                "process 0 failed to prepare the output root "
                "(see its error for the cause)"
            )
        os.makedirs(root, exist_ok=True)  # after the barrier: root is final
    elif failure is not None:
        raise failure


def initialize_distributed_from_args(args) -> tuple[int, int]:
    """Validate the --distributed-* flags and join the JAX distributed runtime.

    MUST run before every other JAX touch (a later ``jax.distributed
    .initialize`` either errors or silently leaves the mesh host-local).
    Returns (process_id, num_processes) — (0, 1) for single-process runs."""
    coordinator = getattr(args, "distributed_coordinator", None)
    if coordinator is None and (
        getattr(args, "distributed_num_processes", None) is not None
        or getattr(args, "distributed_process_id", None) is not None
    ):
        raise ValueError(
            "--distributed-num-processes/--distributed-process-id require "
            "--distributed-coordinator (or --distributed-coordinator=auto)"
        )
    if coordinator is None:
        return 0, 1
    from photon_ml_tpu.parallel import initialize_multi_host

    world = initialize_multi_host(
        coordinator_address=None if coordinator == "auto" else coordinator,
        num_processes=getattr(args, "distributed_num_processes", None),
        process_id=getattr(args, "distributed_process_id", None),
        auto=coordinator == "auto",
        initialization_timeout=getattr(args, "distributed_init_timeout", None),
        retries=getattr(args, "distributed_init_retries", 2) or 0,
    )
    return world["process_id"], world["num_processes"]


def arm_fault_plan_from_args(args) -> None:
    """Arm the deterministic fault-injection plan (resilience/faultpoints.py)
    from --fault-plan; without the flag the PHOTON_FAULT_PLAN env var still
    applies (lazily, at the first fault point)."""
    spec = getattr(args, "fault_plan", None)
    if spec:
        from photon_ml_tpu.resilience import arm

        arm(spec)
