"""Multi-host execution: JAX distributed runtime + host-local data ingestion.

The reference scales across machines through Spark (driver + executors over the
network, SURVEY §2.8). The TPU-native equivalent is JAX's multi-controller
runtime: every host runs the SAME program, `jax.distributed.initialize` wires
the processes together, and a mesh built over `jax.devices()` (which is GLOBAL
after initialization) spans all hosts — collectives ride ICI within a slice and
DCN across slices, placed by GSPMD exactly as in the single-host case. None of
the solver/placement code changes: a mesh is a mesh.

What DOES change on multi-host is ingestion: each host reads only its share of
the input (e.g. its subset of date-partitioned Avro part files), and
`host_local_to_global` assembles the global sharded array from per-process
local shards without any host ever materializing the full dataset — the analog
of executors reading their HDFS splits.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np

from photon_ml_tpu.parallel.mesh import batch_sharding
from photon_ml_tpu.resilience import Retry, faultpoint, register_fault_point

FP_DISTRIBUTED_INIT = register_fault_point("distributed.init")


def initialize_multi_host(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    auto: bool = False,
    initialization_timeout: Optional[float] = None,
    retries: int = 0,
    retry_base_delay: float = 1.0,
) -> dict:
    """Join the JAX distributed runtime.

    MUST run before any other JAX call (backend-initializing operations make
    ``jax.distributed.initialize`` a runtime error / silently host-local).

    Explicit arguments cover bare-metal setups; ``auto=True`` calls
    ``jax.distributed.initialize()`` with no arguments for orchestrated
    environments (TPU pod / GKE metadata autodetection). With neither, this is
    a no-op reporter for single-process runs. Returns {"process_id",
    "num_processes", "local_devices", "global_devices"} for logging.

    Failure model (docs/ARCHITECTURE.md "Failure model & recovery"): a slow
    coordinator bounds each attempt via ``initialization_timeout`` (seconds,
    forwarded to ``jax.distributed.initialize``), and a failed attempt (RuntimeError/OSError: coordinator not
    yet listening, transient DNS/socket errors) retries up to ``retries``
    times with exponential backoff + jitter starting at ``retry_base_delay``
    seconds — a flaky startup ordering is an incident, not a crash. The
    default of 0 retries preserves fail-fast for interactive use.
    """
    if not jax.distributed.is_initialized() and (
        auto or coordinator_address is not None or num_processes is not None
    ):
        kwargs = {}
        if initialization_timeout is not None:
            kwargs["initialization_timeout"] = int(initialization_timeout)

        def _attempt():
            faultpoint(FP_DISTRIBUTED_INIT)
            if auto and coordinator_address is None and num_processes is None:
                jax.distributed.initialize(**kwargs)
            else:
                jax.distributed.initialize(
                    coordinator_address=coordinator_address,
                    num_processes=num_processes,
                    process_id=process_id,
                    **kwargs,
                )

        Retry(
            max_attempts=max(0, int(retries)) + 1,
            base_delay=retry_base_delay,
            max_delay=30.0,
            retry_on=(RuntimeError, OSError),
        ).call(_attempt, description="jax.distributed.initialize")
    return {
        "process_id": jax.process_index(),
        "num_processes": jax.process_count(),
        "local_devices": len(jax.local_devices()),
        "global_devices": len(jax.devices()),
    }


def host_local_to_global(
    local_arr: np.ndarray, mesh, global_rows: Optional[int] = None
):
    """Assemble a GLOBAL batch-sharded array from this process's local rows.

    Every process passes its own row block (concatenated in process order);
    the result is one global jax.Array sharded over the mesh's first axis.
    Each host only ever holds its own block — the multi-host replacement for
    ``device_put`` of a full array.

    Multi-process calls MUST pass ``global_rows`` (the total row count across
    processes — local shapes differ, so it cannot be inferred consistently),
    and it must divide evenly over the mesh's first axis: pad per-process
    blocks with weight-0 rows first (``process_slice`` + host-side padding).
    Single-process meshes degenerate to a plain sharded device_put.
    """
    local_arr = np.asarray(local_arr)
    sharding = batch_sharding(mesh, ndim=local_arr.ndim)
    if jax.process_count() == 1:
        return jax.device_put(local_arr, sharding)
    if global_rows is None:
        raise ValueError(
            "multi-process host_local_to_global requires global_rows (the "
            "total row count over all processes)"
        )
    axis0 = mesh.devices.shape[0]
    if global_rows % axis0:
        raise ValueError(
            f"global_rows={global_rows} must divide over the mesh's first "
            f"axis ({axis0}); pad per-process blocks with inert rows first"
        )
    global_shape = (global_rows,) + local_arr.shape[1:]
    return jax.make_array_from_process_local_data(
        sharding, local_arr, global_shape=global_shape
    )


def split_range(p: int, k: int, n_total: int) -> slice:
    """Contiguous block p of n_total rows split as evenly as possible over k."""
    base, extra = divmod(n_total, k)
    start = p * base + min(p, extra)
    return slice(start, start + base + (1 if p < extra else 0))


def process_slice(n_total: int) -> slice:
    """Contiguous row range this process should read/ingest (the analog of
    Spark executors claiming HDFS splits)."""
    return split_range(jax.process_index(), jax.process_count(), n_total)
