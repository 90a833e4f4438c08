"""Runtime complement to jaxlint: enforce sync/retrace discipline in regions.

jaxlint catches hazards statically; this module turns the two properties PR 1
only *documented* into assertions tests and benchmarks can enforce:

- **no retraces**: the process-wide trace counter that the recorder's
  ``jax.monitoring`` listener keeps (``util/timed.trace_events``), fed by the
  ``/jax/core/compile/jaxpr_trace_duration`` event, which fires on every
  jaxpr trace (including nested sub-traces) and never on a jit cache hit —
  so "zero events in the guarded region" is exactly "the compile cache held".
  It counts traces, not XLA compiles: a persistent-compilation-cache hit
  still traces, and still counts, which is what a steady-state gate wants.
- **no implicit device->host transfers**: ``jax.transfer_guard_device_to_host
  ("disallow")`` scoped to the region. Explicit ``jax.device_get`` stays
  allowed — the point is to force boundary transfers to be *named*, exactly
  jaxlint's suppression policy at runtime. Host->device stays permitted by
  default because dispatching numpy request buffers into a jitted program is
  the normal serving entry path.

  Backend caveat: the transfer guard is authoritative on real accelerators
  (TPU/GPU), where any device->host read is a real transfer. On the CPU
  backend, device buffers are host memory and numpy reads them zero-copy
  through the buffer protocol, below the guard — so d2h enforcement there is
  best-effort. Implicit HOST->DEVICE transfers (np operands mixed into
  device math, scalar fills) ARE guarded on every backend, which is what the
  guard-wiring tests pin. The retrace guard is authoritative everywhere.

Usage::

    from photon_ml_tpu.analysis.runtime_guard import sync_discipline

    engine.score(warmup_request)                    # compiles outside the guard
    with sync_discipline() as region:
        for req in requests:
            engine.score(req)
    # leaving the region raises RetraceError if anything retraced;
    # region.traces is also readable mid-region for reporting.

The trace counter is process-global (jax.monitoring has no per-thread
listeners): guard one region at a time, and keep unrelated background
compilation out of guarded regions.
"""

from __future__ import annotations

import contextlib
import dataclasses

import jax

from photon_ml_tpu.util.timed import trace_events


class RetraceError(AssertionError):
    """A guarded region traced when it promised not to."""


@dataclasses.dataclass
class GuardedRegion:
    """Live view of a guard region; ``traces`` is current at any point inside."""

    _start: int = 0
    allow_retraces: int = 0

    @property
    def traces(self) -> int:
        return trace_events() - self._start


@contextlib.contextmanager
def no_retrace(allow_retraces: int = 0, what: str = "guarded region"):
    """Fail if more than ``allow_retraces`` jaxpr traces happen inside.

    Warmup belongs OUTSIDE the region: compile first, then guard the steady
    state. Raises RetraceError on exit; raises nothing if the body itself
    raised (the original error is more informative than the trace count)."""
    region = GuardedRegion(_start=trace_events(), allow_retraces=allow_retraces)
    try:
        yield region
    except BaseException:
        raise
    else:
        if region.traces > allow_retraces:
            raise RetraceError(
                f"{what}: {region.traces} jaxpr trace(s) occurred "
                f"(allowed {allow_retraces}). A retrace after warmup means a "
                "jit cache miss: check for shape/dtype drift, unhashed static "
                "args, or a fresh wrapper per call. jaxlint rule RT001 finds "
                "the static culprits."
            )


@contextlib.contextmanager
def no_implicit_transfers(device_to_host: str = "disallow",
                          host_to_device: str | None = None):
    """Scope jax transfer guards: implicit device->host transfers (np.asarray
    on a device array, float(), .item()) raise inside; explicit
    jax.device_get stays allowed. Pass ``host_to_device="disallow"`` too for
    fully-device-resident regions."""
    with contextlib.ExitStack() as stack:
        stack.enter_context(jax.transfer_guard_device_to_host(device_to_host))
        if host_to_device is not None:
            stack.enter_context(jax.transfer_guard_host_to_device(host_to_device))
        yield


@contextlib.contextmanager
def sync_discipline(allow_retraces: int = 0,
                    device_to_host: str = "disallow",
                    what: str = "guarded region"):
    """Both guards at once: the contract a warmed serving/benchmark steady
    state must meet — zero retraces AND no unnamed device->host transfer."""
    with no_retrace(allow_retraces=allow_retraces, what=what) as region:
        with no_implicit_transfers(device_to_host=device_to_host):
            yield region
