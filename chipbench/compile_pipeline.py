"""Set-up's compile pipeline in the program's records, for the ``jit.*``
readers: the ``jit.trace``, ``jit.lower`` and ``jit.compile`` spans that
``photon_ml_tpu.util.timed`` records for every program JAX makes (jaxpr
tracing, lowering to MLIR, backend compile or persistent-cache load), cut to
set-up: the records that ended before the traced window.

The stages nest: a jitted call on concrete values while a function is traced
lowers and compiles inside the trace. So each nanosecond of set-up goes to
one stage alone, the first of ``ORDER`` whose records cover it. The three
seconds then never overlap, and their sum is at most set-up.

A program whose recorder makes no ``jit.*`` records (the parent of the PR that
added them), or has no recorder, reads None.
"""

from __future__ import annotations

from chipbench import program_records

ORDER = ("jit.compile", "jit.lower", "jit.trace")


def _union(intervals) -> list:
    """Sorted, disjoint ``[start, end]`` intervals covering ``intervals``."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _outside(intervals, cover: list) -> int:
    """Length of the union of ``intervals`` less what ``cover`` (disjoint) covers."""
    total = 0
    for start, end in _union(intervals):
        total += end - start
        for c_start, c_end in cover:
            total -= max(0, min(end, c_end) - max(start, c_start))
    return total


def seconds(run: dict, stage: str) -> float | None:
    """Seconds of set-up inside ``stage``'s records and inside no record of a
    stage before it in ``ORDER``; None where set-up has no record of ``stage``."""
    found = program_records.window(run)
    own = found.before(stage) if found else []
    if not own:
        return None
    earlier = ORDER[: ORDER.index(stage)]
    cover = _union([(r.start_ns, r.end_ns) for name in earlier for r in found.before(name)])
    return _outside([(r.start_ns, r.end_ns) for r in own], cover) / 1e9
