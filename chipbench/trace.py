"""Reduction of a profiler trace to what the metrics read: the device's busy
intervals, the operations that took most time, and the idle gaps attributed to
what the host was doing (the chipbench spans).

The reduction works on a plain form of the trace — ``{"planes": [{"name",
"lines": [{"name", "events": [[name, start_ns, duration_ns], ...]}]}]}`` —
which ``load_xplane`` makes from the ``.xplane.pb`` the JAX profiler writes and
which ``tests/`` keeps a small recorded sample of.
"""

from __future__ import annotations

import glob
import os

SPAN_PREFIX = "chipbench:"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# inner spans first: a gap inside fe_update is fe_update's, not fit's
SPAN_ORDER = ("fe_update", "re_update", "validate", "fit")


def load_xplane(trace_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` in the plain form, keeping
    only device planes' op and module lines and host events that are
    chipbench spans."""
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [
                [short_name(ev.name) if device else ev.name, int(ev.start_ns), int(ev.duration_ns)]
                for ev in line.events
                if device or ev.name.startswith(SPAN_PREFIX)
            ]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def short_name(name: str) -> str:
    """An operation's HLO text cut to its name and result type
    (``%fusion.3 = f32[124]{...} fusion(...)`` -> ``fusion.3 f32[124]``); a
    module's name without its fingerprint (``jit_solve(123)`` -> ``jit_solve``)."""
    if " = " in name:
        op, rest = name.split(" = ", 1)
        kind = rest.split("{", 1)[0].split(" ", 1)[0]
        return (op.lstrip("%") + ("" if kind.startswith("(") else " " + kind))[:96]
    return name.split("(", 1)[0][:96]


def _union(intervals: list) -> list:
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals: list, lo: int, hi: int) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals if e > lo and s < hi]


def _overlap(a: list, b: list) -> int:
    """Total length of the intersection of two disjoint sorted interval lists."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _subtract(a: list, b: list) -> list:
    """``a`` minus ``b`` (both disjoint, sorted)."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def _self_times(events: list) -> dict:
    """Seconds by event name on one line, each event's time less the events
    nested inside it (a ``while`` op spans its body's ops)."""
    totals: dict = {}
    stack: list = []  # [name, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _end, self_ns = stack.pop()
            totals[name] = totals.get(name, 0) + self_ns

    for name, start, dur in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return {k: v / 1e9 for k, v in totals.items()}


def spans_of(trace: dict) -> dict:
    """name -> disjoint sorted [start, end] intervals of the chipbench spans."""
    spans: dict = {}
    for plane in trace["planes"]:
        if plane["name"].startswith(DEVICE_PREFIX):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(SPAN_PREFIX):
                    spans.setdefault(name[len(SPAN_PREFIX):], []).append([start, start + dur])
    return {k: _union(v) for k, v in spans.items()}


def reduce_trace(trace: dict) -> dict | None:
    """``{"window_s", "busy_s", "busy" (per device, clipped to the window),
    "spans", "device_ops", "idle_gaps"}``; None where the trace holds no
    device operation or no ``fit`` span (nothing to read)."""
    spans = spans_of(trace)
    devices = [p for p in trace["planes"] if p["name"].startswith(DEVICE_PREFIX)]
    if not devices or "fit" not in spans:
        return None
    lo, hi = spans["fit"][0][0], spans["fit"][-1][1]
    busy, op_seconds, module_seconds = [], {}, {}
    for plane in devices:
        lines = {line["name"]: line["events"] for line in plane["lines"]}
        events = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        inside = [ev for ev in events if ev[1] + ev[2] > lo and ev[1] < hi]
        busy.append(_clip(_union([[s, s + d] for _n, s, d in inside]), lo, hi))
        for name, sec in _self_times(inside).items():
            op_seconds[name] = op_seconds.get(name, 0.0) + sec
        for name, start, dur in lines.get(MODULES_LINE, []):
            if start + dur > lo and start < hi:
                key = "module:" + name
                module_seconds[key] = module_seconds.get(key, 0.0) + dur / 1e9
    if not any(busy):
        return None
    n_dev = len(devices)
    busy_s = sum(e - s for b in busy for s, e in b) / 1e9 / n_dev
    # idle gaps of the first device, attributed to the innermost host span
    gaps = _subtract([[lo, hi]], busy[0])
    idle = {}
    for name in SPAN_ORDER:
        cover = spans.get(name, [])
        idle[name] = _overlap(gaps, cover) / 1e9
        gaps = _subtract(gaps, cover)
    idle["_no_span_"] = sum(e - s for s, e in gaps) / 1e9

    def top(seconds: dict, k: int) -> list:
        return sorted(([n, s / n_dev] for n, s in seconds.items()), key=lambda x: -x[1])[:k]

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_s,
        "busy": busy,
        "spans": spans,
        "device_ops": top(module_seconds, 3) + top(op_seconds, 7),
        "idle_gaps": sorted(
            ([n, s] for n, s in idle.items() if s > 0), key=lambda x: -x[1]
        )[:10],
    }


def span_seconds(reduced: dict, span: str) -> float | None:
    """Seconds inside the named spans; None where the trace has none."""
    spans = reduced["spans"].get(span)
    return sum(e - s for s, e in spans) / 1e9 if spans else None


def busy_inside(reduced: dict, span: str) -> float:
    """Seconds the (first) device was busy inside the named spans."""
    return _overlap(reduced["busy"][0], reduced["spans"].get(span, [])) / 1e9
