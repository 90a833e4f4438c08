"""Cross-process fleet benchmark: chaos-kill-under-load through the front router.

The fleet finally leaves the process: N replica PROCESSES
(benchmarks/fleet_proc_worker.py — full engine + frontend + ModelRouter +
HTTP transport each) behind the front router
(photon_ml_tpu/serving/router.py), with the only failure domain production
actually has — a replica process SIGKILLed mid-request — exercised on
purpose, repeatedly, under open-loop load.

Metric: ``fleet_proc_sustained_qps_at_p999`` — the highest fixed arrival
rate the N-process fleet sustains through the router with p999 latency
inside the budget and ZERO sheds/errors. Latency is measured from the
INTENDED send time (request i is due at ``t0 + i/rate`` no matter what the
fleet is doing — PAPERS.md 1612.01437's coordinated-omission discipline;
same open-loop core as benchmarks/fleet_bench.py, adapted to the router's
synchronous call surface by dispatching each due request on a pool thread).

The run is gated, not just measured:

- ``parity_bitwise`` — every response that completed (rate ladder, chaos
  phases, post-recovery) is BITWISE what a direct local engine call on the
  same seed-built model returns: two process hops and a kill storm change
  nothing about the wire contract.
- ``zero_silent_drops`` — every request is accounted: served, typed shed
  (Overloaded / DeadlineExceeded / QuotaExceeded), or typed
  ReplicaUnavailable. An untyped error fails the gate.
- ``reconverged_within_budget`` — after each SIGKILL the restarted replica
  is re-admitted within the probe budget (measured from the moment its
  ``/readyz`` answers, i.e. from when re-admission becomes POSSIBLE —
  restart + recompile time is the worker's, not the router's).
- ``readmitted_serves`` — the re-admitted replica takes real traffic again
  (its served count rises during the post-recovery level).

CPU-ONLY SURFACE for now: a chip belongs to one process at a time, so N
replica processes cannot share one. The bench forces ``JAX_PLATFORMS=cpu`` in
this process and in every worker; its JSON line names ``"platform": "cpu"``
and its qps is a count of what the host sustained, not a device metric.

Run directly (``python benchmarks/fleet_proc_bench.py``) or as
``python bench.py --fleet-proc``. Prints ONE JSON line; exits nonzero when
any gate fails.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import socket
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

os.environ["JAX_PLATFORMS"] = "cpu"  # before any jax import, and inherited by
# every worker: N processes cannot share a chip, and the reference engine and
# the workers must score on the SAME backend or the bitwise gate compares
# different programs

import numpy as np

from serving_load_bench import build_models, build_request_pool, warm_buckets

_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fleet_proc_worker.py")
_SEED = 20260807


# ------------------------------------------------------------ process fleet


@dataclasses.dataclass
class _Worker:
    port: int
    proc: subprocess.Popen


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn(port: int, args) -> _Worker:
    env = dict(os.environ)  # carries the JAX_PLATFORMS=cpu set at import
    proc = subprocess.Popen(
        [
            sys.executable, _WORKER,
            "--port", str(port),
            "--seed", str(_SEED),
            "--scale", str(args.scale),
            "--batch", str(args.batch),
            "--max-batch", str(args.max_batch),
            "--max-wait-ms", str(args.max_wait_ms),
            "--queue-depth", str(args.queue_depth),
        ],
        stdout=subprocess.DEVNULL,
        env=env,
    )
    return _Worker(port=port, proc=proc)


def _wait_ready(port: int, timeout_s: float) -> float:
    """Poll the replica's /readyz until it answers 200 (the worker warms its
    engine before listening, so ready == compiled programs live). Returns the
    perf_counter timestamp of the first ready answer."""
    from photon_ml_tpu.serving import FleetClient

    client = FleetClient("127.0.0.1", port, timeout=2.0, connect_timeout=0.5)
    deadline = time.perf_counter() + timeout_s
    while time.perf_counter() < deadline:
        if client.ready():
            return time.perf_counter()
        time.sleep(0.1)
    raise TimeoutError(f"replica on port {port} never became ready")


# ------------------------------------------------------------ open-loop core


@dataclasses.dataclass
class _Rec:
    idx: int
    intended: float = 0.0
    done_at: float = None
    out: object = None
    gen: object = None
    shed: str = None
    unavailable: str = None
    error: str = None


def run_open_loop(router, requests, rate_qps: float, n_requests: int,
                  deadline_ms=None, max_workers: int = 64):
    """Fixed-rate arrivals against the router's SYNCHRONOUS scoring surface:
    request i is due at ``t0 + i/rate`` and is handed to a pool thread at its
    due time without waiting for earlier completions; the completion stamp is
    taken on the pool thread the moment the call returns, so latency from the
    intended send time includes any queueing the pool itself adds — open-loop
    honesty (a saturated pool is the client falling behind, and it shows up
    in the tail, not in a silently thinned sample)."""
    from photon_ml_tpu.serving import DeadlineExceeded, Overloaded, QuotaExceeded
    from photon_ml_tpu.serving.transport import ReplicaUnavailable

    recs = [_Rec(idx=i % len(requests)) for i in range(n_requests)]

    def call(rec: _Rec) -> None:
        try:
            out, gen = router.score(
                "main", requests[rec.idx], deadline_ms=deadline_ms
            )
        except (Overloaded, DeadlineExceeded, QuotaExceeded) as e:
            rec.shed = type(e).__name__
            return
        except ReplicaUnavailable as e:
            rec.unavailable = f"{e.phase}: {e}"[:200]
            return
        except BaseException as e:  # noqa: BLE001 — a gate failure, not a crash
            rec.error = f"{type(e).__name__}: {e}"[:200]
            return
        rec.done_at = time.perf_counter()
        rec.out, rec.gen = out, gen

    pool = ThreadPoolExecutor(max_workers=max_workers)
    t0 = time.perf_counter() + 0.02
    max_lag = 0.0
    for i, rec in enumerate(recs):
        rec.intended = t0 + i / rate_qps
        while True:
            now = time.perf_counter()
            if now >= rec.intended:
                break
            time.sleep(min(rec.intended - now, 0.002))
        max_lag = max(max_lag, time.perf_counter() - rec.intended)
        pool.submit(call, rec)
    pool.shutdown(wait=True)
    elapsed = max(time.perf_counter() - t0, 1e-9)

    served = [(r.idx, r.out, r.gen) for r in recs if r.done_at is not None]
    latencies = [r.done_at - r.intended for r in recs if r.done_at is not None]
    lat_ms = np.asarray(latencies or [0.0]) * 1e3
    return {
        "offered_qps": rate_qps,
        "achieved_qps": round(len(served) / elapsed, 2),
        "served": len(served),
        "sheds": sum(1 for r in recs if r.shed is not None),
        "unavailable": sum(1 for r in recs if r.unavailable is not None),
        "errors": [r.error for r in recs if r.error is not None],
        "p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
        "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
        "p999_ms": round(float(np.percentile(lat_ms, 99.9)), 3),
        "max_send_lag_ms": round(max_lag * 1e3, 3),
    }, served


def check_parity(served, requests, engine) -> bool:
    for idx, out, _gen in served:
        direct = engine.score(requests[idx])
        if direct.dtype != out.dtype or not np.array_equal(direct, out):
            return False
    return True


# -------------------------------------------------------------------- bench


def run(args) -> dict:
    import jax

    from photon_ml_tpu.serving import FrontRouter, RouterConfig

    rng = np.random.default_rng(_SEED)
    n_users = max(1, int(200 * args.scale))
    n_items = max(1, int(50 * args.scale))
    batch = max(8, int(args.batch * args.scale))

    # reference engine: same seed, same checkpoint-load path as every worker
    # — the bitwise oracle for everything the fleet returns
    import tempfile

    from photon_ml_tpu.io.checkpoint import save_checkpoint
    from photon_ml_tpu.serving import FrontendConfig, ReplicaSet

    ckpt_root = tempfile.mkdtemp(prefix="fleet-proc-ref-")
    save_checkpoint(ckpt_root, build_models(rng, n_users, n_items, scale=1.0),
                    1, keep_generations=2)
    reference = ReplicaSet.from_checkpoint(
        ckpt_root, n_replicas=1, name="reference",
        config=FrontendConfig(max_batch=args.max_batch),
    )
    engine = reference.replicas[0].engine
    warm_buckets(engine, np.random.default_rng(_SEED + 1),
                 args.batch, args.max_batch, n_users, n_items)
    requests = build_request_pool(rng, args.pool, batch, n_users, n_items)

    config = RouterConfig(
        probe_interval_s=args.probe_interval_s,
        evict_after_failures=2,
        readmit_after_successes=2,
        connect_timeout_s=1.0,
        read_timeout_s=30.0,
        max_attempts=3,
        retry_budget_rate=args.rate_base,  # a whole second of load may retry
        retry_budget_burst=4.0 * args.rate_base,
        breaker_open_after=2,
        breaker_reset_s=2 * args.probe_interval_s,
        fleet_budget_per_replica=args.queue_depth,
    )
    # re-admission needs readmit_after consecutive ready probes; the slack
    # covers probe phase alignment and CI scheduling jitter
    probe_budget_s = (
        config.probe_interval_s * (config.readmit_after_successes + 4) + 1.0
    )

    workers = [_spawn(_free_port(), args) for _ in range(args.replicas)]
    router = None
    try:
        for w in workers:
            _wait_ready(w.port, args.ready_timeout_s)
        router = FrontRouter(
            [("127.0.0.1", w.port) for w in workers], config=config, seed=_SEED
        )
        router.register_model("main", priority="interactive")

        # ---- warm the full path (router -> wire -> replica) --------------
        warm_stats, warm_served = run_open_loop(
            router, requests, rate_qps=max(args.rate_base / 2, 1.0),
            n_requests=4 * args.replicas, deadline_ms=args.deadline_ms,
        )
        all_served = list(warm_served)

        # ---- open-loop rate ladder ---------------------------------------
        level_results = []
        rate = float(args.rate_base)
        for _ in range(args.rate_levels):
            stats, served = run_open_loop(
                router, requests, rate_qps=rate,
                n_requests=args.requests_per_level, deadline_ms=args.deadline_ms,
            )
            level_results.append(stats)
            all_served.extend(served)
            rate *= 2.0
        sustained = [
            lv for lv in level_results
            if lv["sheds"] == 0 and lv["unavailable"] == 0 and not lv["errors"]
            and lv["p999_ms"] <= args.p999_budget_ms
        ]
        peak = max(sustained, key=lambda lv: lv["achieved_qps"]) if sustained else None

        # ---- chaos: SIGKILL a replica mid-load, restart, re-admit --------
        chaos_cycles = []
        total_requests = total_served = total_sheds = total_unavail = 0
        untyped_errors: list = []
        for cycle in range(args.kill_cycles):
            victim_i = cycle % len(workers)
            victim = workers[victim_i]
            box = {}
            loot: list = []

            def chaos_traffic():
                stats, served = run_open_loop(
                    router, requests, rate_qps=args.rate_base,
                    n_requests=args.chaos_requests, deadline_ms=args.deadline_ms,
                )
                box.update(stats)
                loot.extend(served)

            loader = threading.Thread(target=chaos_traffic)
            loader.start()
            # kill a quarter of the way into the schedule: load is flowing,
            # requests are in flight at the moment the process dies
            time.sleep(0.25 * args.chaos_requests / args.rate_base)
            victim.proc.kill()
            victim.proc.wait()
            t_kill = time.perf_counter()
            time.sleep(args.down_s)
            workers[victim_i] = _spawn(victim.port, args)
            ready_at = _wait_ready(victim.port, args.ready_timeout_s)
            deadline = ready_at + probe_budget_s
            converged_at = None
            while time.perf_counter() < deadline:
                if router.converged:
                    converged_at = time.perf_counter()
                    break
                time.sleep(0.02)
            loader.join(300.0)
            all_served.extend(loot)
            total_requests += args.chaos_requests
            total_served += box.get("served", 0)
            total_sheds += box.get("sheds", 0)
            total_unavail += box.get("unavailable", 0)
            untyped_errors.extend(box.get("errors", []))
            chaos_cycles.append({
                "victim": f"127.0.0.1:{victim.port}",
                "downtime_s": round(args.down_s, 3),
                "restart_to_ready_s": round(ready_at - t_kill, 3),
                "ready_to_readmit_s": (
                    None if converged_at is None
                    else round(converged_at - ready_at, 3)
                ),
                "probe_budget_s": round(probe_budget_s, 3),
                "reconverged": converged_at is not None,
                **{k: box.get(k) for k in
                   ("served", "sheds", "unavailable", "p999_ms", "achieved_qps")},
            })

        # ---- post-recovery: the re-admitted replica serves again ---------
        before = router.stats()["replicas"]
        post_stats, post_served = run_open_loop(
            router, requests, rate_qps=args.rate_base,
            n_requests=args.post_requests, deadline_ms=args.deadline_ms,
        )
        after = router.stats()["replicas"]
        all_served.extend(post_served)
        readmitted_serves = all(
            after[name].get("requests_ok", 0) > before[name].get("requests_ok", 0)
            for name in after
        )

        parity = check_parity(all_served, requests, engine)
        zero_silent_drops = (
            not untyped_errors
            and not post_stats["errors"]
            and not any(lv["errors"] for lv in level_results)
            and total_served + total_sheds + total_unavail == total_requests
        )
        incidents = router.incidents
        router_stats = router.stats()
        result = {
            "metric": "fleet_proc_sustained_qps_at_p999",
            "value": peak["achieved_qps"] if peak else None,
            "unit": "requests/sec",
            "sustained_offered_qps": peak["offered_qps"] if peak else None,
            "p999_budget_ms": args.p999_budget_ms,
            "replicas": args.replicas,
            "levels": level_results,
            "chaos_cycles": chaos_cycles,
            "post_recovery": post_stats,
            "parity_bitwise": bool(parity),
            "responses_checked_bitwise": len(all_served),
            "zero_silent_drops": bool(zero_silent_drops),
            "reconverged_within_budget": all(c["reconverged"] for c in chaos_cycles),
            "readmitted_serves": bool(readmitted_serves),
            "typed_incidents": {
                kind: sum(1 for i in incidents if i.kind == kind)
                for kind in sorted({i.kind for i in incidents})
            },
            "retries": int(router_stats.get("retries", 0)),
            "retry_budget": router_stats["retry_budget"],
            "sheds_by_cause": router_stats["sheds_by_cause"],
            "platform": jax.default_backend(),
        }
        if args.scale != 1.0:
            result["scale"] = args.scale
        return result
    finally:
        if router is not None:
            router.close()
        reference.close()
        for w in workers:
            if w.proc.poll() is None:
                w.proc.terminate()
        for w in workers:
            try:
                w.proc.wait(timeout=20.0)
            except subprocess.TimeoutExpired:
                w.proc.kill()


def gates_green(result: dict) -> bool:
    return bool(
        result["value"] is not None
        and result["parity_bitwise"]
        and result["zero_silent_drops"]
        and result["reconverged_within_budget"]
        and result["readmitted_serves"]
    )


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        epilog="CPU-only surface for now: N replica processes cannot share "
               "one chip, so JAX_PLATFORMS=cpu is forced here and in every "
               "worker; the qps is host work, not a device metric.",
    )
    p.add_argument("--replicas", type=int, default=3,
                   help="replica PROCESS count behind the front router")
    p.add_argument("--rate-base", type=float, default=10.0,
                   help="open-loop ladder base arrival rate (doubles per level)")
    p.add_argument("--rate-levels", type=int, default=3)
    p.add_argument("--requests-per-level", type=int, default=60)
    p.add_argument("--kill-cycles", type=int, default=2,
                   help="SIGKILL/restart cycles, each under open-loop load")
    p.add_argument("--chaos-requests", type=int, default=80,
                   help="open-loop requests spanning each kill/restart cycle")
    p.add_argument("--post-requests", type=int, default=30,
                   help="post-recovery requests proving the re-admitted "
                        "replica serves real traffic")
    p.add_argument("--down-s", type=float, default=0.3,
                   help="gap between SIGKILL and respawn")
    p.add_argument("--probe-interval-s", type=float, default=0.25)
    p.add_argument("--p999-budget-ms", type=float, default=2000.0)
    p.add_argument("--deadline-ms", type=float, default=10000.0)
    p.add_argument("--ready-timeout-s", type=float, default=300.0,
                   help="worker spawn-to-/readyz budget (includes compile)")
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--max-batch", type=int, default=128)
    p.add_argument("--max-wait-ms", type=float, default=2.0)
    p.add_argument("--queue-depth", type=int, default=512)
    p.add_argument("--pool", type=int, default=16)
    p.add_argument("--scale", type=float, default=1.0)
    args = p.parse_args(argv)
    if args.replicas < 2:
        p.error("--replicas must be >= 2 (the chaos gate kills one mid-load)")
    result = run(args)
    result["platform"] = os.environ["JAX_PLATFORMS"]
    print(json.dumps(result))
    return 0 if gates_green(result) else 1


if __name__ == "__main__":
    sys.exit(main())
