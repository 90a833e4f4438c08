"""Front-router CLI driver: stand the fault-tolerant routing tier up as its
own process.

The serving driver (cli/serving_driver.py ``--fleet-http-port``) puts ONE
replica process on the wire; this driver puts the tier in FRONT of N of
them: a :class:`~photon_ml_tpu.serving.FrontRouter` (probe/evict/re-admit
membership, bounded retries under a fleet-wide budget, per-replica circuit
breakers, priority + per-tenant admission) behind a
:class:`~photon_ml_tpu.serving.RouterHTTPServer` speaking the same endpoint
surface as the replicas — clients cannot tell one tier from N processes.

Topology is static by design (the backends are the processes an operator
started; membership HEALTH is the router's job, membership IDENTITY is the
operator's), so the full deployment is::

    photon-serving-driver --fleet-replicas 2 --fleet-http-port 7101 ... &
    photon-serving-driver --fleet-replicas 2 --fleet-http-port 7102 ... &
    python -m photon_ml_tpu.cli.fleet_router_driver \\
        --backend 127.0.0.1:7101 --backend 127.0.0.1:7102 \\
        --model default=interactive --http-port 7100

Runs until SIGTERM/SIGINT (or ``--duration-s``), then prints one JSON stats
line (membership transitions, retries, retry-budget spend, sheds by cause)
to stdout — the same observability contract as the bench drivers.
"""

from __future__ import annotations

import argparse
import json
import signal
import sys
import threading

from photon_ml_tpu.cli.parsers import add_version_argument


def build_arg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="photon-fleet-router",
        description="Fault-tolerant front router over N replica processes. "
                    "CPU-only surface for now: a chip belongs to one process "
                    "at a time, so the replica processes of one host serve "
                    "from the CPU backend.",
    )
    add_version_argument(p)
    p.add_argument("--backend", action="append", required=True,
                   metavar="HOST:PORT",
                   help="replica process endpoint (repeat for each replica)")
    p.add_argument("--http-port", type=int, default=0,
                   help="front endpoint port (0 = ephemeral, printed at start)")
    p.add_argument("--http-host", default="127.0.0.1")
    p.add_argument("--model", action="append", default=[],
                   metavar="NAME=PRIORITY",
                   help="admission policy: model NAME sheds at PRIORITY "
                        "(interactive|standard|batch); unregistered models "
                        "route at 'standard', unmetered")
    p.add_argument("--tenant-quota", action="append", default=[],
                   metavar="MODEL:TENANT:RATE:BURST",
                   help="per-tenant token bucket at the router (TENANT '*' "
                        "sets the model's default quota)")
    p.add_argument("--probe-interval-s", type=float, default=0.5)
    p.add_argument("--evict-after-failures", type=int, default=2)
    p.add_argument("--readmit-after-successes", type=int, default=2)
    p.add_argument("--connect-timeout-s", type=float, default=1.0)
    p.add_argument("--read-timeout-s", type=float, default=60.0)
    p.add_argument("--max-attempts", type=int, default=3)
    p.add_argument("--retry-budget-rate", type=float, default=10.0)
    p.add_argument("--retry-budget-burst", type=float, default=20.0)
    p.add_argument("--breaker-reset-s", type=float, default=1.0)
    p.add_argument("--fleet-budget-per-replica", type=int, default=None,
                   help="in-flight cap per replica IN ROTATION; a kill "
                        "shrinks admission so low-priority traffic sheds "
                        "first (default: no budget)")
    p.add_argument("--default-deadline-ms", type=float, default=None)
    p.add_argument("--duration-s", type=float, default=None,
                   help="exit after this long (default: run until signal)")
    return p


def _parse_backend(spec: str) -> tuple[str, int]:
    host, sep, port = spec.rpartition(":")
    if not sep or not port.isdigit():
        raise ValueError(f"--backend wants HOST:PORT, got {spec!r}")
    return host, int(port)


def run(args: argparse.Namespace) -> dict:
    from photon_ml_tpu.serving import (
        FrontRouter,
        RouterConfig,
        RouterHTTPServer,
        TenantQuota,
    )

    config = RouterConfig(
        probe_interval_s=args.probe_interval_s,
        evict_after_failures=args.evict_after_failures,
        readmit_after_successes=args.readmit_after_successes,
        connect_timeout_s=args.connect_timeout_s,
        read_timeout_s=args.read_timeout_s,
        max_attempts=args.max_attempts,
        retry_budget_rate=args.retry_budget_rate,
        retry_budget_burst=args.retry_budget_burst,
        breaker_reset_s=args.breaker_reset_s,
        fleet_budget_per_replica=args.fleet_budget_per_replica,
        default_deadline_ms=args.default_deadline_ms,
    )
    router = FrontRouter([_parse_backend(b) for b in args.backend], config=config)

    policies: dict = {}
    for spec in args.model:
        name, sep, priority = spec.partition("=")
        if not sep:
            raise ValueError(f"--model wants NAME=PRIORITY, got {spec!r}")
        policies[name] = {"priority": priority, "default": None, "tenants": {}}
    for spec in args.tenant_quota:
        try:
            model, tenant, rate, burst = spec.split(":")
            quota = TenantQuota(rate=float(rate), burst=float(burst))
        except ValueError as e:
            raise ValueError(
                f"--tenant-quota wants MODEL:TENANT:RATE:BURST, got {spec!r}"
            ) from e
        entry = policies.setdefault(
            model, {"priority": "standard", "default": None, "tenants": {}}
        )
        if tenant == "*":
            entry["default"] = quota
        else:
            entry["tenants"][tenant] = quota
    for name, entry in policies.items():
        router.register_model(
            name,
            priority=entry["priority"],
            tenant_quota=entry["default"],
            tenant_quotas=entry["tenants"],
        )

    done = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: done.set())
    with router, RouterHTTPServer(router, host=args.http_host,
                                  port=args.http_port) as server:
        print(
            json.dumps({
                "listening": f"{server.host}:{server.port}",
                "backends": args.backend,
                "rotation": router.rotation(),
            }),
            flush=True,
        )
        done.wait(timeout=args.duration_s)
        stats = router.stats()
        stats["incidents"] = [i.to_dict() for i in router.incidents]
    print(json.dumps(stats), flush=True)
    return stats


def main(argv=None) -> int:
    run(build_arg_parser().parse_args(argv))
    return 0


if __name__ == "__main__":
    sys.exit(main())
