"""GAME serving CLI driver: replay traffic through the resilient frontend.

No Spark analog — the reference never shipped an online scorer (its GAME
serving story ends at batch score files). This driver stands up the
micro-batching :class:`~photon_ml_tpu.serving.ServingFrontend` over the
newest valid generation of a training run's checkpoint directory
(io/checkpoint.py gen-<n>/ layout) and replays Avro scoring traffic through
it in request-sized chunks — the operational smoke test for the serving
path: micro-batching, deadline shedding, and (with ``--hot-swap-watch``)
zero-downtime generational hot-swap while requests are in flight.

With ``--fleet-replicas N`` the replay runs through the serving FLEET tier
instead (serving/fleet.py): N replicas behind the ModelRouter with
round-robin + overload failover, hot-swap upgraded to replica-at-a-time
rolling rollout with a canary gate, and (``--fleet-http-port``) the HTTP
transport (serving/transport.py) listening while the replay runs.

Scores land as ScoringResultAvro part files (same format as the batch
scoring driver); a JSON stats line (QPS, p50/p99 latency, sheds broken out
by cause — overload vs deadline vs quota vs shutdown — per-generation
served-request counts, swaps, serving generation(s)) goes to the log and the
returned dict. Shed requests (deadline/overload/quota) keep their rows in
the output as NaN — sheds are explicit, never silently missing rows.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time

import numpy as np

from photon_ml_tpu.cli.game_scoring_driver import _write_scores
from photon_ml_tpu.cli.game_training_driver import _load_index_maps
from photon_ml_tpu.cli.parsers import (
    add_version_argument,
    parse_feature_shard_configuration,
)
from photon_ml_tpu.data.readers import read_merged_avro
from photon_ml_tpu.models.game import RandomEffectModel
from photon_ml_tpu.util import PhotonLogger, Timed
from photon_ml_tpu.util.date_range import resolve_input_paths


def build_arg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="game-serving-driver",
        description="Serve scoring traffic through the micro-batching frontend "
                    "from a generational checkpoint directory.",
    )
    add_version_argument(p)
    p.add_argument("--checkpoint-directory", required=True,
                   help="Generational checkpoint root (the training driver's "
                        "<--checkpoint-directory>/config_<i>): the newest "
                        "generation that passes SHA-256 verification serves")
    p.add_argument("--input-data-directories", required=True)
    p.add_argument("--input-data-date-range", default=None)
    p.add_argument("--input-data-days-range", default=None)
    p.add_argument("--root-output-directory", required=True)
    p.add_argument("--override-output-directory", action="store_true")
    p.add_argument("--feature-shard-configurations", action="append", required=True)
    p.add_argument("--index-map-directory", default=None,
                   help="Saved training index maps (<training-output>/index-maps): "
                        "serving requests must map features into the SAME global "
                        "columns the checkpointed coefficients were trained in")
    p.add_argument("--model-id", default=None)
    from photon_ml_tpu.cli.runtime import add_ingest_arguments, add_serving_arguments

    add_ingest_arguments(p)
    add_serving_arguments(p)
    p.add_argument("--log-level", default="INFO")
    p.add_argument("--application-name", default="game-serving")
    return p


def run(args: argparse.Namespace) -> dict:
    from photon_ml_tpu.cli.runtime import configure_compilation_cache, prepare_output_root
    from photon_ml_tpu.serving import FrontendConfig
    from photon_ml_tpu.serving.hotswap import GenerationWatcher, serve_from_checkpoint

    configure_compilation_cache()
    root = args.root_output_directory
    prepare_output_root(root, args.override_output_directory, 0, 1)
    logger = PhotonLogger(os.path.join(root, "logs", "photon.log"), level=args.log_level)
    frontend = watcher = router = http_server = None
    fleet_mode = int(getattr(args, "fleet_replicas", 0) or 0) > 0
    try:
        shard_configs = dict(
            parse_feature_shard_configuration(a)
            for a in args.feature_shard_configurations
        )
        index_maps = _load_index_maps(args.index_map_directory, shard_configs)
        missing = sorted(s for s in shard_configs if s not in index_maps)
        if missing:
            raise FileNotFoundError(
                f"No saved index maps for shard(s) {missing}; pass "
                f"--index-map-directory pointing at the training run's "
                f"<output>/index-maps"
            )

        config = FrontendConfig(
            max_batch=args.serving_max_batch,
            max_wait_ms=args.serving_max_wait_ms,
            max_queue_depth=args.serving_queue_depth,
            default_deadline_ms=args.serving_deadline_ms,
        )
        model_name = args.model_id or "default"
        if fleet_mode:
            from photon_ml_tpu.serving import ModelRouter, ReplicaSet

            with Timed("load newest generation", logger):
                replica_set = ReplicaSet.from_checkpoint(
                    args.checkpoint_directory,
                    n_replicas=args.fleet_replicas,
                    name=model_name,
                    config=config,
                )
            router = ModelRouter()
            router.add_model(model_name, replica_set)
            manager = replica_set  # GenerationWatcher duck type (check_once)
            engine = replica_set.replicas[0].engine
            logger.info(
                "serving generations %s across %d replicas",
                replica_set.generations, args.fleet_replicas,
            )
        else:
            with Timed("load newest generation", logger):
                frontend, manager = serve_from_checkpoint(
                    args.checkpoint_directory, config=config
                )
            engine = frontend.engine
            logger.info("serving generation %d", frontend.generation)
        id_tags = sorted(
            {
                m.re_type
                for _, m in engine.model
                if isinstance(m, RandomEffectModel)
            }
        )

        input_paths = resolve_input_paths(
            args.input_data_directories,
            getattr(args, "input_data_date_range", None),
            getattr(args, "input_data_days_range", None),
        )
        with Timed("read data", logger):
            data, index_maps, uids = read_merged_avro(
                input_paths, shard_configs, index_maps, id_tags,
                ingest_workers=getattr(args, "ingest_workers", None),
            )
        logger.info("replaying %d samples through the serving frontend", data.n)

        if args.hot_swap_watch:
            watcher = GenerationWatcher(
                manager, poll_interval_s=args.hot_swap_poll_seconds
            )

        if fleet_mode:
            if getattr(args, "fleet_http_port", None) is not None:
                from photon_ml_tpu.serving import FleetHTTPServer

                # warm every replica BEFORE the endpoint exists: /readyz
                # (liveness vs readiness — engine.warmed) must answer 200
                # from the first probe a front router sends, or a restarted
                # replica sits in an evicted/unready limbo for a probe cycle
                # it didn't need
                warm_req = data.select(
                    np.arange(min(data.n, int(args.serving_request_batch)))
                )
                with Timed("warm replicas (compile first bucket)", logger):
                    for replica in replica_set.replicas:
                        replica.engine.score(warm_req)
                http_server = FleetHTTPServer(
                    router, port=args.fleet_http_port
                ).start()
                logger.info(
                    "fleet HTTP endpoint listening on %s:%d (readiness: %s)",
                    http_server.host, http_server.port,
                    json.dumps(router.readiness()),
                )
            submit = lambda req: router.submit(model_name, req)  # noqa: E731
            stats_fn = router.stats
            incidents = lambda: (  # noqa: E731
                router.incidents
                + router.replica_set(model_name).incidents
                + [
                    i
                    for r in router.replica_set(model_name).replicas
                    for i in r.frontend.incidents
                ]
            )
        else:
            submit = frontend.submit
            stats_fn = frontend.stats
            incidents = lambda: frontend.incidents  # noqa: E731

        scores, stats = _replay(submit, stats_fn, data, args, logger)
        if http_server is not None:
            stats["http_endpoint"] = f"{http_server.host}:{http_server.port}"
        stats["output_directory"] = root
        stats["incidents"] = [i.to_dict() for i in incidents()]
        with Timed("write scores", logger):
            _write_scores(
                os.path.join(root, "scores", "part-00000.avro"),
                uids, scores, data, args.model_id or "",
            )
        logger.info("serving stats: %s", json.dumps(stats))
        return {"scores": scores, "stats": stats, "output_directory": root}
    finally:
        if watcher is not None:
            watcher.stop()
        if http_server is not None:
            http_server.close()
        if frontend is not None:
            frontend.close()
        if router is not None:
            router.close()
        logger.close()


def _sheds_by_cause(stats: dict) -> dict:
    """The dashboard breakout: shed counts by CAUSE (overload vs deadline vs
    quota vs shutdown) summed over the frontend — or, in fleet mode, the
    router level plus every model's replica-set aggregate (whose shed_* keys
    already sum their replicas, so the nested per-replica dicts are not
    walked again)."""
    causes = {"overload": 0, "deadline": 0, "quota": 0, "shutdown": 0}

    def add(d: dict) -> None:
        causes["overload"] += int(d.get("shed_overload", 0))
        causes["deadline"] += int(d.get("shed_deadline", 0))
        causes["quota"] += int(d.get("shed_quota", 0))
        causes["shutdown"] += int(d.get("shed_shutdown", 0))

    add(stats)
    for model_stats in (stats.get("models") or {}).values():
        add(model_stats)
    return causes


def _served_by_generation(stats: dict) -> dict:
    """Merged per-generation served-request counts across the frontend (or
    every model's replica-set aggregate in fleet mode)."""
    out: collections.Counter = collections.Counter()
    for d in [stats, *list((stats.get("models") or {}).values())]:
        for g, c in (d.get("served_by_generation") or {}).items():
            out[int(g)] += int(c)
    return {g: int(c) for g, c in sorted(out.items())}


def _replay(submit, stats_fn, data, args, logger) -> tuple[np.ndarray, dict]:
    """Windowed closed-loop replay: chunk the table into request-sized
    GameInputs, keep a bounded window of futures outstanding (so the replay
    itself cannot overload the queue it is testing), and reassemble scores in
    row order. Shed chunks stay NaN. ``submit`` is either a frontend's or the
    fleet router's; ``stats_fn`` the matching stats provider."""
    from photon_ml_tpu.serving import DeadlineExceeded, Overloaded, QuotaExceeded

    n = data.n
    chunk = max(1, int(args.serving_request_batch))
    scores = np.full(n, np.nan)
    window: collections.deque = collections.deque()
    window_cap = max(4, min(args.serving_queue_depth // 2, 64))
    served = shed = 0
    latencies = []
    generations = set()

    def drain_one():
        nonlocal served, shed
        start, stop, fut, t0 = window.popleft()
        try:
            out = fut.result(timeout=300.0)
        except (Overloaded, DeadlineExceeded, QuotaExceeded) as e:
            shed += 1
            logger.warning("request rows [%d, %d) shed: %s", start, stop, e)
            return
        latencies.append(time.perf_counter() - t0)
        scores[start:stop] = out
        generations.add(fut.generation)
        served += 1

    t_start = time.perf_counter()
    for start in range(0, n, chunk):
        stop = min(start + chunk, n)
        req = data.select(np.arange(start, stop))
        if len(window) >= window_cap:
            drain_one()
        try:
            # the deadline rides on FrontendConfig.default_deadline_ms (run()
            # wired --serving-deadline-ms there); one authoritative path
            fut = submit(req)
        except (Overloaded, DeadlineExceeded, QuotaExceeded) as e:
            shed += 1
            logger.warning("request rows [%d, %d) shed at admission: %s", start, stop, e)
            continue
        window.append((start, stop, fut, time.perf_counter()))
    while window:
        drain_one()
    elapsed = time.perf_counter() - t_start

    lat_ms = np.asarray(latencies or [0.0]) * 1e3
    stats = {
        "requests_served": served,
        "requests_shed": shed,
        "qps": round(served / elapsed, 2) if elapsed > 0 else None,
        "samples_per_sec": round(float(np.sum(~np.isnan(scores))) / elapsed, 2)
        if elapsed > 0
        else None,
        "p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
        "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
        "generations_served": sorted(g for g in generations if g is not None),
        **stats_fn(),
    }
    stats["sheds_by_cause"] = _sheds_by_cause(stats)
    stats["served_by_generation"] = _served_by_generation(stats)
    return scores, stats


def main(argv=None) -> int:
    run(build_arg_parser().parse_args(argv))
    return 0


if __name__ == "__main__":
    sys.exit(main())
