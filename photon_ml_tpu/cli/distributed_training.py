"""Multi-process FIXED-EFFECT training for the CLI driver.

Each process reads its round-robin slice of the input part files, pads its
block to the common per-process row count with weight-0 rows, and assembles
GLOBAL batch-sharded arrays (``host_local_to_global``) over a mesh spanning
every process's devices — gradient reductions then cross processes as real
collectives, the reference's executor/treeAggregate topology with XLA
collectives in place of Spark (ValueAndGradientAggregator.scala:240-255).

Two runners live here. ``run_multiprocess_fixed_effect``: single
fixed-effect coordinate — regularization sweeps with warm starts,
validation selection, down-sampling, box constraints, variances,
normalization, warm start, per-config checkpoint/resume, and
RANDOM/BAYESIAN hyperparameter tuning. ``run_multiprocess_game``: [fixed,
random...] coordinate sequences through the cross-process entity exchange
of docs/DISTRIBUTED.md. Both require PREBUILT index maps
(``--off-heap-index-map-directory`` / feature-indexing driver output):
per-process maps built from data slices would diverge.

The parity bar (enforced by tests/test_multiprocess.py): an N-process run
must match the single-process driver's model numerically.

CPU-only surface for now: a chip belongs to one process at a time, so N
processes on one host run on the CPU backend (as the tests do). One process
drives all the chips of a host through ``GameEstimator(mesh=...)``.
"""

from __future__ import annotations

import dataclasses as _dc
import json
import os
from typing import Optional

import numpy as np

from photon_ml_tpu.types import NormalizationType, TaskType

MULTIPROC_DESIGN_POINTER = (
    "the fixed-effect-only multi-process runner covers exactly ONE "
    "fixed-effect coordinate (configurations with random effects route to "
    "the GAME runner's entity exchange; MULTIPLE fixed-effect coordinates "
    "have no multi-process path — docs/DISTRIBUTED.md)"
)


def multiprocess_fe_ineligibilities(args, coord_configs, index_maps) -> list[str]:
    """Why this configuration cannot train multi-process. Empty = eligible."""
    from photon_ml_tpu.estimators.config import FixedEffectDataConfiguration

    reasons: list[str] = []
    if len(coord_configs) != 1:
        reasons.append(MULTIPROC_DESIGN_POINTER)
    for cid, cfg in coord_configs.items():
        if not isinstance(cfg.data_config, FixedEffectDataConfiguration):
            reasons.append(MULTIPROC_DESIGN_POINTER)
            break
        if cfg.data_config.feature_shard_id not in index_maps:
            reasons.append(
                f"shard {cfg.data_config.feature_shard_id!r}: multi-process "
                "training requires PREBUILT index maps "
                "(--off-heap-index-map-directory; per-process maps built from "
                "data slices would diverge)"
            )
    if getattr(args, "partial_retrain_locked_coordinates", None):
        reasons.append("partial retrain with locked coordinates")
    if getattr(args, "compute_backend", "host") != "host":
        reasons.append("--compute-backend (the multi-process mesh is implicit)")
    if getattr(args, "evaluators", None):
        try:
            _resolve_validation_evaluators(args, args.training_task)
        except Exception as e:  # unknown spec, bad @K, ...
            reasons.append(f"unparseable --evaluators: {e}")
    return reasons



import functools


@functools.lru_cache(maxsize=None)
def _fe_variance_solver(task, vtype, mesh):
    """Jitted variance pass with REPLICATED output shardings (like
    sharded_glm_solver: propagation could otherwise leave the [D] result
    sharded across processes, making the host fetch fail on every rank).
    l2 and the normalization vectors are traced arguments, so a reg-weight
    sweep reuses one executable."""
    import jax

    from photon_ml_tpu.function.losses import loss_for_task
    from photon_ml_tpu.function.objective import GLMObjective
    from photon_ml_tpu.optimization.solver_cache import compute_variances
    from photon_ml_tpu.parallel.mesh import replicated_sharding

    loss = loss_for_task(TaskType(task))

    def solve(data, w_t, l2, norm):
        obj = GLMObjective(loss, norm, allow_fused=False)
        return compute_variances(obj, data, w_t, l2, vtype, w_t.dtype)

    return jax.jit(solve, out_shardings=replicated_sharding(mesh))


def _sharded_fe_variances(args, train_data, coeffs, opt_cfg, task, norm_ctx, mesh):
    """Coefficient variances for one fixed-effect result over the SHARDED
    data (DistributedOptimizationProblem.computeVariances:84-108): one jitted
    Hessian pass whose data reductions psum across the mesh. With
    normalization the Hessian is taken at the transformed-space optimum and
    the diagonal scales by factor^2 (the delta method, as in
    GLMOptimizationProblem.run). Returns None when variances are off."""
    from photon_ml_tpu.types import VarianceComputationType

    vtype = VarianceComputationType(
        getattr(args, "variance_computation_type", "NONE")
    )
    if vtype == VarianceComputationType.NONE:
        return None
    import jax.numpy as jnp

    from photon_ml_tpu.normalization import NO_NORMALIZATION

    norm = NO_NORMALIZATION if norm_ctx is None else norm_ctx
    w = jnp.asarray(coeffs)
    if not norm.is_identity:
        w = norm.to_transformed_space_device(w)

    solve = _fe_variance_solver(TaskType(task), vtype, mesh)
    variances = solve(
        train_data, w, jnp.asarray(opt_cfg.l2_weight, dtype=w.dtype), norm
    )
    if not norm.is_identity and norm.factors is not None:
        variances = variances * jnp.asarray(
            np.asarray(norm.factors), dtype=variances.dtype
        ) ** 2
    return np.asarray(variances)


def _mp_ckpt_fingerprint(args, nproc, coord_configs) -> str:
    """Run-configuration fingerprint: a resumed run must be the SAME run
    (data, configs, process topology) or the checkpoint is ignored."""
    import hashlib

    from photon_ml_tpu.cli.parsers import coordinate_configuration_to_string

    payload = json.dumps({
        "inputs": args.input_data_directories,
        "input_date_range": getattr(args, "input_data_date_range", None),
        "input_days_range": getattr(args, "input_data_days_range", None),
        "validation": getattr(args, "validation_data_directories", None),
        "validation_date_range": getattr(args, "validation_data_date_range", None),
        "validation_days_range": getattr(args, "validation_data_days_range", None),
        "model_input": getattr(args, "model_input_directory", None),
        "variances": getattr(args, "variance_computation_type", "NONE"),
        "evaluators": getattr(args, "evaluators", None),
        "tuning": getattr(args, "hyper_parameter_tuning", "NONE"),
        "tuning_iterations": getattr(args, "hyper_parameter_tuning_iterations", 0),
        "tuner": getattr(args, "hyper_parameter_tuner", None),
        "task": args.training_task,
        "nproc": nproc,
        "n_iter": args.coordinate_descent_iterations,
        "normalization": args.normalization,
        # bounds change the trained optimum: a resume across a changed
        # constraint map must be rejected, not silently mixed
        "box_constraints": getattr(args, "coefficient_box_constraints", None),
        "locked": sorted(_locked_coordinates(args)),
        "configs": {
            c: coordinate_configuration_to_string(c, cfg)
            for c, cfg in coord_configs.items()
        },
    }, sort_keys=True)
    return hashlib.blake2b(payload.encode(), digest_size=16).hexdigest()


def _mp_ckpt_fingerprint_of(path):
    """The fingerprint stored in one mp checkpoint file, or None when the
    file is absent/torn (then it is simply not a resume candidate — only a
    READABLE file with a DIFFERENT fingerprint warrants the explicit
    'fingerprint mismatch, restarting' operator message)."""
    try:
        with np.load(path, allow_pickle=False) as z:
            return str(z["fingerprint"][0])
    except Exception:
        return None


def _mp_ckpt_paths(directory, rank):
    base = os.path.join(directory, f"mp-game-r{rank:05d}")
    return base + ".npz", base + "-prev.npz"


def _mp_ckpt_write(path, out, logger, rotate_to=None):
    """Atomic (tmp + replace), RETRIED rank-local checkpoint write shared by
    the multi-process checkpointers: transient shared-filesystem OSErrors get
    bounded backoff+jitter (resilience/retry.py) instead of killing every
    rank of the job. ``rotate_to`` keeps one older generation: an existing
    ``path`` moves there before the new file lands (safe across retries — a
    re-attempt after the rotation simply finds no current file)."""
    from photon_ml_tpu.resilience import Retry

    def _attempt():
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            np.savez(f, **out)
        if rotate_to is not None and os.path.exists(path):
            os.replace(path, rotate_to)
        os.replace(tmp, path)

    Retry(max_attempts=3, base_delay=0.1, max_delay=2.0).call(
        _attempt, description=f"checkpoint write {os.path.basename(path)}"
    )


def _mp_clean_stale_tmp(directory, rank, logger):
    """Drop this rank's leaked ``*.tmp`` staging files (a crash mid-write
    leaves them next to the live checkpoint forever otherwise). Rank-scoped:
    peers' staging files may be live concurrent writes."""
    marker = f"-r{rank:05d}.npz.tmp"
    for name in sorted(os.listdir(directory)):
        if name.endswith(marker):
            logger.info("removing stale checkpoint staging file %s", name)
            try:
                os.remove(os.path.join(directory, name))
            except OSError:
                pass


class _MpFeCheckpointer:
    """Per-configuration checkpointing for the fixed-effect-only sweep: each
    completed configuration writes ONE immutable rank-local file (atomic
    tmp+replace); resume counts the consecutive fingerprint-matched files
    every rank can serve and skips that many configs, warm-starting from the
    last saved coefficients. No rotating live state is needed — the sweep's
    only cross-config state IS the last config's coefficients."""

    def __init__(self, directory, args, rank, nproc, coord_configs, logger):
        self.directory = directory
        self.rank, self.nproc = rank, nproc
        self.logger = logger
        self.fingerprint = _mp_ckpt_fingerprint(args, nproc, coord_configs)
        os.makedirs(directory, exist_ok=True)
        _mp_clean_stale_tmp(directory, rank, logger)

    def _path(self, j, rank=None):
        r = self.rank if rank is None else rank
        return os.path.join(self.directory, f"mp-fe-cfg{j:04d}-r{r:05d}.npz")

    def save(self, j, coeffs, variances, evals):
        out = {
            "fingerprint": np.asarray([self.fingerprint], dtype=str),
            "coeffs": np.asarray(coeffs),
            "vars": np.asarray(variances) if variances is not None else np.zeros(0),
            "meta": np.asarray([json.dumps(evals)], dtype=str),
        }
        _mp_ckpt_write(self._path(j), out, self.logger)
        self.logger.info("checkpointed config %d", j)

    def _valid(self, path):
        # torn/corrupt/absent files read as None, which never matches
        return _mp_ckpt_fingerprint_of(path) == self.fingerprint

    def resume_count(self, n_configs) -> int:
        """Consecutive leading configs EVERY rank has a valid file for —
        deterministic from the shared filesystem on every rank."""
        n = 0
        while n < n_configs and all(
            self._valid(self._path(n, r)) for r in range(self.nproc)
        ):
            n += 1
        # operators must be able to tell an INTENTIONAL invalidation (the
        # fingerprint now covers a changed config key, e.g. box_constraints)
        # from a lost checkpoint directory: files that exist but carry a
        # different fingerprint get an explicit restart message
        if n < n_configs:
            for r in range(self.nproc):
                path = self._path(n, r)
                fp = _mp_ckpt_fingerprint_of(path)
                if fp is not None and fp != self.fingerprint:
                    self.logger.warning(
                        "checkpoint fingerprint mismatch, restarting: %s was "
                        "written by a different run configuration (or an older "
                        "fingerprint schema) and is ignored", path,
                    )
                    break
        return n

    def load(self, j):
        with np.load(self._path(j), allow_pickle=False) as z:
            coeffs = np.asarray(z["coeffs"])
            variances = np.asarray(z["vars"]) if z["vars"].size else None
            evals = json.loads(str(z["meta"][0]))
        return coeffs, variances, evals


class _MpGameCheckpointer:
    """Rank-local checkpoint/resume for the multi-process GAME sweep.

    Every rank writes its own state atomically (tmp + os.replace) and keeps
    ONE previous generation. Ranks can be one pass apart when a job dies
    (the pass loop's exchanges keep them in lockstep otherwise), so resume
    picks the LATEST cursor for which EVERY rank has a state file (current
    or previous) — a deterministic decision every rank reaches identically
    from the shared filesystem. A fingerprint mismatch (different data,
    configs, nproc, ...) ignores the checkpoint and starts fresh.
    """

    def __init__(self, directory, args, rank, nproc, coord_configs, re_cids, logger):
        self.directory = directory
        self.rank, self.nproc = rank, nproc
        self.re_cids = list(re_cids)
        self.logger = logger
        self.interval = max(1, getattr(args, "checkpoint_interval", 1) or 1)
        # rank-independent (the rank lives in the FILENAME): every rank can
        # validate every peer file against the same expected value
        self.fingerprint = _mp_ckpt_fingerprint(args, nproc, coord_configs)
        os.makedirs(directory, exist_ok=True)
        _mp_clean_stale_tmp(directory, rank, logger)

    # ---- serialization ----------------------------------------------------
    def _pack_model(self, out, prefix, m):
        out[f"{prefix}:ids"] = np.asarray(m.entity_ids, dtype=str)
        out[f"{prefix}:coeffs"] = np.asarray(m.coeffs)
        out[f"{prefix}:proj"] = np.asarray(m.proj_indices)
        out[f"{prefix}:vars"] = (
            np.asarray(m.variances) if m.variances is not None else np.zeros((0, 0))
        )

    def _unpack_model(self, z, prefix, cid, coord_configs, task, projector):
        from photon_ml_tpu.models.game import RandomEffectModel

        import jax.numpy as jnp

        dc = coord_configs[cid].data_config
        var = z[f"{prefix}:vars"]
        return RandomEffectModel(
            re_type=dc.random_effect_type,
            feature_shard_id=dc.feature_shard_id,
            task=TaskType(task),
            entity_ids=tuple(str(x) for x in z[f"{prefix}:ids"]),
            coeffs=jnp.asarray(z[f"{prefix}:coeffs"]),
            proj_indices=jnp.asarray(z[f"{prefix}:proj"]),
            variances=jnp.asarray(var) if var.size else None,
            projector=projector,
        )

    def _cfg_path(self, j):
        return os.path.join(
            self.directory, f"mp-game-cfg{j:04d}-r{self.rank:05d}.npz"
        )

    def save_config(self, j, entry):
        """One IMMUTABLE snapshot per completed configuration — completed
        configs never change, so per-pass checkpoints need not re-serialize
        them (checkpoint I/O stays O(live state), not O(sweep length))."""
        out = {
            "fingerprint": np.asarray([self.fingerprint], dtype=str),
            "fe": np.asarray(entry["fe"]),
            "fe_vars": (
                np.asarray(entry["fe_vars"])
                if entry.get("fe_vars") is not None else np.zeros(0)
            ),
            "meta": np.asarray([json.dumps({
                "metric": entry["metric"],
                "value": entry["value"],
                "evaluations": entry["evaluations"],
                "auc": entry["auc"],
                # enough to reconstruct the entry's optimization configs on
                # resume (tuned candidates are NOT derivable from the sweep)
                "weights": {
                    c: cfg_.regularization_weight
                    for c, cfg_ in entry["configs"].items()
                },
                "alphas": {
                    c: cfg_.regularization_context.elastic_net_alpha
                    for c, cfg_ in entry["configs"].items()
                },
            })], dtype=str),
        }
        for cid in self.re_cids:
            if entry["re"].get(cid) is not None:
                self._pack_model(out, f"re:{cid}", entry["re"][cid])
        _mp_ckpt_write(self._cfg_path(j), out, self.logger)

    def save(self, i, p, fe_coeffs, fe_vars, re_models, re_scores_home,
             track, n_completed_configs):
        out = {
            "cursor": np.asarray([i, p], dtype=np.int64),
            "fingerprint": np.asarray([self.fingerprint], dtype=str),
            "n_configs": np.asarray([n_completed_configs], dtype=np.int64),
            "fe": np.asarray(fe_coeffs),
            "fe_vars": np.asarray(fe_vars) if fe_vars is not None else np.zeros(0),
            "meta": np.asarray([json.dumps({
                "track": {
                    "value": track["value"],
                    "metric": track["metric"],
                    "evaluations": track["evaluations"],
                },
            })], dtype=str),
        }
        for cid in self.re_cids:
            if re_models[cid] is not None:
                self._pack_model(out, f"re:{cid}", re_models[cid])
            out[f"sc:{cid}"] = np.asarray(re_scores_home[cid])
        if track["fe"] is not None:
            out["track:fe"] = np.asarray(track["fe"])
            out["track:fe_vars"] = (
                np.asarray(track["fe_vars"])
                if track["fe_vars"] is not None else np.zeros(0)
            )
            for cid in self.re_cids:
                if track["re"] and track["re"].get(cid) is not None:
                    self._pack_model(out, f"track:re:{cid}", track["re"][cid])
        cur, prev = _mp_ckpt_paths(self.directory, self.rank)
        _mp_ckpt_write(cur, out, self.logger, rotate_to=prev)
        self.logger.info("checkpointed config %d pass %d", i, p)

    # ---- resume -----------------------------------------------------------
    def _cursor_of(self, path):
        try:
            with np.load(path, allow_pickle=False) as z:
                fp = str(z["fingerprint"][0])
                i, p = (int(x) for x in z["cursor"])
            return (i, p), fp
        except Exception:  # torn/corrupt file: not a resume candidate
            return None, None

    def resume_cursor(self):
        """The latest (i, p) every rank can serve, or None. Deterministic:
        every rank scans the same shared files."""
        per_rank = []
        mismatched = None
        for r in range(self.nproc):
            cur, prev = _mp_ckpt_paths(self.directory, r)
            entries = {}
            for path in (cur, prev):
                if os.path.exists(path):
                    cursor, fp = self._cursor_of(path)
                    if cursor is not None and fp == self.fingerprint:
                        entries[cursor] = path
                    elif fp is not None and fp != self.fingerprint:
                        mismatched = path
            per_rank.append(entries)
        if not per_rank or any(not e for e in per_rank):
            if mismatched is not None:
                # distinguish an intentional invalidation (config/data change
                # reflected in the fingerprint) from a lost checkpoint dir
                self.logger.warning(
                    "checkpoint fingerprint mismatch, restarting: %s was "
                    "written by a different run configuration (or an older "
                    "fingerprint schema) and is ignored", mismatched,
                )
            return None
        common = set(per_rank[0])
        for e in per_rank[1:]:
            common &= set(e)
        if not common:
            return None
        return max(common)

    def load(self, cursor, coord_configs, task, coords):
        import jax.numpy as jnp

        cur, prev = _mp_ckpt_paths(self.directory, self.rank)
        path = None
        for cand in (cur, prev):
            if os.path.exists(cand):
                c, fp = self._cursor_of(cand)
                # fingerprint re-checked here: another run sharing the
                # directory could have rotated a same-cursor file into place
                if c == cursor and fp == self.fingerprint:
                    path = cand
                    break
        assert path is not None
        with np.load(path, allow_pickle=False) as z:
            keys = set(z.files)
            meta = json.loads(str(z["meta"][0]))
            n_configs = int(z["n_configs"][0])
            fe_coeffs = jnp.asarray(z["fe"])
            fe_vars = np.asarray(z["fe_vars"]) if z["fe_vars"].size else None
            re_models = {}
            re_scores_home = {}
            for cid in self.re_cids:
                projector = coords[cid].projector
                re_models[cid] = (
                    self._unpack_model(z, f"re:{cid}", cid, coord_configs, task, projector)
                    if f"re:{cid}:coeffs" in keys else None
                )
                re_scores_home[cid] = np.asarray(z[f"sc:{cid}"])
            track = {
                "value": meta["track"]["value"],
                "metric": meta["track"]["metric"],
                "evaluations": meta["track"]["evaluations"],
                "fe": np.asarray(z["track:fe"]) if "track:fe" in keys else None,
                "fe_vars": (
                    np.asarray(z["track:fe_vars"])
                    if "track:fe_vars" in keys and z["track:fe_vars"].size
                    else None
                ),
                "re": {
                    cid: self._unpack_model(
                        z, f"track:re:{cid}", cid, coord_configs, task,
                        coords[cid].projector,
                    )
                    for cid in self.re_cids
                    if f"track:re:{cid}:coeffs" in keys
                } if "track:fe" in keys else None,
            }
        per_config = []
        for j in range(n_configs):
            with np.load(self._cfg_path(j), allow_pickle=False) as z:
                assert str(z["fingerprint"][0]) == self.fingerprint
                ckeys = set(z.files)
                m = json.loads(str(z["meta"][0]))
                if "weights" not in m:
                    raise ValueError(
                        f"checkpoint config snapshot {self._cfg_path(j)} "
                        "predates per-config weight metadata; clear the "
                        "checkpoint directory to restart this run"
                    )
                configs = {}
                for c, base in coord_configs.items():
                    oc = base.optimization_config.with_weight(
                        float(m["weights"][c])
                    )
                    alpha = m.get("alphas", {}).get(c)
                    if alpha is not None:
                        oc = _dc.replace(
                            oc,
                            regularization_context=_dc.replace(
                                oc.regularization_context,
                                elastic_net_alpha=float(alpha),
                            ),
                        )
                    configs[c] = oc
                per_config.append({
                    "configs": configs,
                    "fe": np.asarray(z["fe"]),
                    "fe_vars": (
                        np.asarray(z["fe_vars"]) if z["fe_vars"].size else None
                    ),
                    "re": {
                        cid: self._unpack_model(
                            z, f"re:{cid}", cid, coord_configs, task,
                            coords[cid].projector,
                        )
                        for cid in self.re_cids
                        if f"re:{cid}:coeffs" in ckeys
                    },
                    "metric": m["metric"],
                    "value": m["value"],
                    "evaluations": m["evaluations"],
                    "auc": m["auc"],
                })
        return fe_coeffs, fe_vars, re_models, re_scores_home, track, per_config


def _locked_coordinates(args) -> set:
    """Locked-coordinate names from the CLI flag (whitespace-tolerant) — the
    ONE parse shared by eligibility and the runners."""
    raw = getattr(args, "partial_retrain_locked_coordinates", "") or ""
    return {c.strip() for c in raw.split(",") if c.strip()}


def _ranked_part_files(directories, date_range, days_range, rank, nproc):
    """THE multi-process file-assignment convention, in exactly one place:
    sorted container part files, round-robin sliced by rank. Both ingest
    (:func:`_read_file_slice`) and the down-sampling draw-key computation
    (:func:`_concat_order_ids`) derive from this — they MUST agree on which
    rows a rank holds, or the masks silently diverge from single-process.
    Returns (all_files, this rank's indices into all_files)."""
    from photon_ml_tpu.data import avro_io
    from photon_ml_tpu.util.date_range import resolve_input_paths

    paths = resolve_input_paths(directories, date_range, days_range)
    all_files = avro_io.container_files(paths)
    return all_files, list(range(len(all_files)))[rank::nproc]


def _read_file_slice(
    directories, date_range, days_range, what,
    shard_configs, index_maps, id_tags, rank, nproc, logger,
    ingest_workers=None,
):
    """Round-robin file-slice ingest shared by the multi-process paths.

    Returns ``(data, all_files, mine_idx)`` — the listing the ingest ACTUALLY
    used, so the down-sampling draw-key computation (:func:`_concat_order_ids`)
    can derive from the identical file set instead of re-listing the
    directory (a concurrent writer between two listings would silently shift
    every draw key)."""
    from photon_ml_tpu.data.game_data import GameInput
    from photon_ml_tpu.data.readers import read_merged_avro
    import scipy.sparse as sp

    all_files, mine_idx = _ranked_part_files(
        directories, date_range, days_range, rank, nproc
    )
    mine = [all_files[i] for i in mine_idx]
    logger.info(
        "process %d/%d reading %d of %d %s part files",
        rank, nproc, len(mine), len(all_files), what,
    )
    if not mine:
        shards = {s for s in index_maps}
        return GameInput(
            features={s: sp.csr_matrix((0, index_maps[s].size)) for s in shards},
            labels=np.zeros(0),
            id_columns={t: np.zeros(0, dtype=object) for t in id_tags},
        ), all_files, mine_idx
    data, _, _ = read_merged_avro(
        mine, shard_configs, index_maps, id_tags, ingest_workers=ingest_workers
    )
    return data, all_files, mine_idx


def _concat_order_ids(all_files, mine):
    """Each LOCAL row's position in the single-process concatenated row order
    — the down-sampling draw key (sampling/down_sampler.per_sample_uniform).

    ``(all_files, mine)`` is the listing the TRAINING ingest returned
    (:func:`_read_file_slice`), so rows and draw keys agree by construction —
    no second directory listing that a concurrent writer could shift.
    Every rank counts every part file from the container block framing alone
    (avro_io.container_row_count: O(blocks) seeks, no payload reads), so the
    global offsets are computed identically everywhere with no exchange."""
    from photon_ml_tpu.data import avro_io

    counts = np.asarray(
        [avro_io.container_row_count(f) for f in all_files], dtype=np.int64
    )
    offsets = np.zeros(len(all_files), dtype=np.int64)
    if len(all_files):
        offsets[1:] = np.cumsum(counts)[:-1]
    if not mine:
        return np.zeros(0, dtype=np.int64)
    return np.concatenate(
        [offsets[i] + np.arange(counts[i], dtype=np.int64) for i in mine]
    )


def _fe_down_sampler(cfg, task):
    """The fixed-effect coordinate's down-sampler, or None — the estimator's
    construction (game_estimator.build_coordinate) with the driver's fixed
    seed, built fresh per swept configuration exactly as the single-process
    sweep does."""
    from photon_ml_tpu.sampling.down_sampler import down_sampler_for_task

    if not (0.0 < cfg.down_sampling_rate < 1.0):
        return None
    return down_sampler_for_task(TaskType(task), cfg.down_sampling_rate, 0)


def _downsampled_weights_global(
    sampler, call, train, dsids_local, per_process, mesh, global_rows
):
    """One down-sampling pass over the HOME rows, assembled to the global
    batch-sharded weights vector. The draws are keyed by each row's position
    in the single-process concatenated order (``dsids_local``), so the global
    mask equals the single-process pass's mask exactly; pad rows keep weight
    0 (inert either way)."""
    import jax.numpy as jnp

    from photon_ml_tpu.parallel.distributed import host_local_to_global

    n_local = train.n
    w_new = np.zeros(per_process, dtype=np.float32)
    if n_local:
        w_new[:n_local] = np.asarray(
            sampler.reweight(
                jnp.asarray(np.asarray(train.labels), dtype=jnp.float32),
                jnp.asarray(np.asarray(train.weights), dtype=jnp.float32),
                jnp.asarray(dsids_local, dtype=jnp.uint32),
                call,
            )
        )
    return host_local_to_global(w_new, mesh, global_rows=global_rows)


def _fe_box_bounds(args, cfg, index_map, norm_ctx):
    """Per-feature (lower, upper) bound vectors for the fixed-effect solve,
    or None: coordinate-level bounds win, else the driver-level
    --coefficient-box-constraints map builds them against the shard's index
    map — the single-process driver's replacement
    (game_training_driver.py:425-436, GLMSuite.createConstraintFeatureMap).
    Bounds + normalization is rejected exactly like the single-process
    coordinate (Params.scala:211-214)."""
    bounds = cfg.box_constraints
    if bounds is None and getattr(args, "coefficient_box_constraints", None):
        from photon_ml_tpu.optimization.constraints import build_bound_vectors

        bounds = build_bound_vectors(
            args.coefficient_box_constraints, index_map
        )
    if bounds is None:
        return None
    if norm_ctx is not None and not norm_ctx.is_identity:
        raise ValueError("Box constraints and normalization cannot be combined")
    return bounds


def run_multiprocess_fixed_effect(
    args, rank: int, nproc: int, logger, root: str,
    task, coord_configs, shard_configs, index_maps,
) -> dict:
    """The multi-process fixed-effect training flow. Returns the driver's
    summary dict; only process 0 writes output."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.cli.game_training_driver import _save_result
    from photon_ml_tpu.estimators.game_estimator import GameResult
    from photon_ml_tpu.models.game import FixedEffectModel, GameModel
    from photon_ml_tpu.models.glm import Coefficients, GeneralizedLinearModel
    from photon_ml_tpu.parallel import make_mesh
    from photon_ml_tpu.util.timed import Timed

    reasons = multiprocess_fe_ineligibilities(args, coord_configs, index_maps)
    if reasons:
        raise NotImplementedError(
            "configuration not eligible for multi-process training: "
            + "; ".join(sorted(set(reasons)))
        )
    (cid, cfg), = coord_configs.items()
    shard = cfg.data_config.feature_shard_id
    evaluators = _resolve_validation_evaluators(args, args.training_task)
    from photon_ml_tpu.evaluation.evaluators import MultiEvaluator

    eval_tags = tuple(
        dict.fromkeys(
            ev.id_tag for ev in evaluators if isinstance(ev, MultiEvaluator)
        )
    )

    def read_slice(directories, date_range, days_range, what):
        return _read_file_slice(
            directories, date_range, days_range, what,
            shard_configs, index_maps,
            # per-group evaluator tags are consumed from VALIDATION rows only
            eval_tags if what == "validation" else (),
            rank, nproc, logger,
            ingest_workers=getattr(args, "ingest_workers", None),
        )

    from photon_ml_tpu.types import HyperparameterTuningMode

    tuning_mode = HyperparameterTuningMode(
        getattr(args, "hyper_parameter_tuning", "NONE") or "NONE"
    )
    if tuning_mode != HyperparameterTuningMode.NONE and not getattr(
        args, "validation_data_directories", None
    ):
        # the single-process driver's check, verbatim
        raise ValueError("Hyperparameter tuning requires validation data")

    # checkpoint resume decided BEFORE ingest: a fully-resumed sweep (every
    # config checkpointed, including tuned ones) never reads the training
    # data at all
    sweep = cfg.expand()
    n_total = len(sweep)
    if tuning_mode != HyperparameterTuningMode.NONE:
        n_total += args.hyper_parameter_tuning_iterations
    ckpt = None
    n_resumed = 0
    if getattr(args, "checkpoint_directory", None):
        ckpt = _MpFeCheckpointer(
            args.checkpoint_directory, args, rank, nproc, coord_configs, logger
        )
        n_resumed = ckpt.resume_count(n_total)
        if n_resumed:
            logger.info("resuming from checkpoint: %d configs done", n_resumed)
    fully_resumed = n_resumed == n_total
    # the data-summary artifact is recomputed every run (single-process
    # semantics): a FULLY-resumed summary-writing run still reads the
    # training slice and runs the stats pass, but skips everything else
    # (validation read, device assembly — zero configs will train)
    summary_only = fully_resumed and bool(
        getattr(args, "data_summary_directory", None)
    )

    train = train_data = norm_ctx = None
    val = None
    train_listing = ([], [])
    mesh = make_mesh(len(jax.devices()))
    if not fully_resumed or summary_only:
        with Timed("read training data", logger):
            train, *train_listing = read_slice(
                args.input_data_directories,
                getattr(args, "input_data_date_range", None),
                getattr(args, "input_data_days_range", None),
                "training",
            )
        from photon_ml_tpu.data.validators import DataValidationType, sanity_check_data

        if train.n:  # per-sample checks are slice-local per process
            with Timed("data validation", logger):
                sanity_check_data(
                    task,
                    train.labels,
                    offsets=train.offsets,
                    weights=train.weights,
                    feature_shards=train.features,
                    validation_type=DataValidationType(args.data_validation),
                )
        if args.validation_data_directories and not fully_resumed:
            with Timed("read validation data", logger):
                val, _, _ = read_slice(
                    args.validation_data_directories,
                    getattr(args, "validation_data_date_range", None),
                    getattr(args, "validation_data_days_range", None),
                    "validation",
                )
        if not fully_resumed:
            train_data, _ = _assemble_global(train, shard, mesh, logger)

        # global statistics -> transformed-space solves with original-space
        # coefficients in/out, exactly the single-process contract (+ the
        # --data-summary-directory artifact from the same stats pass)
        norm_ctx = _build_norm_contexts(
            args, train, [shard], index_maps, logger, rank
        ).get(shard)

    from photon_ml_tpu.parallel import train_glm_sharded

    results = []
    warm = None
    if getattr(args, "model_input_directory", None):
        # every rank loads the same model from the shared filesystem —
        # warm start needs no exchange (GameTrainingDriver.scala:370-409)
        from photon_ml_tpu.io.model_io import load_game_model

        with Timed("load initial model", logger):
            init = load_game_model(
                args.model_input_directory, {cid: index_maps[shard]}
            )
        fe_init = init.get_model(cid)
        # a saved model without this coordinate cold-starts it, matching the
        # single-process driver (game_estimator passes init=None through)
        warm = (
            np.asarray(fe_init.model.coefficients.means)
            if fe_init is not None
            else None
        )
    # selection identity comes from the evaluator list, independent of
    # whether validation was (re-)read this run: a FULLY-resumed sweep skips
    # the validation read but its checkpointed entries still carry values
    metric_name = evaluators[0].name
    larger = evaluators[0].larger_is_better

    def _restored_cfg(j, r_meta):
        """The optimization config a checkpointed entry was trained with:
        grid entries come from the sweep, tuned entries reconstruct from the
        checkpointed weight/alpha (not derivable from the sweep)."""
        if j < len(sweep):
            return sweep[j]
        if r_meta.get("weight") is None:
            raise ValueError(
                f"checkpoint config {j} is a tuned candidate but predates "
                "per-config weight metadata; clear the checkpoint directory "
                "to restart this run"
            )
        oc = cfg.optimization_config.with_weight(float(r_meta["weight"]))
        if r_meta.get("alpha") is not None:
            oc = _dc.replace(
                oc,
                regularization_context=_dc.replace(
                    oc.regularization_context,
                    elastic_net_alpha=float(r_meta["alpha"]),
                ),
            )
        return oc

    if ckpt is not None:
        for j in range(n_resumed):
            r_coeffs, r_vars, r_meta = ckpt.load(j)
            results.append((
                _restored_cfg(j, r_meta), r_coeffs, r_meta.get("value"), r_vars,
                r_meta.get("evaluations"),
            ))
            warm = r_coeffs

    sampler_rate_active = 0.0 < cfg.down_sampling_rate < 1.0
    n_iter = args.coordinate_descent_iterations
    bounds = lower = upper = None
    dsids_local = None
    if not fully_resumed:
        bounds = _fe_box_bounds(args, cfg, index_maps[shard], norm_ctx)
        if bounds is not None:
            lower, upper = bounds
        if sampler_rate_active:
            # keyed off the SAME listing the training ingest used
            dsids_local = _concat_order_ids(*train_listing)

    def evaluate(coeffs):
        if val is None:
            return None, None
        scores = _host_scores(val, shard, coeffs) + np.asarray(
            val.offsets, dtype=np.float64
        )
        evals = _gathered_evaluations(
            evaluators, scores,
            np.asarray(val.labels, dtype=np.float64),
            np.asarray(val.weights, dtype=np.float64),
            val.ids,
        )
        return evals[metric_name], evals

    def train_one(opt_cfg, warm_coeffs):
        """Train ONE configuration; returns (coeffs, value, variances, evals).

        Without down-sampling, one converged solve equals the single-process
        descent's n identical passes over one coordinate. With it, each CD
        pass draws a FRESH mask (DownSampler.down_sample per update), so the
        passes are emulated one by one — draw p's weights, warm-started
        solve, per-update validation tracking (every update is a selection
        candidate, CoordinateDescent.scala:256-289)."""
        if not sampler_rate_active:
            coeffs, _ = train_glm_sharded(
                train_data, task, opt_cfg, mesh,
                initial_coefficients=warm_coeffs, normalization=norm_ctx,
                lower_bounds=lower, upper_bounds=upper,
            )
            value, evals = evaluate(coeffs)
            variances = _sharded_fe_variances(
                args, train_data, coeffs, opt_cfg, task, norm_ctx, mesh
            )
            return np.asarray(coeffs), value, variances, evals

        sampler = _fe_down_sampler(cfg, task)
        global_rows = train_data.labels.shape[0]
        per_proc_rows = global_rows // nproc
        coeffs = warm_coeffs
        best = None  # (value, coeffs, call, evals)
        data_p = train_data
        for p in range(n_iter):
            w_p = _downsampled_weights_global(
                sampler, p, train, dsids_local, per_proc_rows, mesh, global_rows
            )
            data_p = _dc.replace(train_data, weights=w_p)
            coeffs, _ = train_glm_sharded(
                data_p, task, opt_cfg, mesh,
                initial_coefficients=coeffs, normalization=norm_ctx,
                lower_bounds=lower, upper_bounds=upper,
            )
            value, evals = evaluate(coeffs)
            if value is not None and (
                best is None
                or (value > best[0] if larger else value < best[0])
            ):
                best = (value, np.asarray(coeffs).copy(), p, evals)
        if best is not None:
            value, out_coeffs, best_p, evals = best
            if best_p != n_iter - 1:
                # variances belong to the pass that produced the snapshot:
                # rebuild its (deterministic) weights for the Hessian pass
                data_p = _dc.replace(
                    train_data,
                    weights=_downsampled_weights_global(
                        _fe_down_sampler(cfg, task), best_p, train,
                        dsids_local, per_proc_rows, mesh, global_rows,
                    ),
                )
        else:
            value, out_coeffs, evals = None, np.asarray(coeffs), None
        variances = _sharded_fe_variances(
            args, data_p, jnp.asarray(out_coeffs), opt_cfg, task, norm_ctx, mesh
        )
        return out_coeffs, value, variances, evals

    def _ckpt_meta(opt_cfg, value, evals):
        return {
            "value": value,
            "evaluations": evals,
            "weight": opt_cfg.regularization_weight,
            "alpha": opt_cfg.regularization_context.elastic_net_alpha,
        }

    for j, opt_cfg in enumerate(sweep):
        if j < n_resumed:
            continue
        with Timed(f"train lambda={opt_cfg.regularization_weight}", logger):
            coeffs, metric_value, variances, evals = train_one(opt_cfg, warm)
        warm = coeffs
        if evals is not None:
            logger.info(
                "lambda=%s validation %s",
                opt_cfg.regularization_weight,
                " ".join(f"{k}={v:.6f}" for k, v in evals.items()),
            )
        results.append((opt_cfg, coeffs, metric_value, variances, evals))
        if ckpt is not None:
            ckpt.save(j, coeffs, variances, _ckpt_meta(opt_cfg, metric_value, evals))

    # -- hyperparameter tuning (GameTrainingDriver.runHyperparameterTuning):
    # proposals are deterministic functions of the gathered observations, so
    # every rank trains identical candidates in lockstep (the GAME runner's
    # design); candidates COLD-start, as the single-process evaluation
    # function's fresh fits do
    tuned_start = len(sweep)
    if tuning_mode != HyperparameterTuningMode.NONE:
        from photon_ml_tpu.estimators.evaluation_function import (
            GameEstimatorEvaluationFunction,
        )
        from photon_ml_tpu.hyperparameter.tuner import build_tuner

        fn = GameEstimatorEvaluationFunction(
            estimator=None, data=None, validation_data=None,
            base_configs={cid: cfg.optimization_config},
            is_opt_max=larger,
        )
        observations = [
            (
                fn._scale_forward(fn.configuration_to_vector({cid: r_cfg})),
                (-v if larger else v),
            )
            for (r_cfg, _, v, _, _) in results
            if v is not None
        ]

        def mp_eval(candidate):
            configs = fn.vector_to_configuration(fn._scale_backward(candidate))
            opt_cfg = configs[cid]
            j = len(results)
            with Timed(f"tune lambda={opt_cfg.regularization_weight}", logger):
                coeffs, metric_value, variances, evals = train_one(opt_cfg, None)
            results.append((opt_cfg, coeffs, metric_value, variances, evals))
            if ckpt is not None:
                ckpt.save(
                    j, coeffs, variances, _ckpt_meta(opt_cfg, metric_value, evals)
                )
            return ((-metric_value if larger else metric_value), results[-1])

        n_restored_tuned = max(0, len(results) - tuned_start)
        remaining = args.hyper_parameter_tuning_iterations - n_restored_tuned
        if remaining > 0:
            tuner = build_tuner(getattr(args, "hyper_parameter_tuner", "ATLAS"))
            with Timed("hyperparameter tuning", logger):
                tuner.search(
                    remaining, fn.num_params, tuning_mode, mp_eval, observations,
                    # checkpoint-restored tuned candidates already consumed
                    # their Sobol draws; fast-forward past them
                    resumed=n_restored_tuned,
                )

    values = [r[2] for r in results]
    if results and all(v is not None for v in values):
        best_i = int(np.argmax(values) if larger else np.argmin(values))
    else:
        best_i = len(results) - 1  # no validation: last (weakest-reg) config
    logger.info("selected model %d of %d", best_i, len(results))

    # NOTE: the multi-process summary carries plain dicts (JSON-serializable,
    # written to <root>/summary.json), not the single-process path's
    # GameResult objects — the "multiprocess" key marks the shape
    summary = {
        "multiprocess": True,
        "results": [
            {
                "regularization_weight": c.regularization_weight,
                "auc": a if (a is not None and metric_name == "AUC") else None,
                "metric": metric_name if a is not None else None,
                "value": a,
                "evaluations": _e,
            }
            for c, _, a, _v, _e in results
        ],
        "best_index": best_i,
        "output_directory": root,
        "num_processes": nproc,
    }
    if rank == 0:
        from photon_ml_tpu.cli.parsers import ModelOutputMode

        def fe_result(entry):
            r_cfg, r_coeffs, r_value, r_vars, r_evals = entry
            glm = GeneralizedLinearModel(
                Coefficients(
                    jnp.asarray(r_coeffs),
                    None if r_vars is None else jnp.asarray(r_vars),
                ),
                TaskType(task),
            )
            model = GameModel(
                models={cid: FixedEffectModel(model=glm, feature_shard_id=shard)}
            )
            return GameResult(
                model=model,
                best_model=model,
                configuration={cid: r_cfg},
                evaluations=r_evals if r_evals else None,
                best_metric=r_value,
                descent=None,
            )

        output_mode = ModelOutputMode(args.output_mode)
        if output_mode != ModelOutputMode.NONE:
            _save_result(
                os.path.join(root, "best"), fe_result(results[best_i]),
                {cid: index_maps[shard]},
                coord_configs, args.model_sparsity_threshold, logger,
            )
            # models/<i>/ ranges follow the single-process driver
            # (GameTrainingDriver.scala:759-826): ALL saves everything,
            # EXPLICIT excludes tuned results, TUNED saves only them
            if output_mode == ModelOutputMode.ALL:
                save_range = range(len(results))
            elif output_mode == ModelOutputMode.EXPLICIT:
                save_range = range(tuned_start)
            elif output_mode == ModelOutputMode.TUNED:
                save_range = range(tuned_start, len(results))
            else:
                save_range = range(0)
            for i in save_range:
                _save_result(
                    os.path.join(root, "models", str(i)), fe_result(results[i]),
                    {cid: index_maps[shard]},
                    coord_configs, args.model_sparsity_threshold, logger,
                )
            os.makedirs(os.path.join(root, "index-maps"), exist_ok=True)
            index_maps[shard].save(os.path.join(root, "index-maps", f"{shard}.npz"))
        with open(os.path.join(root, "summary.json"), "w") as f:
            json.dump(summary, f, indent=2)
    from jax.experimental import multihost_utils

    # rank 0's writes complete before any process exits (a prompt exit would
    # tear down the distributed runtime under rank 0's collectives)
    multihost_utils.sync_global_devices("photon-multiproc-train-done")
    return summary


def _assemble_global(data, shard: str, mesh, logger):
    """Per-process GameInput slice -> global batch-sharded LabeledData.

    Blocks are padded to a common per-process row count with weight-0 rows
    (inert in every objective reduction) so the global row count divides
    evenly over the mesh. Sparse feature slices stay sparse: the COO triples
    (row indices rebased to GLOBAL sample ids) are padded per process to a
    common nnz count with zero-value entries (inert under scatter-add) and
    sharded over the nnz axis — the billion-feature regime of
    parallel/glm.py, assembled across processes.

    Returns (LabeledData, (n_local_real, pad_rows))."""
    import jax
    import jax.numpy as jnp
    import scipy.sparse as sp

    from jax.experimental import multihost_utils
    from photon_ml_tpu.data.dataset import LabeledData
    from photon_ml_tpu.data.matrix import DenseDesignMatrix, SparseDesignMatrix
    from photon_ml_tpu.parallel.distributed import host_local_to_global

    nproc = jax.process_count()
    X = data.shard(shard)
    n_local = data.n
    counts = np.asarray(
        multihost_utils.process_allgather(np.asarray([n_local]))
    ).ravel()
    devices_per_process = max(1, len(jax.local_devices()))
    dev_counts = np.asarray(
        multihost_utils.process_allgather(np.asarray([devices_per_process]))
    ).ravel()
    if len(set(int(c) for c in dev_counts)) != 1:
        # the padding target below must be computed identically everywhere;
        # heterogeneous local device counts would give processes conflicting
        # global shapes (a hang or shape-mismatch deep in array assembly)
        raise ValueError(
            f"multi-process training requires the same local device count on "
            f"every process, got {dev_counts.tolist()}"
        )
    per_process = -(-int(counts.max()) // devices_per_process) * devices_per_process
    pad = per_process - n_local
    global_rows = per_process * nproc
    logger.info(
        "global assembly: local %d rows (+%d pad), %d processes x %d rows",
        n_local, pad, nproc, per_process,
    )

    def assemble_vec(v, fill=0.0):
        out = np.full(per_process, fill, dtype=np.float32)
        out[:n_local] = np.asarray(v, dtype=np.float32)
        return host_local_to_global(out, mesh, global_rows=global_rows)

    if sp.issparse(X):
        coo = X.tocoo()
        nnz_counts = np.asarray(
            multihost_utils.process_allgather(np.asarray([coo.nnz]))
        ).ravel()
        per_nnz = -(-int(nnz_counts.max()) // devices_per_process) * devices_per_process
        base = jax.process_index() * per_process
        rows = np.zeros(per_nnz, dtype=np.int32)
        cols = np.zeros(per_nnz, dtype=np.int32)
        vals = np.zeros(per_nnz, dtype=np.float32)
        rows[: coo.nnz] = coo.row.astype(np.int32) + base
        cols[: coo.nnz] = coo.col.astype(np.int32)
        vals[: coo.nnz] = coo.data.astype(np.float32)
        global_nnz = per_nnz * nproc
        Xg = SparseDesignMatrix(
            rows=host_local_to_global(rows, mesh, global_rows=global_nnz),
            cols=host_local_to_global(cols, mesh, global_rows=global_nnz),
            vals=host_local_to_global(vals, mesh, global_rows=global_nnz),
            n_rows=global_rows,
            n_cols=X.shape[1],
        )
        logger.info(
            "sparse assembly: local nnz %d (+%d pad) over %d columns",
            coo.nnz, per_nnz - coo.nnz, X.shape[1],
        )
    else:
        dense = np.asarray(X, dtype=np.float32)
        Xp = np.zeros((per_process, dense.shape[1]), dtype=np.float32)
        Xp[:n_local] = dense
        Xg = DenseDesignMatrix(
            host_local_to_global(Xp, mesh, global_rows=global_rows)
        )

    return (
        LabeledData(
            X=Xg,
            labels=assemble_vec(data.labels if data.has_labels else np.zeros(n_local)),
            offsets=assemble_vec(data.offsets),
            weights=assemble_vec(data.weights),
        ),
        (n_local, pad),
    )


def multiprocess_game_ineligibilities(args, coord_configs, index_maps) -> list[str]:
    """Why this GAME configuration cannot train multi-process. Empty = OK.

    The GAME flow adds random-effect coordinates to the fixed-effect path:
    samples route to entity OWNER processes through the filesystem shuffle
    (parallel/shuffle.py), owners solve their entities locally, and residual
    scores travel home per coordinate update — the reference's per-iteration
    score-exchange joins (CoordinateDescent.scala:197-204) over the shared
    filesystem instead of Spark."""
    from photon_ml_tpu.estimators.config import (
        FixedEffectDataConfiguration,
        RandomEffectDataConfiguration,
    )

    reasons: list[str] = []
    ids = list(coord_configs)
    if not ids or not isinstance(
        coord_configs[ids[0]].data_config, FixedEffectDataConfiguration
    ):
        reasons.append("the first coordinate must be the fixed effect")
    for cid in ids[1:]:
        dc = coord_configs[cid].data_config
        if not isinstance(dc, RandomEffectDataConfiguration):
            reasons.append(f"coordinate {cid!r}: only [fixed, random...] sequences")
            continue
        pw = coord_configs[cid].per_entity_reg_weights
        if pw is not None and not isinstance(pw, dict):
            # the array form binds to a dataset's entity ORDER; owners hold
            # arbitrary entity subsets, so no global order exists to align to
            reasons.append(
                f"coordinate {cid!r}: per-entity reg weights must be a "
                "{entity_id: weight} dict for multi-process training "
                "(the [E]-array form has no global entity order to bind to)"
            )
    for cid, cfg in coord_configs.items():
        if cfg.data_config.feature_shard_id not in index_maps:
            reasons.append(
                f"shard {cfg.data_config.feature_shard_id!r}: multi-process "
                "training requires PREBUILT index maps"
            )
    locked = _locked_coordinates(args)
    if locked:
        if not getattr(args, "model_input_directory", None):
            reasons.append(
                "locked coordinates require --model-input-directory "
                "(the locked models must come from somewhere)"
            )
        unknown = set(locked) - set(ids)
        if unknown:
            reasons.append(
                f"locked coordinates not in the update sequence: {sorted(unknown)}"
            )
        if set(locked) >= set(ids):
            reasons.append("every coordinate is locked: nothing to train")
    # the flag-level restrictions are identical to the fixed-effect path
    # (minus partial retrain, which the GAME path handles)
    fe_only = {ids[0]: coord_configs[ids[0]]} if ids else {}
    for r in multiprocess_fe_ineligibilities(args, fe_only, index_maps):
        if (
            r not in reasons
            and r != MULTIPROC_DESIGN_POINTER
            and not r.startswith("partial retrain")
        ):
            reasons.append(r)
    if (
        getattr(args, "hyper_parameter_tuning", "NONE") not in (None, "NONE")
        and not getattr(args, "validation_data_directories", None)
    ):
        reasons.append("hyperparameter tuning requires validation data")
    return reasons


def _spill_re_rows_sparse(
    spill, tag, X_re, owner_of_local, home_ids, gids_local, labels, weights,
    rank, nproc, extra_cols=None,
):
    """Spill one coordinate's rows toward their entity owners: per-sample
    metadata on ``tag`` and the feature matrix as COO triples on ``tag``-x.
    Exchange volume is O(nnz), independent of shard width."""
    import scipy.sparse as sp

    from photon_ml_tpu.parallel.shuffle import exchange_rows

    coo = (X_re if sp.issparse(X_re) else sp.coo_matrix(np.asarray(X_re))).tocoo()
    n_entries = len(coo.data)
    entry_owner = (
        owner_of_local[coo.row] if n_entries else np.zeros(0, dtype=np.int64)
    )
    exchange_rows(
        spill, f"{tag}-x", entry_owner, np.zeros(n_entries, dtype=object),
        {
            "gid": gids_local[coo.row] if n_entries else np.zeros(0, np.int64),
            "col": coo.col.astype(np.int64),
            "val": coo.data.astype(np.float64),
        },
        rank, nproc,
    )
    cols = {"gid": gids_local, "label": labels, "weight": weights}
    cols.update(extra_cols or {})
    exchange_rows(spill, tag, owner_of_local, home_ids, cols, rank, nproc)


def _collect_re_rows_sparse(spill, tag, width, rank, nproc):
    """Collect both halves of :func:`_spill_re_rows_sparse` (after the
    barrier): returns (entity_ids, gids, X csr [n, width], metadata cols)."""
    import scipy.sparse as sp

    from photon_ml_tpu.parallel.shuffle import collect_exchanged_rows

    own_ids, own = collect_exchanged_rows(os.path.join(spill, tag), rank, nproc)
    _, ent = collect_exchanged_rows(os.path.join(spill, f"{tag}-x"), rank, nproc)
    gids = own["gid"].astype(np.int64)
    order = np.argsort(gids, kind="stable")
    ent_gid = ent["gid"].astype(np.int64)
    rowpos = (
        order[np.searchsorted(gids[order], ent_gid)]
        if len(ent_gid)
        else np.zeros(0, dtype=np.int64)
    )
    X = sp.csr_matrix(
        (ent["val"], (rowpos, ent["col"].astype(np.int64))),
        shape=(len(own_ids), width),
    )
    return own_ids, gids, X, own


def _re_score_rows(model, X_rows, entity_ids) -> np.ndarray:
    """Score arbitrary CSR rows against a RandomEffectModel on the host:
    per-entity coefficients scatter into a sparse [E+1, width] matrix (last
    row = zeros for entities without a model), then score = rowwise
    elementwise-product sum. O(nnz) — used for per-update validation scoring
    on entity owners."""
    import scipy.sparse as sp

    n, width = X_rows.shape
    if n == 0:
        return np.zeros(0)
    coeffs = np.asarray(model.coeffs, dtype=np.float64)
    proj = np.asarray(model.proj_indices)
    E = coeffs.shape[0]
    er, slot = np.nonzero(proj >= 0)
    M = sp.csr_matrix(
        (coeffs[er, slot], (er, proj[er, slot].astype(np.int64))),
        shape=(E + 1, width),
    )
    rows_idx = np.asarray(
        [model.row_for_entity(e) for e in entity_ids], dtype=np.int64
    )
    sel = np.where(rows_idx >= 0, rows_idx, E)
    return np.asarray(X_rows.multiply(M[sel]).sum(axis=1)).ravel()


def run_multiprocess_game(
    args, rank: int, nproc: int, logger, root: str,
    task, coord_configs, shard_configs, index_maps,
) -> dict:
    """Multi-process GAME training: sharded fixed-effect solves + owner-local
    random-effect solves + per-update residual score exchanges."""
    import jax
    import jax.numpy as jnp
    import scipy.sparse as sp

    from photon_ml_tpu.algorithm.random_effect import train_random_effect
    from photon_ml_tpu.cli.game_training_driver import _save_result
    from photon_ml_tpu.data.random_effect import build_random_effect_dataset
    from photon_ml_tpu.data.validators import DataValidationType, sanity_check_data
    from photon_ml_tpu.estimators.config import RandomEffectDataConfiguration
    from photon_ml_tpu.estimators.game_estimator import GameResult
    from photon_ml_tpu.estimators.config import expand_game_configurations
    from photon_ml_tpu.models.game import FixedEffectModel, GameModel, RandomEffectModel
    from photon_ml_tpu.models.glm import Coefficients, GeneralizedLinearModel
    from photon_ml_tpu.parallel import make_mesh, train_glm_sharded
    from photon_ml_tpu.parallel.shuffle import (
        collect_exchanged_rows,
        entity_owner_hash,
        exchange_rows,
        shuffle_barrier,
    )
    from photon_ml_tpu.util.timed import Timed

    reasons = multiprocess_game_ineligibilities(args, coord_configs, index_maps)
    if reasons:
        raise NotImplementedError(
            "configuration not eligible for multi-process GAME training: "
            + "; ".join(sorted(set(reasons)))
        )
    from photon_ml_tpu.types import VarianceComputationType

    vtype = VarianceComputationType(
        getattr(args, "variance_computation_type", "NONE")
    )
    coord_ids = list(coord_configs)
    fe_cid, re_cids = coord_ids[0], coord_ids[1:]
    # partial retrain (CoordinateDescent.scala:45 ModelCoordinate semantics):
    # locked coordinates contribute scores every pass, are never re-optimized,
    # and carry their loaded models into the saved result
    locked = _locked_coordinates(args)
    fe_shard = coord_configs[fe_cid].data_config.feature_shard_id
    evaluators = _resolve_validation_evaluators(args, args.training_task)
    from photon_ml_tpu.evaluation.evaluators import MultiEvaluator

    id_tags = sorted(
        {coord_configs[c].data_config.random_effect_type for c in re_cids}
        | {ev.id_tag for ev in evaluators if isinstance(ev, MultiEvaluator)}
    )
    spill = os.path.join(root, "_shuffle")


    def read_slice(directories, date_range, days_range, what):
        return _read_file_slice(
            directories, date_range, days_range, what,
            shard_configs, index_maps, id_tags, rank, nproc, logger,
            ingest_workers=getattr(args, "ingest_workers", None),
        )

    with Timed("read training data", logger):
        train, *train_listing = read_slice(
            args.input_data_directories,
            getattr(args, "input_data_date_range", None),
            getattr(args, "input_data_days_range", None),
            "training",
        )
    if train.n:
        with Timed("data validation", logger):
            sanity_check_data(
                task, train.labels, offsets=train.offsets, weights=train.weights,
                feature_shards=train.features,
                validation_type=DataValidationType(args.data_validation),
            )
    # one global NormalizationContext per DISTINCT shard (FE + RE): statistics
    # reduce over each process's HOME rows, so the union covers every sample
    # exactly once regardless of the entity exchange that follows (+ the
    # --data-summary-directory artifact from the same stats pass)
    norm_ctxs = _build_norm_contexts(
        args, train,
        sorted({coord_configs[c].data_config.feature_shard_id for c in coord_ids}),
        index_maps, logger, rank,
    )
    mesh = make_mesh(len(jax.devices()))
    fe_train, layout = _assemble_global(train, fe_shard, mesh, logger)
    n_local, _pad = layout
    per_process = fe_train.labels.shape[0] // nproc
    gid_base = rank * per_process
    gids_local = np.arange(n_local, dtype=np.int64) + gid_base

    # fixed-effect down-sampling + box constraints (both FE-coordinate-only,
    # exactly as the single-process estimator applies them)
    fe_cfg = coord_configs[fe_cid]
    fe_bounds = _fe_box_bounds(
        args, fe_cfg, index_maps[fe_shard], norm_ctxs.get(fe_shard)
    )
    fe_lower, fe_upper = fe_bounds if fe_bounds is not None else (None, None)
    fe_sampler = _fe_down_sampler(fe_cfg, task)
    # keyed off the SAME listing the training ingest used
    dsids_local = (
        _concat_order_ids(*train_listing) if fe_sampler is not None else None
    )

    # ---- per-coordinate entity exchange (ingest; once) ------------------------
    class RECoord:
        pass

    coords: dict[str, RECoord] = {}
    for cid in re_cids:
        dc: RandomEffectDataConfiguration = coord_configs[cid].data_config
        c = RECoord()
        c.shard = dc.feature_shard_id
        c.home_ids = np.asarray(train.ids(dc.random_effect_type), dtype=object)
        c.owner_of_local = (
            entity_owner_hash(c.home_ids) % np.uint64(nproc)
        ).astype(np.int64) if n_local else np.zeros(0, dtype=np.int64)
        # RE feature rows travel as COO triples, never dense: the exchange
        # volume is O(nnz) regardless of shard width, so arbitrarily wide
        # sparse shards work (RandomEffectDataset.scala:46-508's shuffle is
        # likewise sparse-record-shaped). Triples ride their own exchange tag
        # keyed by global sample id; the owner reassembles CSR rows.
        _spill_re_rows_sparse(
            spill, f"{cid}-ingest", train.shard(c.shard), c.owner_of_local,
            c.home_ids, gids_local,
            np.asarray(train.labels, dtype=np.float64) if train.has_labels else np.zeros(n_local),
            np.asarray(train.weights, dtype=np.float64),
            rank, nproc,
        )
        coords[cid] = c
    shuffle_barrier("ingest")

    for cid, c in coords.items():
        own_ids, c.gids_own, X_own, own = _collect_re_rows_sparse(
            spill, f"{cid}-ingest", index_maps[c.shard].size, rank, nproc
        )
        dc = coord_configs[cid].data_config
        # shared random projection: the matrix is a pure function of
        # (config seed, dim), so every process builds the identical
        # projector with no cross-process state (game_estimator._projector_for)
        from photon_ml_tpu.data.projector import make_projector

        c.norm = norm_ctxs.get(c.shard)
        # with a projector, normalization rides ON the projector so training
        # and scoring datasets agree on the projected space (the estimator's
        # _projector_for discipline)
        c.projector = make_projector(
            dc.projector, index_maps[c.shard].size,
            normalization=c.norm,
        ) if dc.projector is not None else None
        with Timed(f"build RE dataset {cid} ({len(own_ids)} rows)", logger):
            c.ds = build_random_effect_dataset(
                X_own,
                own_ids,
                dc.random_effect_type,
                feature_shard_id=dc.feature_shard_id,
                active_data_upper_bound=dc.active_data_upper_bound,
                active_data_lower_bound=dc.active_data_lower_bound,
                features_max=dc.features_max,
                labels=own["label"],
                weights=own["weight"],
                intercept_index=(
                    c.norm.intercept_index
                    if c.norm is not None and c.projector is None
                    else None
                ),
                normalization=c.norm if c.projector is None else None,
                dtype=jnp.float32,
                projector=c.projector,
            )
        c.home_of_own = c.gids_own // per_process

    # ---- sweep: warm-started coordinate descent -------------------------------
    def send_scores(tag, gids, scores, home_of, n_dest_local, dest_base):
        """Owner -> home score return; gives the home-aligned [n] array."""
        exchange_rows(
            spill, tag, home_of, np.zeros(len(gids), dtype=object),
            {"gid": gids, "s": np.asarray(scores, dtype=np.float64)},
            rank, nproc,
        )
        shuffle_barrier(tag)
        _, got = collect_exchanged_rows(os.path.join(spill, tag), rank, nproc)
        out = np.zeros(n_dest_local)
        out[got["gid"].astype(np.int64) - dest_base] = got["s"]
        return out

    def send_offsets(tag, c, partial_home):
        """Home -> owner residual offsets, aligned to the owner's dataset rows."""
        exchange_rows(
            spill, tag, c.owner_of_local, c.home_ids,
            {"gid": gids_local, "o": np.asarray(partial_home, dtype=np.float64)},
            rank, nproc,
        )
        shuffle_barrier(tag)
        _, got = collect_exchanged_rows(os.path.join(spill, tag), rank, nproc)
        aligned = np.zeros(len(c.gids_own))
        order = np.argsort(c.gids_own)
        pos = order[np.searchsorted(c.gids_own[order], got["gid"].astype(np.int64))]
        aligned[pos] = got["o"]
        return aligned

    # ---- validation ingest (per-update selection, CoordinateDescent.scala:256-289)
    has_val = bool(getattr(args, "validation_data_directories", None))
    val_coords: dict[str, RECoord] = {}
    if has_val:
        with Timed("read validation data", logger):
            val, _, _ = read_slice(
                args.validation_data_directories,
                getattr(args, "validation_data_date_range", None),
                getattr(args, "validation_data_days_range", None),
                "validation",
            )
        # validation rows never ride the device mesh here (scoring is
        # host-side, _host_scores); only the common padded per-process row
        # count is needed for the gid space
        from jax.experimental import multihost_utils

        n_val_local = val.n
        val_counts = np.asarray(
            multihost_utils.process_allgather(np.asarray([n_val_local]))
        ).ravel()
        block = int(val_counts.max())
        per_process_val = ((block + mesh.devices.size - 1) // mesh.devices.size) * mesh.devices.size
        vgid_base = rank * per_process_val
        vgids_local = np.arange(n_val_local, dtype=np.int64) + vgid_base
        for cid in re_cids:
            dcv = coord_configs[cid].data_config
            vc = RECoord()
            vc.shard = dcv.feature_shard_id
            vc.home_ids = np.asarray(val.ids(dcv.random_effect_type), dtype=object)
            vc.owner_of_local = (
                entity_owner_hash(vc.home_ids) % np.uint64(nproc)
            ).astype(np.int64) if n_val_local else np.zeros(0, dtype=np.int64)
            _spill_re_rows_sparse(
                spill, f"{cid}-val", val.shard(vc.shard), vc.owner_of_local,
                vc.home_ids, vgids_local,
                np.zeros(n_val_local), np.zeros(n_val_local), rank, nproc,
            )
            val_coords[cid] = vc
        shuffle_barrier("val-ingest")
        for cid, vc in val_coords.items():
            vc.ids_own, vc.gids_own, vc.X_own, _ = _collect_re_rows_sparse(
                spill, f"{cid}-val", index_maps[vc.shard].size, rank, nproc
            )
            vc.home_of_own = vc.gids_own // per_process_val
        val_base_off = np.asarray(val.offsets, dtype=np.float64)
        val_labels = np.asarray(val.labels, dtype=np.float64)
        val_weights = np.asarray(val.weights, dtype=np.float64)

    base_off_home = np.asarray(train.offsets, dtype=np.float64)
    sweep = expand_game_configurations(coord_configs)
    n_iter = args.coordinate_descent_iterations
    fe_coeffs = None
    fe_vars = None
    last_fe_data = None
    re_models = {cid: None for cid in re_cids}
    re_scores_home = {cid: np.zeros(n_local) for cid in re_cids}

    imaps_by_coord = {
        c: index_maps[coord_configs[c].data_config.feature_shard_id]
        for c in coord_ids
    }
    ckpt = None
    resume_cursor = None
    if getattr(args, "checkpoint_directory", None):
        ckpt = _MpGameCheckpointer(
            args.checkpoint_directory, args, rank, nproc, coord_configs,
            re_cids, logger,
        )
        resume_cursor = ckpt.resume_cursor()
        if resume_cursor is not None:
            logger.info(
                "resuming from checkpoint: config %d pass %d", *resume_cursor
            )
    # resume overwrites everything the warm-start block would compute (and
    # its exchanges are all-rank, so the skip is rank-consistent: the resume
    # decision is deterministic from the shared files)
    if resume_cursor is None and getattr(args, "model_input_directory", None):
        # warm start (GameTrainingDriver.scala:370-409): every rank loads the
        # same saved model; each owner keeps ONLY its own entities' rows
        # (aligned_to its dataset — a full model on every rank would put each
        # entity into nproc model parts at save), and the warm models' scores
        # seed the first fixed-effect residual as in single-process descent.
        # Coordinates absent from the saved model cold-start, matching the
        # single-process driver.
        from photon_ml_tpu.io.model_io import load_game_model

        with Timed("load initial model", logger):
            init_model = load_game_model(
                args.model_input_directory, imaps_by_coord
            )
        fe_init = init_model.get_model(fe_cid)
        if fe_init is None and fe_cid in locked:
            raise ValueError(
                f"locked coordinate {fe_cid!r} is missing from the input model"
            )
        if fe_init is not None:
            fe_coeffs = jnp.asarray(
                np.asarray(fe_init.model.coefficients.means), dtype=jnp.float32
            )
            if fe_init.model.coefficients.variances is not None:
                fe_vars = np.asarray(fe_init.model.coefficients.variances)
        for cid in re_cids:
            c = coords[cid]
            warm_re = init_model.get_model(cid)
            if warm_re is None:
                if cid in locked:
                    raise ValueError(
                        f"locked coordinate {cid!r} is missing from the input model"
                    )
                continue
            if warm_re.projector is None and c.projector is not None:
                raise ValueError(
                    f"coordinate {cid!r}: cannot warm-start a random-"
                    "projection coordinate from an original-space model"
                )
            if cid in locked:
                # LOCKED: the model passes through VERBATIM (ModelCoordinate
                # semantics) — entities absent from the retrain data must
                # survive in the save. score_dataset aligns transiently.
                re_models[cid] = warm_re
                own_scores = np.asarray(warm_re.score_dataset(c.ds))
            else:
                # plain warm start: each owner keeps only ITS entities' rows
                # (a full copy per rank would save each entity nproc times
                # through tracked snapshots)
                re_models[cid] = warm_re.aligned_to(c.ds)
                own_scores = np.asarray(re_models[cid].score_dataset(c.ds))
            re_scores_home[cid] = send_scores(
                f"warm{cid}-sc", c.gids_own, own_scores,
                c.home_of_own, n_local, gid_base,
            )

    _origin_cache: dict = {}

    def _validation_metric_now(tagbase):
        """Full-model validation evaluations (the run's evaluator list,
        FIRST = primary, direction-aware) with the CURRENT coefficients:
        fixed effect scored locally on each process's validation block,
        random effects scored on their entity owners and sent home (unseen
        entities score 0 — the reference's behavior)."""
        fe_val_home = _host_scores(val, fe_shard, fe_coeffs)
        total = val_base_off + fe_val_home
        for vcid in re_cids:
            vc = val_coords[vcid]
            vmodel = re_models[vcid]
            if vmodel is not None and vmodel.projector is not None:
                # _re_score_rows scatters per-entity coefficients by GLOBAL
                # column id; a projected model's slots index the projected
                # space, so score via its exact back-projection — computed
                # once per trained model, not once per tracked update
                cached = _origin_cache.get(vcid)
                if cached is None or cached[0] is not vmodel:
                    _origin_cache[vcid] = (vmodel, vmodel.to_original_space())
                vmodel = _origin_cache[vcid][1]
            own_scores = (
                _re_score_rows(vmodel, vc.X_own, vc.ids_own)
                if vmodel is not None
                else np.zeros(len(vc.gids_own))
            )
            total = total + send_scores(
                f"{tagbase}{vcid}-vs", vc.gids_own, own_scores,
                vc.home_of_own, n_val_local, vgid_base,
            )
        evals = _gathered_evaluations(
            evaluators, total, val_labels, val_weights, val.ids
        )
        primary = evaluators[0]
        return primary.name, evals[primary.name], primary.larger_is_better, evals

    per_config = []
    resumed_track = None
    if resume_cursor is not None:
        (fe_coeffs, fe_vars, re_models, re_scores_home, resumed_track,
         per_config) = ckpt.load(resume_cursor, coord_configs, task, coords)

    # a locked fixed effect never changes: score its contribution once
    # (AFTER any resume load — the locked coefficients come from there when
    # the warm-start block was skipped)
    fe_home_locked = (
        _host_scores(train, fe_shard, fe_coeffs) if fe_cid in locked else None
    )
    def _train_config(i, opt_configs, track):
        """Train ONE configuration (all CD passes, per-update tracking,
        checkpointing) and append its per_config entry — shared by the grid
        sweep and the hyperparameter-tuning loop."""
        nonlocal fe_coeffs, fe_vars, last_fe_data

        def _track(tagbase):
            if not has_val:
                return
            if any(re_models[c_] is None for c_ in re_cids):
                # a snapshot before every coordinate has trained once is not
                # a saveable GAME model; candidates start at the first update
                # that completes the coordinate set
                return
            name, value, larger, evals = _validation_metric_now(tagbase)
            logger.debug("update %s validation %s=%.6f", tagbase, name, value)
            better = (
                track["value"] is None
                or (value > track["value"] if larger else value < track["value"])
            )
            if better:
                track.update(
                    value=value,
                    metric=name,
                    evaluations=evals,
                    fe=np.asarray(fe_coeffs).copy(),
                    fe_vars=None if fe_vars is None else np.asarray(fe_vars).copy(),
                    re={c_: re_models[c_] for c_ in re_cids},
                )

        for p in range(n_iter):
            if (
                resume_cursor is not None
                and i == resume_cursor[0]
                and p <= resume_cursor[1]
            ):
                continue  # pass completed before the checkpoint
            if fe_cid not in locked:
                # fixed effect: residual = base + sum of RE scores
                off_home = base_off_home + sum(re_scores_home.values())
                off_pad = np.zeros(per_process)
                off_pad[:n_local] = off_home
                from photon_ml_tpu.parallel.distributed import host_local_to_global

                fe_data = dataclasses_replace_offsets(fe_train, host_local_to_global(
                    off_pad.astype(np.float32), mesh,
                    global_rows=fe_train.labels.shape[0],
                ))
                if fe_sampler is not None:
                    # fresh mask per CD pass (call index = p; the single-
                    # process sampler is rebuilt per config, so its counter
                    # is the pass index), keyed by concat-order sample
                    # positions — the multi-process masks equal the single-
                    # process run's exactly
                    fe_data = _dc.replace(
                        fe_data,
                        weights=_downsampled_weights_global(
                            fe_sampler, p, train, dsids_local, per_process,
                            mesh, fe_train.labels.shape[0],
                        ),
                    )
                with Timed(f"cfg{i} pass{p} fixed-effect solve", logger):
                    fe_coeffs, _ = train_glm_sharded(
                        fe_data, task, opt_configs[fe_cid], mesh,
                        initial_coefficients=fe_coeffs,
                        normalization=norm_ctxs.get(fe_shard),
                        lower_bounds=fe_lower, upper_bounds=fe_upper,
                    )
                if has_val:
                    # per-update variances ride the update, as in the single-
                    # process coordinate (the saved snapshot keeps its own);
                    # without validation only the config-final model is saved,
                    # so per-update Hessian passes would be thrown away
                    fe_vars = _sharded_fe_variances(
                        args, fe_data, fe_coeffs, opt_configs[fe_cid], task,
                        norm_ctxs.get(fe_shard), mesh,
                    )
                _track(f"c{i}p{p}fe-")
                last_fe_data = fe_data
            if fe_home_locked is None:
                fe_home = _host_scores(train, fe_shard, fe_coeffs)
            else:
                fe_home = fe_home_locked
            for cid in re_cids:
                if cid in locked:
                    # scored (re_scores_home keeps the warm contribution),
                    # never re-optimized
                    continue
                c = coords[cid]
                partial = base_off_home + fe_home + sum(
                    s for k, s in re_scores_home.items() if k != cid
                )
                off_own = send_offsets(f"c{i}p{p}{cid}-off", c, partial)
                with Timed(f"cfg{i} pass{p} {cid} solve", logger):
                    model, _tracker = train_random_effect(
                        c.ds, task, opt_configs[cid], jnp.asarray(off_own, jnp.float32),
                        initial_model=re_models[cid], dtype=jnp.float32,
                        variance_computation=vtype,
                        # normalization folds per bucket; models stay in
                        # original space (the projector carries it instead
                        # for projected coordinates)
                        normalization=c.norm if c.projector is None else None,
                        # dict entries resolve against the owner's own entity
                        # set; absent entities keep the config weight
                        per_entity_reg_weights=coord_configs[cid].per_entity_reg_weights,
                    )
                re_models[cid] = model
                own_scores = np.asarray(model.score_dataset(c.ds))
                re_scores_home[cid] = send_scores(
                    f"c{i}p{p}{cid}-sc", c.gids_own, own_scores,
                    c.home_of_own, n_local, gid_base,
                )
                _track(f"c{i}p{p}{cid}-")
            if (
                not has_val
                and p + 1 == n_iter
                and fe_cid not in locked
                and last_fe_data is not None
            ):
                # config-final variances (the only saved model on the no-
                # validation branch) — computed BEFORE the config-end
                # checkpoint so a resume lands with the right values
                fe_vars = _sharded_fe_variances(
                    args, last_fe_data, fe_coeffs, opt_configs[fe_cid], task,
                    norm_ctxs.get(fe_shard), mesh,
                )
            if ckpt is not None and (
                (p + 1) % ckpt.interval == 0 or p + 1 == n_iter
            ):
                ckpt.save(
                    i, p, fe_coeffs, fe_vars, re_models, re_scores_home,
                    track, len(per_config),
                )
        if has_val:
            logger.info(
                "cfg%d best per-update validation %s=%.6f",
                i, track["metric"], track["value"],
            )
            per_config.append({
                "configs": opt_configs,
                "fe": track["fe"],
                "fe_vars": track["fe_vars"],
                "re": track["re"],
                "metric": track["metric"],
                "value": track["value"],
                "evaluations": track["evaluations"],
                "auc": track["value"] if track["metric"] == "AUC" else None,
            })
        else:
            per_config.append({
                "configs": opt_configs,
                "fe": np.asarray(fe_coeffs),
                "fe_vars": None if fe_vars is None else np.asarray(fe_vars),
                "re": {cid: re_models[cid] for cid in re_cids},
                "metric": None,
                "value": None,
                "evaluations": None,
                "auc": None,
            })
        if ckpt is not None:
            ckpt.save_config(len(per_config) - 1, per_config[-1])

    for i, opt_configs in enumerate(sweep):
        if resume_cursor is not None and i < len(per_config):
            continue  # config fully finished before the checkpoint
        # per-update best-snapshot tracking within this configuration — the
        # single-process CoordinateDescent's selection semantics
        # (CoordinateDescent.scala:256-289): every coordinate update is a
        # selection candidate, not just the configuration's final state
        if resumed_track is not None and resume_cursor is not None and i == resume_cursor[0]:
            track = resumed_track
            resumed_track = None
        else:
            track = {
                "value": None, "metric": None, "evaluations": None, "fe": None,
                "fe_vars": None, "re": None,
            }
        _train_config(i, opt_configs, track)

    # -- hyperparameter tuning (GameTrainingDriver.runHyperparameterTuning) --
    # The GP/random proposals are deterministic functions of (observations,
    # seed), and every rank observes IDENTICAL gathered metric values, so all
    # ranks propose and train the same candidates in lockstep — no extra
    # coordination needed beyond the training exchanges themselves.
    from photon_ml_tpu.types import HyperparameterTuningMode

    tuned_start = len(sweep)
    tuning_mode = HyperparameterTuningMode(
        getattr(args, "hyper_parameter_tuning", "NONE") or "NONE"
    )
    if tuning_mode != HyperparameterTuningMode.NONE and has_val:
        from photon_ml_tpu.estimators.evaluation_function import (
            GameEstimatorEvaluationFunction,
        )
        from photon_ml_tpu.hyperparameter.tuner import build_tuner

        is_max = evaluators[0].larger_is_better
        fn = GameEstimatorEvaluationFunction(
            estimator=None, data=None, validation_data=None,
            base_configs={c: coord_configs[c].optimization_config
                          for c in coord_ids},
            is_opt_max=is_max,
        )
        observations = [
            (
                fn._scale_forward(fn.configuration_to_vector(e["configs"])),
                (-e["value"] if is_max else e["value"]),
            )
            for e in per_config
            if e["value"] is not None
        ]

        def mp_eval(candidate):
            nonlocal resumed_track, fe_coeffs, fe_vars
            configs = fn.vector_to_configuration(fn._scale_backward(candidate))
            j = len(per_config)
            if (
                resumed_track is not None
                and resume_cursor is not None
                and j == resume_cursor[0]
            ):
                # the job died mid-tuned-config; the GP re-proposed the same
                # candidate (identical observations), so its per-update best
                # snapshot resumes exactly like a grid config's would (the
                # cold-start below already happened before the checkpoint)
                track_j = resumed_track
                resumed_track = None
            else:
                track_j = {
                    "value": None, "metric": None, "evaluations": None,
                    "fe": None, "fe_vars": None, "re": None,
                }
                # tuned candidates COLD-start (locked coordinates keep their
                # loaded models): the single-process evaluation function runs
                # a fresh fit per candidate, not a warm continuation
                # (estimators/evaluation_function.py _fit_with)
                if fe_cid not in locked:
                    fe_coeffs = None
                    fe_vars = None
                for cid_ in re_cids:
                    if cid_ not in locked:
                        re_models[cid_] = None
                        re_scores_home[cid_] = np.zeros(n_local)
            _train_config(j, configs, track_j)
            entry = per_config[-1]
            return (
                (-entry["value"] if is_max else entry["value"]),
                entry,
            )

        # a resume that restored finished tuned entries runs only the
        # REMAINING iterations (the restored entries already feed the GP
        # through `observations`, and the tuner fast-forwards its Sobol
        # stream past the draws they consumed)
        n_restored_tuned = max(0, len(per_config) - tuned_start)
        remaining = args.hyper_parameter_tuning_iterations - n_restored_tuned
        tuner = build_tuner(getattr(args, "hyper_parameter_tuner", "ATLAS"))
        if remaining > 0:
            with Timed("hyperparameter tuning", logger):
                tuner.search(
                    remaining,
                    fn.num_params,
                    tuning_mode,
                    mp_eval,
                    observations,
                    resumed=n_restored_tuned,
                )

    if has_val:
        values = [r["value"] for r in per_config]
        larger = evaluators[0].larger_is_better
        best_i = int(np.argmax(values) if larger else np.argmin(values))
    else:
        best_i = len(per_config) - 1  # no validation: last (weakest-reg) config
    logger.info("selected model %d of %d", best_i, len(per_config))
    summary = {
        "multiprocess": True,
        "results": [
            {
                "regularization_weight": {
                    cid: r["configs"][cid].regularization_weight for cid in coord_ids
                },
                "auc": r["auc"],
                "metric": r["metric"],
                "value": r["value"],
                "evaluations": r["evaluations"],
            }
            for r in per_config
        ],
        "best_index": best_i,
        "output_directory": root,
        "num_processes": nproc,
    }

    # ---- assemble + save models (rank 0) --------------------------------------
    # ModelOutputMode (GameTrainingDriver.scala:759-826): BEST writes best/
    # only; ALL additionally writes models/<i>/ per trained configuration,
    # EXPLICIT excludes tuned results, TUNED saves only them; NONE writes no
    # model (summary.json still lands).
    from photon_ml_tpu.cli.parsers import ModelOutputMode

    output_mode = ModelOutputMode(args.output_mode)
    save_tuned = output_mode == ModelOutputMode.TUNED
    model_dir = os.path.join(spill, "model-parts")
    os.makedirs(model_dir, exist_ok=True)
    # (tag, config index, output dirs): parts are written once per config
    # tag — best/ reuses its own config's parts rather than serializing the
    # same (possibly millions-of-entities) tables twice
    to_save: list = []
    if output_mode != ModelOutputMode.NONE:
        if output_mode == ModelOutputMode.ALL:
            save_indices = list(range(len(per_config)))
        elif output_mode == ModelOutputMode.EXPLICIT:
            # EXPLICIT deliberately EXCLUDES tuned results, as single-process
            # (GameTrainingDriver.scala:759-826 save semantics)
            save_indices = sorted({*range(tuned_start), best_i})
        elif save_tuned:
            save_indices = sorted({*range(tuned_start, len(per_config)), best_i})
        else:
            save_indices = [best_i]
        for i in save_indices:
            dirs = []
            if i == best_i:
                dirs.append(os.path.join(root, "best"))
            if (
                output_mode == ModelOutputMode.ALL
                or (output_mode == ModelOutputMode.EXPLICIT and i < tuned_start)
                or (save_tuned and i >= tuned_start)
            ):
                dirs.append(os.path.join(root, "models", str(i)))
            to_save.append((f"cfg{i}", i, dirs))
    for tag, idx, _ in to_save:
        for cid in re_cids:
            if cid in locked:
                continue  # identical verbatim model on every rank: no parts
            m = per_config[idx]["re"][cid]
            np.savez(
                os.path.join(model_dir, f"{cid}-{tag}-part{rank:05d}.npz"),
                entity_ids=np.asarray(m.entity_ids, dtype=str),
                coeffs=np.asarray(m.coeffs),
                proj=np.asarray(m.proj_indices),
                variances=np.asarray(m.variances)
                if m.variances is not None
                else np.zeros((0, 0)),
            )
    shuffle_barrier("model-parts")

    def _assemble_result(tag, entry) -> "GameResult":
        glm = GeneralizedLinearModel(
            Coefficients(
                jnp.asarray(entry["fe"]),
                None if entry.get("fe_vars") is None
                else jnp.asarray(entry["fe_vars"]),
            ),
            TaskType(task),
        )
        models = {fe_cid: FixedEffectModel(model=glm, feature_shard_id=fe_shard)}
        for cid in re_cids:
            if cid in locked:
                # verbatim pass-through of the loaded locked model
                models[cid] = entry["re"][cid]
                continue
            parts = []
            for r in range(nproc):
                with np.load(
                    os.path.join(model_dir, f"{cid}-{tag}-part{r:05d}.npz")
                ) as z:
                    parts.append({k: z[k] for k in z.files})
            k_max = max(int(p["coeffs"].shape[1]) if p["coeffs"].size else 1 for p in parts)
            has_vars = any(p["variances"].size for p in parts)
            ids_all, coeff_rows, proj_rows, var_rows = [], [], [], []
            for part in parts:
                e = len(part["entity_ids"])
                ids_all.extend(str(x) for x in part["entity_ids"])
                cpad = np.zeros((e, k_max), dtype=np.float32)
                ppad = np.full((e, k_max), -1, dtype=np.int32)
                vpad = np.zeros((e, k_max), dtype=np.float32)
                if e:
                    k = part["coeffs"].shape[1]
                    cpad[:, :k] = part["coeffs"]
                    ppad[:, :k] = part["proj"]
                    if part["variances"].size:
                        vpad[:, :k] = part["variances"]
                coeff_rows.append(cpad)
                proj_rows.append(ppad)
                var_rows.append(vpad)
            dc = coord_configs[cid].data_config
            models[cid] = RandomEffectModel(
                re_type=dc.random_effect_type,
                feature_shard_id=dc.feature_shard_id,
                task=TaskType(task),
                entity_ids=tuple(ids_all),
                coeffs=jnp.asarray(np.concatenate(coeff_rows) if ids_all else np.zeros((0, 1))),
                proj_indices=jnp.asarray(
                    np.concatenate(proj_rows) if ids_all else np.full((0, 1), -1, np.int32)
                ),
                variances=jnp.asarray(np.concatenate(var_rows))
                if has_vars and ids_all
                else None,
                # the ONE projector instance training used (built at ingest)
                projector=coords[cid].projector,
            )
        game_model = GameModel(models={c: models[c] for c in coord_ids})
        return GameResult(
            model=game_model, best_model=game_model,
            configuration=entry["configs"],
            evaluations=entry.get("evaluations")
            or ({entry["metric"]: entry["value"]} if entry["value"] is not None else None),
            best_metric=entry["value"], descent=None,
        )

    if rank == 0:
        for tag, idx, out_dirs in to_save:
            result = _assemble_result(tag, per_config[idx])
            for out_dir in out_dirs:
                _save_result(
                    out_dir, result, imaps_by_coord,
                    coord_configs, args.model_sparsity_threshold, logger,
                )
        if to_save:
            os.makedirs(os.path.join(root, "index-maps"), exist_ok=True)
            for shard in {c.data_config.feature_shard_id for c in coord_configs.values()}:
                index_maps[shard].save(os.path.join(root, "index-maps", f"{shard}.npz"))
        with open(os.path.join(root, "summary.json"), "w") as f:
            json.dump(summary, f, indent=2)
    shuffle_barrier("train-done")
    if rank == 0:
        # every rank is past its last read (the barrier above): the spills
        # are scratch, not output
        import shutil

        shutil.rmtree(spill, ignore_errors=True)
    return summary


def dataclasses_replace_offsets(data, offsets):
    return _dc.replace(data, offsets=offsets)


def _build_norm_contexts(args, train, shard_ids, index_maps, logger, rank=0) -> dict:
    """{shard: NormalizationContext} from GLOBAL statistics for each shard —
    the one construction both multi-process runners share. Empty when
    normalization is off.

    ``--data-summary-directory`` rides the same pass: each needed shard's
    statistics are reduced ONCE (per-rank column sums meeting in a host
    allgather) and feed both the normalization context and the per-shard
    FeatureSummarizationResultAvro (game_training_driver.py:407-417 /
    ModelProcessingUtils.writeBasicStatistics:516-606; rank 0 writes).
    The shard iteration order is deterministic (sorted) — EVERY rank must
    execute the collectives identically."""
    norm_type = NormalizationType(args.normalization)
    summary_dir = getattr(args, "data_summary_directory", None)
    if norm_type == NormalizationType.NONE and not summary_dir:
        return {}
    from photon_ml_tpu.normalization import NormalizationContext
    from photon_ml_tpu.util.timed import Timed

    norm_shards = set(shard_ids) if norm_type != NormalizationType.NONE else set()
    # the summary covers every configured shard, as single-process does
    shards = sorted(norm_shards | (set(train.features) if summary_dir else set()))
    out = {}
    for shard_id in shards:
        with Timed(f"global feature statistics [{shard_id}]", logger):
            stats = _global_feature_stats(
                train, shard_id, index_maps[shard_id].intercept_index
            )
        if summary_dir and rank == 0:
            from photon_ml_tpu.cli.game_training_driver import (
                SUMMARY_FILE,
                _write_feature_summary,
            )

            _write_feature_summary(
                os.path.join(summary_dir, f"{shard_id}-{SUMMARY_FILE}"),
                shard_id, index_maps[shard_id], stats,
            )
        if shard_id in norm_shards:
            out[shard_id] = NormalizationContext.build(norm_type, stats)
    return out


def _global_feature_stats(game_input, shard: str, intercept_index):
    """FeatureDataStatistics over the GLOBAL dataset from per-process slices:
    each process reduces its own rows to per-column sums (sparse-safe, zeros
    contribute implicitly) and the sums meet in a host allgather — the
    multi-process form of MultivariateOnlineSummarizer. Matches
    FeatureDataStatistics.compute on the concatenated data exactly (sample
    variance, ddof=1)."""
    import scipy.sparse as sp

    from jax.experimental import multihost_utils
    from photon_ml_tpu.normalization import FeatureDataStatistics

    X = game_input.shard(shard)
    n_local, d = X.shape
    if sp.issparse(X):
        Xc = X.tocsc()
        if Xc.dtype != np.float64:
            # squares and sums in float64: the variance cancellation
            # s2 - n*mean^2 goes catastrophically wrong in f32 when
            # |mean| >> std (and f32 squares already lose digits at ~1e4)
            Xc = Xc.astype(np.float64)
        s1 = np.asarray(Xc.sum(axis=0)).ravel()
        s2 = np.asarray(Xc.multiply(Xc).sum(axis=0)).ravel()
        sabs = np.asarray(abs(Xc).sum(axis=0)).ravel()
        nnz = np.diff(Xc.indptr).astype(np.float64)
        # vectorized per-column min/max over stored values — the same
        # reduceat-with-empty-column-guard as FeatureDataStatistics._compute_sparse
        mins = np.zeros(d)
        maxs = np.zeros(d)
        if n_local:
            nonempty = nnz > 0
            if Xc.nnz:
                safe_starts = np.minimum(Xc.indptr[:-1], Xc.nnz - 1)
                col_min = np.minimum.reduceat(Xc.data, safe_starts)
                col_max = np.maximum.reduceat(Xc.data, safe_starts)
                mins[nonempty] = col_min[nonempty]
                maxs[nonempty] = col_max[nonempty]
            has_implicit_zero = nnz < n_local
            mins = np.where(has_implicit_zero, np.minimum(mins, 0.0), mins)
            maxs = np.where(has_implicit_zero, np.maximum(maxs, 0.0), maxs)
    else:
        Xd = np.asarray(X, dtype=np.float64)
        s1 = Xd.sum(axis=0)
        s2 = (Xd * Xd).sum(axis=0)
        sabs = np.abs(Xd).sum(axis=0)
        nnz = (Xd != 0).sum(axis=0).astype(np.float64)
        mins = Xd.min(axis=0) if n_local else np.zeros(d)
        maxs = Xd.max(axis=0) if n_local else np.zeros(d)
    if n_local == 0:
        # inert aggregands; min/max use infinities so empty slices never win
        mins = np.full(d, np.inf)
        maxs = np.full(d, -np.inf)
    parts = multihost_utils.process_allgather(
        (np.asarray([float(n_local)]), s1, s2, sabs, nnz, mins, maxs)
    )
    # some jax versions return single-process allgathers WITHOUT the leading
    # process axis; normalize every part to [P, ...] so the axis-0 reductions
    # below reduce over processes, never over features
    counts, s1g, s2g, sabsg, nnzg, minsg, maxsg = (
        np.asarray(x).reshape(-1, *ref.shape)
        for x, ref in zip(parts, (np.empty(1), s1, s2, sabs, nnz, mins, maxs))
    )
    n = float(counts.sum())
    if n < 1:
        raise ValueError("Cannot compute feature statistics over zero samples")
    mean = s1g.sum(axis=0) / n
    var = (
        (s2g.sum(axis=0) - n * mean**2) / (n - 1.0)
        if n > 1
        else np.zeros(d)
    )
    return FeatureDataStatistics(
        count=int(n),
        mean=mean,
        variance=np.maximum(var, 0.0),
        min=minsg.min(axis=0),
        max=maxsg.max(axis=0),
        num_nonzeros=nnzg.sum(axis=0),
        mean_abs=sabsg.sum(axis=0) / n,
        intercept_index=intercept_index,
    )


def _host_scores(game_input, shard: str, coeffs) -> np.ndarray:
    """This process's rows of X @ coeffs, computed HOST-SIDE from its own
    file slice.

    Never slice ``addressable_shards`` of a distributed matvec for this: if
    XLA returns the result replicated (it may, and did), every process's
    "local block" aliases the TOP of the global array — rank r>0 silently
    reads rank 0's rows. Caught by the GAME parity tests once their
    random-effect features became non-trivial: every rank's residual offsets
    paired other ranks' fixed-effect scores with its own labels."""
    X = game_input.shard(shard)
    w = np.asarray(coeffs, dtype=np.float64)
    return np.asarray(X @ w).ravel()


def _gather_blocks(*arrays):
    """Host-allgather variable-length per-process blocks, padded with
    weight-0 rows (inert in every weighted statistic). Dtypes are
    preserved (group-key arrays ride along with the float triples)."""
    from jax.experimental import multihost_utils

    n = np.asarray([len(arrays[0])])
    counts = np.asarray(multihost_utils.process_allgather(n)).ravel()
    m = int(counts.max()) if len(counts) else 0

    def pad(v):
        v = np.asarray(v)
        out = np.zeros(m, dtype=v.dtype if v.dtype.kind in "if" else np.float64)
        out[: len(v)] = v
        return out

    # the gather pads each process block to the max length; DROP the padding
    # rows afterwards (their positions are known exactly from the counts) —
    # sentinel values would corrupt ranking metrics (a padding score in a
    # PRECISION@K top-K) or weighted ones (0 * inf = NaN in RMSE)
    keep = np.concatenate([
        np.arange(m, dtype=np.int64) < c for c in counts
    ]) if m else np.zeros(0, dtype=bool)
    return tuple(
        np.asarray(x).reshape(-1)[keep]
        for x in multihost_utils.process_allgather(tuple(pad(v) for v in arrays))
    )


def _resolve_validation_evaluators(args, task):
    """The validation evaluator list, FIRST = primary (the single-process
    suite's convention): parsed --evaluators specs, or the task's default."""
    from photon_ml_tpu.cli.parsers import parse_evaluator_spec
    from photon_ml_tpu.estimators.game_estimator import default_evaluator_type
    from photon_ml_tpu.evaluation.evaluators import evaluator_for_type

    raw = getattr(args, "evaluators", None)
    if raw:
        specs = [parse_evaluator_spec(e) for e in raw.split(",") if e.strip()]
        if not specs:
            raise ValueError(f"--evaluators {raw!r} names no evaluators")
        return specs
    return [evaluator_for_type(default_evaluator_type(TaskType(task)))]


def _group_keys(ids) -> np.ndarray:
    """Entity-id strings -> int32 group keys for the gathered per-group
    evaluators. Only group EQUALITY matters; blake2-derived 31-bit keys make
    collisions negligible at realistic group counts and stay exact through
    the x64-disabled allgather."""
    from photon_ml_tpu.parallel.shuffle import entity_owner_hash

    if len(ids) == 0:
        return np.zeros(0, dtype=np.int32)
    return (entity_owner_hash(ids) % np.uint64(2**31)).astype(np.int32)


def _gathered_evaluations(evaluators, scores, labels, weights, id_lookup):
    """{evaluator name: value} over the gathered validation set. Per-group
    evaluators (MultiEvaluator, e.g. AUC:userId / PRECISION@K:id) gather
    their group keys alongside the score triples; padding rows carry weight
    0 and their all-padding groups evaluate to NaN, which evaluate_grouped
    skips."""
    from photon_ml_tpu.evaluation.evaluators import MultiEvaluator

    tags = []
    for ev in evaluators:
        if isinstance(ev, MultiEvaluator) and ev.id_tag not in tags:
            tags.append(ev.id_tag)
    arrays = [scores, labels, weights]
    arrays += [_group_keys(id_lookup(tag)) for tag in tags]
    gathered = _gather_blocks(*arrays)
    sg, lg, wg = gathered[:3]
    groups = dict(zip(tags, gathered[3:]))
    out = {}
    for ev in evaluators:
        if isinstance(ev, MultiEvaluator):
            out[ev.name] = float(
                ev.evaluate_grouped(sg, lg, wg, groups[ev.id_tag])
            )
        else:
            out[ev.name] = float(ev.evaluate(sg, lg, wg))
    return out
