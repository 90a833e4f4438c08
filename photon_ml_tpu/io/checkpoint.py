"""Generational, integrity-checked checkpoint/resume for coordinate descent.

The reference delegates failure recovery to Spark (RDD lineage recomputation +
DISK_ONLY persistence, CoordinateDescent.scala:130-160); it checkpoints models
only at the end of a full run (ModelProcessingUtils.saveGameModelToHDFS:77-141).
A single-controller JAX program has no lineage to replay, so recovery is
explicit — and *verified*:

- After every completed coordinate-descent iteration the full GAME model state
  (current models, best-model snapshot, best metric, incident history) is
  written as a NEW generation ``<dir>/gen-<n>/``: one ``.npz`` per coordinate
  (raw arrays, no pickling) plus a ``state.json`` manifest carrying a SHA-256
  checksum of every artifact, with the manifest's own checksum in a sidecar.
  Writes land in a ``gen-<n>.tmp`` staging dir renamed into place, so a crash
  at any instruction never damages an existing generation.
- ``load_checkpoint`` verifies every checksum and ROLLS BACK: a torn or
  bit-rotted generation is quarantined (renamed ``gen-<n>.corrupt``) with a
  logged incident, and restore proceeds from the newest generation that
  verifies — never a crash, never a silent load of bad data. The last
  ``keep_generations`` generations are retained for exactly this.
- Transient I/O errors (OSError) retry with exponential backoff + jitter
  (resilience/retry.py); the write path is instrumented with fault points
  (``checkpoint.write.arrays`` / ``.manifest`` / ``.commit``,
  ``checkpoint.restore``) so every failure window is replayable
  (resilience/faultpoints.py, tests/test_chaos.py).

Training scores are pure functions of the models, so nothing else needs
saving: resume reinitializes from the checkpointed models and recomputes
scores exactly (bit-identical resume, tests/test_checkpoint.py). This is the
*internal* fast format — final model export still uses the
reference-compatible BayesianLinearModelAvro layout (io/model_io.py).

Legacy layout (pre-generational: ``state.json`` directly in the checkpoint
directory, ``.old`` sibling from the old overwrite dance) is still read, with
the same never-raise contract: an unreadable legacy checkpoint is quarantined
and restore falls back (to ``.old``, else to a fresh start).
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import re
import shutil
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.models.game import FixedEffectModel, GameModel, RandomEffectModel
from photon_ml_tpu.models.glm import Coefficients, model_class_for_task
from photon_ml_tpu.resilience import (
    Retry,
    corrupt_file,
    faultpoint,
    register_fault_point,
)
from photon_ml_tpu.resilience.incidents import Incident
from photon_ml_tpu.types import TaskType

logger = logging.getLogger(__name__)

STATE_FILE = "state.json"
STATE_SHA_FILE = "state.json.sha256"
BEST_DIR = "best"
AUX_DIR = "aux"
GEN_PREFIX = "gen-"
QUARANTINE_SUFFIX = ".corrupt"
DEFAULT_KEEP_GENERATIONS = 3
_TMP_SUFFIX = ".tmp"
_GEN_RE = re.compile(r"^gen-(\d{8})$")
_FORMAT = 2

FP_WRITE_ARRAYS = register_fault_point("checkpoint.write.arrays")
FP_WRITE_MANIFEST = register_fault_point("checkpoint.write.manifest")
FP_WRITE_COMMIT = register_fault_point("checkpoint.write.commit")
FP_RESTORE = register_fault_point("checkpoint.restore")

# checkpoint I/O rides a shared-filesystem in production: transient OSErrors
# get a bounded, jittered retry instead of killing the run
_DEFAULT_RETRY = Retry(max_attempts=3, base_delay=0.05, max_delay=1.0)


class CheckpointCorruption(Exception):
    """A generation failed integrity verification (internal control flow:
    load_checkpoint converts it into quarantine + rollback, never raises it)."""


# ---------------------------------------------------- reduced-dtype encoding
# np.save writes ml_dtypes arrays (bfloat16) as raw |V2 void: loading one back
# silently reinterprets the table bytes. Every .npz this module writes goes
# through _encode_arrays, which stores such arrays as their uint16 bit
# patterns next to a self-describing "__dtype__<name>" marker, so a bf16
# deployment's generational checkpoints round-trip BIT-EXACTLY and fleet
# replicas can load them. Native dtypes (incl. float16) pass through
# untouched — the marker only exists where np.save would lie.

_DTYPE_MARKER = "__dtype__"
_BITS_ENCODED_DTYPES = ("bfloat16",)


def _encode_arrays(arrays: dict) -> dict:
    out = {}
    for name, arr in arrays.items():
        arr = np.asarray(arr)
        if str(arr.dtype) in _BITS_ENCODED_DTYPES:
            out[name] = arr.view(np.uint16)
            out[_DTYPE_MARKER + name] = np.asarray(str(arr.dtype))
        else:
            out[name] = arr
    return out


def _decode_arrays(arrays: dict) -> dict:
    out = {k: v for k, v in arrays.items() if not k.startswith(_DTYPE_MARKER)}
    for key, marker in arrays.items():
        if not key.startswith(_DTYPE_MARKER):
            continue
        name, dt = key[len(_DTYPE_MARKER):], str(marker)
        if dt not in _BITS_ENCODED_DTYPES:
            raise ValueError(f"unknown encoded dtype {dt!r} for artifact array {name!r}")
        out[name] = out[name].view(np.dtype(dt))  # ml_dtypes registers the name
    return out


def _load_npz(path: str) -> dict:
    with np.load(path, allow_pickle=False) as z:
        return _decode_arrays({k: z[k] for k in z.files})


# ------------------------------------------------------------- model <-> arrays


def _model_to_arrays(model) -> tuple[dict, dict]:
    """(json-metadata, host arrays) for one coordinate model. The model's
    device arrays come to the host in ONE named transfer (jax.device_get):
    a checkpoint save is an intended boundary, and
    runtime_guard.sync_discipline disallows implicit ones."""
    meta, arrays = _model_to_device_arrays(model)
    return meta, {k: np.asarray(v) for k, v in jax.device_get(arrays).items()}


def _model_to_device_arrays(model) -> tuple[dict, dict]:
    if isinstance(model, FixedEffectModel):
        glm = model.model
        meta = {
            "kind": "fixed",
            "feature_shard_id": model.feature_shard_id,
            "task": TaskType(glm.task).value,
        }
        arrays = {"means": glm.coefficients.means}
        if glm.coefficients.variances is not None:
            arrays["variances"] = glm.coefficients.variances
        return meta, arrays

    if isinstance(model, RandomEffectModel):
        entity_ids = list(model.entity_ids)
        ids_are_int = all(isinstance(e, (int, np.integer)) for e in entity_ids)
        meta = {
            "kind": "random",
            "re_type": model.re_type,
            "feature_shard_id": model.feature_shard_id,
            "task": TaskType(model.task).value,
            "entity_ids_int": ids_are_int,
        }
        arrays = {
            "coeffs": model.coeffs,
            "proj_indices": model.proj_indices,
            "entity_ids": (
                np.asarray(entity_ids, dtype=np.int64)
                if ids_are_int
                else np.asarray([str(e) for e in entity_ids])
            ),
        }
        if model.variances is not None:
            arrays["variances"] = model.variances
        proj = model.projector
        if proj is not None:
            from photon_ml_tpu.data.projector import RandomProjector

            if not isinstance(proj, RandomProjector):
                raise TypeError(
                    f"Cannot checkpoint projector of type {type(proj).__name__}"
                )
            arrays["projector_matrix"] = proj.matrix
            meta["projector_intercept_index"] = proj.intercept_index
            norm = proj.normalization
            if norm is not None:
                meta["projector_norm_intercept_index"] = norm.intercept_index
                if norm.factors is not None:
                    arrays["projector_norm_factors"] = norm.factors
                if norm.shifts is not None:
                    arrays["projector_norm_shifts"] = norm.shifts
        return meta, arrays

    raise TypeError(f"Unknown model type: {type(model).__name__}")


def _model_from_arrays(meta: dict, arrays, dtype) -> object:
    task = TaskType(meta["task"])
    if meta["kind"] == "fixed":
        variances = arrays.get("variances")
        coeffs = Coefficients(
            means=jnp.asarray(arrays["means"], dtype=dtype),
            variances=None if variances is None else jnp.asarray(variances, dtype=dtype),
        )
        return FixedEffectModel(
            model=model_class_for_task(task)(coeffs),
            feature_shard_id=meta["feature_shard_id"],
        )

    entity_ids = arrays["entity_ids"]
    ids = (
        tuple(int(e) for e in entity_ids)
        if meta["entity_ids_int"]
        else tuple(str(e) for e in entity_ids)
    )
    projector = None
    if "projector_matrix" in arrays:
        from photon_ml_tpu.data.projector import RandomProjector
        from photon_ml_tpu.normalization import NormalizationContext

        norm = None
        if "projector_norm_factors" in arrays or "projector_norm_shifts" in arrays:
            norm = NormalizationContext(
                factors=arrays.get("projector_norm_factors"),
                shifts=arrays.get("projector_norm_shifts"),
                intercept_index=meta.get("projector_norm_intercept_index"),
            )
        projector = RandomProjector(
            matrix=arrays["projector_matrix"],
            intercept_index=meta.get("projector_intercept_index"),
            normalization=norm,
        )
    variances = arrays.get("variances")
    return RandomEffectModel(
        re_type=meta["re_type"],
        feature_shard_id=meta["feature_shard_id"],
        task=task,
        entity_ids=ids,
        coeffs=jnp.asarray(arrays["coeffs"], dtype=dtype),
        proj_indices=jnp.asarray(arrays["proj_indices"], dtype=jnp.int32),
        variances=None if variances is None else jnp.asarray(variances, dtype=dtype),
        projector=projector,
    )


# ---------------------------------------------------------------- plumbing


def sha256_file(path: str) -> str:
    """Streaming SHA-256 of a file's bytes — the ONE content-fingerprint
    primitive every durable artifact in the store shares (checkpoint
    manifests/arrays here, corpus part files in continuous/manifest.py, and
    the content-addressed cold block pool in continuous/store.py, whose pool
    file NAMES are these digests)."""
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


_sha256_file = sha256_file


def _write_models(directory: str, subdir: str, models: dict, manifest: dict,
                  checksums: dict) -> None:
    """One .npz per coordinate into <directory>/<subdir>; fills per-model meta
    into ``manifest`` and each file's SHA-256 into ``checksums`` (keyed by
    generation-relative path)."""
    for cid, model in models.items():
        meta, arrays = _model_to_arrays(model)
        manifest[cid] = meta
        rel = os.path.join(subdir, f"{cid}.npz") if subdir else f"{cid}.npz"
        path = os.path.join(directory, rel)
        action = faultpoint(FP_WRITE_ARRAYS)
        np.savez(path, **_encode_arrays(arrays))
        checksums[rel] = _sha256_file(path)
        if action == "corrupt":
            # simulated bit-rot: damage lands AFTER the checksum is recorded,
            # exactly the class restore's verification must catch
            corrupt_file(path)


def _read_models(directory: str, manifest: dict, dtype) -> dict:
    models = {}
    for cid, meta in manifest.items():
        arrays = _load_npz(os.path.join(directory, f"{cid}.npz"))
        models[cid] = _model_from_arrays(meta, arrays, dtype)
    return models


def _generations(root: str) -> list[tuple[int, str]]:
    """[(generation number, absolute path)] ascending; ignores staging/
    quarantined/legacy entries."""
    if not os.path.isdir(root):
        return []
    out = []
    for name in os.listdir(root):
        m = _GEN_RE.match(name)
        if m:
            out.append((int(m.group(1)), os.path.join(root, name)))
    return sorted(out)


def _clean_stale_tmp(root: str) -> None:
    """Remove staging leftovers a crash mid-write leaked: ``gen-*.tmp`` dirs
    under the root and the legacy ``<root>.tmp`` sibling."""
    candidates = []
    if os.path.isdir(root):
        candidates += [
            os.path.join(root, n) for n in os.listdir(root) if n.endswith(_TMP_SUFFIX)
        ]
    legacy = root.rstrip(os.sep) + _TMP_SUFFIX
    if os.path.exists(legacy):
        candidates.append(legacy)
    for path in candidates:
        logger.info("removing stale checkpoint staging dir %s", path)
        shutil.rmtree(path, ignore_errors=True)


def _quarantine(path: str) -> None:
    """Move a failed-verification generation aside (never silently reuse it,
    never destroy the evidence)."""
    target = path + QUARANTINE_SUFFIX
    try:
        if os.path.exists(target):
            shutil.rmtree(target)
        os.rename(path, target)
        logger.warning("quarantined corrupt checkpoint generation: %s", target)
    except OSError:  # a failed quarantine must not block the rollback
        logger.warning("could not quarantine %s; ignoring it", path, exc_info=True)


# -------------------------------------------------- read-side generation API
# The serving hot-swap (serving/hotswap.py) is a READ-ONLY consumer of a
# training run's checkpoint directory: it polls for new generations and loads
# one specific generation after integrity verification. Unlike
# ``load_checkpoint`` it must never mutate the directory — quarantine and
# rollback are the training owner's recovery moves; a serving replica that
# renamed gen dirs would race the trainer (and every other replica).


def list_generations(directory: str) -> list[tuple[int, str]]:
    """Committed generations under a checkpoint root as ``[(number, path)]``
    ascending. Staging (``*.tmp``), quarantined (``*.corrupt``) and legacy
    entries are ignored; a missing root is an empty list, not an error."""
    return _generations(os.path.abspath(directory))


def load_generation(gen_dir: str, dtype=jnp.float32) -> dict:
    """Verify + load ONE specific generation directory (as returned by
    :func:`list_generations`): full SHA-256 integrity pass, then
    {completed_iterations, models, best_models, best_metric, best_metrics,
    incidents, generation, fingerprint}. ``dtype=None`` keeps every stored
    coefficient dtype (a bf16 deployment's tables load back as bf16,
    bit-exact); the default casts to float32 as before.

    Raises :class:`CheckpointCorruption` on any defect and touches nothing on
    disk — the caller decides whether to fall back to an older generation
    (the serving hot-swap rolls back to the generation it is already
    serving)."""
    return _verify_and_load_generation(os.path.abspath(gen_dir), dtype)


# ----------------------------------------------------- durable blacklist
# The serving fleet's canary verdict, made durable IN the generational store:
# when a generation fails deterministically (corrupt bytes, canary mismatch,
# warm-up crash), the rejecting process records a per-generation blacklist
# file under <root>/blacklist/. Every ReplicaSet / HotSwapManager reads the
# directory at bootstrap (and before each poll), so INDEPENDENT serving
# processes agree on rejected generations with no channel between them — one
# replica's canary spares the whole fleet, across restarts. Files are
# staged + atomically renamed with a SHA-256 sidecar (the store's integrity
# discipline); a damaged entry is ignored (the worst case is one redundant
# canary evaluation, never a wrong verdict adopted from bit-rot). Writes are
# best-effort: a read-only store degrades to in-memory blacklisting.

BLACKLIST_DIR = "blacklist"


def _blacklist_digest(generation: int, cause: str) -> str:
    return hashlib.sha256(f"{int(generation)}\x00{cause}".encode()).hexdigest()


def record_generation_blacklist(
    directory: str, generation: int, cause: str
) -> Optional[str]:
    """Durably record that ``generation`` under checkpoint root ``directory``
    was rejected deterministically. Returns the file path, or None when the
    store is unwritable (logged, never raised — a full disk must not take
    down serving).

    The integrity digest rides INSIDE the JSON, so one ``os.replace`` is the
    whole commit — a content/sidecar pair would have a torn window between
    its two renames that silently drops the verdict (the archive learned the
    same lesson in continuous/store.py)."""
    root = os.path.join(os.path.abspath(directory), BLACKLIST_DIR)
    final = os.path.join(root, f"{GEN_PREFIX}{int(generation):08d}.json")
    tmp = f"{final}{_TMP_SUFFIX}-{os.getpid()}"
    try:
        os.makedirs(root, exist_ok=True)
        with open(tmp, "w") as f:
            json.dump(
                {
                    "generation": int(generation),
                    "cause": str(cause),
                    "sha256": _blacklist_digest(generation, str(cause)),
                },
                f,
            )
        os.replace(tmp, final)
        return final
    except OSError as e:
        logger.warning(
            "could not record blacklist verdict for generation %d under %s "
            "(%s); the verdict stays process-local", generation, directory, e,
        )
        try:
            os.remove(tmp)
        except OSError:
            pass
        return None


def _prune_blacklist(root: str) -> None:
    """Drop verdicts for generations older than the oldest RETAINED one:
    pruned generations can never become swap candidates again, so their
    verdict files would otherwise accumulate (and cost every poll's
    directory re-read) for the life of the store."""
    gens = _generations(root)
    if not gens:
        return
    oldest = gens[0][0]
    bl_root = os.path.join(root, BLACKLIST_DIR)
    if not os.path.isdir(bl_root):
        return
    for name in os.listdir(bl_root):
        m = re.match(rf"^{GEN_PREFIX}(\d{{8}})\.json$", name)
        if m and int(m.group(1)) < oldest:
            try:
                os.remove(os.path.join(bl_root, name))
            except OSError:
                pass


def load_generation_blacklist(directory: str) -> dict[int, str]:
    """{generation: cause} for every VERIFIED blacklist entry under the
    checkpoint root. Damaged or torn entries are skipped with a warning
    (treated as absent); a missing directory is an empty verdict set."""
    root = os.path.join(os.path.abspath(directory), BLACKLIST_DIR)
    out: dict[int, str] = {}
    if not os.path.isdir(root):
        return out
    for name in sorted(os.listdir(root)):
        m = re.match(rf"^{GEN_PREFIX}(\d{{8}})\.json$", name)
        if not m:
            continue
        path = os.path.join(root, name)
        try:
            with open(path) as f:
                record = json.load(f)
            gen = int(record["generation"])
            cause = str(record.get("cause", ""))
            if record.get("sha256") != _blacklist_digest(gen, cause):
                raise ValueError("checksum mismatch")
            if gen != int(m.group(1)):
                raise ValueError(
                    f"generation {gen} does not match file name {name}"
                )
            out[gen] = cause
        except (OSError, ValueError, KeyError) as e:
            logger.warning(
                "ignoring damaged blacklist entry %s (%s)", path, e
            )
    return out


# ------------------------------------------------------------------ save / load


def save_checkpoint(
    directory: str,
    models: dict,
    completed_iterations: int,
    best_models: Optional[dict] = None,
    best_metric: Optional[float] = None,
    best_metrics: Optional[dict] = None,
    fingerprint: Optional[str] = None,
    incidents: Optional[list] = None,
    keep_generations: int = DEFAULT_KEEP_GENERATIONS,
    retry: Optional[Retry] = None,
    extra_state: Optional[dict] = None,
    aux_arrays: Optional[dict] = None,
) -> str:
    """Write a NEW checkpoint generation (staging dir + rename); returns its
    path. Keeps the newest ``keep_generations`` generations, pruning older
    ones (quarantined generations are left for inspection).

    ``fingerprint`` identifies the run configuration; ``load_checkpoint`` with
    a different fingerprint refuses the checkpoint, so a rerun with changed
    hyperparameters/data cannot silently reuse stale trained state.
    ``incidents`` (list of Incident or dicts) persists the run's survived-
    failure history into the manifest. Transient OSErrors retry with backoff;
    each attempt restages from scratch, so a failed attempt leaves nothing
    half-written.

    ``extra_state`` (JSON-serializable dict) rides inside the manifest —
    subsystem metadata such as the continuous-training corpus manifest and
    delta stats (photon_ml_tpu/continuous/). ``aux_arrays``
    ({name: {array_name: ndarray}}) persists non-model array artifacts (e.g.
    per-shard index-map name tables) as ``aux/<name>.npz`` under the same
    SHA-256 integrity regime as the model files; arrays must be
    pickle-free (numeric or unicode dtypes). Both round-trip through
    ``load_generation``/``load_checkpoint`` as the ``extra`` and ``aux``
    keys."""
    if keep_generations < 1:
        raise ValueError(f"keep_generations must be >= 1, got {keep_generations}")
    root = os.path.abspath(directory)
    incident_dicts = [
        i.to_dict() if isinstance(i, Incident) else dict(i) for i in (incidents or [])
    ]

    def _attempt() -> str:
        os.makedirs(root, exist_ok=True)
        _clean_stale_tmp(root)
        gens = _generations(root)
        gen_num = (gens[-1][0] + 1) if gens else 1
        final = os.path.join(root, f"{GEN_PREFIX}{gen_num:08d}")
        tmp = final + _TMP_SUFFIX
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)

        state = {
            "format": _FORMAT,
            "generation": gen_num,
            "completed_iterations": int(completed_iterations),
            "fingerprint": fingerprint,
            "best_metric": None if best_metric is None else float(best_metric),
            "best_metrics": (
                None
                if best_metrics is None
                else {k: float(v) for k, v in best_metrics.items()}
            ),
            "models": {},
            "best_models": None,
            "incidents": incident_dicts,
            "checksums": {},
            "extra": extra_state,
            "aux": sorted(aux_arrays) if aux_arrays else [],
        }
        _write_models(tmp, "", models, state["models"], state["checksums"])
        if best_models is not None:
            os.makedirs(os.path.join(tmp, BEST_DIR))
            state["best_models"] = {}
            _write_models(
                tmp, BEST_DIR, best_models, state["best_models"], state["checksums"]
            )
        if aux_arrays:
            os.makedirs(os.path.join(tmp, AUX_DIR))
            for name in sorted(aux_arrays):
                if "/" in name or os.sep in name or name.startswith("."):
                    raise ValueError(f"aux artifact name {name!r} must be a flat name")
                rel = os.path.join(AUX_DIR, f"{name}.npz")
                path = os.path.join(tmp, rel)
                action = faultpoint(FP_WRITE_ARRAYS)
                np.savez(path, **_encode_arrays(aux_arrays[name]))
                state["checksums"][rel] = _sha256_file(path)
                if action == "corrupt":
                    corrupt_file(path)

        action = faultpoint(FP_WRITE_MANIFEST)
        state_path = os.path.join(tmp, STATE_FILE)
        with open(state_path, "w") as f:
            json.dump(state, f)
        # the manifest's own integrity record: bit-rot inside syntactically
        # valid JSON is still detected at restore
        with open(os.path.join(tmp, STATE_SHA_FILE), "w") as f:
            f.write(_sha256_file(state_path) + "\n")
        if action == "corrupt":
            corrupt_file(state_path)

        faultpoint(FP_WRITE_COMMIT)
        os.rename(tmp, final)

        for _, old_path in _generations(root)[:-keep_generations]:
            shutil.rmtree(old_path, ignore_errors=True)
        _prune_blacklist(root)
        return final

    return (retry or _DEFAULT_RETRY).call(_attempt, description="checkpoint save")


def _verify_and_load_generation(gen_dir: str, dtype) -> dict:
    """Full integrity pass over one generation; raises CheckpointCorruption on
    ANY defect (missing file, checksum mismatch, unreadable manifest/arrays)."""
    state_path = os.path.join(gen_dir, STATE_FILE)
    sha_path = os.path.join(gen_dir, STATE_SHA_FILE)
    try:
        with open(sha_path) as f:
            expected = f.read().strip()
    except OSError as e:
        raise CheckpointCorruption(f"missing manifest checksum: {e}") from e
    actual = None
    try:
        actual = _sha256_file(state_path)
    except OSError as e:
        raise CheckpointCorruption(f"unreadable manifest: {e}") from e
    if actual != expected:
        raise CheckpointCorruption(
            f"manifest checksum mismatch in {gen_dir} "
            f"(expected {expected[:12]}…, got {actual[:12]}…)"
        )
    try:
        with open(state_path) as f:
            state = json.load(f)
    except (OSError, ValueError) as e:
        raise CheckpointCorruption(f"unparseable manifest: {e}") from e

    for rel, expected in state.get("checksums", {}).items():
        path = os.path.join(gen_dir, rel)
        try:
            actual = _sha256_file(path)
        except OSError as e:
            raise CheckpointCorruption(f"missing artifact {rel}: {e}") from e
        if actual != expected:
            raise CheckpointCorruption(
                f"artifact checksum mismatch: {rel} in {gen_dir}"
            )

    try:
        models = _read_models(gen_dir, state["models"], dtype)
        best_models = None
        if state.get("best_models") is not None:
            best_models = _read_models(
                os.path.join(gen_dir, BEST_DIR), state["best_models"], dtype
            )
        aux = {}
        for name in state.get("aux") or []:
            aux[name] = _load_npz(os.path.join(gen_dir, AUX_DIR, f"{name}.npz"))
    except Exception as e:  # torn .npz, bad metadata, dtype surprises ...
        raise CheckpointCorruption(f"unreadable model arrays: {e}") from e

    return {
        "completed_iterations": state["completed_iterations"],
        "best_metric": state["best_metric"],
        "best_metrics": state.get("best_metrics"),
        "models": models,
        "best_models": best_models,
        "incidents": list(state.get("incidents") or []),
        "generation": state.get("generation"),
        "fingerprint": state.get("fingerprint"),
        "extra": state.get("extra"),
        "aux": aux,
    }


def _load_legacy(directory: str, dtype) -> Optional[dict]:
    """Pre-generational layout: state.json directly in ``directory``. No
    checksums existed; a read failure quarantines the manifest so the next
    restore doesn't retry it (fresh-start fallback, never a raise)."""
    state_path = os.path.join(directory, STATE_FILE)
    if not os.path.exists(state_path):
        return None
    try:
        with open(state_path) as f:
            state = json.load(f)
        models = _read_models(directory, state["models"], dtype)
        best_models = None
        if state.get("best_models") is not None:
            best_models = _read_models(
                os.path.join(directory, BEST_DIR), state["best_models"], dtype
            )
    except Exception as e:
        logger.warning(
            "legacy checkpoint %s is unreadable (%s); quarantining it",
            directory, e,
        )
        try:
            os.rename(state_path, state_path + QUARANTINE_SUFFIX)
        except OSError:
            pass
        return None
    return {
        "completed_iterations": state["completed_iterations"],
        "best_metric": state["best_metric"],
        "best_metrics": state.get("best_metrics"),
        "models": models,
        "best_models": best_models,
        "incidents": list(state.get("incidents") or []),
        "generation": None,
        "fingerprint": state.get("fingerprint"),
    }


def _load_from_root(directory: str, dtype, sink: list) -> Optional[dict]:
    """Newest-valid-generation scan over one checkpoint root: verify newest
    first; quarantine + roll back on corruption; legacy layout as a last
    resort. Each rollback is recorded as a checkpoint-corruption incident in
    ``sink`` (and merged into the returned state's history when something
    loads — the sink outlives a restore that finds nothing valid). The WHOLE
    sink merges, not just this root's entries: when the main root was all
    corrupt and the .old fallback loads, its state must still carry the main
    root's quarantines (they happened during THIS restore)."""
    for gen_num, gen_dir in reversed(_generations(directory)):
        try:
            restored = _verify_and_load_generation(gen_dir, dtype)
        except CheckpointCorruption as e:
            logger.warning(
                "checkpoint generation %d failed verification (%s); "
                "rolling back to the previous generation", gen_num, e,
            )
            _quarantine(gen_dir)
            sink.append(
                Incident(
                    kind="checkpoint-corruption",
                    cause=str(e),
                    action=f"quarantined generation {gen_num}; rolled back",
                ).to_dict()
            )
            continue
        restored["incidents"] = restored["incidents"] + list(sink)
        return restored
    legacy = _load_legacy(directory, dtype)
    if legacy is not None:
        legacy["incidents"] = legacy["incidents"] + list(sink)
    return legacy


def load_checkpoint(
    directory: str,
    dtype=jnp.float32,
    fingerprint: Optional[str] = None,
    incident_sink: Optional[list] = None,
) -> Optional[dict]:
    """Restore {completed_iterations, models, best_models, best_metric,
    best_metrics, incidents, generation} from the newest generation that
    passes integrity verification, or None when no valid checkpoint exists.

    Never raises on damage: a torn/bit-rotted generation is quarantined and
    restore rolls back (the rollback appears in ``incidents``). Stale staging
    dirs from crashes mid-write are removed. A ``.old`` sibling left by the
    legacy overwrite dance is scanned as a fallback root. A saved
    ``fingerprint`` differing from the requested one rejects the checkpoint
    (that is a different RUN, not corruption — no rollback past it).

    ``incident_sink`` (a list) collects rollback incident dicts even when the
    restore ends in a fresh start (every generation corrupt): the caller can
    still record WHY there was nothing to resume from."""
    faultpoint(FP_RESTORE)
    directory = os.path.abspath(directory)
    _clean_stale_tmp(directory)
    sink = incident_sink if incident_sink is not None else []
    restored = _load_from_root(directory, dtype, sink)
    if restored is None:
        old = directory + ".old"
        if os.path.isdir(old):
            restored = _load_from_root(old, dtype, sink)
    if restored is None:
        return None
    if fingerprint is not None and restored.get("fingerprint") not in (None, fingerprint):
        return None
    restored.pop("fingerprint", None)
    return restored


class CoordinateDescentCheckpointer:
    """Save/restore hook handed to ``run_coordinate_descent``.

    ``interval`` saves every k-th completed iteration; the descent loop passes
    ``force=True`` on the final iteration so the completed state is always
    saved regardless of the interval. ``fingerprint`` (optional) ties the
    checkpoint to a run configuration: restore returns None when it differs.
    ``keep_generations`` bounds the rollback window (and the disk footprint).

    ``restore()`` never raises: any unexpected failure logs and falls back to
    a fresh start — a bad checkpoint must never be able to kill a run that
    could simply retrain.

    ``extra_state_provider`` (optional zero-arg callable returning a
    JSON-serializable dict or None) is polled at every save and rides the
    manifest's ``extra`` key — fingerprint-ADJACENT run state (e.g. the
    measured ``re_solver="auto"`` decisions) that a resume needs to replay
    bitwise but that must NOT invalidate the checkpoint the way a
    fingerprint mismatch does. ``restore()`` surfaces it back on the
    returned dict's ``"extra"`` key.
    """

    def __init__(
        self,
        directory: str,
        interval: int = 1,
        dtype=jnp.float32,
        fingerprint: Optional[str] = None,
        keep_generations: int = DEFAULT_KEEP_GENERATIONS,
        extra_state_provider=None,
    ):
        if interval < 1:
            raise ValueError(f"checkpoint interval must be >= 1, got {interval}")
        self.directory = directory
        self.interval = int(interval)
        self.dtype = dtype
        self.fingerprint = fingerprint
        self.keep_generations = int(keep_generations)
        self.extra_state_provider = extra_state_provider

    def maybe_save(
        self,
        completed_iterations: int,
        models: dict,
        best_models: Optional[dict],
        best_metric: Optional[float],
        best_metrics: Optional[dict] = None,
        force: bool = False,
        incidents: Optional[list] = None,
    ) -> bool:
        if not force and completed_iterations % self.interval != 0:
            return False
        extra = (
            self.extra_state_provider()
            if self.extra_state_provider is not None
            else None
        )
        save_checkpoint(
            self.directory,
            models,
            completed_iterations,
            best_models,
            best_metric,
            best_metrics,
            fingerprint=self.fingerprint,
            incidents=incidents,
            keep_generations=self.keep_generations,
            extra_state=extra,
        )
        return True

    def restore(self) -> Optional[dict]:
        """``self.restore_incidents`` afterwards holds any rollback incidents
        this restore produced — populated even when the result is None (all
        generations corrupt -> fresh start), so the run can still record why
        there was nothing to resume from."""
        self.restore_incidents: list = []
        try:
            return load_checkpoint(
                self.directory,
                dtype=self.dtype,
                fingerprint=self.fingerprint,
                incident_sink=self.restore_incidents,
            )
        except Exception:
            # the never-raise contract: unexpected damage (including errors
            # outside the per-generation verification) degrades to a fresh
            # start, not a crash loop. InjectedCrash (BaseException) still
            # propagates — a simulated process death is not recoverable.
            logger.exception(
                "checkpoint restore from %s failed; starting fresh", self.directory
            )
            return None

    def clear(self) -> None:
        # also drop the .old/.tmp siblings: load_checkpoint falls back to .old,
        # so leaving it would resurrect the state the caller tried to discard
        for path in (self.directory, self.directory + ".old", self.directory + _TMP_SUFFIX):
            if os.path.exists(path):
                shutil.rmtree(path)
