"""GAME model containers: fixed-effect, random-effect, and the combined GameModel.

Mirrors photon-lib model/GameModel.scala:32-168, photon-api model/FixedEffectModel.scala
and model/RandomEffectModel.scala:36-304, re-shaped for TPU:

- FixedEffectModel: one GLM per feature shard (the reference broadcasts it; here the
  coefficients are just a replicated device array).
- RandomEffectModel: per-entity coefficient rows in a dense [E, K] matrix in each
  entity's PROJECTED feature space, plus [E, K] global-column ids (the projection).
  The reference keeps an RDD[(REId, GLM)] and scores via joins; here scoring is a
  gather + batched dot over the sample axis.
- GameModel: ordered coordinate -> model map; total score = sum of coordinate scores
  over the global sample axis.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.models.glm import Coefficients, GeneralizedLinearModel
from photon_ml_tpu.types import ModelType, TaskType

Array = jnp.ndarray


@jax.jit
def random_effect_view_score(
    coeffs: Array, entity_rows: Array, local_cols: Array, vals: Array
) -> Array:
    """Per-sample gather/dot scoring kernel over a scoring view: score[i] =
    sum_k coeffs[entity_rows[i], local_cols[i, k]] * vals[i, k], with -1
    entity rows (no model) and -1 column slots (padding / columns the model
    never saw) contributing exactly 0. ONE shared implementation for the
    eager ``RandomEffectModel.score_dataset`` (validation scores, the initial
    score of a warm-started or resumed fit), the fused serving engine
    (serving/engine.py), the streamed working-set chunk programs and the
    update programs' view path (solver_cache: datasets without
    ``sample_slots`` — passive rows, mesh placement —, normalization and
    reduced precision), so every one of them executes identical jnp ops and
    stays numerically interchangeable. On raw float32 blocks the all-resident
    update program no longer calls it: it scores from the bucket blocks it
    has just solved (``solver_cache._re_coordinate_update_fn``, scopes
    ``re.bucket_score`` / ``re.score_gather``; the rule is
    ``algorithm/random_effect.bucket_score_slots``) without the ``[N, K]``
    intermediate below, and is held to this kernel's bits
    (tests/test_bucket_score.py).

    Jitted at module level ON PURPOSE: XLA contracts the multiply into the
    reduction (FMA) when this subgraph sits inside one fusion, so an
    op-by-op eager evaluation differs from any inlined/jitted one in the
    last ulp. One compiled form everywhere keeps the fused-vs-eager bitwise
    parity gates honest (jit-in-jit callers simply inline the same
    subgraph, which XLA fuses the same way — asserted by the update-program
    parity tests and the serving bench gate)."""
    with jax.named_scope("re.view_score"):
        has_model = entity_rows >= 0
        safe_rows = jnp.maximum(entity_rows, 0)
        w = coeffs[safe_rows]  # [N, K]
        safe_cols = jnp.maximum(local_cols, 0)
        gathered = jnp.take_along_axis(w, safe_cols, axis=1)  # [N, nnz]
        gathered = jnp.where(local_cols >= 0, gathered, 0.0)
        scores = jnp.sum(gathered * vals, axis=1)
        return jnp.where(has_model, scores, 0.0)


def _projectors_compatible(a, b) -> bool:
    """True when two RandomProjectors define the same projected space. Full
    matrix equality is O(d*k) host work on potentially huge matrices, so after
    the cheap structural checks we compare a deterministic sample of entries
    (a Gaussian matrix differing anywhere differs almost surely everywhere)."""
    if a is b:
        return True
    if a.matrix.shape != b.matrix.shape or a.intercept_index != b.intercept_index:
        return False
    d, k = a.matrix.shape
    rows = np.unique(np.linspace(0, d - 1, num=min(d, 16), dtype=np.int64))
    cols = np.unique(np.linspace(0, k - 1, num=min(k, 4), dtype=np.int64))
    if not np.array_equal(a.matrix[np.ix_(rows, cols)], b.matrix[np.ix_(rows, cols)]):
        return False
    na, nb = a.normalization, b.normalization
    if (na is None) != (nb is None):
        return False
    if na is not None:
        for fa, fb in ((na.factors, nb.factors), (na.shifts, nb.shifts)):
            if (fa is None) != (fb is None):
                return False
            if fa is not None and not np.array_equal(np.asarray(fa), np.asarray(fb)):
                return False
    return True


@dataclasses.dataclass(frozen=True)
class FixedEffectModel:
    """Global GLM for one feature shard (FixedEffectModel.scala:146)."""

    model: GeneralizedLinearModel
    feature_shard_id: str = "global"

    @property
    def model_type(self) -> ModelType:
        return ModelType.FIXED_EFFECT

    @property
    def task(self) -> TaskType:
        return self.model.task

    def score_dataset(self, dataset) -> Array:
        """Score a FixedEffectDataset (margins WITHOUT its offsets: coordinate scores
        exclude offsets so they can be summed across coordinates)."""
        return dataset.data.X.matvec(self.model.coefficients.means)


@dataclasses.dataclass(frozen=True)
class RandomEffectModel:
    """Per-entity GLMs as one dense coefficient matrix (RandomEffectModel.scala:36-304).

    coeffs[e] are entity e's coefficients in its projected space; proj_indices[e, k]
    is the global column id of local slot k (-1 = padding). Unseen entities score 0
    (the reference's behavior for entities without a model).
    """

    re_type: str  # entity id column, e.g. "userId"
    feature_shard_id: str
    task: TaskType
    entity_ids: tuple  # length E, position = row in coeffs
    coeffs: Array  # [E, K]
    proj_indices: Array  # [E, K] int32 global col ids, -1 pad
    variances: Optional[Array] = None  # [E, K]
    # set when coeffs live in a shared random-projection space (data/projector.py);
    # proj_indices then index PROJECTED columns, and export goes through
    # to_original_space() (RandomEffectModelInProjectedSpace.scala:151 semantics)
    projector: Optional[object] = None

    def __post_init__(self):
        object.__setattr__(self, "_row_by_entity", {e: i for i, e in enumerate(self.entity_ids)})

    @property
    def model_type(self) -> ModelType:
        return ModelType.RANDOM_EFFECT

    @property
    def n_entities(self) -> int:
        return len(self.entity_ids)

    def row_for_entity(self, entity_id) -> int:
        """-1 if the entity has no model."""
        return self._row_by_entity.get(entity_id, -1)

    def coefficients_for_entity(self, entity_id) -> Optional[np.ndarray]:
        row = self.row_for_entity(entity_id)
        return None if row < 0 else np.asarray(self.coeffs[row])

    def aligned_to(self, dataset) -> "RandomEffectModel":
        """Re-layout this model's coefficients into ``dataset``'s entity-row and
        projection-slot order. Needed when the model was loaded from disk (slot
        order = surviving means order) or trained on a different dataset build —
        without this, gathers through the dataset's local columns would read the
        wrong slots."""
        # Identity fast path: a model trained ON this dataset carries the
        # dataset's own proj_indices array and entity tuple (the warm-start
        # case inside coordinate descent, once per coordinate per iteration).
        # Object identity + tuple equality only — NO array materialization,
        # which on an accelerator would be a device->host transfer in the
        # descent hot loop.
        if self.proj_indices is dataset.proj_indices and (
            self.entity_ids is dataset.entity_ids
            or self.entity_ids == tuple(dataset.entity_ids)
        ):
            return self
        # the re-layout below is host work by design: its device->host reads
        # are named (runtime_guard.sync_discipline disallows implicit ones)
        src_proj, dst_proj = jax.device_get((self.proj_indices, dataset.proj_indices))
        if self.entity_ids == tuple(dataset.entity_ids) and np.array_equal(
            src_proj, dst_proj
        ):
            return self
        src, src_var = jax.device_get((self.coeffs, self.variances))
        E, K = dst_proj.shape
        out = np.zeros((E, K), dtype=src.dtype)
        out_var = None if src_var is None else np.zeros((E, K), dtype=src_var.dtype)
        # Tail-growth fast path: continuous training pins the previous
        # generation's entity order (build_random_effect_dataset(entity_order=))
        # so the old table is a row PREFIX of the grown one. Rows whose slot
        # layout is unchanged copy in one vectorized move; only entities whose
        # new rows changed their slot set (a subset of the active set) pay the
        # per-entity remap loop — keeping re-layout cost proportional to the
        # delta, not the corpus.
        n_old = len(self.entity_ids)
        Ks = src_proj.shape[1]
        rows_to_remap = range(E)
        if (
            E >= n_old
            and K >= Ks
            and tuple(dataset.entity_ids[:n_old]) == self.entity_ids
        ):
            same = (dst_proj[:n_old, :Ks] == src_proj).all(axis=1)
            if Ks < K:
                same &= (dst_proj[:n_old, Ks:] < 0).all(axis=1)
            keep = np.flatnonzero(same)
            out[keep, :Ks] = src[keep]
            if out_var is not None:
                out_var[keep, :Ks] = src_var[keep]
            # tail rows (i >= n_old) are NEW entities: no source row, stay zero
            rows_to_remap = np.flatnonzero(~same)
        for i in rows_to_remap:
            e = dataset.entity_ids[i]
            r = self.row_for_entity(e)
            if r < 0:
                continue
            col_val = {int(c): k for k, c in enumerate(src_proj[r]) if c >= 0}
            for k, c in enumerate(dst_proj[i]):
                kk = col_val.get(int(c), -1) if c >= 0 else -1
                if kk >= 0:
                    out[i, k] = src[r, kk]
                    if out_var is not None:
                        out_var[i, k] = src_var[r, kk]
        # hand back the DATASET's own entity tuple and proj array (the re-laid
        # out table matches them by construction): the next aligned_to against
        # this dataset then short-circuits on object identity instead of
        # re-materializing and comparing the [E, K] projection table
        return dataclasses.replace(
            self,
            entity_ids=tuple(dataset.entity_ids),
            coeffs=jnp.asarray(out),
            proj_indices=dataset.proj_indices,
            variances=None if out_var is None else jnp.asarray(out_var),
        )

    def score_dataset(self, dataset) -> Array:
        """Score a RandomEffectDataset-like object exposing per-sample projected
        features: ``scoring_view()`` -> (entity_rows [N], local_cols [N, nnz],
        vals [N, nnz]) where local_cols index into the DATASET's slot layout; the
        model is aligned to that layout first."""
        ds_projector = getattr(dataset, "projector", None)
        if self.projector is not None and ds_projector is None:
            # projected model vs original-space dataset: score via back-projection
            return self.to_original_space().score_dataset(dataset)
        if (
            self.projector is not None
            and ds_projector is not None
            and not _projectors_compatible(self.projector, ds_projector)
        ):
            # two DIFFERENT projections: shapes may even match, but coefficients
            # in one random basis dotted with features in another are garbage
            raise ValueError(
                "Model and dataset were built with different RandomProjectors "
                "(matrix/normalization mismatch); rebuild the scoring dataset "
                "with the model's projector (GameTransformer does this "
                "automatically)"
            )
        if self.projector is None and ds_projector is not None:
            # original-space model vs projected dataset: proj_indices would be
            # interpreted as projected slot ids — silently garbage. There is no
            # exact original->projected coefficient transport (P is not square),
            # so refuse (e.g. a loaded/back-projected model warm-starting a
            # RANDOM_PROJECTION coordinate: rebuild datasets without the
            # projector, or refit from scratch).
            raise ValueError(
                "Cannot score an original-space RandomEffectModel against a "
                "random-projection dataset; drop the coordinate's projector "
                "config or retrain the model in projected space"
            )
        model = self.aligned_to(dataset)
        entity_rows, local_cols, vals = dataset.scoring_view(model)
        return random_effect_view_score(model.coeffs, entity_rows, local_cols, vals)

    def update_entities(self, new_coeffs: Array, variances: Optional[Array] = None) -> "RandomEffectModel":
        return dataclasses.replace(self, coeffs=new_coeffs, variances=variances)

    def to_original_space(self) -> "RandomEffectModel":
        """Back-project a random-projection model into the original feature space
        (coef_orig = P @ w, margin-invariant). Per-entity coefficients become the
        entity's non-zero back-projected columns under an index-map layout, so the
        result saves/scores like any other RandomEffectModel. No-op without a
        projector. Variances don't survive (no exact linear transport through P);
        the reference likewise drops them for projected models."""
        if self.projector is None:
            return self
        E = self.n_entities
        kp = self.projector.projected_dim
        d_orig = self.projector.original_dim
        if E == 0:
            return dataclasses.replace(
                self,
                coeffs=jnp.zeros((0, 1), dtype=np.asarray(self.coeffs).dtype),
                proj_indices=jnp.full((0, 1), -1, dtype=jnp.int32),
                variances=None,
                projector=None,
            )
        proj_tbl = np.asarray(self.proj_indices)
        coeffs_src = np.asarray(self.coeffs)
        # un-pad with one vectorized scatter: slot k holds projected column
        # proj_tbl[i, k]
        W_proj = np.zeros((E, kp), dtype=coeffs_src.dtype)
        rows_idx, slots = np.nonzero(proj_tbl >= 0)
        W_proj[rows_idx, proj_tbl[rows_idx, slots]] = coeffs_src[rows_idx, slots]
        dense = self.projector.project_coefficients_back(W_proj)  # [E, d] batched
        nz = [np.flatnonzero(dense[i]) for i in range(E)]
        K = max((len(c) for c in nz), default=1) or 1
        coeffs = np.zeros((E, K), dtype=dense.dtype)
        proj = np.full((E, K), -1, dtype=np.int32)
        for i, cols in enumerate(nz):
            coeffs[i, : len(cols)] = dense[i, cols]
            proj[i, : len(cols)] = cols
        return dataclasses.replace(
            self,
            coeffs=jnp.asarray(coeffs),
            proj_indices=jnp.asarray(proj),
            variances=None,
            projector=None,
        )


@dataclasses.dataclass(frozen=True)
class GameModel:
    """Ordered coordinateId -> model (GameModel.scala:32-168)."""

    models: Mapping[str, object]  # str -> FixedEffectModel | RandomEffectModel

    def get_model(self, coordinate_id: str):
        return self.models.get(coordinate_id)

    def update_model(self, coordinate_id: str, model) -> "GameModel":
        if coordinate_id not in self.models:
            raise KeyError(f"Unknown coordinate {coordinate_id}")
        old = self.models[coordinate_id]
        if type(old) is not type(model):
            raise TypeError(
                f"Coordinate {coordinate_id}: cannot replace {type(old).__name__} "
                f"with {type(model).__name__} (GameModel type-consistency check)"
            )
        new = dict(self.models)
        new[coordinate_id] = model
        return GameModel(models=new)

    def select(self, coordinate_ids) -> "GameModel":
        """Sub-model over a subset of coordinates, in the given order
        (the reference slices GAME models per coordinate when scoring
        sub-problems and locking coordinates for partial retrains)."""
        missing = [c for c in coordinate_ids if c not in self.models]
        if missing:
            raise KeyError(f"Unknown coordinates {missing}")
        return GameModel(models={c: self.models[c] for c in coordinate_ids})

    @property
    def coordinate_ids(self) -> list[str]:
        return list(self.models.keys())

    @property
    def task(self) -> TaskType:
        for m in self.models.values():
            return m.task
        raise ValueError("Empty GAME model")

    def __iter__(self):
        return iter(self.models.items())

    def __len__(self):
        return len(self.models)
