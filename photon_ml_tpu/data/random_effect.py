"""Random-effect datasets: ragged per-entity data on a dense SPMD machine.

Re-designs photon-api data/RandomEffectDataset.scala:46-508 + LocalDataset.scala:35-251
+ RandomEffectDatasetPartitioner for TPU. The reference keeps RDD[(REId, LocalDataset)]
and solves per entity inside mapValues; here:

- host ingest groups samples by entity ONCE (replacing the groupBy shuffle),
  with the reference's semantics: deterministic reservoir-sampling cap on active
  data with weight rescale count/cap (generateActiveData:293-342,
  groupDataByKeyAndSample:358-420), lower-bound filtering (:433-478 neighborhood),
  per-entity Pearson-correlation feature selection
  (LocalDataset.filterFeaturesByPearsonCorrelationScore:110-138),
  per-entity index-map projection (projector/IndexMapProjectorRDD.scala:36-274);
- entities are BUCKETED by (padded sample count, padded feature count) into dense
  [E_b, S, K] blocks so a vmap-ed optimizer solves a whole bucket as one XLA
  program; padding rows carry weight 0 (inert by construction);
- samples beyond the active cap become passive data (score-only), exactly the
  reference's active/passive split;
- a per-sample gathered view over the FULL dataset supports O(1) scoring and the
  coordinate-descent score exchange without joins.

The partitioner disappears: bucket leading axes are sharded over the device mesh
(parallel/), which replaces the greedy bin-packing of
RandomEffectDatasetPartitioner.scala:1-171.
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp

from photon_ml_tpu.normalization import NormalizationContext
from photon_ml_tpu.types import intercept_key
from photon_ml_tpu.util.timed import span

Array = jnp.ndarray


def _entity_seed(entity_id: str, base_seed: int) -> int:
    """Deterministic per-entity seed (the reference uses byteswap64-mixed keys so
    reservoir sampling is reproducible on recomputation, RandomEffectDataset.scala:
    394-402; a stable hash gives the same property)."""
    h = hashlib.blake2b(f"{base_seed}:{entity_id}".encode(), digest_size=8)
    return int.from_bytes(h.digest(), "little")


def _next_pow2(n: int, minimum: int) -> int:
    p = minimum
    while p < n:
        p *= 2
    return p


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EntityBucket:
    """One padded block of entities with similar shapes.

    X is [E, S, K] in each entity's local (projected) space; sample_ids are global
    sample-axis positions (-1 padding) used to gather offsets/partial scores into
    the block. The way back, from the block slots to the sample axis, is the
    dataset's ``sample_slots`` (the inverse of every bucket's ``sample_ids``): the
    single-program update gathers its ``[N]`` score through it; the streamed
    working-set chunks scatter theirs by ``sample_ids``.
    """

    entity_rows: Array  # [E] int32 — row into the dataset-wide entity table
    X: Array  # [E, S, K]
    labels: Array  # [E, S]
    weights: Array  # [E, S] (0 = padding)
    sample_ids: Array  # [E, S] int32 (-1 padding)

    @property
    def n_entities(self) -> int:
        return self.X.shape[0]

    @property
    def shape(self) -> tuple[int, int]:
        return self.X.shape[1], self.X.shape[2]


@dataclasses.dataclass
class RandomEffectDataset:
    """All per-entity training blocks + the per-sample scoring view for one
    (random-effect type, feature shard) coordinate."""

    re_type: str
    feature_shard_id: str
    entity_ids: tuple  # entities WITH active data (training targets), row order
    buckets: list[EntityBucket]
    # dataset-wide per-entity projection table, [E, K_max] global col ids (-1 pad)
    proj_indices: Array
    # per-sample scoring view over the FULL sample axis:
    sample_entity_rows: Array  # [N] int32, -1 = entity has no model
    sample_local_cols: Array  # [N, nnz] int32 into the entity's K axis, -1 pad
    sample_vals: Array  # [N, nnz]
    n_samples: int
    # passive-sample bookkeeping (reference passiveData): ids not in active blocks
    n_active_samples: int = 0
    n_passive_samples: int = 0
    # RandomProjector when the dataset lives in a shared projected space
    # (projector/ProjectionMatrixBroadcast semantics); None for index-map/identity
    projector: Optional[object] = None
    # set by parallel.placement: NamedSharding for the coefficient tables
    # (entity axis sharded over the mesh) and their padded row count (next
    # multiple of the mesh size >= n_entities; device_put requires divisibility).
    # None on the host backend. Rows >= n_entities are always-zero padding.
    coeffs_sharding: Optional[object] = None
    coeffs_rows: Optional[int] = None
    # share of the padded bucket rows that hold no sample: 1 - active samples /
    # sum over buckets of entities x padded rows per entity (0 without buckets)
    padding_waste: float = 0.0
    # [N] int32, the inverse of the buckets' sample_ids: each sample's position
    # in the concatenation of the bucket blocks, in bucket order, row-major over
    # [E_b, S_b] (base_b + e * S_b + s); a sample that sits in no bucket (its
    # entity trains no model) points at ONE appended zero slot, index
    # sum_b E_b * S_b. The update program scores from its bucket blocks through
    # it (solver_cache._re_coordinate_update_fn) where
    # algorithm/random_effect.bucket_score_slots hands it over. None where the
    # index would be wrong or incomplete — scoring-only datasets, passive rows
    # (scored, but in no bucket), mesh placement (padded entity axes) — and
    # the view scores.
    sample_slots: Optional[Array] = None

    @property
    def n_entities(self) -> int:
        return len(self.entity_ids)

    @property
    def max_k(self) -> int:
        return self.proj_indices.shape[1]

    def scoring_view(self, model=None):
        """(entity_rows [N], local_cols [N, nnz], vals [N, nnz]) for
        RandomEffectModel.score_dataset."""
        return self.sample_entity_rows, self.sample_local_cols, self.sample_vals


# What one more bucket costs a training process off the CPU, in units of what
# one more padded cell (one element of an [E_b, S_b, K_b] block) costs it: 4e6
# cells = 0.5M padded rows at K = 8. Read on a TPU v5e at the benchmark cell's
# size (PERF.md section 6, PR 32): on the device a bucket is nearly free
# (0.24 ms a call-pair against 16 ns a padded row a call: 1e5 cells), in a
# fit unit it costs the host 1.1 ms (3e5 cells), and at every process start
# 2.3-3.1 s of tracing against 0.3 us of block fill a padded row. 4e6 is where
# set-up breaks even on that cell; the steady state alone would take 3e5.
C_BUCKET_CELLS = 4e6


def _bucket_policy(bucket_cost: Optional[float]) -> tuple[float, bool]:
    """``(cost of one more bucket in padded cells, heights are powers of
    two)``: the caller's cost with heights any multiple of the row pad, else
    what the backend says. On XLA:CPU a bucket costs a fit nothing, so
    nothing but the allowed heights bounds their number: powers of two, every
    occupied one a bucket. Elsewhere a bucket costs ``C_BUCKET_CELLS`` and
    the cost bounds them."""
    if bucket_cost is not None:
        return float(bucket_cost), False
    if jax.default_backend() == "cpu":
        return 0.0, True
    return C_BUCKET_CELLS, False


def _chain_partition(heights: np.ndarray, counts: np.ndarray, k: int, cost: float):
    """Exact minimiser of ``sum_b E_b * S_b * k + cost * buckets`` over
    partitions of ascending distinct ``heights`` (``counts`` entities each)
    into contiguous ranges, a bucket as tall as the last height of its range.
    Returns ``(total, ends)``: the minimum and the index one past each range."""
    m = len(heights)
    cum = np.concatenate([[0], np.cumsum(counts)]).astype(np.float64)
    best = np.zeros(m + 1)
    cut = np.zeros(m + 1, dtype=np.int64)
    for j in range(1, m + 1):
        cand = best[:j] + (cum[j] - cum[:j]) * (float(heights[j - 1]) * k) + cost
        cut[j] = int(np.argmin(cand))
        best[j] = cand[cut[j]]
    ends, j = [], m
    while j > 0:
        ends.append(j)
        j = int(cut[j])
    return float(best[m]), ends[::-1]


def bucket_layout(
    rows: np.ndarray,
    k_pads: np.ndarray,
    bucket_cost: float,
    *,
    min_rows: int = 8,
    pow2_heights: bool = False,
) -> dict[tuple[int, int], np.ndarray]:
    """Assign entities to padded ``[E_b, S_b, K_b]`` buckets so that

        sum over buckets of E_b * S_b * K_b  +  bucket_cost * (number of buckets)

    is least. ``rows`` is every entity's active row count, ``k_pads`` its
    padded column count; a bucket is as wide as its widest member and as tall
    as its tallest, rounded up to an allowed height: a multiple of
    ``min_rows``, or with ``pow2_heights`` ``min_rows`` times a power of two.
    Padding is inert by construction (weight-0 rows; zero columns keep their
    coefficients at 0 under L2), so the layout changes shapes, never results.
    Within one width the sizes are one-dimensional and the partition is the
    exact optimum (a dynamic programme over the distinct heights); a width
    class joins the next wider one only where the joined optimum is cheaper
    than the two apart. Counted in cells, one huge entity cannot raise
    everyone's height: that costs more than the bucket it saves.
    ``bucket_cost = 0`` keeps every occupied (height, width) a bucket.
    Returns ``{(S_b, K_b): entity indices}``."""
    rows = np.asarray(rows, dtype=np.int64)
    if pow2_heights:
        s_pads = np.asarray([_next_pow2(int(r), min_rows) for r in rows], dtype=np.int64)
    else:
        s_pads = np.maximum(-(-rows // min_rows), 1) * min_rows
    k_pads = np.asarray(k_pads, dtype=np.int64)

    def optimum(members: np.ndarray, k: int):
        """``(cost, ascending bucket heights)`` of ``members`` at width ``k``."""
        heights, counts = np.unique(s_pads[members], return_counts=True)
        total, ends = _chain_partition(heights, counts, k, bucket_cost)
        return total, heights[np.asarray(ends) - 1]

    # (width, members, cost, bucket heights) of each class, widths ascending
    classes: list[tuple[int, np.ndarray, float, np.ndarray]] = []
    for k in (int(k) for k in np.unique(k_pads)):
        members = np.flatnonzero(k_pads == k)
        total, tops = optimum(members, k)
        if classes:
            _, m_prev, total_prev, _ = classes[-1]
            both = np.concatenate([m_prev, members])
            total_both, tops_both = optimum(both, k)
            if total_both < total_prev + total:
                classes[-1] = (k, both, total_both, tops_both)
                continue
        classes.append((k, members, total, tops))
    layout: dict[tuple[int, int], np.ndarray] = {}
    for _, members, _, tops in classes:
        which = np.searchsorted(tops, s_pads[members])
        for b, top in enumerate(tops):
            inside = np.sort(members[which == b])
            # of a joined class, a bucket that holds narrow entities only stays narrow
            layout[(int(top), int(k_pads[inside].max()))] = inside
    return layout


def build_random_effect_dataset(
    X: sp.spmatrix,
    entity_ids_per_sample: Sequence,
    re_type: str,
    feature_shard_id: str = "global",
    *,
    active_data_upper_bound: Optional[int] = None,
    active_data_lower_bound: int = 1,
    features_max: Optional[int] = None,
    labels: Optional[np.ndarray] = None,
    weights: Optional[np.ndarray] = None,
    intercept_index: Optional[int] = None,
    normalization: Optional[NormalizationContext] = None,
    seed: int = 0,
    dtype=jnp.float32,
    min_samples_pad: int = 8,
    min_features_pad: int = 4,
    bucket_cost: Optional[float] = None,
    scoring_only: bool = False,
    projector: Optional[object] = None,
    entity_order: Optional[Sequence] = None,
    exclude_entities: Optional[set] = None,
) -> RandomEffectDataset:
    """Host-side construction of the bucketed random-effect dataset.

    Two indexes build the same dataset. Where every row of ``X`` stores the
    same columns (and no Pearson selection runs), the index works on the N
    rows: each entity's projection is that column set and a block row is a
    copy of a sample's row (``_index_rows``). Otherwise it works on the
    stored entries (``_index_nnz``). The spans ``ingest.re_index`` and
    ``ingest.re_buckets`` name the one that ran: ``index="rows" | "nnz"``.

    - ``active_data_upper_bound``: reservoir cap; kept samples get weight * n/cap
      (RandomEffectDataset.scala:358-420). Overflow samples become passive.
    - ``active_data_lower_bound``: entities with fewer active samples train no model
      (their samples score 0), reference lower-bound filtering.
    - ``features_max``: per-entity Pearson feature selection cap (needs ``labels``).
    - ``normalization``: applied to the materialized blocks (x' = (x-shift)*factor);
      models are converted back to original space after the solve, so scoring and
      model export always live in the original space.
    - ``bucket_cost``: what one more bucket costs, in padded cells, for
      ``bucket_layout`` (heights then any multiple of ``min_samples_pad``);
      default: the backend's (``_bucket_policy``).
    - ``scoring_only``: skip training-bucket materialization entirely (validation /
      transform datasets only need the per-sample scoring view); caps, lower-bound
      filtering and Pearson selection don't apply to scoring data.
    - ``projector``: a data.projector.RandomProjector. Features — and the
      projector's OWN carried normalization — are folded into the shared
      projected space up-front; the dataset then lives entirely in that space
      (every entity observes the same k(+1) projected columns), matching
      RandomEffectCoordinateInProjectedSpace. Pass normalization via the
      projector (make_projector(..., normalization=...)), not this function's
      ``normalization`` argument, so scoring datasets (which never see the
      training normalization) stay consistent.
    - ``entity_order``: STABLE entity-row growth for incremental training
      (continuous/): entities appearing in this sequence keep its relative
      order (row i of the previous generation's table stays row i as long as
      the entity still trains), unseen entities append at the tail in sorted
      order — so a previous generation's coefficient table aligns with the
      grown dataset by construction. Default (None) keeps the historical
      fully sorted order.
    - ``exclude_entities``: entity-row SHRINK for continuous training's
      eviction (continuous/compaction.py): listed entities get no training
      bucket and no model row — their samples' scoring-view entity row is -1,
      i.e. they score exactly like entities that never had a model (the
      serving engine's missing-entity contract, now on the training side too).
    """
    if projector is not None:
        if normalization is not None and projector.normalization is None:
            raise ValueError(
                "normalization must be carried BY the projector "
                "(make_projector(..., normalization=...)) so training and scoring "
                "datasets agree on the projected space"
            )
        X = projector.project_features(X)
        normalization = None
        intercept_index = (
            projector.projected_dim - 1 if projector.intercept_index is not None else None
        )
    if scoring_only:
        active_data_upper_bound = None
        active_data_lower_bound = 1
        features_max = None
    elif labels is None:
        raise ValueError(
            "labels are required to build training buckets; pass scoring_only=True "
            "for validation/transform datasets that only need the scoring view"
        )
    X = X.tocsr()
    n, d = X.shape
    base_weights = np.ones(n) if weights is None else np.asarray(weights, dtype=np.float64)
    ent = np.asarray(entity_ids_per_sample)
    if len(ent) != n:
        raise ValueError("entity ids and sample count mismatch")
    labels_arr = None if labels is None else np.asarray(labels, dtype=np.float64)
    # the index by rows where every row stores the same columns (Pearson
    # selection is per entity, so it keeps to the index by non-zeros)
    shared = _shared_columns(X) if features_max is None else None
    kind = "nnz" if shared is None else "rows"

    with span("ingest.re_index", re_type=re_type, index=kind):
        groups = _group_by_entity(
            ent,
            upper=active_data_upper_bound,
            lower=active_data_lower_bound,
            exclude=exclude_entities,
            entity_order=entity_order,
            seed=seed,
        )
        if shared is None:
            index = _index_nnz(X, groups, features_max, labels_arr, intercept_index)
        else:
            index = _index_rows(X, groups, shared)

    with span("ingest.re_buckets", re_type=re_type, index=kind) as buckets_span:
        # ---- bucketing by (padded sample count, padded feature count) ---------------
        norm_factors = None if normalization is None or normalization.factors is None else np.asarray(normalization.factors)
        norm_shifts = None if normalization is None or normalization.shifts is None else np.asarray(normalization.shifts)

        n_ent = len(groups.entities)
        lens = groups.lens
        k_pads = np.asarray(
            [_next_pow2(max(int(k), 1), min_features_pad) for k in index.k_counts],
            dtype=np.int64,
        )
        bucket_members: dict[tuple[int, int], np.ndarray] = {}
        if n_ent and not scoring_only:  # scoring datasets hold no buckets
            cost, pow2_heights = _bucket_policy(bucket_cost)
            bucket_members = bucket_layout(
                lens, k_pads, cost, min_rows=min_samples_pad, pow2_heights=pow2_heights
            )

        # Dataset-wide projection table is as wide as the widest PADDED entity so that
        # bucket slices coeffs_global[:, :K_bucket] always fit.
        max_k_all = int(k_pads.max()) if n_ent else min_features_pad
        proj_table = np.full((n_ent, max_k_all), -1, dtype=np.int32)
        col_entity = np.repeat(np.arange(n_ent), index.k_counts)
        col_slot = _positions(index.k_counts)
        proj_table[col_entity, col_slot] = index.cols
        shift_rows = None
        if norm_shifts is not None:
            # x' = (x - shift) * factor = x*factor - shift*factor: the shift term
            # applies to every VALID (sample, observed-col) cell, zeros included.
            sh = -norm_shifts[index.cols]
            if norm_factors is not None:
                sh = sh * norm_factors[index.cols]
            shift_rows = np.zeros((n_ent, max_k_all))
            shift_rows[col_entity, col_slot] = sh

        # padded blocks as HOST arrays: placed together in the ingest.h2d span below
        host_buckets: list[tuple] = []
        sorted_keys = sorted(bucket_members.items())
        if shared is None:
            fill_block = _nnz_filler(X, index, sorted_keys, norm_factors)
        else:
            fill_block = _row_filler(X, shared, norm_factors)
        act_starts = np.cumsum(lens) - lens
        # sample_slots (see RandomEffectDataset): filled bucket by bucket below,
        # samples of no bucket then sent to the zero slot one past the blocks
        slots = np.full(n, -1, dtype=np.int64)
        slot_base = 0
        for b, ((s_pad, k_pad), members) in enumerate(sorted_keys):
            eb = len(members)
            Xb = np.zeros((eb, s_pad, k_pad), dtype=np.float64)
            yb = np.zeros((eb, s_pad), dtype=np.float64)
            wb = np.zeros((eb, s_pad), dtype=np.float64)
            sb = np.full((eb, s_pad), -1, dtype=np.int32)
            # sample-level fills: the members' runs of the active samples
            m_lens = lens[members]
            el_s = np.repeat(np.arange(eb), m_lens)
            sl_s = _positions(m_lens)
            rows_s = groups.act_rows[np.repeat(act_starts[members], m_lens) + sl_s]
            if labels_arr is not None:
                yb[el_s, sl_s] = labels_arr[rows_s]
            wb[el_s, sl_s] = base_weights[rows_s] * np.repeat(groups.scale[members], m_lens)
            sb[el_s, sl_s] = rows_s
            slots[rows_s] = slot_base + el_s * s_pad + sl_s
            slot_base += eb * s_pad
            fill_block(b, Xb, el_s, sl_s, rows_s)
            if shift_rows is not None:
                row_valid = np.arange(s_pad)[None, :] < m_lens[:, None]
                Xb += shift_rows[members, :k_pad][:, None, :] * row_valid[:, :, None]
            host_buckets.append((members.astype(np.int32), Xb, yb, wb, sb))
        if scoring_only or groups.n_passive or slot_base >= np.iinfo(np.int32).max:
            slots = None
        else:
            slots = np.where(slots >= 0, slots, slot_base).astype(np.int32)
        # the layout on the record: slot_base has counted E_b * S_b over the
        # buckets, the lanes are the E_b (the systems a direct solve factors)
        padded_rows = slot_base
        buckets_span.attrs.update(
            buckets=len(sorted_keys), padded_rows=padded_rows,
            lanes=sum(int(hb[1].shape[0]) for hb in host_buckets),
        )

    n_active = int(lens.sum())
    # device placement, synced: the one span that waits for the device (set-up
    # only), so that host index building and H2D are separate numbers
    with span("ingest.h2d", re_type=re_type):
        buckets = [
            EntityBucket(
                entity_rows=jnp.asarray(rows_b),
                X=jnp.asarray(Xb, dtype=dtype),
                labels=jnp.asarray(yb, dtype=dtype),
                weights=jnp.asarray(wb, dtype=dtype),
                sample_ids=jnp.asarray(sb),
            )
            for rows_b, Xb, yb, wb, sb in host_buckets
        ]
        del host_buckets
        placed = (
            jnp.asarray(proj_table),
            jnp.asarray(groups.sample_rows),
            jnp.asarray(index.s_cols),
            jnp.asarray(index.s_vals, dtype=dtype),
            None if slots is None else jnp.asarray(slots),
        )
        jax.block_until_ready((buckets, placed))
    return RandomEffectDataset(
        re_type=re_type,
        feature_shard_id=feature_shard_id,
        entity_ids=tuple(groups.entities),
        buckets=buckets,
        proj_indices=placed[0],
        sample_entity_rows=placed[1],
        sample_local_cols=placed[2],
        sample_vals=placed[3],
        n_samples=n,
        n_active_samples=n_active,
        n_passive_samples=groups.n_passive,
        projector=projector,
        padding_waste=1.0 - n_active / padded_rows if padded_rows else 0.0,
        sample_slots=placed[4],
    )


def _positions(lens: np.ndarray) -> np.ndarray:
    """Each element's position inside its run, for runs of ``lens`` elements
    laid end to end: ``[0, 1, 2, 0, 1]`` for ``[3, 2]``."""
    lens = np.asarray(lens, dtype=np.int64)
    return np.arange(int(lens.sum())) - np.repeat(np.cumsum(lens) - lens, lens)


def _shared_columns(X: sp.csr_matrix) -> Optional[np.ndarray]:
    """The one column set every row of ``X`` stores, ascending, or None where
    the rows differ in their count of stored entries, in their columns or in
    their order (an empty matrix shares the empty set). One comparison for
    each stored entry."""
    n = X.shape[0]
    counts = np.diff(X.indptr)
    c = int(counts[0]) if n else 0
    if not (counts == c).all():
        return None
    indices = X.indices[: X.nnz]
    cols = indices[:c]
    if (np.diff(cols) <= 0).any() or not (indices.reshape(n, c) == cols).all():
        return None
    return cols.astype(np.int32)


@dataclasses.dataclass
class _Groups:
    """Samples grouped by entity, the active-data cap and the entity filters
    applied: the part both indexes share."""

    entities: list  # ids of the entities that train a model, in row order
    lens: np.ndarray  # [E] active samples of each
    scale: np.ndarray  # [E] weight scale of the cap: count / cap, else 1
    act_rows: np.ndarray  # active sample ids, entity by entity, ascending within one
    sample_rows: np.ndarray  # [N] int32 row of each sample's entity, -1 = no model
    n_passive: int


def _group_by_entity(ent, *, upper, lower, exclude, entity_order, seed) -> _Groups:
    """One stable sort of the entity ids groups the samples (the one-time
    'shuffle'); a group's samples then stand in ascending order. The cap keeps
    a group's samples by its own seeded draw; the filters and the row order
    work on groups, so nothing here loops over samples."""
    n = len(ent)
    order = np.argsort(ent, kind="mergesort")
    sorted_ent = ent[order]
    boundaries = np.flatnonzero(sorted_ent[1:] != sorted_ent[:-1]) + 1
    # an empty input (e.g. an empty validation split) has no groups at all
    starts = np.concatenate([[0], boundaries]) if n else boundaries
    counts = np.diff(np.append(starts, n))
    ids = sorted_ent[starts]

    lens = counts.copy()
    scale = np.ones(len(counts))
    kept = np.ones(n, dtype=bool)  # over `order`
    if upper is not None:
        for g in np.flatnonzero(counts > upper):
            count = int(counts[g])
            rng = np.random.default_rng(_entity_seed(str(ids[g]), seed))
            dropped = np.argsort(rng.random(count), kind="mergesort")[upper:]
            kept[starts[g] + dropped] = False
            lens[g] = upper
            scale[g] = count / upper

    # lower-bound filter: entities below the threshold train no model
    chosen = lens >= lower
    if exclude:
        chosen &= np.fromiter((e not in exclude for e in ids), dtype=bool, count=len(ids))
    chosen = np.flatnonzero(chosen)
    entities = list(ids[chosen])  # ascending: the historical fully sorted order
    if entity_order is not None:
        # stable growth: known entities keep the caller's row order, unseen
        # ones append sorted at the tail (continuous-training alignment)
        present = set(entities)
        known = [e for e in entity_order if e in present]
        known_set = set(known)
        group_of = dict(zip(entities, chosen.tolist()))
        entities = known + sorted(e for e in entities if e not in known_set)
        chosen = np.asarray([group_of[e] for e in entities], dtype=np.int64)

    # each chosen group's run of active samples, in row order
    g_lens = lens[chosen]
    group_starts = np.cumsum(lens) - lens
    act_rows = order[kept][np.repeat(group_starts[chosen], g_lens) + _positions(g_lens)]
    row_of_group = np.full(len(ids), -1, dtype=np.int32)
    row_of_group[chosen] = np.arange(len(chosen), dtype=np.int32)
    sample_rows = np.empty(n, dtype=np.int32)
    sample_rows[order] = np.repeat(row_of_group, counts)
    return _Groups(
        entities=entities,
        lens=g_lens,
        scale=scale[chosen],
        act_rows=act_rows,
        sample_rows=sample_rows,
        n_passive=int((counts - lens).sum()),
    )


@dataclasses.dataclass
class _Index:
    """Each entity's projection and the per-sample scoring view: what an index
    answers. ``entries`` is the index by non-zeros' map of the active entries,
    None for the index by rows."""

    cols: np.ndarray  # every entity's sorted global column ids, concatenated
    k_counts: np.ndarray  # [E] columns of each entity
    s_cols: np.ndarray  # [N, nnz] int32 entity-local column of each entry, -1 pad
    s_vals: np.ndarray  # [N, nnz] float64
    entries: Optional[tuple] = None


def _index_rows(X: sp.csr_matrix, groups: _Groups, shared: np.ndarray) -> _Index:
    """The index by rows, for a matrix whose rows all store the columns
    ``shared``: every entity observes them all, so each entity's projection
    is ``shared`` and an entry's local column is its place in the row."""
    n = X.shape[0]
    c = len(shared)
    n_ent = len(groups.entities)
    if c:
        s_cols = np.broadcast_to(np.arange(c, dtype=np.int32), (n, c)).copy()
        s_vals = X.data[: X.nnz].reshape(n, c).astype(np.float64)
    else:  # rows that store nothing: one padding slot, as the non-zero view has
        s_cols = np.full((n, 1), -1, dtype=np.int32)
        s_vals = np.zeros((n, 1), dtype=np.float64)
    no_model = groups.sample_rows < 0
    s_cols[no_model] = -1
    s_vals[no_model] = 0.0
    return _Index(
        cols=np.tile(shared, n_ent),
        k_counts=np.full(n_ent, c, dtype=np.int64),
        s_cols=s_cols,
        s_vals=s_vals,
    )


def _index_nnz(X, groups: _Groups, features_max, labels_arr, intercept_index) -> _Index:
    """The index by non-zeros, for any matrix: each entity's projection is the
    columns its active samples store (optionally Pearson-selected), and every
    stored entry is mapped to its entity's local column."""
    n, d = X.shape
    n_ent = len(groups.entities)
    # Flat active-sample machinery shared by the (vectorized) observed-column
    # computation and the bucket fill: one concatenated row list replaces the
    # per-entity scipy CSR slicing that dominated build time at 100k+ entities.
    act_rows = groups.act_rows
    ent_row_per_act = np.repeat(np.arange(n_ent, dtype=np.int64), groups.lens)
    s_local_per_act = _positions(groups.lens)
    # active nnz: global nnz positions of every active sample's entries
    counts_all = np.diff(X.indptr)
    c_act = counts_all[act_rows]
    act_nnz_idx = (np.repeat(X.indptr[act_rows], c_act) + _positions(c_act)).astype(np.int64)
    ent_of_act_nnz = np.repeat(ent_row_per_act, c_act)
    s_local_of_act_nnz = np.repeat(s_local_per_act, c_act)

    # ---- per-entity projection (+ optional Pearson selection) -------------------
    # cols: sorted global col ids observed in each entity's ACTIVE rows.
    if n_ent == 0:
        cols, k_counts = np.zeros(0, dtype=np.int32), np.zeros(0, dtype=np.int64)
    elif features_max is None:
        keys = ent_of_act_nnz * d + X.indices[act_nnz_idx].astype(np.int64)
        uniq_keys = np.unique(keys)
        k_counts = np.bincount(uniq_keys // d, minlength=n_ent)
        cols = (uniq_keys % d).astype(np.int32)
    else:
        # Pearson feature selection needs per-entity column/label statistics —
        # the per-entity loop stays on this opt-in path only.
        col_of = []
        for start, count in zip(np.cumsum(groups.lens) - groups.lens, groups.lens):
            rows = act_rows[start : start + count]
            sub = X[rows]  # csr [s, d]
            observed = np.unique(sub.indices) if sub.nnz else np.array([], dtype=np.int32)
            if len(observed) > features_max:
                if labels_arr is None:
                    raise ValueError("features_max (Pearson selection) requires labels")
                scores = _pearson_scores(sub, observed, labels_arr[rows])
                keep_order = np.argsort(-scores, kind="mergesort")
                kept = set(observed[keep_order[:features_max]].tolist())
                if intercept_index is not None:
                    kept.add(intercept_index)
                observed = np.asarray(sorted(kept), dtype=observed.dtype)
            col_of.append(observed.astype(np.int32))
        cols = np.concatenate(col_of)
        k_counts = np.asarray([len(c) for c in col_of], dtype=np.int64)

    # ---- global nnz -> entity-local column mapping ------------------------------
    # local col = position of the global col in the entity's projection row.
    # Vectorized over all nnz: a dense [E, D] lookup when it fits, else per-entity
    # dict fallback (huge-D regimes). Used by BOTH the bucket fill (through
    # act_nnz_idx) and the per-sample scoring view.
    local = np.full(X.nnz, -1, dtype=np.int32)
    if n and X.nnz:
        rows_per_nnz = np.repeat(np.arange(n), counts_all)
        slot_per_nnz = np.arange(X.nnz) - np.repeat(X.indptr[:-1], counts_all)
        ent_per_nnz = groups.sample_rows[rows_per_nnz]
        valid = ent_per_nnz >= 0
        if n_ent * d <= 50_000_000:
            lookup = np.full((max(n_ent, 1), d), -1, dtype=np.int32)
            lookup[np.repeat(np.arange(n_ent), k_counts), cols] = _positions(k_counts)
            local[valid] = lookup[ent_per_nnz[valid], X.indices[valid]]
        else:
            col_of = np.split(cols, np.cumsum(k_counts)[:-1])
            local_of = [{int(c): k for k, c in enumerate(cs)} for cs in col_of]
            idx_valid = np.flatnonzero(valid)
            for t in idx_valid:
                local[t] = local_of[ent_per_nnz[t]].get(int(X.indices[t]), -1)

    # ---- per-sample scoring view over the FULL sample axis ----------------------
    nnz_max = max(int(counts_all.max()) if n else 1, 1)
    s_cols = np.full((n, nnz_max), -1, dtype=np.int32)
    s_vals = np.zeros((n, nnz_max), dtype=np.float64)
    if n and X.nnz:
        keep = local >= 0
        s_cols[rows_per_nnz[keep], slot_per_nnz[keep]] = local[keep]
        s_vals[rows_per_nnz[keep], slot_per_nnz[keep]] = X.data[keep]
    return _Index(
        cols=cols,
        k_counts=k_counts,
        s_cols=s_cols,
        s_vals=s_vals,
        entries=(act_nnz_idx, ent_of_act_nnz, s_local_of_act_nnz, local[act_nnz_idx]),
    )


def _row_filler(X: sp.csr_matrix, shared: np.ndarray, norm_factors):
    """``fill(b, Xb, e_local, s_local, rows)`` of the index by rows: a block's
    rows are copies of its samples' rows of ``X``, whose rows all store the
    columns ``shared``."""
    c = len(shared)
    dense = X.data[: X.nnz].reshape(X.shape[0], c)  # the CSR's own buffer
    factors = None if norm_factors is None else norm_factors[shared]

    def fill(b, Xb, e_local, s_local, rows):
        vals = dense[rows].astype(np.float64)
        if factors is not None:
            vals *= factors
        vals += 0.0  # a stored -0.0 lands as +0.0, as the non-zero fill's sum leaves it
        Xb.reshape(-1, Xb.shape[2])[e_local * Xb.shape[1] + s_local, :c] = vals

    return fill


def _nnz_filler(X: sp.csr_matrix, index: _Index, sorted_keys: list, norm_factors):
    """``fill(b, Xb, e_local, s_local, rows)`` of the index by non-zeros: each
    bucket's active entries scatter-added into its block (duplicate (row, col)
    entries sum, as toarray does)."""
    act_nnz_idx, ent_of_act_nnz, s_local_of_act_nnz, local_of_act_nnz = index.entries
    n_buckets = len(sorted_keys)
    n_ent = len(index.k_counts)
    bucket_id = np.full(max(n_ent, 1), -1, dtype=np.int64)
    e_local_all = np.zeros(max(n_ent, 1), dtype=np.int64)
    for b, (_, members) in enumerate(sorted_keys):
        bucket_id[members] = b
        e_local_all[members] = np.arange(len(members))
    # One stable sort groups the active entries by bucket, so each bucket gets
    # a contiguous slice instead of re-scanning everything.
    nnz_bucket = np.where(local_of_act_nnz >= 0, bucket_id[ent_of_act_nnz], -1)
    nnz_order = np.argsort(nnz_bucket, kind="stable")
    nnz_bounds = np.searchsorted(nnz_bucket[nnz_order], np.arange(n_buckets + 1))

    def fill(b, Xb, e_local, s_local, rows):
        # bincount over raveled indices = vectorized scatter-add
        ni = nnz_order[nnz_bounds[b] : nnz_bounds[b + 1]]
        gv = X.data[act_nnz_idx[ni]].astype(np.float64)
        gc = X.indices[act_nnz_idx[ni]]
        if norm_factors is not None:
            gv = gv * norm_factors[gc]
        flat = np.ravel_multi_index(
            (e_local_all[ent_of_act_nnz[ni]], s_local_of_act_nnz[ni], local_of_act_nnz[ni]),
            Xb.shape,
        )
        Xb += np.bincount(flat, weights=gv, minlength=Xb.size).reshape(Xb.shape)

    return fill


def in_layout_of(
    scoring: RandomEffectDataset, entity_ids: tuple, proj_indices: Array
) -> RandomEffectDataset:
    """``scoring``'s per-sample view keyed to a training dataset's entity
    rows and projection slots (its ``entity_ids`` and ``proj_indices``), and
    holding that tuple and table: a model trained on that dataset then scores
    it as it stands (``RandomEffectModel.aligned_to``'s identity path), where
    a dataset in its own layout needs the model's table re-laid out on the
    host at every score. The scores are the same: an entity or a column the
    training dataset holds no coefficient for scores 0, as the re-laid-out
    table's zero rows and slots give it. A dataset in a projected space comes
    back as it is."""
    if scoring.proj_indices is proj_indices or scoring.projector is not None:
        return scoring
    rows, cols, s_proj, t_proj = jax.device_get((
        scoring.sample_entity_rows, scoring.sample_local_cols,
        scoring.proj_indices, proj_indices,
    ))
    # each scoring entity's training row, -1 where it trains no model
    t_ids = np.asarray(entity_ids)
    s_ids = np.asarray(scoring.entity_ids)
    t_row_of = np.full(len(s_ids), -1, dtype=np.int64)
    if len(t_ids) and len(s_ids):
        sorter = np.argsort(t_ids, kind="mergesort")
        at = sorter[np.clip(np.searchsorted(t_ids, s_ids, sorter=sorter), 0, len(t_ids) - 1)]
        t_row_of = np.where(t_ids[at] == s_ids, at, -1)
    t_rows = np.where(rows >= 0, t_row_of[np.maximum(rows, 0)], -1)
    # each scoring entity's slots in its training row (-1: no coefficient),
    # found among the sorted (row, column) keys of the training table; then
    # each nonzero's slot through its entity's row of that map
    width = int(max(t_proj.max(initial=-1), s_proj.max(initial=-1))) + 1
    t_r, t_k = np.nonzero(t_proj >= 0)
    t_keys = t_r.astype(np.int64) * width + t_proj[t_r, t_k]
    order = np.argsort(t_keys, kind="stable")
    t_keys, t_slots = t_keys[order], t_k[order].astype(np.int32)
    s_r, s_k = np.nonzero((s_proj >= 0) & (t_row_of >= 0)[:, None])
    keys = t_row_of[s_r] * width + s_proj[s_r, s_k]
    slot_map = np.full(s_proj.shape, -1, dtype=np.int32)
    if len(t_keys):
        at = np.minimum(np.searchsorted(t_keys, keys), len(t_keys) - 1)
        slot_map[s_r, s_k] = np.where(t_keys[at] == keys, t_slots[at], -1)
    k_s = s_proj.shape[1]
    flat = np.maximum(rows, 0)[:, None].astype(np.int64) * k_s + np.maximum(cols, 0)
    local = np.where(cols >= 0, slot_map.ravel()[flat], -1).astype(np.int32)
    return dataclasses.replace(
        scoring,
        entity_ids=entity_ids,
        proj_indices=proj_indices,
        sample_entity_rows=jnp.asarray(t_rows.astype(np.int32)),
        sample_local_cols=jnp.asarray(local),
    )


def _pearson_scores(sub: sp.csr_matrix, observed: np.ndarray, y: np.ndarray) -> np.ndarray:
    """|Pearson correlation| of each observed column with the label
    (LocalDataset.computePearsonCorrelationScore semantics; constant columns,
    e.g. the intercept, get score ~1 so they are always kept — reference gives the
    intercept a pass-through score)."""
    dense = np.asarray(sub[:, observed].todense(), dtype=np.float64)
    s = len(y)
    if s <= 1:
        return np.ones(len(observed))
    xm = dense - dense.mean(axis=0, keepdims=True)
    ym = y - y.mean()
    denom = np.sqrt((xm**2).sum(axis=0) * (ym**2).sum())
    num = xm.T @ ym
    corr = np.where(denom > 0, np.abs(num / np.where(denom > 0, denom, 1.0)), 1.0)
    return corr
