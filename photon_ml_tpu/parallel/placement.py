"""Mesh placement: turn host-built GAME datasets into SPMD datasets.

The coordinate-descent implementation (algorithm/coordinate_descent.py) is
backend-agnostic: every solve it triggers is a jitted XLA program over whatever
shardings its input arrays carry. Placement is therefore the whole "mesh
backend": pad the global sample axis (weight-0 rows, inert in every weighted
reduction) and each bucket's entity axis (junk rows whose scatters drop), then
``device_put`` every array with batch/entity shardings over the 1-D mesh. XLA
then inserts the psum for the fixed-effect gradient reduction — the
``treeAggregate`` analog (ValueAndGradientAggregator.scala:240-255) — and keeps
the vmapped per-entity random-effect solves communication-free, matching the
executor-local solves of RandomEffectCoordinate.scala:109-127.

Random-effect coefficient tables are sharded over the entity axis (the
reference never collects RandomEffectModel RDDs either, RandomEffectModel.scala:
36-304): placement stamps ``coeffs_sharding`` on the dataset and the solvers
place/update the [E, K] tables under it, so per-device model memory scales as
~1/n_devices.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from photon_ml_tpu.data.dataset import FixedEffectDataset
from photon_ml_tpu.data.random_effect import EntityBucket, RandomEffectDataset
from photon_ml_tpu.parallel.glm import shard_labeled_data
from photon_ml_tpu.parallel.mesh import (
    batch_sharding,
    pad_put,
    replicated_sharding,
)

Array = jnp.ndarray


def pad_and_shard_vector(arr, mesh, fill=0.0, dtype=None) -> Array:
    """Pad a [N] host/device vector to the mesh multiple and batch-shard it
    (device inputs stay on device — see mesh.pad_put)."""
    placed, _ = pad_put(
        arr, mesh.devices.size, batch_sharding(mesh, ndim=1), fill=fill,
        to_dtype=dtype,
    )
    return placed


def place_fixed_effect_dataset(ds: FixedEffectDataset, mesh) -> FixedEffectDataset:
    """Samples sharded over the mesh; dense [N, D] blocks or sparse COO nnz axis
    (billion-feature regime — the PalDBIndexMap.scala:43-278 scale story rides
    the sparse path + offheap_index).

    On a 2-D ("data", "model") mesh the FEATURE axis additionally shards over
    "model" and placement stamps ``coef_sharding`` so coefficient vectors and
    optimizer state live distributed (parallel/feature_sharded.py) — dense
    matrices block-shard [N, D], sparse matrices shard their flat nnz axis
    over both mesh axes (the wide-FE regime: K padded to the model axis,
    coefficients P("model"), scores P("data"))."""
    from photon_ml_tpu.parallel.feature_sharded import (
        feature_sharding,
        shard_labeled_data_2d,
    )

    if len(mesh.axis_names) == 2:
        # sample padding to the TOTAL device count keeps the global score axis
        # consistent with the 1-D-placed random-effect coordinates
        sharded2, _, _ = shard_labeled_data_2d(
            ds.data, mesh, sample_multiple=mesh.devices.size
        )
        return dataclasses.replace(
            ds, data=sharded2, coef_sharding=feature_sharding(mesh)
        )
    sharded, _ = shard_labeled_data(ds.data, mesh)
    return dataclasses.replace(ds, data=sharded)


def place_random_effect_dataset(ds: RandomEffectDataset, mesh) -> RandomEffectDataset:
    """Entity-shard the training buckets, batch-shard the per-sample scoring
    view, and stamp the coefficient-table sharding.

    Bucket padding discipline: padded entities get ``entity_rows == n_entities``
    (one past the [E, K] coefficient table) — their gathers clamp harmlessly and
    their scatters are dropped by XLA's out-of-bounds-update semantics; their
    weights are all zero so the padded solves converge instantly to the L2 prox.
    """
    m = mesh.devices.size
    bs1, bs2, bs3 = (batch_sharding(mesh, ndim=k) for k in (1, 2, 3))
    rep = replicated_sharding(mesh)
    E = ds.n_entities

    def put(arr, sharding, *, fill=0):
        # pad + place without the device->host->device round trip the old
        # np.asarray + np.pad pattern made on device-resident bucket arrays
        placed, _ = pad_put(arr, m, sharding, fill=fill)
        return placed

    buckets = []
    for b in ds.buckets:
        buckets.append(
            EntityBucket(
                entity_rows=put(b.entity_rows, bs1, fill=E),
                X=put(b.X, bs3),
                labels=put(b.labels, bs2),
                weights=put(b.weights, bs2),
                sample_ids=put(b.sample_ids, bs2, fill=-1),
            )
        )

    return dataclasses.replace(
        ds,
        buckets=buckets,
        proj_indices=jax.device_put(ds.proj_indices, rep),
        sample_entity_rows=put(ds.sample_entity_rows, bs1, fill=-1),
        sample_local_cols=put(ds.sample_local_cols, bs2, fill=-1),
        sample_vals=put(ds.sample_vals, bs2),
        coeffs_sharding=batch_sharding(mesh, ndim=2),
        # device_put needs the sharded axis divisible by the mesh size, so the
        # table gets always-zero padding rows; row E (the bucket-padding target)
        # falls in this range and is re-zeroed after every update
        coeffs_rows=-(-max(E, 1) // m) * m,
        # the buckets' entity axes were padded above: the ingest-time slot
        # index no longer names these blocks' slots, so a placed dataset
        # scores through its view
        sample_slots=None,
    )


def place_serving_batch(batch, mesh):
    """Batch-shard a serving request's prepared arrays over the mesh's
    FIRST (batch) axis — 1-D or 2-D: a 2-D training mesh's second axis holds
    replicas, so serving rides its data axis unchanged.

    Every leaf of a serving batch (serving/engine.py) leads with the PADDED
    sample axis — the engine's bucket size is already a batch-axis multiple —
    so placement is a uniform axis-0 sharding; the engine's coefficient
    tables are replicated separately at engine build. This is the scoring-side
    analog of the training placement above, minus the padding (already done)
    and the entity-axis sharding (serving gathers THROUGH the replicated
    tables instead of scattering into them)."""
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(a, batch_sharding(mesh, ndim=a.ndim)), batch
    )


def place_game_datasets(datasets: dict, mesh) -> dict:
    """Place every per-coordinate dataset of a GAME fit on the mesh."""
    out = {}
    for cid, ds in datasets.items():
        if isinstance(ds, FixedEffectDataset):
            out[cid] = place_fixed_effect_dataset(ds, mesh)
        elif isinstance(ds, RandomEffectDataset):
            out[cid] = place_random_effect_dataset(ds, mesh)
        else:
            raise TypeError(f"Cannot place dataset of type {type(ds).__name__}")
    return out
