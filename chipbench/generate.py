"""Data from the seed. A configuration's ``generator`` names a module under
``chipbench/generators/`` whose ``generate(cfg, seed)`` returns a ``Dataset``:
a new generative process is a new file there.

The big matrix is drawn ON the device in one jitted call (threefry is the same
on every backend) and handed to the program as a device array, which
``GameInput`` accepts: the host never holds it. Everything a random-effect
ingest needs on the host (ids, the [N, K] random-effect values, labels) is
small and comes down once. The same seed gives the same bytes.

What the seed chooses: everything that is drawn — planted weights, features,
labels, which entity has how many rows and which rows are whose, the held-out
rows. What it does not choose is the SHAPE of the work: row and entity counts
and the multiset of rows per entity are the configuration's, so every seed
compiles the same programs and pads the same buckets.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import scipy.sparse as sp


@dataclasses.dataclass
class Table:
    """One table of rows (training or held-out) as plain arrays."""

    fe_X: object  # [n, d] float32 device array
    labels: np.ndarray  # [n] float64 0/1
    re_vals: np.ndarray | None = None  # [n, K] float32: the random-effect shard, dense
    ids: dict = dataclasses.field(default_factory=dict)  # tag -> [n] int64

    @property
    def n(self) -> int:
        return int(self.labels.shape[0])

    def re_csr(self) -> sp.csr_matrix:
        """The random-effect shard as the CSR the ingest takes, built from the
        dense [n, K] values without a dense->sparse conversion pass."""
        n, k = self.re_vals.shape
        return sp.csr_matrix(
            (
                self.re_vals.reshape(-1),
                np.tile(np.arange(k, dtype=np.int32), n),
                np.arange(0, n * k + 1, k, dtype=np.int64),
            ),
            shape=(n, k),
        )


@dataclasses.dataclass
class Dataset:
    train: Table
    validation: Table
    n_entities: dict  # tag -> E


def key(seed: int, stream: int):
    """A PRNG key from any whole-number seed (the driver's seeds pass 2**31,
    which a 32-bit PRNGKey argument does not hold)."""
    import jax

    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)


def generate(cfg: dict, seed: int) -> Dataset:
    """The configuration's data for ``seed``."""
    import importlib

    try:
        module = importlib.import_module("chipbench.generators." + cfg["generator"])
    except ImportError:
        raise KeyError(
            f"configuration {cfg.get('name')!r} names generator {cfg.get('generator')!r}: "
            f"no chipbench/generators/{cfg.get('generator')}.py"
        ) from None
    return module.generate(cfg, seed)
