"""Planted logistic GLMix: dense fixed effect + one random effect per entity
column, random-effect shard = intercept + the first K-1 fixed columns. The
generative process is ``bench._generate_workload``'s (listed in PERF.md's open
questions for deletion); sizes come from the configuration, every draw from
the seed, and rows per entity from the configuration's ``entity_sizes`` law
instead of uniform draws."""

from __future__ import annotations

import numpy as np

from chipbench.generate import Dataset, Table, key


def entity_sizes(n_rows: int, n_entities: int, law: dict) -> np.ndarray:
    """Rows per entity, largest last: the configuration's law cut into
    ``n_entities`` equal-probability quantiles. The law is a power law
    ``p(s) ~ s**-alpha`` truncated to the source's published [min, max] rows
    an entity, with ``alpha`` solved so that the sizes sum to ``n_rows`` (the
    source's mean). A pure function of the configuration: every seed gets the
    same multiset of sizes, on other entities and other rows."""
    if law["law"] != "truncated_power":
        raise ValueError(f"entity size law {law['law']!r}: generate.py has truncated_power")
    lo, hi = float(law["min"]), float(min(law["max"], n_rows))
    u = (np.arange(n_entities) + 0.5) / n_entities

    def quantiles(alpha):
        a, b = lo ** (1.0 - alpha), hi ** (1.0 - alpha)
        return (a - u * (a - b)) ** (1.0 / (1.0 - alpha))

    low, high = 1.0001, 8.0  # the mean falls as alpha grows
    if not quantiles(high).sum() <= n_rows <= quantiles(low).sum():
        raise ValueError(f"{n_rows} rows over {n_entities} entities lie outside the law {law}")
    for _ in range(80):
        mid = 0.5 * (low + high)
        low, high = (mid, high) if quantiles(mid).sum() > n_rows else (low, mid)
    q = quantiles(high)  # sums to just under n_rows
    sizes = np.floor(q).astype(np.int64)
    short = int(n_rows - sizes.sum())
    sizes[np.argsort(q - sizes, kind="stable")[::-1][: short % n_entities]] += 1
    sizes += short // n_entities
    assert sizes.sum() == n_rows and sizes.min() >= law["min"]
    return sizes


def generate(cfg: dict, seed: int) -> Dataset:
    import jax
    import jax.numpy as jnp

    d = int(cfg["fixed_effect_dim"])
    k = int(cfg["random_effect_dim"])
    n_train = int(cfg["n_train_rows"])
    n_entities = {t: int(e) for t, e in cfg["entities"].items()}
    rng = np.random.default_rng(int(seed))
    w = (rng.normal(size=d) * 0.3).astype(np.float32)
    effects = {t: 0.4 * rng.normal(size=e) for t, e in n_entities.items()}
    # which entity has which size, and which rows are whose, is the seed's; the
    # sizes themselves are the configuration's, for the held-out rows too: an
    # entity's held-out rows are its share of them (largest remainders), so
    # that the scoring programs' shapes do not move with the seed either
    n_val = int(cfg["n_validation_rows"])
    train_ids, val_ids = {}, {}
    for t, e in n_entities.items():
        sizes = entity_sizes(n_train, e, cfg["entity_sizes"][t])
        share = sizes * (n_val / n_train)
        held = np.floor(share).astype(np.int64)
        held[np.argsort(share - held, kind="stable")[::-1][: n_val - held.sum()]] += 1
        who = rng.permutation(e)
        train_ids[t] = rng.permutation(np.repeat(who, sizes))
        val_ids[t] = rng.permutation(np.repeat(who, held))

    def make(n, key, w):  # w is an argument: a constant would compile anew for every seed
        X = jax.random.normal(key, (n, d), jnp.float32)
        # the random-effect columns leave as k-1 vectors: an [n, k-1] (or
        # [k-1, n]) result would be padded to 128 lanes a row on the device
        return X, X @ w, tuple(X[:, j] for j in range(k - 1))

    make = jax.jit(make, static_argnums=0)

    def table(n, key, ids) -> Table:
        fe_X, z_fe, cols = make(n, key, w)
        z = np.asarray(jax.device_get(z_fe), np.float64)
        for tag, col in ids.items():
            z += effects[tag][col]
        y = (rng.random(n) < 1.0 / (1.0 + np.exp(-z))).astype(np.float64)
        re_vals = np.empty((n, k), np.float32)
        re_vals[:, 0] = 1.0
        for j, col in enumerate(jax.device_get(cols)):
            re_vals[:, j + 1] = col
        return Table(fe_X=fe_X, labels=y, re_vals=re_vals, ids=ids)

    return Dataset(
        train=table(n_train, key(seed, 0), train_ids),
        validation=table(n_val, key(seed, 1), val_ids),
        n_entities=n_entities,
    )
