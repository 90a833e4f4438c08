"""The plain reference: block coordinate descent for a logistic GLMix, in
straightforward ``jax.numpy``. It imports nothing of the program and takes
nothing the program made — only the benchmark's own generated arrays.

What it computes is what the configuration states, not how the program gets
there: every coordinate update is the EXACT minimiser of that coordinate's
L2-regularised logistic objective ``sum_i l(z_i, y_i) + lambda/2 ||w||^2``
(un-averaged, as the configuration's Photon convention has it) given the other
coordinates' scores as offsets, found by Newton's method (a step that does
not lower the objective is replaced by the step under the logistic loss's
curvature bound, which cannot overshoot), run until the estimated distance to
the minimiser is under 1e-6 in coefficient units. Each sub-problem is strictly convex, so its
minimiser is unique: a correct solver of any kind, run to its tolerance, lands
beside it, and the sequence of exact block minimisations is a deterministic
function of the data. There are no kernels, no caches, no donation: a random
effect is E independent K x K Newton systems, solved a size class at a time as
padded [E_c, S_c, K] tensors; the fixed effect is three contractions over the
design matrix (XLA fuses the row weights into the [d, d] Hessian product: no
second copy of the matrix).

``dtype="float32"`` runs every contraction at ``Precision.HIGHEST``.
``dtype="bfloat16"`` is the CONTROL of the comparison that decides
``correct``: the same code with data, coefficients, margins and sums held in
bfloat16 (only the K x K linear solves stay float32) — the precision step a
later PR would be tempted by. It has to come out as not correct.
"""

from __future__ import annotations

import numpy as np

NEWTON_ITERATIONS = 64  # at most; the loop ends when every problem's step is under STEP_DONE
STEP_DONE = 1e-6  # estimated distance to the minimiser, in coefficient units
OBJECTIVE_NOISE = 5e-7  # float32 sums resolve an objective to about this share of it


def _dt(dtype):
    import jax.numpy as jnp

    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[dtype]


def _precision(dtype):
    import jax

    return jax.default_matmul_precision("highest" if dtype == "float32" else "default")


def _logistic_parts(z, y):
    """Per-row loss, first and second derivative in the margin, for 0/1
    labels. The loss is log(1 + exp(-+z)), never log(1 + exp(z)) - z: the
    difference would lose a confident row's small loss to rounding."""
    import jax
    import jax.numpy as jnp

    p = jax.nn.sigmoid(z)
    return jnp.logaddexp(0.0, jnp.where(y > 0.5, -z, z)), p - y, p * (1.0 - p)


def _regularised(w, lam, loss, g, h):
    import jax.numpy as jnp

    w32 = w.astype(jnp.float32)
    obj = loss.astype(jnp.float32) + 0.5 * lam * jnp.sum(w32 * w32, axis=1)
    return obj, g.astype(jnp.float32) + lam * w32, h.astype(jnp.float32)


def _step(w, lam, g, curvature):
    """``w - (curvature + lam I)^-1 g``."""
    import jax.numpy as jnp

    a = curvature + lam * jnp.eye(w.shape[1], dtype=jnp.float32)
    step = jnp.linalg.solve(a, g[..., None])[..., 0]
    return (w.astype(jnp.float32) - step).astype(w.dtype)


def _newton_is_good(obj, g, obj_t, g_t):
    """A Newton step is taken where it lowers the objective; where the change
    is under what a float32 sum resolves, where it lowers the gradient norm
    (near the minimiser of a sum over millions of rows the objective no longer
    tells steps apart)."""
    import jax.numpy as jnp

    noise = OBJECTIVE_NOISE * jnp.abs(obj)
    smaller_gradient = jnp.sum(g_t * g_t, axis=1) <= jnp.sum(g * g, axis=1)
    return jnp.isfinite(obj_t) & (
        (obj_t < obj - noise) | ((obj_t <= obj + noise) & smaller_gradient)
    )


def _distance(lam, g, h):
    """Each problem's estimated distance to its minimiser: the undamped
    Newton step's length."""
    import jax.numpy as jnp

    a = h + lam * jnp.eye(g.shape[1], dtype=jnp.float32)
    return jnp.linalg.norm(jnp.linalg.solve(a, g[..., None])[..., 0], axis=1)


_PROGRAMS = {}


def _newton(stats, gram, w0, lam, *data):
    """Minimise ``data(w) + lam/2 |w|^2`` for ``w0.shape[0]`` independent
    problems at once, as one compiled loop. ``stats(*data, w [E, k]) -> (loss
    [E], grad [E, k], hess [E, k, k])`` gives the data terms, ``gram [E, k, k]``
    each problem's sum of x x^T. Every iteration tries the Newton step; a
    problem whose objective it does not lower takes the step under the
    curvature bound instead (a logistic loss curves at most gram / 4, so that
    step never overshoots and needs no search). The loop ends when every
    problem's estimated distance to its minimiser is under STEP_DONE, or after
    NEWTON_ITERATIONS. Returns (w, objective [E], distance [E])."""
    import jax
    import jax.numpy as jnp

    def program(gram, w0, lam, *data):
        def evaluate(w):
            return _regularised(w, lam, *stats(*data, w))

        def going(carry):
            i, (_w, _obj, g, h) = carry
            return (i < NEWTON_ITERATIONS) & (jnp.max(_distance(lam, g, h)) >= STEP_DONE)

        def step(carry):
            i, (w, obj, g, h) = carry
            w_newton = _step(w, lam, g, h)
            obj_t, g_t, _h = evaluate(w_newton)
            good = _newton_is_good(obj, g, obj_t, g_t)
            w_next = jnp.where(good[:, None], w_newton, _step(w, lam, g, 0.25 * gram))
            return i + 1, (w_next, *evaluate(w_next))

        _i, (w, obj, g, h) = jax.lax.while_loop(going, step, (0, (w0, *evaluate(w0))))
        return w, obj, _distance(lam, g, h)

    if stats not in _PROGRAMS:  # jax is imported on first use, not with this module
        _PROGRAMS[stats] = jax.jit(program)
    return _PROGRAMS[stats](gram, w0, jnp.float32(lam), *data)


def _fixed_stats(X, y, off, w):
    X = X.astype(w.dtype)
    loss, d1, d2 = _logistic_parts(X @ w[0] + off, y)
    return loss.sum()[None], (X.T @ d1)[None], ((X * d2[:, None]).T @ X)[None]


def _random_stats(vals, idx, mask, yb, ob, w):
    import jax
    import jax.numpy as jnp

    V = vals[idx] * mask[..., None]  # [E_c, S_c, K]
    # margins and gradient as plain multiply-and-sum: K is 8, and a batched
    # dot that the chip ran in fewer passes would leave the objective and its
    # gradient inconsistent
    loss, d1, d2 = _logistic_parts(jnp.sum(V * w[:, None, :], axis=2) + ob, yb)
    return (
        jnp.sum(loss * mask, axis=1),
        jnp.sum(V * (d1 * mask)[..., None], axis=1),
        jnp.einsum("esk,es,esl->ekl", V, d2 * mask, V, precision=jax.lax.Precision.HIGHEST),
    )


class _Fixed:
    """A fixed-effect coordinate: one dense [n, d] matrix, one problem."""

    def __init__(self, X, ct):
        self.X, self.ct = X, ct
        self.n, self.k = int(X.shape[0]), int(X.shape[1])
        self.n_problems = 1
        self.gram = None

    def solve(self, y, off, lam):
        import jax
        import jax.numpy as jnp

        if self.gram is None:
            self.gram = jax.jit(lambda X: (X.T @ X)[None])(self.X).astype(jnp.float32)
        return _newton(
            _fixed_stats, self.gram, jnp.zeros((1, self.k), self.ct), lam, self.X, y, off
        )

    def score(self, w):
        return (self.X.astype(self.ct) @ w[0]).astype(self.ct)

    def score_table(self, X, _vals, _ids, w):
        return X.astype(self.ct) @ w[0]


class _Random:
    """A random-effect coordinate: E independent problems. Entities are
    grouped by size into classes (at most 4**c rows), each class one padded
    [E_c, S_c, K] tensor: rows per entity are long-tailed, and one tensor
    padded to the largest entity would not fit."""

    def __init__(self, vals, ids, n_entities, ct):
        import jax
        import jax.numpy as jnp

        ids = np.asarray(ids, np.int64)
        n, self.k = vals.shape
        self.n_problems = int(n_entities)
        self.ct = ct
        self.vals = jnp.asarray(vals, ct)  # [n, K]
        self.ids = jnp.asarray(ids.astype(np.int32))
        counts = np.bincount(ids, minlength=self.n_problems)
        order = np.argsort(ids, kind="stable")
        starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
        size_class = np.ceil(np.log2(np.maximum(counts, 1)) / 2.0).astype(np.int64)
        gram = jax.jit(
            lambda vals, idx, mask: jnp.einsum(
                "esk,es,esl->ekl", vals[idx], mask, vals[idx], precision=jax.lax.Precision.HIGHEST
            ).astype(jnp.float32)
        )
        # (entity rows [E_c], row index [E_c, S_c], mask [E_c, S_c], gram [E_c, K, K])
        self.classes = []
        for c in np.unique(size_class[counts > 0]):
            members = np.flatnonzero((size_class == c) & (counts > 0))
            s_max = int(counts[members].max())
            slot = np.arange(s_max)[None, :]
            valid = slot < counts[members][:, None]
            idx = order[np.minimum(starts[members][:, None] + slot, n - 1)]
            idx, mask = jnp.asarray(np.where(valid, idx, 0).astype(np.int32)), jnp.asarray(valid, ct)
            self.classes.append((members, idx, mask, gram(self.vals, idx, mask)))

    def solve(self, y, off, lam):
        """(w [E, K], objective [E], distance [E]); an entity with no row
        keeps zero coefficients."""
        import jax.numpy as jnp

        w = jnp.zeros((self.n_problems, self.k), self.ct)
        obj = jnp.zeros((self.n_problems,), jnp.float32)
        dist = jnp.zeros((self.n_problems,), jnp.float32)
        for members, idx, mask, gram in self.classes:
            w_c, obj_c, d_c = _newton(
                _random_stats, gram, jnp.zeros((len(members), self.k), self.ct), lam,
                self.vals, idx, mask, y[idx], off[idx],
            )
            w, obj, dist = w.at[members].set(w_c), obj.at[members].set(obj_c), dist.at[members].set(d_c)
        return w, obj, dist

    def score(self, w):
        import jax.numpy as jnp

        return jnp.sum(self.vals * w[self.ids], axis=1)

    def score_table(self, _X, vals, ids, w):
        import jax.numpy as jnp

        return jnp.sum(jnp.asarray(vals, self.ct) * w[jnp.asarray(ids, jnp.int32)], axis=1)


def mean_logloss(z, y) -> float:
    """Mean logistic loss of margins ``z`` against 0/1 labels, float32 on the
    device whatever ``z`` was computed in."""
    import jax.numpy as jnp

    z = jnp.asarray(z, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    return float(jnp.mean(jnp.logaddexp(0.0, jnp.where(y > 0.5, -z, z))))


def auc(z, y) -> float:
    """Area under the ROC curve by ranks (ties share their mean rank), host
    float64."""
    import scipy.stats

    ranks = scipy.stats.rankdata(np.asarray(z, np.float64))
    y = np.asarray(y) > 0.5
    n_pos, n_neg = int(y.sum()), int((~y).sum())
    return float((ranks[y].sum() - n_pos * (n_pos + 1) / 2.0) / (n_pos * n_neg))


def fit(cfg: dict, dataset, dtype: str = "float32") -> list:
    """One record per model of the configuration's sweep, in the order the
    weights are written DESCENDING (the order a Photon sweep trains them):
    ``{"reg", "fixed", "random", "fe_objectives", "train_loss",
    "heldout_loss", "heldout_auc", "distances"}``."""
    import itertools

    import jax
    import jax.numpy as jnp

    ct = _dt(dtype)
    train, val = dataset.train, dataset.validation
    coords = cfg["coordinates"]
    if sum(len(c["reg_weights"]) > 1 for c in coords) and len(coords) > 1:
        raise NotImplementedError(
            "the plain reference follows no warm-start chain across a sweep of a "
            "multi-coordinate model: exact block minimisation there depends on it"
        )
    with _precision(dtype):
        y = jnp.asarray(train.labels, ct)
        solvers = {}
        for c in coords:
            if c["kind"] == "fixed":
                solvers[c["id"]] = _Fixed(train.fe_X, ct)
            else:
                solvers[c["id"]] = _Random(
                    train.re_vals, train.ids[c["entity"]],
                    dataset.n_entities[c["entity"]], ct,
                )
        out = []
        grids = [sorted(set(c["reg_weights"]), reverse=True) for c in coords]
        for weights in itertools.product(*grids):
            lam = {c["id"]: float(w) for c, w in zip(coords, weights)}
            coef = {
                cid: jnp.zeros((s.n_problems, s.k), ct) for cid, s in solvers.items()
            }
            scores = {cid: jnp.zeros((train.n,), ct) for cid in solvers}
            objectives = {cid: [] for cid in solvers}
            distances = {cid: [] for cid in solvers}
            for _pass in range(int(cfg["coordinate_descent_passes"])):
                for cid, s in solvers.items():
                    off = sum(scores[o] for o in solvers if o != cid)
                    off = off if len(solvers) > 1 else jnp.zeros((train.n,), ct)
                    coef[cid], obj, dist = s.solve(y, off, lam[cid])
                    scores[cid] = s.score(coef[cid])
                    objectives[cid].append(float(jnp.sum(obj)))
                    distances[cid].append(float(jnp.max(dist)))
            total = sum(scores.values())
            val_total = sum(
                s.score_table(
                    val.fe_X, val.re_vals,
                    None if cfg_c["kind"] == "fixed" else val.ids[cfg_c["entity"]],
                    coef[cfg_c["id"]],
                )
                for cfg_c, s in zip(coords, solvers.values())
            )
            rec = {
                "reg": lam,
                "fixed": {}, "random": {},
                "fe_objectives": {
                    c["id"]: objectives[c["id"]] for c in coords if c["kind"] == "fixed"
                },
                # the reference's own residual: the worst problem's estimated distance
                # to its exact minimiser, per update
                "distances": distances,
                "train_loss": mean_logloss(total, train.labels),
                "heldout_loss": mean_logloss(val_total, val.labels),
                "heldout_auc": auc(jax.device_get(val_total.astype(jnp.float32)), val.labels),
            }
            for c in coords:
                w = np.asarray(jax.device_get(coef[c["id"]].astype(jnp.float32)), np.float64)
                if c["kind"] == "fixed":
                    rec["fixed"][c["id"]] = w[0]
                else:
                    rec["random"][c["id"]] = (np.arange(w.shape[0]), w)
            out.append(rec)
    return out


def score_answer(cfg: dict, table, answer: dict) -> np.ndarray:
    """Margins of one ANSWER (coefficients as host arrays, random effects keyed
    by entity id; an entity with no row scores 0) on a table of rows, float32
    at ``Precision.HIGHEST`` on the device."""
    import jax
    import jax.numpy as jnp

    with _precision("float32"):
        total = jnp.zeros((table.n,), jnp.float32)
        for c in cfg["coordinates"]:
            if c["kind"] == "fixed":
                total += table.fe_X @ jnp.asarray(answer["fixed"][c["id"]], jnp.float32)
                continue
            entity_ids, rows = answer["random"][c["id"]]
            ids = np.asarray(table.ids[c["entity"]])
            full = np.zeros((int(max(ids.max(), np.max(entity_ids))) + 1, rows.shape[1]))
            full[np.asarray(entity_ids, np.int64)] = rows
            total += jnp.sum(
                jnp.asarray(table.re_vals) * jnp.asarray(full[ids], jnp.float32), axis=1
            )
        return np.asarray(jax.device_get(total), np.float64)
