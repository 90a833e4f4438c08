"""Faults planted underneath the timed path (tests only): each takes the
prepared ``entry.System`` and breaks what the window will drive. A run with
any of them has to come out ``correct: false``."""

from __future__ import annotations


class _Proxy:
    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, attr):
        return getattr(self._inner, attr)


def _last(system, kind):
    ids = [cid for cid, k in system.coordinate_kinds.items() if k == kind]
    return ids[-1] if ids else None


def state_unchanged(system):
    """The last coordinate's update returns the model it was given."""
    target = _last(system, "re") or _last(system, "fe")

    class Unchanged(_Proxy):
        def update_and_score(self, initial_model, partial_scores, prev_score, donate=False):
            out = self._inner.update_and_score(initial_model, partial_scores, prev_score, donate=False)
            if out is None:
                return None
            _model, _score, tracker = out
            return initial_model, prev_score, tracker

        def update_model(self, initial_model, partial_scores):
            _model, tracker = self._inner.update_model(initial_model, partial_scores)
            return initial_model, tracker

    system.coordinate_wrappers.append(
        lambda cid, _kind, coord: Unchanged(coord) if cid == target else coord
    )


def half_batch(system):
    """The fixed effect trains on the first half of the rows only (the second
    half's weights are zero)."""
    import dataclasses

    import jax.numpy as jnp

    cid = _last(system, "fe")
    ds = system.train_datasets[cid]
    n = int(ds.data.weights.shape[0])
    weights = ds.data.weights * (jnp.arange(n) < n // 2)
    system.train_datasets[cid] = dataclasses.replace(
        ds, data=dataclasses.replace(ds.data, weights=weights)
    )


def no_exchange(system):
    """Every coordinate trains as if the others scored nothing."""
    import jax.numpy as jnp

    class Alone(_Proxy):
        def update_and_score(self, initial_model, partial_scores, prev_score, donate=False):
            return self._inner.update_and_score(
                initial_model, jnp.zeros_like(partial_scores), prev_score, donate=donate
            )

        def update_model(self, initial_model, partial_scores):
            return self._inner.update_model(initial_model, jnp.zeros_like(partial_scores))

    system.coordinate_wrappers.append(lambda _cid, _kind, coord: Alone(coord))


ALTERED_SHARE = 0.05


def answer_altered(system):
    """One coefficient of the fixed effect is moved by 5 % of the vector's
    norm where the solve hands it back."""
    import dataclasses

    import jax.numpy as jnp

    target = _last(system, "fe")

    class Altered(_Proxy):
        def update_model(self, initial_model, partial_scores):
            model, tracker = self._inner.update_model(initial_model, partial_scores)
            coef = model.model.coefficients
            means = coef.means.at[0].add(ALTERED_SHARE * jnp.linalg.norm(coef.means))
            glm = dataclasses.replace(model.model, coefficients=dataclasses.replace(coef, means=means))
            return dataclasses.replace(model, model=glm), tracker

    system.coordinate_wrappers.append(
        lambda cid, _kind, coord: Altered(coord) if cid == target else coord
    )


FAULTS = {
    "state_unchanged": state_unchanged,
    "half_batch": half_batch,
    "no_exchange": no_exchange,
    "answer_altered": answer_altered,
}
