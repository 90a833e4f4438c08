"""The system under test, as the window drives it.

This is the only chipbench module that imports the program. It builds the
``GameEstimator`` a configuration file describes (default options: host loop,
``re_update_program=True``, ``re_solver="lbfgs"``, f32, no mesh, no
checkpoints), prepares its datasets ONCE, and exposes ``fit_unit()``: one
whole training job from zero coefficients through ``GameEstimator.fit`` itself
— the same sweep expansion, coordinate construction, warm-start chaining and
``run_coordinate_descent`` a user's fit runs — with only the three
``prepare_*`` steps answered from what set-up already prepared.

With ``spans`` given (traced runs only) every coordinate is wrapped in a proxy
that records a host span around ``update_and_score``, synced at its end, and
the evaluation suite in one around ``evaluate``. Untraced runs use the
program's objects as they are: nothing of the harness sits inside a unit.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np


class Spans:
    """Host spans on the profiler's clock (``time.time_ns`` is what the
    xplane's host lines use) and in the trace itself."""

    def __init__(self):
        self.records: list = []  # (name, start_ns, end_ns)

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t0 = time.time_ns()
        with jax.profiler.TraceAnnotation(f"chipbench:{name}"):
            try:
                yield
            finally:
                self.records.append((name, t0, time.time_ns()))


class _SpannedCoordinate:
    """Duck-typed coordinate (``run_coordinate_descent`` asks for nothing
    more): every attribute is the wrapped coordinate's, and its updates and
    scores run inside a span that ends when their outputs are ready on the
    device."""

    def __init__(self, inner, spans: Spans, name: str):
        self._inner = inner
        self._spans = spans
        self._name = name

    def __getattr__(self, attr):
        return getattr(self._inner, attr)

    def _spanned(self, method, *args, **kwargs):
        import jax

        from photon_ml_tpu.algorithm.coordinate import coefficient_arrays
        from photon_ml_tpu.models.game import FixedEffectModel, RandomEffectModel

        with self._spans.span(self._name):
            out = getattr(self._inner, method)(*args, **kwargs)
            ready = []
            for part in out if isinstance(out, tuple) else (out,):
                if isinstance(part, (FixedEffectModel, RandomEffectModel)):
                    ready.extend(coefficient_arrays(part))
                elif isinstance(part, jax.Array):
                    ready.append(part)
            jax.block_until_ready(ready)
        return out

    # the three calls through which run_coordinate_descent makes a coordinate
    # work: the fused protocol, and the update_model + score pair it falls
    # back to where update_and_score returns None (the fixed effect on one
    # device)
    def update_and_score(self, *args, **kwargs):
        return self._spanned("update_and_score", *args, **kwargs)

    def update_model(self, *args, **kwargs):
        return self._spanned("update_model", *args, **kwargs)

    def score(self, *args, **kwargs):
        return self._spanned("score", *args, **kwargs)


class _SpannedSuite:
    def __init__(self, inner, spans: Spans):
        self._inner = inner
        self._spans = spans

    def __getattr__(self, attr):
        return getattr(self._inner, attr)

    def evaluate(self, *args, **kwargs):
        with self._spans.span("validate"):
            return self._inner.evaluate(*args, **kwargs)


def _coordinate_configurations(cfg: dict) -> dict:
    from photon_ml_tpu.estimators import (
        CoordinateConfiguration,
        FixedEffectDataConfiguration,
        RandomEffectDataConfiguration,
    )
    from photon_ml_tpu.optimization.common import OptimizerConfig
    from photon_ml_tpu.optimization.config import (
        GLMOptimizationConfiguration,
        RegularizationContext,
    )
    from photon_ml_tpu.types import OptimizerType, RegularizationType

    out = {}
    for c in cfg["coordinates"]:
        weights = tuple(float(w) for w in c["reg_weights"])
        opt = GLMOptimizationConfiguration(
            optimizer_config=OptimizerConfig(
                optimizer_type=OptimizerType[c["optimizer"]],
                max_iterations=int(c["max_iterations"]),
            ),
            regularization_context=RegularizationContext(
                RegularizationType[c["regularization"]]
            ),
            regularization_weight=weights[0],
        )
        if c["kind"] == "fixed":
            data = FixedEffectDataConfiguration(c["shard"])
        elif c["kind"] == "random":
            data = RandomEffectDataConfiguration(c["entity"], c["shard"])
        else:
            raise ValueError(f"coordinate {c['id']!r}: unknown kind {c['kind']!r}")
        out[c["id"]] = CoordinateConfiguration(
            data_config=data,
            optimization_config=opt,
            reg_weights=weights if len(weights) > 1 else (),
        )
    return out


def _game_input(table):
    from photon_ml_tpu.data.game_data import GameInput

    features = {"global": table.fe_X}
    if table.re_vals is not None:
        features["re"] = table.re_csr()
    return GameInput(features=features, labels=table.labels, id_columns=dict(table.ids))


def configure_compilation_cache() -> str:
    """The repo's one cache policy: ``JAX_COMPILATION_CACHE_DIR`` where set,
    else the fixed ``<checkout>/.jax_cache``."""
    from photon_ml_tpu.cli.runtime import configure_compilation_cache as configure

    return configure()


class System:
    """One estimator with its prepared datasets: the object set-up builds,
    warms, and hands to the window."""

    def __init__(self, cfg: dict, dataset, spans: Spans | None = None):
        from photon_ml_tpu.estimators import GameEstimator
        from photon_ml_tpu.evaluation import EvaluatorType

        self.cfg = cfg
        self.spans = spans
        self.train = _game_input(dataset.train)
        self.validation = _game_input(dataset.validation)
        system = self

        class PreparedEstimator(GameEstimator):
            def prepare_training_datasets(self, data, *a, **kw):
                return system.train_datasets

            def prepare_scoring_datasets(self, data):
                return system.validation_datasets

            def prepare_evaluation_suite(self, validation):
                return system.suite

            def build_coordinate(self, cid, *a, **kw):
                coord = GameEstimator.build_coordinate(self, cid, *a, **kw)
                kind = system.coordinate_kinds[cid]
                for wrap in system.coordinate_wrappers:
                    coord = wrap(cid, kind, coord)
                return coord

        self.coordinate_kinds = {
            c["id"]: ("fe" if c["kind"] == "fixed" else "re") for c in cfg["coordinates"]
        }
        self.estimator = PreparedEstimator(
            task=cfg["task"],
            coordinate_configurations=_coordinate_configurations(cfg),
            n_iterations=int(cfg["coordinate_descent_passes"]),
            validation_evaluators=[EvaluatorType[cfg["evaluator"]]],
        )
        self.train_datasets = None
        self.validation_datasets = None
        self.suite = None
        # (coordinate id, "fe" | "re", coordinate) -> coordinate, applied to
        # every coordinate the estimator builds: the span proxy of traced
        # runs, and what tests/faults.py plants
        self.coordinate_wrappers = []
        if spans is not None:
            self.coordinate_wrappers.append(
                lambda _cid, kind, coord: _SpannedCoordinate(coord, spans, f"{kind}_update")
            )

    def prepare(self) -> None:
        """``prepare_training_datasets`` + scoring datasets + suite, once,
        ending when every array is on the device."""
        import jax
        from photon_ml_tpu.estimators import GameEstimator

        est = self.estimator
        self.train_datasets = GameEstimator.prepare_training_datasets(est, self.train)
        self.validation_datasets = GameEstimator.prepare_scoring_datasets(
            est, self.validation
        )
        suite = GameEstimator.prepare_evaluation_suite(est, self.validation)
        self.suite = suite if self.spans is None else _SpannedSuite(suite, self.spans)
        jax.block_until_ready(
            [
                leaf
                for leaf in jax.tree_util.tree_leaves(
                    (self.train_datasets, self.validation_datasets)
                )
                if isinstance(leaf, jax.Array)
            ]
        )

    def fit_unit(self):
        """One fit unit: the program's own ``fit`` over the prepared datasets,
        ending in ``block_until_ready`` on every model and score it made."""
        import jax
        from photon_ml_tpu.algorithm.coordinate import coefficient_arrays

        results = self.estimator.fit(self.train, validation_data=self.validation)
        jax.block_until_ready(
            [
                (
                    [a for _cid, m in r.model for a in coefficient_arrays(m)],
                    list(r.descent.training_scores.values()),
                )
                for r in results
            ]
        )
        return results

    def release(self) -> None:
        """Drop everything the program made (datasets, suite, estimator), so
        that the device is free for the reference."""
        self.train_datasets = self.validation_datasets = self.suite = None
        self.estimator = self.train = self.validation = None


def unit_answers(results) -> list:
    """What one unit produced, as plain host arrays, one record per model of
    the sweep in the order the program trained them: coefficients (random
    effects keyed by entity id, in the shard's own column order), each fixed
    solve's final objective as its tracker reports it, solver iteration
    counts, and the last validation metric."""
    import jax
    from photon_ml_tpu.models.game import FixedEffectModel

    out = []
    for r in results:
        rec = {"reg": {}, "fixed": {}, "random": {}, "fe_objectives": {}, "iterations": {}}
        for cid, m in r.model:
            rec["reg"][cid] = float(r.configuration[cid].regularization_weight)
            if isinstance(m, FixedEffectModel):
                rec["fixed"][cid] = np.asarray(
                    jax.device_get(m.model.coefficients.means), np.float64
                )
                continue
            coeffs = np.asarray(jax.device_get(m.coeffs), np.float64)
            proj = np.asarray(jax.device_get(m.proj_indices))
            n_ent = len(m.entity_ids)
            width = int(proj.max()) + 1
            table = np.zeros((n_ent, width))
            ent, slot = np.nonzero(proj[:n_ent] >= 0)
            table[ent, proj[ent, slot]] = coeffs[ent, slot]
            rec["random"][cid] = (np.asarray(m.entity_ids), table)
        for cid, trackers in r.descent.trackers.items():
            if cid in rec["fixed"]:
                rec["fe_objectives"][cid] = [float(t.final_value) for t in trackers]
                rec["iterations"][cid] = [int(t.iterations) for t in trackers]
            else:
                rec["iterations"][cid] = [float(t.iterations_mean) for t in trackers]
        history = r.descent.metrics_history
        rec["validation_metric"] = (
            None if not history else float(next(iter(history[-1][2].values())))
        )
        out.append(rec)
    return out
