"""Unit tests for bench.py's tuned-variant selection — the logic that decides
the headline number the driver records. Measurement is stubbed; only the
selection/gating behavior is under test."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import bench  # noqa: E402
from photon_ml_tpu.types import OptimizerType  # noqa: E402

BF16 = "bf16-token"  # the sweep only forwards this to measure()


def make_measure(table, anchor_value=100.0):
    """table: {(opt_type, storage): (throughput, value)} — missing keys raise."""

    def measure(opt_type, storage):
        key = (OptimizerType(opt_type), storage)
        if key not in table:
            raise RuntimeError(f"variant {key} exploded")
        tp, val = table[key]
        return tp, val if val is not None else anchor_value

    return measure


def test_fastest_gated_variant_wins():
    measure = make_measure({
        (OptimizerType.LBFGS, None): (1000.0, 100.0),
        (OptimizerType.NEWTON, None): (1500.0, 100.2),   # within 1%
        (OptimizerType.NEWTON, BF16): (2000.0, 100.5),   # within 1%, fastest
    })
    best, info = bench.run_variant_sweep(measure, bf16=BF16)
    assert best == 2000.0
    assert info["variant"] == "newton_bf16"
    assert info["newton_f32_quality_gate"] and info["newton_bf16_quality_gate"]
    assert "lbfgs_bf16_samples_per_sec" not in info  # newton won: not measured


def test_quality_gate_rejects_fast_but_wrong():
    measure = make_measure({
        (OptimizerType.LBFGS, None): (1000.0, 100.0),
        (OptimizerType.NEWTON, None): (9999.0, 110.0),   # 10% off: rejected
        (OptimizerType.NEWTON, BF16): (9999.0, 98.0),    # 2% off: rejected
        (OptimizerType.LBFGS, BF16): (1200.0, 100.9),    # within 1%: wins
    })
    best, info = bench.run_variant_sweep(measure, bf16=BF16)
    assert best == 1200.0
    assert info["variant"] == "lbfgs_bf16"
    assert info["newton_f32_quality_gate"] is False
    assert info["newton_bf16_quality_gate"] is False


def test_variant_failure_is_recorded_and_anchor_survives():
    measure = make_measure({
        (OptimizerType.LBFGS, None): (1000.0, 100.0),
        # every tuned variant explodes (missing from the table)
    })
    best, info = bench.run_variant_sweep(measure, bf16=BF16)
    assert best == 1000.0
    assert info["variant"] == "lbfgs_f32"
    assert "newton_f32_error" in info and "exploded" in info["newton_f32_error"]
    # ... and the caller is told: main() exits non-zero on any of these
    assert "newton_f32_error" in bench.variant_errors(info)
    assert bench.variant_errors({"variant": "lbfgs_f32"}) == []


def test_pallas_variant_runs_on_the_winner(monkeypatch):
    from photon_ml_tpu.ops import pallas_glm

    monkeypatch.delenv("PHOTON_PALLAS", raising=False)
    pallas_states = []
    table = {
        (OptimizerType.LBFGS, None): (1000.0, 100.0),
        (OptimizerType.NEWTON, None): (1500.0, 100.0),
        (OptimizerType.NEWTON, BF16): (1400.0, 100.0),
    }
    base = make_measure(table)

    def measure(opt, storage):
        pallas_states.append(pallas_glm.pallas_enabled())
        if pallas_states[-1]:  # the pallas re-measure of the winner
            assert (OptimizerType(opt), storage) == (OptimizerType.NEWTON, None)
            return 1800.0, 100.0
        return base(opt, storage)

    prev = pallas_glm.enabled_override()
    best, info = bench.run_variant_sweep(measure, bf16=BF16)
    assert best == 1800.0
    assert info["variant"] == "newton_f32_pallas"
    assert pallas_glm.enabled_override() == prev  # state restored after the sweep
    assert pallas_states[-1] is True and not any(pallas_states[:-1])


def _run_main(monkeypatch, capsys, argv, info, platform="tpu"):
    """Drive bench.main()'s single-process path with the device and the
    measurement stubbed; returns (exit code or None, parsed stdout lines)."""
    import json

    dev = {"platform": platform, "device_kind": "TPU v5 lite", "device_count": 1}
    monkeypatch.setattr(bench, "device_record", lambda: dict(dev))
    monkeypatch.setattr(
        bench, "run_benchmark", lambda device_data=False: (500000.0, dict(info))
    )
    monkeypatch.setattr(bench.sys, "argv", ["bench.py", *argv])
    code = None
    try:
        bench.main()
    except SystemExit as e:
        code = e.code
    out = capsys.readouterr().out.strip()
    return code, [json.loads(l) for l in out.splitlines() if l]


def test_main_without_tpu_exits_nonzero_and_prints_no_metric(monkeypatch, capsys):
    """No chip -> non-zero exit and NO metric line: a CPU timing must never
    appear under the device metric's name (the removed fallback ladder did
    exactly that, tagged tpu_unavailable)."""
    code, lines = _run_main(
        monkeypatch, capsys, [], {"variant": "lbfgs_f32"}, platform="cpu"
    )
    assert code not in (0, None)
    assert lines == []


def test_main_on_this_cpu_sandbox_refuses_for_real(monkeypatch, capsys):
    """Unstubbed: the suite runs on the CPU platform, so the real
    require_tpu() must stop main() before any workload is built."""
    import pytest as _pytest

    monkeypatch.setattr(bench.sys, "argv", ["bench.py"])
    monkeypatch.setattr(
        bench, "run_benchmark",
        lambda device_data=False: _pytest.fail("measured without a TPU"),
    )
    with _pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_main_result_line_names_the_device(monkeypatch, capsys):
    code, lines = _run_main(
        monkeypatch, capsys, ["--scale", "10"],
        {"variant": "newton_f32", "newton_f32_samples_per_sec": 500000.0},
    )
    assert code in (0, None)
    (rec,) = lines
    assert rec["metric"] == "glmix_cd_pass_samples_per_sec"
    assert rec["value"] == 500000.0 and rec["variant"] == "newton_f32"
    assert (rec["platform"], rec["device_kind"], rec["device_count"]) == (
        "tpu", "TPU v5 lite", 1
    )
    assert rec["scale"] == 10.0
    assert "vs_baseline" not in rec and "tpu_unavailable" not in rec


def test_main_exits_nonzero_when_a_variant_errored(monkeypatch, capsys):
    """try_variant keeps recording a variant's error so the others still
    run, but the run is then not a clean result: exit non-zero, with the
    error in the printed line."""
    code, lines = _run_main(
        monkeypatch, capsys, [],
        {"variant": "lbfgs_f32", "newton_bf16_error": "XlaRuntimeError: boom"},
    )
    assert code not in (0, None)
    (rec,) = lines
    assert rec["newton_bf16_error"].endswith("boom")
    assert rec["platform"] == "tpu"


def test_require_tpu_rejects_a_device_kind_without_peaks(monkeypatch):
    import pytest as _pytest

    monkeypatch.setattr(
        bench, "device_record",
        lambda: {"platform": "tpu", "device_kind": "Strange Chip 9000",
                 "device_count": 1},
    )
    with _pytest.raises(KeyError, match="Strange Chip 9000"):
        bench.require_tpu()


def test_peaks_table_is_keyed_by_device_kind():
    assert bench.peaks_for("TPU v5 lite") == (197e12, 819e9)
    assert bench.peaks_for("TPU v5e") == (197e12, 819e9)
    import pytest as _pytest

    for unknown in ("cpu", "", None, "NVIDIA H100"):
        with _pytest.raises(KeyError):
            bench.peaks_for(unknown)


def test_generate_workload_is_the_builders_source():
    """chip_smoke.py trains GameEstimator on _generate_workload's arrays and
    bench.py buckets the SAME arrays: one seeded generative process."""
    import numpy as np

    a = bench._generate_workload(400, 12, 5)
    b = bench._generate_workload(400, 12, 5)
    fe_X, users, items, y, re_feat = a
    assert fe_X.shape == (400, bench.N_FEATURES) and fe_X.dtype == np.float32
    assert re_feat.shape == (400, 8) and set(np.unique(y)) <= {0.0, 1.0}
    assert users.max() < 12 and items.max() < 5
    for x, z in zip(a[:4], b[:4]):
        np.testing.assert_array_equal(x, z)
    np.testing.assert_array_equal(re_feat.toarray()[:, 1:], fe_X[:, :7])
    _, y2, ds_u, ds_i = bench._build_workload(np.float32, 400, 12, 5)
    np.testing.assert_array_equal(y2, y)
    assert ds_u.n_entities == len(np.unique(users))
    assert ds_i.n_entities == len(np.unique(items))


def test_should_fuse_no_longer_swallows_backend_errors(monkeypatch):
    """The gate used to answer False when the backend query raised — a chip
    that failed to initialise read as 'kernels not applicable'."""
    import jax
    import pytest as _pytest

    from photon_ml_tpu.ops import pallas_glm

    monkeypatch.delenv("PHOTON_PALLAS_INTERPRET", raising=False)

    def broken():
        raise RuntimeError("backend init failed")

    monkeypatch.setattr(jax, "default_backend", broken)
    with pallas_glm.pallas_override(True):
        with _pytest.raises(RuntimeError, match="backend init failed"):
            pallas_glm.should_fuse(64, "float32")


def test_interpreted_kernels_on_a_tpu_backend_are_an_error(monkeypatch):
    import jax
    import pytest as _pytest

    from photon_ml_tpu.ops import pallas_glm

    monkeypatch.setenv("PHOTON_PALLAS_INTERPRET", "1")
    assert pallas_glm.interpret_mode() is True  # the CPU test hook still works
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with _pytest.raises(RuntimeError, match="PHOTON_PALLAS_INTERPRET"):
        pallas_glm.interpret_mode()
    with pallas_glm.pallas_override(True):
        with _pytest.raises(RuntimeError, match="PHOTON_PALLAS_INTERPRET"):
            pallas_glm.should_fuse(64, "float32")


def test_run_benchmarks_errored_config_always_exits_nonzero(monkeypatch, capsys):
    """--no-strict excuses a quality-parity miss, never a config that did
    not run."""
    import importlib.util
    import json

    import pytest as _pytest

    spec = importlib.util.spec_from_file_location(
        "run_benchmarks_under_test",
        os.path.join(os.path.dirname(bench.__file__), "benchmarks", "run_benchmarks.py"),
    )
    rb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rb)

    def boom():
        raise RuntimeError("device lost")

    monkeypatch.setattr(
        rb, "CONFIGS", {"9": ("broken", boom), "8": ("fine", lambda: {"value": 1.0})}
    )
    with _pytest.raises(SystemExit) as e:
        rb.main(["--configs", "9,8", "--no-strict"])
    assert e.value.code == 1
    lines = [json.loads(l) for l in capsys.readouterr().out.strip().splitlines()]
    assert {"configs_errored": ["broken"]} in lines
    assert any("fine" in rec for rec in lines)  # the other config still ran


def test_device_workload_builder_structure(monkeypatch):
    """The device-native builder must produce the same structural invariants
    the host builder guarantees: every sample appears exactly once in exactly
    one bucket of its coordinate, padding rows carry weight 0, and the
    per-sample scoring view references live entity rows."""
    import jax.numpy as jnp
    import numpy as np

    monkeypatch.setattr(bench, "N_SAMPLES", 500)
    monkeypatch.setattr(bench, "N_USERS", 40)
    monkeypatch.setattr(bench, "N_ITEMS", 10)
    data = bench._build_workload_device()
    assert data.labels.shape == (500,)
    assert set(np.unique(np.asarray(data.labels))) <= {0.0, 1.0}
    for rc, E in zip(data.re, (40, 10)):
        assert rc.n_entities == E and rc.max_k == 8
        rows = np.asarray(rc.sample_entity_rows)
        assert rows.min() >= 0 and rows.max() < E
        ids = np.concatenate(
            [np.asarray(b.sample_ids).ravel() for b in rc.buckets]
        )
        ids = ids[ids >= 0]
        assert len(ids) == 500 and len(np.unique(ids)) == 500
        for b in rc.buckets:
            w = np.asarray(b.weights)
            s = np.asarray(b.sample_ids)
            assert ((w > 0) == (s >= 0)).all()
            assert np.asarray(b.X)[s < 0].sum() == 0.0  # padding rows zeroed
        # scoring view reconstructs each sample's RE margin from re_vals
        np.testing.assert_array_equal(
            np.asarray(rc.sample_local_cols[0]), np.arange(8)
        )

    bf16 = bench._build_workload_device(jnp.bfloat16)
    assert bf16.fe_X.dtype == jnp.bfloat16
    assert bf16.labels.dtype == jnp.float32  # compute dtype untouched
    # storage dtype covers the RE hot-loop arrays too
    assert bf16.re[0].sample_vals.dtype == jnp.bfloat16
    assert bf16.re[0].buckets[0].X.dtype == jnp.bfloat16
    assert bf16.re[0].buckets[0].weights.dtype == jnp.float32


class _FakeMatrix:
    def __init__(self, n, d):
        self.n_rows, self.n_cols = n, d


class _FakeBucket:
    def __init__(self, E, S, K):
        import numpy as np

        self.X = np.zeros((E, S, K))


class _FakeRE:
    def __init__(self, buckets, n, k):
        import numpy as np

        self.buckets = buckets
        self.sample_vals = np.zeros((n, k))


class _FakeData:
    def __init__(self, n=1000, d=64):
        self.fe_X = _FakeMatrix(n, d)
        self.re = (_FakeRE([_FakeBucket(10, 16, 8)], n, 8),)


def test_analytic_cost_lbfgs_counts_fe_and_re():
    data = _FakeData(n=1000, d=64)
    c = bench._analytic_cost(data, fe_iters=10, re_iters=5, newton=False, storage_bytes=4)
    fe_flops = 10 * 4.0 * 1000 * 64
    re_flops = 5 * 4.0 * (10 * 16) * 8
    score_flops = 2.0 * 1000 * 8
    assert c["flops_per_pass"] == fe_flops + re_flops + score_flops
    fe_bytes = 10 * 2.0 * 1000 * 64 * 4
    re_bytes = 5 * 2.0 * (10 * 16) * 8 * 4
    score_bytes = 1000 * 8 * 4
    assert c["hbm_bytes_per_pass"] == fe_bytes + re_bytes + score_bytes
    assert c["fe_iterations_measured"] == 10


def test_analytic_cost_newton_adds_hessian_and_bf16_halves_bytes():
    data = _FakeData(n=1000, d=64)
    lb = bench._analytic_cost(data, fe_iters=10, re_iters=5, newton=False, storage_bytes=4)
    nw = bench._analytic_cost(data, fe_iters=10, re_iters=5, newton=True, storage_bytes=4)
    assert nw["flops_per_pass"] > lb["flops_per_pass"]  # + 2nd^2 + d^3/3 terms
    assert nw["hbm_bytes_per_pass"] > lb["hbm_bytes_per_pass"]  # extra X pass
    half = bench._analytic_cost(data, fe_iters=10, re_iters=5, newton=False, storage_bytes=2)
    # matrix traffic halves; only the bytes model scales with storage width
    assert half["hbm_bytes_per_pass"] == lb["hbm_bytes_per_pass"] / 2
    assert half["flops_per_pass"] == lb["flops_per_pass"]


def test_roofline_regime_and_utilization(monkeypatch):
    """MFU/HBM utilization against the chip peak table, regime classification,
    and an unknown chip as an error (no invented numbers)."""
    import types

    fake_dev = types.SimpleNamespace(device_kind="TPU v5 lite")
    import jax as _jax

    monkeypatch.setattr(_jax, "devices", lambda: [fake_dev])
    # 100k samples at 1M samples/s -> 0.1 s/pass
    cost = {"flops_per_pass": 1.97e12, "hbm_bytes_per_pass": 8.19e10}
    out = bench._roofline(cost, samples_per_sec=1_000_000.0, n_samples=100_000)
    assert out["mfu"] == round(1.97e13 / 197e12, 5)  # 0.1
    assert out["hbm_util"] == round(8.19e11 / 819e9, 5)  # 1.0
    assert out["regime"] == "bandwidth"  # intensity 24 < ridge 240.5
    # far from both ceilings -> latency-bound
    tiny = {"flops_per_pass": 1e9, "hbm_bytes_per_pass": 1e8}
    assert (
        bench._roofline(tiny, samples_per_sec=1_000_000.0, n_samples=100_000)["regime"]
        == "latency"
    )
    # compute-bound: intensity above the ridge and high MFU
    hot = {"flops_per_pass": 1.97e13 * 0.8, "hbm_bytes_per_pass": 1.97e13 * 0.8 / 300}
    assert (
        bench._roofline(hot, samples_per_sec=1_000_000.0, n_samples=100_000)["regime"]
        == "compute"
    )
    fake_dev.device_kind = "Strange Chip 9000"
    import pytest as _pytest

    with _pytest.raises(KeyError):  # an unknown device is an error, not a default
        bench._roofline(cost, samples_per_sec=1_000_000.0, n_samples=100_000)


def test_winner_roofline_lookup_decodes_variant_names(monkeypatch):
    import types

    import jax as _jax

    monkeypatch.setattr(
        _jax, "devices", lambda: [types.SimpleNamespace(device_kind="TPU v5 lite")]
    )
    costs = {
        ("LBFGS", None, False, None): {"flops_per_pass": 1.0, "hbm_bytes_per_pass": 1.0},
        ("NEWTON", "bfloat16", False, None): {"flops_per_pass": 2.0, "hbm_bytes_per_pass": 2.0},
        ("NEWTON", "bfloat16", True, None): {"flops_per_pass": 3.0, "hbm_bytes_per_pass": 3.0},
        ("LBFGS", None, False, 15): {"flops_per_pass": 4.0, "hbm_bytes_per_pass": 4.0},
    }
    out = bench._winner_roofline(
        {"variant": "newton_bf16_pallas"}, costs, samples_per_sec=1000.0, n_samples=100
    )
    assert out["roofline"]["flops_per_pass"] == 3.0
    out = bench._winner_roofline(
        {"variant": "lbfgs_f32"}, costs, samples_per_sec=1000.0, n_samples=100
    )
    assert out["roofline"]["flops_per_pass"] == 1.0
    out = bench._winner_roofline(
        {"variant": "lbfgs_f32_ls15"}, costs, samples_per_sec=1000.0, n_samples=100
    )
    assert out["roofline"]["flops_per_pass"] == 4.0
    # a variant whose configuration was never measured yields no roofline
    assert bench._winner_roofline({"variant": "lbfgs_f32"}, {}, 1000.0, 100) == {}


def test_analytic_cost_measured_re_iterations():
    """The measured path: per-coordinate, per-bucket MAX iteration counts
    replace the config cap (a vmapped while_loop executes max-lane iterations
    for every lane), and the record is labeled accordingly."""
    data = _FakeData(n=1000, d=64)
    c = bench._analytic_cost(
        data, fe_iters=10, re_iters=((7,),), newton=False, storage_bytes=4
    )
    fe_flops = 10 * 4.0 * 1000 * 64
    re_flops = 7 * 4.0 * (10 * 16) * 8
    score_flops = 2.0 * 1000 * 8
    assert c["flops_per_pass"] == fe_flops + re_flops + score_flops
    assert c["re_iterations_measured"] == [[7]]
    assert "re_iterations_assumed" not in c
    assert c["cost_model"] == "analytic (fe + re iters measured, mean over timed passes)"
    # int fallback keeps the cap-labeled record
    c2 = bench._analytic_cost(
        data, fe_iters=10, re_iters=5, newton=False, storage_bytes=4
    )
    assert c2["re_iterations_assumed"] == 5


def test_ls15_variant_wins_when_faster_and_gated():
    """The winner is re-measured with the Breeze combined line-search budget
    (ls=15): shape-dependent trade, decided empirically per run."""
    def measure(opt, storage, ls=None):
        if ls == 15:
            assert (OptimizerType(opt), storage) == (OptimizerType.NEWTON, None)
            return 1800.0, 100.1  # faster AND within the 1% gate
        table = {
            (OptimizerType.LBFGS, None): (1000.0, 100.0),
            (OptimizerType.NEWTON, None): (1500.0, 100.0),
            (OptimizerType.NEWTON, BF16): (1400.0, 100.0),
        }
        return table[(OptimizerType(opt), storage)]

    best, info = bench.run_variant_sweep(measure, bf16=BF16)
    assert best == 1800.0
    assert info["variant"] == "newton_f32_ls15"
    assert info["newton_f32_ls15_quality_gate"] is True
