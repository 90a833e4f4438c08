"""chip_smoke.py off the chip, and the one compile-cache policy.

The smoke proves the train -> checkpoint -> serve path on a TPU; here its
control flow is held on the CPU: the rehearsal flag runs every leg tiny with
the kernels interpreted (so chip time is never spent finding typos), and
without the flag — or without the repo around it — the script exits non-zero
having done no work and printed no result.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")
PREFIX = "REHEARSAL platform=cpu "


def _run(args, cwd=REPO, script=SMOKE, timeout=600, **env_overrides):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # the child decides its own device count (conftest forces 8 for the suite)
    env.pop("XLA_FLAGS", None)
    env.update(env_overrides)
    return subprocess.run(
        [sys.executable, script, *args],
        capture_output=True, text=True, cwd=cwd, env=env, timeout=timeout,
    )


def _result_lines(stdout):
    """Lines that parse as a bare JSON object carrying "ok": what the driver
    would read as the smoke's result."""
    out = []
    for line in stdout.splitlines():
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(rec, dict) and "ok" in rec:
            out.append(rec)
    return out


def test_without_the_flag_on_cpu_exits_nonzero_having_done_no_work():
    proc = _run([])
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    # the device line is all it printed: no leg started, no result
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert len(lines) == 1 and lines[0].startswith("device: ")
    assert "platform=cpu" in lines[0]
    assert _result_lines(proc.stdout) == []


def test_alone_in_a_directory_exits_nonzero_and_prints_no_result(tmp_path):
    """The driver also runs the script without the program around it."""
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    for args in ([], ["--rehearsal"]):
        proc = _run(
            args, cwd=str(tmp_path), script=str(tmp_path / "chip_smoke.py"),
            PYTHONPATH="",
        )
        assert proc.returncode != 0
        assert _result_lines(proc.stdout) == []


def _assert_rehearsal(proc, legs):
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    # EVERY line says what it is; none can pass for a chip result
    assert all(l.startswith(PREFIX) for l in lines), [
        l for l in lines if not l.startswith(PREFIX)
    ][:3]
    assert _result_lines(proc.stdout) == []
    # the last line is the contract's result line and nothing more: exactly
    # "ok" and "device", the device exactly platform, kind and count
    last = json.loads(lines[-1][len(PREFIX):])
    assert list(last) == ["ok", "device"] and last["ok"] is True
    assert sorted(last["device"]) == ["count", "kind", "platform"]
    assert last["device"]["platform"] == "cpu"
    assert isinstance(last["device"]["kind"], str)
    assert type(last["device"]["count"]) is int
    # the line before it is the summary, which claims nothing
    assert lines[-2].startswith(PREFIX + "summary: ")
    rec = json.loads(lines[-2][len(PREFIX + "summary: "):])
    assert rec["device"] == last["device"]
    assert list(rec)[-1] == "claim" and rec["claim"] is None
    assert set(rec["seconds"]) == set(legs) and set(rec["legs"]) == set(legs)
    for leg in ("train", "serve"):
        assert rec["seconds"][leg]["first"] > 0
        assert rec["seconds"][leg]["repeat"] is not None
    assert rec["legs"]["train"]["retraces_in_repeat"] == 0
    assert rec["legs"]["serve"]["retraces_in_repeat"] == 0
    assert rec["compile_cache_dir"] == os.environ["JAX_COMPILATION_CACHE_DIR"]
    return rec


def test_rehearsal_runs_every_leg_tiny_on_cpu():
    rec = _assert_rehearsal(
        _run(["--rehearsal"]), ("train", "serve", "kernels", "cli")
    )
    assert rec["mesh_devices"] == 1
    assert rec["legs"]["train"]["auc"] > 0.75
    solver = rec["legs"]["train"]["solver"]
    assert len(solver["fixed"]) == 2  # two passes, iteration counts reported
    assert solver["fixed"][1]["value"] < solver["fixed"][0]["value"]
    assert rec["legs"]["kernels"]["worst_rel_err"] < 0.05
    assert rec["legs"]["cli"]["score_max_abs_diff"] <= 1e-4


def test_rehearsal_over_four_emulated_devices_checks_the_placement():
    rec = _assert_rehearsal(
        _run(["--rehearsal", "--devices", "4"]),
        ("train", "serve", "placement"),
    )
    assert rec["mesh_devices"] == 4 and rec["device"]["count"] == 4
    rows = dict(rec["legs"]["placement"]["rows"])
    assert rows["fe_X"] == [750] * 4  # a quarter of 3000 each
    rows.update(rec["legs"]["train"]["shards"])
    assert rows["score.fixed"] == [750] * 4 and "table.per-user" in rows
    for key, per_device in rows.items():
        assert len(per_device) == 4 and len(set(per_device)) == 1, key
    assert rec["legs"]["placement"]["collectives"]["all-reduce"] >= 1


# ------------------------------------------------------- compile-cache policy


class _ConfigSpy:
    """Stands in for jax.config.update: records, applies nothing (the suite's
    own cache directory must not move under the other tests)."""

    def __init__(self):
        self.calls = []

    def __call__(self, name, value):
        self.calls.append((name, value))


@pytest.fixture()
def config_spy(monkeypatch):
    import jax

    spy = _ConfigSpy()
    monkeypatch.setattr(jax.config, "update", spy)
    return spy


def test_cache_variable_set_means_no_directory_is_set_in_code(monkeypatch, config_spy):
    from photon_ml_tpu.cli.runtime import configure_compilation_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    assert configure_compilation_cache() == "/x"
    assert [c for c in config_spy.calls if c[0] == "jax_compilation_cache_dir"] == []


def test_cache_variable_unset_means_the_checkout_directory(monkeypatch, config_spy):
    from photon_ml_tpu.cli.runtime import configure_compilation_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    expected = os.path.join(REPO, ".jax_cache")
    assert configure_compilation_cache() == expected
    assert ("jax_compilation_cache_dir", expected) in config_spy.calls
    # fixed: not the home directory, a temp name, a pid or a time
    assert configure_compilation_cache() == expected
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def _python_sources():
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [
            d for d in dirs
            if not d.startswith(".") and d not in ("__pycache__", "chiprun_out")
        ]
        for name in files:
            if name.endswith(".py"):
                yield os.path.join(root, name)


def test_exactly_one_cache_directory_update_outside_tests_and_tools():
    hits = []
    for path in _python_sources():
        rel = os.path.relpath(path, REPO)
        if rel.split(os.sep)[0] in ("tests", "tools"):
            continue
        with open(path) as f:
            for lineno, line in enumerate(f, 1):
                if "jax_compilation_cache_dir" in line and "update(" in line:
                    hits.append(f"{rel}:{lineno}")
    assert len(hits) == 1 and hits[0].startswith(
        os.path.join("photon_ml_tpu", "cli", "runtime.py")
    ), hits


def test_the_four_drivers_take_no_cache_flag_and_share_the_policy():
    from photon_ml_tpu.cli import (
        game_scoring_driver,
        game_training_driver,
        serving_driver,
        sweep_driver,
    )

    for driver in (
        game_training_driver, game_scoring_driver, serving_driver, sweep_driver
    ):
        flags = {
            s for a in driver.build_arg_parser()._actions for s in a.option_strings
        }
        assert "--compilation-cache-directory" not in flags, driver.__name__
        with open(driver.__file__) as f:
            assert "configure_compilation_cache()" in f.read(), driver.__name__
    assert "PHOTON_XLA_CACHE" not in os.environ
