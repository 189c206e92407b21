"""Launcher contracts: a failed sweep group fails the launch, the worker-pool
parent never puts a second process on an accelerator, and the persistent
compilation cache lands where the environment (or the checkout) says."""
import json
import os

import jax
import numpy as np
import pytest

from repro import scenario as sc
from repro.launch import bootstrap
from repro.launch import campaign as campaign_cli

_SWEEP = json.dumps({
    "base": {"n_cases": 1, "nt": 4, "mesh_n": [2, 2, 2]},
    "axes": {"soil.vs": [[0.8, 1.0], [1.0, 1.0]]},  # two compile groups
})


def test_sweep_with_a_failed_group_exits_nonzero(tmp_path, monkeypatch, capsys):
    import repro.scenario.planner as planner

    bad = sc.make_plan(sc.sweep_from_json(_SWEEP)).groups[0].key

    def runner(group, **kw):
        if group.key == bad:
            raise RuntimeError("mesh went singular")
        s = group.scenarios[0]
        sr = planner.ScenarioResult(
            scenario=s, waves=np.zeros((1, 4, 3), np.float32),
            responses=np.zeros((1, 4, 1, 3), np.float32))
        return {s.name: sr}, {"completed": True, "wall_s": 0.01,
                              "cases_per_s": 1.0, "mean_iters": 1.0}

    monkeypatch.setattr(planner, "run_group", runner)
    rc = campaign_cli.main(["--sweep", _SWEEP, "--ckpt-dir", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "[failed] group" in out and "mesh went singular" in out
    assert "relaunch to resume" not in out


def test_pool_refuses_a_second_accelerator_process(tmp_path, monkeypatch):
    """When the backend probe finds an accelerator, the spawned workers would
    each open it: the parent refuses before it spawns (or touches JAX)."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(campaign_cli, "_child_platform", lambda args: "tpu")
    base = ["--sweep", _SWEEP, "--schedule", "--out", str(tmp_path / "o")]
    with pytest.raises(SystemExit, match="second process"):
        campaign_cli.main(base + ["--workers", "2"])
    with pytest.raises(SystemExit, match="parent would open the chip"):
        campaign_cli.main(base + ["--workers", "1", "--train-while-generating"])
    assert not os.path.exists(tmp_path / "o")  # nothing was spawned


def test_cpu_pool_runs_without_jax_platforms(tmp_path, monkeypatch, capsys):
    """A host with only a CPU needs no JAX_PLATFORMS: the probe child reports
    ``cpu`` and the pool runs two workers and the concurrent trainer."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    # the probe child sees only a CPU, whatever this machine has installed
    monkeypatch.setattr(campaign_cli, "_PROBE",
                        "import jax; jax.config.update('jax_platforms', 'cpu');"
                        " print(jax.default_backend())")
    out = tmp_path / "o"
    rc = campaign_cli.main(["--sweep", _SWEEP, "--schedule", "--workers", "2",
                            "--train-while-generating", "--train-steps", "2",
                            "--out", str(out), "--shard-size", "1"])
    log = capsys.readouterr().out
    assert rc == 0, log
    assert "2 worker(s)" in log and "plan settled" in log and "val MAE" in log


@pytest.fixture
def restore_cache_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path,
                                               restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    assert bootstrap.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets no other directory
    assert jax.config.jax_compilation_cache_dir is None


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert bootstrap.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
