"""The entry refuses to report where it must not, and BENCHMARK.json names
only files that exist."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import run  # noqa: E402
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def run_entry(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "crs-resident",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_means_no_result():
    p = run_entry(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_mean_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_entry(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_every_name_finds_its_files():
    s = spec()
    assert s["command"] == ["python3", "bench/run.py"] and s["paths"] == ["bench"]
    for c in s["configs"]:
        assert NAME.match(c["name"]) and os.path.isfile(os.path.join(ROOT, c["file"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and set(c["reduced"]) <= set(cfg)
    used = {w["config"] for w in s["workloads"]}
    assert used == {c["name"] for c in s["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in s["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in s["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] == 1 and len(w["why"]) <= 200
        with open(os.path.join(BENCH, "workloads", w["name"] + ".json")) as f:
            cell = json.load(f)
        assert os.path.isfile(os.path.join(BENCH, "drivers", cell["driver"] + ".py"))
        assert os.path.isfile(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    names = [m["name"] for m in s["end_to_end"] + s["per_layer"]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    cells = {w["name"] for w in s["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells)) for m in s["end_to_end"]}
    for cell in cells:  # set-up, another end-to-end metric, a per-layer one
        assert cell in e2e["setup_s"]
        assert sum(cell in ws for ws in e2e.values()) >= 2
        assert any(cell in m["workloads"] for m in s["per_layer"])
    for m in s["per_layer"]:
        assert set(m["workloads"]) <= e2e[m["moves"]]  # its cells report it
        assert os.path.isfile(os.path.join(BENCH, "metrics", m["name"] + ".py"))
    assert set(e2e) == {"case_steps_per_s", "setup_s"}


@pytest.mark.parametrize("cell", ["ebe-k2-resident", "crs-hoststream", "crs-resident"])
def test_cell_limits_cover_every_number(cell):
    sys.path.insert(0, BENCH)
    from harness import checks

    with open(os.path.join(BENCH, "workloads", cell + ".json")) as f:
        limits = json.load(f)["limits"]
    assert set(limits) == set(checks.ORDER)
    assert limits["unhealthy"] == 0 and limits["misplaced"] == 0
