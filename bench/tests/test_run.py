"""Whole runs of the harness on the CPU, at small sizes: a sound run is
correct, each fault planted under the timed path makes ``correct``
false, the control fails the check, and off the chip the command
refuses to run."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

import control
import harness
from conftest import BENCH, REPO

CELLS = ["torus-8x8x8.few", "tree-4-16-16.few"]
SECONDS = 3.0       # about eight placements of either small cell


@pytest.fixture
def restore_program():
    """Undo the faults' patches of the program after a test."""
    harness.program(REPO)
    from repro.core import plan
    from repro.engine import sweep
    saved = (sweep.RefinementEngine.refine, plan.MappingPlan.execute)
    yield
    sweep.RefinementEngine.refine, plan.MappingPlan.execute = saved


def run_cell(root, cell, seed, trace=False, faults=()):
    run = harness.Run(root, cell, seed, SECONDS, trace, time.perf_counter(),
                      require_chip=False, faults=faults)
    return run.execute()


def switched_on(make):
    """A fault callable that installs the patch and turns it on."""
    return lambda: make()(True)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(small_root, cell, restore_program):
    out = run_cell(small_root, cell, 2**40 + 17)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"placement_s", "j_ratio", "setup_s"}
    assert 0 < out["metrics"]["j_ratio"]["value"] < 1


@pytest.mark.parametrize("cell", CELLS)
def test_refinement_returning_its_input_is_not_correct(small_root, cell,
                                                       restore_program):
    out = run_cell(small_root, cell, 2**40 + 19,
                   faults=[switched_on(control.unchanged_refine)])
    checks = out["checks"]
    assert not out["correct"], checks
    assert checks["j_ratio"]["value"] > checks["j_ratio"]["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_altered_answer_is_not_correct(small_root, cell, restore_program):
    out = run_cell(small_root, cell, 2**40 + 23,
                   faults=[switched_on(control.altered_answer)])
    assert not out["correct"]
    assert out["checks"]["j_rel_err"]["value"] > \
        out["checks"]["j_rel_err"]["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_bfloat16_control_fails_the_check(small_root, cell,
                                          restore_program):
    res = control.control(small_root, cell, [2**40 + 29, 2**40 + 31], SECONDS,
                          fault_seeds=1, require_chip=False)
    seeds = res["seeds"].values()
    limit = json.loads((small_root / "bench" / "configs"
                        / f"{cell.split('.')[0]}.json").read_text()
                       )["limits"]["j_rel_err"]
    assert all(s["program"]["j_rel_err"] <= limit for s in seeds)
    assert all(s["control"]["j_rel_err"] > limit for s in seeds)
    first = next(iter(seeds))
    assert first["altered"]["j_rel_err"] > limit


def test_new_files_are_found_by_name(small_root, tmp_path):
    """A configuration, a traffic mix and a per-layer metric dropped into
    a copy as new files, with entries in BENCHMARK.json only."""
    from conftest import add_cell, add_config
    root = tmp_path / "checkout"
    shutil.copytree(small_root, root, symlinks=True)
    add_config(root, "torus-4x4x8", "torus-16x16x16", {
        "machine": {"kind": "torus", "dims": [4, 4, 8],
                    "weights": [1.0, 1.0, 1.0]},
        "graph": {"family": "stencil3d", "dims": [4, 4, 8],
                  "weights": [1, 9]},
        "limits": {"missing": 0, "perm_invalid": 0, "j_rel_err": 1e-05,
                   "j_ratio": 0.7}})
    pair = json.loads((root / "bench/traffic/few.json").read_text())
    pair.update(name="pairs", burst=2, pool=4)
    (root / "bench/traffic/pairs.json").write_text(json.dumps(pair))
    cell = add_cell(root, "torus-4x4x8", "pairs")
    (root / "bench/metrics/placements_done.py").write_text(
        "def read(ctx):\n    return len(ctx['placements'])\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "placements_done", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "Service",
        "moves": "placement_s", "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    out = run_cell(root, cell, 2**40 + 37, trace=True)
    assert out["correct"], out["checks"]
    assert out["metrics"]["placements_done"]["value"] == out["attempted"]
    assert out["attempted"] % 2 == 0
    assert "engine.sweeps" in out["metrics"]


def test_every_file_is_found_by_name():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for entry in bench["configs"]:
        cfg = json.loads((REPO / entry["file"]).read_text())
        assert cfg["name"] == entry["name"]
        assert set(entry["reduced"]) <= set(cfg)
    configs = {p.stem for p in (BENCH / "configs").glob("*.json")}
    assert configs == {e["name"] for e in bench["configs"]}
    traffic = {p.stem for p in (BENCH / "traffic").glob("*.json")}
    assert traffic == {c["traffic"] for c in bench["workloads"]}
    metrics = {p.stem for p in (BENCH / "metrics").glob("*.py")
               if not p.stem.startswith("_")}
    assert metrics == {m["name"] for m in bench["per_layer"]}
    for m in bench["per_layer"]:
        assert callable(harness.load_metric(REPO, m["name"]))


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "torus-16x16x16.single", "--seed", str(2**40 + 41), "--seconds",
         "1", "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_command_refuses_to_run_off_the_chip():
    proc = _command(REPO)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(line.startswith("{") for line in
                   proc.stdout.splitlines())


def test_command_refuses_without_the_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    proc = _command(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


@pytest.mark.parametrize("cell", CELLS)
def test_rewired_warmup_leaves_nothing_to_compile(small_root, tmp_path, cell,
                                                  restore_program):
    """With no warm-up draws at all, every shape of the pool is warmed by
    a rewired graph, and the window compiles nothing."""
    root = tmp_path / "checkout"
    shutil.copytree(small_root, root, symlinks=True)
    few = json.loads((root / "bench/traffic/few.json").read_text())
    few["warmup_draws"] = 0
    (root / "bench/traffic/few.json").write_text(json.dumps(few))
    out = run_cell(root, cell, 2**40 + 43, trace=True)
    assert out["correct"], out["checks"]
    assert out["metrics"]["jit.compiles"]["value"] == 0
