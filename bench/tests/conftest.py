"""Shared set-up of the benchmark's own tests (run by hand:
``python -m pytest bench/tests``).  They run on the CPU; the harness's
look for a chip is skipped where a test drives a whole run."""

import json
import os
import shutil
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"

import pytest  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(REPO / "src"))

# Small stand-ins of the two configurations, added as data only.  Their
# j_ratio limits sit between what sound CPU runs at these sizes read
# (torus 0.399-0.426; tree 0.130-0.156 per graph, 0.144 over ten) and
# what runs whose refinement returns its input read (torus 0.558-0.563;
# tree 0.153-0.177 per graph, 0.165 over ten).
SMALL = {
    "torus-8x8x8": ("torus-16x16x16", {
        "machine": {"kind": "torus", "dims": [8, 8, 8],
                    "weights": [1.0, 1.0, 1.0]},
        "graph": {"family": "stencil3d", "dims": [8, 8, 8],
                  "weights": [1, 9]},
        "limits": {"missing": 0, "perm_invalid": 0, "j_rel_err": 1e-05,
                   "j_ratio": 0.49}}),
    "tree-4-16-16": ("tree-4-16-64", {
        "machine": {"kind": "tree", "factors": [4, 16, 16],
                    "distances": [1.0, 10.0, 100.0]},
        "graph": {"family": "rgg", "n": 1024, "radius_factor": 0.55,
                  "weights": [1, 9]},
        "limits": {"missing": 0, "perm_invalid": 0, "j_rel_err": 1e-05,
                   "j_ratio": 0.155}}),
}


def add_config(root: Path, name: str, base: str, changes: dict) -> None:
    cfg = json.loads((BENCH / "configs" / f"{base}.json").read_text())
    cfg.update(name=name, **changes)
    (root / "bench" / "configs" / f"{name}.json").write_text(
        json.dumps(cfg))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": name, "source": "test",
                             "file": f"bench/configs/{name}.json",
                             "reduced": [], "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def add_cell(root: Path, config: str, traffic: str) -> str:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    name = f"{config}.{traffic}"
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": traffic, "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return name


@pytest.fixture(scope="session")
def small_root(tmp_path_factory) -> Path:
    """A checkout-like copy: ``BENCHMARK.json``, ``bench/`` and the
    program, plus small configurations and an eight-request traffic mix
    added as new files and entries only."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    (root / "src").symlink_to(REPO / "src")
    few = json.loads((BENCH / "traffic" / "single.json").read_text())
    few.update(name="few", pool=8)
    (root / "bench" / "traffic" / "few.json").write_text(json.dumps(few))
    for name, (base, changes) in SMALL.items():
        add_config(root, name, base, changes)
        add_cell(root, name, "few")
    return root
