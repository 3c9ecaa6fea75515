"""The device decisions (repro.runtime.device) and the chip smoke script's
behaviour off the chip: interpret only on CPU, compiled only on TPU,
an error anywhere else; the compile cache follows
``JAX_COMPILATION_CACHE_DIR`` or the fixed checkout path; and
``chip_smoke.py`` refuses the CPU while its phases pass here in
interpret mode at a small size."""

from __future__ import annotations

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.core import Hierarchy
from repro.kernels import derive_kernel_config
from repro.runtime.device import CHECKOUT_CACHE_DIR, pallas_interpret
from repro.topology import make_topology

ROOT = Path(__file__).resolve().parents[1]


def _env(**extra) -> dict:
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"), **extra)
    return env


@pytest.mark.parametrize("backend,interpret",
                         [("tpu", False), ("cpu", True)])
def test_pallas_interpret_per_backend(backend, interpret):
    assert pallas_interpret(backend) is interpret


@pytest.mark.parametrize("backend", ["gpu", "cuda", "rocm", "metal"])
def test_unknown_backend_raises_instead_of_interpreting(backend):
    with pytest.raises(RuntimeError, match="no Pallas path"):
        pallas_interpret(backend)
    with pytest.raises(ValueError, match="no kernel tile budget"):
        derive_kernel_config("tree", backend=backend)


def test_pallas_interpret_follows_default_backend():
    import jax
    assert pallas_interpret() is (jax.default_backend() == "cpu")


_CACHE_PROBE = ("import jax; from repro.runtime.device import "
                "enable_compile_cache as e; d = e(); "
                "print(d); print(jax.config.jax_compilation_cache_dir)")


def test_compile_cache_defaults_to_checkout_dir():
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=_env(),
                         capture_output=True, text=True, timeout=120,
                         check=True).stdout.split()
    assert out == [str(CHECKOUT_CACHE_DIR)] * 2
    assert CHECKOUT_CACHE_DIR == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_compile_cache_follows_env(tmp_path):
    where = str(tmp_path / "cache")
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE],
                         env=_env(JAX_COMPILATION_CACHE_DIR=where),
                         capture_output=True, text=True, timeout=120,
                         check=True).stdout.split()
    assert out == [where, where]


def test_chip_smoke_refuses_cpu(tmp_path):
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       env=_env(), cwd=tmp_path, capture_output=True,
                       text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "platform=cpu" in r.stdout


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("kind", ["tree", "torus"])
def test_chip_smoke_phases_pass_in_interpret_mode(chip_smoke, kind):
    cs = chip_smoke
    if kind == "tree":
        machine = Hierarchy.from_strings("4:4:32", "1:10:100")
        requests = [cs.COLD, cs.REPEAT]
    else:
        machine = make_topology("torus", dims=[8, 8, 8])
        requests = [cs.COLD, cs.WARM, cs.REPEAT, cs.STRONG]
    # on CPU the engines take the fused-jnp gain path and would only
    # interpret the kernels: (use_pallas, interpret) == (False, True)
    records = cs.run_machine(kind, machine, (8, 8, 8), requests,
                             want=(False, True))
    assert [r["request"] for r in records] == [q[0] for q in requests]
    assert all(r["rel_err"] <= cs.REL_TOL for r in records)
    assert any(r["J"] < r["J_constructed"] for r in records)
    assert records[0]["engine_traces"] == records[1]["engine_traces"]
    cold_spans = {name for name, _, _ in records[0]["spans"]}
    assert {"plan.lower", "vcycle.construct",
            "vcycle.refine"} <= cold_spans
