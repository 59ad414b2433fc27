"""chip_smoke.py's logic on the CPU: its reference counter and its
service phase at a tiny size (the script itself refuses the CPU)."""

import importlib.util
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import DDSL
from repro.core.pattern import PATTERN_LIBRARY
from repro.data.graphs import rmat_graph

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("log2,draws,seed", [(6, 300, 0), (7, 900, 1), (8, 2500, 2)])
def test_reference_counts_match_host_engine(log2, draws, seed):
    g = rmat_graph(log2, draws, seed=seed)
    got = _smoke().reference_counts(g.n, g.edges())
    for name in ("q2_triangle", "q4_clique4"):
        d = DDSL(g, PATTERN_LIBRARY[name], m=2)
        d.initial()
        assert got[name] == d.count(), name


def test_service_phase_runs_on_cpu_at_tiny_size():
    out = _smoke().service_phase(log2=7, draws=900, batches=2, batch_ops=8)
    assert out["compiles"] > 0


def test_smoke_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "platform=cpu" in r.stdout
