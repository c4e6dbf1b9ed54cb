"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and its
serving path and scipy reference agree on a small graph (the same code the
chip runs at 2^20 vertices)."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_refuses_cpu(smoke, capsys):
    """No fallback: on the CPU the smoke exits non-zero, names the platform
    it found and prints no result line."""
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "'cpu'" in err
    assert '"ok"' not in out


def test_smoke_reference_semantics(smoke):
    """The scipy reference keeps the program's semantics: a source is in
    its own answer only on a cycle, and parallel arcs keep the lightest
    weight."""
    arcs = np.array([[0, 1], [1, 2], [2, 0], [3, 4]], np.int64)
    warcs = np.array([[0, 1, 5], [0, 1, 2], [1, 2, 1], [2, 0, 4], [3, 4, 7]],
                     np.int64)
    ref = smoke.Reference(5, arcs, warcs)
    assert ref.reach(0).tolist() == [0, 1, 2]
    assert ref.reach(3).tolist() == [4]
    dst, dist = ref.dpath(0)
    assert dst.tolist() == [0, 1, 2] and dist.tolist() == [7, 2, 3]
    dst, dist = ref.dpath(3)
    assert dst.tolist() == [4] and dist.tolist() == [7]


def test_smoke_serves_small_graph_on_cpu(smoke, capsys):
    """The whole one-chip smoke at 2^12 vertices: max_batch probe, two
    flushes per carrier, the append, and every sampled answer equal to
    scipy's before and after it."""
    rep = smoke.serve_and_check(n=1 << 12, m=1 << 15, seed=0,
                                free_bytes=1 << 40)
    assert rep["checked"] == 16
    assert rep["max_batch"] == smoke.BATCH_LADDER[-1]
    out = capsys.readouterr().out
    probe = [json.loads(ln.split("max_batch probe: ", 1)[1])
             for ln in out.splitlines() if "max_batch probe: " in ln]
    assert [p["batch"] for p in probe] == list(smoke.BATCH_LADDER)
    assert all(p["fits"] for p in probe)
