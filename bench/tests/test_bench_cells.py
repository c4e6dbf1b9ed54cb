"""CPU rehearsals of each cell's path through the harness's functions, at a
tiny scale: the result line, the check against the reference, the faults
that check has to catch, the control, and a cell added by files alone.

The harness's look for a chip lives in ``run.py``; these tests call
``harness.run_cell`` directly with the CPU device.
"""
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from bench.lib import harness, spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
CELLS = ["reach-g500-uniform", "sssp-g500-uniform"]
E2E = {"answers_per_s", "latency_p50_s", "latency_p95_s", "setup_s"}
SEED = 2**31 + 17
SECONDS = 1.5


def tiny(workload: str, root: str = ROOT) -> spec.Cell:
    """The cell at 2^9 vertices, pads (2, 8), 30 queries in the window and
    every answer of the window checked."""
    cell = spec.load(root, workload)
    cell.config.update(scale=9, bits=17, batch_pads=[2, 8], max_batch=8,
                       check_sample=1000)
    cell.mix.update(preroll_s=0.3, drain_s=30.0)
    cell.mix["arrivals"]["rate_qps"] = 20.0
    return cell


def run(cell, trace=False, root=ROOT, seed=SEED):
    return harness.run_cell(root, cell.name, seed, SECONDS, trace,
                            time.perf_counter(), jax.devices()[:1], cell=cell)


@pytest.mark.parametrize("workload", CELLS)
def test_cell_rehearsal(workload):
    res = run(tiny(workload))
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True
    assert res["attempted"] == round(20.0 * SECONDS) and res["failed"] == 0
    want = E2E - ({"latency_p95_s"} if workload.startswith("sssp") else set())
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["window"]["compiles_in_window"] == 0
    assert res["window"]["fixpoint_retraces"] == 0
    assert res["checks"]["wrong_answers"] == {"value": 0, "limit": 0}
    json.dumps(res)


def test_warm_up_covers_every_pad():
    """Flushes that fill the widest pad compile nothing in the window: the
    warm-up ran every pad, full and one short of full."""
    cell = tiny("reach-g500-uniform")
    cell.config.update(scale=12, batch_pads=[8, 32, 64, 128],
                       max_batch=128, check_sample=4)
    cell.mix["arrivals"]["rate_qps"] = 2500.0
    res = run(cell)
    assert res["correct"] is True
    assert res["window"]["flushed_queries"] / res["window"]["flushes"] > 64
    assert res["window"]["compiles_in_window"] == 0
    assert res["window"]["compiled_in_window"] == []
    assert res["window"]["fixpoint_retraces"] == 0


def test_traced_rehearsal():
    res = run(tiny("reach-g500-uniform"), trace=True)
    assert res["correct"] is True
    # the CPU has no device plane: the device's metrics are left out
    assert set(res["metrics"]) == {"admission.batch_fill",
                                   "session.finalize_ms"}
    assert 0 < res["metrics"]["admission.batch_fill"]["value"] <= 1
    assert res["device"]["window_s"] > SECONDS
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def one_hop(csr, init, spmv=None, max_iters=None):
    """A fixpoint whose step returns its state unchanged."""
    from repro.core.seminaive import DenseResult
    return DenseResult(init, jnp.int32(0), jnp.int64(0))


def half_batch(run_batch):
    """Rows past the first half of every flush left out."""
    def run_half(csr, srcs, *a, **kw):
        res = run_batch(csr, srcs, *a, **kw)
        keep = jnp.arange(res.table.shape[0]) < -(-len(srcs) // 2)
        zero = jnp.asarray(csr.semiring.zero, res.table.dtype)
        return res._replace(table=jnp.where(keep[:, None], res.table, zero)) \
            if hasattr(res, "_replace") else type(res)(
                jnp.where(keep[:, None], res.table, zero), res.iterations,
                res.generated)
    return run_half


def drop_last(fmt):
    """Every answer formatted with its last vertex left out."""
    def altered(src, row, n):
        out = fmt(src, row, n)
        return (out[0][:-1], out[1][:-1]) if isinstance(out, tuple) \
            else out[:-1]
    return altered


@pytest.mark.parametrize("workload", CELLS[:2])
@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch",
                                   "altered_answer"])
def test_faults_come_out_not_correct(workload, fault, monkeypatch):
    from repro.core import sparse
    from repro.service import batch

    if fault == "unchanged_state":
        monkeypatch.setattr(sparse, "fixpoint_csr_cached", one_hop)
    elif fault == "half_batch":
        monkeypatch.setattr(batch, "run_frontier_batch_csr",
                            half_batch(batch.run_frontier_batch_csr))
        cell = tiny(workload)
        cell.mix["arrivals"]["rate_qps"] = 60.0  # flushes of several rows
    else:
        for name in ("format_bool_row", "format_minplus_row"):
            monkeypatch.setattr(batch, name, drop_last(getattr(batch, name)))
    res = run(cell if fault == "half_batch" else tiny(workload))
    assert res["correct"] is False
    assert res["checks"]["wrong_answers"]["value"] > 0


@pytest.mark.parametrize("workload", CELLS[:2])
def test_control_comes_out_not_correct(workload):
    sys.path.insert(0, os.path.join(ROOT, "bench"))
    import control

    cell = tiny(workload)
    cell.config.update(scale=11, check_sample=8)
    got = control.control_run(cell, SEED, 5.0)
    assert got["checked"] == 8 and got["wrong_answers"] > 0
    sound = control.control_run(cell, SEED, 5.0, exact=True)
    assert sound["checked"] == 8 and sound["wrong_answers"] == 0


def test_new_cell_from_files_alone(tmp_path):
    """A configuration, a mix, a metric and a cell added as new files and
    entries, in a copy of the layout, run with no file edited."""
    bench = tmp_path / "bench"
    shutil.copytree(os.path.join(ROOT, "bench"), bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {os.path.join(dp, p): open(os.path.join(dp, p), "rb").read()
              for dp, _, fs in os.walk(bench) for p in fs}
    with open(os.path.join(ROOT, "bench/configs/g500-reach.json")) as f:
        cfg = json.load(f)
    cfg.update(name="g500-reach-tiny", scale=9, bits=17, batch_pads=[2, 8],
               max_batch=8)
    (bench / "configs" / "g500-reach-tiny.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "burst.json").write_text(json.dumps({
        "arrivals": {"process": "poisson", "rate_qps": 40.0, "gap_seed": 9},
        "sources": {"dist": "zipf", "a": 2.0}, "preroll_s": 0.3,
        "drain_s": 30.0, "appends": []}))
    (bench / "metrics" / "admission.flushes.py").write_text(
        "def read(rec):\n    return rec['counters']['flushes'] or None\n")
    b_hit = {"name": "cache.hit_share", "unit": "share", "better": "higher",
             "source": "program_counter", "layer": "result cache",
             "moves": "latency_p95_s", "workloads": ["tiny.burst"]}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"].append({"name": "g500-reach-tiny", "source": "test",
                         "file": "bench/configs/g500-reach-tiny.json",
                         "reduced": ["scale"], "why": "test"})
    b["workloads"].append({"name": "tiny.burst", "config": "g500-reach-tiny",
                           "traffic": "burst", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "admission.flushes", "unit": "flushes",
                           "better": "lower", "source": "program_counter",
                           "layer": "admission (service/admission.py)",
                           "moves": "latency_p95_s",
                           "workloads": ["tiny.burst"]})
    b["per_layer"].append(b_hit)  # a reader already there, a new cell
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    cell = spec.load(str(tmp_path), "tiny.burst")
    res = run(cell, trace=True, root=str(tmp_path))
    assert res["correct"] is True
    assert res["metrics"]["admission.flushes"]["value"] > 0
    assert res["attempted"] == round(40.0 * SECONDS)
    assert set(res["metrics"]) == {"admission.flushes", "cache.hit_share"}
    assert 0 < res["metrics"]["cache.hit_share"]["value"] <= 1
    after = {os.path.join(dp, p): open(os.path.join(dp, p), "rb").read()
             for dp, _, fs in os.walk(bench) for p in fs}
    assert {p: after[p] for p in before} == before  # no file edited


def test_run_needs_a_tpu(tmp_path):
    """On anything but a TPU, and in a directory that holds only the
    benchmark's files, run.py exits non-zero and prints no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    for where in (ROOT, str(tmp_path)):
        p = subprocess.run(
            [sys.executable, "bench/run.py", "--workload",
             "reach-g500-uniform", "--seed", str(SEED), "--seconds", "1",
             "--trace", "0"], cwd=where, env=env, capture_output=True,
            text=True, timeout=300)
        assert p.returncode != 0 and p.stdout == "", p.stderr
