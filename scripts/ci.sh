#!/usr/bin/env bash
# Fast CI tier: lint-free imports + the quick test tier (slow-marked tests —
# the multi-minute JAX compiles — are excluded by pytest.ini's addopts).
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== import lint =="
python - <<'EOF'
import importlib

MODULES = [
    "repro",
    "repro.core", "repro.core.engine", "repro.core.magic", "repro.core.parser",
    "repro.core.planner", "repro.core.ir", "repro.core.stratify",
    "repro.core.prem", "repro.core.relation", "repro.core.seminaive",
    "repro.core.semiring", "repro.core.distributed", "repro.core.sparse",
    "repro.service", "repro.service.session", "repro.service.batch",
    "repro.service.incremental", "repro.service.cache", "repro.service.serve",
    "repro.service.admission", "repro.service.durable",
    "repro.checkpoint", "repro.checkpoint.store",
    "repro.obs", "repro.obs.trace", "repro.obs.metrics",
    "repro.obs.roofline_attr",
    "repro.kernels", "repro.kernels.autotune", "repro.data.graphs",
]
for m in MODULES:
    importlib.import_module(m)
print(f"{len(MODULES)} modules import clean")
EOF

echo "== fast test tier =="
# the differential sweep runs once, below, under its pinned profile
python -m pytest -q --ignore=tests/test_differential.py

echo "== differential suite (pinned profile) =="
# Deterministic sweep: DIFF_SEED pins the generator, DIFF_CASES sizes it
# (CI smoke size here; DIFF_CASES=200 is the acceptance-sized local run).
# The hypothesis twin runs seed-pinned + deadline-free when hypothesis is
# installed; without it the @given tests self-skip via tests/_hypothesis_stub.
HYPOTHESIS_FLAGS=""
if python -c "import hypothesis" 2>/dev/null; then
    HYPOTHESIS_FLAGS="--hypothesis-seed=0"
fi
DIFF_SEED=0 DIFF_CASES="${DIFF_CASES:-16}" \
    python -m pytest -q tests/test_differential.py ${HYPOTHESIS_FLAGS}

echo "== kernel tuning smoke bench (tuned >= untuned steady qps + JSON parses) =="
python benchmarks/bench_kernels.py --smoke --out /tmp/BENCH_kernels.json
python - <<'EOF'
import json

rec = json.load(open("/tmp/BENCH_kernels.json"))
assert rec["tuned"]["steady_qps"] >= rec["untuned"]["steady_qps"], rec
assert rec["tuned"]["waste"] <= rec["untuned"]["waste"], rec
print(f"tuned/untuned = {rec['tuned_over_untuned']:.2f}x "
      f"(waste {rec['untuned']['waste']:.1f}x -> {rec['tuned']['waste']:.2f}x)")
EOF

echo "== serving smoke bench (incl. tuple-batch + trace-count assert) =="
python benchmarks/bench_serve.py --smoke

echo "== sparse serving smoke bench (CSR >= dense qps + warm-shape trace assert) =="
python benchmarks/bench_serve.py --smoke --sparse

echo "== counting smoke bench (fast path >= tuple-engine qps, exact int counts) =="
python benchmarks/bench_serve.py --smoke --counting

echo "== async admission smoke bench (>= 1.5x sync qps + warm-flush trace assert) =="
python benchmarks/bench_serve.py --smoke --async

echo "== fault-injection recovery suite (durable layer) =="
python -m pytest -q tests/test_durable.py

echo "== durable restart smoke bench (warm restart beats cold rebuild; torn-write recovery exact) =="
python benchmarks/bench_serve.py --smoke --durable

echo "== observability smoke bench (metrics-on >= 0.95x metrics-off + exports parse) =="
python benchmarks/bench_serve.py --smoke --obs \
    --trace-out /tmp/trace.json --metrics-out /tmp/metrics.prom
python - <<'EOF'
import json

doc = json.load(open("/tmp/trace.json"))
evs = doc["traceEvents"]
assert evs, "exported Chrome trace is empty"
for e in evs:
    assert e["ph"] in ("X", "i") and all(
        k in e for k in ("name", "cat", "ts", "pid", "tid")), e
    assert e["ph"] != "X" or "dur" in e, e

text = open("/tmp/metrics.prom").read()
assert text.strip(), "exported Prometheus text is empty"
families = 0
for line in text.splitlines():
    if line.startswith("# TYPE "):
        kind = line.split()[-1]
        assert kind in ("counter", "gauge", "histogram"), line
        families += 1
    elif line and not line.startswith("#"):
        float(line.rsplit(" ", 1)[1])  # every sample line parses
assert families >= 5, f"only {families} metric families exported"
print(f"trace: {len(evs)} events ok; metrics: {families} families ok")
EOF
