#!/usr/bin/env bash
# PR gate: tier-1 tests + a quick-mode Fig. 15 smoke so the edge-list/CSR
# crossover benchmark and the adaptive dispatcher run on every change.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== edgelist-vs-CSR smoke (quick mode) =="
python - <<'PY'
from benchmarks.bench_edgelist_vs_csr import run
run(quick=True)
PY

echo "== query sweeps: pushdown + chunk pipeline + GSQL parity (quick mode) =="
# writes the BENCH_queries.json snapshot: the pushdown sweep (chunks
# skipped, bytes decoded), the latency-scaled sequential-vs-pipelined sweep
# (wall times, speedup floor, overlap efficiency), and the GSQL-vs-builder
# parity sweep (both front ends bit-identical, parse+compile <= 5% of a
# cold execution).  All assert their results stay bit-identical to their
# baselines.
python - <<'PY'
from benchmarks.bench_queries import run
run(quick=True)
PY

echo "== epoch refresh: incremental advance vs full rebuild (quick mode) =="
# writes the BENCH_refresh.json snapshot: incremental epoch advance vs a
# full topology rebuild on a <=5% append, asserting the >=5x floor and
# bit-identical post-sync query results against a cold-started engine.
python - <<'PY'
from benchmarks.bench_refresh import run
run(quick=True)
PY

echo "== shared-scan serving: batched vs unbatched throughput (quick mode) =="
# writes the BENCH_serving.json snapshot: query_batch bit-parity vs solo
# runs, the shared-pass chunk-counter contract (same-parameter riders cost
# one solo run's chunks), and the closed-loop throughput floor — batched
# serving >= 2x unbatched at 16 concurrent clients on the same worker pool.
python - <<'PY'
from benchmarks.bench_serving import run
run(quick=True)
PY

echo "== point-lookup tier: fast path vs full engine (quick mode) =="
# writes the BENCH_lookup.json snapshot: bit-parity of the plan-cached
# lookup path against the full engine on green/yellow templates, then the
# warm-cache closed-loop p50 sweep asserting the >=10x speedup floor for
# green (point + single-hop) lookups.
python - <<'PY'
from benchmarks.bench_lookup import run
run(quick=True)
PY

echo "== chaos: success rate + p99 under seeded faults (quick mode) =="
# writes the BENCH_chaos.json snapshot: the seeded fault-rate sweep
# (transient + torn + spike on lake-table reads at 0/5/10%), asserting the
# 100% success floor, bit-parity of results against the fault-free run,
# and bounded p99 inflation.  The chaos test suite itself (fixed seeds)
# runs with the tier-1 tests below.
python - <<'PY'
from benchmarks.bench_chaos import run
run(quick=True)
PY

echo "== streaming ingestion: freshness SLO + oracle parity (quick mode) =="
# writes the BENCH_freshness.json snapshot: p50/p99 commit->queryable and
# ingest->queryable latency for a paced CDC stream under concurrent query
# load (bounded-p99 floor), row-for-row parity of the micro-batched lake
# against a batch-committed oracle (zero dropped/duplicated events), and
# the typed-backpressure-under-stall / heal-and-drain-exactly-once arc.
python - <<'PY'
from benchmarks.bench_freshness import run
run(quick=True)
PY

echo "== shard fabric: scatter-gather throughput + bit-parity (quick mode) =="
# writes the BENCH_shard.json snapshot: the 1/2/4-shard BI-suite sweep with
# cold caches under modeled lake latency, asserting every sharded result is
# bit-identical to the single engine (vset, accumulators, frames in global
# edge-id order) and the 4-shard fabric clears the >=1.5x suite-throughput
# floor.  The shard test suite itself runs with the tier-1 tests below.
python - <<'PY'
from benchmarks.bench_shard import run
run(quick=True)
PY

echo "== tier-1 tests (slow SPMD dry-runs deselected) =="
# CPU only: Pallas kernels run in interpret mode where a test asks for it
# (REPRO_PALLAS=interpret); tests/test_tpu_compile.py compiles them for a
# described v5e.  python chip_smoke.py is the run on the chip.
JAX_PLATFORMS=cpu python -m pytest -x -q -m "not slow"

echo "OK"
