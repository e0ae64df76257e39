# Developer entry points.  The tier-1 gate is `make test-fast` (the pytest
# default: everything not marked `slow`, kept under ~3 minutes including the
# differential conformance matrix); `make test` adds the paper-size sweeps
# and the exhaustive (program, capacity, machine) grids; `make docs-check`
# executes the README quickstart block and examples/quickstart.py.

PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))
PYTEST = PYTHONPATH=$(PYTHONPATH) python -m pytest

# Fast-tier wall-clock budget (seconds).  The suite must stay within it so
# a growing program population (the trace-from-model bridge multiplies
# registered kernels) cannot silently inflate tier-1; `timeout` fails the
# target loudly instead.  Sized from the measured full fast-tier wall on
# CI-class hardware with headroom for cold JIT compiles.
TEST_BUDGET_SECS ?= 900

.PHONY: test-fast test bench bench-smoke serve-smoke roofline-smoke \
	network-smoke cluster-smoke dse-smoke docs-check

test-fast:
	timeout $(TEST_BUDGET_SECS) $(PYTEST) -x -q

test:
	$(PYTEST) -x -q -m ""

# The full report.  By default the Pallas suites (roofline,
# vmem_dispersion) run in the Pallas interpreter so that the report can be
# regenerated on the CPU backend; their rows are then interpreter timings
# (the roofline payload records `"interpret": true`).  On a TPU, run
# `make bench BENCH_FLAGS=` to compile and time the kernels on the chip.
BENCH_FLAGS ?= --interpret

bench:
	PYTHONPATH=$(PYTHONPATH) python -m benchmarks.run --json BENCH_core.json \
	  $(BENCH_FLAGS)

# Schema guard: the full front door (suites, --kernels subsetting, schema-5
# JSON with metric metadata) on a 2-kernel subset in a couple of minutes.
bench-smoke: serve-smoke roofline-smoke network-smoke cluster-smoke dse-smoke
	PYTHONPATH=$(PYTHONPATH) python -m benchmarks.run \
	  --json BENCH_smoke.json --kernels dropout,gemv \
	  fig2 table3 fig6 fig8 pareto

# Cluster regression guard: the multi-core dispersion suite on a reduced
# grid.  Asserts rows present, cluster-engine compiles bounded by the
# (bucket x L1 geometry x cores) plan groups, and an N=1 row identical to
# a fresh single-core Session.run at the same point (the passthrough pin,
# exercised through the whole benchmark + JSON path).
cluster-smoke:
	PYTHONPATH=$(PYTHONPATH) python -m benchmarks.run \
	  --json BENCH_cluster_smoke.json --kernels dropout \
	  --max-events 4000 cluster_sweep
	PYTHONPATH=$(PYTHONPATH) python -c "import json; \
	  from repro import api; \
	  r = json.load(open('BENCH_cluster_smoke.json'))['suites']['cluster_sweep']; \
	  x = r['extra']; \
	  assert r['rows'] > 0, r; \
	  assert x['compiles'] <= x['plan_groups'], x; \
	  row = [t for t in x['rows'] if t['cores'] == 1][0]; \
	  res = api.Session().run(api.Sweep(kernels=[row['name']], \
	    capacity=[row['capacity']], \
	    l1_geometry=[api.L1Geometry.from_kbytes(row['l1_kb'])], \
	    max_events=4000)); \
	  assert int(res.value('cycles', capacity=row['capacity'])) \
	    == row['cycles'], row; \
	  print('cluster smoke OK:', r['rows'], 'rows,', x['compiles'], \
	        'compiles /', x['plan_groups'], 'plan groups, N=1 identity holds')"

# Silicon DSE regression guard: the 3-objective macro-model driver on a
# reduced grid.  The schema-7 JSON must carry a non-empty front per macro
# model with the arXiv:2410.08396 external baseline labeled on it, and
# cluster-engine compiles bounded by the (bucket x geometry x cores) plan
# groups.
dse-smoke:
	PYTHONPATH=$(PYTHONPATH) python -m benchmarks.run \
	  --json BENCH_dse_smoke.json --kernels dropout \
	  --max-events 4000 dse
	PYTHONPATH=$(PYTHONPATH) python -c "import json; \
	  rep = json.load(open('BENCH_dse_smoke.json')); \
	  assert rep['schema'] == 7, rep['schema']; \
	  assert {'flop', 'sram6t', 'table'} <= set(rep['macro_models']); \
	  r = rep['suites']['dse']; x = r['extra']; \
	  assert r['rows'] > 0, r; \
	  assert x['compiles'] <= x['plan_groups'], x; \
	  fronts = [x['fronts'][m]['dropout'] for m in ('flop', 'sram6t', 'table')]; \
	  assert all(fronts), [len(f) for f in fronts]; \
	  assert all(any(p.get('external') and p['source'] == 'arXiv:2410.08396' \
	    for p in f) for f in fronts), 'external baseline missing'; \
	  print('dse smoke OK:', r['rows'], 'rows,', \
	        [len(f) for f in fronts], 'front points,', x['compiles'], \
	        'compiles /', x['plan_groups'], 'plan groups')"

# Network-bridge regression guard: whole registry models lowered through
# repro.bridge on the truncation grid.  The JSON must record >0 rows, the
# lowered-network summaries, and a compile count no larger than the number
# of (shape bucket x L1 geometry) plan groups.
network-smoke:
	PYTHONPATH=$(PYTHONPATH) python -m benchmarks.run \
	  --json BENCH_network_smoke.json --max-events 120 network_sweep
	PYTHONPATH=$(PYTHONPATH) python -c "import json; \
	  r = json.load(open('BENCH_network_smoke.json'))['suites']['network_sweep']; \
	  x = r['extra']; \
	  assert r['rows'] > 0 and x['networks'], r; \
	  assert x['compiles'] <= x['plan_groups'], x; \
	  print('network smoke OK:', r['rows'], 'rows,', len(x['networks']), \
	        'models,', x['compiles'], 'compiles /', x['plan_groups'], \
	        'plan groups')"

# Serving-side schema guard: kv_dispersion + the serving SLO suite on the
# smoke grid (2 hot-pool sizes, tiny scenario) under a tight event budget.
serve-smoke:
	PYTHONPATH=$(PYTHONPATH) python -m benchmarks.run \
	  --json BENCH_serve_smoke.json --max-events 120 \
	  kv_dispersion serving_slo

# Roofline regression guard: the measured Pallas suite on the smoke grid
# must record >0 rows and >0 dispatches with the per-point measured/model
# payload present — the suite can never silently regress to 0 rows again.
# It runs the kernels in the Pallas interpreter (--interpret) so that it
# also works on the CPU backend.
roofline-smoke:
	PYTHONPATH=$(PYTHONPATH) python -m benchmarks.run \
	  --json BENCH_roofline_smoke.json --max-events 120 --interpret roofline
	PYTHONPATH=$(PYTHONPATH) python -c "import json; \
	  r = json.load(open('BENCH_roofline_smoke.json'))['suites']['roofline']; \
	  assert r['rows'] > 0 and r['dispatches'] > 0, r; \
	  assert r['extra']['rows'] and all('model_agree' in p \
	    for p in r['extra']['rows']), r['extra']; \
	  print('roofline smoke OK:', r['rows'], 'rows,', \
	        r['dispatches'], 'dispatches')"

docs-check:
	$(PYTEST) -x -q tests/test_docs.py
