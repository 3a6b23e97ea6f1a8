# Repo-level developer/CI entry points. External CI needs exactly one
# command per gate: `make lint` (static analysis, exit 0/1),
# `make test` (tier-1), `make native-sanitize` (dynamic analysis of
# the C++ layer).

PY ?= python
ASAN_RT := $(shell gcc -print-file-name=libasan.so)
TSAN_RT := $(shell gcc -print-file-name=libtsan.so)

.PHONY: lint lint-json lint-changed env-table rule-table dur-table \
	wire-table order-smoke \
	crash-smoke test native native-sanitize bench \
	bench-warm obs-smoke serve-smoke fleet-smoke trace-report \
	cost-report \
	search-report planner-report

# Self-hosted static analysis: gate registry, JAX hazards, concurrency
# discipline, shm lifecycle, tracer discipline, plus the cross-boundary
# analyses — ABI/layout prover, tensor-contract dataflow, lockset
# analysis, happens-before prover, frame-protocol drift
# (jepsen_tpu/lint/). order-smoke runs the two protocol families
# standalone first so their findings surface even if the full pass
# dies earlier.
lint: order-smoke
	$(PY) -m jepsen_tpu.cli lint

lint-json:
	$(PY) -m jepsen_tpu.cli lint --format json

# The fast inner loop: only files dirty vs the git merge-base, through
# the content-hash result cache (bench_artifacts/.lintcache). Full
# runs stay the tier-1 default.
lint-changed:
	$(PY) -m jepsen_tpu.cli lint --changed

# Regenerate the README rule table from the rule registry (lint rule
# JT-META-001 fails the build when the committed table drifts).
rule-table:
	$(PY) -c "from pathlib import Path; from jepsen_tpu import lint; \
	p = Path('README.md'); t = p.read_text(); \
	s = t.index(lint.RULES_BEGIN); \
	e = t.index(lint.RULES_END) + len(lint.RULES_END); \
	p.write_text(t[:s] + lint.render_rule_block() + t[e:]); \
	print('README.md rule table regenerated')"

# Regenerate the README env-gate table from the gates registry (lint
# rule JT-GATE-003 fails the build when the committed table drifts).
env-table:
	$(PY) -c "from pathlib import Path; from jepsen_tpu import gates; \
	p = Path('README.md'); t = p.read_text(); \
	s = t.index(gates.TABLE_BEGIN); \
	e = t.index(gates.TABLE_END) + len(gates.TABLE_END); \
	p.write_text(t[:s] + gates.render_env_block() + t[e:]); \
	print('README.md env-gate table regenerated')"

# Regenerate the README "Store durability" table from the
# STORE_ARTIFACTS registry (lint rule JT-DUR-006 fails the build when
# the committed table drifts).
dur-table:
	$(PY) -c "from pathlib import Path; \
	from jepsen_tpu.lint import contracts as c; \
	p = Path('README.md'); t = p.read_text(); \
	s = t.index(c.DUR_BEGIN); \
	e = t.index(c.DUR_END) + len(c.DUR_END); \
	p.write_text(t[:s] + c.render_dur_block() + t[e:]); \
	print('README.md store-durability table regenerated')"

# Regenerate the README wire-frame table from serve/protocol.py's
# FRAME_OPS registry (lint rule JT-WIRE-003 fails the build when the
# committed table drifts).
wire-table:
	$(PY) -c "from pathlib import Path; \
	from jepsen_tpu.lint import wireflow as w; \
	reg = w.live_registry(Path('.')); \
	p = Path('README.md'); t = p.read_text(); \
	s = t.index(w.WIRE_BEGIN); \
	e = t.index(w.WIRE_END) + len(w.WIRE_END); \
	p.write_text(t[:s] + w.render_wire_block(reg) + t[e:]); \
	print('README.md wire-frame table regenerated')"

# The two protocol families standalone against the live tree: JT-ORD
# module rules over the contracted modules, JT-WIRE project rules over
# the serve trio. Exit 1 on any finding.
order-smoke:
	$(PY) -c "import sys; from pathlib import Path; \
	from jepsen_tpu import lint; \
	from jepsen_tpu.lint import contracts, order, wireflow; \
	root = lint.default_root(); \
	files = sorted({root / c.file for c in contracts.ORDER_CONTRACTS}); \
	out = list(lint.lint_paths(files, root, rules=order.RULES)); \
	ctx = lint.ProjectCtx(root, []); \
	out += [f for r in wireflow.RULES for f in r.check_project(ctx)]; \
	[print(f.render()) for f in out]; \
	print(f'order-smoke: {len(out)} findings ' \
	      f'({len(contracts.ORDER_CONTRACTS)} contracts proved)'); \
	sys.exit(1 if out else 0)"

# Crash-consistency smoke: the kill-mid-write / short-write /
# torn-tail / rotation tests over the journal-class artifacts
# (costdb, verdict journal, events rotation) — the dynamic
# counterpart of the JT-DUR static prover.
crash-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_costdb.py \
	  tests/test_obs.py tests/test_durability_prover.py -q \
	  -m 'not slow' -k 'crash or torn or seal or rotat or caught'

# Tier-1: the ROADMAP verification gate.
test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow'

native:
	$(MAKE) -C native

# Dynamic analysis of the native layer:
#   1. ASan+UBSan builds of hist_encode/wgl/graph_algo, replayed
#      through the existing differential encode tests with
#      JEPSEN_TPU_NATIVE_LIB_DIR pinning the instrumented .so's
#      (no silent fallback to a production build), plus the hostile-
#      input fuzz drive;
#   2. a TSan build of the encode/sidecar writer path hammered from
#      concurrent threads (native/asan_drive.py --tsan).
# detect_leaks=0: CPython's interpreter allocations drown the report;
# overflows/UB in the libraries still abort loudly.
native-sanitize:
	$(MAKE) -C native asan tsan
	LD_PRELOAD=$(ASAN_RT) ASAN_OPTIONS=detect_leaks=0 \
	  JEPSEN_TPU_NATIVE_LIB_DIR=native/build/asan JAX_PLATFORMS=cpu \
	  $(PY) -c "from jepsen_tpu import native_lib; \
	  assert native_lib.hist_lib() is not None, 'asan lib did not load'"
# TestHbmEnvelope is deselected: it exercises jitted bucket dispatch,
# and gcc-10 libasan's __cxa_throw interceptor CHECK-fails on
# exceptions unwound from jaxlib's statically-linked MLIR .so — a
# toolchain conflict, not a finding. Every test that touches the
# native encode/split/sidecar path stays in.
	LD_PRELOAD=$(ASAN_RT) ASAN_OPTIONS=detect_leaks=0 \
	  JEPSEN_TPU_NATIVE_LIB_DIR=native/build/asan JAX_PLATFORMS=cpu \
	  $(PY) -m pytest tests/test_ingest_pipeline.py \
	    tests/test_native_split.py -q -m 'not slow' \
	    -k 'not TestHbmEnvelope'
	LD_PRELOAD=$(ASAN_RT) ASAN_OPTIONS=detect_leaks=0 JAX_PLATFORMS=cpu \
	  $(PY) native/asan_drive.py
	LD_PRELOAD=$(TSAN_RT) TSAN_OPTIONS=halt_on_error=1 JAX_PLATFORMS=cpu \
	  $(PY) native/asan_drive.py --tsan

bench:
	JAX_PLATFORMS=cpu $(PY) bench.py

# The copy-free warm-path gate: smoke-shape cold -> warm -> warm-again
# sweeps (each its own process, shared store + executable cache); fails
# if the second warm run copies any host bytes on the pack path or
# misses the AOT executable cache even once. Exit 0/1.
bench-warm:
	JAX_PLATFORMS=cpu $(PY) -m jepsen_tpu.warm_bench

# Live-telemetry + trace-fabric smoke: a tiny POOLED sweep with the
# health sampler, the /metrics endpoint and the attribution report
# force-enabled, one mid-flight scrape, an exposition<->metrics.json
# parity check, and the merged-trace/report contract (>=1 worker
# track with encode spans; shares sum to ~1.0). Exit 0/1.
obs-smoke:
	JAX_PLATFORMS=cpu $(PY) -m jepsen_tpu.obs.smoke

# Verdict-service smoke: the REAL `jepsen-tpu serve` daemon as a
# subprocess over a synthetic store, two concurrent tenants through
# the real socket, a mid-flight /metrics scrape (per-tenant series),
# a SIGTERM drain (exit 0, zero lost/duplicated journal entries), and
# streamed-vs-`analyze-store` byte-identical verdict parity. Exit 0/1.
serve-smoke:
	JAX_PLATFORMS=cpu $(PY) -m jepsen_tpu.serve.smoke

# Serve-fleet smoke: a REAL 3-daemon fleet behind the router, three
# tenants streaming through `fleet.sock` while a self-nemesis schedule
# (socket partition, SIGKILL mid-load, SIGSTOP hammer, clock-skewed
# member via the faketime shim) breaks members underneath them. Every
# tenant must land every verdict with zero lost/duplicated journal
# lines, byte-identical to a post-hoc `analyze-store` sweep. Exit 0/1.
fleet-smoke:
	JAX_PLATFORMS=cpu $(PY) -m jepsen_tpu.serve.fleet_smoke

# Convenience: re-sweep an existing store (STORE ?= store) and emit
# the merged trace + critical-path attribution report
# (<store>/trace.json, report.json, report.md).
STORE ?= store
trace-report:
	$(PY) -m jepsen_tpu.cli analyze-store --store $(STORE) --report

# trace-report with the device cost observatory on: additionally
# appends per-(executable, geometry) XLA-cost × measured-window
# records to <store>/costdb.jsonl (provenance-tagged) and adds the
# device roofline section to the report.
cost-report:
	JEPSEN_TPU_COSTDB=1 \
	  $(PY) -m jepsen_tpu.cli analyze-store --store $(STORE) --report

# trace-report with kernel search telemetry on (and the costdb, so
# the search section's edge-density-vs-device-time join has measured
# windows): journals one stats line per history to
# <store>/analytics.jsonl and adds the "search" section (anomaly
# rate, closure-round + margin distributions) to the report.
search-report:
	JEPSEN_TPU_KERNEL_STATS=1 JEPSEN_TPU_COSTDB=1 \
	  $(PY) -m jepsen_tpu.cli analyze-store --store $(STORE) --report

# search-report with the cost-aware planner on: routes the sweep
# through the fitted model (warm-started from <store>/plan.json when
# one exists), refits the plan from this sweep's measured costdb ×
# analytics join at the end, and adds the "planner" section
# (decisions, fallbacks, predicted-vs-measured error) to the report.
planner-report:
	JEPSEN_TPU_PLANNER=1 JEPSEN_TPU_KERNEL_STATS=1 JEPSEN_TPU_COSTDB=1 \
	  $(PY) -m jepsen_tpu.cli analyze-store --store $(STORE) --report
