.PHONY: proto test native jvm-compile bench lint lint-changed perfcheck sqlgate obscheck servecheck servegate streamgate

# keep `make` (no target) regenerating the proto, as before the lint gate
.DEFAULT_GOAL := proto

# Both static gates, one uniform report schema (tools/auronlint/report.py;
# --json and --sarif emitters on both):
# auronlint = engine-invariant rules R1-R13 over auron_tpu/ (AST-based,
#             R7-R13 interprocedural via tools/auronlint/callgraph.py),
# jvm_lint  = structural/ABI/wire-contract checks over jvm/.
# Exit nonzero on any unsuppressed finding OR a LINT_RATCHET.json
# regression (per-rule suppression counts may only shrink; improvements
# are persisted atomically) OR wall time past the budget (guard: a new
# rule pass must not blow up tier-1; parse/summary caching in
# tools/auronlint/filecache.py keeps warm runs fast). The SARIF artifact
# always lands at build/auronlint.sarif for CI pickup. Also gated in
# tier-1 via tests/test_auronlint.py and tests/test_jvm_contract.py.
AURONLINT_TIME_BUDGET ?= 60
lint:
	JAX_PLATFORMS=cpu python -m tools.auronlint --sarif-out build/auronlint.sarif --time-budget $(AURONLINT_TIME_BUDGET)
	python tools/jvm_lint.py --sarif-out build/jvm_lint.sarif

# Inner-loop fast mode: lint only git-touched engine files with the
# per-file rules (the whole-package interprocedural pass R4/R7-R13 stays
# in `make lint` and tier-1; no ratchet here — counts are tree-wide).
lint-changed:
	JAX_PLATFORMS=cpu python -m tools.auronlint --changed

# Runtime half of the R1 host-sync contract: replay a tiny SF<=1 q3-class
# breakdown and fail if any declared sync site exceeds the per-batch/
# per-task multiplicity budget its sync-point comment promises
# (tools/perfcheck.py; budgets parsed by tools/auronlint/syncbudget.py).
perfcheck:
	JAX_PLATFORMS=cpu python tools/perfcheck.py

# Observability overhead gate (docs/observability.md): replays the same
# tiny q3-class pipeline in no-obs / obs-off / flight-recorder subprocess
# configurations and fails when obs-off exceeds 2% or the always-on
# flight recorder exceeds 5% wall over the no-obs baseline; also
# sanity-checks a full-trace run's Perfetto artifact and that
# obs.window_summary of the replay is complete (tools/obscheck.py).
obscheck:
	JAX_PLATFORMS=cpu python tools/obscheck.py

proto:
	protoc --python_out=. auron_tpu/proto/plan.proto

native:
	$(MAKE) -C native

test:
	python -m pytest tests/ -q

bench:
	python bench.py

# Serving gate (docs/serving.md): boot the SQL server over real HTTP at
# toy scale and prove the serving contract — serial replay and N
# concurrent clients byte-identical with ZERO new XLA compiles (plan
# cache), tenancy/conf isolation incl. plan-knob cache invalidation, no
# cross-query trace bleed in /queries, bad requests refused
# (tools/servecheck.py). The >=2x throughput floor + queries/s ratchet
# run at real scale via `make servegate`.
servecheck:
	JAX_PLATFORMS=cpu python tools/servecheck.py

# Concurrency differential gate at real scale (models/servegate.py):
# serve.gate.clients clients replay the sqlgate corpus against the warm
# server — bit-identical to serial, zero compiles on the cached legs,
# concurrent/serial queries/s over the substrate-resolved floor
# (SERVEGATE_MIN_SPEEDUP overrides; 2.0 accelerators / 1.4 CPU — the
# measured GIL split, docs/serving.md), queries/s ratcheted in
# PERF_RATCHET.json, p50/p99 recorded.
servegate:
	JAX_PLATFORMS=cpu python -m auron_tpu.models.servegate

# Streaming gate (docs/streaming.md): fused vs eager Calc-chain
# differential over one deterministic Kafka corpus (bit-identical
# emissions, fused must beat eager), zero-compile replay, a crash-resume
# bit-identity leg, and the sustained stream_events_s ratchet in
# PERF_RATCHET.json. The kill-at-every-seam fuzz runs in tier-1
# (tests/test_stream_exactly_once.py); this is the at-scale run.
streamgate:
	JAX_PLATFORMS=cpu python -m auron_tpu.models.streamgate

# Real-text SQL differential gate (docs/sql.md): 24 actual TPC-DS query
# strings through sql/ parse->bind->lower and the mesh driver, row-level
# vs pandas oracles at sql.gate.sf (default 4) + plan-stability goldens +
# 11 unsupported texts that must raise positioned diagnostics. Exit
# nonzero on any failure. Tier-1 runs the same corpus at toy scale via
# tests/test_sqlgate.py; AURON_SQL_UPDATE_GOLDENS=1 regenerates goldens.
sqlgate:
	JAX_PLATFORMS=cpu python -m auron_tpu.models.sqlgate

# JVM shim compile gate (VERDICT r2 item 4): compiles jvm/ against Spark +
# JDK 21 when a toolchain is present. The gate needs SPARK_HOME (a Spark
# 3.5+ distribution whose jars/ supplies the compile classpath), scalac on
# PATH, and JDK 21+ (java.lang.foreign). CI images without these skip with
# a loud message; images with them FAIL the build on any compile error.
SPARK_JARS = $(wildcard $(SPARK_HOME)/jars/*.jar)
EMPTY :=
SPACE := $(EMPTY) $(EMPTY)
JVM_CLASSPATH = $(subst $(SPACE),:,$(strip $(SPARK_JARS)))

jvm-compile:
	@if [ -z "$(SPARK_HOME)" ] || ! command -v scalac >/dev/null; then \
	  echo "jvm-compile SKIPPED: needs SPARK_HOME + scalac + JDK21 (none in this image)"; \
	  echo "  the ABI + JSON contract is gated instead by tests/test_native.py"; \
	  echo "  and tests/test_stage_split.py (C host harness) and"; \
	  echo "  tests/test_convert.py (serializer-shaped JSON conversion)"; \
	else \
	  mkdir -p jvm/target/classes && \
	  javac --release 21 -d jvm/target/classes \
	    $$(find jvm -name '*.java') && \
	  scalac -release 21 -classpath "$(JVM_CLASSPATH):jvm/target/classes" \
	    -d jvm/target/classes $$(find jvm -name '*.scala') && \
	  echo "jvm-compile OK"; \
	fi
