# Test lanes:
#   make test      - main suite on an 8-virtual-device CPU platform (mesh/
#                    sharding coverage without hardware)
#   make tpu-test  - hardware lane on the real TPU chip (kernels vs oracles,
#                    engine end-to-end); skips itself when no TPU is present
#   (speed)        - `python3 benchmark/run.py` as BENCHMARK.json gives it, on a chip
#   make lint      - ruff (when available) + metrics↔OBSERVABILITY.md gate
#   make check     - THE pre-snapshot gate: everything the driver measures.
#                    Run before every snapshot commit; nothing ships red.

# the tier-1 recipe uses pipefail/PIPESTATUS (bash, not POSIX sh)
SHELL := /bin/bash

test:
	python -m pytest tests/ -q

# THE tier-1 gate, verbatim from ROADMAP.md ("Tier-1 verify") — builders and
# CI run the same command the driver measures, so "green locally" and "green
# at the gate" cannot diverge (same markers, same timeout, same dot count).
tier1:
	set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=$${PIPESTATUS[0]}; echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c); exit $$rc

# The hardware lane runs ONCE: a test that fails on the chip has failed.
# (On the builders' machine the chip is reached through the chip tool, one
# command per call: `python -m pytest tests_tpu/ -q` after `chip_smoke.py`.)
tpu-test:
	python -m pytest tests_tpu/ -q

# Chaos lane (ISSUE 4 + ISSUE 5): the fault-injection suite with
# TPU_RAG_FAULTS armed (enables the harness end-to-end, including the
# arm_from_env path), proving on CPU that: a queue over cap returns 429 +
# Retry-After, a deadline expiry mid-decode frees its slot, an injected
# EngineStateLost completes via resubmit (and, on the PAGED engine, returns
# every KV block to the free list — zero leaks), and a reset storm flips
# /healthz readiness. docs/RESILIENCE.md, docs/KV_POOL.md.
# Adds to tier1, which runs the same file with the variable unset:
# TPU_RAG_FAULTS set, so /debug/faults may arm sites (faults.endpoint_enabled).
chaos:
	env TPU_RAG_FAULTS=1 JAX_PLATFORMS=cpu python -m pytest tests/test_resilience.py -q -p no:cacheprovider

# Flight-recorder smoke (ISSUE 11, docs/OBSERVABILITY.md "Engine flight
# recorder"): with the fault harness armed, a forced reset storm must
# produce an incident bundle whose per-request timelines reconstruct each
# in-flight lifecycle (admit → reset → resubmit → complete) BYTE-
# CONSISTENT with the streams the clients actually received, and
# scripts/flightview.py must round-trip the bundle offline. The full
# matrix (ring semantics, debug-endpoint gating, spool bounds, timeline
# opt-in) lives in the rest of tests/test_flight.py and runs under tier1.
# Adds to tier1, which runs the class with the variable unset: TPU_RAG_FAULTS=1.
flight-smoke:
	env TPU_RAG_FAULTS=1 JAX_PLATFORMS=cpu python -m pytest tests/test_flight.py::TestFlightSmoke -q -p no:cacheprovider

# Interleave smoke (ISSUE 16, docs/KV_POOL.md "Unified ragged sync
# windows"): with chunked prefill interleaved into decode windows on the
# tiny config, greedy AND seeded-sampled streams are BYTE-IDENTICAL to
# the phase-separated scheduler — mixed-length admission groups,
# mid-flight admission, and a chaos reset landing mid-chunk (the fault
# harness armed, partial KV + queue record dropped, zero leaked blocks,
# resubmission reproducing the stream). The full matrix (planner budget
# arithmetic, preempt/evict/reset accounting, prefix + speculation
# composition, goodput attribution, tp=2) lives in the rest of
# tests/test_chunked_prefill.py and runs under tier1.
# Adds to tier1, which runs the class with the variable unset: TPU_RAG_FAULTS=1.
interleave-smoke:
	env TPU_RAG_FAULTS=1 JAX_PLATFORMS=cpu python -m pytest tests/test_chunked_prefill.py::TestSmoke -q -p no:cacheprovider

# Journal-replay smoke (ISSUE 17, docs/REPLAY.md): record a live CPU run
# under the lockstep driver, extract_trace the journal, and re-drive it —
# the decision stream (admissions, windows, budgets, preemptions, resets)
# must be IDENTICAL, including a chaos-reset recording (the fault harness
# armed mid-decode) and the chunked-prefill planner; plus the pure-host
# simulator, calibrated on the same recording, must land its busy
# chip-time within the ±25% fidelity band. The full matrix (policy
# arithmetic, trace generation, journal round-trip/forward-compat,
# simulator speedup/preemption/oracle) lives in the rest of
# tests/test_replay.py and runs under tier1.
# Adds to tier1, which runs the class with the variable unset: TPU_RAG_FAULTS=1.
replay-smoke:
	env TPU_RAG_FAULTS=1 JAX_PLATFORMS=cpu python -m pytest tests/test_replay.py::TestReplaySmoke -q -p no:cacheprovider

# Static checks: ruff (when the environment provides it — this container
# does not bake it in, and the no-new-deps rule forbids installing it
# here; its rule selection is PINNED in pyproject.toml [tool.ruff] so a
# locally-installed ruff can't fail CI on unconfigured defaults) plus the
# metrics↔docs consistency gate, now a shim over ragcheck's METRIC-DRIFT
# rule (stdlib-only so it runs everywhere tier1 runs).
lint:
	@if command -v ruff >/dev/null 2>&1; then \
		ruff check rag_llm_k8s_tpu tests scripts; \
	else \
		echo "lint: ruff not installed in this environment; skipping style pass"; \
	fi
	python scripts/check_metrics_docs.py

# ragcheck (ISSUE 10, docs/STATIC_ANALYSIS.md): the repo-native static
# analyzer — AST rules distilled from this repo's own bug history
# (LOCK-DISCIPLINE, JIT-HYGIENE, SHARDING-CONTRACT, CONFIG-DRIFT,
# FAULT-SITE-REGISTRY, METRIC-DRIFT). Stdlib-only, CPU-only, no network;
# exits non-zero on any finding not in the ratcheted baseline
# (scripts/ragcheck/baseline.json — justified entries only, may only
# shrink) and on stale baseline entries whose finding no longer fires.
analyze:
	python -m scripts.ragcheck

validate-8b:
	python scripts/validate_8b.py

check: test tpu-test
	python -c "from __graft_entry__ import entry; import jax; fn, a = entry(); jax.jit(fn).lower(*a).compile(); print('entry: compile OK')"
	XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
		python -c "from __graft_entry__ import dryrun_multichip; dryrun_multichip(8); print('dryrun_multichip(8): OK')"

# The no-hardware CI lane: the tier-1 gate verbatim, the four lanes that
# re-run a part of it with TPU_RAG_FAULTS set (a lane that re-ran a class
# under tier1's own environment added nothing and went at PR 57) and the
# static checks. Speed is not judged here: the
# driver runs the benchmark on the chip and holds a PR to BENCHMARK.json's
# bounds.
ci: tier1 chaos interleave-smoke flight-smoke replay-smoke lint analyze

.PHONY: test tier1 tpu-test chaos interleave-smoke flight-smoke replay-smoke ci lint analyze check validate-8b
