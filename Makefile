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
chaos:
	env TPU_RAG_FAULTS=1 JAX_PLATFORMS=cpu python -m pytest tests/test_resilience.py -q -p no:cacheprovider

# Tensor-parallel paged smoke (ISSUE 6): the head-sharded arena + the
# shard_map'd paged kernels on the fake 2-device CPU mesh (conftest forces
# 8 virtual host devices) — byte-identical greedy streams vs dense tp=2 and
# paged tp=1, interpret-mode kernel↔oracle parity under the serving
# partition specs, and zero leaked blocks at tp=2.
tp2-smoke:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_kv_pool_tp.py -q -p no:cacheprovider

# Lookahead smoke (ISSUE 7): sequential-vs-overlapped /query greedy streams
# byte-identical with retrieval lookahead off and on — solo, concurrent,
# and with an explicitly pre-launched (resolved-at-join) future. The full
# pipeline matrix (staging release, headroom gating, session pipelining,
# fault fallback) lives in the rest of tests/test_lookahead.py and runs
# under tier1; docs/LOOKAHEAD.md.
lookahead-smoke:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_lookahead.py::TestSmoke -q -p no:cacheprovider

# KV-tiering smoke (ISSUE 8): with tiering ENABLED and every chain hot,
# greedy streams are byte-identical to tiering-off on BOTH substrates
# (splice buffers and paged pool blocks); a hot→cold→swap-in round trip is
# byte-exact; forced WARM demotion serves within the pinned int8 logit
# tolerance, and mixed hot/warm rows share one paged admission group. The
# full matrix (transitions, hotness decay, pool tier ledgers, chaos) lives
# in the rest of tests/test_kv_tiering.py and runs under tier1;
# docs/KV_POOL.md "hotness-aware tiering".
tiering-smoke:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_kv_tiering.py::TestSmoke -q -p no:cacheprovider

# Chunk-splice smoke (ISSUE 12, docs/PREFIX_CACHE.md "chunk-granular
# reuse"): shuffled-composition logit-tolerance parity on the tiny config
# — the same chunk set permuted across queries serves from re-rotated +
# boundary-corrected canonical KV within the pinned tolerance on BOTH
# substrates (one-shot splice buffers and paged per-chunk pool assembly),
# and exact-chain hits stay byte-identical. The full matrix (hot gate,
# warm tier, chaos fallback, pool accounting) lives in the rest of
# tests/test_chunk_reuse.py and runs under tier1.
splice-smoke:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_chunk_reuse.py::TestSmoke -q -p no:cacheprovider

# Paged-speculation smoke (ISSUE 13, docs/SPECULATIVE.md): with
# TPU_RAG_SPEC_PAGED-style speculation enabled on the tiny config, paged
# continuous greedy AND seeded-sampled streams are BYTE-IDENTICAL to
# speculation-off across mixed-length admission groups and mid-flight
# admission, with verify steps proven to fire (non-vacuous). The full
# matrix (EOS mid-window, budget clamps, slot-ladder top, prefixed
# admissions, preemption, adaptive-K, tp=2) lives in the rest of
# tests/test_spec_paged.py and runs under tier1; the chaos interactions
# ride `make chaos` (tests/test_resilience.py::TestSpecChaos).
spec-smoke:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_spec_paged.py::TestSmoke -q -p no:cacheprovider

# Flight-recorder smoke (ISSUE 11, docs/OBSERVABILITY.md "Engine flight
# recorder"): with the fault harness armed, a forced reset storm must
# produce an incident bundle whose per-request timelines reconstruct each
# in-flight lifecycle (admit → reset → resubmit → complete) BYTE-
# CONSISTENT with the streams the clients actually received, and
# scripts/flightview.py must round-trip the bundle offline. The full
# matrix (ring semantics, debug-endpoint gating, spool bounds, timeline
# opt-in) lives in the rest of tests/test_flight.py and runs under tier1.
flight-smoke:
	env TPU_RAG_FAULTS=1 JAX_PLATFORMS=cpu python -m pytest tests/test_flight.py::TestFlightSmoke -q -p no:cacheprovider

# Goodput-ledger smoke (ISSUE 14, docs/GOODPUT.md): with the ledger ON
# (its default), N concurrent mixed-length requests through the paged
# scheduler must satisfy the conservation invariant — per-window category
# chip-time sums to each window's duration, and per-request attributed
# chip-seconds sum to the scheduler's measured busy time within 5%,
# including under preemption (rework attributed once, never double) —
# with a non-vacuous category split (compute, useful decode AND bubble
# all present), and GET /debug/goodput honors the 403-unless-armed
# contract while flightview --goodput rebuilds the same report offline.
# The full matrix (roofline arithmetic, spec stats, one-shot windows,
# env round-trip) lives in the rest of tests/test_goodput.py under tier1.
goodput-smoke:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_goodput.py::TestSmoke -q -p no:cacheprovider

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
interleave-smoke:
	env TPU_RAG_FAULTS=1 JAX_PLATFORMS=cpu python -m pytest tests/test_chunked_prefill.py::TestSmoke -q -p no:cacheprovider

# Shadow-auditor smoke (ISSUE 15, docs/OBSERVABILITY.md "Shadow quality
# auditor"): forced-sample shadow audits on the tiny config — greedy
# spec-on continuous traffic and exact-chain prefix reuse audit at
# divergence rate 0.0 (the byte-identity contracts hold on live
# traffic); FORCED warm-tier demotion audits within the pinned 0.15
# logit tolerance with the divergence attributed to warm_tier; and a
# forced divergence burst spools a quality_divergence incident bundle
# that scripts/flightview.py --quality round-trips offline into the
# SAME report GET /debug/quality serves. The full matrix (sampling,
# headroom/backlog skips, fingerprints, SLO spec, config round-trip)
# lives in the rest of tests/test_shadow.py and runs under tier1.
shadow-smoke:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_shadow.py::TestShadowSmoke -q -p no:cacheprovider

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
replay-smoke:
	env TPU_RAG_FAULTS=1 JAX_PLATFORMS=cpu python -m pytest tests/test_replay.py::TestReplaySmoke -q -p no:cacheprovider

# Tenant-attribution smoke (ISSUE 18, docs/OBSERVABILITY.md "Tenant
# attribution"): the cardinality-bounded TenantTracker holds K tracked
# tenants + __other__ under a 10k-id churn storm; a 3-tenant workload
# through the paged scheduler conserves chip-seconds per tenant (rollup
# sum tracks the ledger's attributed total within 5%); and
# scripts/flightview.py --tenants rebuilds byte-identically the SAME
# report GET /debug/tenants serves live — proven against a poisoned jax
# import. The full matrix (HELP escaping, re-promotion, scrape-thread
# safety, lockstep round-trip, SLO reconcile) lives in the rest of
# tests/test_tenants.py and runs under tier1.
tenants-smoke:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_tenants.py::TestTenantsSmoke -q -p no:cacheprovider

# Drain smoke (ISSUE 19, docs/RESILIENCE.md "Crash-safe lifecycle"):
# POST /drain with a request deterministically in flight — readiness
# flips to 503 "draining" (liveness stays 200), new work sheds 503
# reason="draining" + the drain Retry-After, the in-flight request
# finishes 200 (zero 5xx), and the coordinator reaches DRAINED under
# deadline; a wedged overrun spools a drain_timeout incident bundle.
# The admission/coordinator state-machine matrix runs under tier1.
drain-smoke:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_lifecycle.py::TestHttpDrain tests/test_lifecycle.py::TestLifecycleCoordinator tests/test_lifecycle.py::TestAdmissionDraining -q -p no:cacheprovider

# Restart smoke (ISSUE 19): the crash-consistency pin — a subprocess is
# SIGKILLed with two requests mid-decode (token_emit progress proven in
# the WAL, no completes), a second process restores against the same WAL
# dir, and every delivered stream is BYTE-IDENTICAL to an uninterrupted
# oracle run; plus the in-process service restore path (fold-resume via
# the scheduler, synthetic-prompt skip, warmth-manifest rehydrate).
restart-smoke:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_lifecycle.py::TestCrashRestartChaos tests/test_lifecycle.py::TestServiceRestore -q -p no:cacheprovider

# Disaggregation smoke (ISSUE 20, docs/ROUTER.md): greedy AND seeded
# streams through a routed prefill->decode pair must be BYTE-IDENTICAL
# to a unified engine (the hand-off moves KV blocks, sampling keys, and
# the kv frontier without perturbing a single draw), the journal must
# carry matched migrate_begin/migrate_done pairs, affinity routing must
# be non-vacuous, and the simulator must size both tiers from a trace.
# tp=2 identity and the mid-migration chaos reset ride `make chaos` +
# tier1 (tests/test_router.py::TestDisaggTP2,
# tests/test_resilience.py::TestMigrationChaos).
disagg-smoke:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_router.py::TestSmoke -q -p no:cacheprovider

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

# CI-sized: streams ONE true-shape 70B layer in the int8 deployment mode
# (unlike validate-8b there is no separate full-depth script — a full 70B
# checkpoint is ~140 GB, beyond this environment's disk; the per-layer
# shapes and tp=8 shardings are what the single-layer proof pins)
validate-70b:
	python -m pytest tests/test_loader_70b.py -q

check: test tpu-test
	python -c "from __graft_entry__ import entry; import jax; fn, a = entry(); jax.jit(fn).lower(*a).compile(); print('entry: compile OK')"
	XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
		python -c "from __graft_entry__ import dryrun_multichip; dryrun_multichip(8); print('dryrun_multichip(8): OK')"

# The no-hardware CI lane: the tier-1 gate verbatim, the chaos (fault
# injection) suite and the static checks. Speed is not judged here: the
# driver runs the benchmark on the chip and holds a PR to BENCHMARK.json's
# bounds.
ci: tier1 chaos tp2-smoke lookahead-smoke tiering-smoke splice-smoke spec-smoke interleave-smoke flight-smoke goodput-smoke shadow-smoke replay-smoke tenants-smoke drain-smoke restart-smoke disagg-smoke lint analyze

.PHONY: test tier1 tpu-test chaos tp2-smoke lookahead-smoke tiering-smoke splice-smoke spec-smoke interleave-smoke flight-smoke goodput-smoke shadow-smoke replay-smoke tenants-smoke drain-smoke restart-smoke disagg-smoke ci lint analyze check validate-8b validate-70b
