#!/usr/bin/env python
"""CI perf gate: fail when a fresh bench JSON regresses vs the baseline.

Usage (``make bench-gate`` wires the default form):

    python scripts/bench_gate.py \
        --baseline BENCH_BASELINE.json \
        --current  /tmp/bench_fresh.json \
        [--tolerance 0.25] [--strict] [--dry-run]

Exit codes: 0 clean (or dry-run schema OK), 1 regression(s), 2 bad input.

- Direction awareness lives in ``rag_llm_k8s_tpu/obs/regression.py``:
  latency up = bad, tok/s down = bad, improvements never fail the gate.
- ``--dry-run`` validates both documents' SCHEMA (parse + at least one
  comparable numeric metric) without judging values — the fast ``make ci``
  leg, which must not need a TPU.
- A current document carrying ``"truncated": true`` (bench ran out of its
  ``TPU_RAG_BENCH_BUDGET_S`` budget) is compared on the legs it completed;
  the truncation is reported so a "clean" gate over half a bench is never
  mistaken for a full pass.
- ``--strict`` also fails on metrics missing from the current document
  (catching a silently dropped bench leg).

Stdlib + the repo only: runs everywhere tier-1 runs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from rag_llm_k8s_tpu.obs import regression  # noqa: E402

# Metrics that may NEVER silently vanish from a judged bench document: a
# dropped leg reads as "no regression" under the default missing-is-info
# policy, which is exactly how the B=64 continuous-step collapse went
# unjudged for a round. Keys here fail the gate when the CURRENT document
# lacks them while the baseline has them — unless the current run was
# budget-truncated before that leg (truncation is already reported).
# b64_sync16 is tracked higher-is-better by regression.classify; the paged
# keys are what the round-5 capture (before PR 1, in git history) lost to
# its rc 124 — a judged run that silently drops the paged leg must fail, not pass.
REQUIRED_KEYS = (
    "continuous_device_steps_per_s.b64_sync16",
    "paged_decode_steps_per_s.b64_paged",
    "paged_b64_speedup",
    "paged_tp.b8_steps_per_s",
    # ISSUE 7: the lookahead overlapped-query leg's headline — a dropped
    # leg must fail loudly, not read as "retrieval overlap unjudged"
    "lookahead_overlap.query_p50_overlap_ms",
    # ISSUE 8: the KV-tiering capacity headline (servable cached chunks at
    # fixed HBM, tiered vs hot-only; acceptance ≥ 3) — a dropped leg must
    # never read as "tiering capacity unjudged"
    "kv_tiering.effective_capacity_x",
    # ISSUE 11: the flight recorder's measured cost (recorder-on vs -off
    # B=8 continuous decode; acceptance ≤ 2%) — the recorder is ON by
    # default, so its overhead may never go unjudged in a bench round
    "flight_overhead.overhead_frac",
    # ISSUE 12: chunk-granular prefix reuse — prefill tokens skipped on
    # the shuffled-composition stream (acceptance ≥ 0.5 with the logit
    # tolerance green); a silently dropped leg must fail the gate instead
    # of reading as "chunk reuse unjudged"
    "chunk_reuse.prefill_skip_frac",
    # ISSUE 13: speculative decoding in the continuous paged engine — the
    # B=8 spec-on/spec-off tok/s ratio on the repeat-heavy RAG workload
    # (acceptance > 1.5×); a silently dropped leg must fail the gate, not
    # read as "paged speculation unjudged"
    "continuous_spec.b8_speedup",
    # ISSUE 14: the goodput ledger's measured cost (ledger-on vs -off B=8
    # continuous decode; acceptance ≤ 2%) — the ledger is ON by default,
    # so its overhead may never go unjudged in a bench round
    "goodput_overhead.overhead_frac",
    # ISSUE 15: the shadow quality auditor's measured cost (audits-on vs
    # -off B=8 continuous decode at the default 5% sample rate;
    # acceptance ≤ 2%) — the auditor is ON by default, so its overhead
    # may never go unjudged in a bench round
    "shadow_overhead.overhead_frac",
    # ISSUE 16: unified ragged sync windows — the padding-bubble share of
    # busy chip time on the heavy-admission-churn workload with chunked
    # prefill interleaved into decode (acceptance: lower than the
    # phase-separated scheduler's; regression.classify tracks bubble_frac
    # lower-is-better) — a silently dropped leg must fail the gate, not
    # read as "admission-churn occupancy unjudged"
    "chunked_prefill.bubble_frac",
    # ISSUE 17: the replay simulator's fidelity headline — simulated
    # steps/s over the measurement its step model was calibrated on
    # (acceptance: within ±25% of 1.0; regression.classify judges it
    # "band" — drifting high is as wrong as drifting low). A silently
    # dropped leg must fail the gate, not read as "capacity-planning
    # predictions unjudged" (docs/REPLAY.md)
    "replay_fidelity.steps_per_s_ratio",
    # ISSUE 18: tenant attribution's measured cost (full per-request
    # lifecycle — edge intern, stamp, fold, counter pushes — on vs off at
    # B=8 continuous decode; acceptance ≤ 2%) — attribution is ON by
    # default, so its overhead may never go unjudged in a bench round
    "tenant_overhead.overhead_frac",
    # ISSUE 19: warm restart's measured benefit — the fraction of the
    # cold first-burst's first-touch prefill tokens the warmth-manifest
    # rehydration makes unnecessary (regression.classify tracks
    # "reduction" higher-is-better). A silently dropped leg must fail
    # the gate, not read as "restart warmth unjudged"
    "restart_warmth.warm_prefill_reduction",
    # ISSUE 20: disaggregated prefill/decode pools — tokens-per-dollar of
    # the routed pair over the unified baseline on the same concurrent
    # workload (regression.classify judges tokens_per_usd higher-is-
    # better). A silently dropped leg must fail the gate, not read as
    # "the split's cost unjudged"
    "disagg.tokens_per_usd_ratio",
)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--baseline", default=os.path.join(REPO, "BENCH_BASELINE.json"))
    ap.add_argument("--current", default=None,
                    help="fresh bench JSON (defaults to the baseline itself "
                         "— a self-comparison smoke that must pass)")
    ap.add_argument("--tolerance", type=float,
                    default=regression.DEFAULT_TOLERANCE,
                    help="relative band before a bad-direction move fails "
                         f"(default {regression.DEFAULT_TOLERANCE})")
    ap.add_argument("--strict", action="store_true",
                    help="also fail on metrics missing from --current")
    ap.add_argument("--require", action="append", default=None,
                    metavar="KEY",
                    help="flattened metric key(s) the CURRENT document must "
                         "carry (repeatable); overrides the built-in "
                         "REQUIRED_KEYS list")
    ap.add_argument("--dry-run", action="store_true",
                    help="schema check only (no value judgment, no TPU)")
    args = ap.parse_args(argv)
    current_path = args.current or args.baseline

    try:
        baseline = regression.load_json(args.baseline)
    except Exception as e:  # noqa: BLE001
        print(f"bench-gate: cannot load baseline {args.baseline}: {e}",
              file=sys.stderr)
        return 2
    try:
        current = regression.load_json(current_path)
    except Exception as e:  # noqa: BLE001
        print(f"bench-gate: cannot load current {current_path}: {e}",
              file=sys.stderr)
        return 2

    problems = regression.schema_check(baseline) + regression.schema_check(current)
    if problems:
        for p in problems:
            print(f"bench-gate: schema: {p}", file=sys.stderr)
        return 2
    if args.dry_run:
        n = sum(
            1 for k, v in regression.flatten(current).items()
            if regression.classify(k) != "ignore"
            and isinstance(v, (int, float)) and not isinstance(v, bool)
        )
        print(f"bench-gate: dry-run OK ({n} comparable metrics in "
              f"{os.path.basename(current_path)})")
        return 0

    overlap = regression.comparable_overlap(current, baseline)
    if not overlap:
        # zero shared comparable metrics = the gate would judge NOTHING;
        # "OK" here would green-light any regression (schema drift, wrong
        # file, stale baseline) — fail loudly instead
        print(
            "bench-gate: the two documents share no comparable metrics — "
            "nothing would be judged. Wrong baseline/current pairing?",
            file=sys.stderr,
        )
        return 2
    findings = regression.compare(current, baseline, tolerance=args.tolerance)
    if current.get("truncated"):
        skipped = current.get("legs_skipped") or []
        print("bench-gate: NOTE current bench was budget-truncated"
              + (f" (skipped legs: {', '.join(skipped)})" if skipped else ""))
    for f in findings["improvement"]:
        print(f"bench-gate: improvement  {f.describe()}")
    for f in findings["missing"]:
        print(f"bench-gate: missing      {f.describe()}")
    for f in findings["regression"]:
        print(f"bench-gate: REGRESSION   {f.describe()}", file=sys.stderr)

    failed = bool(findings["regression"])
    cur_flat = regression.flatten(current)
    base_flat = regression.flatten(baseline)
    for key in (args.require if args.require is not None else REQUIRED_KEYS):
        if key in cur_flat or key not in base_flat:
            continue  # present, or the baseline never had it either
        if current.get("truncated"):
            # budget truncation already printed its NOTE; a leg the budget
            # cut is not a SILENT drop
            print(f"bench-gate: required {key} absent (budget-truncated run)")
            continue
        print(
            f"bench-gate: REQUIRED metric {key} missing from current — a "
            "dropped leg must never read as a pass", file=sys.stderr,
        )
        failed = True
    if args.strict and any(f.current is None for f in findings["missing"]):
        print("bench-gate: strict: metrics missing from current", file=sys.stderr)
        failed = True
    if failed:
        print(f"bench-gate: FAIL ({len(findings['regression'])} regression(s) "
              f"at tolerance {args.tolerance:.0%})", file=sys.stderr)
        return 1
    print(f"bench-gate: OK ({len(overlap)} metrics judged at tolerance "
          f"{args.tolerance:.0%}; {len(findings['improvement'])} "
          f"improvement(s), {len(findings['missing'])} missing)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
