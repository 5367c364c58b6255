"""flightview — offline renderer for flight-recorder bundles and journals.

Turns an incident bundle (``GET /debug/incidents?id=...``, or a file
copied off a pod's spool directory) — or a bare journal dump — into:

- **per-request lifecycle timelines**: each request's ordered event chain
  (admit → sync windows → eos/preempt/evict/resubmit → complete) with
  inter-event deltas, as ASCII or JSON;
- **a scheduler-occupancy summary**: windows observed, active-row
  distribution, rows completed, resets/preemptions/sheds in the window
  the journal covers;
- **the goodput report** (``--goodput``): per-category chip-time split,
  rolling MFU/roofline per executable kind, and cost-per-query
  percentiles, rebuilt from the journal's ``goodput_window``/``complete``
  events by the SAME renderer ``GET /debug/goodput`` uses live
  (rag_llm_k8s_tpu/obs/goodput.py, loaded by file path so no jax is
  pulled in) — the two reports cannot drift apart;
- **the shadow quality report** (``--quality``): audit outcomes,
  divergence rate, logit-err/first-divergence distributions and
  per-approximation attribution, rebuilt from the journal's
  ``shadow_audit`` events by the SAME renderer ``GET /debug/quality``
  uses live (rag_llm_k8s_tpu/obs/shadow.py, same jax-free contract);
- **the tenant attribution report** (``--tenants``): per-tenant
  arrivals/completions/sheds/tokens/chip-seconds/cost and shadow-audit
  divergence, rebuilt from the journal's tenant-stamped lifecycle events
  by the SAME renderer ``GET /debug/tenants`` uses live
  (rag_llm_k8s_tpu/obs/tenants.py, same jax-free contract);
- **the replay diff** (``--replay-diff OTHER``): event-by-event
  comparison of two journals' scheduler decision streams — the first
  divergent decision, per-event-type count deltas, occupancy deltas —
  via rag_llm_k8s_tpu/sim/replay.py (same jax-free contract). This is
  how a ``make replay-smoke`` failure or a live-vs-simulated run is
  triaged (docs/REPLAY.md);
- **the restore report** (``--restore-report``): the warm-restart
  post-mortem over a flight-WAL directory copied off the pod's PVC —
  per epoch (one per process incarnation), what died in flight and what
  the next incarnation's restore pass resumed, rehydrated, or skipped
  (sim/replay.py ``build_restore_report``, same jax-free contract;
  docs/RESILIENCE.md "Crash-safe lifecycle").

No live pod, no jax, no third-party deps — a bundle is self-contained by
contract (docs/OBSERVABILITY.md "Engine flight recorder").

Usage:
    python scripts/flightview.py BUNDLE.json            # ASCII render
    python scripts/flightview.py BUNDLE.json --json     # structured form
    python scripts/flightview.py BUNDLE.json --request 7
    python scripts/flightview.py BUNDLE.json --goodput [--chip-hour-usd X]
    python scripts/flightview.py BUNDLE.json --quality
    python scripts/flightview.py BUNDLE.json --tenants [--chip-hour-usd X]
    python scripts/flightview.py RECORDED.json --replay-diff REPLAYED.json
    python scripts/flightview.py WAL_DIR/ --restore-report

Input shapes accepted: a full incident bundle (``{"journal": [...],
"trigger": ..., ...}``), a journal-only dump (``{"journal": [...]}``), or
a plain JSON list of events. Events newer than this tool's known
``schema_version`` are refused loudly rather than misread.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
from typing import Dict, List, Optional

# keep in sync with rag_llm_k8s_tpu/obs/flight.py — flightview must run
# standalone on a laptop holding nothing but the bundle file, so the
# constant is duplicated here ON PURPOSE (the round-trip smoke in
# tests/test_flight.py fails if the two drift apart)
SCHEMA_VERSION = 1


def load_events(doc) -> List[Dict]:
    """Extract the event list from any accepted input shape."""
    if isinstance(doc, list):
        events = doc
    elif isinstance(doc, dict):
        ver = doc.get("schema_version", SCHEMA_VERSION)
        if int(ver) > SCHEMA_VERSION:
            raise SystemExit(
                f"flightview: bundle schema_version {ver} is newer than "
                f"this tool understands ({SCHEMA_VERSION}) — update the repo"
            )
        events = doc.get("journal", [])
    else:
        raise SystemExit("flightview: unrecognized input shape")
    return sorted(events, key=lambda e: e.get("seq", 0))


def _attrs(e: Dict) -> Dict:
    return {
        k: v for k, v in e.items() if k not in ("seq", "t", "type", "rid")
    }


def build_view(events: List[Dict],
               request_id: Optional[int] = None) -> Dict:
    """The structured form: per-request timelines + occupancy summary."""
    requests: Dict[int, List[Dict]] = {}
    t0 = events[0]["t"] if events else 0.0
    for e in events:
        rid = e.get("rid")
        if rid is None or (request_id is not None and rid != request_id):
            continue
        requests.setdefault(int(rid), []).append(e)

    timelines = {}
    for rid, evs in sorted(requests.items()):
        base = evs[0]["t"]
        prev = base
        rows = []
        for e in evs:
            rows.append({
                "seq": e.get("seq"),
                "type": e["type"],
                "t_ms": round((e["t"] - base) * 1e3, 3),
                "dt_ms": round((e["t"] - prev) * 1e3, 3),
                "attrs": _attrs(e),
            })
            prev = e["t"]
        types = [r["type"] for r in rows]
        timelines[str(rid)] = {
            "events": rows,
            "complete": "complete" in types,
            # only real reset recoveries: a preempt_resume is scheduled
            # backpressure (no reset happened) and a gave_up is the one
            # case the client did NOT survive
            "resets_survived": sum(
                1 for r in rows
                if r["type"] == "resubmit"
                and r["attrs"].get("outcome") == "resubmitted"
            ),
            "span_ms": round((evs[-1]["t"] - base) * 1e3, 3),
        }

    windows = [e for e in events if e["type"] == "sync_window_open"]
    active = [int(e.get("active", 0)) for e in windows]
    closes = [e for e in events if e["type"] == "sync_window_close"]
    # unified ragged sync windows (ISSUE 16): every mixed window journals
    # one window_budget (the planner's decode/prefill token split) and one
    # prefill_chunk_sched per chunk it granted
    budgets = [e for e in events if e["type"] == "window_budget"]
    chunks = [e for e in events if e["type"] == "prefill_chunk_sched"]
    occupancy = {
        "windows": len(windows),
        "active_mean": round(sum(active) / len(active), 2) if active else 0.0,
        "active_max": max(active) if active else 0,
        "mixed_windows": len(budgets),
        "prefill_chunks": len(chunks),
        "prefill_chunk_tokens": sum(int(e.get("tokens", 0)) for e in chunks),
        "rows_done": sum(int(e.get("done", 0)) for e in closes),
        "resets": sum(1 for e in events if e["type"] == "reset"),
        "preemptions": sum(1 for e in events if e["type"] == "preempt"),
        "sheds": sum(1 for e in events if e["type"] == "shed"),
        "deadline_expiries": sum(
            1 for e in events if e["type"] == "deadline"
        ),
        "journal_span_ms": round(
            (events[-1]["t"] - t0) * 1e3, 3
        ) if events else 0.0,
    }
    return {
        "schema_version": SCHEMA_VERSION,
        "requests": timelines,
        "occupancy": occupancy,
    }


def render_ascii(view: Dict, meta: Optional[Dict] = None) -> str:
    lines: List[str] = []
    if meta:
        lines.append(
            f"incident {meta.get('id', '?')}  trigger={meta.get('trigger')}"
            f"  ts={meta.get('ts')}"
        )
        lines.append("")
    for rid, tl in view["requests"].items():
        status = "complete" if tl["complete"] else "INCOMPLETE"
        lines.append(
            f"request {rid}  [{status}  span={tl['span_ms']:.1f}ms"
            f"  resets_survived={tl['resets_survived']}]"
        )
        for r in tl["events"]:
            attrs = " ".join(f"{k}={v}" for k, v in r["attrs"].items())
            lines.append(
                f"  +{r['t_ms']:>10.3f}ms  (Δ{r['dt_ms']:>9.3f})  "
                f"{r['type']:<18} {attrs}"
            )
        lines.append("")
    occ = view["occupancy"]
    lines.append("scheduler occupancy")
    lines.append(
        f"  windows={occ['windows']}  active mean={occ['active_mean']}"
        f" max={occ['active_max']}  rows done={occ['rows_done']}"
    )
    if occ.get("mixed_windows"):
        lines.append(
            f"  mixed windows={occ['mixed_windows']}  prefill chunks="
            f"{occ['prefill_chunks']}  chunk tokens="
            f"{occ['prefill_chunk_tokens']}"
        )
    lines.append(
        f"  resets={occ['resets']}  preemptions={occ['preemptions']}"
        f"  sheds={occ['sheds']}  deadline expiries="
        f"{occ['deadline_expiries']}  journal span="
        f"{occ['journal_span_ms']:.1f}ms"
    )
    return "\n".join(lines)


def _load_obs_module(name: str):
    """Load an obs/ module DIRECTLY by file path: importing the package
    would execute ``rag_llm_k8s_tpu.obs.__init__`` (which pulls tracing →
    jax), and flightview must run on a laptop holding nothing but the
    bundle. goodput.py and shadow.py are stdlib-only by contract."""
    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), os.pardir,
        "rag_llm_k8s_tpu", "obs", f"{name}.py",
    )
    spec = importlib.util.spec_from_file_location(f"_flightview_{name}", path)
    if spec is None or spec.loader is None:
        raise SystemExit(f"flightview: cannot load {name} module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_goodput_module():
    return _load_obs_module("goodput")


def build_goodput_report(events: List[Dict],
                         chip_hour_usd: float = 0.0) -> Dict:
    """The offline half of the same-report contract: rebuild the ledger
    state from ``goodput_window``/``complete`` events and render with the
    exact function ``GET /debug/goodput`` uses live."""
    gp = _load_goodput_module()
    return gp.render_report(
        gp.state_from_events(events), chip_hour_usd=chip_hour_usd
    )


def _load_sim_module(name: str):
    """Load a sim/ module by file path — same laptop contract as
    ``_load_obs_module`` (the modules are stdlib-only by SIM-PURITY and
    load their own siblings by path)."""
    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), os.pardir,
        "rag_llm_k8s_tpu", "sim", f"{name}.py",
    )
    spec = importlib.util.spec_from_file_location(f"_flightview_{name}", path)
    if spec is None or spec.loader is None:
        raise SystemExit(f"flightview: cannot load {name} module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def build_replay_diff(events_a: List[Dict], events_b: List[Dict]) -> Dict:
    """Decision-stream comparison of two journals (recorded vs replayed
    or simulated) — sim/replay.py's ``diff_journals`` payload."""
    rp = _load_sim_module("replay")
    return rp.diff_journals(events_a, events_b)


def render_replay_diff_ascii(diff: Dict, name_a: str, name_b: str) -> str:
    lines = [
        "replay diff  (A = recorded reference, B = replay/simulation)",
        f"  A: {name_a}",
        f"  B: {name_b}",
        f"  decision streams: identical={diff['identical']}"
        f"  ({diff['decisions'][0]} vs {diff['decisions'][1]} decisions)",
    ]
    fd = diff.get("first_divergence")
    if fd is not None:
        lines.append(f"  first divergent decision (index {fd['index']}):")
        lines.append(f"    A: {json.dumps(fd['a'], sort_keys=True)}")
        lines.append(f"    B: {json.dumps(fd['b'], sort_keys=True)}")
    deltas = {
        t: v for t, v in diff["event_counts"].items() if v["delta"] != 0
    }
    lines.append("  event counts (A / B / delta):")
    for t, v in diff["event_counts"].items():
        mark = "  <-- " if v["delta"] else ""
        lines.append(
            f"    {t:<20} {v['a']:>6} {v['b']:>6} {v['delta']:>+5}{mark}"
        )
    if not deltas:
        lines.append("    (no count deltas)")
    occ = diff["occupancy"]
    lines.append(
        f"  occupancy: windows {occ['a']['windows']} vs "
        f"{occ['b']['windows']};  mean active rows "
        f"{occ['a']['mean_active']} vs {occ['b']['mean_active']} "
        f"(delta {occ['mean_active_delta']:+})"
    )
    rd = diff["requests_diverged"]
    if rd:
        head = ", ".join(str(r) for r in rd[:16])
        more = f" (+{len(rd) - 16} more)" if len(rd) > 16 else ""
        lines.append(f"  requests whose decision chains diverge: {head}{more}")
    else:
        lines.append("  per-request decision chains: all identical")
    return "\n".join(lines)


def build_restore_report(path: str) -> Dict:
    """The warm-restart post-mortem (``--restore-report``): per WAL epoch,
    what that incarnation did, what it left in flight at death, and what
    the next incarnation's restore pass did about it (resumed /
    rehydrated / skipped) — sim/replay.py's ``build_restore_report`` over
    ``obs/flight.py``'s ``scan_wal``. ``path`` may be a WAL *directory*
    (the usual case: copied off the pod's PVC) or a single journal/bundle
    file (rendered as one epoch)."""
    rp = _load_sim_module("replay")
    if os.path.isdir(path):
        fl = _load_obs_module("flight")
        epochs = fl.scan_wal(path)
        if not epochs:
            raise SystemExit(
                f"flightview: no WAL segments (wal_*.jsonl) under {path}"
            )
    else:
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError) as e:
            raise SystemExit(f"flightview: cannot read {path}: {e}")
        epochs = {0: load_events(doc)}
    return rp.build_restore_report(epochs)


def render_restore_ascii(report: Dict) -> str:
    lines = ["restore report  (one section per WAL epoch = one "
             "process incarnation)"]
    for ep in report["epochs"]:
        lines.append("")
        lines.append(
            f"epoch {ep['epoch']}  events={ep['events']}"
            f"  arrivals={ep['arrivals']}  completes={ep['completes']}"
        )
        for d in ep["drain"]:
            attrs = " ".join(
                f"{k}={v}" for k, v in d.items() if k != "phase"
            )
            lines.append(f"  drain {d.get('phase'):<9} {attrs}")
        inflight = ep["inflight_at_end"]
        if inflight:
            lines.append(
                f"  in flight at death ({len(inflight)}):"
            )
            for r in inflight:
                syn = "  [synthetic prompt]" if r["synthetic_prompt"] else ""
                lines.append(
                    f"    rid={r['rid']:<6} prompt_len={r['prompt_len']:<6}"
                    f" emitted={r['n_emitted']}{syn}"
                )
        else:
            lines.append("  in flight at death: none (clean exit)")
        if ep["restored"]:
            lines.append(f"  resumed here ({len(ep['restored'])}):")
            for r in ep["restored"]:
                # the restore event precedes the resumed submit, so the
                # NEW rid may be unknown (None) — the original identity
                # is the meaningful one
                lines.append(
                    f"    epoch {r['orig_epoch']} rid={r['orig_rid']}"
                    f"  folded {r['n_emitted']} tokens"
                )
        if ep["rehydrated"]:
            toks = sum(r["tokens"] for r in ep["rehydrated"])
            lines.append(
                f"  cache rehydrated: {len(ep['rehydrated'])} segments,"
                f" {toks} tokens pre-staged"
            )
        if ep["skipped"]:
            lines.append(f"  skipped ({len(ep['skipped'])}):")
            for r in ep["skipped"]:
                lines.append(
                    f"    orig_rid={r['orig_rid']}  reason={r['reason']}"
                )
    return "\n".join(lines)


def build_quality_report(events: List[Dict]) -> Dict:
    """The offline half of the quality same-report contract: rebuild the
    auditor state from ``shadow_audit`` events and render with the exact
    function ``GET /debug/quality`` uses live (obs/shadow.py)."""
    sh = _load_obs_module("shadow")
    return sh.render_report(sh.state_from_events(events))


def build_tenant_report(events: List[Dict],
                        chip_hour_usd: float = 0.0) -> Dict:
    """The offline half of the tenant same-report contract: fold the
    journal's arrival/admit/complete/shed/shadow_audit events through the
    exact renderer ``GET /debug/tenants`` serves live (obs/tenants.py,
    stdlib-only by contract) — the two reports are byte-identical over
    the same events."""
    tn = _load_obs_module("tenants")
    return tn.render_report(
        tn.state_from_events(events), chip_hour_usd=chip_hour_usd
    )


def render_tenant_ascii(report: Dict) -> str:
    tot = report["totals"]
    lines = [
        "tenant attribution report",
        f"  events={report['events']}  wall={report['wall_s']:.3f}s"
        f"  tenants={tot['tenants']}",
        f"  totals: arrivals={tot['arrivals']}  admitted={tot['admitted']}"
        f"  completed={tot['completed']}  sheds={tot['sheds']}"
        f"  tokens={tot['tokens']}  chip_s={tot['chip_s']:.4f}"
        f"  cost_usd={tot['cost_usd']:.6f}",
        "  per tenant (sorted by chip-seconds):",
    ]
    for row in report["tenants"]:
        lines.append(
            f"    {row['tenant']:<16} arr={row['arrivals']:<5}"
            f" done={row['completed']:<5} shed={row['sheds']:<4}"
            f" tokens={row['tokens']:<7} chip_s={row['chip_s']:<10.4f}"
            f" share={row['chip_share']:.4f}"
            f" cost={row['cost_usd']:.6f}"
            f" tok/chip_s={row['tokens_per_chip_s']}"
        )
        if row["audits"]:
            lines.append(
                f"      audits={row['audits']}  diverged={row['diverged']}"
            )
    return "\n".join(lines)


def render_quality_ascii(report: Dict) -> str:
    a = report["audits"]
    lines = [
        "shadow quality report",
        f"  audits: clean={a['clean']}  diverged={a['diverged']}"
        f"  skipped={a['skipped']}  failed={a['failed']}"
        f"  divergence_rate={report['divergence_rate']:.6f}",
        f"  tokens compared: {report['tokens_compared']}",
    ]
    if report["skips"]:
        lines.append("  skips: " + "  ".join(
            f"{k}={v}" for k, v in sorted(report["skips"].items())
        ))
    lines.append("  attribution (audits per active approximation):")
    for approx, v in report["attribution"].items():
        lines.append(
            f"    {approx:<16} clean={v['clean']:<6} diverged={v['diverged']}"
        )
    le = report["logit_err"]
    lines.append(
        f"  logit_err: p50={le['p50']}  p99={le['p99']}  max={le['max']}"
    )
    fd = report["first_divergence_token"]
    lines.append(f"  first divergence token: p50={fd['p50']}")
    lines.append("  logit_err histogram:")
    for lbl, n in le["hist"].items():
        if n:
            lines.append(f"    {lbl:<10} {n}")
    return "\n".join(lines)


def render_goodput_ascii(report: Dict) -> str:
    lines = [
        "goodput report",
        f"  wall={report['wall_s']:.3f}s  busy={report['busy_s']:.3f}s"
        f"  idle={report['idle_s']:.3f}s  busy_frac={report['busy_frac']:.3f}",
        "  chip-time attribution (frac of busy; idle of wall):",
    ]
    for cat, v in report["categories"].items():
        lines.append(
            f"    {cat:<16} {v['chip_s']:>10.4f}s  frac={v['frac']:.4f}"
        )
    lines.append("  executables (roofline):")
    for kind, v in report["kinds"].items():
        lines.append(
            f"    {kind:<11} windows={v['windows']:<5} busy={v['busy_s']:.4f}s"
            f"  tokens={v['tokens']:<7} mfu={v['mfu']:.5f}"
            f"  bw={v['bw_util']:.5f}  bound={v['bound']}"
        )
    cost = report["cost"]
    pq = cost["per_query_chip_ms"]
    lines.append(
        f"  cost: chip_hour_usd={cost['chip_hour_usd']}"
        f"  wall_usd={cost['wall_usd']}"
        f"  tokens_per_usd={cost['tokens_per_usd']}"
    )
    lines.append(
        f"  per-query chip_ms: p50={pq['p50']}  p95={pq['p95']}  n={pq['n']}"
    )
    if "per_query_usd" in cost:
        pu = cost["per_query_usd"]
        lines.append(
            f"  per-query usd:     p50={pu['p50']}  p95={pu['p95']}"
        )
    cons = report["conservation"]
    lines.append(
        f"  conservation: attributed={cons['attributed_s']:.4f}s"
        f"  busy={cons['busy_s']:.4f}s  ratio={cons['ratio']:.4f}"
    )
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("bundle", help="incident bundle / journal dump (JSON)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the structured view instead of ASCII")
    ap.add_argument("--request", type=int, default=None,
                    help="render only this request id's lifecycle")
    ap.add_argument("--goodput", action="store_true",
                    help="render the goodput/cost report rebuilt from the "
                         "journal's goodput_window events instead of the "
                         "lifecycle view")
    ap.add_argument("--quality", action="store_true",
                    help="render the shadow-audit quality report rebuilt "
                         "from the journal's shadow_audit events instead "
                         "of the lifecycle view")
    ap.add_argument("--tenants", action="store_true",
                    help="render the per-tenant attribution report rebuilt "
                         "from the journal's arrival/complete/shed/"
                         "shadow_audit events instead of the lifecycle view")
    ap.add_argument("--chip-hour-usd", type=float, default=0.0,
                    help="chip rental price for the --goodput/--tenants "
                         "cost figures (defaults to 0: attribution only, "
                         "no dollars)")
    ap.add_argument("--replay-diff", metavar="OTHER", default=None,
                    help="compare BUNDLE's scheduler decision stream "
                         "against OTHER's (a replayed or simulated "
                         "journal): first divergence, per-event-type "
                         "count deltas, occupancy deltas")
    ap.add_argument("--restore-report", action="store_true",
                    help="render the warm-restart post-mortem: per WAL "
                         "epoch, what died in flight and what the next "
                         "incarnation resumed/rehydrated/skipped. BUNDLE "
                         "may be a WAL directory (wal_*.jsonl) or a "
                         "journal file")
    args = ap.parse_args(argv)
    if args.restore_report:
        # dispatched before the generic json.load: the input is usually a
        # WAL *directory*, not a bundle file
        report = build_restore_report(args.bundle)
        if args.as_json:
            print(json.dumps(report, indent=1))
        else:
            print(render_restore_ascii(report))
        return 0
    try:
        with open(args.bundle) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        print(f"flightview: cannot read {args.bundle}: {e}", file=sys.stderr)
        return 2
    events = load_events(doc)
    if args.replay_diff is not None:
        try:
            with open(args.replay_diff) as f:
                doc_b = json.load(f)
        except (OSError, ValueError) as e:
            print(f"flightview: cannot read {args.replay_diff}: {e}",
                  file=sys.stderr)
            return 2
        diff = build_replay_diff(events, load_events(doc_b))
        if args.as_json:
            print(json.dumps(diff, indent=1))
        else:
            print(render_replay_diff_ascii(
                diff, args.bundle, args.replay_diff
            ))
        return 0 if diff["identical"] else 1
    if args.quality:
        report = build_quality_report(events)
        if args.as_json:
            print(json.dumps(report, indent=1))
        else:
            print(render_quality_ascii(report))
        return 0
    if args.tenants:
        report = build_tenant_report(
            events, chip_hour_usd=args.chip_hour_usd
        )
        if args.as_json:
            print(json.dumps(report, indent=1))
        else:
            print(render_tenant_ascii(report))
        return 0
    if args.goodput:
        report = build_goodput_report(
            events, chip_hour_usd=args.chip_hour_usd
        )
        if args.as_json:
            print(json.dumps(report, indent=1))
        else:
            print(render_goodput_ascii(report))
        return 0
    view = build_view(events, request_id=args.request)
    if args.as_json:
        print(json.dumps(view, indent=1))
    else:
        meta = doc if isinstance(doc, dict) and "trigger" in doc else None
        print(render_ascii(view, meta))
    return 0


if __name__ == "__main__":
    sys.exit(main())
