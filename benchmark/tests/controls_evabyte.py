#!/usr/bin/env python3
"""The readings ``references/evabyte.py``'s limits were set from, on the chip:

    python3 benchmark/tests/controls_evabyte.py [--cell evabyte-pp4.closed8] \
        [--audits 48] [--seed N] [--trace 0|1] [--controls a,b] [--window-end 1900]

Runs the cell itself (``run.py``'s own ``main``: the server, the window, the
audit) and judges ``--audits`` of ITS OWN finished requests, not four: beside
what ``correct`` compares (``sound``) the plain reference is computed again
under each of ``references/evabyte.py CONTROLS`` (summaries dropped, ``mu`` and
``phi`` exchanged, the plain mean for the pooling, a summary of the query's own
window made visible, every matmul's operands rounded to fp8) and set against
the same exact-path scores. The last line, after ``run.py``'s result line, is
one JSON object: ``sound`` and one entry a control (``controls_dots_vlm.py
readings``: per audited request the reference's half gap and the exact path's
logit error against it, ``moved``: the control against the sound reference,
``fails``: how many requests a limit refuses), where in their window the
prompts end (``prompt_end_in_window``), and what the program counted in the
window (ring and summary slots fetched, chunks and windows closed).

``--window-end N`` adds ``window_end``: the cell's prompts end mid-window and
its batches of eight do not speculate, so no answer of the window crosses a
window's end and none goes through the verify step. After the audit one
sampled prompt, cut so that it ends N positions into a window, is therefore
served ALONE by the engine that served the window (batch 1: the speculative
program, whose verify steps ``Family.verify_span`` bounds; ``speculative`` is
held at ``prompt_lookup`` for the call, since ``auto`` leaves the verify loop
while measured acceptance is low), its answer runs
over the window's end (the ring wraps, chunks written by decode become
summaries later steps read), and it is judged as the others are: the exact
path against the served stream and against the plain reference.

Not a pytest file; it needs the chip (``--allow-cpu-rehearsal`` walks it at toy
sizes) and exits 2 without one.
"""

import argparse
import dataclasses
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main() -> int:
    from benchmark.lib import serve, stats

    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="evabyte-pp4.closed8")
    ap.add_argument("--audits", type=int, default=48)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--controls", default="no_summaries,swap_mu_phi,mean_pool,own_window_summaries,fp8_matmuls",
                    help="which of references/evabyte.py CONTROLS to compute")
    ap.add_argument("--window-end", type=int, default=0,
                    help="also serve one prompt alone that ends this many positions into a window")
    ap.add_argument("--allow-cpu-rehearsal", action="store_true", help="the walk at toy sizes")
    ap.add_argument("--seed", type=int, default=2**31 + 401)
    args = ap.parse_args()

    walk = _load("controls_dots_vlm", os.path.join(HERE, "controls_dots_vlm.py"))  # readings(), GROUP
    run = _load("benchmark_run", os.path.join(BENCH, "run.py"))
    run.N_AUDITS = args.audits
    controls = tuple(c for c in args.controls.split(",") if c)
    load_config, load_reference, Served = serve.load_config, serve.load_reference, serve.Served
    seen = {"exact": [], "sample": [], "refs": {}, "cfg": None}

    class Tapped(Served):  # run.py's own server, kept in sight for --window-end
        def __init__(self, service):
            seen["engine"] = service.engine
            super().__init__(service)

    def config_of(path, *a, **kw):
        cfg, family = load_config(path, *a, **kw)
        seen["cfg"] = cfg
        return cfg, family

    class Reference:
        """The family's reference, each distinct (prompt, answer) once, and
        once more under every control."""

        def __init__(self, real):
            self.real = real
            self.HALF_GAP_TOL, self.LOGIT_TOL, self.__file__ = real.HALF_GAP_TOL, real.LOGIT_TOL, real.__file__

        def score(self, params, cfg, sample, device):
            keys = [(tuple(p), tuple(e)) for p, e in sample]
            first = {k: i for i, k in reversed(list(enumerate(keys)))}
            distinct = [sample[i] for i in sorted(first.values())]
            place = {keys[i]: n for n, i in enumerate(sorted(first.values()))}
            seen["sample"], seen["distinct"] = sample, [place[k] for k in keys]
            for name in ("",) + controls:
                seen["refs"][name] = [
                    r for i in range(0, len(distinct), walk.GROUP)
                    for r in self.real.score(params, cfg, distinct[i:i + walk.GROUP], device, control=name)]
            if args.window_end:
                seen["window_end"] = self.across_a_windows_end(params, cfg, sample[0][0], device)
            return [seen["refs"][""][n] for n in seen["distinct"]]

        def across_a_windows_end(self, params, cfg, prompt, device) -> dict:
            window, engine = int(cfg["window_size"]), seen["engine"]
            n = (len(prompt) // window - 1) * window + args.window_end
            cut = list(prompt[:n])
            before = (engine.stats.spec_verify_steps, engine.stats.spec_emitted_tokens)
            auto = engine.engine_config  # "auto" skips the verify loop while measured acceptance is low
            engine.engine_config = dataclasses.replace(auto, speculative="prompt_lookup")
            try:
                answer = engine.generate([cut])[0]
            finally:
                engine.engine_config = auto
            exact = engine.score_exact(cut, answer)
            ref = self.real.score(params, cfg, [(cut, answer)], device)[0]
            return {"prompt_tokens": n, "prompt_end_in_window": n % window, "answer_tokens": len(answer),
                    "window_ends_at_step": window - n % window,
                    "verify_steps": engine.stats.spec_verify_steps - before[0],
                    "verify_emitted": engine.stats.spec_emitted_tokens - before[1],
                    "served_half_gap": round(stats.judge_audit(exact, answer), 5),
                    "half_gap": round(stats.half_gap_max(ref), 5), "logit_err": round(real_err(exact, ref), 5)}

    real_err, real_parse, scrapes = stats.logit_err_max, stats.parse_exposition, []

    def parse_exposition(text):  # run.py's last two scrapes stand around the window
        scrapes.append(real_parse(text))
        return scrapes[-1]

    def logit_err_max(exact, ref):  # run.py hands the exact path's scores over here, in order
        seen["exact"].append(exact)
        return real_err(exact, ref)

    serve.load_config, serve.Served = config_of, Tapped
    serve.load_reference = lambda *a, **kw: Reference(load_reference(*a, **kw))
    stats.logit_err_max, stats.parse_exposition = logit_err_max, parse_exposition
    sys.argv = [run.__file__, "--workload", args.cell, "--seed", str(args.seed),
                "--trace", str(args.trace)] + (["--seconds", str(args.seconds)] if args.seconds else []) \
        + (["--allow-cpu-rehearsal"] if args.allow_cpu_rehearsal else [])
    rc = run.main()
    stats.logit_err_max, stats.parse_exposition = real_err, real_parse
    if rc or not seen["refs"]:
        return rc or 1
    ref = load_reference(seen["cfg"]["model_type"])
    tols = (ref.HALF_GAP_TOL, ref.LOGIT_TOL)
    sound = seen["refs"][""]
    by_place = dict(zip(seen["distinct"], seen["exact"]))  # the exact path's score of each distinct request
    exact = [by_place[n] for n in range(len(sound))]
    prompts = sorted(len(seen["sample"][seen["distinct"].index(n)][0]) for n in range(len(sound)))
    window = int(seen["cfg"]["window_size"])
    line = {"cell": args.cell, "audits": len(seen["sample"]), "distinct": len(sound),
            "half_gap_tolerance": tols[0], "logit_tolerance": tols[1], "prompt_tokens": prompts,
            "prompt_end_in_window": [n % window for n in prompts],
            "sound": walk.readings(stats, exact, sound, None, tols)}
    for name in controls:
        line[name] = walk.readings(stats, exact, seen["refs"][name], sound, tols)
    if "window_end" in seen:
        line["window_end"] = seen["window_end"]
    if len(scrapes) >= 2:  # what the program itself counted in the window
        names = ("decode_ring_slots_fetched", "decode_summary_slots_fetched", "decode_slots_attended_positions",
                 "chunks_closed", "windows_closed")
        line["counted_in_window"] = {n: stats.delta(scrapes[-2], scrapes[-1], "tpu_rag_engine_" + n) for n in names}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
