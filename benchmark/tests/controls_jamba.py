#!/usr/bin/env python3
"""The readings ``references/jamba.py``'s limits were set from, on the chip:

    python3 benchmark/tests/controls_jamba.py [--cell jamba2-3b.closed8] \
        [--audits 24] [--seed N] [--trace 0|1] [--controls a,b] [--solo 2]

Runs the cell itself (``run.py``'s own ``main``: the server, the window, the
audit) and judges ``--audits`` of ITS OWN finished requests, not four: beside
what ``correct`` compares (``sound``) the plain reference is computed again
under each of ``references/jamba.py CONTROLS`` (the state, or the
convolution's history, not handed from prefill to decode; pads run through
the state layers unmasked; the inner norms left out; the state kept in bf16;
the softplus dropped; the attention layers made to see a window of 512; every
matmul's operands rounded to fp8) and set against the same exact-path scores.
The last line, after ``run.py``'s result line, is one JSON object: ``sound``
and one entry a control (``controls_dots_vlm.py readings``: per audited
request the reference's half gap and the exact path's logit error against it,
``moved``: the control against the sound reference, ``fails``: how many
requests a limit refuses), and what the program counted in the window.

``--solo N`` adds ``solo``: the cell's batches of eight do not speculate, so
no answer of the window goes through the verify step and ``Family.commit``.
After the audit, N sampled prompts are therefore served ALONE by the engine
that served the window (batch 1: the speculative program; ``speculative`` is
held at ``prompt_lookup`` for the calls, since ``auto`` leaves the verify loop
while measured acceptance is low), and each is judged as the others are: the
exact path against the served stream and against the plain reference, with the
verify steps, the tokens a step emitted and what ``commit`` was told.

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

COUNTED = ("prefill_tokens_computed", "prefill_tokens_bucketed", "decode_slots_streamed", "decode_slots_allocated",
           "ssm_positions_scanned", "ssm_state_updates", "verify_positions_fed", "verify_positions_kept")


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main() -> int:
    from benchmark.lib import serve, stats

    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="jamba2-3b.closed8")
    ap.add_argument("--audits", type=int, default=24)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--controls", default=None, help="which of references/jamba.py CONTROLS to compute (all)")
    ap.add_argument("--solo", type=int, default=0, help="also serve this many sampled prompts alone, speculating")
    ap.add_argument("--allow-cpu-rehearsal", action="store_true", help="the walk at toy sizes")
    ap.add_argument("--seed", type=int, default=2**31 + 401)
    args = ap.parse_args()

    walk = _load("controls_dots_vlm", os.path.join(HERE, "controls_dots_vlm.py"))  # readings(), GROUP
    run = _load("benchmark_run", os.path.join(BENCH, "run.py"))
    run.N_AUDITS = args.audits
    load_config, load_reference, Served = serve.load_config, serve.load_reference, serve.Served
    seen = {"exact": [], "sample": [], "refs": {}, "cfg": None}

    class Tapped(Served):  # run.py's own server, kept in sight for --solo
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
            self.controls = tuple(c for c in (args.controls or ",".join(real.CONTROLS)).split(",") if c)

        def score(self, params, cfg, sample, device):
            keys = [(tuple(p), tuple(e)) for p, e in sample]
            first = {k: i for i, k in reversed(list(enumerate(keys)))}
            distinct = [sample[i] for i in sorted(first.values())]
            place = {keys[i]: n for n, i in enumerate(sorted(first.values()))}
            seen["sample"], seen["distinct"], seen["controls"] = sample, [place[k] for k in keys], self.controls
            for name in ("",) + self.controls:
                seen["refs"][name] = [
                    r for i in range(0, len(distinct), walk.GROUP)
                    for r in self.real.score(params, cfg, distinct[i:i + walk.GROUP], device, control=name)]
            if args.solo:
                seen["solo"] = [self.alone(params, cfg, p, device) for p, _ in distinct[:args.solo]]
            return [seen["refs"][""][n] for n in seen["distinct"]]

        def alone(self, params, cfg, prompt, device) -> dict:
            engine = seen["engine"]
            st, fam = engine.stats, engine.stats.family_counters
            before = (st.spec_verify_steps, st.spec_emitted_tokens,
                      fam.get("verify_positions_fed", 0), fam.get("verify_positions_kept", 0))
            auto = engine.engine_config  # "auto" skips the verify loop while measured acceptance is low
            engine.engine_config = dataclasses.replace(auto, speculative="prompt_lookup")
            try:
                answer = engine.generate([list(prompt)])[0]
            finally:
                engine.engine_config = auto
            exact = engine.score_exact(list(prompt), answer)
            ref = self.real.score(params, cfg, [(prompt, answer)], device)[0]
            steps, emitted = st.spec_verify_steps - before[0], st.spec_emitted_tokens - before[1]
            return {"prompt_tokens": len(prompt), "answer_tokens": len(answer), "verify_steps": steps,
                    "verify_emitted": emitted, "tokens_per_verify": round(emitted / max(steps, 1), 3),
                    "commit_fed": fam.get("verify_positions_fed", 0) - before[2],
                    "commit_kept": fam.get("verify_positions_kept", 0) - before[3],
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
    line = {"cell": args.cell, "audits": len(seen["sample"]), "distinct": len(sound),
            "half_gap_tolerance": tols[0], "logit_tolerance": tols[1], "prompt_tokens": prompts,
            "sound": walk.readings(stats, exact, sound, None, tols)}
    for name in seen["controls"]:
        line[name] = walk.readings(stats, exact, seen["refs"][name], sound, tols)
    if "solo" in seen:
        line["solo"] = seen["solo"]
    if len(scrapes) >= 2:  # what the program itself counted in the window
        line["counted_in_window"] = {n: stats.delta(scrapes[-2], scrapes[-1], "tpu_rag_engine_" + n) for n in COUNTED}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
