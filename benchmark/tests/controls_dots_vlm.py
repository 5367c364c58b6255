#!/usr/bin/env python3
"""The readings ``references/dots_vlm.py``'s limits were set from, on the chip:

    python3 benchmark/tests/controls_dots_vlm.py --cell dots-vlm1-ep16.closed8 \
        [--audits 16] [--seed N] [--trace 0|1] [--ep-rank R]
    python3 benchmark/tests/controls_dots_vlm.py [--gains 1.0,0.5,0.25] [--sequences 6]

``--cell`` runs the cell itself (``run.py``'s own ``main``: the server, the
window, the audit) and judges ``--audits`` of ITS OWN finished requests, not
four: beside what ``correct`` compares (``sound``) the plain reference is
computed again under each of ``references/dots_vlm.py CONTROLS`` and set
against the same exact-path scores. The last line, after ``run.py``'s result
line, is one JSON object:

- ``sound``, and one entry a control: per audited request the half gap of the
  reference and the logit error of the program's exact path against it (a
  faulty reference against a sound program: the program's own bf16 distance
  is in the reading); ``moved``: the control against the sound reference;
  ``fails``: how many of the requests a limit refuses, and in how many of the
  groups of four that one run audits at least one is refused;
- ``routing``: for each of the ``ep_size`` ranks the assignments a token sends
  to that rank's experts a MoE layer, over the tokens the program prefills and
  over those it decodes (the float32 reference's routing of the audited
  requests): 8 / ``ep_size`` when the router is balanced.

``--ep-rank`` serves another rank's share than the configuration's (to read
its sound distance before the file names it). Without ``--cell``: seeded
random-token prompts of the cell's length through the engine alone, for
each expert gain (``families/dots_vlm.py EXPERT_GAIN``), as PR 27 first read
them.

Not a pytest file; it needs the chip (the reference at 7168 wide is minutes on
a CPU) and exits 2 without one.
"""

import argparse
import importlib.util
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)
CONFIG = os.path.join(BENCH, "configs", "dots-vlm1-bf16-ep16-share.json")
GROUP = 4  # what one run audits (run.py N_AUDITS), and what the reference scores at a time


def readings(stats, exact, refs, sound, tols) -> dict:
    """One entry of the result: ``refs`` against the exact path (and against
    the ``sound`` reference, if they are a control's)."""
    import numpy as np

    half = [stats.half_gap_max(r) for r in refs]
    err = [stats.logit_err_max(x, r) for x, r in zip(exact, refs)]
    refused = [g > tols[0] or e > tols[1] for g, e in zip(half, err)]
    out = {"half_gap": [round(g, 5) for g in half], "logit_err": [round(e, 5) for e in err],
           "logit_err_mean": [round(float(np.mean(np.abs(x["chosen_logit"] - r["chosen_logit"]))), 5)
                              for x, r in zip(exact, refs)],
           "fails": {"requests": f"{sum(refused)} of {len(refused)}",
                     "runs_of_four": "{} of {}".format(
                         sum(any(refused[i:i + GROUP]) for i in range(0, len(refused), GROUP)),
                         -(-len(refused) // GROUP))}}
    if sound is not None:
        out["moved"] = [round(stats.logit_err_max(s, r), 5) for s, r in zip(sound, refs)]
    return out


def routing(route_log, ep_size: int) -> dict:
    """Assignments a token-layer sends to each rank, prefill and decode."""
    import numpy as np

    out = {}
    for mode in ("prefill", "decode"):
        chosen = sum(np.asarray(e[mode], np.float64) for e in route_log)
        token_layers = sum(e[mode + "_tokens"] for e in route_log)
        by_rank = chosen.reshape(ep_size, -1).sum(1) / max(token_layers, 1)
        out[mode] = {"token_layers": int(token_layers), "by_rank": [round(float(x), 4) for x in by_rank]}
    return out


def run_cell(args) -> int:
    from benchmark.lib import serve, stats

    spec = importlib.util.spec_from_file_location("benchmark_run", os.path.join(BENCH, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    run.N_AUDITS = args.audits
    load_config, load_reference = serve.load_config, serve.load_reference
    controls = tuple(c for c in args.controls.split(",") if c)
    seen = {"exact": [], "sample": [], "refs": {}, "route_log": [], "cfg": None}

    def config_of(path, *a, **kw):
        cfg, family = load_config(path, *a, **kw)
        if args.ep_rank is not None:
            cfg["ep_rank"] = args.ep_rank
        seen["cfg"] = cfg
        return cfg, family

    class Reference:
        """The family's reference, scoring four at a time (a float32 row is
        0.1 GB a layer) and once more under every control."""

        def __init__(self, real):
            self.real = real
            self.HALF_GAP_TOL, self.LOGIT_TOL, self.__file__ = real.HALF_GAP_TOL, real.LOGIT_TOL, real.__file__

        def score(self, params, cfg, sample, device):
            # a request asked twice was answered alike: score each (prompt, answer) once
            keys = [(tuple(p), tuple(e)) for p, e in sample]
            first = {k: i for i, k in reversed(list(enumerate(keys)))}
            distinct = [sample[i] for i in sorted(first.values())]
            place = {keys[i]: n for n, i in enumerate(sorted(first.values()))}
            seen["sample"], seen["distinct"] = sample, [place[k] for k in keys]
            for name in ("",) + controls:
                seen["refs"][name] = [
                    r for i in range(0, len(distinct), GROUP)
                    for r in self.real.score(params, cfg, distinct[i:i + GROUP], device, control=name,
                                             route_log=None if name else seen["route_log"])]
            return [seen["refs"][""][n] for n in seen["distinct"]]

    real_err, real_parse, scrapes = stats.logit_err_max, stats.parse_exposition, []

    def parse_exposition(text):  # run.py's last two scrapes stand around the window
        scrapes.append(real_parse(text))
        return scrapes[-1]

    def logit_err_max(exact, ref):  # run.py hands the exact path's scores over here, in order
        seen["exact"].append(exact)
        return real_err(exact, ref)

    serve.load_config = config_of
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
    line = {"cell": args.cell, "ep_rank": seen["cfg"].get("ep_rank", 0), "audits": len(seen["sample"]),
            "distinct": len(sound), "half_gap_tolerance": tols[0], "logit_tolerance": tols[1],
            "prompt_tokens": sorted(len(seen["sample"][seen["distinct"].index(n)][0])
                                    for n in range(len(sound))),
            "sound": readings(stats, exact, sound, None, tols)}
    for name in controls:
        line[name] = readings(stats, exact, seen["refs"][name], sound, tols)
    line["routing"] = routing(seen["route_log"], int(seen["cfg"]["ep_size"]))
    if len(scrapes) >= 2:  # what the program itself counted in the window
        line["counted_in_window"] = {
            k.replace("tpu_rag_engine_", ""): stats.delta(scrapes[-2], scrapes[-1], k)
            for k in sorted(scrapes[-1]) if k.startswith("tpu_rag_engine_moe_")}
    print(json.dumps(line), flush=True)
    return 0


def run_random_tokens(args) -> int:
    import jax
    import numpy as np

    from benchmark.lib import serve, stats
    from rag_llm_k8s_tpu.core.compile_cache import ensure_compile_cache
    from rag_llm_k8s_tpu.core.config import DTypePolicy, EngineConfig, MeshConfig, SamplingConfig
    from rag_llm_k8s_tpu.core.mesh import make_mesh
    from rag_llm_k8s_tpu.engine.engine import InferenceEngine

    ensure_compile_cache()
    cfg, family = serve.load_config(CONFIG)
    reference = serve.load_reference(cfg["model_type"])
    tols = (reference.HALF_GAP_TOL, reference.LOGIT_TOL)
    model, serving = family.model_config(cfg), cfg["serving"]
    device = jax.devices()[0]
    mesh = make_mesh(MeshConfig(dp=1, sp=1, tp=1), devices=[device])
    gains = [float(g) for g in args.gains.split(",") if g] or [family.EXPERT_GAIN]
    rng = np.random.default_rng(args.seed)
    prompts = [[int(t) for t in rng.integers(3, model.vocab_size, args.prompt_tokens + int(rng.integers(0, 200)))]
               for _ in range(args.sequences)]
    for gain in gains:
        t0 = time.monotonic()
        family.EXPERT_GAIN = gain
        params = family.make_params(model, DTypePolicy(), int(serving["weights_seed"]), "bf16", mesh,
                                    float(serving["recite_gain"]))
        engine = InferenceEngine(
            model, params, sampling=SamplingConfig(do_sample=False, max_new_tokens=150),
            engine_config=EngineConfig(prompt_buckets=(2048, 4096), attn_impl="pallas", speculative="off"),
            mesh=mesh)
        sample = [(p, engine.generate([p])[0]) for p in prompts]
        exact = [engine.score_exact(p, e) for p, e in sample]
        line = {"expert_gain": gain, "sequences": len(sample), "counters": dict(engine.stats.family_counters)}
        route_log, sound = [], None
        for name in ("",) + tuple(c for c in args.controls.split(",") if c):
            refs = [r for i in range(0, len(sample), GROUP)
                    for r in reference.score(engine.params, cfg, sample[i:i + GROUP], device, control=name,
                                             route_log=None if name else route_log)]
            line[name or "sound"] = readings(stats, exact, refs, sound, tols)
            sound = sound or refs
        line["routing"] = routing(route_log, int(cfg["ep_size"]))
        line["seconds"] = round(time.monotonic() - t0, 1)
        print(json.dumps(line), flush=True)
        del engine, params
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="")
    ap.add_argument("--audits", type=int, default=16)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--ep-rank", type=int, default=None)
    ap.add_argument("--controls", default="drop_weakest,int8_expert_inputs,int8_matmuls,fp8_matmuls",
                    help="which of references/dots_vlm.py CONTROLS to compute")
    ap.add_argument("--allow-cpu-rehearsal", action="store_true", help="the walk at toy sizes, for --cell")
    ap.add_argument("--gains", default="")
    ap.add_argument("--sequences", type=int, default=6)
    ap.add_argument("--prompt-tokens", type=int, default=3300)
    ap.add_argument("--seed", type=int, default=2**31 + 401)
    args = ap.parse_args()
    if args.cell:
        return run_cell(args)  # run.py says itself when there is no chip
    import jax

    if jax.devices()[0].platform != "tpu":
        print("controls: no TPU", file=sys.stderr)
        return 2
    return run_random_tokens(args)


if __name__ == "__main__":
    sys.exit(main())
