#!/usr/bin/env python3
"""The readings ``references/kimi_linear.py``'s limits were set from, on the chip:

    python3 benchmark/tests/controls_kimi_linear.py [--cell kimi-linear-ep16.solo] \
        [--audits 12] [--seed N] [--trace 0|1] [--controls a,b] [--ep-rank R]

The walk is ``controls_dots_vlm.py``'s own (``run_cell``: the cell through
``run.py``'s ``main``, then every distinct finished request judged sound and
under each control, the program's ``moe`` counters in the window beside them,
and the float32 reference's routing: for each of the ``ep_size`` ranks the
assignments a token-layer sends to that rank's experts, 0.5 when balanced:
``--ep-rank`` serves another rank's share than the file's, to read it before
the file names it); this file gives it this family's cell and its controls
(``references/kimi_linear.py CONTROLS``: no decay; one decay a head; beta 1;
q and k not L2-normed; the state kept in bf16; the taps reversed; the 64-wide
slices rotated; the weights taken from score plus bias; the chunk form with a
clamped ``1 / exp(G)``; ``commit`` told one position fewer; every matmul
rounded to fp8), and adds one
line: the 5th, 50th and 95th percentile of ``alpha = exp(g)`` over the linear
layers of the audited requests (the smallest 5th, the median of the 50ths,
the largest 95th), as the configuration file's ``assumed`` states them.

Not a pytest file; it needs the chip (``--allow-cpu-rehearsal`` walks it at
toy sizes) and exits 2 without one.
"""

import argparse
import importlib.util
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CONTROLS = ("no_decay,scalar_decay,beta_one,no_l2norm,bf16_state,taps_reversed,rotated,bias_in_weights,"
            "clamped_inverse,commit_short,fp8_matmuls")


def main() -> int:
    spec = importlib.util.spec_from_file_location("controls_dots_vlm", os.path.join(HERE, "controls_dots_vlm.py"))
    walk = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(walk)
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="kimi-linear-ep16.solo")
    ap.add_argument("--audits", type=int, default=12)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--ep-rank", type=int, default=None)
    ap.add_argument("--controls", default=CONTROLS, help="which of references/kimi_linear.py CONTROLS to compute")
    ap.add_argument("--allow-cpu-rehearsal", action="store_true", help="the walk at toy sizes")
    ap.add_argument("--seed", type=int, default=2**31 + 401)
    args = ap.parse_args()

    from benchmark.lib import serve

    load_reference, alphas = serve.load_reference, []

    def load(*a, **kw):  # the sound reference also says what decay the draw produces
        ref = load_reference(*a, **kw)
        score = ref.score
        ref.score = lambda *args, **kwargs: score(
            *args, **(kwargs if kwargs.get("control") else dict(kwargs, alpha_log=alphas)))
        return ref

    serve.load_reference = load
    rc = walk.run_cell(args)
    if alphas:
        p5, p50, p95 = zip(*(a["alpha_p5_p50_p95"] for a in alphas))
        print(json.dumps({"alpha": {"layer_sequences": len(alphas), "p5_min": min(p5),
                                    "p50_median": statistics.median(p50), "p95_max": max(p95)}}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
