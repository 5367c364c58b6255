#!/usr/bin/env python3
"""The readings ``references/longcat_flash.py``'s limits were set from, on the chip:

    python3 benchmark/tests/controls_longcat_flash.py [--cell longcat-flash-ep32.closed8] \
        [--audits 200] [--seed N] [--trace 0|1] [--ep-rank R] [--controls a,b]

The walk is ``controls_dots_vlm.py``'s own (``run_cell``: the cell through
``run.py``'s ``main``, then every distinct finished request judged sound and
under each control, the program's counters in the window beside them); this
file gives it this family's cell, its controls
(``references/longcat_flash.py CONTROLS``) and a ``routing`` that knows the
router's outputs past the routed experts are zero-computation ones: per
expert-parallel rank the assignments a token-layer sends to that rank's 16
experts (balanced: ``moe_topk`` x 16 / 768 = 0.25), and the share of all
choices that fell on zero experts, over the tokens the program prefills and
over those it decodes (the float32 reference's routing of the audited
requests). ``--ep-rank`` serves another rank's share than the
configuration's, to read a rank before the file names it.

Not a pytest file; it needs the chip (``--allow-cpu-rehearsal`` walks it at
toy sizes) and exits 2 without one.
"""

import argparse
import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def routing(route_log, ep_size: int) -> dict:
    """Assignments a token-layer to each rank's experts, and to zero experts."""
    import numpy as np

    out = {}
    for mode in ("prefill", "decode"):
        chosen = sum(np.asarray(e[mode], np.float64) for e in route_log)
        token_layers = max(sum(e[mode + "_tokens"] for e in route_log), 1)
        n_routed = len(chosen) - int(route_log[0]["zero_experts"])
        by_rank = chosen[:n_routed].reshape(ep_size, -1).sum(1) / token_layers
        out[mode] = {"token_layers": int(token_layers), "by_rank": [round(float(x), 4) for x in by_rank],
                     "zero_share": round(float(chosen[n_routed:].sum() / max(chosen.sum(), 1)), 4)}
    return out


def main() -> int:
    spec = importlib.util.spec_from_file_location("controls_dots_vlm", os.path.join(HERE, "controls_dots_vlm.py"))
    walk = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(walk)
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="longcat-flash-ep32.closed8")
    ap.add_argument("--audits", type=int, default=200)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--ep-rank", type=int, default=None)
    ap.add_argument("--controls", default="fp8_matmuls,drop_zero_experts,fp8_dense_path,drop_second_sublayer,shared_plane,early_join,int8_matmuls",
                    help="which of references/longcat_flash.py CONTROLS to compute")
    ap.add_argument("--allow-cpu-rehearsal", action="store_true", help="the walk at toy sizes")
    ap.add_argument("--seed", type=int, default=2**31 + 401)
    walk.routing = routing
    return walk.run_cell(ap.parse_args())


if __name__ == "__main__":
    sys.exit(main())
