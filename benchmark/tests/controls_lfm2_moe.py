#!/usr/bin/env python3
"""The readings ``references/lfm2_moe.py``'s limits were set from, on the chip:

    python3 benchmark/tests/controls_lfm2_moe.py [--cell lfm2-24b-a2b-pp4.solo] \
        [--audits 12] [--seed N] [--trace 0|1] [--controls a,b]

The walk is ``controls_dots_vlm.py``'s own (``run_cell``: the cell through
``run.py``'s ``main``, then every distinct finished request judged sound and
under each control, the program's ``moe`` counters in the window beside them,
and the float32 reference's routing: at ``ep_size`` 1 one rank, which must
read ``num_experts_per_tok`` = 4.0 assignments a token-layer, nothing dropped
and nothing left to another chip); this file gives it this family's cell and
its controls (``references/lfm2_moe.py CONTROLS``: the two kept inputs not
handed from prefill to decode; pads run through the conv layers unmasked; the
taps reversed; q and k not normed; the weights taken from score plus bias; the
chosen scores not normalised; the experts' matmuls, or every matmul, rounded
to fp8). A run with all eight takes ~13 minutes of a chip.

Not a pytest file; it needs the chip (``--allow-cpu-rehearsal`` walks it at
toy sizes) and exits 2 without one.
"""

import argparse
import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CONTROLS = ("no_conv_handover,pads_unmasked,taps_reversed,no_qk_norm,bias_in_weights,unnormed_topk,"
            "fp8_experts,fp8_matmuls")


def main() -> int:
    spec = importlib.util.spec_from_file_location("controls_dots_vlm", os.path.join(HERE, "controls_dots_vlm.py"))
    walk = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(walk)
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="lfm2-24b-a2b-pp4.solo")
    ap.add_argument("--audits", type=int, default=12)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--controls", default=CONTROLS, help="which of references/lfm2_moe.py CONTROLS to compute")
    ap.add_argument("--allow-cpu-rehearsal", action="store_true", help="the walk at toy sizes")
    ap.add_argument("--seed", type=int, default=2**31 + 401)
    return walk.run_cell(ap.parse_args(namespace=argparse.Namespace(ep_rank=None)))


if __name__ == "__main__":
    sys.exit(main())
