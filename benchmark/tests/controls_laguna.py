#!/usr/bin/env python3
"""The readings ``references/laguna.py``'s limits were set from, on the chip:

    python3 benchmark/tests/controls_laguna.py [--cell laguna-s-ep16.closed8] \
        [--audits 200] [--seed N] [--trace 0|1] [--ep-rank R] [--controls a,b]

The walk is ``controls_dots_vlm.py``'s own (``run_cell``: the cell through
``run.py``'s ``main``, then every distinct finished request judged sound and
under each control, the program's counters in the window beside them, and the
float32 reference's routing by expert-parallel rank: balanced is
``num_experts_per_tok`` x 16 / 256 = 0.625 assignments a token-layer); this
file gives it this family's cell and its controls (``references/laguna.py
CONTROLS``: every sliding layer run as a full one, the per-head gate removed,
every matmul's operands rounded to fp8). ``--ep-rank`` serves another rank's
share than the configuration's, to read a rank before the file names it.

Not a pytest file; it needs the chip (``--allow-cpu-rehearsal`` walks it at
toy sizes) and exits 2 without one.
"""

import argparse
import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def main() -> int:
    spec = importlib.util.spec_from_file_location("controls_dots_vlm", os.path.join(HERE, "controls_dots_vlm.py"))
    walk = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(walk)
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="laguna-s-ep16.closed8")
    ap.add_argument("--audits", type=int, default=200)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--ep-rank", type=int, default=None)
    ap.add_argument("--controls", default="sliding_as_full,no_gate,fp8_matmuls",
                    help="which of references/laguna.py CONTROLS to compute")
    ap.add_argument("--allow-cpu-rehearsal", action="store_true", help="the walk at toy sizes")
    ap.add_argument("--seed", type=int, default=2**31 + 401)
    return walk.run_cell(ap.parse_args())


if __name__ == "__main__":
    sys.exit(main())
