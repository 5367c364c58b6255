#!/usr/bin/env python3
"""The readings ``references/phi4flash.py``'s limits were set from, on the chip:

    python3 benchmark/tests/controls_phi4flash.py [--cell phi4-mini-flash.solo-12chunk] \
        [--audits 6] [--seed N] [--trace 0|1] [--controls a,b] [--solo 1]

The walk is ``controls_jamba.py``'s own (the cell through ``run.py``'s
``main``, then every distinct finished request judged sound and under each
control of the family's reference, the program's counters in the window beside
them; ``--solo N`` serves N sampled prompts alone through the verify loop and
``Family.commit``, which ``speculative=auto`` leaves after the first answers of
a run); this file gives it this family's cell, its audits (a 12 k-token
sequence is ~25 s of float32 reference a control) and its counters. The
controls are ``references/phi4flash.py CONTROLS``: lambda = 0; the 128-wide
norm left out; the window layers unbounded; the cross layers reading the last
WINDOW layer's plane; the memory taken behind the gate, or one position back;
the state kept in bf16; pads run through the state layers; ``commit`` keeping
nothing; every matmul rounded to fp8.

Not a pytest file; it needs the chip (``--allow-cpu-rehearsal`` walks it at toy
sizes) and exits 2 without one.
"""

import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CELL, AUDITS = "phi4-mini-flash.solo-12chunk", "6"
COUNTED = ("decode_slots_streamed_window", "decode_slots_allocated_window", "prefill_window_pairs_multiplied",
           "prefill_window_pairs_live", "cross_positions_computed", "cross_positions_fed",
           "shared_plane_slots_streamed")


def main() -> int:
    spec = importlib.util.spec_from_file_location("controls_jamba", os.path.join(HERE, "controls_jamba.py"))
    walk = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(walk)
    walk.COUNTED += COUNTED
    given = {a.split("=")[0] for a in sys.argv[1:]}
    for flag, value in (("--cell", CELL), ("--audits", AUDITS)):
        if flag not in given:
            sys.argv += [flag, value]
    return walk.main()


if __name__ == "__main__":
    sys.exit(main())
