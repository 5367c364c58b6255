#!/usr/bin/env python3
"""The readings ``references/nemotron_h.py``'s limits were set from, on the chip:

    python3 benchmark/tests/controls_nemotron_h.py [--cell nemotron-3-super-ep4.solo] \
        [--audits 12] [--seed N] [--trace 0|1] [--controls a,b] [--ep-rank R]

The walk is ``controls_kimi_linear.py``'s (``controls_dots_vlm.py run_cell``:
the cell through ``run.py``'s ``main``, then every distinct finished request
judged sound and under each control, the program's ``moe`` counters in the
window beside them, the float32 reference's routing by rank, 5.5 assignments
a token-layer when balanced, where ``--ep-rank`` serves another rank's share
than the file's; and the line of the decay's percentiles: here ``exp(a)``, a
head's decay a position, over the Mamba-2 layers of the audited requests).
This file gives it this family's cell and its controls
(``references/nemotron_h.py CONTROLS``: the state kept in bf16; every matmul
rounded to fp8; one RMS over all the mixer's channels; the norm in front of
the gate; every head reading group 0's B and C; a relu that is not squared;
the routed scaling left out; the weights taken from score plus bias).

Not a pytest file; it needs the chip (``--allow-cpu-rehearsal`` walks it at
toy sizes) and exits 2 without one.
"""

import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "nemotron-3-super-ep4.solo"
CONTROLS = ("bf16_state,fp8_matmuls,ungrouped_norm,norm_before_gate,one_group_bc,relu_not_squared,"
            "scaling_one,weight_from_biased_score")


def main() -> int:
    spec = importlib.util.spec_from_file_location("controls_kimi_linear", os.path.join(HERE, "controls_kimi_linear.py"))
    walk = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(walk)
    walk.CONTROLS = CONTROLS  # its ``--controls`` default
    given = {a.split("=")[0] for a in sys.argv[1:]}
    if "--cell" not in given:
        sys.argv += ["--cell", CELL]
    return walk.main()


if __name__ == "__main__":
    sys.exit(main())
