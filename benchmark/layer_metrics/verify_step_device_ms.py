"""Device time of one speculative verify step: self time under the ``verify``
scope (the while-loop of engine/engine.py ``_make_gen_spec``: propose, one
forward over the draft, accept) over the verify steps in the same slice (the
passes of the loop: the executions of the operations traced directly in its
body). ``lib/phases.py``."""

from benchmark.lib import phases


def read(ctx):
    return phases.step_ms(phases.of(ctx), "verify")
