"""Device time of one vanilla decode step: self time under the ``decode``
scope (the while-loop of engine/engine.py, its sampler included) over the
steps in the same slice (the passes of the loop: the executions of the
operations traced directly in its body). ``lib/phases.py``."""

from benchmark.lib import phases


def read(ctx):
    return phases.step_ms(phases.of(ctx), "decode")
