"""Device time one prompt's prefill costs: self time of the operations traced
under the ``prefill`` scope (engine/engine.py), over the prefill rows in the
same slice of the same trace: the rows each generate program states in its
scope (``prefill/rows<N>``), by the layers of each pass that ran in the slice
(the executions of the operations of the layers' loop over
``num_hidden_layers``). ``lib/phases.py``."""

from benchmark.lib import phases


def read(ctx):
    ph = phases.of(ctx)
    return ph and phases.ms_per(ph["seconds"].get("prefill"), ph["prefill_rows"])
