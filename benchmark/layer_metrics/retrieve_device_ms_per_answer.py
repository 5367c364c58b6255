"""Device time retrieval costs an answer: self time under the ``retrieve``
scope (the encoder's forward, the kNN kernel, and the prompt assembly that
runs on the device) over the requests whose ``retrieve`` span closed in the
same slice. ``lib/phases.py``."""

from benchmark.lib import phases


def read(ctx):
    ph = phases.of(ctx)
    return ph and phases.ms_per(ph["seconds"].get("retrieve"), ph["retrievals"])
