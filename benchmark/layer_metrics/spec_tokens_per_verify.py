"""Tokens emitted per speculative verify step over the window (1.0 would be
vanilla decoding): ``engine_spec_emitted_tokens`` / ``engine_spec_verify_steps``."""

EMITTED = "tpu_rag_engine_spec_emitted_tokens"
STEPS = "tpu_rag_engine_spec_verify_steps"


def read(ctx):
    d = ctx["stats"].delta
    emitted, steps = d(ctx["before"], ctx["after"], EMITTED), d(ctx["before"], ctx["after"], STEPS)
    if emitted is None or not steps:
        return None
    return emitted / steps
