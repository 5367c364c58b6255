"""Answers per generate call the engine made over the window
(``engine_generate_calls`` counts one for a fused solo answer and one for a
coalesced batch alike): the callers of a closed loop when every round is
coalesced into one batch, lower by each round that was split."""

CALLS = "tpu_rag_engine_generate_calls"


def read(ctx):
    calls = ctx["stats"].delta(ctx["before"], ctx["after"], CALLS)
    done = sum(1 for r in ctx["requests"] if r["status"] == 200)
    if not calls or not done:
        return None
    return done / calls
