"""Softmax steps the block-window prefill kernel took a live query block:
``tpu_rag_engine_prefill_window_softmax_steps`` /
``tpu_rag_engine_prefill_window_query_blocks`` over the window (one head's
steps and blocks, summed over the rows and the layers; both counted on the
device by the integer rule the kernel's own bounds follow,
``ops/block_window.py window_summary_steps``, and fetched with each answer).
A step is one pass of scores, row max, ``exp`` and ``p v`` over a slice of
keys or summaries; every step after a block's first re-scales the block's
running sum and accumulator. The walk over key blocks of 512 and summary
blocks of 256 took about 4.7 on 17.4 k-byte prompts (8 at the last block of
the ninth window); a window taken in one step and the summaries behind it in
pieces of 512 take 1 to 3. A count, not a time. None where the program has no
such counters (another family, or a program from before them) or the window
no prefill through the kernel."""

STEPS = "tpu_rag_engine_prefill_window_softmax_steps"
BLOCKS = "tpu_rag_engine_prefill_window_query_blocks"


def read(ctx):
    d = ctx["stats"].delta
    steps = d(ctx["before"], ctx["after"], STEPS)
    blocks = d(ctx["before"], ctx["after"], BLOCKS)
    if steps is None or not blocks:
        return None
    return steps / blocks
