"""Of the query-key pairs the steps of the windowed flash prefill kernel
multiplied in the window's prefills, the share that was live: 100 x
``tpu_rag_engine_prefill_window_pairs_live`` /
``tpu_rag_engine_prefill_window_pairs_multiplied`` (one query head's pairs,
summed over the rows and over the sliding layers; both counted on the device
by the integer rule the kernel's own bounds follow, ``ops/attention.py
flash_window_pairs``, and fetched with each answer). A query block of 128
under a window of 512 has 128 x 512 live pairs (fewer on a prompt's first
blocks and behind its left pad); a walk over key blocks of 512 multiplies two
of them, 128 x 1024: about 48.5 on 3.9k-token prompts. One slice of 640 keys
a query block multiplies 128 x 640: 73.5 there (80 is the form's ceiling). A
count, not a time. None where the program has no such counters (another
family, or a program from before them) or the window no prefill through the
kernel."""

LIVE = "tpu_rag_engine_prefill_window_pairs_live"
MULTIPLIED = "tpu_rag_engine_prefill_window_pairs_multiplied"


def read(ctx):
    d = ctx["stats"].delta
    live = d(ctx["before"], ctx["after"], LIVE)
    multiplied = d(ctx["before"], ctx["after"], MULTIPLIED)
    if live is None or not multiplied:
        return None
    return 100.0 * live / multiplied
