"""The block-window family's decode kernel against its roofline: the least
time for its calls (bytes over HBM bandwidth, or operations over peak,
whichever is longer) over their time in the trace. The trace names the kernel
``ring_summary_decode_attention`` (``ops/attention.py``'s decode walk over the
family's joined plane) and gives its result type ``[rows, heads, 1,
head_dim]``: one call is one layer of one decode step for the whole batch.

Bytes: every row's LIVE slots, keys and values of every head: at position
``t`` the ``t % W + 1`` ring slots of its window and the ``(W / C) * (t // W)``
summaries of the windows before it (``t`` averaged over the prompts' real
lengths and the answer's steps); q and o are ``heads x head_dim`` a row and
negligible. Operations: 2 x head_dim multiply-adds a slot and head for the
scores and as many for the weighted sum. Whatever fetches dead slots beside
the live ones (a step of the walk is 128 slots) does not raise the share.

The executable's rows stand for prompts only where a whole batch rode it: a
round of callers that split is answered by a program built for eight whose
eighth row is one token long, and the share then over-reads by 8/7 (102 to
124 where the kernel was at 93). The trace cannot tell such a call from a
full one, so where the window dispatched any batch that had to be padded
(``rag_generate_dispatch_rows_total``: a count of rows that is no power of
two) there is nothing sound to read and the reader returns None."""

import re

KERNEL = "ring_summary_decode_attention"
DISPATCH_ROWS = "rag_generate_dispatch_rows_total"
_ROWS = re.compile(r'rows="(\d+)"')


def live_slots(prompt_tokens, new_tokens: int, window: int, chunk: int) -> float:
    """Ring slots + summaries a decode step reads for a row, averaged over
    the prompts and over the answer's steps."""
    per = window // chunk
    total = 0.0
    for n in prompt_tokens:
        for step in range(int(new_tokens)):
            t = int(n) + step
            total += t % window + 1 + per * (t // window)
    return total / (len(prompt_tokens) * max(int(new_tokens), 1))


def padded_dispatches(ctx) -> float:
    """Answers of the window that rode a dispatch the coalescer had to pad."""
    n = 0.0
    for key in ctx["after"]:
        m = _ROWS.search(key) if key.startswith(DISPATCH_ROWS + "{") else None
        if m and int(m.group(1)) & (int(m.group(1)) - 1):
            n += ctx["stats"].delta(ctx["before"], ctx["after"], key) or 0.0
    return n


def bytes_moved(rows: int, slots: float, heads: int, head_dim: int, itemsize: int = 2) -> float:
    return rows * heads * head_dim * float(itemsize) * 2 * slots


def flops(rows: int, slots: float, heads: int, head_dim: int) -> float:
    return 4.0 * rows * heads * head_dim * slots


def read(ctx):
    tr, cfg = ctx["trace"], ctx["config"]
    if tr is None or not ctx.get("prompt_tokens") or "window_size" not in cfg or "chunk_size" not in cfg:
        return None
    if padded_dispatches(ctx):
        return None
    heads = int(cfg["num_attention_heads"])
    hd = int(cfg["hidden_size"]) // heads
    slots = live_slots(ctx["prompt_tokens"], ctx["new_tokens"], int(cfg["window_size"]), int(cfg["chunk_size"]))
    peaks = ctx["peaks"]
    least = seconds = 0.0
    for key, (n, sec) in tr["kernels"].items():
        m = re.match(r"^" + KERNEL + r" \w+\[(\d+),(\d+),(\d+),(\d+)\]$", key)
        if m and int(m.group(2)) == heads and int(m.group(4)) == hd:
            rows = int(m.group(1))
            least += n * max(bytes_moved(rows, slots, heads, hd) / peaks["hbm_bytes_per_s"],
                             flops(rows, slots, heads, hd) / peaks["bf16_flops_per_s"])
            seconds += sec
    if not seconds:
        return None
    return least / seconds * 100.0
