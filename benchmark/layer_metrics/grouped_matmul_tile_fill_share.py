"""How full the grouped expert matmul's row tiles were in the window's
prefills: the assignment rows the down projection's kernel STORED over the
rows it MULTIPLIED (``tpu_rag_engine_moe_prefill_assignments_computed`` /
``tpu_rag_engine_moe_prefill_tile_rows``, both counted on the device by the
kernel's own call and fetched with each answer: the first from the bounds of
every store, the second its (row tile, group) visits times its row tile). A
row tile that spans groups is multiplied once a group, so a buffer of ``m``
rows in ``G`` groups multiplies about ``(m / tm + G - 1) * tm`` rows whatever
the rows that exist: 16 groups of about a thousand rows under 512-row tiles
read 52-73; 64 groups of about 256 read about 34 under 512-row tiles, 50
under 256-row and 67 under 128-row ones. A count, not a time: fuller tiles
are smaller tiles, which the MXU runs slower. None where the program has no
such counter (a dense family, or a program from before it) or the window no
prefill."""

NAME = "tpu_rag_engine_moe_prefill_{}"


def read(ctx):
    d = ctx["stats"].delta
    computed = d(ctx["before"], ctx["after"], NAME.format("assignments_computed"))
    tile_rows = d(ctx["before"], ctx["after"], NAME.format("tile_rows"))
    if computed is None or not tile_rows:
        return None
    return 100.0 * computed / tile_rows
