"""The block-window pooled-summary family (models/block_window.py over
ops/block_window.py and ops/attention.py's decode walk) against its plain
reference (tests/evabyte_reference.py), at a toy size on the CPU with seeded
weights under the fp32 policy: windows of 32 positions in chunks of 4, 4 heads
of 16, two layers, 8 next-position heads of 40 columns. Every prompt here is
longer than a window, so every query past position 31 reads summaries.

Tolerances. The program and the reference compute the same float32 numbers in
different orders (the cache round-trips nothing in fp32; the kernels' running
softmax against one softmax over a masked row), so logits of magnitude ~1-4
agree to a few 1e-6; ``ATOL`` is 1e-4, some thirty times that. The faults
the comparison must see are far above it: window-only attention, the pooling
vectors exchanged, a plain mean for the pooling and a summary of the query's
own window each move a logit by 1e-2 or more past the first window.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import evabyte_reference as ref
from rag_llm_k8s_tpu.core.config import (
    BlockWindowConfig,
    DTypePolicy,
    EngineConfig,
    MeshConfig,
    PrefixCacheConfig,
    SamplingConfig,
)
from rag_llm_k8s_tpu.core.mesh import make_mesh
from rag_llm_k8s_tpu.engine.engine import InferenceEngine
from rag_llm_k8s_tpu.models import block_window as bwm, families
from rag_llm_k8s_tpu.ops import block_window as bw

FP32 = DTypePolicy.fp32()
ATOL = 1e-4
V = 40
CFG = BlockWindowConfig.tiny(vocab_size=V)
W, C = CFG.window_size, CFG.chunk_size
NEW = 6
GREEDY = SamplingConfig(do_sample=False, max_new_tokens=NEW)


def seeded_params(cfg, seed=0):
    """Weights with statistics that make every part matter: kernels of std
    1/sqrt(fan_in), norm offsets near 0, pooling vectors of unit std (so the
    pooling weights are far from uniform)."""
    shapes = jax.eval_shape(lambda: bwm.init_block_window_params(jax.random.PRNGKey(0), cfg, FP32))
    rng = np.random.default_rng(seed)
    out = {}
    for name, leaf in shapes.items():
        if "norm" in name:
            value = 0.1 * rng.standard_normal(leaf.shape)
        elif name == "embedding" or name.endswith(("mu", "phi")):
            value = rng.standard_normal(leaf.shape)
        else:
            value = rng.standard_normal(leaf.shape) / np.sqrt(leaf.shape[-2])
        out[name] = jnp.asarray(value, jnp.float32)
    return out


@pytest.fixture(scope="module")
def params():
    return seeded_params(CFG)


def prompt_of(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(3, V, size=n)]


def head0(logits):
    return np.asarray(logits)[..., 0, :]


_REF = {}


def reference(params, tokens):
    """The reference's logits ``[len, 8, V]``, once a sequence."""
    key = tuple(tokens)
    if key not in _REF:
        _REF[key] = np.asarray(ref.forward(params, CFG, list(tokens)))
    return _REF[key]


def greedy_reference(params, prompt, n):
    """The reference's greedy continuation. Sequences are padded on the right
    to a multiple of 16, so the eager operations compile for a few lengths and
    not for every one. No real position reads a pad: it attends to nothing
    behind it in its own window (causal), and the chunk a pad completes lies
    in the last real position's window or a later one, while a query sees the
    summaries of EARLIER windows only (the reference's rule 4)."""
    tokens = list(prompt)
    for _ in range(n):
        padded = tokens + [0] * (-len(tokens) % 16)
        tokens.append(int(np.argmax(head0(ref.forward(params, CFG, padded))[len(tokens) - 1])))
    return tokens[len(prompt):]


def through_the_cache(params, rows, S, lengths, T=None, impl="xla"):
    """All 8 heads' logits of ``rows`` (left-padded to ``S``, of which
    ``lengths`` are prefilled at once and the rest decoded a token at a
    time), and the cache."""
    B = len(rows)
    lens = np.asarray(lengths)
    T = T or S + max(len(r) - n for r, n in zip(rows, lens))
    model = bwm.BlockWindowModel(CFG, FP32, attn_impl=impl, all_heads=True)
    call = jax.jit(lambda *a: model.apply({"params": params}, *a))
    cache = bwm.make_block_window_cache(CFG, B, T, jnp.float32)
    kv_start = jnp.asarray(S - lens, jnp.int32)
    padded = np.zeros((B, S), np.int32)
    for b, row in enumerate(rows):
        padded[b, S - lens[b]:] = row[:lens[b]]
    positions = jnp.maximum(jnp.arange(S)[None, :] - kv_start[:, None], 0)
    logits, cache = call(jnp.asarray(padded), positions, cache, kv_start, jnp.full((B,), S, jnp.int32), jnp.int32(0))
    out = [[np.asarray(logits[b, S - lens[b] + t]) for t in range(lens[b])] for b in range(B)]
    for t in range(min(len(r) - n for r, n in zip(rows, lens))):
        tok = jnp.asarray([[r[n + t]] for r, n in zip(rows, lens)], jnp.int32)
        logits, cache = call(tok, jnp.asarray(lens + t)[:, None].astype(jnp.int32), cache, kv_start,
                             jnp.full((B,), S + t + 1, jnp.int32), jnp.int32(S + t))
        for b in range(B):
            out[b].append(np.asarray(logits[b, 0]))
    return [np.stack(o).reshape(-1, CFG.num_pred_heads, V) for o in out], cache


# ---- (a) prefill, then decode through the ring and the summary plane ----


@pytest.mark.parametrize("prompt_len,why", [
    (70, "inside a chunk"), (72, "on a chunk's end"), (64, "on a window's end"), (95, "a step before a window's end"),
])
@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_prefill_then_decode_matches_reference_at_every_position(params, impl, prompt_len, why):
    """All 8 heads' logits at every position: the bucket prefilled at once,
    then 40 positions decoded, which crosses a window's end (and closes ten
    chunks) whatever the prompt's length."""
    tokens = prompt_of(prompt_len + 40, prompt_len)
    (got,), cache = through_the_cache(params, [tokens], 96, [prompt_len], impl=impl)
    np.testing.assert_allclose(got, reference(params, tokens), atol=ATOL)
    counted = bwm.fold_counters(np.asarray(cache.counters))
    assert counted["chunks_closed"] == len(tokens) // C and counted["windows_closed"] == len(tokens) // W
    assert (counted["decode_ring_slots_fetched"] > 0) == (impl != "xla")
    # the planes hold what the layout says: position p at ring slot p % W, chunk c at summary c
    ring, pooled = bwm.ring_of(cache.k, CFG), bwm.summaries_of(cache.k, CFG)
    assert ring.shape[-2] == W and pooled.shape[-2] == cache.k.shape[3] - W < len(tokens)  # no plane as long


BLOCK = bwm.live_block(CFG)  # positions a trip of a prompt row's loops takes: the window, at this size


def live_slots(cache, row, n):
    """What a decode step may ever read of ``row``'s planes after a prefill of
    ``n`` positions: the last window's ring slots and every complete chunk's
    summary, keys and values, layer by layer."""
    out = []
    for plane in (cache.k, cache.v):
        ring = np.asarray(bwm.ring_of(plane, CFG))[:, row, :, :(n - 1) % W + 1]
        pooled = np.asarray(bwm.summaries_of(plane, CFG))[:, row, :, :n // C]
        out += [ring, pooled]
    return out


@pytest.mark.parametrize("lengths,why", [
    ((1,), "one position: one block"), ((BLOCK - 1,), "a position short of a block"), ((BLOCK,), "a whole block"),
    ((BLOCK + 1,), "a position into the second block"), ((96,), "the whole bucket: every block"),
    ((1, BLOCK + 1, 96), "rows of one, two and three live blocks in one batch"),
])
@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_a_prefill_runs_the_blocks_a_rows_positions_fill(params, impl, lengths, why):
    """A row of ``n`` real positions in the bucket of 96 runs its layers over
    ``ceil(n / BLOCK)`` blocks of the three (``LIVE_BLOCK``; the kernels are
    told the same bound): all 8 heads' logits at every prefilled position and
    at 40 decoded ones (across a window's end, where the prefilled window's
    summaries are first read) are the reference's; the planes' live slots are
    those of the same row prefilled in the smallest bucket that holds it
    whole, where every block runs; and the program counts what it ran."""
    rows = [prompt_of(n + 40, 50 + n) for n in lengths]
    got, cache = through_the_cache(params, rows, 96, list(lengths), impl=impl)
    for row, g in zip(rows, got):
        np.testing.assert_allclose(g, reference(params, row), atol=ATOL)
    counted = bwm.fold_counters(np.asarray(cache.counters))
    assert counted["prefill_tokens_computed"] == sum(-(-n // BLOCK) * BLOCK for n in lengths)
    assert counted["prefill_tokens_bucketed"] == 96 * len(lengths)
    # through the kernel, a live block is its window's step and, past window 0, one piece of summaries
    blocks = [-(-n // BLOCK) for n in lengths]
    through = (0, 0) if impl == "xla" else (sum(2 * n - 1 for n in blocks), sum(blocks))
    assert (counted["prefill_window_softmax_steps"], counted["prefill_window_query_blocks"]) == tuple(
        CFG.num_layers * n for n in through)
    prompts = [row[:n] for row, n in zip(rows, lengths)]
    _, fresh = through_the_cache(params, prompts, 96, list(lengths), T=136, impl=impl)  # the prefill, no step behind it
    for b, (prompt, n) in enumerate(zip(prompts, lengths)):
        _, whole = through_the_cache(params, [prompt], -(-n // W) * W, [n], T=136, impl=impl)
        for mine, full in zip(live_slots(fresh, b, n), live_slots(whole, 0, n)):
            np.testing.assert_allclose(mine, full, atol=ATOL)


def test_what_the_kernel_leaves_unwritten_reaches_nothing(params, monkeypatch):
    """The prefill kernel writes nothing behind a row's live blocks: fill
    what it left with NaN (interpret mode writes zeros there) and nothing that
    leaves the layer holds one: not the stream behind the live blocks (every
    slot's logits), not the planes."""
    kernel = bw.window_summary_flash_attention

    def poisoned(q, k, v, sk, sv, live, **kw):
        out = kernel(q, k, v, sk, sv, live, **kw)
        return jnp.where((jnp.arange(out.shape[1]) >= live)[None, :, None], jnp.nan, out)

    monkeypatch.setattr(bw, "window_summary_flash_attention", poisoned)
    n = BLOCK + 1
    tokens = prompt_of(n, 9)
    model = bwm.BlockWindowModel(CFG, FP32, attn_impl="pallas_interpret", all_heads=True)
    padded = jnp.asarray([[0] * (96 - n) + tokens], jnp.int32)
    kv_start = jnp.asarray([96 - n], jnp.int32)
    logits, cache = model.apply(
        {"params": params}, padded, jnp.maximum(jnp.arange(96)[None] - kv_start[:, None], 0),
        bwm.make_block_window_cache(CFG, 1, 160, jnp.float32), kv_start, jnp.full((1,), 96, jnp.int32), jnp.int32(0))
    assert all(np.isfinite(np.asarray(a)).all() for a in (logits, cache.k, cache.v))
    np.testing.assert_allclose(np.asarray(logits[0, 96 - n:]).reshape(n, -1, V), reference(params, tokens), atol=ATOL)


def test_two_rows_of_one_bucket_with_different_left_padding(params):
    rows = [prompt_of(100, 2), prompt_of(77, 3), prompt_of(50, 4)]
    lengths = [90, 41, 33]  # windows and chunks fall at different slots in every row
    got, _ = through_the_cache(params, rows, 96, lengths)
    for row, g in zip(rows, got):
        np.testing.assert_allclose(g, reference(params, row)[:len(g)], atol=ATOL)


def chunk_call(params, tokens, start, n, kv_start=0, kv_len=None, T=160, all_heads=True):
    """``tokens[:start]`` prefilled and decoded into a cache, then ``n``
    positions from ``start`` in ONE chunk call; returns its logits and cache."""
    S = 32 * (-(-start // 32))
    model = bwm.BlockWindowModel(CFG, FP32, attn_impl="xla", all_heads=all_heads)
    mc = model.copy(chunked=True)
    cache = bwm.make_block_window_cache(CFG, 1, T, jnp.float32)
    pad = S - start
    padded = np.zeros((1, S), np.int32)
    padded[0, pad:] = tokens[:start]
    ks = jnp.asarray([pad], jnp.int32)
    positions = jnp.maximum(jnp.arange(S)[None] - pad, 0)
    _, cache = model.apply({"params": params}, jnp.asarray(padded), positions, cache, ks,
                           jnp.full((1,), S, jnp.int32), jnp.int32(0))
    fed = jnp.asarray([tokens[start:start + n]], jnp.int32)
    pos = (start + jnp.arange(n))[None]
    end = S + n if kv_len is None else kv_len
    return mc.apply({"params": params}, fed, pos, cache, ks, jnp.full((1,), end, jnp.int32), jnp.int32(S)), S


@pytest.mark.parametrize("start,n,why", [
    (57, 12, "straddles a window's end"), (40, 16, "inside a window, closes four chunks"),
    (62, 2, "ends on a window's last position"), (64, 9, "starts a window"),
])
def test_a_chunk_over_the_cache_matches_reference(params, start, n, why):
    """What a verify step and the scorer feed: positions over the cache whose
    first ones are of one window and whose last of the next. The later
    window's queries read the summary of the chunk this very call completes."""
    tokens = prompt_of(120, 7)
    (logits, cache), S = chunk_call(params, tokens, start, n)
    want = reference(params, tokens[:start + n])[start:]
    np.testing.assert_allclose(np.asarray(logits[0]).reshape(n, -1, V), want, atol=ATOL)
    # and the cache it leaves serves the next positions exactly
    model = bwm.BlockWindowModel(CFG, FP32, attn_impl="xla", all_heads=True)
    at = start + n
    step, _ = model.apply({"params": params}, jnp.asarray([[tokens[at]]], jnp.int32), jnp.asarray([[at]]), cache,
                          jnp.asarray([S - start], jnp.int32), jnp.full((1,), S + n + 1, jnp.int32), jnp.int32(S + n))
    np.testing.assert_allclose(np.asarray(step[0, 0]).reshape(-1, V), reference(params, tokens[:at + 1])[-1], atol=ATOL)


def test_a_verify_step_keeps_the_ring_it_may_still_need(params):
    """A verify step at position 57 feeding 12 proposals may write 7 (to the
    window's end): ``verify_span`` says so and the engine hands it over as
    ``kv_len``. The ring slots of the next window (slots 0..4: this window's
    positions 32..36) keep their keys, so a rejected proposal costs nothing."""
    tokens = prompt_of(120, 7)
    assert int(bwm.verify_span(CFG, jnp.int32(57), 12)) == 7 and int(bwm.verify_span(CFG, jnp.int32(64), 12)) == 12
    (_, before), S = chunk_call(params, tokens, 57, 1)
    junk = tokens[:58] + prompt_of(11, 99)
    (logits, after), _ = chunk_call(params, junk, 57, 12, kv_len=64 + 7)
    ring = lambda cache: np.asarray(bwm.ring_of(cache.k, CFG))[:, 0, :, :57 % W]  # noqa: E731
    np.testing.assert_array_equal(ring(after), ring(before))
    np.testing.assert_allclose(np.asarray(logits[0, :7]).reshape(7, -1, V), reference(params, junk[:64])[57:], atol=ATOL)


# ---- (b) the kernels against the XLA forms ----


def test_prefill_kernel_is_the_dense_form():
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((4, 128, 16)), jnp.float32) for _ in range(3))
    mu, phi = (jnp.asarray(rng.standard_normal((4, 16)), jnp.float32) for _ in range(2))
    sk, sv = bw.pool_chunks(k, v, mu, phi, C)
    want = bw.window_summary_attention_xla(q, k, v, sk, sv, window=W, chunk=C)
    got = bw.window_summary_flash_attention(q, k, v, sk, sv, window=W, chunk=C, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    # a length that is no whole number of windows is padded inside
    got = bw.window_summary_flash_attention(q[:, :80], k[:, :80], v[:, :80], sk[:, :20], sv[:, :20],
                                            window=W, chunk=C, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want)[:, :80], atol=2e-6)


# The kernel's step plan is from the shape alone (``window_summary_plan``), so its
# forms are reached by shapes: a window of 2048 is four query blocks of 512
# (``at`` = 0, 512, 1024, 1536) and 256 summaries (chunks of 8), so window 1
# reads one ragged piece, window 2 one whole piece of 512, window 3 a whole one
# and a ragged one, window 4 two whole ones; 8704 positions are padded to five
# windows, whose 1280 summaries are no whole number of pieces (the served
# shape's count). In bfloat16 the slices fit VMEM: the window's part is ONE
# step and the summaries' another; in float32 the blocks are twice the bytes
# and the walk over key blocks and pieces stays.
KW, KC, KS, KHD = 2048, 8, 8704, 16
KERNEL_FORMS = {"bfloat16": True, "float32": False}  # dtype -> the keys as slices


@pytest.fixture(scope="module", params=sorted(KERNEL_FORMS))
def kernel_row(request):
    """One row through the kernel (interpret mode) and through the dense form."""
    dtype = jnp.dtype(request.param)
    rng = np.random.default_rng(54)
    q, k, v = (jnp.asarray(rng.standard_normal((1, KS, KHD)), dtype) for _ in range(3))
    mu, phi = (jnp.asarray(rng.standard_normal((1, KHD)) * 0.4, dtype) for _ in range(2))
    sk, sv = bw.pool_chunks(k, v, mu, phi, KC)
    want = np.asarray(bw.window_summary_attention_xla(q, k, v, sk, sv, window=KW, chunk=KC), np.float32)
    attend = lambda sk, sv, live=None: np.asarray(bw.window_summary_flash_attention(  # noqa: E731
        q, k, v, sk, sv, live, window=KW, chunk=KC, interpret=True), np.float32)
    # bfloat16: both round the probabilities to 8 bits before p v, in another order
    tol = dict(atol=2e-6) if dtype == jnp.float32 else dict(atol=1e-2, rtol=2e-2)
    return {"dtype": request.param, "sk": sk, "sv": sv, "want": want, "got": attend(sk, sv), "attend": attend,
            "tol": tol}


def test_the_step_plan_is_from_the_shape(kernel_row):
    plan = bw.window_summary_plan(KS, KW, KC, KHD, jnp.dtype(kernel_row["dtype"]).itemsize)
    assert plan == (512, 512, KERNEL_FORMS[kernel_row["dtype"]])


@pytest.mark.parametrize("qi", range(KS // 512), ids=lambda qi: (
    f"window{qi // 4}_at{qi % 4 * 512}_" + ("no_summaries", "a_ragged_piece", "a_whole_piece",
                                          "a_whole_and_a_ragged_piece", "two_whole_pieces")[qi // 4]))
def test_the_kernel_is_the_dense_form_in_every_step_form(kernel_row, qi):
    at, pieces, n_sum = bw.window_summary_block_plan(qi, 512, 512, KW, KW // KC)
    assert (at, pieces, n_sum) == (qi % 4 * 512, (qi // 4 + 1) // 2, qi // 4 * 256)
    rows = slice(qi * 512, (qi + 1) * 512)
    np.testing.assert_allclose(kernel_row["got"][:, rows], kernel_row["want"][:, rows], **kernel_row["tol"])


@pytest.mark.parametrize("junk", [None, "nan", "inf"])
def test_a_row_cut_inside_a_window_and_junk_behind_its_summaries(kernel_row, junk):
    """``live`` ends the row one block into window 3: the blocks before it are
    the whole row's, bit for bit, the ones past it are not computed; a summary
    past the live ones (pooled from a row's junk tail) reaches nothing, though
    it shares the ragged piece with live ones."""
    live = 3 * KW + 512
    sk, sv = kernel_row["sk"], kernel_row["sv"]
    if junk:
        dead = jnp.arange(sk.shape[1])[None, :, None] >= 3 * (KW // KC)
        sk, sv = (jnp.where(dead, getattr(jnp, junk), x) for x in (sk, sv))
    got = kernel_row["attend"](sk, sv, live)
    np.testing.assert_array_equal(got[:, :live], kernel_row["got"][:, :live])
    assert not np.allclose(got[:, live:], kernel_row["got"][:, live:], atol=1e-3)


def steps_by_hand(live, S, window, chunk, bq, bs, sliced):
    """Softmax steps and query blocks, a block at a time from the plan's words."""
    steps = blocks = 0
    for first in range(0, -(-S // window) * window, bq):
        if first >= live:
            break
        w, at = divmod(first, window)
        n_sum = window // chunk * w
        steps += 1 + (n_sum > 0) if sliced else at // bq + 1 + -(-n_sum // bs)
        blocks += 1
    return steps, blocks


@pytest.mark.parametrize("shape,sliced,why", [
    ((20480, 2048, 16, 128, 2), True, "the served shape: 13.7 of 15.5 MiB"),
    ((24576, 2048, 16, 128, 2), True, "a longer bucket: still three pieces of summaries"),
    ((32768, 2048, 16, 128, 2), False, "four pieces: 50 key blocks of code in the bodies, past what ran at speed"),
    ((65536, 2048, 16, 128, 2), False, "4096 summaries: their slice is too wide"),
    ((16384, 8192, 16, 128, 2), False, "a window in the many thousands keeps the walk"),
    ((16384, 4096, 16, 128, 2), False, "a window of 4096: 3584 unmasked keys a row"),
    ((20480, 2048, 16, 256, 2), False, "heads of 256: the strips are twice the bytes"),
    ((20480, 2048, 16, 128, 4), False, "float32 operands"),
    ((4096, 1024, 16, 64, 2), True, "a short window, 64 lanes padded to 128"),
    ((128, 32, 4, 16, 4), True, "the toy: one query block a window, one piece of 32 summaries"),
])
def test_the_fit_rule_and_the_step_count(shape, sliced, why):
    S, W, chunk, hd, itemsize = shape
    bq, bs, got = bw.window_summary_plan(S, W, chunk, hd, itemsize)
    assert got == sliced, why
    assert W % bq == 0 and bq <= 512 and bs == min(512, -(-S // W) * W // chunk)
    lives = [1, bq, S // 3, S - 7, S]
    steps, blocks = bw.window_summary_steps(jnp.asarray(lives), S, W, chunk, hd, itemsize)
    want = [steps_by_hand(n, S, W, chunk, bq, bs, sliced) for n in lives]
    assert (int(steps), int(blocks)) == (sum(s for s, _ in want), sum(b for _, b in want))


def test_the_served_row_takes_two_steps_a_block_past_its_first_window():
    """A 17.4 k-byte prompt in the 20480 bucket (35 live blocks of 512): 66
    steps where the walk over key blocks of 512 and summary blocks of 256 took 162."""
    steps, blocks = bw.window_summary_steps(jnp.asarray([35 * 512]), 20480, 2048, 16, 128)
    assert (int(steps), int(blocks)) == (4 + 2 * 31, 35)
    walk = sum(first % 2048 // 512 + 1 + -(-(first // 2048 * 128) // 256) for first in range(0, 35 * 512, 512))
    assert walk == 162


def pooled_by_hand(k, v, mu, phi, chunk):
    """``[..., H, S, hd]`` float64 numpy -> the two pooled planes, a chunk at a time."""
    *lead, H, S, hd = k.shape
    sk, sv = np.zeros((*lead, H, S // chunk, hd)), np.zeros((*lead, H, S // chunk, hd))
    for at in np.ndindex(*lead, H, S // chunk):
        h, c = at[-2], at[-1]
        kc, vc = k[at[:-1]][chunk * c:chunk * (c + 1)], v[at[:-1]][chunk * c:chunk * (c + 1)]
        wk, wv = np.exp(kc @ mu[h]), np.exp(kc @ phi[h])
        sk[at], sv[at] = (wk / wk.sum()) @ kc, (wv / wv.sum()) @ vc
    return sk, sv


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_pooling_is_the_softmax_weighted_sum(impl):
    rng = np.random.default_rng(1)
    k, v = (rng.standard_normal((2, 8, 16)) for _ in range(2))
    mu, phi = rng.standard_normal((2, 16)), rng.standard_normal((2, 16))
    assert (bw.pool_blocks(k.shape, 4, jnp.float32, impl) is None) == (impl == "xla")
    sk, sv = bw.pool_chunks(*(jnp.asarray(a, jnp.float32) for a in (k, v, mu, phi)), 4, impl)
    want_k, want_v = pooled_by_hand(k, v, mu, phi, 4)
    np.testing.assert_allclose(np.asarray(sk), want_k, atol=1e-5)
    np.testing.assert_allclose(np.asarray(sv), want_v, atol=1e-5)


@pytest.mark.parametrize("shape,dtype,why", [
    ((4, 96, 16), "float32", "a prompt row of three windows: pieces of 8 chunks, one grid step a head"),
    ((4, 2560, 16), "float32", "a row of 80 windows: 40 pieces of 16 chunks in 5 grid steps a head"),
    ((4, 100, 16), "float32", "25 chunks a row: pieces of one chunk"),
    ((3, 4, 4, 16), "float32", "a batch of rows' single chunks"),
    ((2, 4, 28, 16), "float32", "a chunk call's merged rows: 7 chunks a row"),
    ((4, 256, 16), "bfloat16", "bf16 keys and values against the float32 oracle"),
])
def test_the_pooling_kernel_is_the_jnp_body_at_the_served_shapes(shape, dtype, why):
    rng = np.random.default_rng(2)
    k, v = (rng.standard_normal(shape) for _ in range(2))
    mu, phi = rng.standard_normal((4, 16)) / 2, rng.standard_normal((4, 16)) / 2
    args = [jnp.asarray(a, dtype) for a in (k, v, mu, phi)]
    bs, piece = bw.pool_blocks(shape, C, dtype, "pallas_interpret")
    assert bs % (piece * C) == 0 and shape[-2] % bs == 0
    got = bw.pool_chunks(*args, C, "pallas_interpret")
    assert all(g.dtype == dtype and g.shape == (*shape[:-2], shape[-2] // C, 16) for g in got)
    # the oracle on the values the kernel was given, in float64: nothing but the OUTPUT's rounding may differ
    exact = pooled_by_hand(*(np.asarray(a.astype(jnp.float32), np.float64) for a in args), C)
    for g, b, e in zip(got, bw.pool_chunks(*args, C), exact):
        if dtype == "bfloat16":
            assert np.abs(np.asarray(g.astype(jnp.float32)) - e).max() <= 2.0 ** -8 * np.abs(e).max()
        else:
            np.testing.assert_allclose(np.asarray(g), e, atol=2e-6)
            np.testing.assert_allclose(np.asarray(g), np.asarray(b), atol=2e-6)


def test_compiled_the_kernel_takes_whole_tiles_only():
    """What Mosaic stores is ``[piece, hd]`` by whole tiles: a prompt row and a
    scorer's 2032 positions go through the kernel, a verify step's few chunks
    and a toy width through the jnp body; ``"xla"`` never asks."""
    bf16 = jnp.bfloat16
    assert bw.pool_blocks((32, 20480, 128), 16, bf16, "pallas") == (2048, 16)
    assert bw.pool_blocks((8, 32, 2048, 128), 16, bf16, "pallas") == (2048, 16)
    assert bw.pool_blocks((1, 32, 48, 128), 16, bf16, "pallas") is None
    assert bw.pool_blocks((4, 96, 16), 4, jnp.float32, "pallas") is None
    assert bw.pool_blocks((32, 20480, 128), 16, bf16, "xla") is None
    plane = (8, 8, 32, 1408 + 2048, 128)
    assert bw.in_place_pool_serves(plane, 16, bf16, "pallas") and not bw.in_place_pool_serves(plane, 16, bf16, "xla")
    assert not bw.in_place_pool_serves((8, 64, 32, 3456, 128), 16, bf16, "pallas")  # 64 rows' buffers: 16 MB
    assert not bw.in_place_pool_serves((2, 3, 4, 72, 16), 4, jnp.float32, "pallas")


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_decode_steps_batched_pooling_leaves_the_planes_the_per_row_form_does(dtype):
    """Rows at different ``t % chunk`` (and in different windows): one kernel
    call a layer for all of them writes the summaries the per-row form writes,
    and nothing else."""
    rng = np.random.default_rng(3)
    NS, B, H, hd = 40, 5, 4, 16
    planes = [jnp.asarray(rng.standard_normal((2, B, H, NS + W, hd)), dtype) for _ in range(2)]
    mu, phi = (jnp.asarray(rng.standard_normal((H, hd)) / 2, dtype) for _ in range(2))
    t = jnp.asarray([0, 5, 38, 63, 159], jnp.int32)  # t % 4 = 0, 1, 2, 3, 3; windows 0, 0, 1, 1, 4
    src, dst = NS + t % W // C * C, NS - 1 - t // C
    want = list(planes)
    for b in range(B):  # the parent's lines: a row at a time
        at = (1, b, 0, int(src[b]), 0)
        sk, sv = bw.pool_chunks(*(jax.lax.dynamic_slice(p, at, (1, 1, H, C, hd))[0, 0] for p in want), mu, phi, C)
        want = [jax.lax.dynamic_update_slice(p, s[None, None], (1, b, 0, int(dst[b]), 0)) for p, s in zip(want, (sk, sv))]
    for impl in ("xla", "pallas_interpret"):
        assert bw.in_place_pool_serves(planes[0].shape, C, dtype, impl) == (impl != "xla")
        got = jax.jit(lambda kp, vp: bw.pool_ring_chunks(kp, vp, mu, phi, jnp.int32(1), src, dst, C, impl))(*planes)
        for g, w, p in zip(got, want, planes):
            g, w, p = (np.asarray(a.astype(jnp.float32)) for a in (g, w, p))
            np.testing.assert_allclose(g, w, atol=2e-6 if dtype == "float32" else 2.0 ** -8 * np.abs(w).max())
            changed = np.argwhere((g != p).any(axis=(2, 4)))  # (layer, row, slot)
            assert sorted(map(tuple, changed)) == sorted((1, b, int(dst[b])) for b in range(B))


def test_the_model_counts_the_pooling_form_it_built(params):
    from rag_llm_k8s_tpu.obs import tracing

    before = tracing.kernel_builds()
    tokens = prompt_of(34, 8)
    (got,), _ = through_the_cache(params, [tokens], 32, [32], impl="pallas_interpret")
    np.testing.assert_allclose(got, reference(params, tokens), atol=ATOL)
    built = {key for key, n in tracing.kernel_builds().items() if n > before.get(key, 0)}
    assert {("prefill", "chunk_pool"), ("decode", "chunk_pool_in_place")} <= built
    assert not {("prefill", "pool_chunks"), ("decode", "pool_chunks")} & built


def test_the_live_range_is_one_run_of_slots():
    NS = 24
    for t, first, end in ((0, 24, 25), (31, 24, 56), (32, 16, 25), (70, 8, 31), (95, 8, 56), (96, 0, 25)):
        assert tuple(int(x) for x in bwm.live_range(jnp.int32(t), CFG, NS)) == (first, end)
    assert bwm.summary_slots(CFG, 160) == 40 and bwm.summary_slots(BlockWindowConfig(), 20992) == 1408
    cache = families.make_cache(CFG, 2, 160, jnp.float32)
    assert cache.k.shape == (2, 2, 4, 40 + W, 16) and cache.counters.shape == (bwm.N_COUNTERS,)


# ---- (c) the faults the comparison must see ----


@pytest.mark.parametrize("fault", ["summaries", "swap_mu_phi", "mean_pool", "own_window_summaries"])
def test_a_fault_fails_the_tolerance(params, fault):
    tokens = prompt_of(110, 1)
    sound = head0(reference(params, tokens))
    bad = head0(ref.forward(params, CFG, tokens, **{fault: fault != "summaries"}))
    first = C - 1 if fault == "own_window_summaries" else W  # where the fault can first show
    np.testing.assert_allclose(bad[:first], sound[:first], atol=1e-5)
    assert np.abs(bad[first:] - sound[first:]).max() > 100 * ATOL
    (got,), _ = through_the_cache(params, [tokens], 96, [96])
    assert np.abs(head0(got) - bad).max() > 100 * ATOL  # and the program is on the sound side


# ---- (d) every one-shot program of the engine ----


def engine_for(params, cfg=CFG, **kw):
    ec = EngineConfig(**{**dict(prompt_buckets=(64, 96), max_batch_size=4, max_seq_len=224,
                                speculative="off", attn_impl="xla", max_chunked_prompt=256), **kw})
    return InferenceEngine(cfg, params, sampling=GREEDY, engine_config=ec, dtypes=FP32)


def test_batched_rows_of_unequal_length(params):
    prompts = [prompt_of(n, 10 + n) for n in (61, 90, 35)]  # 61 + 6 crosses a window's end while decoding
    got = engine_for(params).generate(prompts)
    assert got == [greedy_reference(params, p, NEW) for p in prompts]


def test_verify_across_a_windows_end_is_the_vanilla_stream(params):
    base = prompt_of(7, 3)
    prompt = (base * 14)[:91]  # repeats: prompt lookup drafts; 91 + 6 crosses position 96
    e = engine_for(params, speculative="prompt_lookup", spec_tokens=12)
    assert e.generate([prompt]) == [greedy_reference(params, prompt, NEW)]
    assert e.stats.spec_verify_steps > 0 and e.stats.family_counters["chunks_closed"] > 0


def test_chunked_prefill_past_the_largest_bucket(params):
    prompt = prompt_of(150, 4)  # > 96: two chunks of 96 through the ring, each in pieces of 24
    assert engine_for(params).generate([prompt]) == [greedy_reference(params, prompt, NEW)]


def test_score_exact_matches_reference_logits(params):
    prompt, emitted = prompt_of(59, 5), prompt_of(8, 6)  # the scored positions straddle position 64
    got = engine_for(params).score_exact(prompt, emitted)
    logits = head0(reference(params, prompt + emitted))[len(prompt) - 1:-1]
    assert list(got["argmax"]) == list(np.argmax(logits, -1))
    np.testing.assert_allclose(got["max_logit"], logits.max(-1), atol=ATOL)
    np.testing.assert_allclose(got["chosen_logit"], logits[np.arange(8), emitted], atol=ATOL)


def test_fused_single_fetch_path(params):
    e = engine_for(params)
    a_ids, b_ids = np.asarray(prompt_of(5, 7), np.int32), np.asarray(prompt_of(4, 8), np.int32)
    store = np.zeros((8, 30), np.int32)
    lens = np.asarray([30, 22, 30, 27, 30, 30, 30, 30], np.int32)
    for i in range(8):
        store[i, :lens[i]] = prompt_of(int(lens[i]), 20 + i)
    packed = jnp.asarray([[0.1, 0.2, 0.3, 3.0, 1.0, 6.0]], jnp.float32)  # dists | ids
    got = e.generate_rag(a_ids, b_ids, packed, jnp.asarray(store), jnp.asarray(lens), n_chunks=2)
    prompt = list(a_ids) + list(store[3, :27]) + list(store[1, :22]) + list(b_ids)
    assert got == greedy_reference(params, [int(t) for t in prompt], NEW)


def test_pallas_path_is_the_xla_path(params):
    """The prefill kernel and the decode walk over the joined plane (both in
    interpret mode) give the stream the XLA forms give, and the program
    counts what the walk fetched."""
    prompts = [prompt_of(n, 30 + n) for n in (93, 40)]
    e = engine_for(params, attn_impl="pallas_interpret")
    assert e.generate(prompts) == engine_for(params).generate(prompts)
    counted = e.stats.family_counters
    ring, pooled = counted["decode_ring_slots_fetched"], counted["decode_summary_slots_fetched"]
    assert ring > 0 and pooled > 0
    assert counted["decode_slots_attended_positions"] == ring + C * pooled
    # the prefill kernel's steps, both layers: rows of three and of two live blocks, a block past
    # window 0 its window's step and one piece of summaries
    assert (counted["prefill_window_softmax_steps"], counted["prefill_window_query_blocks"]) == (2 * (5 + 3), 2 * 5)


# ---- (e) what the family cannot be served with refuses by name ----


@pytest.mark.parametrize("overrides,mechanism", [
    (dict(kv_quant="int8"), "kv_quant"),
    (dict(weight_quant="int8"), "weight_quant"),
    (dict(prefix_cache=PrefixCacheConfig(enabled=True)), "prefix cache"),
    (dict(batching="continuous"), "continuous"),
])
def test_refusals_name_the_mechanism(params, overrides, mechanism):
    with pytest.raises(NotImplementedError, match=mechanism):
        engine_for(params, **overrides)


def test_continuous_engine_and_tp_refuse(params):
    from rag_llm_k8s_tpu.engine.continuous import ContinuousEngine

    with pytest.raises(NotImplementedError, match="continuous engine"):
        ContinuousEngine(CFG, params, sampling=GREEDY, engine_config=EngineConfig(), dtypes=FP32)
    mesh = make_mesh(MeshConfig(dp=1, sp=1, tp=2), devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="tp=2"):
        InferenceEngine(CFG, params, sampling=GREEDY, engine_config=EngineConfig(), dtypes=FP32, mesh=mesh)


def test_the_family_row(params):
    fam = families.of(CFG)
    assert fam.counter_names == bwm.COUNTER_NAMES and fam.counters_width == 9
    assert set(fam.counter_names) == set(bwm.fold_counters(np.zeros(9)))
    assert fam.checkpoint_loader_refusal and "name map" in fam.checkpoint_loader_refusal
    assert fam.verify_span is bwm.verify_span
    from rag_llm_k8s_tpu.core.config import LlamaConfig

    assert families.of(LlamaConfig.tiny()).verify_span is None
    assert params["lm_head"].shape == (64, 8 * V) and params["layers_mu"].shape == (2, 4, 16)


def test_config_refuses_what_the_layout_cannot_hold():
    with pytest.raises(ValueError, match="whole number of chunks"):
        BlockWindowConfig.tiny(window_size=30)
    with pytest.raises(ValueError, match="multi-head"):
        BlockWindowConfig.tiny(num_key_value_heads=2)
    big = BlockWindowConfig()
    assert (big.head_dim, big.chunks_per_window, big.num_pred_heads * big.vocab_size) == (128, 128, 2560)
