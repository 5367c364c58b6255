"""The window bound of the prefill flash kernel, its plan, the XLA forms and
the decode walk a windowed layer hands a shortened window (interpret mode on
the CPU), and the windowed family's decode-slot counters against numpy."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rag_llm_k8s_tpu.ops.attention import (
    attention_xla,
    chunk_attention_xla,
    decode_attention,
    decode_attention_xla,
    decode_block_plan,
    flash_attention,
    flash_block_plan,
    flash_blocks,
)


def _live(S, kv_start, kv_len, window):
    pos = np.arange(S)
    live = (pos[None, :] >= kv_start) & (pos[None, :] < kv_len) & (pos[None, :] <= pos[:, None])
    if window is not None:
        live &= pos[None, :] > pos[:, None] - window
    return live


class TestWindowedPlan:
    """``flash_block_plan(window=...)`` against the brute-force mask, for
    every query block: what it skips is dead, what it calls interior needs no
    mask, and a fully live block is not left to an edge's mask."""

    S = 1024

    @pytest.mark.parametrize("window", [1, 64, 200, 256, 512, 1024, 5000])
    @pytest.mark.parametrize("bq,bk", [(64, 256), (128, 128), (256, 128), (64, 512)])
    @pytest.mark.parametrize("kv_start,kv_len", [(0, 1024), (1, 1024), (402, 1024), (255, 700), (950, 951),
                                                 (1023, 1024), (0, 300), (300, 300)])
    def test_plan_matches_the_brute_force_mask(self, kv_start, kv_len, bq, bk, window):
        S = self.S
        live = _live(S, kv_start, kv_len, window)
        for qi in range(S // bq):
            lo, hi, int_lo, int_hi = (int(x) for x in flash_block_plan(
                qi, kv_start, kv_len, S, bq, bk, True, window))
            for kj in range(S // bk):
                tile = live[qi * bq:(qi + 1) * bq, kj * bk:(kj + 1) * bk]
                visited = lo <= kj <= hi
                assert visited == bool(tile.any()), (qi, kj, lo, hi)
                if visited and int_lo <= kj <= int_hi:
                    assert tile.all(), (qi, kj, "interior block with a masked pair")
                if visited and tile.all() and kj != lo:
                    assert int_lo <= kj <= int_hi, (qi, kj, int_lo, int_hi)

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("bq,bk", [(128, 256), (256, 128), (256, 512)])
    def test_no_window_is_the_plan_as_it_was(self, bq, bk, causal):
        """``window=None`` (and a window no query can outgrow) give the
        bounds the rule gave before it knew a window, written out here."""
        S = self.S
        for kv_start, kv_len in [(0, S), (1, S), (bk - 1, 700), (402, S), (950, 951), (300, 300)]:
            for qi in range(S // bq):
                q_lo, q_hi = qi * bq, qi * bq + bq - 1
                lo, hi = max(kv_start // bk, 0), min((kv_len - 1) // bk, S // bk - 1)
                int_lo, int_hi = (kv_start + bk - 1) // bk, kv_len // bk - 1
                empty = kv_len <= kv_start
                if causal:
                    hi, int_hi = min(hi, q_hi // bk), min(int_hi, (q_lo + 1) // bk - 1)
                    empty = empty or q_hi < kv_start
                want = (lo, lo - 1 if empty else hi, int_lo, int_hi)
                got = tuple(int(x) for x in flash_block_plan(qi, kv_start, kv_len, S, bq, bk, causal))
                assert got == want
                if causal:
                    wide = tuple(int(x) for x in flash_block_plan(qi, kv_start, kv_len, S, bq, bk, True, 2 * S))
                    assert wide == want


@pytest.mark.parametrize("S,G,dq,dv,want", [
    (4096, 4, 128, 128, (256, 512)),  # Mistral-7B, Mistral-Nemo under tp 4 (32 / 8 heads)
    (2048, 4, 128, 128, (256, 512)),
    (4096, 1, 192, 128, (512, 512)),  # dots' expanded latent form
    (4096, 1, 192, 128, (512, 512)),  # longcat's
    (2048, 1, 192, 128, (512, 512)),
    (4096, 9, 128, 128, (128, 512)),  # 72 query heads over 8: 1152 rows a step
    (4096, 6, 128, 128, (128, 512)),  # 48 over 8: 768 rows a step
    (4096, 3, 128, 128, (256, 512)),
    (4096, 2, 128, 128, (512, 512)), (4096, 8, 128, 128, (128, 512)), (4096, 16, 128, 128, (64, 512)),
])
def test_the_block_rule_at_the_served_shapes(S, G, dq, dv, want):
    """Powers of two come out as they did (the three accepted families'
    shapes, pinned); a group size that is none takes the power of two nearest
    ``1024 / G`` and no longer collapses to a block of one or two queries."""
    assert flash_blocks(S, G, dq, dv) == want


def _problem(seed, B, S, H, K, hd=32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, S, H, hd), jnp.float32),
            jax.random.normal(ks[1], (B, S, K, hd), jnp.float32),
            jax.random.normal(ks[2], (B, S, K, hd), jnp.float32))


class TestWindowedKernel:
    @pytest.mark.parametrize("streamed", [False, True], ids=["resident", "streamed"])
    @pytest.mark.parametrize("G", [9, 6, 4])
    def test_matches_the_windowed_oracle(self, G, streamed):
        """Rows with different left pads and frontiers, a window several
        blocks wide and one under a block; NaN outside the live slots must not
        reach a live query."""
        B, S, K, W = 3, 256, 2, 80
        q, k, v = _problem(G, B, S, G * K, K)
        kv_start = jnp.array([0, 37, 130], jnp.int32)
        kv_len = jnp.array([S, S, 200], jnp.int32)
        pos = jnp.arange(S)[None, :, None, None]
        ok = (pos >= kv_start[:, None, None, None]) & (pos < kv_len[:, None, None, None])
        k, v = jnp.where(ok, k, jnp.nan), jnp.where(ok, v, jnp.nan)
        want = attention_xla(q, jnp.nan_to_num(k), jnp.nan_to_num(v), kv_start, kv_len, window=W)
        for bq, bk in [(32, 32), (16, 64), (64, 16)]:
            if streamed:
                from rag_llm_k8s_tpu.ops.attention import _flash_call

                qt = q.transpose(0, 2, 1, 3).reshape(B * G * K, S, -1)
                kt, vt = (x.transpose(0, 2, 1, 3).reshape(B * K, S, -1) for x in (k, v))
                got = _flash_call(qt, kt, vt, kv_start, kv_len, scale=q.shape[-1] ** -0.5, causal=True,
                                  bq=bq, bk=bk, interpret=True, name="flash_attention_window",
                                  resident=False, window=W)
                got = got.reshape(B, G * K, S, -1).transpose(0, 2, 1, 3)
            else:
                got = flash_attention(q, k, v, kv_start, kv_len, bq=bq, bk=bk, interpret=True, window=W)
            live = np.asarray((pos >= kv_start[:, None, None, None]) & (pos < kv_len[:, None, None, None]))
            live = np.broadcast_to(live, got.shape)
            np.testing.assert_allclose(np.where(live, got, 0), np.where(live, want, 0), rtol=2e-4, atol=2e-5)
            assert np.isfinite(np.asarray(got)).all()

    def test_a_window_wider_than_the_sequence_is_causal_attention(self):
        q, k, v = _problem(0, 2, 128, 18, 2)
        got = flash_attention(q, k, v, bq=32, bk=32, interpret=True, window=4096)
        np.testing.assert_allclose(got, attention_xla(q, k, v), rtol=2e-4, atol=2e-5)

    def test_the_window_changes_the_answer(self):
        """The bound is not decoration: past ``window`` tokens the windowed
        output differs from full causal attention, before them it does not."""
        q, k, v = _problem(1, 1, 128, 9, 1)
        full, win = attention_xla(q, k, v), attention_xla(q, k, v, window=40)
        np.testing.assert_allclose(win[:, :40], full[:, :40], rtol=1e-5, atol=1e-6)
        assert float(jnp.max(jnp.abs(win[:, 40:] - full[:, 40:]))) > 0.05


class TestWindowOverTheCache:
    """A windowed layer's decode step hands the walk ``max(kv_start, kv_len -
    W)``: the kernel has no window of its own."""

    @pytest.mark.parametrize("G", [9, 6, 4])
    def test_decode_walk_on_the_shortened_window(self, G):
        L, B, K, T, hd, W = 2, 4, 2, 512, 32, 96
        ks = jax.random.split(jax.random.PRNGKey(G), 3)
        q = jax.random.normal(ks[0], (B, 1, G * K, hd), jnp.float32)
        kc = jax.random.normal(ks[1], (L, B, K, T, hd), jnp.float32)
        vc = jax.random.normal(ks[2], (L, B, K, T, hd), jnp.float32)
        kv_start = jnp.array([0, 100, 300, 410], jnp.int32)
        kv_len = jnp.array([50, 400, 512, 411], jnp.int32)
        want = decode_attention_xla(q, kc, vc, kv_start, kv_len, jnp.int32(1), window=W)
        got = decode_attention(q, kc, vc, jnp.maximum(kv_start, kv_len - W), kv_len, jnp.int32(1),
                               bk=128, interpret=True)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
        # and the oracle's window is the mask it says: slots [kv_len - W, kv_len) past kv_start
        full = decode_attention_xla(q, kc, vc, jnp.maximum(kv_start, kv_len - W), kv_len, jnp.int32(1))
        np.testing.assert_allclose(want, full, rtol=1e-6, atol=1e-6)

    def test_chunk_oracle_window_agrees_with_the_fresh_form(self):
        """A chunk at ``write_index`` over the cache against the same tokens
        attended fresh: the two XLA forms carry the same window."""
        B, S, K, G, hd, W = 2, 64, 2, 3, 16, 20
        q, k, v = _problem(5, B, S, G * K, K, hd)
        kv_start, kv_len = jnp.array([0, 9], jnp.int32), jnp.array([S, S], jnp.int32)
        fresh = attention_xla(q, k, v, kv_start, kv_len, window=W)
        cache_k = k.transpose(0, 2, 1, 3)[None]  # [1, B, K, T, hd]
        cache_v = v.transpose(0, 2, 1, 3)[None]
        off = 40
        got = chunk_attention_xla(q[:, off:], cache_k, cache_v, kv_start, kv_len, jnp.int32(0),
                                  jnp.int32(off), window=W)
        np.testing.assert_allclose(got, fresh[:, off:], rtol=1e-5, atol=1e-6)


def test_windowed_counters_are_the_plan_s():
    """``decode_slots_streamed_window`` / ``_allocated_window`` over a few
    decode steps against numpy on ``decode_block_plan``: every sliding layer
    fetches the steps of ``[max(kv_start, kv_len - W), kv_len)``, a full
    layer those of ``[kv_start, kv_len)`` (counted once a step)."""
    from rag_llm_k8s_tpu.core.config import DTypePolicy, WindowedMoEConfig
    from rag_llm_k8s_tpu.models import families, windowed_moe as wm
    from rag_llm_k8s_tpu.ops.attention import gqa_decode_step

    cfg = WindowedMoEConfig.tiny(vocab_size=64)
    dt = DTypePolicy.fp32()
    params = wm.init_windowed_moe_params(jax.random.PRNGKey(0), cfg, dt)
    model = wm.WindowedMoEModel(cfg, dt, attn_impl="pallas_interpret")
    call = jax.jit(lambda *a, **kw: model.apply({"params": params}, *a, **kw), static_argnames=("last_logit_only",))
    B, S, T, new = 2, 32, 256, 3
    lens = np.array([32, 11])
    cache = families.make_cache(cfg, B, T, jnp.float32)
    tokens = jnp.asarray(np.random.RandomState(0).randint(3, 64, (B, S)), jnp.int32)
    kv_start = jnp.asarray(S - lens, jnp.int32)
    positions = jnp.maximum(jnp.arange(S)[None, :] - kv_start[:, None], 0)
    logits, cache = call(tokens, positions, cache, kv_start, jnp.full((B,), S, jnp.int32), jnp.int32(0),
                         last_logit_only=True)
    step = gqa_decode_step(T, cfg.num_kv_heads, 1, cfg.head_dim, jnp.float32)
    want_full = want_win = 0
    for t in range(new):
        kv_len = jnp.full((B,), S + t + 1, jnp.int32)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        logits, cache = call(tok, jnp.asarray((lens + t)[:, None], jnp.int32), cache, kv_start, kv_len,
                             jnp.int32(S + t))
        _, n = decode_block_plan(np.asarray(kv_start), np.asarray(kv_len), T, step)
        want_full += int(np.sum(n)) * step
        _, n = decode_block_plan(np.maximum(np.asarray(kv_start), np.asarray(kv_len) - cfg.sliding_window),
                                 np.asarray(kv_len), T, step)
        want_win += int(np.sum(n)) * step * cfg.num_sliding_layers
    got = wm.fold_counters(np.asarray(cache.counters))
    assert got["decode_slots_streamed"] == want_full
    assert got["decode_slots_allocated"] == new * B * T
    assert got["decode_slots_streamed_window"] == want_win
    assert got["decode_slots_allocated_window"] == new * B * T * cfg.num_sliding_layers


# ---------------------------------------------------------------------------
# a window that fits ONE step (``flash_window_step``): the kernel without a
# walk, its plan, the pairs it multiplies, and what it must leave alone
# ---------------------------------------------------------------------------

from rag_llm_k8s_tpu.ops.attention import (  # noqa: E402
    flash_walk_blocks,
    flash_window_pairs,
    flash_window_plan,
    flash_window_step,
)


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


# name: (S, W, bq, kv_start, kv_len, the form it must take); two rows a case,
# the second always whole, so that no case is all one kind of block
ONE_STEP_CASES = {
    "whole rows": (256, 64, 32, 0, 256, "one step"),
    "kv_start inside the first window": (256, 64, 32, 37, 256, "one step"),
    "kv_start inside a later query block": (256, 64, 32, 130, 256, "one step"),
    "kv_len short of S": (256, 64, 32, 20, 200, "one step"),
    "an empty row": (256, 64, 32, 50, 50, "one step"),
    "W no multiple of the query block": (256, 80, 32, 37, 256, "one step"),
    "W odd, under one query block": (256, 33, 64, 70, 250, "one step"),
    "W + bq reaches S: the walk": (128, 96, 32, 37, 128, "walk"),
}


@pytest.mark.parametrize("G", [9, 6])
@pytest.mark.parametrize("case", sorted(ONE_STEP_CASES))
def test_one_step_window_matches_the_windowed_oracle(case, G):
    """``flash_attention(window=)`` where the window fits one step, against
    ``attention_xla(window=)``: NaN planted outside ``[kv_start, kv_len)``
    reaches no output, a query in the left pad reads zeros, and the call is
    the kernel without scratch exactly where ``flash_window_step`` says."""
    S, W, bq, start, end, form = ONE_STEP_CASES[case]
    B, K, hd = 2, 2, 32
    q, k, v = _problem(G + S, B, S, G * K, K, hd)
    kv_start, kv_len = jnp.array([start, 0], jnp.int32), jnp.array([end, S], jnp.int32)
    pos = jnp.arange(S)[None, :, None, None]
    ok = (pos >= kv_start[:, None, None, None]) & (pos < kv_len[:, None, None, None])
    k, v = jnp.where(ok, k, jnp.nan), jnp.where(ok, v, jnp.nan)
    want = attention_xla(q, jnp.nan_to_num(k), jnp.nan_to_num(v), kv_start, kv_len, window=W)

    step = flash_window_step(S, G, hd, hd, W, 4, bq)
    assert (step is not None) == (form == "one step"), step
    if step is not None:
        assert step[0] == bq and step[1] % bq == 0 and W + bq <= step[1] < min(S, W + 2 * bq)

    def call(q, k, v):
        return flash_attention(q, k, v, kv_start, kv_len, bq=bq, interpret=True, window=W)

    (eqn,) = _pallas_calls(jax.make_jaxpr(call)(q, k, v).jaxpr)
    assert eqn.params["name"] == "flash_attention_window"
    assert (eqn.params["grid_mapping"].num_scratch_operands == 0) == (form == "one step")
    got = np.asarray(call(q, k, v))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-4, atol=2e-5)
    assert not got[0, :start].any() and (start == end) == (not got[0].any())


class TestOneStepPlan:
    """``flash_window_plan`` and ``flash_window_pairs`` against the
    brute-force mask."""

    S = 1024

    @pytest.mark.parametrize("window", [1, 64, 200, 256, 512, 700])
    @pytest.mark.parametrize("bq", [32, 64, 128])
    @pytest.mark.parametrize("kv_start,kv_len", [(0, 1024), (1, 1024), (402, 1024), (255, 700), (950, 951),
                                                 (1023, 1024), (0, 300), (300, 300)])
    def test_the_slice_holds_every_live_pair_and_steady_means_two_edges(self, kv_start, kv_len, bq, window):
        S = self.S
        span = -(-window // bq) * bq + bq
        assert span < S
        live = _live(S, kv_start, kv_len, window)
        slack = span - bq - window
        for qi in range(S // bq):
            off, alive, steady = (int(x) for x in flash_window_plan(qi, kv_start, kv_len, bq, span, window))
            assert off % bq == 0 and 0 <= off <= S - span
            rows = live[qi * bq:(qi + 1) * bq]
            assert bool(alive) == bool(rows.any()), (qi, off)
            assert not rows[:, :off].any() and not rows[:, off + span:].any(), (qi, "a live pair outside the slice")
            if steady:
                # the kernel's steady body: every key a live slot, the mask
                # two compares of a column against the query's place in the block
                assert alive and off == (qi + 1) * bq - span
                t, c = np.arange(bq)[:, None], np.arange(span)[None, :]
                np.testing.assert_array_equal(rows[:, off:off + span], (c > t + slack) & (c <= t + span - bq))

    @pytest.mark.parametrize("G,window,form", [(9, 200, "one step"), (6, 512, "one step"), (4, 100, "one step"),
                                               (9, 900, "walk"), (6, 2000, "walk")])
    def test_pairs_multiplied_and_live(self, G, window, form):
        S, hd = self.S, 128
        kv_start = np.array([0, 1, 402, 255, 950, 300, 0])
        kv_len = np.array([1024, 1024, 1024, 700, 951, 300, 300])
        step = flash_window_step(S, G, hd, hd, window)
        assert (step is not None) == (form == "one step")
        if step is None:
            bq, width, _ = flash_walk_blocks(S, S, G, hd, hd, True, 2)
        else:
            bq, width = step
        multiplied = pairs = 0
        for start, end in zip(kv_start, kv_len):
            live = _live(S, start, end, window)
            pairs += int(live.sum())
            for qi in range(S // bq):
                rows = live[qi * bq:(qi + 1) * bq]
                if step is None:  # the key blocks that hold a live pair
                    multiplied += bq * width * sum(
                        bool(rows[:, kj * width:(kj + 1) * width].any()) for kj in range(S // width))
                else:  # one slice a live block
                    multiplied += bq * width * bool(rows.any())
        got = flash_window_pairs(jnp.asarray(kv_start), jnp.asarray(kv_len), S, G, hd, hd, window)
        assert (int(got[0]), int(got[1])) == (multiplied, pairs)
        assert pairs <= multiplied


def test_the_served_shapes_take_the_form_the_sweep_gave():
    """A window of 512 under a 4096 bucket at 72 / 8 heads of 128 (the
    sliding layers of ``laguna-s-ep16.closed8``): 128 queries against one
    slice of 640 keys. A window in the thousands, a strip that is streamed
    and a bucket the slice fills keep the walk."""
    assert flash_window_step(4096, 9, 128, 128, 512) == (128, 640)
    assert flash_window_step(4096, 6, 128, 128, 512) == (128, 640)
    assert flash_window_step(4096, 4, 128, 128, 512) == (256, 768)
    assert flash_window_step(8192, 9, 128, 128, 4096) is None  # 1152 rows x 4224 keys: over VMEM
    assert flash_window_step(16384, 9, 128, 128, 512) is None  # the strips are streamed
    assert flash_window_step(512, 9, 128, 128, 512) is None


# sha256 (first 16 hex digits) of ``str(jax.make_jaxpr(call)(*shapes))`` as the
# commit before the one-step form traced it (PR 46's tree). The text holds the
# kernel's whole body, its grid, blocks and scratch, and no file or line. To
# re-record after a change that MEANS to move one of these programs, print the
# digest this test computes.
NO_WINDOW_PROGRAMS = {
    "Mistral-7B prefill [32, 4096, 128]": (
        "06b30de1912bbc4e", "flash", dict(), [(1, 4096, 32, 128), (1, 4096, 8, 128), (1, 4096, 8, 128)]),
    "a full layer beside the sliding ones [48, 4096, 128]": (
        "cb76126efbb554ab", "flash", dict(), [(1, 4096, 48, 128), (1, 4096, 8, 128), (1, 4096, 8, 128)]),
    "MLA's expanded form [128, 4096, 192 / 128]": (
        "11f367d2dec79259", "mla", dict(scale=0.1), [(1, 4096, 128, 192), (1, 4096, 128, 192), (1, 4096, 128, 128)]),
    "the encoder [8 x 16, 1536, 64]": (
        "7d08a7b620ceb89a", "flash", dict(causal=False), [(8, 1536, 16, 64)] * 3),
    "streamed strips [32, 16384, 128]": (
        "b6f2810b924d1a68", "flash", dict(), [(1, 16384, 32, 128), (1, 16384, 8, 128), (1, 16384, 8, 128)]),
    "a window too wide for one step [72, 8192, 128], W 4096": (
        "7c5d175aac2a97e6", "flash", dict(window=4096), [(1, 8192, 72, 128), (1, 8192, 8, 128), (1, 8192, 8, 128)]),
}


@pytest.mark.parametrize("name", sorted(NO_WINDOW_PROGRAMS))
def test_calls_without_a_one_step_window_trace_to_the_programs_they_were(name):
    import hashlib

    from rag_llm_k8s_tpu.ops.mla import mla_flash_attention

    digest, kind, static, shapes = NO_WINDOW_PROGRAMS[name]
    fn = mla_flash_attention if kind == "mla" else flash_attention
    B = shapes[0][0]
    avals = [jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in shapes] + [jax.ShapeDtypeStruct((B,), jnp.int32)] * 2
    text = str(jax.make_jaxpr(lambda q, k, v, s, e: fn(q, k, v, s, e, **static))(*avals))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest, name


def test_prefill_pair_counters_are_the_rule_s():
    """``prefill_window_pairs_multiplied`` / ``_live`` after one single-shot
    prefill through the kernels: the sliding layers × ``flash_window_pairs``
    of the batch, the live ones also the brute-force mask's; nothing on the
    XLA path, nothing from a decode step."""
    from rag_llm_k8s_tpu.core.config import DTypePolicy, WindowedMoEConfig
    from rag_llm_k8s_tpu.models import families, windowed_moe as wm

    cfg = WindowedMoEConfig.tiny(vocab_size=64)
    dt = DTypePolicy.fp32()
    params = wm.init_windowed_moe_params(jax.random.PRNGKey(0), cfg, dt)
    B, S, T = 2, 64, 128
    lens = np.array([64, 23])
    tokens = jnp.asarray(np.random.RandomState(0).randint(3, 64, (B, S)), jnp.int32)
    kv_start = jnp.asarray(S - lens, jnp.int32)
    kv_len = jnp.full((B,), S, jnp.int32)
    positions = jnp.maximum(jnp.arange(S)[None, :] - kv_start[:, None], 0)
    heads = dict(zip(cfg.layer_types, cfg.num_attention_heads_per_layer))[wm.SLIDING]
    W, n_win = cfg.sliding_window, cfg.num_sliding_layers
    want = flash_window_pairs(kv_start, kv_len, S, heads // cfg.num_kv_heads, cfg.head_dim, cfg.head_dim, W, 4)
    live = sum(int(_live(S, s, S, W).sum()) for s in np.asarray(kv_start))
    assert int(want[1]) == live and n_win > 0
    for impl, counted in (("pallas_interpret", True), ("xla", False)):
        model = wm.WindowedMoEModel(cfg, dt, attn_impl=impl)
        logits, cache = model.apply({"params": params}, tokens, positions, families.make_cache(cfg, B, T, jnp.float32),
                                    kv_start, kv_len, jnp.int32(0), last_logit_only=True)
        got = wm.fold_counters(np.asarray(cache.counters))
        assert got["prefill_window_pairs_multiplied"] == counted * n_win * int(want[0])
        assert got["prefill_window_pairs_live"] == counted * n_win * live
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        _, cache = model.apply({"params": params}, tok, jnp.asarray(lens[:, None], jnp.int32), cache, kv_start,
                               kv_len + 1, jnp.int32(S))
        after = wm.fold_counters(np.asarray(cache.counters))
        assert after["prefill_window_pairs_live"] == got["prefill_window_pairs_live"]
