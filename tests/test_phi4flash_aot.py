"""The decoder-hybrid-decoder family's one-shot programs compiled, without a
chip, for a DESCRIBED ``v5e:2x2`` topology (``tests/test_tpu_aot_compile.py``
is the pattern and holds the fixtures; the case stood there until PR 57 and
stands alone so that it rides another worker)."""

import re

import jax
import jax.numpy as jnp

from rag_llm_k8s_tpu.ops import attention as A

from test_tpu_aot_compile import one_chip, topo, uncached  # noqa: F401  (its fixtures: the described chip, no compile cache)
from test_tpu_aot_compile import BF16, F32, I32


def test_cross_decoder_programs_compile_with_their_kernels(one_chip, uncached):
    """The eighth decoder family's one-shot programs at the published mixer
    geometry (hidden 2560: 40 query heads of 64 over 20 KV heads, served as 40
    zero-padded heads of 128 over 10 pair heads, a group of 4; d_inner 5120,
    16 states, dt rank 160) with a narrow SwiGLU, a small vocabulary and the
    shallowest depth the rule derives (8 layers: two (Mamba, window) pairs,
    the memory's layer, the full layer, one (memory unit, cross) pair), at the
    cell's buckets, through the Pallas path: the fresh prompt call (the scan
    kernel, gated and with its un-gated output; the windowed flash kernel in
    its one-step form; the flash kernel; the cross-decoder's one position, a
    decode walk INSIDE the prefill) with the decode loop, the verify loop
    with ``commit``, and the exact scorer all lower for the chip. 11776 and
    not 11264 is the lower bucket: there the full layer's flash call asks for
    92 KB more scoped VMEM than Mosaic has (PERF.md section 7)."""
    from rag_llm_k8s_tpu.core.config import (
        CrossDecoderConfig, DTypePolicy, EngineConfig, GoodputConfig, PrefixCacheConfig, SamplingConfig,
    )
    from rag_llm_k8s_tpu.engine import engine as engine_mod
    from rag_llm_k8s_tpu.models.cross_decoder import init_cross_decoder_params
    from rag_llm_k8s_tpu.ops import ssm

    cfg = CrossDecoderConfig(vocab_size=1024, intermediate_size=512, num_hidden_layers=8, tie_word_embeddings=False)
    dt = DTypePolicy()
    shapes = jax.eval_shape(lambda: init_cross_decoder_params(jax.random.PRNGKey(0), cfg, dt))
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), shapes)
    S = 13312
    assert A.flash_window_step(S, 4, 128, 128, 512) == (64, 576)  # the window in one step: the strips are resident
    ec = EngineConfig(prompt_buckets=(11776, S), max_seq_len=16384, max_batch_size=2, attn_impl="pallas",
                      speculative="prompt_lookup", goodput=GoodputConfig(enabled=False),
                      prefix_cache=PrefixCacheConfig(enabled=False))
    eng = engine_mod.InferenceEngine(
        cfg, params, sampling=SamplingConfig(do_sample=False, max_new_tokens=8), engine_config=ec, dtypes=dt)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)

    def tok(B, S):
        return jax.ShapeDtypeStruct((B, S), I32, sharding=one_chip)

    def compiled(fn, *args):
        return jax.jit(fn).lower(params, *args).compile().as_text()

    text = compiled(eng._make_gen(1, S, 8), tok(1, S), tok(1, S), rng)
    for kernel in ("%selective_scan", "%flash_attention_window", "%flash_attention", "%decode_attention"):
        assert kernel in text, f"{kernel}: not in the generate program"
    assert re.search(rf"%flash_attention_window(\.\d+)? = bf16\[40,{S},128\]", text)  # what the roofline readers find
    assert re.search(rf"%flash_attention(\.\d+)? = bf16\[40,{S},128\]", text)
    assert re.search(r"%decode_attention(\.\d+)? = bf16\[1,10,4,128\]", text)
    assert "f32[1,%d,16,5120]" % S not in text  # no state a position anywhere
    text = compiled(eng._make_gen_spec(S, 8), tok(1, S), tok(1, S), rng)
    assert "%selective_scan" in text  # the prefill's; the verify steps' scan is XLA's
    assert "f32[3,1,16,16,5120]" in text  # every fed position's state of the three state layers, kept for commit
    score, avals = eng._build_score_exact(S + 256, 256)
    assert "%selective_scan" in score.lower(params, *avals[1:]).compile().as_text()

    def aval(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    # the lower bucket's full-layer flash call fits the scoped VMEM (at 11264 it does not)
    q, kv = aval((1, 11776, 40, 128)), aval((1, 11776, 10, 128))
    assert "%flash_attention" in jax.jit(A.flash_attention).lower(q, kv, kv).compile().as_text()
    R, Di, N = 1, 5120, 16
    seq, scalars = aval((R, S, Di)), aval((R, S, N), F32)
    alone = jax.jit(ssm.selective_scan_pallas, static_argnames=("ungated",)).lower(
        seq, seq, seq, aval((N, Di), F32), scalars, scalars, aval((Di,), F32), aval((Di,), F32),
        aval((R, N, Di), F32), aval((R,), I32), ungated=True).compile().as_text()
    assert "%selective_scan" in alone and alone.count(f"bf16[1,{S},40,128]") >= 2  # y and, beside it, the memory
