"""The hybrid state-space family's one-shot programs compiled, without a chip,
for a DESCRIBED ``v5e:2x2`` topology (``tests/test_tpu_aot_compile.py`` is the
pattern and holds the fixtures; the case stood there until PR 57 and stands
alone so that it rides another worker)."""

import jax
import jax.numpy as jnp

from test_tpu_aot_compile import one_chip, topo, uncached  # noqa: F401  (its fixtures: the described chip, no compile cache)
from test_tpu_aot_compile import BF16, F32, I32


def test_hybrid_ssm_programs_compile_with_their_kernels(one_chip, uncached):
    """The fifth decoder family's five one-shot programs, at the published
    mixer geometry (hidden 2560: 20 query heads over ONE KV head of 128, a
    group no other family has; d_inner 5120, 16 states, dt rank 160) with a
    narrow SwiGLU, a small vocabulary and one layer of each kind, through the
    Pallas path: the bucketed prefill (the selective-scan kernel, the flash
    kernel) with the decode loop (the decode walk at a group of 20), the
    verify loop with ``commit`` (the XLA scan that keeps every position's
    state), a prompt chunked past the largest bucket and the exact scorer
    (the scan kernel from the state it is handed) all lower for the chip. The
    scan kernel alone at the served batch: 8 rows of 4096."""
    from rag_llm_k8s_tpu.core.config import (
        DTypePolicy, EngineConfig, GoodputConfig, HybridSSMConfig, PrefixCacheConfig, SamplingConfig,
    )
    from rag_llm_k8s_tpu.engine import engine as engine_mod
    from rag_llm_k8s_tpu.models.hybrid_ssm import init_hybrid_ssm_params
    from rag_llm_k8s_tpu.ops import ssm
    cfg = HybridSSMConfig(vocab_size=1024, intermediate_size=512, num_hidden_layers=2, attn_layer_period=2,
                          attn_layer_offset=1, tie_word_embeddings=False)
    dt = DTypePolicy()
    shapes = jax.eval_shape(lambda: init_hybrid_ssm_params(jax.random.PRNGKey(0), cfg, dt))
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), shapes)
    ec = EngineConfig(prompt_buckets=(4096,), max_seq_len=4096 + 256, attn_impl="pallas", speculative="prompt_lookup",
                      goodput=GoodputConfig(enabled=False), prefix_cache=PrefixCacheConfig(enabled=False),
                      max_chunked_prompt=8192)
    eng = engine_mod.InferenceEngine(
        cfg, params, sampling=SamplingConfig(do_sample=False, max_new_tokens=8), engine_config=ec, dtypes=dt)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)

    def tok(B, S):
        return jax.ShapeDtypeStruct((B, S), I32, sharding=one_chip)

    def compiled(fn, *args):
        return jax.jit(fn).lower(params, *args).compile().as_text()

    text = compiled(eng._make_gen(2, 4096, 8), tok(2, 4096), tok(2, 4096), rng)
    for kernel in ("%selective_scan", "%flash_attention", "%decode_attention"):
        assert kernel in text, f"{kernel}: not in the batched generate program"
    text = compiled(eng._make_gen_spec(4096, 8), tok(1, 4096), tok(1, 4096), rng)
    assert "%selective_scan" in text  # the prefill's; the verify steps' scan is XLA's
    assert "f32[1,1,16,16,5120]" in text  # every fed position's state, kept for commit
    text = compiled(eng._make_gen(1, 8192, 8, 4096), tok(1, 8192), tok(1, 8192), rng)
    assert "%selective_scan" in text and "%decode_attention" in text
    score, avals = eng._build_score_exact(4096 + 256, 256)
    assert "%selective_scan" in score.lower(params, *avals[1:]).compile().as_text()

    def aval(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    R, S, Di, N = 8, 4096, 5120, 16
    seq, scalars = aval((R, S, Di)), aval((R, S, N), F32)
    alone = jax.jit(ssm.selective_scan_pallas).lower(
        seq, seq, seq, aval((N, Di), F32), scalars, scalars, aval((Di,), F32), aval((Di,), F32),
        aval((R, N, Di), F32), aval((R,), I32)).compile()
    assert "%selective_scan" in alone.as_text()
    assert "f32[8,4096,16,5120]" not in alone.as_text()  # no state a position anywhere
