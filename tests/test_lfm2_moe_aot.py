"""The gated-convolution sparse-expert family's one-shot programs compiled,
without a chip, for a DESCRIBED ``v5e:2x2`` topology
(``tests/test_tpu_aot_compile.py`` is the pattern and holds the fixtures; the
case stood there until PR 57 and stands alone so that it rides another
worker)."""

import jax
import jax.numpy as jnp

from test_tpu_aot_compile import one_chip, topo, uncached  # noqa: F401  (its fixtures: the described chip, no compile cache)
from test_tpu_aot_compile import I32


def test_conv_moe_programs_compile_with_their_kernels(one_chip, uncached):
    """The sixth decoder family's batch-1 programs (what its one-caller cell
    runs), at the published operator geometry (hidden 2048: 32 query heads over
    8 KV heads of SIXTY-FOUR, a head no other decoder has; three taps; 64
    experts, all held, top 4) with narrow FFNs, a small vocabulary and one
    layer of each kind behind a dense one, through the Pallas path: the
    bucketed prefill (the flash kernel at 64 lanes) with the decode loop, whose
    single-token step takes the grouped chunk kernel (Mosaic refuses the decode
    walk's copy out of a 64-lane plane: PERF.md section 7), the verify loop
    with ``commit`` (the run of gated inputs kept for it), a prompt chunked
    past the largest bucket and the exact scorer all lower for the chip."""
    from rag_llm_k8s_tpu.core.config import (
        ConvMoEConfig, DTypePolicy, EngineConfig, GoodputConfig, PrefixCacheConfig, SamplingConfig,
    )
    from rag_llm_k8s_tpu.engine import engine as engine_mod
    from rag_llm_k8s_tpu.models.conv_moe import init_conv_moe_params
    cfg = ConvMoEConfig(vocab_size=1024, intermediate_size=512, moe_intermediate_size=256,
                        layer_types=("conv", "full_attention", "conv"), num_dense_layers=1,
                        tie_word_embeddings=False)
    dt = DTypePolicy()
    shapes = jax.eval_shape(lambda: init_conv_moe_params(jax.random.PRNGKey(0), cfg, dt))
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

    text = compiled(eng._make_gen(1, 4096, 8), tok(1, 4096), tok(1, 4096), rng)
    for kernel in ("%flash_attention", "%chunk_attention_grouped", "%grouped_matmul"):
        assert kernel in text, f"{kernel}: not in the batch-1 generate program"
    assert "%decode_attention" not in text  # a head of 64: the step is a chunk of one position
    text = compiled(eng._make_gen_spec(4096, 8), tok(1, 4096), tok(1, 4096), rng)
    assert "%chunk_attention_grouped" in text and "bf16[2,1,18,2048]" in text  # 2 + 16 gated inputs a conv layer, for commit
    text = compiled(eng._make_gen(1, 8192, 8, 4096), tok(1, 8192), tok(1, 8192), rng)
    assert "%chunk_prefill_attention" in text and "%chunk_attention_grouped" in text
    score, avals = eng._build_score_exact(4096 + 256, 256)
    assert "%grouped_matmul" in score.lower(params, *avals[1:]).compile().as_text()
