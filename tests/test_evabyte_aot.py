"""The block-window pooled-summary family's one-shot programs compiled, without a
chip, for a DESCRIBED ``v5e:2x2`` topology (``tests/test_tpu_aot_compile.py``
is the pattern and holds the fixtures; the case stood there until PR 57 and
stands alone so that it rides another worker)."""

import jax
import jax.numpy as jnp

from test_tpu_aot_compile import one_chip, topo, uncached  # noqa: F401  (its fixtures: the described chip, no compile cache)
from test_tpu_aot_compile import I32


def test_block_window_programs_compile_with_their_kernels(one_chip, uncached):
    """The fourth decoder family's five one-shot programs, at toy widths but
    the published attention geometry (heads of 128, windows of 2048 positions
    in chunks of 16, a 4096 bucket: two windows), through the Pallas path: the
    bucketed prefill (the window-and-summaries kernel) with the decode loop
    (the decode walk over the joined ring-and-summary plane), the verify loop,
    a prompt chunked past the largest bucket and the exact scorer (the XLA
    chunk form over the plane) all lower for the chip."""
    from rag_llm_k8s_tpu.core.config import (
        BlockWindowConfig, DTypePolicy, EngineConfig, GoodputConfig, SamplingConfig,
    )
    from rag_llm_k8s_tpu.engine import engine as engine_mod
    from rag_llm_k8s_tpu.models.block_window import init_block_window_params
    cfg = BlockWindowConfig(vocab_size=320, hidden_size=256, intermediate_size=512, num_hidden_layers=2,
                            num_attention_heads=2, num_key_value_heads=2, max_seq_len=16384)
    dt = DTypePolicy()
    shapes = jax.eval_shape(lambda: init_block_window_params(jax.random.PRNGKey(0), cfg, dt))
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), shapes)
    ec = EngineConfig(prompt_buckets=(4096,), max_seq_len=4096 + 128, attn_impl="pallas", speculative="prompt_lookup",
                      goodput=GoodputConfig(enabled=False), max_chunked_prompt=8192)
    eng = engine_mod.InferenceEngine(
        cfg, params, sampling=SamplingConfig(do_sample=False, max_new_tokens=8),
        engine_config=ec, dtypes=dt)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)

    def tok(B, S):
        return jax.ShapeDtypeStruct((B, S), I32, sharding=one_chip)

    def compiled(fn, *args):
        return jax.jit(fn).lower(params, *args).compile().as_text()

    text = compiled(eng._make_gen(2, 4096, 8), tok(2, 4096), tok(2, 4096), rng)
    for kernel in ("%window_summary_flash_attention", "%ring_summary_decode_attention", "%chunk_pool.", "%chunk_pool_in_place"):
        assert kernel in text, f"{kernel}: not in the batched generate program"
    assert "%decode_attention" not in text  # the walk carries the family's name here
    text = compiled(eng._make_gen_spec(4096, 8), tok(1, 4096), tok(1, 4096), rng)
    assert "%window_summary_flash_attention" in text and "tpu_custom_call" in text
    text = compiled(eng._make_gen(1, 8192, 8, 4096), tok(1, 8192), tok(1, 8192), rng)
    assert "%ring_summary_decode_attention" in text  # chunks through the ring, then the decode walk
    score, avals = eng._build_score_exact(4096 + 256, 256)
    assert score.lower(params, *avals[1:]).compile() is not None
