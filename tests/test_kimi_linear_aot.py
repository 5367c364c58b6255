"""The gated delta-rule sparse-expert family's one-shot programs compiled,
without a chip, for a DESCRIBED ``v5e:2x2`` topology
(``tests/test_tpu_aot_compile.py`` is the pattern and holds the fixtures; the
case stood there until PR 57 and stands alone so that it rides another
worker)."""

import re

import jax
import jax.numpy as jnp

from test_tpu_aot_compile import one_chip, topo, uncached  # noqa: F401  (its fixtures: the described chip, no compile cache)
from test_tpu_aot_compile import I32


def test_delta_moe_programs_compile_with_their_kernels(one_chip, uncached):
    """The seventh decoder family's batch-1 programs (what its one-caller cell
    runs) at the published mixer geometry (hidden 2304 = 9 * 256, the first
    width here that 512 does not divide; 32 heads of 128 on both kinds; four
    taps; 256 experts of 1024 of which 16 are held, top 8) with a narrow dense
    FFN, a small vocabulary and the pattern K | K K M, through the Pallas path:
    the bucketed prefill (the chunked recurrence's kernel beside the latent
    flash kernel at 32 heads; the grouped expert matmul at 768-wide tiles of 2304)
    with the decode loop (the single-token step beside the absorbed decode
    kernel), the verify loop with ``commit`` (the step's k, v, g and beta kept
    for the replay; the state as it was), and the exact scorer all lower for
    the chip; no program copies the float32 state stack."""
    from rag_llm_k8s_tpu.core.config import (
        DeltaMoEConfig, DTypePolicy, EngineConfig, GoodputConfig, PrefixCacheConfig, SamplingConfig,
    )
    from rag_llm_k8s_tpu.engine import engine as engine_mod
    from rag_llm_k8s_tpu.models.delta_moe import init_delta_moe_params
    cfg = DeltaMoEConfig(vocab_size=1024, intermediate_size=512, num_hidden_layers=4, kda_layers=(1, 2, 3),
                         full_attn_layers=(4,), ep_size=16)
    dt = DTypePolicy()
    shapes = jax.eval_shape(lambda: init_delta_moe_params(jax.random.PRNGKey(0), cfg, dt))
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), shapes)
    ec = EngineConfig(prompt_buckets=(4096,), max_seq_len=4096 + 256, attn_impl="pallas", speculative="prompt_lookup",
                      goodput=GoodputConfig(enabled=False), prefix_cache=PrefixCacheConfig(enabled=False))
    eng = engine_mod.InferenceEngine(
        cfg, params, sampling=SamplingConfig(do_sample=False, max_new_tokens=8), engine_config=ec, dtypes=dt)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    tok = jax.ShapeDtypeStruct((1, 4096), I32, sharding=one_chip)

    def compiled(fn, *args):
        return jax.jit(fn).lower(params, *args).compile().as_text()

    state_copy = re.compile(r"= f32\[3,1,32,128,128\]\S* copy\(")
    text = compiled(eng._make_gen(1, 4096, 8), tok, tok, rng)
    for kernel in ("%delta_rule_chunked", "%mla_flash_attention", "%mla_decode_attention", "%grouped_matmul",
                   "%route_topk"):
        assert kernel in text, f"{kernel}: not in the batch-1 generate program"
    assert "f32[1,32,128,128]" in text and " conditional(" in text and not state_copy.search(text)
    # the bucket's recurrence is the kernel's: no triangular solve of a 64-position chunk is left
    assert "f32[1,32,1,64,64]" not in text
    text = compiled(eng._make_gen_spec(4096, 8), tok, tok, rng)
    assert "f32[3,1,16,32,128]" in text  # sixteen fed positions' k, v and g a linear layer, for commit's replay
    assert "f32[3,1,16,32,128,128]" not in text and not state_copy.search(text)  # and no state a position
    score, avals = eng._build_score_exact(4096 + 256, 256)
    assert "%grouped_matmul" in score.lower(params, *avals[1:]).compile().as_text()
