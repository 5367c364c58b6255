"""The state-space-duality latent-expert family's batch-1 programs compiled,
without a chip, for a DESCRIBED ``v5e:2x2`` topology at the published mixer
widths and the cell's bucket (``tests/test_tpu_aot_compile.py`` is the
pattern, and the longest file of a run: this one stands alone so that it rides
another worker)."""

import re

import jax
import jax.numpy as jnp
import pytest

from test_tpu_aot_compile import one_chip, topo, uncached  # noqa: F401  (its fixtures: the described chip, no compile cache)


@pytest.fixture(scope="module")
def built(one_chip, uncached):
    """The engine over abstract parameters at the published widths of every
    layer kind (128 Mamba-2 heads of 64 at state 128 in 8 groups, 32 query
    heads over 2 KV heads of 128, 512 experts of 1024 <-> 2688 of which 128
    are held, top 22, a 5376-wide shared expert) on a cut pattern ``MEM*E``
    and a small vocabulary: a trip's branches are the cell's."""
    from rag_llm_k8s_tpu.core.config import (
        DTypePolicy, EngineConfig, GoodputConfig, PrefixCacheConfig, SamplingConfig, SSDMoEConfig,
    )
    from rag_llm_k8s_tpu.engine import engine as engine_mod
    from rag_llm_k8s_tpu.models.ssd_moe import init_ssd_moe_params

    cfg = SSDMoEConfig(vocab_size=1024, num_hidden_layers=5, hybrid_override_pattern="MEM*E", ep_size=4)
    dt = DTypePolicy()
    shapes = jax.eval_shape(lambda: init_ssd_moe_params(jax.random.PRNGKey(0), cfg, dt))
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), shapes)
    ec = EngineConfig(prompt_buckets=(4096,), max_seq_len=4096 + 256, attn_impl="pallas", speculative="prompt_lookup",
                      goodput=GoodputConfig(enabled=False), prefix_cache=PrefixCacheConfig(enabled=False))
    eng = engine_mod.InferenceEngine(
        cfg, params, sampling=SamplingConfig(do_sample=False, max_new_tokens=8), engine_config=ec, dtypes=dt)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    tok = jax.ShapeDtypeStruct((1, 4096), jnp.int32, sharding=one_chip)
    return eng, params, tok, rng


STATE_COPY = re.compile(r"= f32\[2,1,128,64,128\]\S* copy\(")


def test_the_generate_program_compiles_with_its_kernels(built):
    """The bucketed prefill (the flash kernel at 32 over 2 heads, the router's
    kernel over 512 outputs and 22 rounds, the grouped expert matmul at 1024
    <-> 2688, the chunked recurrence in XLA) with the decode loop (the decode
    walk, the single-position update of a ``[128, 64, 128]`` float32 state);
    a trip's kind is a branch, and no program copies the state stack."""
    eng, params, tok, rng = built
    text = jax.jit(eng._make_gen(1, 4096, 8)).lower(params, tok, tok, rng).compile().as_text()
    for kernel in ("%flash_attention", "%decode_attention", "%grouped_matmul", "%route_topk"):
        assert kernel in text, f"{kernel}: not in the batch-1 generate program"
    assert "f32[1,128,64,128]" in text and " conditional(" in text and not STATE_COPY.search(text)


def test_the_verify_program_keeps_the_step_and_not_a_state_a_position(built):
    eng, params, tok, rng = built
    text = jax.jit(eng._make_gen_spec(4096, 8)).lower(params, tok, tok, rng).compile().as_text()
    assert "f32[2,1,16,128]" in text  # sixteen fed positions' time steps a Mamba-2 layer, for commit's replay
    assert "f32[2,1,16,128,64,128]" not in text and not STATE_COPY.search(text)  # and no state a position


def test_the_exact_scorer_compiles(built):
    eng, params, _, _ = built
    score, avals = eng._build_score_exact(4096 + 256, 256)
    assert "%grouped_matmul" in score.lower(params, *avals[1:]).compile().as_text()
