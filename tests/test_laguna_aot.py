"""The windowed-attention sparse-expert family's batched generate program
compiled, without a chip, for a DESCRIBED ``v5e:2x2`` topology
(``tests/test_tpu_aot_compile.py`` is the pattern and holds the fixtures; the
case stood there until PR 57 and stands alone so that it rides another
worker)."""

import re

import jax
import jax.numpy as jnp

from rag_llm_k8s_tpu.ops import attention as A

from test_tpu_aot_compile import one_chip, topo, uncached  # noqa: F401  (its fixtures: the described chip, no compile cache)
from test_tpu_aot_compile import HD, I32, K, T


def test_windowed_moe_generate_program_compiles_with_its_kernels(one_chip, uncached):
    """The third decoder family's batched generate program, at toy widths but
    the published attention geometry (72 and 48 query heads over 8 KV heads of
    128, window 512 under a 4096 bucket, a batch that goes a row at a time),
    through the Pallas path: the windowed and the full flash prefill, the
    decode kernel on both layer kinds and the grouped expert matmul all lower
    for the chip inside one program. The windowed prefill is the ONE-STEP
    kernel at ``[72, 4096, 128]`` (``flash_window_step``: 1152 rows against a
    slice of 640 keys beside the resident strips, which ``_flash_fits`` reckons
    at 12 bytes a key), inside the default scoped VMEM."""
    from rag_llm_k8s_tpu.core.config import (
        DTypePolicy, EngineConfig, GoodputConfig, SamplingConfig, WindowedMoEConfig,
    )
    from rag_llm_k8s_tpu.engine import engine as engine_mod
    from rag_llm_k8s_tpu.models.windowed_moe import init_windowed_moe_params
    kinds = ("full_attention", "sliding_attention", "sliding_attention", "full_attention")
    cfg = WindowedMoEConfig.tiny(
        vocab_size=512, hidden_size=256, intermediate_size=512, moe_intermediate_size=128,
        shared_expert_intermediate_size=128, num_kv_heads=K, head_dim=HD, sliding_window=512,
        layer_types=kinds, num_attention_heads_per_layer=(48, 72, 72, 48),
        mlp_layer_types=("dense", "sparse", "sparse", "sparse"), max_seq_len=8192)
    dt = DTypePolicy()
    shapes = jax.eval_shape(lambda: init_windowed_moe_params(jax.random.PRNGKey(0), cfg, dt))
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), shapes)
    ec = EngineConfig(prompt_buckets=(4096,), max_seq_len=T, attn_impl="pallas", speculative="off",
                      goodput=GoodputConfig(enabled=False))
    eng = engine_mod.InferenceEngine(
        cfg, params, sampling=SamplingConfig(do_sample=False, max_new_tokens=8),
        engine_config=ec, dtypes=dt)
    fn = eng._make_gen(4, 4096, 8)
    tok = jax.ShapeDtypeStruct((4, 4096), I32, sharding=one_chip)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    assert A.flash_window_step(4096, 72 // K, HD, HD, cfg.sliding_window) == (128, 640)
    built = []
    one_step = A._window_call
    A._window_call = lambda qt, *a, **kw: built.append(qt.shape) or one_step(qt, *a, **kw)
    try:
        text = jax.jit(fn).lower(params, tok, tok, rng).compile().as_text()
    finally:
        A._window_call = one_step
    for kernel in ("%flash_attention_window", "%flash_attention.", "%decode_attention", "%grouped_matmul"):
        assert kernel in text, f"{kernel}: not in the compiled program"
    # a row at a time: the call the benchmark's roofline reader finds, built by
    # the one-step form, and no kernel of the program asks for more scoped VMEM
    assert built and set(built) == {(72, 4096, HD)}, built
    assert re.search(rf"%flash_attention_window(\.\d+)? = bf16\[72,4096,{HD}\]\S* custom-call\(", text)
    assert "scoped_memory_configs" in text and '"scoped_memory_configs":[{' not in text
