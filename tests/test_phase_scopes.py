"""Phase scopes inside the compiled programs, and the record of every dispatch
(ISSUE 24).

The first half is the CPU twin of the benchmark's ``unscoped_device_time_share``:
every executable the serving path runs is lowered at a toy size, and every
operation the PROGRAM traced (an instruction of the optimized HLO whose
``op_name`` is a path from its ``jit``) must name a phase of
``obs/tracing.PHASES``. What the compiler writes itself carries no path and
differs by backend, so it is the chip's to judge (the benchmark's reader and
its metric); of ``benchmark/`` only its phase reader is imported, by one test. The second half
drives the dispatch counters and spans.
"""

import re
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from rag_llm_k8s_tpu.core.config import (
    AppConfig,
    DTypePolicy,
    EncoderConfig,
    EngineConfig,
    LlamaConfig,
    PrefixCacheConfig,
    SamplingConfig,
)
from rag_llm_k8s_tpu.engine.batching import BatchScheduler
from rag_llm_k8s_tpu.engine.continuous import ContinuousEngine
from rag_llm_k8s_tpu.engine.encoder import EncoderRunner
from rag_llm_k8s_tpu.engine.engine import InferenceEngine
from rag_llm_k8s_tpu.index.store import VectorStore
from rag_llm_k8s_tpu.models.bge_m3 import init_encoder_params
from rag_llm_k8s_tpu.models.llama import init_llama_params
from rag_llm_k8s_tpu.obs import metrics as obs_metrics
from rag_llm_k8s_tpu.obs import tracing
from rag_llm_k8s_tpu.ops.knn import knn_topk_xla
from rag_llm_k8s_tpu.server.app import RagService, create_app

FP32 = DTypePolicy.fp32()
GREEDY = SamplingConfig(do_sample=False, max_new_tokens=6)
PC = PrefixCacheConfig(
    enabled=True, max_prefix_tokens=64, segment_buckets=(16,),
    suffix_buckets=(16,), hbm_budget_mb=64,
)
EC = EngineConfig(
    prompt_buckets=(32, 64), max_batch_size=2, max_seq_len=128,
    speculative="prompt_lookup", prefix_cache=PC,
)
# opcodes that only carry values around: no device time is theirs to file
PLUMBING = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast",
            "while", "conditional", "call")


class ByteTokenizer:
    def encode(self, text):
        return [b + 3 for b in text.encode("utf-8")]

    def decode(self, ids, skip_special_tokens=True):
        return bytes((i - 3) % 256 for i in ids if i >= 3).decode("utf-8", "replace")


# ---------------------------------------------------------------------------
# (a) every executable arrives scoped; (b) the helper's vocabulary is closed
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def llama():
    cfg = LlamaConfig.tiny(vocab_size=300)
    return cfg, init_llama_params(jax.random.PRNGKey(0), cfg, FP32)


@pytest.fixture(scope="module")
def engine(llama):
    cfg, params = llama
    return InferenceEngine(cfg, params, sampling=GREEDY, engine_config=EC, dtypes=FP32)


@pytest.fixture(scope="module")
def continuous(llama):
    cfg, params = llama
    import dataclasses

    ec = dataclasses.replace(
        EC, speculative="off", kv_paged=True, kv_block_size=16,
        interleave_prefill=True, prefill_chunk_tokens=16, spec_paged=True,
    )
    return ContinuousEngine(cfg, params, sampling=GREEDY, engine_config=ec, dtypes=FP32)


@pytest.fixture(scope="module")
def encoder():
    cfg = EncoderConfig.tiny(vocab_size=300)
    return EncoderRunner(cfg, init_encoder_params(jax.random.PRNGKey(1), cfg, FP32),
                         dtypes=FP32, length_buckets=(32,), max_batch=4)


def _encoder_program(encoder):
    i32 = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    return encoder._jit.lower(encoder.params, i32, i32).compile()


def _knn_program(_):
    f32 = jnp.float32
    return knn_topk_xla.lower(
        jax.ShapeDtypeStruct((2, 64), f32), jax.ShapeDtypeStruct((512, 64), f32),
        jax.ShapeDtypeStruct((1, 512), f32), k=3).compile()


def _compiled(staged):
    """A builder's ``(jitted, avals)`` through the stages ``build_span`` runs."""
    jitted, avals = staged
    return jitted.trace(*avals).lower().compile()


ENGINE_PROGRAMS = {
    "generate": lambda e: _compiled(e._build_generate(2, 32, 6)),
    "generate_chunked": lambda e: _compiled(e._build_generate(1, 128, 6, chunk=64)),
    "generate_spec": lambda e: _compiled(e._build_generate_spec(32, 6)),
    "generate_rag": lambda e: _compiled(e._build_generate_rag(64, 6, 16, 24, 8, 16, 2, 3, False)),
    "generate_rag_spec": lambda e: _compiled(
        e._build_generate_rag(64, 6, 16, 24, 8, 16, 2, 3, True)),
    "generate_prefixed": lambda e: _compiled(e._build_generate_prefixed(16, 6)),
    "segment_kv": lambda e: _compiled(e._build_segment_kv(16)),
    "score_exact": lambda e: _compiled(e._build_score_exact(64, 32)),
}
CONTINUOUS_PROGRAMS = {
    "continuous_prefill": lambda c: _compiled(c._build_prefill_paged(32)),
    "continuous_insert": lambda c: _compiled(c._build_insert_paged(32)),
    "continuous_step": lambda c: _compiled(c._build_step_paged(1)),
    "continuous_verify": lambda c: _compiled(c._build_verify_paged(3)),
    "continuous_mixed": lambda c: _compiled(c._build_mixed_step(16)),
}
RETRIEVE_PROGRAMS = {"encoder": _encoder_program, "knn": _knn_program}
# the phase each program's operations must be filed under (any of them)
EXPECTED = {
    "generate": {"prefill", "decode"}, "generate_chunked": {"prefill", "decode"},
    "generate_spec": {"prefill", "verify"},
    "generate_rag": {"retrieve", "prefill", "decode"},
    "generate_rag_spec": {"retrieve", "prefill", "verify"},
    "generate_prefixed": {"prefill", "decode"}, "segment_kv": {"prefill"},
    "score_exact": {"score"}, "continuous_prefill": {"prefill"},
    "continuous_insert": {"prefill"}, "continuous_step": {"decode"},
    "continuous_verify": {"verify"}, "continuous_mixed": {"mixed"},
    "encoder": {"retrieve"}, "knn": {"retrieve"},
}


_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = \S+ ([\w\-]+)\(.*op_name=\"(jit\([^\"]*)\"")


def _scope(op_name):
    """``(phase, sub-scope)`` of a traced path: the first component that is
    a phase, and the first sub-scope (or sampler) after it; None outside."""
    parts = op_name.split("/")
    for i, part in enumerate(parts):
        if part in tracing.PHASES:
            subs = tracing.SUB_SCOPES + ("sample",)
            return part, next((p for p in parts[i + 1:] if p in subs), "")
    return None, ""


def _traced(compiled):
    """``[(opcode, op_name)]`` of the optimized program's instructions that
    carry a path the program traced, plumbing apart."""
    found = (_INSTRUCTION.match(line) for line in compiled.as_text().splitlines())
    return [m.groups() for m in found if m and m.group(1) not in PLUMBING]


def _assert_scoped(name, compiled):
    rows = _traced(compiled)
    assert len(rows) > 3, compiled.as_text()[:2000]
    unscoped = [row for row in rows if _scope(row[1])[0] is None]
    assert not unscoped, f"{name}: operations outside every phase scope: {unscoped}"
    assert {_scope(path)[0] for _, path in rows} == EXPECTED[name], name


@pytest.mark.parametrize("name", sorted(ENGINE_PROGRAMS))
def test_engine_programs_arrive_scoped(engine, name):
    _assert_scoped(name, ENGINE_PROGRAMS[name](engine))


@pytest.mark.parametrize("name", sorted(CONTINUOUS_PROGRAMS))
def test_continuous_programs_arrive_scoped(continuous, name):
    _assert_scoped(name, CONTINUOUS_PROGRAMS[name](continuous))


@pytest.mark.parametrize("name", sorted(RETRIEVE_PROGRAMS))
def test_retrieve_programs_arrive_scoped(encoder, name):
    _assert_scoped(name, RETRIEVE_PROGRAMS[name](encoder))


def test_model_sub_scopes_are_named(engine):
    """The decoder files its work under the sub-scopes, inside whichever phase
    traced it: a decode step's attention is ``decode/attn``."""
    found = {_scope(path) for _, path in _traced(ENGINE_PROGRAMS["generate"](engine))}
    for sub in ("attn", "mlp", "lm_head", "norm_rope", "embed"):
        assert ("prefill", sub) in found and ("decode", sub) in found, (sub, sorted(found))
    assert ("decode", "sample") in found and ("prefill", "sample") in found


def test_the_programs_state_what_the_benchmark_counts_by(engine):
    """A step is counted by the operations traced in the body of the ``while``
    that stands right under ``decode`` / ``verify``; a prefill's rows are the
    ``rows<N>`` its scope states, by the layers' loop beneath it."""
    for name, phase, rows in (("generate", "decode", 2), ("generate_spec", "verify", 1),
                              ("generate_rag", "decode", 1), ("generate_rag_spec", "verify", 1),
                              ("generate_prefixed", "decode", 1)):
        paths = [path for _, path in _traced(ENGINE_PROGRAMS[name](engine))]
        assert any(f"/{phase}/while/body/" in p for p in paths), (name, paths[:5])
        layers = [p for p in paths if f"/prefill/rows{rows}/" in p and "/while/body/" in p]
        assert layers, (name, [p for p in paths if "/prefill/" in p][:5])
        assert not [p for p in paths if "/prefill/" in p and f"/prefill/rows{rows}/" not in p]


def test_a_live_suffix_prefill_still_counts_its_rows(llama, tmp_path):
    """A bucket with rungs (``models/llama.py live_offsets``) puts the layers'
    matmuls in branches. The benchmark counts a prefill's rows from the
    operations of the layers' loop that stand in NO branch, so the loop has
    to keep some: its own reader (``benchmark/lib/phases.py``, the one piece
    of the benchmark imported here) reduces a CPU capture of the program."""
    from benchmark.lib import phases, trace

    cfg, params = llama
    ec = EngineConfig(prompt_buckets=(1280,), max_batch_size=2, max_seq_len=1408)
    eng = InferenceEngine(cfg, params, sampling=GREEDY, engine_config=ec, dtypes=FP32)
    prompts = [list(range(5, 905)), list(range(7, 807))]
    eng.generate(prompts)  # compiled outside the capture
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(2):
        eng.generate(prompts)
    jax.profiler.stop_trace()
    reduced = phases.reduce_phases(phases.load(trace.find_xplane(str(tmp_path))), cfg.num_layers)
    assert reduced["prefill_rows"] == 2 * 2  # the batch, twice
    assert reduced["steps"].get("decode", 0) > 0
    assert eng.stats.family_counters["prefill_tokens_computed"] == 3 * 2 * (1280 - 320)


LATENT_PROGRAMS = {k: ENGINE_PROGRAMS[k] for k in (
    "generate", "generate_chunked", "generate_spec", "generate_rag", "generate_rag_spec", "score_exact")}


@pytest.fixture(scope="module")
def latent_engine():
    """The latent-attention sparse-expert family through the same programs
    (no prefix cache: the family refuses it)."""
    import dataclasses

    from rag_llm_k8s_tpu.core.config import LatentMoEConfig
    from rag_llm_k8s_tpu.models.latent_moe import init_latent_moe_params

    cfg = LatentMoEConfig.tiny(vocab_size=300)
    params = init_latent_moe_params(jax.random.PRNGKey(0), cfg, FP32)
    ec = dataclasses.replace(EC, prefix_cache=PrefixCacheConfig(enabled=False), attn_impl="xla")
    return InferenceEngine(cfg, params, sampling=GREEDY, engine_config=ec, dtypes=FP32)


@pytest.mark.parametrize("name", sorted(LATENT_PROGRAMS))
def test_latent_moe_programs_arrive_scoped(latent_engine, name):
    """Every operation of the second decoder family carries a phase."""
    _assert_scoped(name, LATENT_PROGRAMS[name](latent_engine))


def test_latent_moe_fine_scopes_sit_beneath_attn_and_mlp(latent_engine):
    """``attn/latent``, ``mlp/router``, ``mlp/experts``, ``mlp/shared`` are in
    the vocabulary and BENEATH the sub-scopes a reader already knows, so an
    operation of them still files under ``decode/attn`` or ``decode/mlp``; the
    leading dense layer stands outside the layers' loop and the MoE layers in
    it, which is what a prefill's rows are counted by."""
    assert set(tracing.FINE_SCOPES) == {"latent", "router", "experts", "shared", "zero", "dense",
                                        "window", "global", "gate", "ring", "pool", "scan", "conv",
                                        "kda", "delta", "cross", "gmu", "diff", "ssd"}
    assert set(tracing.FINE_SCOPES) <= tracing.SCOPE_NAMES
    paths = [path for _, path in _traced(LATENT_PROGRAMS["generate"](latent_engine))]
    for phase in ("prefill", "decode"):
        for sub, fine in (("attn", "latent"), ("mlp", "router"), ("mlp", "experts"), ("mlp", "shared")):
            hits = [p for p in paths if f"/{phase}/" in p and f"/{sub}/{fine}/" in p]
            assert hits and all(_scope(p) == (phase, sub) for p in hits), (phase, sub, fine)
    dense = [p for p in paths if "/prefill/rows2/" in p and "/dense_0/" in p]
    assert dense and not [p for p in dense if "/while/" in p.split("/dense_0/")[0]]
    assert [p for p in dense if "/mlp/dense/" in p] and not [p for p in paths if "/mlp/zero/" in p]
    looped = [p for p in paths if "/prefill/rows2/" in p and "/while/body/" in p and "/layers/" in p]
    assert looped and not [p for p in looped if "/dense_0/" in p]


@pytest.fixture(scope="module")
def windowed_engine():
    """The windowed-attention sparse-expert family through the same programs."""
    import dataclasses

    from rag_llm_k8s_tpu.core.config import WindowedMoEConfig
    from rag_llm_k8s_tpu.models.windowed_moe import init_windowed_moe_params

    cfg = WindowedMoEConfig.tiny(vocab_size=300)
    params = init_windowed_moe_params(jax.random.PRNGKey(0), cfg, FP32)
    ec = dataclasses.replace(EC, prefix_cache=PrefixCacheConfig(enabled=False), attn_impl="xla")
    return InferenceEngine(cfg, params, sampling=GREEDY, engine_config=ec, dtypes=FP32)


@pytest.mark.parametrize("name", sorted(LATENT_PROGRAMS))
def test_windowed_moe_programs_arrive_scoped(windowed_engine, name):
    """Every operation of the third decoder family carries a phase."""
    _assert_scoped(name, LATENT_PROGRAMS[name](windowed_engine))


def test_windowed_moe_fine_scopes_sit_beneath_attn_and_mlp(windowed_engine):
    """``attn/window``, ``attn/global`` and ``attn/gate`` are BENEATH ``attn``
    (a reader that knows no finer scope still files them under
    ``decode/attn``); a period's layers sit in the layers' loop (``periods``)
    each under its own name, the leading dense layer outside it."""
    paths = [path for _, path in _traced(LATENT_PROGRAMS["generate"](windowed_engine))]
    for phase in ("prefill", "decode"):
        for sub, fine in (("attn", "window"), ("attn", "global"), ("attn", "gate"), ("mlp", "router"),
                          ("mlp", "experts"), ("mlp", "shared"), ("mlp", "dense")):
            hits = [p for p in paths if f"/{phase}/" in p and f"/{sub}/{fine}/" in p]
            assert hits and all(_scope(p) == (phase, sub) for p in hits), (phase, sub, fine)
    lead = [p for p in paths if "/prefill/rows2/" in p and "/lead_0/" in p]
    assert lead and not [p for p in lead if "/while/" in p.split("/lead_0/")[0]]
    assert [p for p in lead if "/attn/global/" in p] and not [p for p in lead if "/attn/window/" in p]
    looped = [p for p in paths if "/prefill/rows2/" in p and "/periods/" in p and "/while/body/" in p]
    assert looped and not [p for p in looped if "/lead_0/" in p]
    for sub, fine in (("l0", "window"), ("l1", "window"), ("l2", "global")):  # the toy period: two sliding, one full
        assert [p for p in looped if f"/periods/{sub}/" in p and f"/attn/{fine}/" in p], (sub, fine)
    assert not [p for p in looped if ("/periods/l2/" in p and "/attn/window/" in p)
                or ("/periods/l0/" in p and "/attn/global/" in p)]


@pytest.fixture(scope="module")
def block_window_engine():
    """The block-window pooled-summary family through the same programs."""
    import dataclasses

    from rag_llm_k8s_tpu.core.config import BlockWindowConfig
    from rag_llm_k8s_tpu.models.block_window import init_block_window_params

    cfg = BlockWindowConfig.tiny(vocab_size=300)
    params = init_block_window_params(jax.random.PRNGKey(0), cfg, FP32)
    ec = dataclasses.replace(EC, prefix_cache=PrefixCacheConfig(enabled=False), attn_impl="xla")
    return InferenceEngine(cfg, params, sampling=GREEDY, engine_config=ec, dtypes=FP32)


@pytest.mark.parametrize("name", sorted(LATENT_PROGRAMS))
def test_block_window_programs_arrive_scoped(block_window_engine, name):
    """Every operation of the fourth decoder family carries a phase."""
    _assert_scoped(name, LATENT_PROGRAMS[name](block_window_engine))


def test_block_window_fine_scopes_sit_beneath_attn(block_window_engine):
    """``attn/ring`` and ``attn/pool`` are BENEATH ``attn`` in a prefill and in
    a decode step; a prefill's ROWS are unrolled, each its own trip through
    the layers' loop, so an operation of the loop is ONE loop deep in every
    row (what ``benchmark/lib/phases.py`` counts a prefill's rows by)."""
    paths = [path for _, path in _traced(LATENT_PROGRAMS["generate"](block_window_engine))]
    for phase in ("prefill", "decode"):
        for fine in ("ring", "pool"):
            hits = [p for p in paths if f"/{phase}/" in p and f"/attn/{fine}/" in p]
            assert hits and all(_scope(p) == (phase, "attn") for p in hits), (phase, fine)
    looped = [p for p in paths if "/prefill/rows2/" in p and "/attn/ring/" in p]
    assert looped and all(p.split("/prefill/rows2/")[1].split("/").count("while") == 1 for p in looped)


@pytest.fixture(scope="module")
def hybrid_ssm_engine():
    """The hybrid state-space family through the same programs."""
    import dataclasses

    from rag_llm_k8s_tpu.core.config import HybridSSMConfig
    from rag_llm_k8s_tpu.models.hybrid_ssm import init_hybrid_ssm_params

    cfg = HybridSSMConfig.tiny(vocab_size=300, num_hidden_layers=4, attn_layer_period=2)
    params = init_hybrid_ssm_params(jax.random.PRNGKey(0), cfg, FP32)
    ec = dataclasses.replace(EC, prefix_cache=PrefixCacheConfig(enabled=False), attn_impl="xla")
    return InferenceEngine(cfg, params, sampling=GREEDY, engine_config=ec, dtypes=FP32)


@pytest.mark.parametrize("name", sorted(LATENT_PROGRAMS))
def test_hybrid_ssm_programs_arrive_scoped(hybrid_ssm_engine, name):
    """Every operation of the fifth decoder family carries a phase: the
    verify loop's ``commit`` among them."""
    _assert_scoped(name, LATENT_PROGRAMS[name](hybrid_ssm_engine))


def test_hybrid_ssm_fine_scopes_sit_beneath_attn(hybrid_ssm_engine):
    """``attn/scan``, ``attn/conv`` (a state layer) and ``attn/global`` (an
    attention layer) are BENEATH ``attn`` in a prefill and in a decode step.
    Layers of both kinds are trips of ONE loop: the mixers are two loops deep
    in a decode step (the step loop, the layers' loop) and one in a prefill,
    in a branch; at this bucket, which has no rungs, the norms and the SwiGLU
    stand in the layers' loop outside any branch, which is what
    ``benchmark/lib/phases.py`` counts a prefill's rows by (a bucket with
    rungs: ``test_a_hybrid_live_suffix_prefill_still_counts_its_rows``)."""
    paths = [path for _, path in _traced(LATENT_PROGRAMS["generate"](hybrid_ssm_engine))]
    for phase in ("prefill", "decode"):
        for fine in ("scan", "conv", "global"):
            hits = [p for p in paths if f"/{phase}/" in p and "/attn/" in p and f"/{fine}/" in p.split("/attn/")[1]]
            assert hits and all(_scope(p) == (phase, "attn") for p in hits), (phase, fine)
            assert all("/cond/" in p or "/branch_" in p for p in hits), (phase, fine)
    looped = [p for p in paths if "/prefill/rows2/" in p and "/mlp/" in p]
    assert looped and all(p.split("/prefill/rows2/")[1].split("/").count("while") == 1 for p in looped)
    assert not any("/cond/" in p or "/branch_" in p for p in looped)


@pytest.fixture(scope="module")
def conv_moe_engine():
    """The gated-convolution sparse-expert family through the same programs."""
    import dataclasses

    from rag_llm_k8s_tpu.core.config import ConvMoEConfig
    from rag_llm_k8s_tpu.models.conv_moe import init_conv_moe_params

    cfg = ConvMoEConfig.tiny(vocab_size=300)
    params = init_conv_moe_params(jax.random.PRNGKey(0), cfg, FP32)
    ec = dataclasses.replace(EC, prefix_cache=PrefixCacheConfig(enabled=False), attn_impl="xla")
    return InferenceEngine(cfg, params, sampling=GREEDY, engine_config=ec, dtypes=FP32)


@pytest.mark.parametrize("name", sorted(LATENT_PROGRAMS))
def test_conv_moe_programs_arrive_scoped(conv_moe_engine, name):
    """Every operation of the sixth decoder family carries a phase: the
    verify loop's ``commit`` among them."""
    _assert_scoped(name, LATENT_PROGRAMS[name](conv_moe_engine))


def test_conv_moe_fine_scopes_sit_beneath_attn_and_mlp(conv_moe_engine):
    """``attn/conv`` (a conv operator's gates, taps and state roll) and
    ``attn/global`` (an attention layer) are BENEATH ``attn``, ``mlp/router``,
    ``mlp/experts`` and ``mlp/dense`` beneath ``mlp``, in a prefill and in a
    decode step, the operators in no branch (a trip is a period of layers, each
    of its own kind). The two dense layers stand in front of the layers' loop and
    the sparse ones in it, which is what a prefill's rows are counted by."""
    paths = [path for _, path in _traced(LATENT_PROGRAMS["generate"](conv_moe_engine))]
    for phase in ("prefill", "decode"):
        for sub, fine in (("attn", "conv"), ("attn", "global"), ("mlp", "router"), ("mlp", "experts"), ("mlp", "dense")):
            hits = [p for p in paths if f"/{phase}/" in p and -1 < p.find(f"/{sub}/") < p.find(f"/{fine}/")]
            assert hits and all(_scope(p) == (phase, sub) for p in hits), (phase, sub, fine)
            if sub == "attn":  # the operators stand in no branch (the experts' combine chooses its form in one)
                assert not any("/cond/" in p or "/branch_" in p for p in hits), (phase, sub, fine)
    dense = [p for p in paths if "/prefill/rows2/" in p and "/mlp/dense/" in p]
    assert dense and all("/lead_" in p and "/while/" not in p.split("/lead_")[0] for p in dense)
    looped = [p for p in paths if "/prefill/rows2/" in p and "/shortconv/conv/" in p and "/periods/" in p]
    assert looped and all(p.split("/prefill/rows2/")[1].split("/shortconv/conv/")[0].split("/").count("while") == 1
                          for p in looped)
    # the operator's two projections are OUTSIDE ``attn/conv`` (its module is not named as the scope is)
    assert not [p for p in paths if "/conv/" in p and ("in_proj" in p or "out_proj" in p)]
    assert [p for p in paths if "/attn/shortconv/in_proj/" in p]
    verify = [path for _, path in _traced(LATENT_PROGRAMS["generate_spec"](conv_moe_engine))]
    assert [p for p in verify if "/verify/" in p and "/attn/shortconv/conv/" in p]


def test_a_hybrid_live_suffix_prefill_still_counts_its_rows(tmp_path):
    """The twin of ``test_a_live_suffix_prefill_still_counts_its_rows`` for
    the hybrid state-space family: at a bucket with rungs both norms, the
    mixer and the SwiGLU of a trip stand in branches (``models/hybrid_ssm.py
    live_trip``: the kind of mixer, and inside it the rung), and what stays
    outside every branch is the write of the layer's new state into the
    stacked ``conv`` / ``ssm``. The benchmark's reader needs ONE such
    operation a trip, not all of them: it still reads the rows, and the fine
    scopes still sit beneath ``attn``."""
    import dataclasses

    from benchmark.lib import phases, ssm_scopes, trace
    from rag_llm_k8s_tpu.core.config import HybridSSMConfig
    from rag_llm_k8s_tpu.models.hybrid_ssm import init_hybrid_ssm_params

    cfg = HybridSSMConfig.tiny(vocab_size=300, num_hidden_layers=4, attn_layer_period=2)
    params = init_hybrid_ssm_params(jax.random.PRNGKey(0), cfg, FP32)
    ec = dataclasses.replace(EC, prompt_buckets=(1280,), max_seq_len=1408, attn_impl="xla",
                             prefix_cache=PrefixCacheConfig(enabled=False))
    eng = InferenceEngine(cfg, params, sampling=GREEDY, engine_config=ec, dtypes=FP32)
    prompts = [list(range(5, 295)) * 3, list(range(7, 297)) * 3][:2]  # 870 tokens: 1280 - 870 = 410 -> the rung at 320
    eng.generate(prompts)  # compiled outside the capture
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(2):
        eng.generate(prompts)
    jax.profiler.stop_trace()
    data = phases.load(trace.find_xplane(str(tmp_path)))
    reduced = phases.reduce_phases(data, cfg.num_layers)
    assert reduced["prefill_rows"] == 2 * 2  # the batch, twice
    assert reduced["steps"].get("decode", 0) > 0
    assert eng.stats.family_counters["prefill_tokens_computed"] == 3 * 2 * (1280 - 320)
    assert eng.stats.family_counters["prefill_tokens_bucketed"] == 3 * 2 * 1280
    by = ssm_scopes.seconds_by_fine_scope(data)
    assert by["prefill"].get("scan", 0) > 0 and by["prefill"].get("conv", 0) > 0
    paths = [path for _, path in _traced(_compiled(eng._build_generate(2, 1280, 6)))]
    for fine in ("scan", "conv", "global"):
        hits = [p for p in paths if "/prefill/" in p and "/attn/" in p and f"/{fine}/" in p.split("/attn/")[1]]
        assert hits and all(_scope(p) == ("prefill", "attn") for p in hits), fine
        assert all("cond/" in p.split("/attn/")[1] for p in hits), fine  # the kind of mixer; the convolution in a rung too
    outside = [p for p in paths if "/prefill/rows2/" in p and "/while/body/" in p
               and "/cond/" not in p and "/branch_" not in p]
    assert outside, "no operation of a trip stands outside every branch"


def test_shortcut_block_arrives_scoped_with_its_own_fine_scopes():
    """The shortcut-connected block through the same generate program: every
    operation carries a phase; the two dense FFNs are ``mlp/dense``, the
    identity experts' term ``mlp/zero``, and no ``mlp/shared`` is opened where
    there is no shared expert."""
    import dataclasses

    from longcat_flash_reference import tiny_config
    from rag_llm_k8s_tpu.models.latent_moe import init_latent_moe_params

    cfg = tiny_config(vocab_size=300)
    params = init_latent_moe_params(jax.random.PRNGKey(0), cfg, FP32)
    ec = dataclasses.replace(EC, prefix_cache=PrefixCacheConfig(enabled=False), attn_impl="xla")
    engine = InferenceEngine(cfg, params, sampling=GREEDY, engine_config=ec, dtypes=FP32)
    traced = LATENT_PROGRAMS["generate"](engine)
    _assert_scoped("generate", traced)
    paths = [path for _, path in _traced(traced)]
    for phase in ("prefill", "decode"):
        for fine in ("router", "experts", "zero", "dense"):
            hits = [p for p in paths if f"/{phase}/" in p and f"/mlp/{fine}/" in p]
            assert hits and all(_scope(p) == (phase, "mlp") for p in hits), (phase, fine)
        assert {"ffn_0", "ffn_1"} <= {part for p in paths if f"/{phase}/" in p and "/mlp/dense/" in p
                                      for part in p.split("/")}
    assert not [p for p in paths if "/mlp/shared/" in p]
    # the whole block (both sublayers and the branch) runs in the layers'
    # loop at the rows2 prefill: a prefill's rows are counted by that
    looped = [p for p in paths if "/prefill/rows2/" in p and "/while/body/" in p and "/layers/" in p]
    for name in ("attn_0", "attn_1", "ffn_0", "ffn_1", "/mlp/experts/"):
        assert [p for p in looped if name in p], name
    assert not [p for p in paths if "/prefill/rows2/" in p and "/ffn_" in p and "/while/body/" not in p]


def test_an_operation_outside_every_scope_shows():
    def f(x):
        y = jnp.tanh(x) @ x  # traced outside every scope
        with tracing.phase_scope("decode"):
            return jnp.sin(y) @ x

    compiled = jax.jit(f).lower(jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
    assert {_scope(path)[0] for _, path in _traced(compiled)} == {"decode", None}
    with pytest.raises(AssertionError, match="outside every phase scope"):
        _assert_scoped("generate", compiled)


class TestVocabulary:
    def test_refuses_a_name_outside_it(self):
        with pytest.raises(ValueError, match="vocabulary"):
            tracing.phase_scope("warmup")
        with pytest.raises(ValueError, match="vocabulary"):
            tracing.phase_scope("decode/attention")

    @pytest.mark.parametrize("path", ["decode", "retrieve/embed", "verify/attn", "mixed"])
    def test_a_scope_is_op_metadata(self, path):
        def f(x):
            with tracing.phase_scope(path):
                return x * 2.0

        text = jax.jit(f).lower(jnp.ones((4,))).compile().as_text()
        assert f"/{path}/" in text

    def test_rows_are_stated_in_the_path(self):
        def f(x):
            with tracing.phase_scope("prefill", rows=3):
                return x * 2.0

        text = jax.jit(f).lower(jnp.ones((4,))).compile().as_text()
        assert "/prefill/rows3/" in text
        with pytest.raises(ValueError, match="vocabulary"):
            tracing.phase_scope("rows3")


# ---------------------------------------------------------------------------
# (c) the dispatch counters; (d) the dispatch spans
# ---------------------------------------------------------------------------


def _rows(reg):
    fam = reg.get_family("rag_generate_dispatch_rows_total")
    return {(dict(k)["path"], dict(k)["rows"]): c.value for k, c in fam.items()}


def _reasons(reg):
    fam = reg.get_family("rag_generate_dispatch_reason_total")
    return {dict(k)["reason"]: c.value for k, c in fam.items()}


class StubEngine:
    """An engine whose generate is instant (or held), for the drain loop."""

    def __init__(self, cap, hold=None):
        self.engine_config = EngineConfig(max_batch_size=cap)
        self.hold = hold
        self.batches = []

    def generate(self, prompts, max_new_tokens=None, seed=None):
        if self.hold is not None:
            self.hold.wait(5.0)
        self.batches.append(len(prompts))
        return [[1] for _ in prompts]


def _scheduler(cap, max_wait_ms, hint=None, hold=None):
    reg = obs_metrics.MetricsRegistry()
    sched = BatchScheduler(StubEngine(cap, hold), max_wait_ms=max_wait_ms, pending_hint=hint)
    sched.dispatch_counter = reg.labeled_counter("rag_generate_dispatch_rows_total")
    sched.reason_counter = reg.labeled_counter("rag_generate_dispatch_reason_total")
    return reg, sched


def _submit_all(sched, requests):
    infos = [{} for _ in requests]
    threads = [threading.Thread(target=sched.submit, args=(p,), kwargs=dict(info=i, **kw))
               for (p, kw), i in zip(requests, infos)]
    for t in threads:
        t.start()
        time.sleep(0.01)  # arrival order is the order given
    for t in threads:
        t.join(10.0)
    return infos


class TestDispatchRecord:
    def test_a_batch_of_three_counts_three_answers_under_rows_3(self):
        n = [3]
        reg, sched = _scheduler(cap=4, max_wait_ms=2000.0, hint=lambda: n[0])
        try:
            infos = _submit_all(sched, [([1, 2], {})] * 3)
        finally:
            sched.shutdown()
        assert _rows(reg) == {("batched", "3"): 3.0}
        assert _reasons(reg) == {"hint": 1.0}
        assert [i["dispatch_rows"] for i in infos] == [3, 3, 3]
        assert all(i["queue_wait_ms"] >= 0.0 for i in infos)

    def test_full(self):
        reg, sched = _scheduler(cap=2, max_wait_ms=2000.0)
        try:
            _submit_all(sched, [([1], {})] * 2)
        finally:
            sched.shutdown()
        assert _reasons(reg) == {"full": 1.0}
        assert _rows(reg) == {("batched", "2"): 2.0}

    def test_deadline(self):
        reg, sched = _scheduler(cap=4, max_wait_ms=20.0)
        try:
            _submit_all(sched, [([1], {})])
        finally:
            sched.shutdown()
        assert _reasons(reg) == {"deadline": 1.0}
        assert _rows(reg) == {("batched", "1"): 1.0}

    def test_incompatible(self):
        """A request that needs another executable ends the drain and leads
        the next round: two dispatches of one row each."""
        reg, sched = _scheduler(cap=4, max_wait_ms=300.0)
        try:
            _submit_all(sched, [([1], {"max_new_tokens": 4}), ([1], {"max_new_tokens": 8})])
        finally:
            sched.shutdown()
        assert _reasons(reg)["incompatible"] == 1.0
        assert _rows(reg) == {("batched", "1"): 2.0}

    def test_the_shutdown_wake_up_is_not_a_decision(self):
        reg, sched = _scheduler(cap=4, max_wait_ms=5000.0)
        t = threading.Thread(target=lambda: sched.submit([1]))
        t.start()
        time.sleep(0.05)
        sched.shutdown()
        t.join(10.0)
        assert _reasons(reg) == {}


@pytest.fixture(scope="module")
def served():
    llama_cfg = LlamaConfig.tiny(vocab_size=300)
    enc_cfg = EncoderConfig.tiny(vocab_size=300)
    engine = InferenceEngine(
        llama_cfg, init_llama_params(jax.random.PRNGKey(0), llama_cfg, FP32),
        sampling=GREEDY, dtypes=FP32,
        engine_config=EngineConfig(prompt_buckets=(128, 512), max_batch_size=4,
                                   max_seq_len=640, rag_fused=True),
    )
    encoder = EncoderRunner(
        enc_cfg, init_encoder_params(jax.random.PRNGKey(1), enc_cfg, FP32),
        dtypes=FP32, length_buckets=(32,), max_batch=4,
    )
    store = VectorStore(dim=enc_cfg.hidden_size)
    scheduler = BatchScheduler(engine, max_wait_ms=2000.0)
    svc = RagService(AppConfig(model=llama_cfg, encoder=enc_cfg), engine, ByteTokenizer(),
                     encoder, ByteTokenizer(), store, scheduler=scheduler)
    svc.ready = True
    vec = encoder.encode([ByteTokenizer().encode("tiny doc text")])[0]
    store.add([vec], [{"filename": "f", "chunk_id": 0, "text": "kernels tile queries"}])
    yield svc, create_app(svc).test_client()
    scheduler.shutdown()


def _find(spans, name):
    return [s for s in spans if s["name"] == name]


class TestDispatchThroughTheService:
    def test_one_fused_request_and_a_batch_of_three(self, served):
        svc, client = served
        before = _rows(svc.metrics)
        r = client.post("/generate", json={"prompt": "what do kernels do?", "trace": True})
        assert r.status_code == 200, r.get_json()
        body = r.get_json()
        after_one = _rows(svc.metrics)
        # a solo request takes the fused single-fetch path: one row, its own dispatch
        moved = {k: v - before.get(k, 0) for k, v in after_one.items() if v != before.get(k, 0)}
        assert moved == {("fused", "1"): 1.0}
        # (d) generate -> dispatch -> {launch, fetch, deliver}, rows set, and
        # the top-level spans still sum to the request's total
        tree = body["trace"]
        gen = _find(tree["spans"], "generate")[0]
        assert gen["attrs"]["rows"] == 1.0 and gen["attrs"]["queue_wait_ms"] == 0.0
        dispatch = _find(gen["spans"], "dispatch")[0]
        assert dispatch["attrs"]["rows"] == 1.0
        assert [s["name"] for s in dispatch["spans"]] == ["launch", "fetch", "deliver"]
        stage_sum = sum(s["duration_ms"] for s in tree["spans"])
        assert stage_sum == pytest.approx(body["timings"]["total_ms"], rel=0.05)

        # three callers at once: one batch of three through the scheduler
        results = []
        threads = [threading.Thread(target=lambda i=i: results.append(
            client.post("/generate", json={"prompt": f"question {i}?", "trace": True})))
            for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60.0)
        assert [x.status_code for x in results] == [200] * 3
        moved = {k: v - after_one.get(k, 0) for k, v in _rows(svc.metrics).items()
                 if v != after_one.get(k, 0)}
        assert sum(moved.values()) == 3.0, moved
        # whichever way the three were admitted (one batch, or the admission
        # race's one-and-two), each request's tree says what it rode with
        rode = sorted(_find(x.get_json()["trace"]["spans"], "generate")[0]["attrs"]["rows"]
                      for x in results)
        counted = sorted(float(rows) for (_, rows), n in moved.items()
                         for _ in range(int(n)))
        assert rode == counted, (rode, moved)

    def test_a_dispatch_that_fails_was_still_dispatched(self, served):
        """Counted at the launch, as the scheduler counts its batches, so the
        family has one meaning on every path."""
        svc, _ = served
        before = _rows(svc.metrics).get(("direct", "1"), 0.0)
        with pytest.raises(RuntimeError, match="device lost"):
            with svc._dispatch("direct"):
                raise RuntimeError("device lost")
        assert _rows(svc.metrics)[("direct", "1")] == before + 1.0


# ---------------------------------------------------------------------------
# (e) a dispatch keeps its own clock (ISSUE 51): stage seconds in every run, a
# tree of its own on the scheduler's worker, the request's side of the link,
# and the retrieve coalescer's two counters
# ---------------------------------------------------------------------------

STAGE_FAMILY = "rag_generate_dispatch_stage_seconds"


def _stages(reg, path):
    """{stage: (count, sum)} of one path's children of the stage family."""
    fam = reg.get_family(STAGE_FAMILY)
    return {dict(k)["stage"]: (c.count, c.sum) for k, c in (fam.items() if fam else [])
            if dict(k)["path"] == path}


def _stage_delta(before, after):
    return {s: (after[s][0] - before.get(s, (0, 0.0))[0], after[s][1] - before.get(s, (0, 0.0))[1])
            for s in after}


def _toy_engine_call(fail_in=None):
    """What an engine's generate opens inside a dispatch, a few ms each."""
    for name, seconds in (("launch", 0.004), ("fetch", 0.006), ("deliver", 0.002)):
        with tracing.span(name):
            time.sleep(seconds)
            if name == fail_in:
                raise RuntimeError("device lost")


class ClockedStub(StubEngine):
    def __init__(self, cap, fail_in=None):
        super().__init__(cap)
        self.fail_in = fail_in

    def generate(self, prompts, max_new_tokens=None, seed=None):
        _toy_engine_call(self.fail_in)
        return super().generate(prompts, max_new_tokens, seed)


def _sink():
    reg = obs_metrics.MetricsRegistry()
    return reg, tracing.DispatchSink(reg.labeled_histogram(STAGE_FAMILY), tracing.TraceBuffer(8))


def _names(node):
    return [s["name"] for s in node.get("spans", [])]


class TestADispatchKeepsItsOwnClock:
    @pytest.mark.parametrize("path", ["fused", "prefixed", "direct", "batched"])
    def test_one_sample_a_stage_and_the_four_sum_to_the_wall(self, path):
        reg, sink = _sink()
        if path == "batched":
            sched = BatchScheduler(ClockedStub(cap=4), max_wait_ms=30.0)
            sched.dispatch_sink = sink
            try:
                info = _submit_all(sched, [([1], {})])[0]
            finally:
                sched.shutdown()
            (tree,) = sink.ring.list()
            wall_ms = tree["total_ms"]
            assert _names(tree) == ["gather", "dispatch"]
            dispatch = tree["spans"][1]
            assert tree["attrs"]["reason"] == "deadline" and tree["attrs"]["rows"] == 1
        else:
            # on a request's thread the dispatch sits in the request's tree
            tr = tracing.start_trace()
            with tracing.dispatch_record(path, 1, sink=sink) as rec:
                _toy_engine_call()
            info = rec.link()
            (dispatch,) = tracing.finish_trace(tr)["spans"]
            wall_ms = dispatch["duration_ms"]
            assert len(sink.ring) == 0
        # launch, fetch and the engine's deliver; the scheduler adds its own
        # deliver (the riders' release) under the same name
        assert _names(dispatch)[:3] == ["launch", "fetch", "deliver"]
        assert set(_names(dispatch)) == {"launch", "fetch", "deliver"}
        got = _stages(reg, path)
        assert set(got) == set(tracing.DISPATCH_STAGES)
        assert {s: n for s, (n, _) in got.items()} == dict.fromkeys(tracing.DISPATCH_STAGES, 1)
        assert sum(sec for _, sec in got.values()) * 1e3 == pytest.approx(wall_ms, abs=2.0)
        assert got["launch"][1] >= 0.004 and got["device"][1] >= 0.006 and got["deliver"][1] >= 0.002
        if path == "batched":
            assert 0.030 <= got["gather"][1] < 0.5  # the window ran out with one aboard
        else:
            assert got["gather"][1] == 0.0 and info["queue_wait_ms"] == 0.0
        # the request's side: the same dispatch, and its own four intervals
        assert info["dispatch_seq"] == dispatch["attrs"]["seq"] and info["dispatch_rows"] == 1
        assert info["launch_ms"] == pytest.approx(got["launch"][1] * 1e3, abs=0.01)
        assert info["device_ms"] == pytest.approx(got["device"][1] * 1e3, abs=0.01)
        assert info["deliver_ms"] >= 2.0

    @pytest.mark.parametrize("fail_in", ["launch", "fetch"])
    def test_a_dispatch_whose_engine_call_raises_is_still_recorded(self, fail_in):
        reg, sink = _sink()
        sched = BatchScheduler(ClockedStub(cap=4, fail_in=fail_in), max_wait_ms=1.0)
        sched.dispatch_sink = sink
        try:
            with pytest.raises(RuntimeError, match="device lost"):
                sched.submit([1])
        finally:
            sched.shutdown()
        got = _stages(reg, "batched")
        assert {s: n for s, (n, _) in got.items()} == dict.fromkeys(tracing.DISPATCH_STAGES, 1)
        (tree,) = sink.ring.list()
        assert sum(sec for _, sec in got.values()) * 1e3 == pytest.approx(tree["total_ms"], abs=2.0)
        # what the call did not reach holds nothing: the riders' release is all of ``deliver``
        assert got["launch"][1] >= 0.004 and got["deliver"][1] < 0.05, got
        assert (got["device"][1] == 0.0) if fail_in == "launch" else (got["device"][1] >= 0.006), got

    def test_seq_is_process_wide_and_built_marks_a_cold_shape(self):
        _, sink = _sink()
        seqs = []
        for build in (False, True):
            with tracing.dispatch_record("direct", 1, sink=sink) as rec:
                if build:
                    with tracing.span("build/generate"):
                        pass
                _toy_engine_call()
            seqs.append(rec.seq)
        cold_free, cold = sink.ring.list()
        assert seqs[1] == seqs[0] + 1 == cold["attrs"]["seq"]
        assert (cold_free["attrs"]["built"], cold["attrs"]["built"]) == (0, 1)
        assert cold["attrs"]["kind"] == "dispatch" and cold["attrs"]["path"] == "direct"

    def test_gather_is_never_opened_on_an_empty_queue(self, monkeypatch):
        opened = []
        real = tracing.annotate
        monkeypatch.setattr(tracing, "annotate", lambda name: (opened.append(name), real(name))[1])
        sched = BatchScheduler(StubEngine(cap=4), max_wait_ms=1.0)
        try:
            time.sleep(0.05)  # the worker waits on an empty queue
            assert opened == []
            sched.submit([1])
            sched.submit([1])
            time.sleep(0.05)
        finally:
            sched.shutdown()
        assert opened == ["gather", "gather"]

    def test_a_batch_through_the_service(self, served, monkeypatch):
        """Three callers, one batch: its tree is in the dispatch ring with its
        riders' trace ids, and each rider's ``generate`` span and ``timings``
        name the same dispatch."""
        svc, client = served
        monkeypatch.setenv("TPU_RAG_FAULTS", "")  # arms the /debug routes
        # past the admission race (one caller retrieved alone takes the fused
        # path): the retrieve stage asks its hint only once all are in flight
        monkeypatch.setattr(svc.retrieve_coalescer, "hint_grace_ms", 300.0)
        monkeypatch.setattr(svc.retrieve_coalescer, "max_wait_ms", 2000.0)
        before = _stages(svc.metrics, "batched")
        rows_before = _coalesce(svc.metrics, "rows")
        results = []
        threads = [threading.Thread(target=lambda i=i: results.append(
            client.post("/generate", json={"prompt": f"question {i}?", "trace": True})))
            for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60.0)
        bodies = [x.get_json() for x in results]
        assert [x.status_code for x in results] == [200] * 3, bodies
        tree = client.get("/debug/traces?kind=dispatch&limit=1").get_json()["traces"][-1]
        attrs = tree["attrs"]
        assert attrs["path"] == "batched" and attrs["rows"] == 3 and attrs["reason"] == "hint"
        assert sorted(attrs["riders"]) == sorted(b["trace"]["trace_id"] for b in bodies)
        assert _names(tree) == ["gather", "dispatch"]
        assert _names(tree["spans"][1]) == ["launch", "fetch", "deliver", "deliver"]
        for body in bodies:
            gen = _find(body["trace"]["spans"], "generate")[0]["attrs"]
            t = body["timings"]
            assert gen["seq"] == attrs["seq"] == t["dispatch_seq"]
            assert gen["rows"] == 3.0 == t["dispatch_rows"]
            for key in ("queue_wait_ms", "launch_ms", "device_ms", "deliver_ms"):
                assert gen[key] == pytest.approx(t[key], abs=0.01)
            # the four are the request's own time inside ``generate``
            parts = t["queue_wait_ms"] + t["launch_ms"] + t["device_ms"] + t["deliver_ms"]
            assert parts <= t["generate_ms"] + 1.0
        # one sample a stage for the one dispatch, and they sum to its wall
        moved = _stage_delta(before, _stages(svc.metrics, "batched"))
        assert {s: n for s, (n, _) in moved.items()} == dict.fromkeys(tracing.DISPATCH_STAGES, 1)
        assert sum(sec for _, sec in moved.values()) * 1e3 == pytest.approx(tree["total_ms"], abs=2.0)
        # and the round's retrievals were one batch of three
        rows = _coalesce(svc.metrics, "rows")
        assert {k: v - rows_before.get(k, 0.0) for k, v in rows.items() if v != rows_before.get(k, 0.0)} \
            == {("retrieve", "3"): 3.0}
        # the request ring is still the default view
        assert "boot" in client.get("/debug/traces").get_json()


def _coalesce(reg, what):
    fam = reg.get_family(f"rag_coalesce_dispatch_{what}_total")
    label = "rows" if what == "rows" else "reason"
    return {(dict(k)["stage"], dict(k)[label]): c.value for k, c in (fam.items() if fam else [])}


def _coalescer(max_batch, max_wait_ms, hint=None, hold=None):
    from rag_llm_k8s_tpu.engine.batching import Coalescer

    def batch_fn(items):
        if hold is not None:
            hold.wait(5.0)
        return list(items)

    reg = obs_metrics.MetricsRegistry()
    co = Coalescer(batch_fn, max_batch=max_batch, max_wait_ms=max_wait_ms, pending_hint=hint,
                   hint_grace_ms=1.0)
    co.dispatch_counter = reg.labeled_counter("rag_coalesce_dispatch_rows_total")
    co.reason_counter = reg.labeled_counter("rag_coalesce_dispatch_reason_total")
    return reg, co


def _coalesce_all(co, n):
    threads = [threading.Thread(target=co.submit, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
        time.sleep(0.01)
    for t in threads:
        t.join(10.0)


class TestTheRetrieveStageCountsItsBatches:
    @pytest.mark.parametrize("reason,kw,n", [
        ("full", dict(max_batch=2, max_wait_ms=2000.0), 2),
        ("hint", dict(max_batch=4, max_wait_ms=2000.0, hint=lambda: 3), 3),
        ("deadline", dict(max_batch=4, max_wait_ms=20.0), 1),
    ])
    def test_rows_and_each_stop_reason(self, reason, kw, n):
        reg, co = _coalescer(**kw)
        try:
            _coalesce_all(co, n)
        finally:
            co.shutdown()
        assert _coalesce(reg, "rows") == {("retrieve", str(n)): float(n)}
        assert _coalesce(reg, "reason") == {("retrieve", reason): 1.0}

    def test_the_shutdown_wake_up_is_not_a_decision_and_the_batch_has_a_span(self, monkeypatch):
        opened = []
        real = tracing.annotate
        monkeypatch.setattr(tracing, "annotate", lambda name: (opened.append(name), real(name))[1])
        reg, co = _coalescer(max_batch=4, max_wait_ms=5000.0)
        t = threading.Thread(target=co.submit, args=(1,))
        t.start()
        time.sleep(0.05)
        co.shutdown()
        t.join(10.0)
        assert _coalesce(reg, "reason") == {}
        assert opened == ["retrieve_batch"]
