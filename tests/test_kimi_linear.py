"""The gated delta-rule, sparse-expert family (models/delta_moe.py over
ops/delta_rule.py, ops/ssm.py causal_conv, models/latent_moe.py's latent
attention and sparse FFN) against its plain reference
(tests/kimi_linear_reference.py), at a toy size on the CPU with seeded weights
under the fp32 policy: hidden 64, eleven layers in the published pattern (a
dense linear layer, then K K M | K K K M | K K M), 4 heads of 16 on both
kinds, 16 experts of 32 of which rank 1 of 2 holds 8, top 4.

Tolerances. The program and the reference compute the same float32 numbers in
different orders (a chunk's triangular solve against a running rank-one
correction; the absorbed latent form against the expanded one; the grouped
experts against a loop over every expert), so logits of magnitude ~3 agree to
a few 1e-5; ``ATOL`` is 5e-4. The faults the comparison must see are far above
it: no decay, a scalar decay, beta 1, q and k not normed, the taps reversed,
the slices rotated, weights from score plus bias each move a logit by 5e-2 or
more. Router inputs are float32 on both sides: no expert is swapped at the
top-4's edge here.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import traverse_util

import kimi_linear_reference as ref
from rag_llm_k8s_tpu.core.config import (
    DeltaMoEConfig,
    DTypePolicy,
    EngineConfig,
    LlamaConfig,
    MeshConfig,
    PrefixCacheConfig,
    SamplingConfig,
)
from rag_llm_k8s_tpu.core.mesh import make_mesh
from rag_llm_k8s_tpu.engine.engine import InferenceEngine
from rag_llm_k8s_tpu.models import delta_moe as dm
from rag_llm_k8s_tpu.models import families
from rag_llm_k8s_tpu.models import latent_moe as lm
from rag_llm_k8s_tpu.obs import tracing
from rag_llm_k8s_tpu.ops import delta_rule, moe

FP32 = DTypePolicy.fp32()
ATOL = 5e-4
V = 48
CFG = DeltaMoEConfig.tiny(vocab_size=V)
NEW = 6
GREEDY = SamplingConfig(do_sample=False, max_new_tokens=NEW)
S0 = 80  # the bucket the tests prefill: one whole chunk of the recurrence and a part of one


def seeded_params(cfg, seed=0):
    """Weights with statistics that make every part matter: kernels of std
    1/sqrt(fan_in) (the four taps too), norm scales near 1, ``A`` uniform in
    (1, 16), a time step log-uniform in (0.01, 1) so that a few dozen tokens
    see alpha from 1e-7 to 0.99, a selection bias of std 0.1, a unit-std
    embedding."""
    shapes = traverse_util.flatten_dict(
        jax.eval_shape(lambda: dm.init_delta_moe_params(jax.random.PRNGKey(0), cfg, FP32)))
    rng = np.random.default_rng(seed)
    out = {}
    for path, leaf in sorted(shapes.items()):
        name = path[-1]
        if name == "A_log":
            value = np.log(rng.uniform(1, 16, leaf.shape))
        elif name == "dt_bias":
            step = np.exp(rng.uniform(np.log(0.01), np.log(1.0), leaf.shape))
            value = step + np.log(-np.expm1(-step))
        elif any("norm" in part for part in path):
            value = 1.0 + 0.1 * rng.standard_normal(leaf.shape)
        elif name == "router_bias":
            value = 0.1 * rng.standard_normal(leaf.shape)
        elif name == "embedding":
            value = rng.standard_normal(leaf.shape)
        else:
            value = rng.standard_normal(leaf.shape) / np.sqrt(leaf.shape[-2])
        out[path] = jnp.asarray(value, jnp.float32)
    # no stream ends early: the EOS column is zero, as the benchmark's head has it
    out[("lm_head",)] = out[("lm_head",)].at[:, list(cfg.eos_token_ids)].set(0.0)
    return traverse_util.unflatten_dict(out)


PARAMS = seeded_params(CFG)


@pytest.fixture(scope="module")
def params():
    return PARAMS


def prompt_of(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(3, V, size=n)]


_REF, _FORWARD = {}, {}


def forward(tokens, fault="", state_log=None):
    """The reference's logits of ``tokens``, computed at a padded length (a
    pad behind the sequence changes nothing in front of it: every mixer is
    causal), so that one compiled program serves every length up to it."""
    n = -(-len(tokens) // 64) * 64
    ids = jnp.asarray(list(tokens) + [0] * (n - len(tokens)), jnp.int32)
    if state_log is not None:  # the states are the real length's: no padded run
        return np.asarray(ref.forward(PARAMS, CFG, jnp.asarray(tokens, jnp.int32), fault, state_log))
    if (n, fault) not in _FORWARD:
        _FORWARD[n, fault] = jax.jit(lambda params, ids: ref.forward(params, CFG, ids, fault))
    return np.asarray(_FORWARD[n, fault](PARAMS, ids))[:len(tokens)]


def reference(tokens):
    key = tuple(tokens)
    if key not in _REF:
        _REF[key] = forward(tokens)
    return _REF[key]


def greedy_reference(prompt, n):
    tokens = list(prompt)
    for _ in range(n):
        tokens.append(int(np.argmax(forward(tokens)[-1])))
    return tokens[len(prompt):]


_CALLS = {}


def model_call(impl="xla", **kw):
    key = (impl,) + tuple(sorted(kw.items()))
    if key not in _CALLS:
        model = dm.DeltaMoEModel(CFG, FP32, attn_impl=impl, **kw)
        _CALLS[key] = jax.jit(lambda *a: model.apply({"params": PARAMS}, *a))
    return _CALLS[key]


def through_the_cache(rows, S, lengths, impl="xla"):
    """Logits of ``rows`` (left-padded to ``S``, of which ``lengths`` are
    prefilled at once and the rest decoded a token at a time), and the cache."""
    B, lens = len(rows), np.asarray(lengths)
    call = model_call(impl)
    cache = dm.make_delta_cache(CFG, B, 256, jnp.float32)
    kv_start = jnp.asarray(S - lens, jnp.int32)
    padded = np.zeros((B, S), np.int32)
    for b, row in enumerate(rows):
        padded[b, S - lens[b]:] = row[:lens[b]]
    positions = jnp.maximum(jnp.arange(S)[None, :] - kv_start[:, None], 0)
    logits, cache = call(jnp.asarray(padded), positions, cache, kv_start, jnp.full((B,), S, jnp.int32), jnp.int32(0))
    out = [[np.asarray(logits[b, S - lens[b] + t]) for t in range(lens[b])] for b in range(B)]
    after_prefill = cache
    for t in range(min(len(r) - n for r, n in zip(rows, lens))):
        tok = jnp.asarray([[r[n + t]] for r, n in zip(rows, lens)], jnp.int32)
        pos = jnp.asarray([[n + t] for n in lens], jnp.int32)
        step, cache = call(tok, pos, cache, kv_start, jnp.full((B,), S + t + 1, jnp.int32), jnp.int32(S + t))
        for b in range(B):
            out[b].append(np.asarray(step[b, 0]))
    return [np.stack(o) for o in out], after_prefill, cache


# ---- (a) prefill, then decode through the cache ----


@pytest.mark.parametrize("impl,prompt_len", [("xla", 70), ("xla", 3), ("pallas_interpret", 70), ("pallas_interpret", 80)])
def test_prefill_then_decode_matches_reference_at_every_position(impl, prompt_len):
    tokens = prompt_of(prompt_len + 5, 1)
    (got,), _, _ = through_the_cache([tokens], S0, [prompt_len], impl)
    np.testing.assert_allclose(got, reference(tokens), atol=ATOL)
    if impl != "xla":  # the bucket's recurrence went through the kernel (80 positions: two of its chunks)
        assert tracing.kernel_builds()[("prefill", "delta_rule_chunked_pallas")] > 0


def test_the_published_depth_is_a_dense_linear_layer_then_three_to_one_to_the_last_layer():
    """27 layers: full layers at 4, 8, .., 24 AND 27 (1-indexed), so the
    pattern starts and ends on a cut period; at the cell's share (16 of 256
    experts held, an eighth of the vocabulary) the tree is 4296 M parameters."""
    c = DeltaMoEConfig(ep_size=16, vocab_size=20480)
    kinds = "".join("M" if c.is_full(i) else "K" for i in range(c.num_layers))
    assert kinds == "KKKM" * 6 + "KKM" and (c.num_kda_layers, c.num_mla_layers, c.num_moe_layers) == (20, 7, 26)
    shapes = jax.eval_shape(lambda: dm.init_delta_moe_params(jax.random.PRNGKey(0), c))
    count = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert count == 4296057728  # 8.59 GB of bf16
    kda = sum(int(np.prod(s.shape[1:])) for s in jax.tree.leaves(shapes["kda_layers"]))
    mla = sum(int(np.prod(s.shape[1:])) for s in jax.tree.leaves(shapes["mla_layers"]))
    assert (kda, mla) == (39514272, 29114880)  # 39.51 M and 29.11 M a mixer
    assert shapes["kda_layers"]["A_log"].dtype == shapes["kda_layers"]["dt_bias"].dtype == jnp.float32
    cache = jax.eval_shape(lambda: dm.make_delta_cache(c, 8, 4352))
    assert cache.state.shape == (20, 8, 32, 128, 128) and cache.state.dtype == jnp.float32  # 2.1 MB a row-layer
    assert cache.conv.shape == (20, 8, 3, 3 * 4096) and cache.c_kv.shape == (7, 8, 4352, 512)


def test_two_rows_of_one_bucket_with_different_left_padding():
    """Each row equals the reference, and a shorter row's state is what it
    keeps alone in a bucket it fills."""
    rows = [prompt_of(74, 2), prompt_of(29, 3), prompt_of(5, 4)]
    lengths = [70, 25, 1]
    got, _, cache = through_the_cache(rows, S0, lengths)
    for row, g in zip(rows, got):
        np.testing.assert_allclose(g, reference(row)[:len(g)], atol=ATOL)
    _, _, alone = through_the_cache([rows[1][:25 + 4]], 25, [25])
    np.testing.assert_allclose(np.asarray(cache.state[:, 1]), np.asarray(alone.state[:, 0]), atol=ATOL)
    np.testing.assert_allclose(np.asarray(cache.conv[:, 1]), np.asarray(alone.conv[:, 0]), atol=ATOL)


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_pads_leave_the_state_bit_for_bit_zero(impl):
    """A row of nothing but pads (the batch ladder's filler; a prompt chunk in
    front of a long prompt's first token) leaves the matrix state and the kept
    convolution inputs of every linear layer exactly zero, and the state a
    padded row reaches is the reference's last state."""
    S = 128
    tokens = prompt_of(70, 6)
    padded = np.zeros((2, S), np.int32)
    padded[0, S - 70:] = tokens
    padded[1] = prompt_of(S, 7)  # tokens that are all in front of kv_start: pads
    kv_start = jnp.asarray([S - 70, S], jnp.int32)
    positions = jnp.maximum(jnp.arange(S)[None, :] - kv_start[:, None], 0)
    cache = dm.make_delta_cache(CFG, 2, 256, jnp.float32)
    _, cache = model_call(impl)(jnp.asarray(padded), positions, cache, kv_start, jnp.full((2,), S, jnp.int32),
                                jnp.int32(0))
    assert not np.asarray(cache.state[:, 1]).any() and not np.asarray(cache.conv[:, 1]).any()
    states = []
    forward(tokens, state_log=states)
    np.testing.assert_allclose(np.asarray(cache.state[:, 0]), np.stack(states), atol=ATOL)
    counted = dm.fold_counters(np.asarray(cache.counters))
    # the batch's first live chunk is chunk 0 (58 pads in the first row): the bucket is advanced
    assert counted["kda_prefill_positions"] == counted["kda_prefill_positions_bucketed"] == 2 * S * CFG.num_kda_layers


@pytest.mark.parametrize("pads,advanced", [(0, 256), (63, 256), (64, 192), (130, 128), (200, 64)])
def test_a_fresh_prompt_s_recurrence_starts_at_its_first_live_chunk(pads, advanced):
    S = 256
    tokens = prompt_of(S - pads, 9)
    padded = np.zeros((1, S), np.int32)
    padded[0, pads:] = tokens
    ks = jnp.asarray([pads], jnp.int32)
    cache = dm.make_delta_cache(CFG, 1, 384, jnp.float32)
    logits, cache = model_call()(jnp.asarray(padded), jnp.maximum(jnp.arange(S)[None] - pads, 0), cache, ks,
                                 jnp.full((1,), S, jnp.int32), jnp.int32(0))
    np.testing.assert_allclose(np.asarray(logits[0, pads:]), reference(tokens), atol=ATOL)
    counted = dm.fold_counters(np.asarray(cache.counters))
    assert counted["kda_prefill_positions"] == advanced * CFG.num_kda_layers
    assert counted["kda_prefill_positions_bucketed"] == S * CFG.num_kda_layers


# ---- (b) a chunk over the cache; the verify step and what it commits ----


def chunk_call(tokens, start, n, keep_steps=False, impl="xla"):
    """``tokens[:start]`` prefilled (left-padded to ``S0``), then ``n``
    positions from ``start`` in ONE chunk call; returns its logits and cache."""
    pad = S0 - start
    padded = np.zeros((1, S0), np.int32)
    padded[0, pad:] = tokens[:start]
    ks = jnp.asarray([pad], jnp.int32)
    cache = dm.make_delta_cache(CFG, 1, 256, jnp.float32)
    _, cache = model_call(impl)(jnp.asarray(padded), jnp.maximum(jnp.arange(S0)[None] - pad, 0), cache, ks,
                                jnp.full((1,), S0, jnp.int32), jnp.int32(0))
    fed = jnp.asarray([tokens[start:start + n]], jnp.int32)
    return model_call(impl, chunked=True, keep_steps=keep_steps)(
        fed, (start + jnp.arange(n))[None], cache, ks, jnp.full((1,), S0 + n, jnp.int32), jnp.int32(S0)), ks


@pytest.mark.parametrize("kept,why", [(16, "accepted in full"), (7, "accepted in part"), (1, "none accepted"),
                                      (0, "not even the pending token")])
def test_a_verify_step_commits_the_state_it_kept(kept, why):
    """A verify step feeds 16 positions of which only the first ``kept`` are
    the sequence's; the state stays as it was until ``commit`` replays the
    kept ones, the state is then the reference's after ``kept`` tokens, and
    the steps that follow equal the reference."""
    tokens = prompt_of(100, 7)
    start, n = 57, 16
    junk = tokens[:start + kept] + prompt_of(n - kept, 99)  # rejected proposals behind the kept ones
    (logits, cache), ks = chunk_call(junk, start, n, keep_steps=True)
    np.testing.assert_allclose(np.asarray(logits[0, :kept]), reference(tokens[:start + kept])[start:], atol=ATOL)
    before = []
    forward(tokens[:start], state_log=before)
    np.testing.assert_allclose(np.asarray(cache.state[:, 0]), np.stack(before), atol=ATOL)  # as it was
    assert [s.shape for s in cache.steps] == [(8, 1, 3 + n, 192)] + [(8, 1, n, 4, 16)] * 3 + [(8, 1, n, 4)]
    cache = dm.commit(cache, jnp.int32(kept))
    assert cache.steps is None
    after = []
    forward(tokens[:start + kept], state_log=after)
    np.testing.assert_allclose(np.asarray(cache.state[:, 0]), np.stack(after), atol=ATOL)
    counted = dm.fold_counters(np.asarray(cache.counters))
    assert (counted["kda_verify_positions"], counted["kda_verify_positions_kept"]) == (n * 8, kept * 8)
    assert counted["moe_chunk_assignments_held"] > 0  # the verify step's count under ``chunk``
    for at in range(start + kept, start + kept + 3):  # the frontier stands behind the kept positions
        slot = S0 + at - start
        step, cache = model_call()(jnp.asarray([[tokens[at]]], jnp.int32), jnp.asarray([[at]]), cache, ks,
                                   jnp.full((1,), slot + 1, jnp.int32), jnp.int32(slot))
        np.testing.assert_allclose(np.asarray(step[0, 0]), reference(tokens[:at + 1])[-1], atol=ATOL)


def test_commit_told_one_position_fewer_is_the_fault_the_control_names():
    tokens = prompt_of(100, 7)
    (_, cache), ks = chunk_call(tokens, 57, 16, keep_steps=True)
    cache = dm.commit(cache, jnp.int32(6))  # seven were kept
    step, _ = model_call()(jnp.asarray([[tokens[64]]], jnp.int32), jnp.asarray([[64]]), cache, ks,
                           jnp.full((1,), S0 + 8, jnp.int32), jnp.int32(S0 + 7))
    assert np.abs(np.asarray(step[0, 0]) - reference(tokens[:65])[-1]).max() > 100 * ATOL


@pytest.mark.parametrize("impl,start,n", [("xla", 20, 11), ("xla", 20, 16), ("pallas_interpret", 60, 16)])
def test_a_chunk_over_the_cache_starts_from_the_state_it_is_handed(impl, start, n):
    tokens = prompt_of(start + n + 1, 5)
    (logits, cache), ks = chunk_call(tokens, start, n, impl=impl)
    np.testing.assert_allclose(np.asarray(logits[0]), reference(tokens[:start + n])[start:], atol=ATOL)
    slot = S0 + n
    step, _ = model_call(impl)(jnp.asarray([[tokens[start + n]]], jnp.int32), jnp.asarray([[start + n]]), cache, ks,
                               jnp.full((1,), slot + 1, jnp.int32), jnp.int32(slot))
    np.testing.assert_allclose(np.asarray(step[0, 0]), reference(tokens)[-1], atol=ATOL)
    if impl != "xla":  # the kernel starts from the state it is handed, as from zeros
        assert tracing.kernel_builds()[("chunk", "delta_rule_chunked_pallas")] > 0


@pytest.mark.parametrize("impl,S,kw,mode,kernel", [
    ("pallas_interpret", S0, {}, "prefill", "delta_rule_chunked_pallas"),
    ("pallas_interpret", 16, {"chunked": True}, "chunk", "delta_rule_chunked_pallas"),
    ("pallas_interpret", 16, {"chunked": True, "keep_steps": True}, "chunk", "delta_rule_chunked_xla"),
    ("xla", S0, {}, "prefill", "delta_rule_chunked_xla"),
    ("xla", 16, {"chunked": True}, "chunk", "delta_rule_chunked_xla"),
    ("pallas_interpret", 1, {}, "decode", "delta_rule_step")])
def test_the_build_counter_names_the_form_of_the_recurrence(impl, S, kw, mode, kernel):
    """``rag_attend_kernel_builds_total{mode, kernel}``: the kernel for a
    fresh prefill and a prompt chunk from a held state where ``impl`` builds
    kernels; XLA's chunk form for a verify step (one chunk from the state,
    whatever ``impl``) and on the CPU; the step form for a token."""
    model = dm.DeltaMoEModel(CFG, FP32, attn_impl=impl, **kw)
    cache = dm.make_delta_cache(CFG, 1, 256, jnp.float32)
    ids = jnp.zeros((1, S), jnp.int32)
    before = tracing.kernel_builds()
    jax.eval_shape(lambda *a: model.apply({"params": PARAMS}, *a), ids, ids, cache, jnp.zeros((1,), jnp.int32),
                   jnp.full((1,), 100, jnp.int32), jnp.int32(0 if mode == "prefill" else 80))
    built = {key for key, n in tracing.kernel_builds().items() if n > before.get(key, 0)}
    assert (mode, kernel) in built
    assert not {name for _, name in built if name.startswith("delta_rule")} - {kernel}


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_a_batch_goes_through_the_mixer_a_row_at_a_time_by_shape(monkeypatch, impl):
    """The rule reads the shape alone, and a batch served a row at a time is
    the batch: each row's recurrence then starts at its OWN first live chunk
    (through the kernel too: each row's call meets it at batch 1)."""
    assert not dm.mixer_by_rows(DeltaMoEConfig(), 1, 4096) and not dm.mixer_by_rows(DeltaMoEConfig(), 2, 2048)
    assert dm.mixer_by_rows(DeltaMoEConfig(), 2, 4096) and dm.mixer_by_rows(DeltaMoEConfig(), 8, 2048)
    rows = [prompt_of(150, 2), prompt_of(40, 3)]
    want, _, _ = through_the_cache(rows, 192, [150, 40])
    monkeypatch.setattr(lm, "ROWWISE_BYTES", 1)
    _CALLS.clear()
    got, after, _ = through_the_cache(rows, 192, [150, 40], impl)
    _CALLS.clear()
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=ATOL)
    counted = dm.fold_counters(np.asarray(after.counters))
    assert counted["kda_prefill_positions"] == (192 + 64) * CFG.num_kda_layers  # 42 and 152 pads: chunks 0 and 2


# ---- (c) the share ties to the model; the routing rule ----


def test_the_shares_of_the_expert_layer_sum_to_the_uncut_layer():
    """The parts of the result that all the shares give, with what every chip
    computes alike (the shared expert) counted once, add up to what the uncut
    layer gives."""
    whole = dataclasses.replace(CFG, ep_size=1, ep_rank=0)
    p_whole = seeded_params(whole, seed=3)
    layer = jax.tree.map(lambda a: a[0], p_whole["layers"]["mlp"])
    x = jnp.asarray(np.random.default_rng(4).standard_normal((2, 24, CFG.hidden_size)), jnp.float32)

    def run(cfg, stack):
        y, _ = lm.SparseMLP(cfg, FP32, "xla").apply({"params": layer}, x, stack, jnp.int32(1))
        return np.asarray(y, np.float64)

    stack = tuple(p_whole["experts"][n] for n in ("w_gate", "w_up", "w_down"))
    ranks = 4
    with jax.default_matmul_precision("highest"):
        uncut = run(whole, stack)
        held = CFG.num_experts // ranks
        shares = [run(dataclasses.replace(CFG, ep_size=ranks, ep_rank=r),
                      tuple(w[:, r * held:(r + 1) * held] for w in stack)) for r in range(ranks)]
        sh = layer["shared"]
        shared = np.asarray(ref._swiglu(x, sh["w_gate"]["kernel"], sh["w_up"]["kernel"], sh["w_down"]["kernel"]),
                            np.float64)
    np.testing.assert_allclose(sum(shares) - (ranks - 1) * shared, uncut, atol=5e-5)
    assert all(np.abs(s - shared).max() > 1e-2 for s in shares)


def test_choice_is_by_score_plus_bias_and_weight_by_score_times_the_scaling_factor():
    x = jnp.asarray(np.random.default_rng(5).standard_normal((40, CFG.hidden_size)), jnp.float32)
    mlp = jax.tree.map(lambda a: a[2], PARAMS["layers"]["mlp"])
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.route(x, mlp, CFG))
        logits = jnp.dot(x, mlp["router"]["kernel"])
        experts, weights = moe.route(
            logits, mlp["router_bias"], top_k=CFG.num_experts_per_tok, n_group=CFG.n_group, topk_group=CFG.topk_group,
            scaling=CFG.routed_scaling_factor, normalize=CFG.norm_topk_prob, scoring=CFG.scoring_func, impl="xla",
            eps=CFG.norm_topk_eps)
    got = np.zeros(want.shape)
    np.put_along_axis(got, np.asarray(experts), np.asarray(weights), axis=-1)
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_allclose(want.sum(-1), CFG.routed_scaling_factor, rtol=1e-5)


# ---- (d) the comparison can fail ----


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_a_fault_fails_the_tolerance(fault):
    tokens = prompt_of(75, 1)
    (got,), _, _ = through_the_cache([tokens], S0, [70])
    assert np.abs(got - forward(tokens, fault)).max() > 100 * ATOL


# ---- (e) the engine's programs ----


def engine_for(**kw):
    ec = dict(prompt_buckets=(64, 128), max_batch_size=4, max_seq_len=256, attn_impl="xla", speculative="off")
    ec.update(kw)
    return InferenceEngine(CFG, PARAMS, sampling=GREEDY, dtypes=FP32, engine_config=EngineConfig(**ec))


def test_batched_rows_of_unequal_length_and_the_counters_they_leave():
    prompts = [prompt_of(n, 10 + n) for n in (61, 40)]
    engine = engine_for()
    assert engine.generate(prompts) == [greedy_reference(p, NEW) for p in prompts]
    counted = engine.stats.family_counters
    assert counted["kda_decode_positions"] == (NEW - 1) * 2 * CFG.num_kda_layers
    assert counted["kda_prefill_positions"] == counted["kda_prefill_positions_bucketed"] == 2 * 64 * CFG.num_kda_layers
    assert counted["moe_decode_layer_steps"] == (NEW - 1) * CFG.num_moe_layers
    assert counted["moe_decode_assignments_computed"] == counted["moe_decode_assignments_held"]


def repeating(n, period, seed):
    return [prompt_of(period, seed)[i % period] for i in range(n)]


@pytest.mark.parametrize("prompt,why", [
    (repeating(50, 7, 31), "a prompt that repeats: proposals accepted in full and in part"),
    (prompt_of(50, 32), "no repeat: nothing accepted"),
])
def test_the_verify_loop_is_the_vanilla_loop(prompt, why):
    """Prompt-lookup speculation commits what it kept: the stream is the
    vanilla greedy stream, which is the reference's."""
    sampling = SamplingConfig(do_sample=False, max_new_tokens=16)
    engine = InferenceEngine(CFG, PARAMS, sampling=sampling, dtypes=FP32, engine_config=EngineConfig(
        prompt_buckets=(64, 128), max_batch_size=4, max_seq_len=256, attn_impl="xla",
        speculative="prompt_lookup", spec_tokens=5, spec_ngram=2))
    assert engine.generate([prompt]) == [greedy_reference(prompt, 16)]
    counted = engine.stats.family_counters
    assert counted["kda_verify_positions"] == 6 * engine.stats.spec_verify_steps * CFG.num_kda_layers
    assert counted["kda_verify_positions_kept"] == engine.stats.spec_emitted_tokens * CFG.num_kda_layers


def test_a_prompt_past_the_largest_bucket_prefills_in_chunks():
    prompt = prompt_of(200, 21)  # two chunks of the largest bucket
    assert engine_for().generate([prompt]) == [greedy_reference(prompt, NEW)]


def test_score_exact_is_the_reference():
    prompt = prompt_of(45, 41)
    emitted = greedy_reference(prompt, NEW)
    got = engine_for().score_exact(prompt, emitted)
    logits = reference(prompt + emitted)[len(prompt) - 1:-1]
    np.testing.assert_array_equal(got["argmax"], np.argmax(logits, axis=-1))
    np.testing.assert_allclose(got["max_logit"], logits.max(axis=-1), atol=ATOL)
    np.testing.assert_allclose(got["chosen_logit"], logits[np.arange(NEW), emitted], atol=ATOL)


# ---- (f) what the family cannot be served with yet; the configuration ----


@pytest.mark.parametrize("kw,engine,names", [
    (dict(batching="continuous"), "one-shot", "continuous"),
    (dict(), "continuous", "paged KV pool"),
    (dict(prefix_cache=PrefixCacheConfig(enabled=True)), "one-shot", "prefix cache"),
    (dict(kv_quant="int8"), "one-shot", "kv_quant='int8'"),
    (dict(weight_quant="int8"), "one-shot", "weight_quant='int8'"),
])
def test_refusals_name_the_mechanism(kw, engine, names):
    ec = EngineConfig(**{**dict(prefix_cache=PrefixCacheConfig(enabled=False)), **kw})
    with pytest.raises(NotImplementedError, match="gated delta-rule sparse-expert family") as e:
        families.refuse_unsupported(CFG, ec, None, engine=engine)
    assert names in str(e.value)


def test_tensor_parallel_is_refused_by_name_and_the_row_carries_commit():
    mesh = make_mesh(MeshConfig(dp=1, sp=1, tp=2), devices=jax.devices()[:2])
    ec = EngineConfig(prefix_cache=PrefixCacheConfig(enabled=False))
    with pytest.raises(NotImplementedError, match="tp=2"):
        families.refuse_unsupported(CFG, ec, mesh)
    family = families.of(CFG)
    assert family.commit is dm.commit and family.verify_span is None
    assert "name map" in family.checkpoint_loader_refusal and "DeltaMoEConfig" in family.name
    assert family.counter_names == dm.COUNTER_NAMES and family.counters_width == dm.N_COUNTERS
    assert dm.COUNTER_NAMES[:lm.N_COUNTERS and len(lm.COUNTER_STATS)] == tuple(lm.COUNTER_STATS)
    assert families.of(LlamaConfig.tiny()).commit is None  # a frontier does the job there


@pytest.mark.parametrize("bad,says", [
    (dict(full_attn_layers=(4, 8)), "every layer"), (dict(kda_layers=(1, 2, 3, 4)), "every layer"),
    (dict(q_lora_rank=32), "q_lora_rank"), (dict(short_conv_kernel_size=1), "short_conv_kernel_size"),
    (dict(ep_size=3), "ep_size"), (dict(first_k_dense_replace=12), "first_k_dense_replace"),
    (dict(first_k_dense_replace=4), "leading dense"), (dict(num_expert_group=3), "num_expert_group"),
    (dict(tie_word_embeddings=True), "untied"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_the_configuration_refuses_what_the_block_does_not_run(bad, says):
    with pytest.raises(ValueError, match=says):
        DeltaMoEConfig.tiny(**bad)


def test_roofline_terms_count_the_state_once_and_the_latent_planes_by_position():
    c = DeltaMoEConfig(ep_size=16, vocab_size=20480)
    flops, weight_bytes, kv_bytes = c.roofline_terms()
    kda = 4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
    mla = 2304 * 6144 + 2304 * 576 + 512 * 8192 + 4096 * 2304
    sparse = 2304 * 256 + (1 + 8 * 16 / 256) * 3 * 2304 * 1024
    active = 20 * kda + 7 * mla + 3 * 2304 * 9216 + 26 * sparse + 20480 * 2304
    assert flops == 2.0 * active
    state = 4 * 32 * 128 * 128 + 2 * 3 * 4096 * 3  # a float32 matrix a head, three kept inputs of q, k and v
    assert weight_bytes == 2.0 * active + 2.0 * 20 * state  # read and written a step, constant in the context
    assert kv_bytes == 2.0 * 7 * (512 + 64)  # the seven full layers' latent rows only
    assert 2.5e9 < weight_bytes < 3.2e9  # ~2.9 GB a token at batch 1


# ---- (g) the tile rules at a width 512 does not divide ----


@pytest.mark.parametrize("case,m,G,k,n,want", [
    ("2304 up, a decode step's buffer", 128, 16, 2304, 1024, (128, 768, 1024)),
    ("2304 down, a decode step's buffer", 128, 16, 1024, 2304, (128, 1024, 768)),
    ("2304 up, one row's prefill", 4096, 16, 2304, 1024, (512, 768, 1024)),
    ("2304 down, one row's prefill", 4096, 16, 1024, 2304, (256, 1024, 768)),
    ("2304 up, eight rows' prefill", 32768, 16, 2304, 1024, (512, 768, 1024)),
    ("dots up", 4096, 16, 7168, 2048, (512, 1024, 1024)), ("dots down", 4096, 16, 2048, 7168, (512, 1024, 1024)),
    ("longcat up", 4096, 16, 6144, 2048, (512, 1024, 1024)),
    ("lfm2 up", 4096, 64, 2048, 1536, (128, 2048, 512)), ("lfm2 down", 4096, 64, 1536, 2048, (128, 1536, 1024)),
    ("lfm2 up, eight rows", 32768, 64, 2048, 1536, (512, 2048, 512)),
    ("laguna up", 4096, 16, 3072, 1024, (512, 1024, 1024)), ("laguna down", 4096, 16, 1024, 3072, (256, 1024, 1024)),
])
def test_the_grouped_kernel_s_tiles_at_2304_and_the_other_cells_unchanged(case, m, G, k, n, want):
    assert moe.grouped_blocks(m, G, k, n, 2) == want


@pytest.mark.parametrize("width,pref,want", [(2304, 1024, 768), (2304, 512, 384), (1536, 1024, 512), (7168, 1024, 1024),
                                             (3072, 1024, 1024), (1024, 1024, 1024), (192, 1024, 192)])
def test_a_width_512_does_not_divide_takes_its_widest_lane_multiple(width, pref, want):
    assert moe._fit_width(width, pref) == want


def test_the_other_rules_take_2304_as_it_is():
    assert moe.route_blocks(4096, 256, 1) == 8 and moe.route_blocks(8, 256, 1) is None
    assert moe.combine_blocks(4096, 4096, 2304, 2) == (256, 128, 2304)  # the whole width a cell
    assert moe.combine_blocks(8, 128, 2304, 2) is None  # a decode step takes the dot
    assert moe.rows_per_pass(4096, 8, 256, 16) % moe.ROW_ALIGN == 0
    assert delta_rule.CHUNK == 64
