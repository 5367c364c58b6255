"""The state-space-duality latent-expert family (models/ssd_moe.py) against its
plain reference (tests/nemotron_h_reference.py: float32, the recurrence a
position at a time, a loop over the experts), LOGITS not tokens, at the toy
size of tests/nemotron_h_toy.py on the CPU. ``nemotron_h_toy.ATOL`` says what
the tolerance is and why."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import nemotron_h_reference as ref
from nemotron_h_toy import (
    ATOL, CFG, CHUNK, M, PARAMS, calls, forward, prompt_of, reference, sizes_of, through_the_cache, uncut,
)
from rag_llm_k8s_tpu.core.config import SSDMoEConfig
from rag_llm_k8s_tpu.models import ssd_moe as sm

HERE = os.path.dirname(os.path.abspath(__file__))


# ---- (a) prefill then decode through the cache is the reference's full forward ----


@pytest.mark.parametrize("S,lengths,why", [
    (32, (29, 17), "left pads that end inside a chunk of 8, rows of unequal length"),
    (32, (32, 8), "no pad at all beside a row that is three chunks of pads"),
    (40, (33, 1), "five chunks, of which a one-token row walks the last alone"),
])
def test_prefill_then_decode_equals_the_full_forward(S, lengths, why):
    rows = [prompt_of(n + 6, 10 * S + n) for n in lengths]
    got, cache = through_the_cache(rows, S, lengths)
    for g, row in zip(got, rows):
        np.testing.assert_allclose(g, reference(row)[:len(g)], atol=ATOL)
    counted = sm.fold_counters(np.asarray(cache.counters))
    assert counted["ssd_prefill_positions"] == M * sum(lengths)  # live positions, not pads
    assert counted["ssd_prefill_positions_bucketed"] == M * S * len(rows)
    assert counted["ssd_prefill_chunks"] == M * len(rows) * (-(-S // CHUNK) - (S - max(lengths)) // CHUNK)
    assert counted["ssd_decode_positions"] == M * len(rows) * 6
    assert counted["moe_prefill_layer_calls"] == CFG.num_moe_layers
    assert counted["moe_decode_layer_steps"] == 6 * CFG.num_moe_layers
    for mode in ("prefill", "decode"):  # every assignment to a held expert is computed and combined: nothing dropped
        held = counted[f"moe_{mode}_assignments_held"]
        assert held > 0 and held == counted[f"moe_{mode}_assignments_computed"] == counted[
            f"moe_{mode}_assignments_combined"]


def test_the_kernels_serve_it_in_interpret_mode():
    """The flash prefill, the decode walk, the grouped matmul and the combine
    in front of the same reference (``pallas_interpret``; the router's kernel
    starts at 1024 tokens and the recurrence has no kernel yet)."""
    row = prompt_of(130, 3)
    (got,), cache = through_the_cache([row], 128, [126], impl="pallas_interpret")
    np.testing.assert_allclose(got, reference(row)[:len(got)], atol=ATOL)
    counted = sm.fold_counters(np.asarray(cache.counters))
    assert counted["decode_slots_allocated"] == 4 * 256 and 0 < counted["decode_slots_streamed"] <= 4 * 256


def test_the_engine_s_prompt_call_is_the_last_position():
    row = prompt_of(30, 4)
    padded = np.zeros((1, 32), np.int32)
    padded[0, 2:] = row
    ks = jnp.asarray([2], jnp.int32)
    logits, cache = calls()[1](jnp.asarray(padded), jnp.maximum(jnp.arange(32)[None] - 2, 0),
                               sm.make_ssd_cache(CFG, 1, 64, jnp.float32), ks, jnp.full((1,), 32, jnp.int32),
                               jnp.int32(0))
    np.testing.assert_allclose(np.asarray(logits[0, 0]), reference(row)[-1], atol=ATOL)
    assert cache.state.shape == (M, 1, 8, 16, 16) and cache.state.dtype == jnp.float32  # heads, head_dim, N: N the lanes
    assert cache.conv.shape == (M, 1, 3, 8 * 16 + 2 * 2 * 16) and cache.k.shape == (1, 1, 2, 64, 16)


def test_a_row_of_nothing_but_pads_leaves_no_state():
    rows = [prompt_of(20, 5), []]
    padded = np.zeros((2, 24), np.int32)
    padded[0, 4:] = rows[0]
    ks = jnp.asarray([4, 24], jnp.int32)
    _, cache = calls()[0](jnp.asarray(padded), jnp.maximum(jnp.arange(24)[None] - ks[:, None], 0),
                          sm.make_ssd_cache(CFG, 2, 32, jnp.float32), ks, jnp.full((2,), 24, jnp.int32), jnp.int32(0))
    assert not np.asarray(cache.state[:, 1]).any() and not np.asarray(cache.conv[:, 1]).any()
    assert np.asarray(cache.state[:, 0]).any()


# ---- (b) the faults the comparison must see ----


_SERVED = {}


def served(tokens):
    """The program's logits of ``tokens`` through the cache, once for all controls."""
    if tokens not in _SERVED:
        (_SERVED[tokens],), _ = through_the_cache([list(tokens)], 48, [40])
    return _SERVED[tokens]


@pytest.mark.parametrize("control", ref.CONTROLS)
def test_a_control_fails_the_tolerance(control):
    """Each control of the reference (the group norm, the gate's order, ``B``
    and ``C`` by group, the squared relu, the scaling, weights from the score
    alone, the state's type, every matmul a precision down) moves a logit by
    tens of tolerances, and the program stands on the sound side: the chip's
    limits are set against the same controls
    (benchmark/tests/controls_nemotron_h.py), and one the chip cannot tell
    from sound is held here, against float32."""
    tokens = prompt_of(52, 1)
    sound, bad = reference(tokens), forward(tokens, control=control)
    assert np.abs(bad - sound).max() > 10 * ATOL
    got = served(tuple(tokens))
    assert np.abs(got - bad).max() > 10 * ATOL
    np.testing.assert_allclose(got, sound, atol=ATOL)


# ---- (c) the share: four ranks' partial sums and what all compute alike add up to the uncut layer ----


def test_the_ranks_partial_sums_add_up_to_the_uncut_layer():
    """model-configs section 4: one expert layer's output on the same input,
    as each of four ranks of EP4 computes it (its held experts' weighted
    partial sum through ``W_up``, plus the shared expert that every rank
    computes alike), against the layer with every expert held. The shared
    expert and the residual are in every rank's output, so they are counted
    once: ``sum_r (F_r - base) + base = F_uncut``, ``base`` the layer with no
    routed expert at all."""
    cfg4 = dataclasses.replace(CFG, ep_size=4, ep_rank=0)
    whole, params = uncut()
    x = jnp.asarray(np.random.default_rng(2).standard_normal((24, CFG.hidden_size)), jnp.float32)
    layer = {n[len("moe_"):]: params[n][1] for n in params if n.startswith("moe_")}
    stacks = (params["experts_w_up"], params["experts_w_down"])

    def output(cfg, stacks):
        with jax.default_matmul_precision("highest"):
            return np.asarray(ref.moe_layer(x, params["norms"][4], layer, stacks, 1, sizes_of(cfg)), np.float64)

    full = output(whole, stacks)
    held = CFG.n_routed_experts // 4
    none = tuple(s[:, :0] for s in stacks)
    base = output(dataclasses.replace(cfg4, ep_rank=0), none)  # residual + shared expert, no routed expert
    total = base.copy()
    for rank in range(4):
        share = tuple(s[:, rank * held:(rank + 1) * held] for s in stacks)
        part = output(dataclasses.replace(cfg4, ep_rank=rank), share) - base
        assert np.abs(part).max() > 100 * ATOL  # every rank holds experts the tokens chose
        total += part
    np.testing.assert_allclose(total, full, atol=ATOL)

    # and the program's share is the reference's share: rank 1 of 2, as the toy configuration cuts it
    row = prompt_of(28, 9)
    (got,), _ = through_the_cache([row], 32, [28])
    np.testing.assert_allclose(got, reference(row), atol=ATOL)
    assert np.abs(forward(row, cfg=whole, params=params) - reference(row)).max() > 100 * ATOL  # the cut is felt


# ---- (d) the configuration ----


@pytest.mark.parametrize("kw,match", [
    (dict(hybrid_override_pattern="MEM*EM"), "one letter a layer"),
    (dict(hybrid_override_pattern="MEM-EME"), "dense MLP"),
    (dict(n_groups=3), "whole number of groups"),
    (dict(ep_size=3), "divide evenly"),
    (dict(tie_word_embeddings=True), "untied"),
])
def test_the_configuration_refuses_what_the_model_does_not_build(kw, match):
    with pytest.raises(ValueError, match=match):
        SSDMoEConfig.tiny(**kw)


def test_the_published_sizes_are_the_defaults():
    c = SSDMoEConfig()
    assert (c.d_inner, c.conv_width, c.in_proj_width) == (8192, 10240, 18560)
    assert (c.num_mamba_layers, c.num_moe_layers, c.num_attention_layers) == (40, 40, 8)
    assert c.hybrid_override_pattern[:11] == "MEMEMEM*EME"
    # 120.7 B parameters, 12.8 B touched a token: the published 120B-A12B
    mamba = 4096 * 18560 + 8192 * 4096 + 5 * 10240 + 3 * 128 + 8192 + 4096
    attention = 2 * 4096 * 4096 + 2 * 4096 * 256 + 4096
    expert = 2 * 1024 * 2688
    shared = 2 * 4096 * 5376 + 2 * 4096 * 1024 + 4096 * 512 + 512 + 4096
    total = 40 * mamba + 8 * attention + 40 * (shared + 512 * expert) + 2 * 131072 * 4096 + 4096
    touched = 40 * mamba + 8 * attention + 40 * (shared + 22 * expert) + 2 * 131072 * 4096
    assert round(total / 1e9, 1) == 120.7 and round(touched / 1e9, 1) == 12.8


def test_the_two_copies_of_the_reference_are_one_file():
    with open(os.path.join(HERE, "nemotron_h_reference.py"), "rb") as a, \
            open(os.path.join(HERE, "..", "benchmark", "references", "nemotron_h.py"), "rb") as b:
        assert a.read() == b.read()
