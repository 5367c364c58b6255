"""Ring attention — sequence/context parallelism over the ICI ring.

The reference avoids long context entirely (survey §5: it truncates to top-3
chunks and 150 new tokens). This framework makes long-context first-class:
sequences shard over the ``sp`` mesh axis, each device holds one block of
Q/K/V, and K/V blocks rotate around the ring via ``lax.ppermute`` while every
device accumulates its queries' attention with an online (streaming) softmax —
attention over a sequence of length S costs O(S/sp) memory per device and the
K/V transfers ride the ICI ring concurrently with compute.

Algorithm: blockwise attention with running (max, sum, out) renormalization —
the same stable accumulation flash attention uses, distributed over devices.
GQA is supported (K/V may carry fewer heads; queries group over them).

Usage: ``ring_attention`` is written for ``shard_map`` bodies (it calls
collectives by axis name); ``ring_attention_sharded`` wraps it for a mesh.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from rag_llm_k8s_tpu.core.mesh import MeshContext

NEG_INF = -1e30


def _block_attend(q, k, v, bias, scale):
    """One block pair: returns (scores_max, exp_scores @ v, exp row sums).

    q: [B, Sq, K, G, hd]; k/v: [B, Sk, K, hd]; bias: [B, 1, Sq, Sk] additive.
    All accumulation fp32.
    """
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, k, preferred_element_type=jnp.float32)
    s = s * scale + bias[:, :, None, :, :]  # [B,K,G,Sq,Sk]
    m = jnp.max(s, axis=-1)  # [B,K,G,Sq]
    # masked entries sit at <= NEG_INF/2 even after the score add; zero them
    # explicitly so fully-masked rows accumulate l=0 (emit zeros, not mean(V))
    p = jnp.where(s > NEG_INF / 2, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)  # [B,K,G,Sq]
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    return m, o, l


def ring_attention(
    q: jax.Array,  # [B, Sq_local, H, hd]   (sequence-sharded over axis_name)
    k: jax.Array,  # [B, Sk_local, K, hd]
    v: jax.Array,  # [B, Sk_local, K, hd]
    axis_name: str,
    causal: bool = True,
    kv_valid: Optional[jax.Array] = None,  # [B, Sk_local] bool (local block)
) -> jax.Array:
    """Distributed attention inside a ``shard_map`` body. Returns fp32
    ``[B, Sq_local, H, hd]``."""
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    scale = hd ** -0.5

    qg = q.reshape(B, Sq, K, G, hd)
    q_pos = my * Sq + jnp.arange(Sq)  # global query positions

    if kv_valid is None:
        kv_valid = jnp.ones((B, k.shape[1]), dtype=bool)

    def _bias(valid_blk, src):
        """Additive mask for the block currently held: key positions derive
        from the block's ORIGIN (src), and its validity mask rotates around
        the ring together with the data."""
        Sk = k.shape[1]
        k_pos = src * Sk + jnp.arange(Sk)
        ok = jnp.broadcast_to(valid_blk[:, None, :], (B, Sq, Sk))
        if causal:
            ok = ok & (k_pos[None, None, :] <= q_pos[None, :, None])
        return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)[:, None, :, :]

    # running accumulators (fp32)
    m0 = jnp.full((B, K, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, K, G, Sq), jnp.float32)
    o0 = jnp.zeros((B, Sq, K, G, hd), jnp.float32)

    perm = [(j, (j + 1) % n) for j in range(n)]

    def step(i, carry):
        m, l, o, k_blk, v_blk, valid_blk = carry
        src = (my - i) % n  # global block index of the k/v slice we now hold
        bm, bo, bl = _block_attend(qg, k_blk, v_blk, _bias(valid_blk, src), scale)
        new_m = jnp.maximum(m, bm)
        # renormalize both accumulators onto the new running max
        alpha = jnp.exp(m - new_m)  # old weight
        beta = jnp.exp(bm - new_m)  # block weight
        l = l * alpha + bl * beta
        o = (
            o * alpha.transpose(0, 3, 1, 2)[..., None]
            + bo * beta.transpose(0, 3, 1, 2)[..., None]
        )
        # rotate k/v (and their validity) one hop around the ring
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        valid_blk = jax.lax.ppermute(valid_blk, axis_name, perm)
        return new_m, l, o, k_blk, v_blk, valid_blk

    m, l, o, _, _, _ = jax.lax.fori_loop(0, n, step, (m0, l0, o0, k, v, kv_valid))
    # rows with no valid key (fully masked) produce l=0: emit zeros not NaN
    safe_l = jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
    out = (o / safe_l).reshape(B, Sq, H, hd)
    return out


def ring_attention_sharded(
    ctx: MeshContext,
    q: jax.Array,  # [B, S, H, hd] (full arrays; sharded by the wrapper)
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    kv_valid: Optional[jax.Array] = None,
) -> jax.Array:
    """shard_map wrapper: shards sequences over ``sp``, runs the ring."""
    if kv_valid is None:
        kv_valid = jnp.ones(k.shape[:2], dtype=bool)

    def body(q, k, v, valid):
        return ring_attention(q, k, v, axis_name="sp", causal=causal, kv_valid=valid)

    fn = jax.shard_map(
        body,
        mesh=ctx.mesh,
        in_specs=(
            P(None, "sp", None, None),
            P(None, "sp", None, None),
            P(None, "sp", None, None),
            P(None, "sp"),
        ),
        out_specs=P(None, "sp", None, None),
        check_vma=False,
    )
    return fn(q, k, v, kv_valid)
