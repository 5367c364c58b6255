"""Flax bge-m3 embedding encoder (XLM-RoBERTa-large backbone).

Replaces the reference's ``SentenceTransformer('BAAI/bge-m3')`` CPU-torch
encoder (/root/reference/llm/rag.py:33,55): dense retrieval embeddings are the
CLS-token hidden state, L2-normalized (the SentenceTransformer pipeline for
bge-m3 is Transformer → CLS pooling → Normalize; normalization parity with
``normalize_embeddings=True`` at rag.py:55).

TPU-first construction mirrors ``models/llama.py``: encoder layers are
``nn.scan``-stacked (one compiled block × 24), bf16 storage/compute with fp32
LayerNorm/softmax, batched token ids in, ``[B, 1024]`` fp32 unit vectors out —
the ingest path embeds whole PDF-chunk batches in one device call where the
reference loops one chunk per ``encode`` call (rag.py:55,101).

Architecture notes (XLM-R, post-LN BERT variant):
- learned positions with a pad offset: position id = cumsum(mask) + pad_id,
  so the first real token sits at pad_id + 1 = 2;
- exact (erf) GELU;
- single token type (type vocab 1).
"""

from __future__ import annotations

from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from rag_llm_k8s_tpu.core.config import DTypePolicy, EncoderConfig
from rag_llm_k8s_tpu.obs.tracing import phase_scope

NEG_INF = -1e9


def xlmr_position_ids(tokens: jax.Array, pad_id: int) -> jax.Array:
    """XLM-R position ids: pads get ``pad_id``, token t gets cumsum offset."""
    mask = (tokens != pad_id).astype(jnp.int32)
    return jnp.cumsum(mask, axis=1) * mask + pad_id


class LayerNorm(nn.Module):
    eps: float
    dtypes: DTypePolicy

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), self.dtypes.param_dtype)
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],), self.dtypes.param_dtype)
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + self.eps)
        y = y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
        return y.astype(self.dtypes.compute_dtype)


class EncoderBlock(nn.Module):
    config: EncoderConfig
    dtypes: DTypePolicy
    attn_impl: str = "xla"  # resolved by BgeM3Encoder ("flash" on TPU)

    @nn.compact
    def __call__(self, h: jax.Array, mask_info) -> Tuple[jax.Array, None]:
        c, dt = self.config, self.dtypes
        bias, kv_len = mask_info
        D, H = c.hidden_size, c.num_heads
        hd = D // H
        dense = lambda feats, name: nn.Dense(  # noqa: E731
            feats, use_bias=True, dtype=dt.compute_dtype, param_dtype=dt.param_dtype, name=name
        )
        B, S, _ = h.shape
        q = dense(D, "wq")(h).reshape(B, S, H, hd)
        k = dense(D, "wk")(h).reshape(B, S, H, hd)
        v = dense(D, "wv")(h).reshape(B, S, H, hd)
        if self.attn_impl in ("flash", "flash_interpret"):
            # fused bidirectional flash path: the dense-scores einsum below
            # materializes an fp32 [B, H, S, S] tensor — 8.6 GB per layer
            # at the (32, 2048) INGEST shape — and made warm chunk
            # embedding HBM-bound (~the whole round-4 49 ms/chunk). The
            # Pallas kernel streams [bq, bk] blocks instead; right-padded
            # rows window via kv_len (kv_start = 0), padded QUERY rows
            # compute garbage that CLS pooling never reads.
            from rag_llm_k8s_tpu.ops.attention import flash_attention

            ctx = flash_attention(
                q, k, v, kv_len=kv_len, causal=False,
                interpret=self.attn_impl == "flash_interpret",
            )
        else:
            scores = jnp.einsum(
                "bshd,bthd->bhst", q, k, preferred_element_type=jnp.float32
            )
            scores = scores * (hd**-0.5) + bias
            probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
            ctx = jnp.einsum(
                "bhst,bthd->bshd", probs.astype(dt.compute_dtype), v,
                preferred_element_type=jnp.float32,
            ).astype(dt.compute_dtype)
        attn_out = dense(D, "wo")(ctx.reshape(B, S, D))
        h = LayerNorm(c.layer_norm_eps, dt, name="attn_ln")(h + attn_out)

        inner = dense(c.intermediate_size, "w_in")(h)
        if dt.compute_dtype == jnp.bfloat16:
            # bf16 tanh-approx GELU: the exact-erf fp32 activation over the
            # [B, S, 4096] intermediate was ~13% of the ingest forward
            # (measured 59.3 -> 68.3 chunks/s at the (32, 1536) shape);
            # embedding-similarity ranking is insensitive to the ~1e-3
            # elementwise shift. The fp32 policy (CPU parity tests vs
            # torch) keeps the exact path.
            inner = nn.gelu(inner, approximate=True)
        else:
            inner = nn.gelu(
                inner.astype(jnp.float32), approximate=False
            ).astype(dt.compute_dtype)
        ffn_out = dense(D, "w_out")(inner)
        h = LayerNorm(c.layer_norm_eps, dt, name="ffn_ln")(h + ffn_out)
        return h, None


class BgeM3Encoder(nn.Module):
    """``(tokens [B,S], mask [B,S]) -> [B, embed_dim]`` fp32 unit vectors."""

    config: EncoderConfig
    dtypes: DTypePolicy = DTypePolicy()
    attn_impl: str = "auto"  # "auto" | "flash" | "flash_interpret" | "xla"

    def _resolved_impl(self) -> str:
        if self.attn_impl not in ("auto", "flash", "flash_interpret", "xla"):
            raise ValueError(
                f"attn_impl={self.attn_impl!r}: expected auto/flash/"
                "flash_interpret/xla"
            )
        if self.attn_impl == "auto":
            return "flash" if jax.default_backend() == "tpu" else "xla"
        return self.attn_impl

    @nn.compact
    @phase_scope("retrieve/embed")  # whoever traces the encoder: a query or an ingest batch
    def __call__(self, tokens: jax.Array, mask: jax.Array) -> jax.Array:
        c, dt = self.config, self.dtypes
        word = self.param(
            "word_embeddings",
            nn.initializers.normal(0.02),
            (c.vocab_size, c.hidden_size),
            dt.param_dtype,
        )
        pos = self.param(
            "position_embeddings",
            nn.initializers.normal(0.02),
            (c.max_position_embeddings, c.hidden_size),
            dt.param_dtype,
        )
        typ = self.param(
            "token_type_embeddings",
            nn.initializers.normal(0.02),
            (c.type_vocab_size, c.hidden_size),
            dt.param_dtype,
        )
        pos_ids = xlmr_position_ids(tokens, c.pad_token_id)
        h = (
            jnp.take(word, tokens, axis=0)
            + jnp.take(pos, pos_ids, axis=0)
            + typ[0][None, None, :]
        ).astype(dt.compute_dtype)
        h = LayerNorm(c.layer_norm_eps, dt, name="embed_ln")(h)

        bias = jnp.where(mask[:, None, None, :].astype(bool), 0.0, NEG_INF).astype(jnp.float32)
        kv_len = jnp.sum(mask, axis=-1).astype(jnp.int32)  # right-padded rows
        ScanBlocks = nn.scan(
            EncoderBlock,
            variable_axes={"params": 0},
            split_rngs={"params": True},
            in_axes=nn.broadcast,
            out_axes=0,
            length=c.num_layers,
        )
        h, _ = ScanBlocks(c, dt, self._resolved_impl(), name="layers")(
            h, (bias, kv_len)
        )

        cls = h[:, 0, :].astype(jnp.float32)  # CLS pooling (bge-m3 dense head)
        norm = jnp.linalg.norm(cls, axis=-1, keepdims=True)
        return cls / jnp.maximum(norm, 1e-12)


def init_encoder_params(rng: jax.Array, config: EncoderConfig, dtypes: DTypePolicy = DTypePolicy()):
    model = BgeM3Encoder(config, dtypes)
    tokens = jnp.full((1, 8), config.pad_token_id, jnp.int32)
    mask = jnp.ones((1, 8), jnp.int32)
    return model.init(rng, tokens, mask)["params"]
