"""Batched embedding runner over the Flax bge-m3 encoder.

The reference embeds ONE chunk per ``SentenceTransformer.encode`` call in a
Python loop (/root/reference/llm/rag.py:55,101,133). Here ingest batches whole
chunk sets into bucketed device calls (BASELINE.json config #2: the
"batch embedding (PDF-chunk ingest path)") — right-padded, mask-aware, one
executable per (batch, length) bucket.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from rag_llm_k8s_tpu.core.config import DTypePolicy, EncoderConfig
from rag_llm_k8s_tpu.core.mesh import MeshContext
from rag_llm_k8s_tpu.engine.engine import param_avals
from rag_llm_k8s_tpu.models.bge_m3 import BgeM3Encoder
from rag_llm_k8s_tpu.obs import tracing
from rag_llm_k8s_tpu.resilience import faults
from rag_llm_k8s_tpu.utils.buckets import bucket_len, next_pow2
from rag_llm_k8s_tpu.utils.tokens import truncate_keep_eos


class EncoderRunner:
    def __init__(
        self,
        config: EncoderConfig,
        params,
        dtypes: DTypePolicy = DTypePolicy(),
        mesh: Optional[MeshContext] = None,
        # 1536/3072 snug buckets: the reference's 1000-word chunks tokenize
        # to ~1.3-1.5k pieces — padding them to 2048 wastes a third of every
        # (compute-bound) ingest forward
        length_buckets: Sequence[int] = (
            64, 128, 256, 512, 1024, 1536, 2048, 3072, 4096, 8192
        ),
        max_batch: int = 32,
        eos_id: Optional[int] = None,
        attn_impl: str = "auto",  # BgeM3Encoder.attn_impl
    ):
        self.config = config
        self.params = params
        self.dtypes = dtypes
        self.mesh = mesh
        # when set, sequences clamped to the largest bucket keep a trailing
        # EOS — bge-m3's CLS pooling is trained on </s>-terminated input
        self.eos_id = eos_id
        self.length_buckets = tuple(
            b for b in length_buckets if b <= config.max_encode_len
        ) or (config.max_encode_len,)
        self.max_batch = max_batch
        self.model = BgeM3Encoder(config, dtypes, attn_impl)
        self._jit = jax.jit(
            lambda params, tokens, mask: self.model.apply(
                {"params": params}, tokens, mask
            )
        )
        # what the traced forward closes over (``tracing.build_span`` keys a
        # build on it); the server's fused embed+kNN program holds the same model
        self.build_identity = ("encoder", config, dtypes, attn_impl, jax.default_backend())
        self._compiled = {}  # (batch, length bucket) -> executable

    def _get(self, B: int, S: int):
        fn = self._compiled.get((B, S))
        if fn is None:
            i32 = jax.ShapeDtypeStruct((B, S), jnp.int32)
            fn = self._compiled[(B, S)] = tracing.build_span(
                "encode", (B, S), lambda: (self._jit, (param_avals(self.params), i32, i32)),
                identity=self.build_identity, rows=B, bucket=S)
        return fn

    def prepare_batch(self, ids: Sequence[int]):
        """One bucketed, padded, EOS-preserving ``[1, S]`` (tokens, mask)
        pair — the SAME truncation/bucketing rules the ingest path applies,
        shared with the server's fused query-retrieval so query and chunk
        embeddings can never diverge."""
        S = bucket_len(max(len(ids), 1), self.length_buckets)
        ids = truncate_keep_eos(ids, S, self.eos_id)
        tokens = np.full((1, S), self.config.pad_token_id, np.int32)
        mask = np.zeros((1, S), np.int32)
        tokens[0, : len(ids)] = ids
        mask[0, : len(ids)] = 1
        return tokens, mask

    def encode(self, token_lists: Sequence[Sequence[int]]) -> np.ndarray:
        """Token-id sequences → ``[N, hidden]`` fp32 unit vectors.

        Two-phase: DISPATCH every bucketed group back-to-back (JAX dispatch
        is async, so the device pipeline stays full and the host pads the
        next group while the previous one computes), then fetch ALL results
        in one device→host transfer. One fetch per call instead of one per
        ``max_batch`` group — on a slow host link the per-group fetch was
        ~40% of warm ingest time (round-4: ~13 ms of every chunk's 49 ms).
        """
        if not token_lists:
            return np.zeros((0, self.config.hidden_size), np.float32)
        faults.maybe_fail("embed")
        out = np.zeros((len(token_lists), self.config.hidden_size), np.float32)
        # group by length bucket to minimize padding waste
        order = sorted(range(len(token_lists)), key=lambda i: len(token_lists[i]))
        pending = []  # (group, device_emb)
        pad = self.config.pad_token_id
        for start in range(0, len(order), self.max_batch):
            group = order[start : start + self.max_batch]
            S = bucket_len(max(len(token_lists[i]) for i in group), self.length_buckets)
            B = next_pow2(len(group))
            tokens = np.full((B, S), pad, np.int32)
            mask = np.zeros((B, S), np.int32)
            for row, i in enumerate(group):
                ids = truncate_keep_eos(token_lists[i], S, self.eos_id)
                tokens[row, : len(ids)] = ids
                mask[row, : len(ids)] = 1
            pending.append(
                (group, self._get(B, S)(self.params, jnp.asarray(tokens), jnp.asarray(mask)))
            )
        # device-side concat → ONE host fetch for the whole call (group
        # batch dims differ, but the hidden dim is shared)
        stacked = np.asarray(jnp.concatenate([e for _, e in pending], axis=0))
        off = 0
        for group, e in pending:
            for row, i in enumerate(group):
                out[i] = stacked[off + row]
            off += e.shape[0]
        return out
