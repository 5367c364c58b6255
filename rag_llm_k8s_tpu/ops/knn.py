"""Brute-force L2 kNN as a fused Pallas TPU kernel — the faiss replacement.

The reference's retrieval is ``faiss.IndexFlatL2.search`` on CPU
(/root/reference/llm/rag.py:61,116): exact squared-L2 over all chunk
embeddings, k=5. Here the embedding matrix lives in HBM as ``[N, 1024]``;
one kernel fuses

    distance matmul (MXU)  →  running top-k selection (VPU, VMEM scratch)

over row blocks of the matrix, so candidate distances never round-trip to
HBM — only the final ``[Q, k]`` result leaves the chip (BASELINE.json
config #4: "faiss.IndexFlatL2 kNN as Pallas kernel over HBM-resident chunk
embeddings").

Grid layout: 1-D over row blocks (sequential on TPU), with the running
top-k carried in VMEM scratch across grid steps. Per block:
``d = ||q||² + ||e||² − 2·q·eᵀ`` (true squared L2, matching the scores the
reference prints into its context string, rag.py:165), then k rounds of
min/argmin/mask merge the block into the running top-k. k is tiny (5), so
selection is k VPU passes over ``[Q, k + BN]``.

Squared-L2 on unit vectors is monotone in cosine (2 − 2cos), so ranking
parity with the reference's normalized embeddings is exact.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rag_llm_k8s_tpu.obs.tracing import phase_scope

BIG = 3.4e38  # +inf stand-in that survives arithmetic (python float: not traced)


def _knn_kernel(q_ref, e_ref, en_ref, vals_ref, idx_ref, top_v, top_i, *, block_n: int, k: int):
    """One grid step: merge a [BN, D] block of embeddings into the running top-k."""
    i = pl.program_id(0)
    n_blocks = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        top_v[:] = jnp.full_like(top_v, BIG)
        top_i[:] = jnp.full_like(top_i, -1)

    q = q_ref[:]  # [Q, D] fp32
    e = e_ref[:]  # [BN, D] fp32
    qn = jnp.sum(q * q, axis=1, keepdims=True)  # [Q, 1]
    dot = jax.lax.dot_general(
        q, e, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [Q, BN]
    d = qn + en_ref[0, :][None, :] - 2.0 * dot  # [Q, BN]; padded rows carry BIG norms

    base = i * block_n
    Q = d.shape[0]
    cand_v = jnp.concatenate([top_v[:], d], axis=1)  # [Q, k+BN]
    block_ids = base + jax.lax.broadcasted_iota(jnp.int32, (Q, block_n), 1)
    cand_i = jnp.concatenate([top_i[:], block_ids], axis=1)

    cols = jax.lax.broadcasted_iota(jnp.int32, cand_v.shape, 1)
    out_cols = jax.lax.broadcasted_iota(jnp.int32, (Q, k), 1)
    new_v = top_v[:]
    new_i = top_i[:]
    for j in range(k):  # k static and tiny: unrolled VPU passes
        am = jnp.argmin(cand_v, axis=1)  # [Q]
        hit = cols == am[:, None]
        # (.at[:, j].set would lower to scatter — unsupported in Mosaic;
        #  select on the static column index instead)
        new_v = jnp.where(out_cols == j, jnp.min(cand_v, axis=1)[:, None], new_v)
        new_i = jnp.where(
            out_cols == j, jnp.sum(jnp.where(hit, cand_i, 0), axis=1)[:, None], new_i
        )
        cand_v = jnp.where(hit, BIG, cand_v)
    top_v[:] = new_v
    top_i[:] = new_i

    @pl.when(i == n_blocks - 1)
    def _emit():
        vals_ref[:] = top_v[:]
        idx_ref[:] = top_i[:]


@functools.partial(jax.jit, static_argnames=("k", "block_n", "interpret"))
@phase_scope("retrieve/knn")
def knn_topk_pallas(
    queries: jax.Array,  # [Q, D] fp32
    embeddings: jax.Array,  # [N_pad, D] fp32, rows >= n_valid are arbitrary
    sq_norms: jax.Array,  # [1, N_pad] fp32, padded entries = BIG
    k: int = 5,
    block_n: int = 512,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Fused distance + top-k. ``N_pad`` must be a multiple of ``block_n``."""
    Q, D = queries.shape
    N = embeddings.shape[0]
    assert N % block_n == 0, (N, block_n)
    grid = (N // block_n,)
    return pl.pallas_call(
        functools.partial(_knn_kernel, block_n=block_n, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((Q, D), lambda i: (0, 0)),
            pl.BlockSpec((block_n, D), lambda i: (i, 0)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((Q, k), lambda i: (0, 0)),
            pl.BlockSpec((Q, k), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Q, k), jnp.float32),
            jax.ShapeDtypeStruct((Q, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((Q, k), jnp.float32),
            pltpu.VMEM((Q, k), jnp.int32),
        ],
        interpret=interpret,
        name="knn_topk_pallas",  # the trace's row for this kernel, whatever the function is called
    )(queries, embeddings, sq_norms)


@functools.partial(jax.jit, static_argnames=("k",))
@phase_scope("retrieve/knn")
def knn_topk_xla(
    queries: jax.Array,  # [Q, D]
    embeddings: jax.Array,  # [N_pad, D]
    sq_norms: jax.Array,  # [1, N_pad]
    k: int = 5,
) -> Tuple[jax.Array, jax.Array]:
    """Pure-XLA reference/fallback (CPU tests, numerics oracle)."""
    qn = jnp.sum(queries * queries, axis=1, keepdims=True)
    d = qn + sq_norms - 2.0 * (queries @ embeddings.T)
    neg, idx = jax.lax.top_k(-d, k)
    return -neg, idx.astype(jnp.int32)


def knn_topk(
    queries: jax.Array,
    embeddings: jax.Array,
    sq_norms: jax.Array,
    k: int = 5,
    block_n: int = 512,
) -> Tuple[jax.Array, jax.Array]:
    """Backend dispatch: Pallas on TPU, XLA elsewhere. On TPU a row count
    that does not tile ``block_n`` is an error, not a quiet fall to the XLA
    path: the store pads its device snapshot to >= 512-row buckets
    (index/store.py), so a misaligned count means a caller bypassed it."""
    if jax.default_backend() != "tpu":
        return knn_topk_xla(queries, embeddings, sq_norms, k=k)
    if embeddings.shape[0] % block_n:
        raise ValueError(
            f"knn_topk on TPU needs a row count that is a multiple of "
            f"block_n={block_n}, got {embeddings.shape[0]}: pad the index "
            "snapshot (VectorStore.device_snapshot does)"
        )
    return knn_topk_pallas(queries, embeddings, sq_norms, k=k, block_n=block_n)
