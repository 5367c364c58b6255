#!/usr/bin/env python3
"""Bring-up proof: the retrieve-then-generate path on a TPU v5e, end to end.

    python chip_smoke.py [--seed N]                   # one chip, one process
    python chip_smoke.py --chips 4 [--seed N]         # the sharded path only

The default run drives the system's main path once through the entry points
a user calls, at the published widths of Llama-3.1-8B (int8 weights + int8
KV, the one-chip layout of docs/8B.md) and bge-m3, with weights made on the
device from ``--seed``:

1. device gate — no accelerator, no run (there is no CPU fallback);
2. every main-path Pallas kernel, compiled by Mosaic, against its XLA oracle
   at 8B head geometry; the sparse experts' combine against the XLA
   scatter-add at the three served prefill shapes; the router's kernel
   against its jnp body; the block-window prefill kernel told a row's live
   length against the same kernel without one;
3. the default deployment shape (coalesce batching, single-fetch RAG,
   speculation auto) assembled by ``server.main.assemble_service``, warmed,
   served by a real werkzeug server over sockets: /healthz, /upload_pdf,
   /index_info, two solo and four concurrent /generate at the reference
   budget, /metrics;
4. the paged continuous shape (block-pool KV sized from free HBM, mixed
   prefill/decode windows, paged draft-and-verify) on the same params;
5. checks on 3 and 4: token counts, finite logits, the shadow auditor at
   sample rate 1.0 inside its pinned tolerance, no executable built on any
   thread between the return of ``warmup()`` and the last audit — except
   inside the ``/upload_pdf`` request, whose builds are counted and printed
   (ingest into an empty index pays for the shapes its data brings, by
   design: no /generate ever does) — the Pallas path present in the
   compiled programs, the block pool drained.

Any failed check raises: the exit code is non-zero and no result line is
printed. Every earlier stdout line is information (JSON, one per event);
none of its numbers is a benchmark. The last line is the result.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import logging
import os
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
T_START = time.monotonic()
NEW_TOKENS = 150  # the reference budget (rag.py:172; deploy.yaml)
AUDIT_TOL = 0.15  # SloConfig.quality_logit_err — the auditor's pinned bound
ATTN_IMPL = "pallas"  # explicit, never "auto": no backend sniffing on this path
# Depth served. A cold one-chip run at full depth takes ~11 of the 20 minutes
# allowed; if that ever stops fitting, cut LAYERS here — never a width — and
# the "params" line prints the depth served.
LAYERS = 32
CUT_LAYERS = 8  # --chips 4: depth at which the same params also fit one chip
GIB = float(1 << 30)

QUERIES = (
    "What does the corpus say about retrieval latency and index throughput?",
    "Which practices should a delivery team adopt for container deployment?",
    "Summarize the guidance on observability and testing of data pipelines.",
    "How do compiler and kernel choices affect memory bandwidth in a cluster?",
    "What is the advice on security review during a platform migration?",
    "Describe how batch scheduling and request caching interact at runtime.",
)


def say(phase: str, **fields) -> None:
    """One information line on stdout."""
    fields = {"phase": phase, "t": round(time.monotonic() - T_START, 1), **fields}
    print(json.dumps(fields, default=str), flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# ---------------------------------------------------------------------------
# instruments: executables built, errors logged, HBM
# ---------------------------------------------------------------------------


class CompileCounter:
    """Counts every executable JAX builds (backend compile or persistent-
    cache retrieval alike) with the thread that asked for it, plus the
    persistent cache's hits and misses."""

    def __init__(self):
        import jax

        self.builds = []  # (thread name, seconds)
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.builds.append((threading.current_thread().name, seconds))

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def mark(self) -> int:
        return len(self.builds)

    def since(self, mark: int):
        return self.builds[mark:]


class ErrorLog(logging.Handler):
    """Collects every ERROR the package logs: the serving path catches a
    failed prefix-cache warm-up, WAL restore, post-ingest warm-up or audit
    and carries on — right for a server, a failure for a smoke."""

    def __init__(self):
        super().__init__(level=logging.ERROR)
        self.records = []

    def emit(self, record):
        self.records.append(self.format(record))


def hbm(device) -> dict:
    stats = device.memory_stats()
    check(stats, f"memory_stats() is empty on {device}")
    return stats


# ---------------------------------------------------------------------------
# phase 2: kernels against oracles, compiled by Mosaic, 8B head geometry
# ---------------------------------------------------------------------------

H, K, HD = 32, 8, 128  # Llama-3.1-8B attention heads
T_MAX = 4352  # EngineConfig.max_seq_len: the 4096 bucket + 256
ENC_S = 1536  # the encoder's snug bucket for reference-size chunks
KNN_ROWS = 131072  # a 128k-vector index snapshot


# the single-token decode kernels at the benchmark's four serving shapes:
# name -> (kind, rows, query heads, the rows' left pads)
SERVING_DECODE = {
    "decode_attention_q8[8,8,4,128]": ("q8", 8, 32, [903, 917, 951, 966, 978, 990, 1001, 940]),
    "decode_attention[4,2,4,128]": ("bf16", 4, 8, [1290, 1305, 1320, 1296]),  # Nemo's local heads at tp=4
    "mla_decode_attention[8,128,512]": ("latent", 8, 128, [402, 431, 470, 498, 512, 530, 547, 455]),
    "mla_decode_attention[8,64,512]": ("latent", 8, 64, [402, 431, 470, 498, 512, 530, 547, 455]),
}

# the held experts' combine at the three sparse-expert cells' prefill shapes
# (batch 8 of a 4096 bucket): name -> (tokens, a pass's rows, width,
# assignments a token that fall on the 16 held experts)
SERVING_COMBINE = {
    "expert_combine[32768,32768,7168]": (32768, 32768, 7168, 0.55),
    "expert_combine[32768,16384,6144]": (32768, 16384, 6144, 0.25),
    "expert_combine[32768,40960,3072]": (32768, 40960, 3072, 0.64),
}


# the router at the three sparse-expert cells' prefill shapes (batch 8 of a
# 4096 bucket): name -> (tokens, the router's outputs, route's rule)
SERVING_ROUTE = {
    "route[32768,256] top-8 of 4 of 8 groups": (32768, 256, dict(
        top_k=8, n_group=8, topk_group=4, scaling=2.5, normalize=True, scoring="sigmoid")),
    "route[32768,768] top-12, softmax": (32768, 768, dict(
        top_k=12, n_group=1, topk_group=1, scaling=6.0, normalize=False, scoring="softmax")),
    "route[32768,256] top-10": (32768, 256, dict(
        top_k=10, n_group=1, topk_group=1, scaling=2.5, normalize=True, scoring="sigmoid")),
}


def _block_tables(kv_len, bs: int, mb: int):
    """Distinct physical blocks (0 stays the null block) for each row's
    logical blocks, allocated interleaved so neighbours are not adjacent."""
    import numpy as np

    need = [-(-int(n) // bs) for n in kv_len]
    tables = np.zeros((len(kv_len), mb), np.int32)
    phys = 1
    for j in range(max(need)):
        for b, n in enumerate(need):
            if j < n:
                tables[b, j] = phys
                phys += 1
    return tables, phys


def kernel_cases(seed: int):
    """Yields ``(name, run, rtol, atol)``; ``run()`` returns ``(got, want)``
    arrays (or a list of such pairs). Tolerances are the ones the
    interpret-mode tests in tests/ pin for the same kernel/oracle pair."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from rag_llm_k8s_tpu.core.config import LlamaConfig
    from rag_llm_k8s_tpu.models.llama import rope_frequencies
    from rag_llm_k8s_tpu.ops import attention as A
    from rag_llm_k8s_tpu.ops.knn import knn_topk_pallas, knn_topk_xla

    root = jax.random.PRNGKey(seed)

    def normal(i, shape):
        return jax.random.normal(jax.random.fold_in(root, i), shape, jnp.float32)

    i32 = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731
    L = 2

    # -- paged family (the first time it meets Mosaic) ---------------------
    def paged(q8: bool, chunk: int):
        bs = 32 if q8 else 16
        mb = T_MAX // bs
        T = T_MAX
        kv_len = np.array(
            [T, 37, T // 4 + 1, T // 2 + 1, T - 252, bs, 513, T - 1000 % T], np.int32)
        if chunk:  # every row holds at least the chunk being prefilled
            kv_len = np.maximum(kv_len, chunk + np.arange(8) * 3).astype(np.int32)
        B = len(kv_len)
        tables, n_blocks = _block_tables(kv_len, bs, mb)
        ka = normal(1, (L, n_blocks, K, bs, HD))
        va = normal(2, (L, n_blocks, K, bs, HD))
        S = chunk or 1
        q = normal(3, (B, S, H, HD))
        arena = (ka, va)
        if q8:
            (kq, ksc), (vq, vsc) = A.quantize_kv(ka), A.quantize_kv(va)
            arena = (kq, vq, ksc, vsc)
        pairs = []
        for lay in range(L):
            common = (i32(tables), i32(kv_len), i32(lay))
            if chunk:
                fn, ref = (
                    (A.paged_chunk_attention_q8, A.paged_chunk_attention_xla_q8)
                    if q8 else (A.paged_chunk_attention, A.paged_chunk_attention_xla)
                )
                # the chunk's queries are the last S slots of each row
                args = (q, *arena, *common, i32(kv_len - S))
                pairs.append((fn(*args), ref(*args)))
            else:
                fn, ref = (
                    (A.paged_decode_attention_q8, A.paged_decode_attention_xla_q8)
                    if q8 else (A.paged_decode_attention, A.paged_decode_attention_xla)
                )
                args = (q, *arena, *common)
                pairs.append((fn(*args), ref(*args)))
        return pairs

    yield "paged_decode_attention", lambda: paged(False, 0), 0.0, 1e-5
    yield "paged_decode_attention_q8", lambda: paged(True, 0), 0.0, 1e-4
    yield "paged_chunk_attention", lambda: paged(False, 256), 0.0, 1e-5
    yield "paged_chunk_attention_q8", lambda: paged(True, 256), 0.0, 1e-4

    # -- dense family -------------------------------------------------------
    def flash(S, heads, kv_heads, hd, B, causal):
        """Rows behind a left pad next to unpadded ones: ``kv_start`` 950 of
        4096 is the benchmark's prompt, so a row's blocks are edge blocks
        (the one that straddles the pad, the diagonal), interior ones taken
        two at a time, and query blocks wholly in the pad."""
        q = normal(10, (B, S, heads, hd))
        k = normal(11, (B, S, kv_heads, hd))
        v = normal(12, (B, S, kv_heads, hd))
        pos = jnp.arange(S)[None, :]
        if causal:  # the decoder's left-padded prefill
            kv_start = i32([0, 950, 402, 1300][:B])
            kw = dict(kv_start=kv_start, causal=True)
            valid = pos >= kv_start[:, None]
        else:  # the encoder's right-padded bidirectional pass; two rows padded on both sides
            kv_len = i32(np.linspace(S // 3, S, B))
            kv_start = i32([0] * (B - 2) + [402, 950])
            kw = dict(kv_start=kv_start, kv_len=kv_len, causal=False)
            valid = pos < kv_len[:, None]
        m = valid[:, :, None, None]
        got = A.flash_attention(q, k, v, **kw)
        want = A.attention_xla(q, k, v, **kw)
        return jnp.where(m, got, 0), jnp.where(m, want, 0)

    yield "flash_attention[prefill]", lambda: flash(T_MAX - 256, H, K, HD, 2, True), 2e-4, 2e-5
    yield "flash_attention[encoder hd=64]", lambda: flash(ENC_S, 16, 16, 64, 8, False), 2e-4, 2e-5

    def dense_cache(B):
        kc = normal(20, (L, B, K, T_MAX, HD))
        vc = normal(21, (L, B, K, T_MAX, HD))
        return kc, vc

    def decode(q8: bool):
        B = 8
        kc, vc = dense_cache(B)
        q = normal(22, (B, 1, H, HD))
        T = T_MAX
        kv_start = i32([0, 17, 300, 0, T - 352, 1, T // 2, 512])
        kv_len = i32([T, 400, 301, 128, T - 52, T - 256, T // 2 + 1, 513])
        cache, fn, ref = (kc, vc), A.decode_attention, A.decode_attention_xla
        if q8:
            (kq, ksc), (vq, vsc) = A.quantize_kv(kc), A.quantize_kv(vc)
            cache = (kq, vq, ksc, vsc)
            fn, ref = A.decode_attention_q8, A.decode_attention_xla_q8
        pairs = []
        for lay in range(L):
            args = (q, *cache, kv_start, kv_len, i32(lay))
            pairs.append((fn(*args), ref(*args)))
        return pairs

    yield "decode_attention", lambda: decode(False), 2e-4, 2e-5
    yield "decode_attention_q8", lambda: decode(True), 0.0, 0.03

    def serving_decode(kind, B, heads, starts):
        """A single-token decode kernel at one of the benchmark's four
        serving shapes, in the dtypes served (bf16; int8 KV): a step of the
        middle of an answer (75 of 150 tokens behind the 4096 bucket) with
        the cell's left pads, so every row's walk starts inside the cache
        and its last step is fetched from ``T - step``."""
        from rag_llm_k8s_tpu.ops import mla as M

        bf = lambda i, shape: normal(i, shape).astype(jnp.bfloat16)  # noqa: E731
        kv_start = i32([s * T_MAX // 4352 for s in starts])  # (a rehearsal shrinks T_MAX)
        kv_len, lay = i32([T_MAX - 256 + 75] * B), i32(1)
        if kind == "latent":
            C, R = 512, 64
            args = (bf(50, (B, 1, heads, C)), bf(51, (B, 1, heads, R)), bf(52, (L, B, T_MAX, C)),
                    bf(53, (L, B, T_MAX, R)), kv_start, kv_len, lay)
            return (M.mla_decode_attention(*args, scale=0.05),
                    M.latent_attention_xla(*args, kv_len[0] - 1, scale=0.05))
        kv_heads = heads // 4
        q = bf(54, (B, 1, heads, HD))
        kc, vc = normal(55, (L, B, kv_heads, T_MAX, HD)), normal(56, (L, B, kv_heads, T_MAX, HD))
        if kind == "q8":
            (kq, ksc), (vq, vsc) = A.quantize_kv(kc), A.quantize_kv(vc)
            args = (q, kq, vq, ksc, vsc, kv_start, kv_len, lay)
            return A.decode_attention_q8(*args), A.decode_attention_xla_q8(*args)
        args = (q, kc.astype(jnp.bfloat16), vc.astype(jnp.bfloat16), kv_start, kv_len, lay)
        return A.decode_attention(*args), A.decode_attention_xla(*args)

    # [rows, KV heads, group, head] / [rows, heads, rank] as the trace names them;
    # bf16 outputs and probabilities against the oracle's: an output's last place, and 2e-3
    for name, case in SERVING_DECODE.items():
        yield name, functools.partial(serving_decode, *case), 1e-2, 2e-3

    def chunk(q8: bool):
        B, S = 2, 512
        kc, vc = dense_cache(B)
        q = normal(23, (B, S, H, HD))
        wi = T_MAX - S
        tail = (i32([0, T_MAX // 6]), i32([T_MAX, T_MAX]), i32(1), i32(wi))
        if q8:
            (kq, ksc), (vq, vsc) = A.quantize_kv(kc), A.quantize_kv(vc)
            args = (q, kq, vq, ksc, vsc, *tail)
            return A.chunk_prefill_attention_q8(*args), A.chunk_attention_xla_q8(*args)
        args = (q, kc, vc, *tail)
        return A.chunk_prefill_attention(*args), A.chunk_attention_xla(*args)

    yield "chunk_prefill_attention", lambda: chunk(False), 2e-4, 2e-5
    yield "chunk_prefill_attention_q8", lambda: chunk(True), 0.0, 0.03

    def grouped(q8: bool):
        """A speculative verify step of one row (spec_tokens + 1 = 16
        positions, G*S = 64 query rows a KV head) against the oracle AND the
        per-head kernel it stands in for: behind a left pad mid-generation,
        from slot 0 across a block edge, and on the cache's last slots."""
        B, S = 1, 16
        kc, vc = dense_cache(B)
        q = normal(24, (B, S, H, HD))
        cache = (kc, vc)
        fn, per_head, ref = (
            A.chunk_attention_grouped, A.chunk_prefill_attention, A.chunk_attention_xla)
        if q8:
            (kq, ksc), (vq, vsc) = A.quantize_kv(kc), A.quantize_kv(vc)
            cache = (kq, vq, ksc, vsc)
            fn, per_head, ref = (
                A.chunk_attention_grouped_q8, A.chunk_prefill_attention_q8,
                A.chunk_attention_xla_q8)
        pairs = []
        for kv_start, wi in ((900, T_MAX - 215), (0, 250), (T_MAX // 2, T_MAX - S)):
            args = (q, *cache, i32([kv_start]), i32([wi + S]), i32(1), i32(wi))
            got = fn(*args)
            pairs += [(got, ref(*args)), (got, per_head(*args))]
        return pairs

    yield "chunk_attention_grouped", lambda: grouped(False), 2e-4, 2e-5
    yield "chunk_attention_grouped_q8", lambda: grouped(True), 0.0, 0.03

    # -- RoPE re-rotation of cached K ----------------------------------------
    inv = np.asarray(rope_frequencies(LlamaConfig.llama_3_1_8b()), np.float64)

    def rope_host(x, pos):
        """Rotate-by-halves RoPE at ``pos`` on the host, in float64 — an
        oracle that shares no code with the device."""
        half = x.shape[-1] // 2
        phase = pos[None, :, None, None] * inv
        c, s = np.cos(phase), np.sin(phase)
        x1, x2 = x[..., :half], x[..., half:]
        return np.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)

    def rerotate():
        x = np.asarray(normal(30, (1, 8, K, HD)), np.float64)
        pos = np.arange(8, dtype=np.float64)
        k_at = jnp.asarray(rope_host(x, pos), jnp.float32)
        return [
            (A.rope_rerotate(k_at, jnp.int32(delta), jnp.asarray(inv, jnp.float32)),
             rope_host(x, pos + delta).astype(np.float32))
            for delta in (1, 7, -3)
        ]

    yield "rope_rerotate[delta!=0]", rerotate, 0.0, 1e-5

    def knn():
        Q, N, D, k = 8, KNN_ROWS, 1024, 5
        emb = normal(40, (N, D))
        emb = emb / jnp.linalg.norm(emb, axis=1, keepdims=True)
        queries = emb[:Q] + 0.01 * normal(41, (Q, D))
        norms = jnp.sum(emb * emb, axis=1)[None, :]
        v_got, i_got = knn_topk_pallas(queries, emb, norms, k=k)
        v_ref, i_ref = knn_topk_xla(queries, emb, norms, k=k)
        check(
            np.array_equal(np.asarray(i_got), np.asarray(i_ref)),
            "knn_topk_pallas ranks differ from knn_topk_xla",
        )
        return v_got, v_ref

    yield "knn_topk_pallas", knn, 1e-4, 1e-3


def phase_kernels(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from rag_llm_k8s_tpu.core.config import LlamaConfig
    from rag_llm_k8s_tpu.models.llama import rope_frequencies
    from rag_llm_k8s_tpu.ops import attention as A

    for name, run, rtol, atol in kernel_cases(seed):
        t0 = time.monotonic()
        # true-fp32 accumulation on both sides: at default precision the
        # MXU rounds inputs to bf16 and kernel and oracle differ by rounding
        # noise, not by bugs (tests_tpu/test_on_chip.py does the same)
        with jax.default_matmul_precision("highest"):
            pairs = run()
        if isinstance(pairs, tuple):
            pairs = [pairs]
        err = 0.0
        for got, want in pairs:
            got, want = np.asarray(got), np.asarray(want)
            check(np.isfinite(got).all(), f"{name}: non-finite kernel output")
            check(np.isfinite(want).all(), f"{name}: non-finite oracle output")
            err = max(err, float(np.max(np.abs(got - want))))
            np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=name)
        say("kernel", name=name, max_abs_err=err, rtol=rtol, atol=atol,
            seconds=round(time.monotonic() - t0, 2))

    # delta == 0 is the identity, bit for bit, in both layouts
    inv = rope_frequencies(LlamaConfig.llama_3_1_8b())
    k = jax.random.normal(jax.random.PRNGKey(seed), (2, 1, K, 64, HD), jnp.float32)
    out = A.rope_rerotate(k, jnp.int32(0), inv)
    check(np.array_equal(np.asarray(out), np.asarray(k)), "rope_rerotate delta=0 not exact")
    kq, ks = A.quantize_kv(k)
    rq, rs = A.rope_rerotate_q8(kq, ks, jnp.int32(7), inv)
    want = np.asarray(A.rope_rerotate(k, jnp.int32(7), inv))
    deq = np.asarray(rq.astype(jnp.float32) * rs[..., None])
    # two quantization round trips, each max|x|/254 per element
    bound = 2.0 * float(np.max(np.abs(want))) / 127.0 + 1e-6
    err = float(np.max(np.abs(deq - want)))
    check(err <= bound, f"rope_rerotate_q8 drift {err} over bound {bound}")
    say("kernel", name="rope_rerotate[delta=0] + rope_rerotate_q8",
        max_abs_err=err, bound=bound)


def best_us_a_call(many, calls: int, *args) -> float:
    """Microseconds a call of a program that chains ``calls`` of them: four
    runs, the first (it compiles) left out, the best of the other three."""
    times = []
    for _ in range(4):
        t0 = time.perf_counter()
        many(*args).block_until_ready()
        times.append(time.perf_counter() - t0)
    return round(min(times[1:]) / calls * 1e6, 1)


def phase_combine(seed: int, cases=None, interpret: bool = False) -> None:
    """``ops/moe.py expert_combine`` against the XLA scatter-add it replaced,
    on a pass as the experts leave it (rows run by run, an expert each, tokens
    rising inside a run, the tail behind the routed rows unwritten: NaN here):
    values, the kernel's own count of what it combined, and microseconds a
    call of both (eight calls chained in one program, the best of three)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from rag_llm_k8s_tpu.ops import moe

    def scatter(acc, y, weight, token, tail):
        # as the parent did: the tail's rows are zeros added to real tokens
        rows = jnp.where((token < acc.shape[0])[:, None], y.astype(jnp.float32) * weight[:, None], 0.0)
        return acc.at[jnp.where(token < acc.shape[0], token, tail)].add(rows.astype(acc.dtype))

    def us_a_call(fn, acc, *rest):
        many = jax.jit(lambda a, *r: jax.lax.fori_loop(0, 8, lambda i, x: fn(x, *r), a))
        return best_us_a_call(many, 8, acc, *rest)

    for name, (N, C, D, load) in (cases or SERVING_COMBINE).items():
        rng = np.random.default_rng(seed)
        runs = [np.flatnonzero(rng.random(N) < load / 16) for _ in range(16)]
        held = np.concatenate(runs)[:C]
        expert = np.repeat(np.arange(16), [len(r) for r in runs])[:C]
        token = jnp.asarray(np.concatenate([held, np.full(C - len(held), N)]), jnp.int32)
        group = jnp.asarray(np.concatenate([expert, np.full(C - len(held), 16)]), jnp.int32)
        tail = jnp.asarray(np.sort(rng.integers(0, N, C)), jnp.int32)
        key = jax.random.PRNGKey(seed)
        y = jnp.where((token < N)[:, None], jax.random.normal(key, (C, D), jnp.bfloat16), jnp.nan)
        weight = jax.random.uniform(jax.random.fold_in(key, 1), (C,), jnp.float32, 0.05, 0.5)
        acc = jax.random.normal(jax.random.fold_in(key, 2), (N, D), jnp.bfloat16)
        blocks = moe.combine_blocks(N, C, D, 2)
        check(blocks is not None, f"{name}: the rule sends a served prefill shape to the dense dot")
        combine = functools.partial(moe.expert_combine, groups=16, blocks=blocks, interpret=interpret)
        got, hot = combine(acc, y, weight, token, group)
        want = np.asarray(scatter(acc, y, weight, token, tail), np.float32)
        got = np.asarray(got, np.float32)
        check(np.isfinite(got).all(), f"{name}: non-finite kernel output")
        check(int(hot) == len(held), f"{name}: combined {int(hot)} of {len(held)} rows")
        # one rounding to bf16 against one after every row: a few of the sum's last places
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=6e-2, err_msg=name)
        say("kernel", name=name, blocks=blocks, rows_combined=int(hot),
            max_abs_err=float(np.max(np.abs(got - want))),
            us_a_call=us_a_call(lambda a, *r: combine(a, *r)[0], acc, y, weight, token, group),
            xla_scatter_add_us_a_call=us_a_call(scatter, acc, y, weight, token, tail))


def route_us_a_call(fn, logits, bias, calls: int = 8) -> float:
    """Microseconds a call of ``fn(logits, bias) -> (experts, weights)``,
    ``calls`` of them chained in one program, the best of three. Each call's
    logits hang on the last call's weights (by a branch never taken: no pass
    over them is added), so no call is hoisted out of the loop or dropped."""
    import jax
    import jax.numpy as jnp

    def chain(i, x):
        return jax.lax.cond(jnp.sum(fn(x, bias)[1]) > 1e30, lambda x: x + 1.0, lambda x: x, x)

    return best_us_a_call(jax.jit(lambda x: jax.lax.fori_loop(0, calls, chain, x)), calls, logits)


def phase_route(seed: int, cases=None, interpret: bool = False) -> None:
    """``ops/moe.py route`` under its kernel (``route_topk``) against its jnp
    body (``lax.top_k`` and a gather), on random logits with a block of rows
    all tied, a block of rounded logits (ties inside and across the cut) and,
    a second time, a correction bias that leaves two finite scores a group of
    32 (the ``-inf`` tail is chosen in the order of its index); and
    microseconds a call of both. The kernel computes the scores itself, so a
    score may differ from XLA's in its last place and with it a choice
    between two experts that near: a row may differ from the oracle's only
    where the oracle's own ``s + bias`` at the two choices agree to 1e-6."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from rag_llm_k8s_tpu.ops import moe

    def choice_at(logits, bias, experts, scoring):
        s = jax.nn.softmax(logits, axis=-1) if scoring == "softmax" else jax.nn.sigmoid(logits)
        return np.asarray(jnp.take_along_axis(s + bias[None, :], experts, axis=1))

    impl = "pallas_interpret" if interpret else "pallas"
    for name, (N, E, rule) in (cases or SERVING_ROUTE).items():
        key = jax.random.PRNGKey(seed)
        logits = 2.0 * jax.random.normal(key, (N, E), jnp.float32)
        logits = logits.at[:128].set(0.25).at[128:2 * 128].set(jnp.round(logits[128:2 * 128]))
        std = 1.0 / E if rule["scoring"] == "softmax" else 0.05  # the families' own draws
        bias = std * jax.random.normal(jax.random.fold_in(key, 1), (E,), jnp.float32)
        check(moe.route_blocks(N, E, rule["n_group"]) is not None,
              f"{name}: the rule sends a served prefill shape to the jnp body")
        kernel = jax.jit(functools.partial(moe.route, **rule, impl=impl))
        body = jax.jit(functools.partial(moe.route, **rule, impl="xla"))
        report = {}
        for what, b in (("served bias", bias),
                        ("two finite scores a group of 32", jnp.where(jnp.arange(E) % 32 >= 2, -jnp.inf, bias))):
            got, want = kernel(logits, b), body(logits, b)
            same = np.all(np.asarray(got[0]) == np.asarray(want[0]), axis=1)
            check(same.mean() >= 0.999, f"{name}, {what}: {int((~same).sum())} rows chose otherwise")
            if not same.all():  # only between choices the oracle itself cannot tell apart
                mine, its = (choice_at(logits, b, e[~same], rule["scoring"]) for e in (got[0], want[0]))
                near = np.isclose(mine, its, rtol=1e-6, atol=0.0) | (mine == its)
                check(near.all(), f"{name}, {what}: a row differs from the oracle's by more than a near tie")
            np.testing.assert_allclose(np.asarray(got[1])[same], np.asarray(want[1])[same], rtol=1e-5,
                                       err_msg=f"{name}, {what}")
            check(np.isfinite(np.asarray(got[1])).all(), f"{name}, {what}: non-finite weights")
            report[what] = {"rows_equal": int(same.sum()), "weights_bit_equal": bool(
                np.array_equal(np.asarray(got[1]), np.asarray(want[1])))}
        say("kernel", name=name, cols=moe.route_blocks(N, E, rule["n_group"]), **{
            k.replace(" ", "_"): v for k, v in report.items()},
            us_a_call=route_us_a_call(kernel, logits, bias),
            jnp_body_us_a_call=route_us_a_call(body, logits, bias))


def phase_live_window(seed: int, shape=(32, 20480, 128), window: int = 2048, chunk: int = 16,
                      block: int = 0, lengths=(17353, 20480), interpret: bool = False) -> None:
    """``ops/block_window.py window_summary_flash_attention`` told a row's
    live length (``models/block_window.py``: whole blocks of ``LIVE_BLOCK``
    positions) against the same kernel without one, at the served shape: the
    live blocks bit-equal (what is behind them is unwritten, and not looked
    at), and microseconds a call both ways."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from rag_llm_k8s_tpu.models.block_window import LIVE_BLOCK
    from rag_llm_k8s_tpu.ops import block_window as bw

    block = block or LIVE_BLOCK
    H, S, hd = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v = (jax.random.normal(key, shape, jnp.bfloat16) for key in keys[:3])
    mu, phi = (jax.random.normal(key, (H, hd), jnp.bfloat16) * 1.5 / hd ** 0.5 for key in keys[3:])
    sk, sv = bw.pool_chunks(k, v, mu, phi, chunk, "pallas_interpret" if interpret else "pallas")
    attend = functools.partial(bw.window_summary_flash_attention, window=window, chunk=chunk, interpret=interpret)
    whole = np.asarray(attend(q, k, v, sk, sv).astype(jnp.float32))
    many = jax.jit(lambda x, n: jax.lax.fori_loop(0, 8, lambda i, x: attend(x, k, v, sk, sv, n), x), static_argnums=1)
    whole_us = best_us_a_call(many, 8, q, None)
    for n in lengths:
        live = -(-n // block) * block
        got = np.asarray(attend(q, k, v, sk, sv, live).astype(jnp.float32))[:, :live]
        check(np.isfinite(got).all() and np.array_equal(got, whole[:, :live]),
              f"{n} live positions: the first {live} differ from the whole row's")
        say("kernel", name=f"window_summary_flash_attention[{n} of {S} positions]", live_positions=live,
            live_blocks_bit_equal=True, us_a_call=best_us_a_call(many, 8, q, live), whole_row_us_a_call=whole_us)


# ---------------------------------------------------------------------------
# inputs made from the seed
# ---------------------------------------------------------------------------


def load_tokenizers():
    """The repo's own tokenizers at the models' vocabulary scale (128k BPE,
    250k Unigram), trained here when absent — git does not carry them."""
    from rag_llm_k8s_tpu.native.build import load_library
    from rag_llm_k8s_tpu.tokenizer import load_tokenizer

    scale = os.path.join(REPO, "tests", "fixtures", "tokenizers_scale")
    bpe = os.path.join(scale, "bpe_128k.json")
    uni = os.path.join(scale, "unigram_250k.json")
    t0 = time.monotonic()
    built = not (os.path.exists(bpe) and os.path.exists(uni))
    if built:  # a child that never touches JAX (or the chip)
        subprocess.run(
            [sys.executable, os.path.join(REPO, "tests", "fixtures", "gen_tokenizers.py"),
             "--scale"],
            check=True, timeout=600, stdout=subprocess.DEVNULL,
        )
    llm_tok, enc_tok = load_tokenizer(bpe), load_tokenizer(uni)
    native = {name: load_library(name) is not None for name in ("bpe", "indexio")}
    say("tokenizers", trained_now=built, seconds=round(time.monotonic() - t0, 1),
        native_libraries=native)
    check(all(native.values()), f"native libraries fell back to Python: {native}")
    return llm_tok, enc_tok


def http(method: str, url: str, body=None, headers=None, timeout: float = 600.0):
    """One request over a real socket -> (status, parsed JSON or text)."""
    data = None
    headers = dict(headers or {})
    if body is not None and not isinstance(body, bytes):
        data = json.dumps(body).encode()
        headers["Content-Type"] = "application/json"
    elif body is not None:
        data = body
    req = urllib.request.Request(url, data=data, headers=headers, method=method)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            status, raw = r.status, r.read()
    except urllib.error.HTTPError as e:
        status, raw = e.code, e.read()
    try:
        return status, json.loads(raw)
    except ValueError:
        return status, raw.decode("utf-8", "replace")


def multipart_pdf(pdf: bytes, filename: str):
    boundary = "chipsmoke-7d1f3c"
    body = (
        f"--{boundary}\r\nContent-Disposition: form-data; name=\"file\"; "
        f"filename=\"{filename}\"\r\nContent-Type: application/pdf\r\n\r\n"
    ).encode() + pdf + f"\r\n--{boundary}--\r\n".encode()
    return body, {"Content-Type": f"multipart/form-data; boundary={boundary}"}


# ---------------------------------------------------------------------------
# phases 3-5: assemble, warm, serve over sockets, check
# ---------------------------------------------------------------------------


def serve_and_check(name, config, mesh, params, tokenizers, enc_params,
                    counter: CompileCounter, errors: ErrorLog, seed: int,
                    n_solo: int = 2, n_burst: int = 4):
    """One serving shape, start to finish. Returns the engine's fused
    params so the next shape shares the weight buffers."""
    import jax
    import numpy as np
    from werkzeug.serving import make_server

    from rag_llm_k8s_tpu.server.app import create_app
    from rag_llm_k8s_tpu.server.main import assemble_service
    from rag_llm_k8s_tpu.utils.synth import synth_pdf

    llm_tok, enc_tok = tokenizers
    device = mesh.mesh.devices.flat[0]
    n_err0 = len(errors.records)
    service = assemble_service(
        config, mesh, config.model, params, llm_tok, enc_params, enc_tok,
        # the encoder's spelling of the decoder's explicit backend
        encoder_attn_impl=config.engine.attn_impl.replace("pallas", "flash"),
    )
    engine = service.engine
    sched_engine = getattr(service.scheduler, "engine", engine)

    # §2: the explicit Pallas backends, not whatever "auto" would resolve to
    check(engine.model.attn_impl == ATTN_IMPL == "pallas",
          "decoder not on the Pallas backend")
    check(service.encoder.model.attn_impl == "flash", "encoder not on the flash backend")

    mark = counter.mark()
    hits0, miss0 = counter.cache_hits, counter.cache_misses
    t0 = time.monotonic()
    service.warmup()
    warm_s = time.monotonic() - t0
    built = counter.since(mark)
    mark_ready = counter.mark()  # every build from here on is after warmup()
    service.restore_from_wal()  # what server/main runs after warmup
    say(name, event="warmup", seconds=round(warm_s, 1), executables=len(built),
        compile_seconds=round(sum(s for _, s in built), 1),
        cache_hits=counter.cache_hits - hits0,
        cache_misses=counter.cache_misses - miss0,
        hbm_in_use_gib=round(hbm(device)["bytes_in_use"] / GIB, 2))
    check(service.ready, "service not ready after warmup()")

    srv = make_server("127.0.0.1", 0, create_app(service), threaded=True)
    base = f"http://127.0.0.1:{srv.server_port}"
    th = threading.Thread(target=srv.serve_forever, name="wsgi", daemon=True)
    th.start()
    try:
        status, body = http("GET", base + "/healthz")
        check(status == 200 and body["ready"], f"/healthz: {status} {body}")
        check(body["device_platform"] == "tpu", f"/healthz device: {body}")
        say(name, event="healthz", engine_mode=body["engine_mode"],
            device_platform=body["device_platform"])

        mark_ingest = counter.mark()
        t0 = time.monotonic()
        payload, headers = multipart_pdf(synth_pdf(seed), "corpus.pdf")
        status, body = http("POST", base + "/upload_pdf", payload, headers)
        check(status == 200, f"/upload_pdf: {status} {body}")
        status, info = http("GET", base + "/index_info")
        check(status == 200 and info.get("total_vectors", 0) >= 5,
              f"/index_info: {status} {str(info)[:200]}")
        mark_ingested = counter.mark()
        # FINDING, printed on every run: the first ingest into an empty
        # index builds the executables its data's shapes need (encoder at
        # the chunk batch, fused retrieve, prompt assembly) inside the
        # upload request, after warmup() returned — ingest pays for index
        # growth so that no /generate does. These are the only builds the
        # after-warmup check below lets through.
        by_upload = counter.builds[mark_ingest:mark_ingested]
        say(name, event="upload_pdf", seconds=round(time.monotonic() - t0, 1),
            message=body.get("message"), total_vectors=info["total_vectors"],
            dimension=info["dimension"],
            executables_built_inside_upload=len(by_upload),
            compile_seconds_inside_upload=round(sum(s for _, s in by_upload), 1))

        def generate(query, out):
            t = time.monotonic()
            out.append((*http("POST", base + "/generate", {"prompt": query}),
                        time.monotonic() - t))

        def tokens_served():
            return int(service.metrics.snapshot().get("query_decode_tokens", 0))

        responses = []
        tok0 = tokens_served()
        for q in QUERIES[:n_solo]:
            generate(q, responses)
        # the auditor skips a job that finds no headroom within ~2 s; let
        # the solo audits finish before the burst competes with them
        check(service.shadow.drain(timeout=600.0), "shadow audits did not finish")
        burst = [threading.Thread(target=generate, args=(q, responses))
                 for q in QUERIES[n_solo:n_solo + n_burst]]
        for t in burst:
            t.start()
        for t in burst:
            t.join(timeout=900)
            check(not t.is_alive(), "a /generate request did not return")
        n_req = n_solo + n_burst
        check(len(responses) == n_req, f"{len(responses)} of {n_req} responses")
        for status, body, seconds in responses:
            check(status == 200, f"/generate: {status} {body}")
            check(isinstance(body.get("generated_text"), str), f"/generate body: {body}")
            check(body.get("context"), "response carries no retrieved context")
        served = tokens_served() - tok0
        check(served == n_req * NEW_TOKENS,
              f"{served} tokens served, expected {n_req * NEW_TOKENS}")
        say(name, event="generate", requests=n_req, tokens=served,
            latencies_s=[round(s, 2) for _, _, s in responses],
            timings_ms=[r[1]["timings"] for r in responses[:1]])

        status, metrics = http("GET", base + "/metrics")
        check(status == 200 and "rag_request_duration_seconds_bucket" in metrics,
              "/metrics exposition incomplete")
    finally:
        srv.shutdown()
        srv.server_close()
        th.join(timeout=30)

    # shadow auditor over everything served: the fast path said what the
    # exact path says, inside the pinned tolerance
    check(service.shadow.drain(timeout=600.0), "shadow audits did not finish")
    st = service.shadow.state()
    say(name, event="shadow", audits=st["audits"], skips=st["skips"],
        err_max=st["err_max"], tokens_compared=st["tokens_compared"],
        attribution=st["attribution"])
    judged = st["audits"]["clean"] + st["audits"]["diverged"]
    check(judged == n_req and not st["audits"]["failed"] and not st["skips"],
          f"not every served request was audited: {st['audits']} {st['skips']}")
    check(st["err_max"] <= AUDIT_TOL,
          f"shadow audit err_max {st['err_max']} over tolerance {AUDIT_TOL}")

    # nothing was built after warmup() returned — on the request threads,
    # the scheduler's or the auditor's — but what the upload built
    late = counter.builds[mark_ready:mark_ingest] + counter.since(mark_ingested)
    say(name, event="built_after_warmup", outside_upload=late,
        inside_upload=len(by_upload))
    check(not late, f"executables were built after warmup() returned: {late}")

    # finite logits, read straight from the exact scorer
    ids = [config.model.bos_token_id] + llm_tok.encode(QUERIES[0])
    out = engine.generate([ids], max_new_tokens=8)[0]
    score = engine.score_exact(ids, out)
    check(np.isfinite(score["max_logit"]).all() and np.isfinite(score["chosen_logit"]).all(),
          "non-finite logits")

    # the Pallas path was taken, not a reference: kernels in the programs
    S = max(config.engine.prompt_buckets)
    programs = {}
    if sched_engine is engine:
        key = (1, S, engine._clamp_max_new(S, NEW_TOKENS), "spec")
        programs["one-shot prefill-4096 + decode loop"] = engine._compiled[key]
        check(service.metrics.snapshot().get("query_single_fetch", 0) >= n_solo,
              "solo requests did not take the single-fetch path")
    else:
        programs["paged decode step"] = sched_engine._compiled[("step_paged", 1, 1)]
        programs["paged prefill-4096"] = sched_engine._compiled[("prefill_paged", S, 1)]
        programs["paged verify"] = sched_engine._compiled[
            ("verify_paged", sched_engine.spec_K, 1)]
        programs["mixed window"] = sched_engine._compiled[
            ("mixed_step", sched_engine.chunk_tokens, 1)]
    for what, compiled in programs.items():
        check("tpu_custom_call" in compiled.as_text(), f"{what}: no Pallas kernel in it")
    emb, _ = service.store.device_snapshot()
    tokens, _ = service.encoder.prepare_batch(enc_tok.encode(QUERIES[0]))
    k_eff = min(config.retrieval.k, service.store.ntotal)
    text = service._fused_retrieve[(tokens.shape[1], emb.shape[0], k_eff, 1)].as_text()
    check(text.count("tpu_custom_call") >= 2,
          "fused embed+kNN: encoder flash and kNN kernels not both present")
    pallas_in = sorted(programs) + ["fused embed+kNN"]

    extra = {}
    if sched_engine is not engine:
        stats, pool = sched_engine.stats, sched_engine.kv_pool
        extra = dict(
            spec_verify_steps=stats.spec_verify_steps,
            spec_drafted=stats.spec_drafted_tokens,
            spec_accepted=stats.spec_accepted_tokens,
            mixed_windows=sched_engine.ledger.state()["kinds"].get("mixed", {}),
            pool_blocks=pool.num_blocks, blocks_in_use=pool.blocks_in_use(),
        )
        check(stats.spec_verify_steps > 0, "the paged verify step never ran")
        check(extra["mixed_windows"].get("busy_s", 0) > 0, "no mixed window ran")
        check(pool.blocks_in_use() == 0, f"pool not drained: {pool.stats()}")
    else:
        extra = dict(spec_verify_steps=engine.stats.spec_verify_steps,
                     spec_emitted=engine.stats.spec_emitted_tokens)
    new_errors = errors.records[n_err0:]
    say(name, event="checks", pallas_in=pallas_in, errors_logged=len(new_errors),
        peak_hbm_gib=round(hbm(device)["peak_bytes_in_use"] / GIB, 2), **extra)
    check(not new_errors, "the package logged errors:\n" + "\n".join(new_errors))

    fused_params = engine.params
    service.shutdown()
    return fused_params


def pool_blocks_from_free_hbm(device, model_cfg, block_size: int, max_batch: int,
                              reserve_gib: float) -> int:
    """Size the paged arena from what is actually free: int8 payload + fp32
    scale planes per block, capped at dense parity (every slot full)."""
    stats = hbm(device)
    free = stats["bytes_limit"] - stats["bytes_in_use"]
    L, Kh, hd = model_cfg.num_layers, model_cfg.num_kv_heads, model_cfg.head_dim
    per_block = block_size * 2 * L * Kh * (hd + 4)
    row = T_MAX // block_size
    blocks = min(max_batch * row, int((free - reserve_gib * GIB) // per_block))
    say("paged", event="pool_sizing", free_gib=round(free / GIB, 2),
        reserve_gib=reserve_gib, block_mib=round(per_block / (1 << 20), 2),
        dense_parity_blocks=max_batch * row, kv_pool_blocks=blocks)
    check(blocks >= row, f"free HBM holds {blocks} blocks, one row needs {row}")
    return blocks


def app_config(work: str, shape: str, model, engine, tp: int):
    """The served configuration: greedy at the reference budget (the exact
    path has no reference for a sampled stream), every request audited,
    everything the service writes kept under ``work``."""
    from rag_llm_k8s_tpu.core.config import (
        AppConfig, FlightConfig, MeshConfig, SamplingConfig, ServerConfig,
        ShadowConfig,
    )

    return AppConfig(
        mesh=MeshConfig(dp=1, sp=1, tp=tp), model=model, engine=engine,
        sampling=SamplingConfig(do_sample=False, max_new_tokens=NEW_TOKENS),
        server=ServerConfig(
            host="127.0.0.1", model_path=work, embedder_path=work,
            index_path=os.path.join(work, f"index_{shape}"),
            pdf_dir=os.path.join(work, "pdfs"),
        ),
        flight=FlightConfig(spool_dir=os.path.join(work, "incidents")),
        shadow=ShadowConfig(sample_rate=1.0),
    )


def run_one_chip(args, counter, errors) -> None:
    import jax

    from rag_llm_k8s_tpu.core.config import (
        DTypePolicy, EncoderConfig, EngineConfig, LlamaConfig, MeshConfig,
    )
    from rag_llm_k8s_tpu.core.mesh import make_mesh
    from rag_llm_k8s_tpu.utils.synth import synth_encoder_params, synth_llama_params

    device = jax.devices()[0]
    say("kernels", event="start")
    phase_kernels(args.seed)
    phase_combine(args.seed)
    phase_route(args.seed)
    phase_live_window(args.seed)
    gc.collect()

    tokenizers = load_tokenizers()
    mesh = make_mesh(MeshConfig(dp=1, sp=1, tp=1), devices=[device])
    dtypes = DTypePolicy()
    model = dataclasses.replace(LlamaConfig.llama_3_1_8b(), num_layers=LAYERS)
    t0 = time.monotonic()
    params = synth_llama_params(
        model, dtypes, args.seed, quant="int8", mesh=mesh, recite_gain=5.0
    )
    enc_params = synth_encoder_params(EncoderConfig.bge_m3(), dtypes, args.seed + 1)
    jax.block_until_ready((params, enc_params))
    say("params", model="Llama-3.1-8B published widths", layers_served=model.num_layers,
        weights="int8", kv="int8", encoder="bge-m3 published widths", seed=args.seed,
        seconds=round(time.monotonic() - t0, 1),
        hbm_in_use_gib=round(hbm(device)["bytes_in_use"] / GIB, 2))

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
        # phase 3: the shape deploy/llm/deploy.yaml ships (coalesce,
        # single-fetch RAG, speculation auto), int8 for one chip
        one_chip = EngineConfig(weight_quant="int8", kv_quant="int8", attn_impl=ATTN_IMPL)
        fused = serve_and_check(
            "serve_default", app_config(work, "default", model, one_chip, 1), mesh, params,
            tokenizers, enc_params, counter, errors, args.seed,
        )
        del params
        gc.collect()

        # phase 4: paged continuous, same weight buffers. Reserve what the
        # compiler says the largest concurrent transients need (admission
        # prefill of a 4-group at 4096, the exact scorer, the fused
        # retrieve) — it counts one program at a time, the pool must not
        bs = 32  # the int8 arena's Mosaic tile
        blocks = pool_blocks_from_free_hbm(
            device, model, bs, one_chip.max_batch_size, reserve_gib=4.5)
        paged = dataclasses.replace(
            one_chip, batching="continuous", kv_paged=True, kv_block_size=bs,
            kv_pool_blocks=blocks, interleave_prefill=True, spec_paged=True,
        )
        serve_and_check(
            "serve_paged", app_config(work, "paged", model, paged, 1), mesh, fused,
            tokenizers, enc_params, counter, errors, args.seed,
        )


# ---------------------------------------------------------------------------
# --chips 4: the sharded path and what it is compared with
# ---------------------------------------------------------------------------


def run_four_chips(args, counter, errors) -> None:
    import jax
    import numpy as np

    from rag_llm_k8s_tpu.core.config import (
        DTypePolicy, EncoderConfig, EngineConfig, LlamaConfig, MeshConfig,
        SamplingConfig, ShadowConfig,
    )
    from rag_llm_k8s_tpu.core.mesh import make_mesh
    from rag_llm_k8s_tpu.engine.continuous import ContinuousEngine, ContinuousScheduler
    from rag_llm_k8s_tpu.engine.engine import InferenceEngine
    from rag_llm_k8s_tpu.obs.shadow import ShadowAuditor
    from rag_llm_k8s_tpu.utils.synth import synth_encoder_params, synth_llama_params

    devices = jax.devices()
    check(len(devices) == 4, f"--chips 4 needs four devices, JAX sees {len(devices)}")
    mesh4 = make_mesh(MeshConfig(dp=1, sp=1, tp=4))
    mesh1 = make_mesh(MeshConfig(dp=1, sp=1, tp=1), devices=[devices[0]])
    dtypes = DTypePolicy()
    full = LlamaConfig.llama_3_1_8b()
    cut = dataclasses.replace(full, num_layers=CUT_LAYERS)
    check(cut.num_kv_heads % 4 == 0 and cut.num_heads % 4 == 0,
          "head counts do not tile tp=4: attention would fall to the XLA path")
    sampling = SamplingConfig(do_sample=False, max_new_tokens=64)
    rs = np.random.RandomState(args.seed)
    prompts = [
        [full.bos_token_id] + [int(t) for t in rs.randint(3, full.vocab_size - 300, n)]
        for n in (23, 180, 97, 240)
    ]

    def in_use():
        return [hbm(d)["bytes_in_use"] for d in devices]

    def engines(params, mesh):
        ec = EngineConfig(
            attn_impl=ATTN_IMPL, prompt_buckets=(256,), max_batch_size=4,
            max_seq_len=512, speculative="off",
        )
        one = InferenceEngine(cut, params, sampling=sampling, engine_config=ec,
                              dtypes=dtypes, mesh=mesh)
        paged = ContinuousEngine(
            cut, one.params, sampling=sampling, dtypes=dtypes, mesh=mesh,
            engine_config=dataclasses.replace(
                ec, batching="continuous", kv_paged=True, kv_block_size=16),
        )
        return one, paged

    def serve(one, paged):
        streams = {"one-shot": one.generate(prompts)}
        sched = ContinuousScheduler(paged)
        out = [None] * len(prompts)

        def submit(i):
            out[i] = sched.submit(prompts[i])

        threads = [threading.Thread(target=submit, args=(i,)) for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
            check(not t.is_alive(), "a paged submit did not return")
        sched.shutdown()
        check(paged.kv_pool.blocks_in_use() == 0, f"pool not drained: {paged.kv_pool.stats()}")
        streams["paged"] = out
        for kind, ss in streams.items():
            check(all(len(s) == sampling.max_new_tokens for s in ss),
                  f"{kind}: wrong stream lengths {[len(s) for s in ss]}")
        return streams

    # ---- depth cut so the same params also fit device 0 alone ------------
    t0 = time.monotonic()
    params4 = synth_llama_params(cut, dtypes, args.seed, mesh=mesh4, recite_gain=5.0)
    jax.block_until_ready(params4)
    n_sharded = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(params4):
        if all(a is None for a in leaf.sharding.spec):
            continue  # norms: replicated
        n_sharded += 1
        shards = leaf.addressable_shards
        check(len({s.device for s in shards}) == 4, f"{path}: not on four devices")
        check(all(s.data.nbytes * 4 == leaf.nbytes for s in shards),
              f"{path}: shards are not 1/4 of the bytes each")
    one4, paged4 = engines(params4, mesh4)
    plane = paged4._cache[0]
    check(plane.addressable_shards[0].data.shape[2] == cut.num_kv_heads // 4,
          f"arena plane not head-sharded: {plane.addressable_shards[0].data.shape}")
    streams4 = serve(one4, paged4)
    per_device = in_use()
    say("tp4", event="served_cut", layers=cut.num_layers, sharded_leaves=n_sharded,
        seconds=round(time.monotonic() - t0, 1),
        bytes_in_use_gib=[round(b / GIB, 2) for b in per_device])
    check(max(per_device) <= 1.25 * min(per_device),
          f"HBM piled on one device: {per_device}")
    text = paged4._compiled[("step_paged", 1, 1)].as_text()
    check("all-reduce" in text, "tp=4 decode step has no all-reduce")
    check("tpu_custom_call" in text, "tp=4 decode step has no Pallas kernel")
    check("tpu_custom_call" in next(iter(one4._compiled.values())).as_text(),
          "tp=4 one-shot program has no Pallas kernel")

    # the same numbers on device 0 alone
    params1 = jax.device_put(params4, mesh1.replicated)
    one1, paged1 = engines(params1, mesh1)
    del params1
    streams1 = serve(one1, paged1)
    auditor = ShadowAuditor(ShadowConfig(sample_rate=1.0, backlog=32),
                            score_fn=one1.score_exact)
    agree = {}
    for kind, streams in (("tp4 one-shot", streams4["one-shot"]),
                          ("tp4 paged", streams4["paged"]),
                          ("tp1 paged", streams1["paged"]),
                          ("tp1 one-shot", streams1["one-shot"])):
        agree[kind] = sum(a == b for a, b in zip(streams, streams1["one-shot"]))
        for prompt, stream in zip(prompts, streams):
            check(auditor.observe(stream, prompt_ids=prompt, force=True),
                  "audit not enqueued")
    check(auditor.drain(timeout=600.0), "audits did not finish")
    st = auditor.state()
    auditor.shutdown()
    say("tp4", event="parity_vs_tp1", identical_streams_of_4=agree,
        audits=st["audits"], err_max=st["err_max"])
    check(st["audits"]["clean"] + st["audits"]["diverged"] == 16
          and not st["audits"]["failed"], f"audits incomplete: {st['audits']}")
    check(st["err_max"] <= AUDIT_TOL, f"tp=4 vs tp=1 err_max {st['err_max']}")
    # engines built outside a RagService stay referenced by the process-wide
    # metrics registry's callback gauges, so dropping the names frees
    # nothing: release the cut-depth buffers themselves before 16 GB more
    # are born next to them
    for leaf in jax.tree.leaves(
        (params4, one1.params, paged4._cache, paged1._cache)
    ):
        leaf.delete()
    del one4, paged4, one1, paged1, params4, auditor
    gc.collect()

    # ---- full depth: 16 GB of bf16 weights exist only across chips -------
    tokenizers = load_tokenizers()
    t0 = time.monotonic()
    params = synth_llama_params(full, dtypes, args.seed, mesh=mesh4, recite_gain=5.0)
    jax.block_until_ready(params)
    per_device = in_use()
    say("tp4", event="params_full_depth", layers=full.num_layers,
        seconds=round(time.monotonic() - t0, 1),
        bytes_in_use_gib=[round(b / GIB, 2) for b in per_device])
    check(max(per_device) <= 1.05 * min(per_device),
          f"full-depth weights piled on one device: {per_device}")
    # the encoder and the index are single-device programs: they sit on
    # device 0 by design, on top of its quarter of the decoder
    enc_params = synth_encoder_params(EncoderConfig.bge_m3(), dtypes, args.seed + 1)
    jax.block_until_ready(enc_params)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
        # deploy.yaml's shape as it ships for a slice: bf16 weights and KV
        config = app_config(work, "tp4", full, EngineConfig(attn_impl=ATTN_IMPL), 4)
        serve_and_check("serve_tp4", config, mesh4, params, tokenizers, enc_params,
                        counter, errors, args.seed, n_solo=2, n_burst=0)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the tensor-parallel phase (builder-run)")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {devices[0].platform}); "
              "there is no CPU fallback", file=sys.stderr)
        sys.exit(2)

    from rag_llm_k8s_tpu.core.compile_cache import (
        CACHE_ENV, cache_entry_count, ensure_compile_cache,
    )

    cache_dir = ensure_compile_cache()
    entries0 = cache_entry_count(cache_dir)
    say("device", platform=devices[0].platform, kind=devices[0].device_kind,
        count=len(devices), jax=jax.__version__,
        compile_cache=cache_dir, placed_by_env=bool(os.environ.get(CACHE_ENV)),
        cache_entries_before=entries0, cache_warm=entries0 > 0)
    hbm(devices[0])

    counter = CompileCounter()
    errors = ErrorLog()
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr)
    logging.getLogger("rag_llm_k8s_tpu").addHandler(errors)

    if args.chips == 4:
        run_four_chips(args, counter, errors)
    else:
        run_one_chip(args, counter, errors)

    say("done", seconds=round(time.monotonic() - T_START, 1),
        executables_built=len(counter.builds),
        compile_seconds=round(sum(s for _, s in counter.builds), 1),
        cache_hits=counter.cache_hits, cache_misses=counter.cache_misses,
        cache_entries_before=entries0, cache_entries_after=cache_entry_count(cache_dir),
        peak_hbm_gib=[round(d.memory_stats()["peak_bytes_in_use"] / GIB, 2)
                      for d in devices])
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
