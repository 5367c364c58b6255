"""Single-fetch RAG serving: device-side prompt assembly (generate_rag).

The contract under test: the prompt assembled ON DEVICE from the packed
retrieve output + the store's chunk-token sidecar is token-identical to the
host's piecewise assembly (`RagService._piecewise_prompt`), so greedy
generation over either is identical; budget overflow drops trailing chunks
(token-truncating the first when it alone overflows) the same way on both
sides; and the serving path pays ONE device→host fetch per solo query.
"""

import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rag_llm_k8s_tpu.core.config import (
    AppConfig,
    DTypePolicy,
    EncoderConfig,
    EngineConfig,
    LlamaConfig,
    SamplingConfig,
)
from rag_llm_k8s_tpu.engine.batching import BatchScheduler
from rag_llm_k8s_tpu.engine.encoder import EncoderRunner
from rag_llm_k8s_tpu.engine.engine import InferenceEngine
from rag_llm_k8s_tpu.index.store import VectorStore
from rag_llm_k8s_tpu.models.bge_m3 import init_encoder_params
from rag_llm_k8s_tpu.models.llama import init_llama_params
from rag_llm_k8s_tpu.obs import tracing
from rag_llm_k8s_tpu.server.app import RagService

FP32 = DTypePolicy.fp32()


class ByteTokenizer:
    vocab_size = 300
    eos_id = None

    def encode(self, text):
        return [2 + (b % 250) for b in text.encode("utf-8")]

    def decode(self, ids, skip_special_tokens=True):
        return "".join(chr(97 + (int(i) % 26)) for i in ids)


def make_engine(speculative="off", buckets=(256,), max_new=8):
    cfg = LlamaConfig.tiny(vocab_size=300)
    params = init_llama_params(jax.random.PRNGKey(0), cfg, FP32)
    return cfg, InferenceEngine(
        cfg,
        params,
        sampling=SamplingConfig(do_sample=False, max_new_tokens=max_new),
        engine_config=EngineConfig(
            prompt_buckets=buckets, max_batch_size=4, speculative=speculative
        ),
        dtypes=FP32,
    )


def seg_ids(tok, md):
    return tok.encode(
        f"Document '{md.get('filename')}' (chunk {md.get('chunk_id')}): "
        f"{md.get('text')}\n\n"
    )


def make_store(tok, texts):
    store = VectorStore(dim=8)
    rng = np.random.default_rng(7)
    store.add(
        [rng.standard_normal(8).astype(np.float32) for _ in texts],
        [{"filename": "f.pdf", "chunk_id": i, "text": t} for i, t in enumerate(texts)],
    )
    store.attach_token_source(lambda md: seg_ids(tok, md))
    return store


def host_assemble(a, segs, b, S):
    """The budget rule both sides must implement."""
    avail = S - len(a) - len(b)
    ids = list(a)
    used = 0
    for j, s in enumerate(segs):
        if used + len(s) <= avail:
            ids.extend(s)
            used += len(s)
        else:
            if j == 0:
                ids.extend(s[:avail])
            break
    return ids + list(b)


def packed_for(idx_order, k):
    """A packed [1, 2k] retrieve output with chosen ranking."""
    d = np.linspace(0.1, 0.9, k, dtype=np.float32)
    row = np.concatenate([d, np.asarray(idx_order[:k], np.float32)])
    return jnp.asarray(row[None, :])


class TestGenerateRagMatchesHostAssembly:
    @pytest.mark.parametrize("speculative", ["off", "prompt_lookup"])
    def test_greedy_identical_to_host_ids(self, speculative):
        tok = ByteTokenizer()
        cfg, engine = make_engine(speculative=speculative)
        store = make_store(tok, ["alpha beta gamma", "delta epsilon", "zeta eta"])
        toks_dev, lens_dev = store.token_snapshot()
        a = [cfg.bos_token_id] + tok.encode("SYS\n\nContext: ")
        b = tok.encode("\n\nUser: what?\n\nChatbot:")
        packed = packed_for([2, 0, 1], k=3)
        segs = [seg_ids(tok, store._metadata[i]) for i in (2, 0, 1)]
        want_ids = host_assemble(a, segs, b, S=256)
        want = engine.generate([want_ids])[0]
        got = engine.generate_rag(
            np.asarray(a, np.int32), np.asarray(b, np.int32),
            packed, toks_dev, lens_dev, n_chunks=3,
        )
        assert got == want

    def test_budget_drops_trailing_chunks(self):
        tok = ByteTokenizer()
        cfg, engine = make_engine(buckets=(128,))
        texts = ["x " * 30, "y " * 30, "z " * 30]  # each ~60 tokens + header
        store = make_store(tok, texts)
        toks_dev, lens_dev = store.token_snapshot()
        a = [cfg.bos_token_id] + tok.encode("S: ")
        b = tok.encode("\n\nUser: q\n\nChatbot:")
        packed = packed_for([0, 1, 2], k=3)
        segs = [seg_ids(tok, store._metadata[i]) for i in (0, 1, 2)]
        want_ids = host_assemble(a, segs, b, S=128)
        # the budget really dropped something (or the test proves nothing)
        assert len(want_ids) < len(a) + sum(map(len, segs)) + len(b)
        want = engine.generate([want_ids])[0]
        got = engine.generate_rag(
            np.asarray(a, np.int32), np.asarray(b, np.int32),
            packed, toks_dev, lens_dev, n_chunks=3,
        )
        assert got == want

    def test_first_chunk_alone_overflowing_truncates(self):
        tok = ByteTokenizer()
        cfg, engine = make_engine(buckets=(64,))
        store = make_store(tok, ["w " * 100])  # segment >> bucket
        toks_dev, lens_dev = store.token_snapshot()
        a = [cfg.bos_token_id] + tok.encode("S: ")
        b = tok.encode("\n\nU: q\n\nChatbot:")
        packed = packed_for([0], k=1)
        seg = seg_ids(tok, store._metadata[0])
        want_ids = host_assemble(a, [seg], b, S=64)
        assert len(want_ids) == 64  # exactly full: truncation engaged
        want = engine.generate([want_ids])[0]
        got = engine.generate_rag(
            np.asarray(a, np.int32), np.asarray(b, np.int32),
            packed, toks_dev, lens_dev, n_chunks=1,
        )
        assert got == want


class TestRagCompileOnce:
    """``_get_rag_compiled`` after a cap-growing ingest: every thread that
    misses a key together used to compile the same executable inside its
    request and keep one. Builds are faked (a slow stand-in that counts):
    what is under test is the guard around the build, not the compiler."""

    KEY = dict(S=256, max_new=8, cap=64, Lc=32, LA=8, LB=16, n=3, kk=3, spec=False)

    def _racing(self, engine, n_threads):
        """n_threads ask for KEY at once; returns (results, errors)."""
        results, errors = [], []
        barrier = threading.Barrier(n_threads)

        def ask():
            barrier.wait(timeout=30)
            try:
                results.append(engine._get_rag_compiled(**self.KEY))
            except RuntimeError as e:
                errors.append(e)

        threads = [threading.Thread(target=ask) for _ in range(n_threads)]
        was = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        finally:
            sys.setswitchinterval(was)
        assert not any(t.is_alive() for t in threads)
        return results, errors

    class _Staged:
        """Stands for a jitted function and every stage after it: what
        ``tracing.build_span`` walks (``trace`` -> ``lower`` -> ``compile``)."""

        def __init__(self, executable):
            self.executable = executable

        def trace(self, *avals):
            return self

        def lower(self):
            return self

        def compile(self):
            return self.executable

    def test_concurrent_misses_build_each_key_once(self, monkeypatch):
        _, engine = make_engine(speculative="auto")  # two variants a key
        built = []

        def slow_build(S, max_new, cap, Lc, LA, LB, n, kk, v):
            built.append(v)
            time.sleep(0.05)  # long enough for every other thread to miss
            return self._Staged(("executable", v, len(built))), ()

        monkeypatch.setattr(engine, "_build_generate_rag", slow_build)
        counted = sum(n for (prog, _), n in tracing.compile_census()[1].items()
                      if prog == "generate_rag")
        results, errors = self._racing(engine, n_threads=16)
        assert not errors
        assert sorted(built) == [False, True]  # one build a variant, not 16
        assert sum(n for (prog, _), n in tracing.compile_census()[1].items()
                   if prog == "generate_rag") == counted + 2
        assert len(results) == 16 and len(set(results)) == 1 and results[0][1] is False
        # and a later hit builds nothing
        assert engine._get_rag_compiled(**self.KEY) == results[0] and len(built) == 2

    def test_failed_build_releases_the_key(self, monkeypatch):
        _, engine = make_engine()
        calls = []

        def flaky_build(S, max_new, cap, Lc, LA, LB, n, kk, v):
            calls.append(v)
            time.sleep(0.05)
            if len(calls) == 1:
                raise RuntimeError("compile failed")
            return self._Staged(("executable", len(calls))), ()

        monkeypatch.setattr(engine, "_build_generate_rag", flaky_build)
        results, errors = self._racing(engine, n_threads=8)
        # the builder's caller sees the failure; a waiter takes the key over,
        # builds it once, and everyone else is served that build
        assert len(errors) == 1 and len(calls) == 2
        assert len(results) == 7 and set(results) == {("executable", 2)}
        assert engine._get_rag_compiled(**self.KEY) == ("executable", 2) and len(calls) == 2


class TestFusedService:
    def _service(self, buckets=(256,), rag_fused=True):
        llama_cfg = LlamaConfig.tiny(vocab_size=300)
        enc_cfg = EncoderConfig.tiny(vocab_size=300)
        cfg = AppConfig(
            model=llama_cfg, encoder=enc_cfg, system_message="SYS"
        )
        engine = InferenceEngine(
            llama_cfg,
            init_llama_params(jax.random.PRNGKey(0), llama_cfg, FP32),
            sampling=SamplingConfig(do_sample=False, max_new_tokens=4),
            engine_config=EngineConfig(
                prompt_buckets=buckets, max_batch_size=4, rag_fused=rag_fused
            ),
            dtypes=FP32,
        )
        encoder = EncoderRunner(
            enc_cfg,
            init_encoder_params(jax.random.PRNGKey(1), enc_cfg, FP32),
            dtypes=FP32, length_buckets=(32,), max_batch=4,
        )
        store = VectorStore(dim=enc_cfg.hidden_size)
        scheduler = BatchScheduler(engine, max_wait_ms=25.0)
        svc = RagService(cfg, engine, ByteTokenizer(), encoder, ByteTokenizer(),
                         store, scheduler=scheduler)
        svc.ready = True
        texts = ["alpha beta gamma", "delta epsilon", "zeta eta theta"]
        vecs = encoder.encode([ByteTokenizer().encode(t) for t in texts])
        store.add(list(vecs), [
            {"filename": "f", "chunk_id": i, "text": t} for i, t in enumerate(texts)
        ])
        return svc

    def test_solo_takes_single_fetch_and_matches_host_path(self):
        svc = self._service()
        try:
            solo = svc.answer("alpha beta")
            assert svc.metrics.snapshot().get("query_single_fetch") == 1
            assert "context" in solo and solo["generated_text"]

            # the batched HOST path (what a burst runs): piecewise ids
            # through the ordinary engine — greedy, it must answer
            # identically to the device-assembled solo path
            results, _ = svc._retrieve("alpha beta")
            context, ids = svc._piecewise_prompt("alpha beta", results)
            out = svc.engine.generate([ids])[0]
            from rag_llm_k8s_tpu.rag.prompt import extract_answer

            host_text = extract_answer(svc.llm_tokenizer.decode(out))
            assert host_text == solo["generated_text"]
            assert context == solo["context"]

            # concurrent answers agree too (whichever path each took)
            got = {}

            def run(tag):
                got[tag] = svc.answer("alpha beta")["generated_text"]

            threads = [threading.Thread(target=run, args=(i,)) for i in range(2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert set(got.values()) == {solo["generated_text"]}
        finally:
            svc.shutdown()

    def test_sidecar_disabled_when_config_off(self):
        svc = self._service(rag_fused=False)
        try:
            out = svc.answer("alpha beta")
            assert out["generated_text"]
            assert "query_single_fetch" not in svc.metrics.snapshot()
        finally:
            svc.shutdown()

    def test_head_tail_overflow_falls_back_to_host_path(self):
        # bucket too small for head+tail+16: the device branch must decline
        # and the host path still answer
        svc = self._service(buckets=(32,))
        try:
            out = svc.answer("alpha beta gamma delta epsilon")
            assert out["generated_text"]
            assert "query_single_fetch" not in svc.metrics.snapshot()
        finally:
            svc.shutdown()

    def test_token_snapshot_splices_incrementally(self):
        """Adds after the first sidecar build must splice O(batch) (not a
        full rebuild) while the bucket holds, and force a full rebuild
        when the row bucket outgrows."""
        tok = ByteTokenizer()
        store = make_store(tok, [f"chunk {i} words" for i in range(3)])
        toks0, lens0 = store.token_snapshot()
        assert store.transfer_stats.get("tok_full_uploads") == 1
        rng = np.random.default_rng(3)
        # add within the 512-row bucket -> splice, same plane shape
        store.add(
            [rng.standard_normal(8).astype(np.float32)],
            [{"filename": "f.pdf", "chunk_id": 99, "text": "a new chunk"}],
        )
        toks1, lens1 = store.token_snapshot()
        assert store.transfer_stats.get("tok_row_splices") == 1
        assert toks1.shape == toks0.shape
        want = seg_ids(tok, {"filename": "f.pdf", "chunk_id": 99, "text": "a new chunk"})
        got = np.asarray(toks1[3][: int(lens1[3])]).tolist()
        assert got == want
        # rows 0-2 untouched by the splice
        np.testing.assert_array_equal(np.asarray(toks1[:3]), np.asarray(toks0[:3]))
        # a row longer than the Lc bucket -> full rebuild at a wider plane
        store.add(
            [rng.standard_normal(8).astype(np.float32)],
            [{"filename": "f.pdf", "chunk_id": 100, "text": "w " * 300}],
        )
        toks2, lens2 = store.token_snapshot()
        assert store.transfer_stats.get("tok_full_uploads") == 2
        assert toks2.shape[1] > toks0.shape[1]
        assert int(lens2[4]) > 128

    def test_near_capacity_splice_rebuilds_instead_of_clamping(self):
        """A padded splice block that would overrun the row bucket must fall
        back to a full rebuild: dynamic_update_slice CLAMPS an overflowing
        start index, which would silently shift the new rows onto earlier
        real rows (wrong chunk text in every later fused prompt)."""
        tok = ByteTokenizer()
        store = make_store(tok, [f"c{i}" for i in range(509)])
        toks0, lens0 = store.token_snapshot()
        cap = toks0.shape[0]
        assert cap == 512 and store.transfer_stats.get("tok_full_uploads") == 1
        rng = np.random.default_rng(5)
        # 3 adds: n = 512 <= cap, but the padded block (4 rows) at offset
        # 509 would overrun — must NOT splice
        store.add(
            [rng.standard_normal(8).astype(np.float32) for _ in range(3)],
            [
                {"filename": "f.pdf", "chunk_id": 600 + i, "text": f"new {i}"}
                for i in range(3)
            ],
        )
        toks1, lens1 = store.token_snapshot()
        assert store.transfer_stats.get("tok_row_splices") is None
        assert store.transfer_stats.get("tok_full_uploads") == 2
        for i in range(512):
            want = seg_ids(tok, store._metadata[i])
            got = np.asarray(toks1[i][: int(lens1[i])]).tolist()
            assert got == want, f"row {i} corrupted"

    def test_single_fetch_serves_over_tp2_mesh(self, devices8):
        """The production deployment pins TPU_RAG_MESH=tp=8 — the single-
        fetch path must serve over a mesh (replicated placement for the
        per-query inputs, a once-per-snapshot broadcast for the sidecar)
        and answer token-identically to the meshless fused service."""
        import dataclasses

        from rag_llm_k8s_tpu.core.config import MeshConfig
        from rag_llm_k8s_tpu.core.mesh import make_mesh
        from rag_llm_k8s_tpu.parallel.sharding import shard_llama_params

        llama_cfg = dataclasses.replace(
            LlamaConfig.tiny(vocab_size=300), num_kv_heads=2
        )
        enc_cfg = EncoderConfig.tiny(vocab_size=300)
        cfg = AppConfig(model=llama_cfg, encoder=enc_cfg, system_message="SYS")
        params = init_llama_params(jax.random.PRNGKey(0), llama_cfg, FP32)
        from rag_llm_k8s_tpu.models.bge_m3 import init_encoder_params as init_enc

        enc_params = init_enc(jax.random.PRNGKey(1), enc_cfg, FP32)
        texts = ["alpha beta gamma", "delta epsilon", "zeta eta theta"]

        def serve(mesh_ctx, eng_params):
            engine = InferenceEngine(
                llama_cfg, eng_params,
                sampling=SamplingConfig(do_sample=False, max_new_tokens=4),
                engine_config=EngineConfig(prompt_buckets=(256,), max_batch_size=2),
                dtypes=FP32, mesh=mesh_ctx,
            )
            encoder = EncoderRunner(
                enc_cfg, enc_params, dtypes=FP32, length_buckets=(32,), max_batch=4
            )
            store = VectorStore(dim=enc_cfg.hidden_size)
            svc = RagService(
                cfg, engine, ByteTokenizer(), encoder, ByteTokenizer(), store,
                scheduler=BatchScheduler(engine, max_wait_ms=20.0),
            )
            svc.ready = True
            vecs = encoder.encode([ByteTokenizer().encode(t) for t in texts])
            store.add(list(vecs), [
                {"filename": "f", "chunk_id": i, "text": t}
                for i, t in enumerate(texts)
            ])
            return svc

        ctx = make_mesh(MeshConfig(dp=1, sp=1, tp=2), devices=devices8[:2])
        svc_mesh = serve(ctx, shard_llama_params(params, ctx))
        svc_solo = serve(None, params)
        try:
            got = svc_mesh.answer("alpha beta")
            want = svc_solo.answer("alpha beta")
            assert svc_mesh.metrics.snapshot().get("query_single_fetch") == 1
            assert svc_solo.metrics.snapshot().get("query_single_fetch") == 1
            assert got["generated_text"] == want["generated_text"]
            assert got["context"] == want["context"]
            # second query reuses the cached replicated sidecar
            svc_mesh.answer("zeta eta")
            assert len(svc_mesh.engine._sidecar_placed) == 1
        finally:
            svc_mesh.shutdown()
            svc_solo.shutdown()

    def test_teardown_releases_engine_and_sidecar(self):
        """A long-lived store must not retain the dead service's engine (a
        bound-method token source did exactly that — the params graph
        stayed HBM-resident and OOMed the next model's build) nor keep the
        device sidecar pair alive past shutdown."""
        import gc
        import weakref

        svc = self._service()
        store = svc.store
        svc.answer("alpha beta")  # sidecar attached + device pair built
        assert store._tok_dev is not None
        svc.shutdown()
        ref = weakref.ref(svc.engine)
        del svc
        gc.collect()
        assert ref() is None, "engine retained after service teardown"
        assert store._tok_dev is None  # device pair released
        # host rows survive for the next service sharing the tokenizer
        assert any(r is not None for r in store._chunk_tokens)

    def test_token_snapshot_survives_save_load(self, tmp_path):
        tok = ByteTokenizer()
        store = make_store(tok, ["one two", "three four"])
        toks0, lens0 = store.token_snapshot()
        path = str(tmp_path / "idx")
        store.path = path
        store.save()
        loaded = VectorStore.load(path)
        loaded.attach_token_source(lambda md: seg_ids(tok, md))
        toks1, lens1 = loaded.token_snapshot()
        np.testing.assert_array_equal(np.asarray(lens0), np.asarray(lens1))
        np.testing.assert_array_equal(np.asarray(toks0), np.asarray(toks1))
