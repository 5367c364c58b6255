"""End-to-end HTTP integration tests: tiny models behind the real Flask app,
exercising every route (survey §4: 'HTTP-level integration tests with a tiny
stand-in model')."""

import io
import zlib

import jax
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
from rag_llm_k8s_tpu.engine.encoder import EncoderRunner
from rag_llm_k8s_tpu.engine.engine import InferenceEngine
from rag_llm_k8s_tpu.index.store import VectorStore
from rag_llm_k8s_tpu.models.bge_m3 import init_encoder_params
from rag_llm_k8s_tpu.models.llama import init_llama_params
from rag_llm_k8s_tpu.server.app import RagService, create_app

FP32 = DTypePolicy.fp32()


class ByteTokenizer:
    """Reversible byte-level stub tokenizer (ids = byte + 3)."""

    def encode(self, text):
        return [b + 3 for b in text.encode("utf-8")]

    def decode(self, ids, skip_special_tokens=True):
        return bytes((i - 3) % 256 for i in ids if i >= 3).decode("utf-8", "replace")


def make_pdf(text: str, compress: bool = False) -> bytes:
    """Minimal single-page PDF with a text content stream."""
    content = f"BT /F1 12 Tf ({text}) Tj ET".encode()
    filt = b""
    if compress:
        content = zlib.compress(content)
        filt = b" /Filter /FlateDecode"
    parts = [b"%PDF-1.4\n"]
    parts.append(b"1 0 obj << /Type /Catalog /Pages 2 0 R >> endobj\n")
    parts.append(b"2 0 obj << /Type /Pages /Kids [3 0 R] /Count 1 >> endobj\n")
    parts.append(
        b"3 0 obj << /Type /Page /Parent 2 0 R /Contents 4 0 R "
        b"/Resources << /Font << /F1 5 0 R >> >> >> endobj\n"
    )
    parts.append(
        b"4 0 obj << /Length %d%s >> stream\n%s\nendstream endobj\n"
        % (len(content), filt, content)
    )
    parts.append(b"5 0 obj << /Type /Font /Subtype /Type1 /BaseFont /Helvetica >> endobj\n")
    parts.append(b"%%EOF")
    return b"".join(parts)


@pytest.fixture(scope="module")
def client(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("srv")
    llama_cfg = LlamaConfig.tiny(vocab_size=300)
    enc_cfg = EncoderConfig.tiny(vocab_size=300)
    cfg = AppConfig(model=llama_cfg, encoder=enc_cfg)

    engine = InferenceEngine(
        llama_cfg,
        init_llama_params(jax.random.PRNGKey(0), llama_cfg, FP32),
        sampling=SamplingConfig(do_sample=False, max_new_tokens=8),
        engine_config=EngineConfig(prompt_buckets=(128, 256), max_batch_size=2),
        dtypes=FP32,
    )
    encoder = EncoderRunner(
        enc_cfg,
        init_encoder_params(jax.random.PRNGKey(1), enc_cfg, FP32),
        dtypes=FP32,
        length_buckets=(32, 64),
        max_batch=4,
    )
    store = VectorStore(dim=enc_cfg.hidden_size, path=str(tmp / "idx"))
    service = RagService(cfg, engine, ByteTokenizer(), encoder, ByteTokenizer(), store)
    service.ready = True
    app = create_app(service)
    return app.test_client()


class TestRoutes:
    def test_upload_pdf_and_index_info(self, client):
        pdf = make_pdf("TPU retrieval systems use interchip links for collectives")
        r = client.post(
            "/upload_pdf",
            data={"file": (io.BytesIO(pdf), "doc.pdf")},
            content_type="multipart/form-data",
        )
        assert r.status_code == 200, r.get_json()
        assert "chunks created" in r.get_json()["message"]

        info = client.get("/index_info").get_json()
        assert info["total_vectors"] >= 1
        assert info["dimension"] == 32
        assert info["sample_chunks"][0]["filename"] == "doc.pdf"

    def test_upload_rejections(self, client):
        r = client.post("/upload_pdf", data={}, content_type="multipart/form-data")
        assert r.status_code == 400
        assert r.get_json()["error"] == "No file part"
        r = client.post(
            "/upload_pdf",
            data={"file": (io.BytesIO(b"x"), "notes.txt")},
            content_type="multipart/form-data",
        )
        assert r.status_code == 400
        assert r.get_json()["error"] == "Invalid file format"

    def test_generate_and_query_alias(self, client):
        # ensure something is indexed
        pdf = make_pdf("flash attention kernels tile queries and keys", compress=True)
        client.post(
            "/upload_pdf",
            data={"file": (io.BytesIO(pdf), "doc2.pdf")},
            content_type="multipart/form-data",
        )
        for route in ("/generate", "/query"):
            r = client.post(route, json={"prompt": "what do kernels tile?"})
            assert r.status_code == 200, r.get_json()
            body = r.get_json()
            assert "generated_text" in body
            assert "context" in body
            assert "Document 'doc" in body["context"]
            assert "score:" in body["context"]
            # chip_ms / goodput_frac: the goodput ledger's per-request
            # attribution (ISSUE 14, additive; cost_usd only when priced);
            # the last six: the dispatch the request rode (ISSUE 51, additive)
            assert set(body["timings"]) == {
                "tokenize_ms", "embed_retrieve_ms", "generate_ms",
                "total_ms", "chip_ms", "goodput_frac",
                "dispatch_seq", "dispatch_rows", "queue_wait_ms",
                "launch_ms", "device_ms", "deliver_ms",
            }

    def test_healthz_and_metrics(self, client):
        assert client.get("/healthz").status_code == 200
        m = client.get("/metrics", headers={"Accept": "application/json"}).get_json()
        assert m["index_vectors"] >= 1
        assert m["engine_generate_calls"] >= 1

    def test_metrics_prometheus_exposition(self, client):
        # the default (no Accept) output must be scrapable text exposition
        r = client.get("/metrics")
        assert r.status_code == 200
        assert r.content_type.startswith("text/plain")
        text = r.get_data(as_text=True)
        lines = [l for l in text.splitlines() if l]
        assert any(l.startswith("# TYPE tpu_rag_") for l in lines)
        samples = {}
        for l in lines:
            if l.startswith("#"):
                continue
            name, val = l.rsplit(" ", 1)
            float(val)  # every sample parses as a number
            samples[name] = float(val)
        assert samples["tpu_rag_index_vectors"] >= 1
        assert samples["tpu_rag_engine_generate_calls"] >= 1

    def test_ingest_idempotent_via_http(self, client):
        pdf = make_pdf("deduplicated content should index once")
        for _ in range(2):
            r = client.post(
                "/upload_pdf",
                data={"file": (io.BytesIO(pdf), "dup.pdf")},
                content_type="multipart/form-data",
            )
            assert r.status_code == 200
        info = client.get("/index_info").get_json()
        dup_chunks = [c for c in info["sample_chunks"] if c["filename"] == "dup.pdf"]
        # store-level check: exactly one vector for the duplicated doc
        assert info["total_vectors"] == info["total_chunks"]

    def test_empty_index_message(self, tmp_path):
        llama_cfg = LlamaConfig.tiny(vocab_size=300)
        enc_cfg = EncoderConfig.tiny(vocab_size=300)
        cfg = AppConfig(model=llama_cfg, encoder=enc_cfg)
        engine = InferenceEngine(
            llama_cfg,
            init_llama_params(jax.random.PRNGKey(0), llama_cfg, FP32),
            sampling=SamplingConfig(do_sample=False, max_new_tokens=4),
            engine_config=EngineConfig(prompt_buckets=(128,)),
            dtypes=FP32,
        )
        encoder = EncoderRunner(
            enc_cfg,
            init_encoder_params(jax.random.PRNGKey(1), enc_cfg, FP32),
            dtypes=FP32,
            length_buckets=(32,),
        )
        store = VectorStore(dim=enc_cfg.hidden_size)
        service = RagService(cfg, engine, ByteTokenizer(), encoder, ByteTokenizer(), store)
        service.ready = False
        app = create_app(service)
        c = app.test_client()
        assert c.get("/healthz").status_code == 503  # not warmed yet
        body = c.post("/generate", json={"prompt": "anything"}).get_json()
        assert body["generated_text"] == "No relevant information found in the index."


class TestSetUpTrees:
    """Boot and ingest as span trees (``GET /debug/traces``) and as the
    ``rag_ingest_stage_seconds`` / ``rag_ready_seconds`` families."""

    STAGES = ["extract", "chunk", "embed", "index", "warm"]

    def test_upload_stage_spans_cover_the_request(self, client, monkeypatch):
        monkeypatch.setenv("TPU_RAG_FAULTS", "1")  # arms the /debug surface
        pdf = make_pdf("span trees of an ingest name extract chunk embed index and warm " * 6)
        r = client.post("/upload_pdf", data={"file": (io.BytesIO(pdf), "stages.pdf")},
                        content_type="multipart/form-data")
        assert r.status_code == 200, r.get_json()
        tree = client.get("/debug/traces?limit=1").get_json()["traces"][0]
        assert tree["attrs"]["kind"] == "upload"
        assert [s["name"] for s in tree["spans"]] == self.STAGES
        covered = sum(s["duration_ms"] for s in tree["spans"])
        assert covered == pytest.approx(tree["total_ms"], rel=0.05)
        # the post-ingest builds fall under ``warm`` (and the encoder's under ``embed``)
        warm = tree["spans"][-1]
        assert any(s["name"] == "build/retrieve" for s in warm.get("spans", []))
        # and the same stages in the scrape, one sample an upload so far
        text = client.get("/metrics").get_data(as_text=True)
        for stage in self.STAGES:
            count = [ln for ln in text.splitlines()
                     if ln.startswith(f'rag_ingest_stage_seconds_count{{stage="{stage}"}}')]
            assert count and float(count[0].rsplit(" ", 1)[1]) >= 1, stage

    def test_boot_tree_is_kept_and_served(self, client, monkeypatch):
        monkeypatch.setenv("TPU_RAG_FAULTS", "1")
        service = client.application.service
        if service.boot_trace is None:
            assert client.get("/debug/traces").get_json()["boot"] is None
            assert "rag_ready_seconds 0" in client.get("/metrics").get_data(as_text=True)
            service.warmup()
        for _ in range(3):  # requests roll the ring; the boot tree is not in it
            client.post("/generate", json={"prompt": "roll the ring"})
        body = client.get("/debug/traces?limit=1").get_json()
        assert len(body["traces"]) == 1 and body["traces"][0].get("attrs", {}).get("kind") != "boot"
        boot = body["boot"]
        assert boot["attrs"]["kind"] == "boot"
        assert [s["name"] for s in boot["spans"]][0] == "warm_generate"
        assert "warm_retrieve" in [s["name"] for s in boot["spans"]]
        ready = [ln for ln in client.get("/metrics").get_data(as_text=True).splitlines()
                 if ln.startswith("rag_ready_seconds ")]
        assert float(ready[0].split()[1]) > 0


class TestEmbedTruncation:
    def test_truncation_preserves_eos(self):
        """Over-limit encoder inputs keep their trailing EOS (the bge-m3 CLS
        pipeline expects </s>-terminated sequences; a bare [:limit] cut used
        to drop it)."""

        class EosTokenizer(ByteTokenizer):
            eos_id = 2

            def encode(self, text):
                return [1] + super().encode(text) + [2]

        class RecordingEncoder:
            def __init__(self):
                self.seen = None

            def encode(self, token_lists):
                self.seen = [list(t) for t in token_lists]
                return np.zeros((len(token_lists), 4), np.float32)

        cfg = AppConfig(model=LlamaConfig.tiny(), encoder=EncoderConfig.tiny())
        rec = RecordingEncoder()
        svc = RagService(cfg, None, ByteTokenizer(), rec, EosTokenizer(), None)
        limit = cfg.encoder.max_encode_len

        svc.embed_texts(["x" * (limit * 2), "short"])
        long_ids, short_ids = rec.seen
        assert len(long_ids) == limit
        assert long_ids[-1] == 2  # EOS survives truncation
        assert short_ids[-1] == 2 and short_ids[0] == 1  # untouched


class TestLongPromptRouting:
    def test_over_bucket_prompt_bypasses_scheduler_for_chunked_prefill(self):
        """A /generate prompt beyond the largest bucket must run through the
        chunk-capable one-shot engine, not the fixed-slot scheduler (which
        would loudly truncate it)."""
        llama_cfg = LlamaConfig.tiny(vocab_size=300)
        enc_cfg = EncoderConfig.tiny(vocab_size=300)
        cfg = AppConfig(model=llama_cfg, encoder=enc_cfg)
        engine = InferenceEngine(
            llama_cfg,
            init_llama_params(jax.random.PRNGKey(0), llama_cfg, FP32),
            sampling=SamplingConfig(do_sample=False, max_new_tokens=4),
            engine_config=EngineConfig(prompt_buckets=(128, 512), max_batch_size=2),
            dtypes=FP32,
        )

        class SlotEngineStub:
            # models a ContinuousEngine: fixed slot ladder, no chunking
            buckets = (128, 512)
            engine_config = engine.engine_config
            stats = engine.stats

        class RecordingScheduler:
            def __init__(self):
                self.engine = SlotEngineStub()
                self.submitted = []

            def submit(self, prompt, **kw):
                self.submitted.append(len(prompt))
                return engine.generate([prompt])[0]

        encoder = EncoderRunner(
            enc_cfg,
            init_encoder_params(jax.random.PRNGKey(1), enc_cfg, FP32),
            dtypes=FP32, length_buckets=(32,), max_batch=4,
        )
        store = VectorStore(dim=enc_cfg.hidden_size)
        svc = RagService(cfg, engine, ByteTokenizer(), encoder, ByteTokenizer(),
                         store, scheduler=RecordingScheduler())
        svc.ready = True
        # seed the index so answer() reaches generation; tiny chunk text
        # keeps the assembled prompt under the bucket for the short case
        vec = encoder.encode([ByteTokenizer().encode("tiny")])[0]
        store.add([vec], [{"filename": "f", "chunk_id": 0, "text": "ok"}])

        svc.answer("hi")  # short: assembled prompt fits -> scheduler path
        assert svc.scheduler.submitted, "short prompt should use the scheduler"

        before = list(svc.scheduler.submitted)
        svc.answer("x" * 1200)  # long: prompt exceeds bucket 512 -> engine path
        assert svc.scheduler.submitted == before  # scheduler NOT used
        assert any(k[3] == 512 for k in engine._compiled)  # chunked exe ran


class TestCoalescedRetrieval:
    """Under concurrency the embed+kNN stage batches into one fused device
    call (RagService.retrieve_coalescer) — results must match the solo path
    exactly, and concurrent /query must return the sequential answers."""

    def _make_service(self, with_scheduler: bool):
        from rag_llm_k8s_tpu.engine.batching import BatchScheduler

        llama_cfg = LlamaConfig.tiny(vocab_size=300)
        enc_cfg = EncoderConfig.tiny(vocab_size=300)
        cfg = AppConfig(model=llama_cfg, encoder=enc_cfg)
        engine = InferenceEngine(
            llama_cfg,
            init_llama_params(jax.random.PRNGKey(0), llama_cfg, FP32),
            sampling=SamplingConfig(do_sample=False, max_new_tokens=4),
            engine_config=EngineConfig(prompt_buckets=(128,), max_batch_size=4),
            dtypes=FP32,
        )
        encoder = EncoderRunner(
            enc_cfg,
            init_encoder_params(jax.random.PRNGKey(1), enc_cfg, FP32),
            dtypes=FP32, length_buckets=(32,), max_batch=4,
        )
        store = VectorStore(dim=enc_cfg.hidden_size)
        scheduler = BatchScheduler(engine, max_wait_ms=20.0) if with_scheduler else None
        svc = RagService(cfg, engine, ByteTokenizer(), encoder, ByteTokenizer(),
                         store, scheduler=scheduler)
        svc.ready = True
        texts = ["alpha beta gamma", "delta epsilon", "zeta eta theta iota"]
        vecs = encoder.encode([ByteTokenizer().encode(t) for t in texts])
        store.add(list(vecs), [
            {"filename": "f", "chunk_id": i, "text": t} for i, t in enumerate(texts)
        ])
        return svc

    def test_retrieve_many_matches_solo(self):
        svc = self._make_service(with_scheduler=False)
        queries = ["alpha", "epsilon delta", "theta", "gamma beta alpha"]
        solo = [svc._retrieve(q)[0] for q in queries]
        batched = [r for r, _ in svc._retrieve_many(queries)]
        assert len(batched) == len(solo)
        for s, b in zip(solo, batched):
            assert [r.metadata["chunk_id"] for r in s] == [r.metadata["chunk_id"] for r in b]
            np.testing.assert_allclose(
                [r.distance for r in s], [r.distance for r in b], rtol=1e-5, atol=1e-6
            )
        # the batch used ONE padded executable (B=cap), not one per query
        assert any(k[3] == svc._retrieve_cap for k in svc._fused_retrieve)

    def test_concurrent_queries_match_sequential(self):
        import threading

        svc = self._make_service(with_scheduler=True)
        assert svc.retrieve_coalescer is not None
        queries = ["alpha", "epsilon delta", "theta iota", "gamma"]
        try:
            want = {}
            for q in queries:
                # sequential answers through the full serving path
                want[q] = svc.answer(q)["generated_text"]
            got = {}
            errors = []

            def run(q):
                try:
                    got[q] = svc.answer(q)["generated_text"]
                except BaseException as e:  # noqa: BLE001
                    errors.append(e)

            threads = [threading.Thread(target=run, args=(q,)) for q in queries]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert not errors, errors
            assert got == want
        finally:
            svc.shutdown()

    def test_shutdown_is_idempotent(self):
        svc = self._make_service(with_scheduler=True)
        svc.shutdown()
        svc.shutdown()

    def test_inflight_hints_balance_and_skip_window(self):
        """The per-stage in-flight counters feed pending_hint: a solo query
        must not wait out the coalescing windows, and every path (success,
        empty index, engine failure) must release its claim."""
        import threading
        import time as _time

        svc = self._make_service(with_scheduler=True)
        try:
            # hints are wired to the live counters
            assert svc.retrieve_coalescer.pending_hint() == 0
            assert svc.scheduler.pending_hint() == 0
            # widen the windows: if a solo query waited them out it would be
            # glaring; the hint must end both waits immediately
            svc.retrieve_coalescer.max_wait_ms = 1500.0
            svc.scheduler.max_wait_ms = 1500.0
            svc.answer("warm")  # executables compiled outside the timed call
            t0 = _time.monotonic()
            out = svc.answer("alpha")
            assert (_time.monotonic() - t0) < 1.0
            assert out["generated_text"]
            assert svc._inflight_retrieve == 0 and svc._inflight_generate == 0

            # error path releases the claims too
            orig = svc.scheduler.submit
            svc.scheduler.submit = lambda *a, **kw: (_ for _ in ()).throw(
                RuntimeError("boom")
            )
            try:
                with pytest.raises(RuntimeError, match="boom"):
                    svc.answer("alpha")
            finally:
                svc.scheduler.submit = orig
            assert svc._inflight_retrieve == 0 and svc._inflight_generate == 0

            # concurrent burst: counters settle back to zero afterwards
            threads = [
                threading.Thread(target=svc.answer, args=(q,))
                for q in ["alpha", "gamma", "theta"]
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert svc._inflight_retrieve == 0 and svc._inflight_generate == 0
        finally:
            svc.shutdown()


class TestSpServing:
    """VERDICT r3 #8: serve a real HTTP /query on a dp=1,sp=2,tp=4 mesh —
    the long-prompt prefill must run as RING attention over the sp axis
    (models/llama.py _attend_ring), and the answer must match the meshless
    engine token-for-token."""

    def test_http_query_over_sp2_tp4_mesh(self, monkeypatch, devices8):
        import dataclasses

        from rag_llm_k8s_tpu.core.config import MeshConfig
        from rag_llm_k8s_tpu.core.mesh import make_mesh
        from rag_llm_k8s_tpu.parallel import ring_attention as ring_mod
        from rag_llm_k8s_tpu.parallel.sharding import shard_llama_params

        llama_cfg = dataclasses.replace(
            LlamaConfig.tiny(vocab_size=300), num_kv_heads=4  # K % tp == 0
        )
        enc_cfg = EncoderConfig.tiny(vocab_size=300)
        cfg = AppConfig(model=llama_cfg, encoder=enc_cfg)
        params = init_llama_params(jax.random.PRNGKey(0), llama_cfg, FP32)
        eng_cfg = EngineConfig(prompt_buckets=(512,), max_batch_size=1, max_seq_len=640)
        sampling = SamplingConfig(do_sample=False, max_new_tokens=6)

        ctx = make_mesh(MeshConfig(dp=1, sp=2, tp=4), devices=devices8)
        rings = []
        real_ring = ring_mod.ring_attention

        def spy_ring(*a, **kw):
            rings.append(kw.get("axis_name"))
            return real_ring(*a, **kw)

        monkeypatch.setattr(ring_mod, "ring_attention", spy_ring)
        engine = InferenceEngine(
            llama_cfg, shard_llama_params(params, ctx), sampling=sampling,
            engine_config=eng_cfg, dtypes=FP32, mesh=ctx,
        )
        encoder = EncoderRunner(
            enc_cfg, init_encoder_params(jax.random.PRNGKey(1), enc_cfg, FP32),
            dtypes=FP32, length_buckets=(32,), max_batch=4,
        )
        store = VectorStore(dim=enc_cfg.hidden_size)
        svc = RagService(cfg, engine, ByteTokenizer(), encoder, ByteTokenizer(), store)
        svc.ready = True
        texts = ["ring attention rotates key blocks over the ici links",
                 "sequence parallel prefill shards long prompts"]
        vecs = encoder.encode([ByteTokenizer().encode(t) for t in texts])
        store.add(list(vecs), [
            {"filename": "f", "chunk_id": i, "text": t} for i, t in enumerate(texts)
        ])
        client = create_app(svc).test_client()

        # long prompt: the assembled RAG prompt (system msg + context) lands
        # in the 512 bucket, so prefill runs S=512 >> sp
        r = client.post("/query", json={"prompt": "how do the key blocks move?"})
        assert r.status_code == 200, r.get_json()
        body = r.get_json()
        assert "generated_text" in body and "context" in body
        assert "sp" in rings, "prefill never went through ring attention"

        # token parity vs the meshless engine on the same assembled prompt
        solo = InferenceEngine(
            llama_cfg, params, sampling=sampling, engine_config=eng_cfg, dtypes=FP32
        )
        svc_solo = RagService(cfg, solo, ByteTokenizer(), encoder, ByteTokenizer(), store)
        svc_solo.ready = True
        want = svc_solo.answer("how do the key blocks move?")["generated_text"]
        assert body["generated_text"] == want


class TestGreedyDefaultSpeculates:
    """VERDICT r4 #8: greedy serving (TPU_RAG_DO_SAMPLE=0) gets speculation
    by DEFAULT (speculative="auto") — and the served /query tokens must be
    identical to a speculative-off server on the same weights."""

    def _serve(self, llama_cfg, enc_cfg, params, enc_params, speculative):
        import dataclasses

        cfg = AppConfig(model=llama_cfg, encoder=enc_cfg)
        # 512: the byte-tokenized RAG prompt is ~470 ids — it must land in
        # a single-shot bucket (chunked prefill correctly skips spec)
        ec = EngineConfig(prompt_buckets=(128, 512), max_batch_size=2, max_seq_len=640)
        if speculative is not None:
            ec = dataclasses.replace(ec, speculative=speculative)
        engine = InferenceEngine(
            llama_cfg, params,
            sampling=SamplingConfig(do_sample=False, max_new_tokens=8),
            engine_config=ec, dtypes=FP32,
        )
        encoder = EncoderRunner(
            enc_cfg, enc_params, dtypes=FP32, length_buckets=(32, 64), max_batch=4
        )
        store = VectorStore(dim=enc_cfg.hidden_size)
        service = RagService(cfg, engine, ByteTokenizer(), encoder, ByteTokenizer(), store)
        service.ready = True
        return engine, create_app(service).test_client()

    def test_default_engine_speculates_and_matches_off(self):
        llama_cfg = LlamaConfig.tiny(vocab_size=300)
        enc_cfg = EncoderConfig.tiny(vocab_size=300)
        params = init_llama_params(jax.random.PRNGKey(0), llama_cfg, FP32)
        enc_params = init_encoder_params(jax.random.PRNGKey(1), enc_cfg, FP32)
        assert EngineConfig().speculative == "auto"  # the default IS on

        pdf = make_pdf("speculation serves greedy queries by default now")
        answers = {}
        for mode in (None, "off"):  # None = the default config
            engine, c = self._serve(llama_cfg, enc_cfg, params, enc_params, mode)
            r = c.post(
                "/upload_pdf",
                data={"file": (io.BytesIO(pdf), "a.pdf")},
                content_type="multipart/form-data",
            )
            assert r.status_code == 200
            r = c.post("/query", json={"prompt": "what serves greedy queries"})
            assert r.status_code == 200, r.get_data()
            answers[mode] = r.get_json()["generated_text"]
            if mode is None:
                # the default really took the speculative executable
                assert engine.stats.spec_verify_steps >= 1
        assert answers[None] == answers["off"]
