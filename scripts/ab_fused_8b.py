#!/usr/bin/env python
"""On-chip A/B: single-fetch fused /query vs the host-assembly path at the
8B int8+int8-KV behavioral point (bench.py::make_params_8b_behavioral).

Small-bucket (1024) probe for fast iteration — the full-bucket headline
comes from bench.py. Also sweeps spec_tokens / spec_ngram when --sweep.

Usage: python scripts/ab_fused_8b.py [--sweep] [--queries N]
Prints one JSON object.
"""

import argparse
import io
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402


def build_service(cfg_8b, params, dtypes, llm_tok, enc_tok, encoder, store,
                  rag_fused=True, spec="auto", spec_tokens=None,
                  spec_ngram=None, bucket=1024):
    """spec_tokens/spec_ngram default to None = the PRODUCTION EngineConfig
    defaults, so the headline A/B always measures what actually serves."""
    from rag_llm_k8s_tpu.core.config import (
        AppConfig, EngineConfig, SamplingConfig,
    )
    from rag_llm_k8s_tpu.engine.batching import BatchScheduler
    from rag_llm_k8s_tpu.engine.engine import InferenceEngine
    from rag_llm_k8s_tpu.server.app import RagService, create_app

    app_cfg = AppConfig(model=cfg_8b, encoder=encoder.config)
    spec_kw = {}
    if spec_tokens is not None:
        spec_kw["spec_tokens"] = spec_tokens
    if spec_ngram is not None:
        spec_kw["spec_ngram"] = spec_ngram
    engine = InferenceEngine(
        cfg_8b, params,
        sampling=SamplingConfig(),
        engine_config=EngineConfig(
            prompt_buckets=(bucket,), max_batch_size=4, weight_quant="int8",
            kv_quant="int8", speculative=spec, rag_fused=rag_fused, **spec_kw,
        ),
        dtypes=dtypes,
    )
    scheduler = BatchScheduler(engine, max_wait_ms=30.0)
    service = RagService(app_cfg, engine, llm_tok, encoder, enc_tok, store,
                         scheduler=scheduler)
    service.warmup()
    return service, create_app(service), engine


def run_leg(app, n):
    client = app.test_client()
    client.post("/query", json={"prompt": bench.QUERIES[0]})  # warm/compile
    lats = []
    for q in bench.QUERIES[:n]:
        t0 = time.monotonic()
        r = client.post("/query", json={"prompt": q})
        lats.append((time.monotonic() - t0) * 1e3)
        assert r.status_code == 200, r.get_data()
    lats.sort()
    return round(lats[len(lats) // 2], 1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--queries", type=int, default=6)
    ap.add_argument("--bucket", type=int, default=1024)
    args = ap.parse_args()

    import jax

    from rag_llm_k8s_tpu.core.config import (
        DTypePolicy, EncoderConfig, LlamaConfig,
    )
    from rag_llm_k8s_tpu.engine.encoder import EncoderRunner
    from rag_llm_k8s_tpu.index.store import VectorStore
    from rag_llm_k8s_tpu.models.bge_m3 import init_encoder_params
    import jax.numpy as jnp

    dtypes = DTypePolicy()
    enc_cfg = EncoderConfig.bge_m3()
    encoder = EncoderRunner(
        enc_cfg,
        jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            jax.eval_shape(lambda: init_encoder_params(jax.random.PRNGKey(1), enc_cfg, dtypes)),
        ),
        dtypes=dtypes, length_buckets=(128, 1536), max_batch=8,
    )
    llm_tok, enc_tok = bench._real_tokenizers()
    cfg_8b = LlamaConfig.llama_3_1_8b()
    params, alpha, top1 = bench.make_params_8b_behavioral(cfg_8b, dtypes, llm_tok)

    out = {"alpha": alpha, "top1": top1, "bucket": args.bucket,
           "device_fetch_ms": round(bench.measure_device_fetch_ms(), 1)}

    def fresh_store():
        s = VectorStore(dim=enc_cfg.embed_dim)
        return s

    def leg(tag, **kw):
        s = fresh_store()
        svc, app, engine = build_service(
            cfg_8b, params, dtypes, llm_tok, enc_tok, encoder, s, **kw
        )
        try:
            pdf = bench._synthetic_pdf(2500)
            r = app.test_client().post(
                "/upload_pdf", data={"file": (io.BytesIO(pdf), "c.pdf")},
                content_type="multipart/form-data",
            )
            assert r.status_code == 200, r.get_data()
            p50 = run_leg(app, args.queries)
            snap = svc.metrics.snapshot()
            v = engine.stats.spec_verify_steps
            out[tag] = {
                "p50_ms": p50,
                "single_fetch": snap.get("query_single_fetch", 0),
                "tokens_per_verify": round(
                    engine.stats.spec_emitted_tokens / v, 2) if v else None,
            }
            print(f"[{tag}] {out[tag]}", file=sys.stderr)
        finally:
            svc.shutdown()

    leg("fused", rag_fused=True)
    leg("host", rag_fused=False)
    if args.sweep:
        for k in (7, 11, 15, 19, 23, 31):
            leg(f"fused_k{k}", rag_fused=True, spec_tokens=k)
        leg("fused_n3", rag_fused=True, spec_ngram=3)
        leg("fused_nospec", rag_fused=True, spec="off")
    print(json.dumps(out))


if __name__ == "__main__":
    main()
