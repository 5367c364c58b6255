"""Production entrypoint: assemble the full service from PVC-staged artifacts.

Boot sequence (parity with rag.py's __main__, rag.py:199-204, plus the fixes
from survey §5):

1. build the (dp, sp, tp) mesh over the slice's chips;
2. stream Llama-3.1 safetensors (the exact 10-file layout download_model.py
   stages) into TP-sharded device arrays;
3. load the bge-m3 encoder + both tokenizers;
4. open-or-create the index (idempotent), ingest ``/pdfs``;
5. AOT-warm the generate/embed executables, THEN mark ready (/healthz);
6. serve on :5001.

Run: ``python -m rag_llm_k8s_tpu.server.main``
"""

from __future__ import annotations

import logging
import os
import threading

if os.environ.get("TPU_RAG_JSON_LOGS", "").lower() in ("1", "true", "yes"):
    # trace-correlated structured logs: every record becomes one JSON
    # object carrying trace_id/span_id when emitted inside a traced
    # request (obs/logging.py) — the production default for fleet log
    # aggregation; the plain format remains for interactive runs
    from rag_llm_k8s_tpu.obs.logging import configure_json_logging

    configure_json_logging()
else:
    logging.basicConfig(level=os.environ.get("TPU_RAG_LOG_LEVEL", "INFO"))
logger = logging.getLogger(__name__)


def build_service():
    from rag_llm_k8s_tpu.core.config import AppConfig
    from rag_llm_k8s_tpu.core.mesh import make_mesh
    from rag_llm_k8s_tpu.models.loader import (
        config_from_hf_json,
        load_encoder_safetensors,
        load_safetensors_params,
    )
    from rag_llm_k8s_tpu.parallel.sharding import make_streaming_put
    from rag_llm_k8s_tpu.tokenizer import load_tokenizer

    config = AppConfig.from_env()
    mesh = make_mesh(config.mesh)
    logger.info("mesh: %s", mesh.mesh)

    model_dir = config.server.model_path
    model_cfg = config.model
    if os.path.exists(os.path.join(model_dir, "config.json")):
        model_cfg = config_from_hf_json(model_dir)
    from rag_llm_k8s_tpu.models import families

    refusal = families.of(model_cfg).checkpoint_loader_refusal
    if refusal:  # a family the safetensors loader has no name map for
        raise NotImplementedError(refusal)
    logger.info("loading Llama weights from %s", model_dir)

    # TPU_RAG_WEIGHT_QUANT=int8 streams the weight-only int8 layout straight
    # from the safetensors shards — bf16 kernels never exist on device, which
    # is what lets 8B serve on a single 16 GB chip (docs/8B.md)
    quant = config.engine.weight_quant

    def _convert():
        return load_safetensors_params(
            model_dir,
            model_cfg,
            config.dtypes,
            put=make_streaming_put(mesh, config.dtypes.param_dtype),
            quant=quant,
        )

    def _abstract():
        import jax

        from flax import traverse_util
        from jax.sharding import NamedSharding

        from rag_llm_k8s_tpu.models.llama import (
            init_llama_params,
            quantize_llama_params,
        )
        from rag_llm_k8s_tpu.parallel.sharding import llama_param_specs

        shapes = jax.eval_shape(
            lambda: init_llama_params(jax.random.PRNGKey(0), model_cfg, config.dtypes)
        )
        if quant == "int8":  # the cached checkpoint holds the int8 layout
            shapes = jax.eval_shape(quantize_llama_params, shapes)
        specs = traverse_util.flatten_dict(llama_param_specs(shapes, mesh))
        flat = {
            path: jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, sharding=NamedSharding(mesh.mesh, specs[path])
            )
            for path, leaf in traverse_util.flatten_dict(shapes).items()
        }
        return traverse_util.unflatten_dict(flat)

    from rag_llm_k8s_tpu.models.checkpoint import CACHE_SUBDIR, load_params_cached

    # the cache holds whichever layout was converted — key it by quant mode
    # so toggling TPU_RAG_WEIGHT_QUANT swaps caches instead of tripping a
    # structure-mismatch restore failure and a full reconversion
    cache_dir = os.path.join(
        model_dir, CACHE_SUBDIR if quant == "bf16" else f"{CACHE_SUBDIR}_{quant}"
    )
    params = load_params_cached(
        model_dir, _convert, abstract_params_fn=_abstract, cache_dir=cache_dir
    )
    llm_tokenizer = load_tokenizer(model_dir)

    logger.info("loading bge-m3 from %s", config.server.embedder_path)
    enc_params = load_encoder_safetensors(
        config.server.embedder_path, config.encoder, config.dtypes
    )
    enc_tokenizer = load_tokenizer(config.server.embedder_path)

    return assemble_service(
        config, mesh, model_cfg, params, llm_tokenizer, enc_params, enc_tokenizer
    )


def assemble_service(
    config, mesh, model_cfg, params, llm_tokenizer, enc_params, enc_tokenizer,
    encoder_attn_impl: str = "auto",
):
    """Everything ``build_service`` does AFTER parameter loading: engines,
    encoder runner, embedder fingerprint, index, scheduler choice,
    ``RagService``. Takes params and tokenizers from the caller so an entry
    point that makes its weights from a seed (``chip_smoke.py``) serves
    through the same assembly a deployment runs, not a copy of it. A
    deployment leaves the encoder's attention backend on ``"auto"`` (no
    config field governs it); ``chip_smoke.py`` names it explicitly."""
    import hashlib

    from rag_llm_k8s_tpu.engine.encoder import EncoderRunner
    from rag_llm_k8s_tpu.engine.engine import InferenceEngine
    from rag_llm_k8s_tpu.index.store import VectorStore
    from rag_llm_k8s_tpu.server.app import RagService

    engine = InferenceEngine(
        model_cfg,
        params,
        sampling=config.sampling,
        engine_config=config.engine,
        dtypes=config.dtypes,
        mesh=mesh,
    )
    encoder = EncoderRunner(
        config.encoder, enc_params, config.dtypes, mesh=mesh,
        eos_id=getattr(enc_tokenizer, "eos_id", None),
        attn_impl=encoder_attn_impl,
    )

    # fingerprint the embedder with a probe embedding so a persisted index
    # built by different encoder weights is detected and rebuilt
    probe = encoder.encode([enc_tokenizer.encode("__embedder_fingerprint__")])[0]
    fingerprint = hashlib.sha256(probe.tobytes()).hexdigest()[:16]
    store = VectorStore.open_or_create(
        config.server.index_path, dim=config.retrieval.embed_dim, fingerprint=fingerprint
    )

    if config.engine.batching == "continuous":
        if config.engine.speculative == "prompt_lookup":
            # TPU_RAG_SPECULATIVE governs the ONE-SHOT engine only;
            # without this the EXPLICIT knob would be silently inert
            # behind the scheduler (the default "auto" simply never
            # engages here — no warning). The continuous PAGED engine has
            # its own draft-and-verify under TPU_RAG_SPEC_PAGED
            # (docs/SPECULATIVE.md) — point the operator at it.
            logger.warning(
                "TPU_RAG_SPECULATIVE='prompt_lookup' is configured but "
                "TPU_RAG_BATCHING='continuous' routes requests through the "
                "slot engine, which that knob does not govern — the paged "
                "continuous engine speculates under TPU_RAG_SPEC_PAGED=1 "
                "(with TPU_RAG_KV_PAGED=1; docs/SPECULATIVE.md); "
                "batching='coalesce' (the default) serves the one-shot "
                "speculative path"
            )
        from rag_llm_k8s_tpu.engine.continuous import (
            ContinuousEngine,
            ContinuousScheduler,
        )

        # engine.params is already fused when tp == 1; passing it (rather
        # than the raw tree) lets the two engines SHARE the fused weight
        # buffers instead of materializing a second concatenated copy in HBM
        cont = ContinuousEngine(
            model_cfg, engine.params, sampling=config.sampling,
            engine_config=config.engine, dtypes=config.dtypes, mesh=mesh,
        )
        scheduler = ContinuousScheduler(
            cont,
            retries=config.resilience.inflight_retries,
            retry_backoff_s=config.resilience.retry_backoff_ms / 1e3,
        )
    else:
        from rag_llm_k8s_tpu.engine.batching import BatchScheduler

        # how long the worker waits, from the first request aboard, for the
        # others still in flight upstream (retrieval, prompt assembly). The
        # wait ends at once when every in-flight request is aboard, so a round
        # of callers pays it only when one of them is late, and a late one
        # costs less than the second dispatch it would otherwise ride: a
        # whole prefill and decode, and in a closed loop the round stays
        # split. 120 ms covers the slowest prompt assembly served: a byte
        # vocabulary's 20 k ids, tokenized twice by the budget rule, 15 ms a
        # request where a BPE prompt takes 3
        scheduler = BatchScheduler(engine, max_wait_ms=120.0)
    return RagService(
        config, engine, llm_tokenizer, encoder, enc_tokenizer, store, scheduler=scheduler
    )


def main():
    import signal

    from rag_llm_k8s_tpu.core.compile_cache import ensure_compile_cache
    from rag_llm_k8s_tpu.resilience import faults
    from rag_llm_k8s_tpu.server.app import create_app

    logger.info("compile cache: %s", ensure_compile_cache())
    service = build_service()
    service.ingest_directory()
    if service.store.ntotal == 0:
        logger.warning("No PDF files were processed. The index might be empty.")

    # crash-safe lifecycle (ISSUE 19): SIGTERM — every k8s roll, node
    # drain, and reschedule — begins the graceful drain instead of killing
    # decodes mid-stream. The coordinator's watcher finishes the in-flight
    # tail, persists the WAL + warmth manifest, and THEN exits the
    # process (os._exit: the dev WSGI server has no clean shutdown handle,
    # and persist already ran — nothing atexit could add).
    service.lifecycle.exit_fn = lambda: os._exit(0)
    signal.signal(
        signal.SIGTERM, lambda *_: service.lifecycle.begin_drain("sigterm")
    )

    def _warm_then_restore():
        # warm in the background so /healthz can report progress
        # immediately; the WAL restore pass runs AFTER warmup so the
        # resumed submits execute on compiled paths (and after the dead
        # epoch's WAL is on disk untouched — this incarnation appends to
        # its own epoch only)
        service.warmup()
        try:
            summary = service.restore_from_wal()
            if summary["resumed"] or summary["skipped"]:
                logger.info(
                    "WAL restore: resumed=%d skipped=%d rehydrated=%d",
                    summary["resumed"], summary["skipped"],
                    summary["rehydrated"],
                )
        except Exception:  # noqa: BLE001 — a failed restore must not kill boot
            logger.exception("WAL restore failed; serving cold")

    threading.Thread(target=_warm_then_restore, daemon=True).start()

    # chaos/staging only: TPU_RAG_FAULTS arms named failure sites and
    # enables POST /debug/faults (no-op when the variable is absent).
    # Armed AFTER boot ingest so the budget tests the SERVING path — arming
    # earlier let ingest consume e.g. an embed:1 budget and silently drop a
    # document instead. (Background warmup can still traverse a site; arm
    # via the endpoint once ready for a fully quiescent start.)
    armed = faults.arm_from_env()
    if armed:
        logger.warning("fault injection armed from TPU_RAG_FAULTS: %s", armed)

    app = create_app(service)
    cfg = service.config.server
    logger.info("serving on %s:%d", cfg.host, cfg.port)
    logger.info(
        "observability: /metrics (Prometheus exposition), /slo (error "
        "budgets + burn rates), /debug/traces (span-tree ring), /profile "
        "{\"seconds\": N} (background xprof) — see docs/OBSERVABILITY.md"
    )
    res = service.config.resilience
    logger.info(
        "resilience: admission %d concurrent + %d queued (429 beyond), "
        "default deadline %d ms, breaker %d resets / %.0f s — see "
        "docs/RESILIENCE.md",
        res.admission_max_concurrency, res.admission_max_queue,
        res.deadline_ms, res.breaker_reset_threshold, res.breaker_window_s,
    )
    app.run(host=cfg.host, port=cfg.port)


if __name__ == "__main__":
    main()
