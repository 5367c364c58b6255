"""The system under test, stood up as a deployment stands it up.

The scaffold ``chip_smoke.py`` proved on the chip (PR 21), copied so that the
yardstick does not move when the smoke does: seeded weights made on the
device, ``server.main.assemble_service``, ``warmup()``, a real werkzeug
server over a socket, one PDF through ``/upload_pdf``. From the
program it takes the service, its parameter layout and its counters.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import math
import os
import threading
import time
import urllib.error
import urllib.request

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH_DIR)
# what a run builds and keeps between runs of one checkout (gitignored):
# tokenizers beside the compile cache, never under a temporary name
STATE_DIR = os.path.join(REPO, ".bench_state")
AUDIT_TOL = 0.15  # SloConfig.quality_logit_err, the auditor's pinned bound
ATTN_IMPL = "pallas"  # explicit, never "auto": no backend sniffing here

# published config.json key -> LlamaConfig field
HF_TO_LLAMA = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "rms_norm_eps": "rms_norm_eps",
    "rope_theta": "rope_theta",
    "max_position_embeddings": "max_seq_len",
    "tie_word_embeddings": "tie_word_embeddings",
    "bos_token_id": "bos_token_id",
}
# published keys that select nothing in this decoder but must hold these
# values for it to be the published block
HF_FIXED = {"hidden_act": "silu", "sliding_window": None, "rope_scaling": None,
            "attention_bias": False, "mlp_bias": False, "model_type": "mistral"}
SERVING_KEYS = {"tp", "weight_quant", "kv_quant", "encoder", "recite_gain", "weights_seed",
                "tokenizer_vocab", "engine", "sampling", "shadow"}
CONFIG_KEYS = set(HF_TO_LLAMA) | set(HF_FIXED) | {
    "source", "eos_token_id", "serving", "assumed", "reduced", "deployment"}

_INT8_UNIFORM_STD = 72.75  # 126 / sqrt(3): std of uniform int8 per unit scale
_LAYER_GAIN = 0.25  # projection std as a multiple of 1/sqrt(fan_in)
_RECITE_PERIOD = 8  # cycle length of the reciting output head


class CompileCounter:
    """Every executable JAX builds (compiled or fetched from the persistent
    cache alike), with the cache's hits and misses."""

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


class ErrorLog(logging.Handler):
    """Every ERROR the package logs: the serving path catches and carries
    on, which is right for a server and a wrong answer for a benchmark."""

    def __init__(self):
        super().__init__(level=logging.ERROR)
        self.records = []

    def emit(self, record):
        self.records.append(self.format(record))


def load_config(path: str) -> dict:
    """One configuration file; unknown keys are an error."""
    with open(path, encoding="utf-8") as f:
        cfg = json.load(f)
    unknown = set(cfg) - CONFIG_KEYS
    if unknown:
        raise ValueError(f"{path}: unknown keys {sorted(unknown)}")
    for key, want in HF_FIXED.items():
        if key in cfg and cfg[key] != want:
            raise ValueError(f"{path}: {key}={cfg[key]!r}; this decoder runs {want!r} only")
    unknown = set(cfg.get("serving", {})) - SERVING_KEYS
    if unknown:
        raise ValueError(f"{path}: unknown serving keys {sorted(unknown)}")
    return cfg


def llama_config(cfg: dict):
    from rag_llm_k8s_tpu.core.config import LlamaConfig

    fields = {dst: cfg[src] for src, dst in HF_TO_LLAMA.items() if src in cfg}
    return LlamaConfig(rope_scaling=None, eos_token_ids=(int(cfg["eos_token_id"]),), **fields)


def _replace(obj, overrides: dict, where: str):
    """Dataclass overrides by field name; a name the program does not have is
    an error, so a renamed field cannot be dropped silently."""
    names = {f.name for f in dataclasses.fields(obj)}
    unknown = set(overrides) - names
    if unknown:
        raise ValueError(f"{where}: no such field {sorted(unknown)}")
    fixed = {k: tuple(v) if isinstance(v, list) else v for k, v in overrides.items()}
    return dataclasses.replace(obj, **fixed)


def app_config(cfg: dict, model, work: str, max_new_tokens: int, attn_impl: str = ATTN_IMPL):
    """The served configuration: the file's overrides on the program's
    defaults, everything the service writes kept under ``work``."""
    from rag_llm_k8s_tpu.core.config import (
        AppConfig, EngineConfig, FlightConfig, MeshConfig, SamplingConfig,
        ServerConfig, ShadowConfig,
    )

    s = cfg["serving"]
    engine = _replace(
        EngineConfig(weight_quant=s["weight_quant"], kv_quant=s["kv_quant"],
                     attn_impl=attn_impl),
        s.get("engine", {}), "serving.engine")
    sampling = _replace(SamplingConfig(max_new_tokens=max_new_tokens),
                        s.get("sampling", {}), "serving.sampling")
    return AppConfig(
        mesh=MeshConfig(dp=1, sp=1, tp=int(s["tp"])), model=model, engine=engine,
        sampling=sampling,
        server=ServerConfig(
            host="127.0.0.1", model_path=work, embedder_path=work,
            index_path=os.path.join(work, "index"), pdf_dir=os.path.join(work, "pdfs")),
        flight=FlightConfig(spool_dir=os.path.join(work, "incidents")),
        shadow=_replace(ShadowConfig(), s.get("shadow", {}), "serving.shadow"),
    )


# ---------------------------------------------------------------------------
# tokenizers: trained once per checkout, kept in STATE_DIR
# ---------------------------------------------------------------------------


def _harvest_corpus(target_mb: float):
    """A deterministic sample of the installation's Python sources (there is
    no network; this is the text the machine has)."""
    import glob
    import random
    import site

    paths = []
    for root in [os.path.dirname(os.__file__)] + site.getsitepackages():
        paths += glob.glob(os.path.join(root, "**", "*.py"), recursive=True)
    paths.sort()
    random.Random(0).shuffle(paths)
    texts, total = [], 0
    for p in paths:
        try:
            with open(p, encoding="utf-8", errors="ignore") as f:
                t = f.read()
        except OSError:
            continue
        texts.append(t)
        total += len(t)
        if total > target_mb * 1e6:
            break
    return texts


def _train_bpe(path: str, texts, vocab_size: int):
    from tokenizers import Tokenizer
    from tokenizers.decoders import ByteLevel as ByteLevelDecoder
    from tokenizers.models import BPE
    from tokenizers.pre_tokenizers import ByteLevel
    from tokenizers.trainers import BpeTrainer

    tok = Tokenizer(BPE(unk_token=None))
    tok.pre_tokenizer = ByteLevel(add_prefix_space=False, use_regex=True)
    tok.decoder = ByteLevelDecoder()
    tok.train_from_iterator(texts, BpeTrainer(
        vocab_size=vocab_size, special_tokens=["<unk>", "<s>", "</s>"],
        initial_alphabet=ByteLevel.alphabet(), show_progress=False))
    tok.save(path)


def _build_unigram(path: str, texts, n_pieces: int):
    """A quarter-million-piece Unigram from word statistics (the arithmetic of
    tests/fixtures/gen_tokenizers.py gen_scale_unigram)."""
    import collections

    from tokenizers import Tokenizer
    from tokenizers.models import Unigram
    from tokenizers.pre_tokenizers import Metaspace

    words, chars = collections.Counter(), collections.Counter()
    for t in texts:
        for w in t.split():
            w = w[:16]
            words["▁" + w] += 1
            if len(w) > 1:
                words[w] += 1
        chars.update(t.replace(" ", "▁"))
    total = sum(words.values()) + sum(chars.values())
    vocab, seen = [("<unk>", 0.0)], {"<unk>"}
    for ch, c in chars.items():
        if ch not in seen:
            vocab.append((ch, math.log(max(c, 1) / total)))
            seen.add(ch)
    for w, c in words.most_common():
        if len(vocab) >= n_pieces:
            break
        if w not in seen:
            vocab.append((w, math.log(c / total)))
            seen.add(w)
    tok = Tokenizer(Unigram(vocab=vocab, unk_id=0))
    tok.pre_tokenizer = Metaspace()
    tok.save(path)


def ensure_tokenizers(llm_vocab: int, enc_pieces: int = 250000, corpus_mb: float = 24.0):
    """Paths of a byte-level BPE with ``llm_vocab`` entries at most and the
    encoder's Unigram, trained here when the checkout has none yet. Touches
    no JAX. Returns ``(bpe_path, unigram_path, trained_now)``."""
    out = os.path.join(STATE_DIR, "tokenizers")
    bpe = os.path.join(out, f"bpe_{llm_vocab}.json")
    uni = os.path.join(out, f"unigram_{enc_pieces}.json")
    missing = [p for p in (bpe, uni) if not os.path.exists(p)]
    if missing:
        os.makedirs(out, exist_ok=True)
        texts = _harvest_corpus(corpus_mb)
        if bpe in missing:
            _train_bpe(bpe + ".tmp", texts, llm_vocab)
            os.replace(bpe + ".tmp", bpe)
        if uni in missing:
            _build_unigram(uni + ".tmp", texts, enc_pieces)
            os.replace(uni + ".tmp", uni)
    return bpe, uni, bool(missing)


# ---------------------------------------------------------------------------
# weights: one jitted call, on the device, in the type they are served in
# ---------------------------------------------------------------------------


def make_llama_params(config, dtypes, seed: int, quant: str, mesh, recite_gain: float):
    """Seeded random params in the program's ``LlamaModel`` layout, every
    leaf born on its device(s) in its serving dtype and sharding, in ONE
    jitted call. The statistics are those of ``utils/synth.py
    synth_llama_params`` (PR 21): RMSNorm weights 1; projection kernels of
    std ``0.25/sqrt(fan_in)``; a unit-std embedding; an untied head giving
    unit-std logits with zeroed EOS columns (every stream runs its whole
    budget) and ``recite_gain/D`` of each token's cycle predecessor's
    embedding (answers that partly repeat their history, which is what
    prompt-lookup speculation drafts from)."""
    import jax
    import jax.numpy as jnp
    from flax import traverse_util
    from jax.sharding import NamedSharding

    from rag_llm_k8s_tpu.models.llama import (
        init_llama_params, quantize_llama_params, synth_leaf_kind,
    )
    from rag_llm_k8s_tpu.parallel.sharding import llama_param_specs

    shapes = jax.eval_shape(lambda: init_llama_params(jax.random.PRNGKey(0), config, dtypes))
    if quant == "int8":
        shapes = jax.eval_shape(quantize_llama_params, shapes)
    elif quant != "bf16":
        raise ValueError(f"weight_quant={quant!r}: expected 'bf16' or 'int8'")
    flat = traverse_util.flatten_dict(shapes)
    specs = traverse_util.flatten_dict(llama_param_specs(shapes, mesh))
    D, V = config.hidden_size, config.vocab_size
    head_paths = [p for p in (("lm_head",), ("lm_head_q",), ("lm_head_scale",)) if p in flat]
    body = sorted(p for p in flat if p not in head_paths)

    def draw(path, s, key):
        kind = synth_leaf_kind(path, s.dtype)
        if kind == "norm":
            return jnp.ones(s.shape, s.dtype)
        fan_in = config.intermediate_size if "w_down" in path else D
        if kind == "quant_scale":
            return jnp.full(s.shape, _LAYER_GAIN / (_INT8_UNIFORM_STD * math.sqrt(fan_in)), s.dtype)

        def block(k, shape):
            if kind == "kernel_q":
                return jax.random.randint(k, shape, -126, 127, jnp.int8)
            std = 1.0 if kind == "embedding" else _LAYER_GAIN / math.sqrt(fan_in)
            return (jax.random.normal(k, shape, jnp.float32) * std).astype(s.dtype)

        if s.ndim == 3:  # stacked [L, in, out]: one layer per loop step
            return jax.lax.map(lambda k: block(k, s.shape[1:]), jax.random.split(key, s.shape[0]))
        return block(key, s.shape)

    def draw_head(key, embedding):
        w = jax.random.normal(key, (D, V), jnp.float32) / math.sqrt(D)
        if recite_gain:
            v = jnp.arange(V)
            base = v - v % _RECITE_PERIOD
            pred = jnp.minimum(base + (v - base - 1) % _RECITE_PERIOD, V - 1)
            w = w + (recite_gain / D) * embedding[pred].T.astype(jnp.float32)
        w = w.at[:, jnp.asarray(config.eos_token_ids)].set(0.0)
        if quant == "bf16":
            return (w.astype(flat[("lm_head",)].dtype),)
        scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0) / 127.0, 1e-8)
        return jnp.round(w / scale[None, :]).astype(jnp.int8), scale

    def make(root):
        out = {p: draw(p, flat[p], jax.random.fold_in(root, i)) for i, p in enumerate(body)}
        if head_paths:
            leaves = draw_head(jax.random.fold_in(root, len(flat)), out[("embedding",)])
            out.update(zip(head_paths, leaves))
        return out

    shardings = {p: NamedSharding(mesh.mesh, specs[p]) for p in flat}
    root = prng_key(seed, 0)
    return traverse_util.unflatten_dict(jax.jit(make, out_shardings=shardings)(root))


def prng_key(seed: int, salt: int):
    """A seed over 2**31 does not fit the int32 a PRNGKey takes: fold it in
    halves."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) * 2 + salt)


def make_encoder_params(config, dtypes, seed: int):
    import jax

    from rag_llm_k8s_tpu.models.bge_m3 import init_encoder_params

    return jax.jit(lambda k: init_encoder_params(k, config, dtypes))(prng_key(seed, 1))


# ---------------------------------------------------------------------------
# HTTP over a real socket
# ---------------------------------------------------------------------------


def http(method: str, url: str, body=None, headers=None, timeout: float = 600.0):
    """One request -> (status, parsed JSON or text). A refused or reset
    connection is status 0, so a dead server fails a run, not a thread."""
    data, headers = None, dict(headers or {})
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
    except (urllib.error.URLError, OSError) as e:
        return 0, repr(e)
    try:
        return status, json.loads(raw)
    except ValueError:
        return status, raw.decode("utf-8", "replace")


def multipart_pdf(pdf: bytes, filename: str):
    boundary = "benchmark-7d1f3c"
    body = (
        f"--{boundary}\r\nContent-Disposition: form-data; name=\"file\"; "
        f"filename=\"{filename}\"\r\nContent-Type: application/pdf\r\n\r\n"
    ).encode() + pdf + f"\r\n--{boundary}--\r\n".encode()
    return body, {"Content-Type": f"multipart/form-data; boundary={boundary}"}


class Served:
    """A warmed service behind a real server on a free port."""

    def __init__(self, service):
        from werkzeug.serving import make_server

        from rag_llm_k8s_tpu.server.app import create_app

        self.service = service
        self.delivered = []  # (emitted ids, prompt ids or a function giving them)
        self._tap_deliveries()
        self.srv = make_server("127.0.0.1", 0, create_app(service), threaded=True)
        self.base = f"http://127.0.0.1:{self.srv.server_port}"
        self.thread = threading.Thread(target=self.srv.serve_forever, name="wsgi", daemon=True)
        self.thread.start()

    def _tap_deliveries(self):
        """Keep what each response delivered (token ids and the prompt they
        answered) for the audit after the window. The tap sits where the
        program already hands every response to its shadow auditor, whose
        sampler stays off in the window; it does no device work."""
        shadow = self.service.shadow
        if shadow is None:
            raise RuntimeError("the service has no shadow auditor to tap")
        inner = shadow.observe

        def observe(emitted, *args, prompt_ids=None, prompt_fn=None, **kw):
            self.delivered.append((list(emitted), prompt_ids if prompt_ids is not None else prompt_fn))
            return inner(emitted, *args, prompt_ids=prompt_ids, prompt_fn=prompt_fn, **kw)

        shadow.observe = observe

    def upload(self, pdf: bytes) -> dict:
        payload, headers = multipart_pdf(pdf, "corpus.pdf")
        status, body = http("POST", self.base + "/upload_pdf", payload, headers)
        if status != 200:
            raise RuntimeError(f"/upload_pdf: {status} {str(body)[:300]}")
        status, info = http("GET", self.base + "/index_info")
        if status != 200:
            raise RuntimeError(f"/index_info: {status} {str(info)[:300]}")
        return info

    def generate(self, question: str, due: float) -> dict:
        """One /generate; the record the metric readers work from. ``tokens``
        is what the response delivered, read at the tap."""
        start = time.monotonic()
        status, body = http("POST", self.base + "/generate", {"prompt": question})
        end = time.monotonic()
        timings = body.get("timings", {}) if isinstance(body, dict) else {}
        return {"due": due, "start": start, "end": end, "status": status,
                "timings": timings, "tokens": None,
                "error": None if status == 200 else str(body)[:200]}

    def scrape(self) -> str:
        status, text = http("GET", self.base + "/metrics")
        if status != 200 or not isinstance(text, str):
            raise RuntimeError(f"/metrics: {status}")
        return text

    def close(self):
        self.srv.shutdown()
        self.srv.server_close()
        self.thread.join(timeout=30)
        self.service.shutdown()
