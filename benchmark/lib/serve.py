"""The system under test, stood up as a deployment stands it up.

The scaffold ``chip_smoke.py`` proved on the chip (PR 21), copied so that the
yardstick does not move when the smoke does: seeded weights made on the
device, ``server.main.assemble_service``, ``warmup()``, a real werkzeug
server over a socket, one PDF through ``/upload_pdf``. From the
program it takes the service and its counters. What belongs to one decoder
family (its published keys, the program's model configuration, the parameter
layout its weights are drawn in) is ``families/<model_type>.py``, found by
the configuration's ``model_type``; what every family shares is here.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import logging
import math
import os
import re
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

# what every configuration file has, whatever its family; the published keys a
# family reads and the values it insists on are its own (families/<model_type>.py)
COMMON_KEYS = {"model_type", "source", "eos_token_id", "serving", "assumed", "reduced", "deployment"}
# ``serving``: these, any further key the family names (``SERVING_KEYS``), and
# sections: a dict under the name of a dataclass field of the program's
# ``AppConfig`` (``engine``, ``sampling``, ``shadow``, ...) overrides it by field name
SERVING_KEYS = {"tp", "weight_quant", "kv_quant", "encoder", "recite_gain", "weights_seed",
                "tokenizer_vocab"}
# what ``families/<model_type>.py`` and ``references/<model_type>.py`` must define
FAMILY_CONTRACT = ("PUBLISHED_KEYS", "FIXED", "REHEARSAL_MODEL", "model_config", "make_params",
                   "layer_loop_trips")
REFERENCE_CONTRACT = ("score", "HALF_GAP_TOL", "LOGIT_TOL")

# the statistics of every family's seeded weights (utils/synth.py, PR 21)
INT8_UNIFORM_STD = 72.75  # 126 / sqrt(3): std of uniform int8 per unit scale
LAYER_GAIN = 0.25  # projection std as a multiple of 1/sqrt(fan_in)
RECITE_PERIOD = 8  # cycle length of the reciting output head


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


def _load_by_model_type(kind: str, model_type: str, contract, root: str):
    """``<root>/<kind>/<model_type>.py`` as a module, held to ``contract``.
    The directory is the list: no registry, and a file that is missing or
    short of the contract is an error that names it. Imports no JAX (the
    files import it inside their functions)."""
    if not re.fullmatch(r"[A-Za-z0-9_-]+", str(model_type)):
        raise ValueError(f"model_type {model_type!r} is not a plain name")
    path = os.path.join(root, kind, model_type + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"model_type {model_type!r}: no {path} "
                                "(benchmark/README.md, 'A decoder family')")
    spec = importlib.util.spec_from_file_location(f"benchmark_{kind}_{model_type}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [name for name in contract if not hasattr(mod, name)]
    if missing:
        raise AttributeError(f"{path}: lacks {missing} of the contract {list(contract)}")
    return mod


def load_family(model_type: str, root: str = BENCH_DIR):
    """``families/<model_type>.py``: where a file becomes a model and its weights."""
    return _load_by_model_type("families", model_type, FAMILY_CONTRACT, root)


def load_reference(model_type: str, root: str = BENCH_DIR):
    """``references/<model_type>.py``: the family's plain reference and its tolerances."""
    return _load_by_model_type("references", model_type, REFERENCE_CONTRACT, root)


def load_config(path: str, root: str = BENCH_DIR):
    """One configuration file and the family its ``model_type`` names, as
    ``(cfg, family)``; unknown keys are an error."""
    with open(path, encoding="utf-8") as f:
        cfg = json.load(f)
    if "model_type" not in cfg:
        raise ValueError(f"{path}: no model_type, so no family to read it by")
    family = load_family(cfg["model_type"], root)
    unknown = set(cfg) - COMMON_KEYS - set(family.PUBLISHED_KEYS) - set(family.FIXED)
    if unknown:
        raise ValueError(f"{path}: unknown keys {sorted(unknown)}")
    for key, want in family.FIXED.items():
        if key in cfg and cfg[key] != want:
            raise ValueError(f"{path}: {key}={cfg[key]!r}; this decoder runs {want!r} only")
    known = SERVING_KEYS | set(getattr(family, "SERVING_KEYS", ()))
    unknown = {k for k, v in cfg.get("serving", {}).items() if k not in known and not isinstance(v, dict)}
    if unknown:
        raise ValueError(f"{path}: unknown serving keys {sorted(unknown)}")
    return cfg, family


def _replace(obj, overrides: dict, where: str):
    """Dataclass overrides by field name; a name the program does not have is
    an error, so a renamed field cannot be dropped silently. A dict for a
    field that is itself a dataclass is overrides of that dataclass."""
    names = {f.name for f in dataclasses.fields(obj)}
    unknown = set(overrides) - names
    if unknown:
        raise ValueError(f"{where}: no such field {sorted(unknown)}")
    fixed = {}
    for k, v in overrides.items():
        if isinstance(v, dict) and dataclasses.is_dataclass(getattr(obj, k)):
            v = _replace(getattr(obj, k), v, f"{where}.{k}")
        fixed[k] = tuple(v) if isinstance(v, list) else v
    return dataclasses.replace(obj, **fixed)


def app_config(cfg: dict, model, work: str, max_new_tokens: int, attn_impl: str = ATTN_IMPL):
    """The served configuration: the file's overrides on the program's
    defaults, everything the service writes kept under ``work``."""
    from rag_llm_k8s_tpu.core.config import (
        AppConfig, EngineConfig, FlightConfig, MeshConfig, SamplingConfig, ServerConfig,
    )

    s = cfg["serving"]
    config = AppConfig(
        mesh=MeshConfig(dp=1, sp=1, tp=int(s["tp"])), model=model,
        engine=EngineConfig(weight_quant=s["weight_quant"], kv_quant=s["kv_quant"],
                            attn_impl=attn_impl),
        sampling=SamplingConfig(max_new_tokens=max_new_tokens),
        server=ServerConfig(
            host="127.0.0.1", model_path=work, embedder_path=work,
            index_path=os.path.join(work, "index"), pdf_dir=os.path.join(work, "pdfs")),
        flight=FlightConfig(spool_dir=os.path.join(work, "incidents")),
    )
    sections = {k: v for k, v in s.items() if isinstance(v, dict)}
    return _replace(config, sections, "serving")


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


def draw_head(key, embedding, eos_ids, recite_gain: float, dtype):
    """The untied output head of every family, traced inside the family's one
    jitted call: unit-std logits, zeroed EOS columns (every stream runs its
    whole budget) and ``recite_gain/D`` of each token's cycle predecessor's
    embedding (answers that partly repeat their history, which is what
    prompt-lookup speculation drafts from, so every speculation number rests
    on it). ``embedding`` is ``[V, D]``; ``dtype`` is the type of the head's
    first leaf in the program's layout: int8 gives ``(lm_head_q, lm_head_scale)``,
    any other ``(lm_head [D, V],)`` in it."""
    import jax
    import jax.numpy as jnp

    V, D = embedding.shape
    w = jax.random.normal(key, (D, V), jnp.float32) / math.sqrt(D)
    if recite_gain:
        v = jnp.arange(V)
        base = v - v % RECITE_PERIOD
        pred = jnp.minimum(base + (v - base - 1) % RECITE_PERIOD, V - 1)
        w = w + (recite_gain / D) * embedding[pred].T.astype(jnp.float32)
    w = w.at[:, jnp.asarray(eos_ids)].set(0.0)
    if dtype != jnp.int8:
        return (w.astype(dtype),)
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0) / 127.0, 1e-8)
    return jnp.round(w / scale[None, :]).astype(jnp.int8), scale


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
