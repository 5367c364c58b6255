"""Weight loading: HF safetensors → stacked, sharded Flax parameter pytrees.

The reference stages exactly 10 files of Meta-Llama-3.1-8B-Instruct into the
model PVC (/root/reference/llm/download_model.py:14-25) and loads them with
``AutoModelForCausalLM.from_pretrained`` (rag.py:24). This loader consumes the
SAME on-disk layout (``model-0000x-of-00004.safetensors`` + config/tokenizer
files) but materializes each tensor directly as a device array with its
NamedSharding — weights stream HBM-ward shard by shard, never building the
whole fp32 model on host (the reference needs ~32 GB host RAM for that).

Name mapping (HF → framework; torch ``nn.Linear`` stores ``[out, in]`` so all
kernels transpose):

    model.embed_tokens.weight                  -> embedding            [V, D]
    model.layers.{i}.self_attn.q_proj.weight   -> layers.attn.wq.kernel[i]  (T)
    model.layers.{i}.self_attn.k_proj.weight   -> layers.attn.wk.kernel[i]  (T)
    model.layers.{i}.self_attn.v_proj.weight   -> layers.attn.wv.kernel[i]  (T)
    model.layers.{i}.self_attn.o_proj.weight   -> layers.attn.wo.kernel[i]  (T)
    model.layers.{i}.mlp.gate_proj.weight      -> layers.mlp.w_gate.kernel[i] (T)
    model.layers.{i}.mlp.up_proj.weight        -> layers.mlp.w_up.kernel[i]   (T)
    model.layers.{i}.mlp.down_proj.weight      -> layers.mlp.w_down.kernel[i] (T)
    model.layers.{i}.input_layernorm.weight    -> layers.input_norm.scale[i]
    model.layers.{i}.post_attention_layernorm.weight -> layers.post_attn_norm.scale[i]
    model.norm.weight                          -> final_norm.scale
    lm_head.weight                             -> lm_head              (T; absent when tied)

Layer-indexed entries stack into ``[L, ...]`` arrays matching the ``nn.scan``
parameter layout of :class:`~rag_llm_k8s_tpu.models.llama.LlamaModel`.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from rag_llm_k8s_tpu.core.config import DTypePolicy, LlamaConfig
from rag_llm_k8s_tpu.parallel.sharding import is_quant_leaf

_LAYER_RE = re.compile(r"^model\.layers\.(\d+)\.(.+)$")

# HF suffix -> (framework path under layers/, transpose?)
_LAYER_MAP = {
    "self_attn.q_proj.weight": (("attn", "wq", "kernel"), True),
    "self_attn.k_proj.weight": (("attn", "wk", "kernel"), True),
    "self_attn.v_proj.weight": (("attn", "wv", "kernel"), True),
    "self_attn.o_proj.weight": (("attn", "wo", "kernel"), True),
    "mlp.gate_proj.weight": (("mlp", "w_gate", "kernel"), True),
    "mlp.up_proj.weight": (("mlp", "w_up", "kernel"), True),
    "mlp.down_proj.weight": (("mlp", "w_down", "kernel"), True),
    "input_layernorm.weight": (("input_norm", "scale"), False),
    "post_attention_layernorm.weight": (("post_attn_norm", "scale"), False),
}

_TOP_MAP = {
    "model.embed_tokens.weight": (("embedding",), False),
    "model.norm.weight": (("final_norm", "scale"), False),
    "lm_head.weight": (("lm_head",), True),
}


def _to_numpy(t) -> np.ndarray:
    """torch tensor / numpy array -> numpy (torch bf16 upcasts to fp32; the
    framework casts back to its param dtype at placement)."""
    if isinstance(t, np.ndarray):
        return t
    if hasattr(t, "detach"):  # torch tensor (tests convert HF models directly)
        t = t.detach()
        if "bfloat16" in str(t.dtype):
            t = t.float()
        return t.cpu().numpy()
    return np.asarray(t)


def _quantize_np(arr: np.ndarray, axis: int):
    """Host-side symmetric per-output-channel int8 (the numpy twin of
    ``models.llama._quantize_leaf``). All paths are CHUNKED so the fp32
    transient stays at ~hundreds of MB regardless of tensor size — a naive
    whole-tensor pass holds ~3 fp32 copies (cast + |w| + rounded quotient),
    which for a 70B lm_head (2.1 GiB bf16) is a ~13 GiB spike that defeats
    the streaming loader's whole memory contract (caught by
    tests/test_loader_8b.py's transient bound)."""
    if arr.ndim == 3:
        assert axis == 1
        out_q = np.empty(arr.shape, np.int8)
        scales = np.empty((arr.shape[0], arr.shape[2]), np.float32)
        for layer in range(arr.shape[0]):
            out_q[layer], scales[layer] = _quantize_np(arr[layer], 0)
        return out_q, scales
    keep = 1 - axis  # the per-channel (scale) axis
    out_q = np.empty(arr.shape, np.int8)
    scales = np.empty(arr.shape[keep], np.float32)
    # ~64 MB of fp32 per chunk along the channel axis
    step = max(1, (64 << 20) // max(arr.shape[axis] * 4, 1))
    for c0 in range(0, arr.shape[keep], step):
        c1 = min(c0 + step, arr.shape[keep])
        sl = [slice(None), slice(None)]
        sl[keep] = slice(c0, c1)
        sl = tuple(sl)
        w = arr[sl].astype(np.float32)
        s = np.maximum(np.abs(w).max(axis=axis) / 127.0, 1e-8)
        out_q[sl] = np.round(w / np.expand_dims(s, axis))
        scales[c0:c1] = s
    return out_q, scales


def convert_hf_state_dict(
    state_dict,
    config: LlamaConfig,
    dtypes: DTypePolicy = DTypePolicy(),
    put: Optional[Callable[[tuple, np.ndarray], jax.Array]] = None,
    quant: str = "bf16",
) -> dict:
    """Convert a flat HF llama state dict into the framework's param pytree.

    ``state_dict`` is any mapping with ``keys()`` and ``__getitem__`` —
    a plain dict (tests) or :class:`_LazyStateDict` (production). Conversion
    is TARGET-driven: each framework parameter pulls exactly the HF tensors it
    needs, stacks, places, and frees them — host peak memory is one stacked
    layer group, never the whole checkpoint.

    ``put(path, array)`` controls device placement (e.g. ``device_put`` with a
    NamedSharding looked up from ``parallel.sharding``); default is host->
    default-device with dtype cast to ``dtypes.param_dtype``.

    ``quant="int8"`` quantizes each projection kernel (and the logit head —
    tied or untied) HOST-SIDE before placement, emitting the
    ``LlamaModel(quantized=True)`` layout (``kernel_q``/``qscale``). This is
    how 8B fits ONE 16 GB chip: bf16 kernels never exist on device, and the
    transfer ships half the bytes. Norm scales and an untied embedding stay
    ``param_dtype``.
    """
    if quant not in ("bf16", "int8"):
        raise ValueError(f"quant={quant!r}: expected 'bf16' or 'int8'")
    if put is None:
        put = lambda path, arr: jnp.asarray(  # noqa: E731
            arr,
            dtype=None if is_quant_leaf(path) else dtypes.param_dtype,
        )

    def place(path: tuple, arr: np.ndarray, quant_axis: Optional[int]):
        """Emit one framework parameter: verbatim, or as its int8 pair."""
        if quant == "int8" and quant_axis is not None:
            kq, scales = _quantize_np(arr, quant_axis)
            del arr
            if path[-1] == "kernel":
                q_path, s_path = path[:-1] + ("kernel_q",), path[:-1] + ("qscale",)
            else:  # top-level: lm_head / embedding
                q_path, s_path = (path[0] + "_q",), (path[0] + "_scale",)
            assign(params, q_path, put(q_path, kq))
            assign(params, s_path, put(s_path, scales))
        else:
            assign(params, path, put(path, arr))

    L = config.num_layers

    # -- validate the key surface up front (names only, no tensor loads) ----
    names = set(state_dict.keys())
    expected = set(_TOP_MAP)
    if config.tie_word_embeddings:
        expected.discard("lm_head.weight")
    for i in range(L):
        for suffix in _LAYER_MAP:
            expected.add(f"model.layers.{i}.{suffix}")
    unknown = {
        n for n in names - expected if not n.endswith("rotary_emb.inv_freq")
    }
    if unknown:
        raise KeyError(f"unrecognized HF params: {sorted(unknown)[:5]} ...")
    missing = expected - names
    if config.tie_word_embeddings:
        missing.discard("lm_head.weight")
    if missing:
        raise ValueError(f"missing HF params: {sorted(missing)[:5]} ...")

    def assign(tree: dict, path: tuple, value):
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = value

    params: dict = {}

    for name, (path, transpose) in _TOP_MAP.items():
        if name == "lm_head.weight" and config.tie_word_embeddings:
            continue
        arr = _to_numpy(state_dict[name])
        if transpose:
            arr = arr.T
        if path == ("lm_head",):  # [D, V]: logit channels are vocab columns
            qaxis = 0
        elif path == ("embedding",) and config.tie_word_embeddings:
            qaxis = 1  # tied [V, D]: rows double as logit output channels
        else:
            qaxis = None  # untied embedding (gather-only) and norms stay bf16
        place(path, arr, qaxis)
        del arr

    for suffix, (sub_path, transpose) in _LAYER_MAP.items():
        path = ("layers",) + sub_path
        layers = []
        for i in range(L):
            arr = _to_numpy(state_dict[f"model.layers.{i}.{suffix}"])
            layers.append(arr.T if transpose else arr)
        stacked = np.stack(layers, axis=0)
        del layers
        # stacked [L, in, out] projection kernels contract over axis 1
        place(path, stacked, 1 if path[-1] == "kernel" else None)
        del stacked

    return params


class _LazyStateDict:
    """Mapping over safetensors shards that loads one tensor at a time.

    ``items()`` yields tensors in on-disk order but each array is read only
    when yielded and can be freed by the consumer — peak host memory is one
    stacked parameter group (~4 GB bf16 for an 8B MLP stack), not the whole
    checkpoint (~16 GB). The reference, by contrast, materializes the full
    fp32 model on host (rag.py:24 ⇒ the README's 64 GB node floor).
    """

    def __init__(self, files):
        from safetensors import safe_open

        self._index: Dict[str, str] = {}
        self._safe_open = safe_open
        for f in files:
            with safe_open(f, framework="np") as reader:
                for name in reader.keys():
                    self._index[name] = f

    def keys(self):
        return self._index.keys()

    def __getitem__(self, name: str) -> np.ndarray:
        with self._safe_open(self._index[name], framework="np") as reader:
            return reader.get_tensor(name)


def load_safetensors_params(
    model_dir: str,
    config: LlamaConfig,
    dtypes: DTypePolicy = DTypePolicy(),
    put: Optional[Callable[[tuple, np.ndarray], jax.Array]] = None,
    quant: str = "bf16",
) -> dict:
    """Read every ``*.safetensors`` shard under ``model_dir`` (the PVC layout
    staged by download_model.py) and build the sharded param tree, streaming
    tensor-by-tensor to device. ``quant="int8"`` streams the weight-only
    int8 layout instead (see :func:`convert_hf_state_dict`)."""
    files = sorted(glob.glob(os.path.join(model_dir, "*.safetensors")))
    if not files:
        raise FileNotFoundError(f"no .safetensors files under {model_dir}")
    return convert_hf_state_dict(
        _LazyStateDict(files), config, dtypes, put=put, quant=quant
    )


# ---------------------------------------------------------------------------
# XLM-R / bge-m3 encoder conversion
# ---------------------------------------------------------------------------

# HF suffix (under encoder.layer.{i}.) -> framework path under layers/
_XLMR_LAYER_MAP = {
    "attention.self.query": ("wq",),
    "attention.self.key": ("wk",),
    "attention.self.value": ("wv",),
    "attention.output.dense": ("wo",),
    "intermediate.dense": ("w_in",),
    "output.dense": ("w_out",),
}
_XLMR_LAYER_LN = {
    "attention.output.LayerNorm": ("attn_ln",),
    "output.LayerNorm": ("ffn_ln",),
}


def convert_xlmr_state_dict(
    state_dict,
    config,
    dtypes: DTypePolicy = DTypePolicy(),
    put: Optional[Callable[[tuple, np.ndarray], jax.Array]] = None,
) -> dict:
    """HF ``XLMRobertaModel`` state dict → :class:`BgeM3Encoder` params.

    Accepts keys with or without a ``roberta.`` prefix; the unused pooler is
    skipped. Kernel transposes follow torch Linear ``[out, in]`` storage.
    """
    if put is None:
        put = lambda path, arr: jnp.asarray(arr, dtype=dtypes.param_dtype)  # noqa: E731

    # name map only — tensors load lazily one at a time
    names = {n.removeprefix("roberta."): n for n in state_dict.keys()}
    L = config.num_layers

    def get(name):
        return _to_numpy(state_dict[names[name]])

    params: dict = {
        "word_embeddings": put(("word_embeddings",), get("embeddings.word_embeddings.weight")),
        "position_embeddings": put(
            ("position_embeddings",), get("embeddings.position_embeddings.weight")
        ),
        "token_type_embeddings": put(
            ("token_type_embeddings",), get("embeddings.token_type_embeddings.weight")
        ),
        "embed_ln": {
            "scale": put(("embed_ln", "scale"), get("embeddings.LayerNorm.weight")),
            "bias": put(("embed_ln", "bias"), get("embeddings.LayerNorm.bias")),
        },
        "layers": {},
    }
    layers: dict = params["layers"]
    for suffix, sub in _XLMR_LAYER_MAP.items():
        kernels = [get(f"encoder.layer.{i}.{suffix}.weight").T for i in range(L)]
        biases = [get(f"encoder.layer.{i}.{suffix}.bias") for i in range(L)]
        layers[sub[0]] = {
            "kernel": put(("layers",) + sub + ("kernel",), np.stack(kernels)),
            "bias": put(("layers",) + sub + ("bias",), np.stack(biases)),
        }
    for suffix, sub in _XLMR_LAYER_LN.items():
        scales = [get(f"encoder.layer.{i}.{suffix}.weight") for i in range(L)]
        biases = [get(f"encoder.layer.{i}.{suffix}.bias") for i in range(L)]
        layers[sub[0]] = {
            "scale": put(("layers",) + sub + ("scale",), np.stack(scales)),
            "bias": put(("layers",) + sub + ("bias",), np.stack(biases)),
        }
    return params


def load_encoder_safetensors(model_dir: str, config, dtypes: DTypePolicy = DTypePolicy(), put=None):
    """Load a bge-m3 / XLM-R checkpoint directory (PVC-staged) into params."""
    files = sorted(glob.glob(os.path.join(model_dir, "*.safetensors")))
    if not files:
        raise FileNotFoundError(f"no .safetensors files under {model_dir}")
    return convert_xlmr_state_dict(_LazyStateDict(files), config, dtypes, put=put)


def config_from_hf_json(model_dir: str) -> LlamaConfig:
    """Build a LlamaConfig from the staged ``config.json``
    (download_model.py:15 stages it alongside the weights)."""
    import json

    from rag_llm_k8s_tpu.core.config import RopeScalingConfig

    with open(os.path.join(model_dir, "config.json")) as f:
        hf = json.load(f)
    rs = hf.get("rope_scaling") or None
    rope_scaling = None
    if rs and rs.get("rope_type", rs.get("type")) == "llama3":
        rope_scaling = RopeScalingConfig(
            factor=rs["factor"],
            low_freq_factor=rs["low_freq_factor"],
            high_freq_factor=rs["high_freq_factor"],
            original_max_position_embeddings=rs["original_max_position_embeddings"],
        )
    eos = hf.get("eos_token_id", 128009)
    eos = tuple(eos) if isinstance(eos, (list, tuple)) else (eos,)
    return LlamaConfig(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
        head_dim=hf.get("head_dim", hf["hidden_size"] // hf["num_attention_heads"]),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
        rope_theta=hf.get("rope_theta", 500000.0),
        rope_scaling=rope_scaling,
        max_seq_len=hf.get("max_position_embeddings", 131072),
        tie_word_embeddings=hf.get("tie_word_embeddings", False),
        bos_token_id=hf.get("bos_token_id", 128000),
        eos_token_ids=eos,
    )
