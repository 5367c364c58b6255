"""A streamed checkpoint load, measured in a process of its own.

``ru_maxrss`` is a high-water mark over a process's LIFETIME, and a pytest
worker has run other files before a loader test: memory they left to the
collector is freed during the load, so ``peak - max(rss_after, peak_before)``
taken in the worker measures its neighbours. The loader tests therefore make
the load here, in a fresh interpreter whose only history is its imports, and
assert on the facts this prints (one JSON object on the last stdout line):
every leaf's shape, dtype, sharding and bytes, the host-memory readings around
the load, and, when asked, the logits of one forward over the loaded tree.

    python tests/loader_probe.py <checkpoint dir> <LlamaConfig preset> <layers> <bf16|int8> <forward tokens|0>

``probe(...)`` is the tests' side: it runs the above and returns the object.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def probe(synth_dir: str, preset: str, layers: int, quant="bf16", forward_tokens: int = 0) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), synth_dir, preset, str(layers),
         quant, str(forward_tokens)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    return json.loads(r.stdout.strip().splitlines()[-1])


def main(argv) -> None:
    synth_dir, preset, layers, quant, forward_tokens = argv
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:  # as tests/conftest.py
        os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

    import dataclasses
    import resource

    import jax
    import jax.numpy as jnp
    import psutil

    from rag_llm_k8s_tpu.core import MeshConfig
    from rag_llm_k8s_tpu.core.config import DTypePolicy, LlamaConfig
    from rag_llm_k8s_tpu.core.mesh import make_mesh
    from rag_llm_k8s_tpu.models.llama import LlamaModel, make_kv_cache
    from rag_llm_k8s_tpu.models.loader import load_safetensors_params
    from rag_llm_k8s_tpu.parallel.sharding import make_streaming_put

    cfg = dataclasses.replace(getattr(LlamaConfig, preset)(), num_layers=int(layers))
    mesh = make_mesh(MeshConfig(dp=1, sp=1, tp=8), devices=jax.devices()[:8])

    def high_water() -> int:
        """This interpreter's own peak RSS. ``VmHWM`` belongs to the address
        space exec made; ``ru_maxrss`` does not do: it starts at the SPAWNING
        process's peak (Linux keeps it across fork and exec), so after a
        pytest worker that once held more than the load does, it never moved."""
        with open("/proc/self/status", encoding="ascii") as f:
            for line in f:
                if line.startswith("VmHWM:"):
                    return int(line.split()[1]) * 1024
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024

    proc = psutil.Process()
    peak_before = high_water()
    put = make_streaming_put(mesh, dtype=jnp.bfloat16)
    params = load_safetensors_params(synth_dir, cfg, DTypePolicy(), put=put, quant=quant)
    rss_after = proc.memory_info().rss
    peak = high_water()

    leaves = {}
    for path, x in jax.tree_util.tree_flatten_with_path(params)[0]:
        leaves["/".join(str(getattr(k, "key", k)) for k in path)] = {
            "shape": list(x.shape),
            "dtype": str(x.dtype),
            "spec": str(x.sharding.spec),
            "nbytes": int(x.nbytes),
            "shard0_nbytes": int(x.addressable_shards[0].data.nbytes),
        }
    out = {"leaves": leaves, "peak_before": peak_before, "rss_after": rss_after, "peak": peak}

    S = int(forward_tokens)
    if S:
        model = LlamaModel(cfg, DTypePolicy(), attn_impl="xla", quantized=quant == "int8")
        cache = make_kv_cache(cfg, 1, S, jnp.bfloat16)
        logits, _ = model.apply(
            {"params": params},
            jnp.zeros((1, S), jnp.int32),
            jnp.broadcast_to(jnp.arange(S), (1, S)),
            cache,
            jnp.zeros((1,), jnp.int32),
            jnp.full((1,), S, jnp.int32),
            jnp.int32(0),
        )
        out["logits_shape"] = list(logits.shape)
        out["logits_finite"] = bool(jnp.all(jnp.isfinite(logits)))
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1:])
