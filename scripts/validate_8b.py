"""Full-depth 8B streaming-load validation (run on demand, not in CI).

Writes a zero-filled 4-shard safetensors checkpoint with EXACTLY the tensor
surface of Meta-Llama-3.1-8B-Instruct (~16 GB bf16, the layout
download_model.py stages into the PVC), streams it through
``load_safetensors_params`` + ``make_streaming_put`` onto an 8-virtual-device
dp2×tp4 CPU mesh, and reports transient host overhead versus checkpoint
size. Results are recorded in docs/8B.md.

Usage:  python scripts/validate_8b.py [--workdir DIR] [--keep]
"""

import argparse
import os
import resource
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

GB = 1 << 30


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--keep", action="store_true")
    args = ap.parse_args()

    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import psutil

    from rag_llm_k8s_tpu.core.compile_cache import ensure_compile_cache

    ensure_compile_cache()

    from rag_llm_k8s_tpu.core.config import DTypePolicy, LlamaConfig, MeshConfig
    from rag_llm_k8s_tpu.core.mesh import make_mesh
    from rag_llm_k8s_tpu.models.loader import load_safetensors_params
    from rag_llm_k8s_tpu.parallel.sharding import make_streaming_put
    from rag_llm_k8s_tpu.utils.synth import write_synth_checkpoint

    cfg = LlamaConfig.llama_3_1_8b()
    workdir = args.workdir or tempfile.mkdtemp(prefix="synth8b_")
    proc = psutil.Process()

    print(f"devices: {jax.devices()}")
    t0 = time.monotonic()
    paths = write_synth_checkpoint(workdir, cfg, n_shards=4)
    ckpt_bytes = sum(os.path.getsize(p) for p in paths)
    print(
        f"wrote {len(paths)} shards, {ckpt_bytes / GB:.2f} GB total "
        f"in {time.monotonic() - t0:.1f}s -> {workdir}"
    )

    ctx = make_mesh(MeshConfig(dp=2, sp=1, tp=4))
    print(f"mesh: {ctx.mesh}")
    put = make_streaming_put(ctx, dtype=jnp.bfloat16)

    rss_before = proc.memory_info().rss
    t0 = time.monotonic()
    params = load_safetensors_params(workdir, cfg, DTypePolicy(), put=put)
    load_s = time.monotonic() - t0
    rss_after = proc.memory_info().rss
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024

    placed = sum(x.nbytes for x in jax.tree.leaves(params))
    wq = params["layers"]["attn"]["wq"]["kernel"]
    per_dev = wq.addressable_shards[0].data.nbytes
    transient = peak - rss_after
    print(f"load time:            {load_s:.1f}s")
    print(f"placed param bytes:   {placed / GB:.2f} GB "
          f"({len(jax.tree.leaves(params))} tensors, stacked [32, ...])")
    print(f"wq kernel:            {wq.shape} {wq.dtype}, "
          f"per-device shard {per_dev / (1 << 20):.0f} MB (x8 devices)")
    print(f"rss before/after:     {rss_before / GB:.2f} / {rss_after / GB:.2f} GB "
          f"(placed params stay host-resident on the CPU mesh)")
    print(f"peak rss:             {peak / GB:.2f} GB")
    print(f"TRANSIENT overhead:   {transient / GB:.2f} GB "
          f"(vs {ckpt_bytes / GB:.2f} GB checkpoint)")
    ok = transient < 6 * GB
    print("RESULT:", "OK — streaming (transient << checkpoint)" if ok
          else "FAIL — loader materializes too much")

    if not args.keep:
        shutil.rmtree(workdir, ignore_errors=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
