"""The main path's Pallas kernels compile for a TPU v5e at Llama-3.1-8B widths.

No chip is attached: the TPU compiler installed here compiles for a
DESCRIBED ``v5e:2x2`` topology, which refuses what Mosaic would refuse on the
machine — a slice not aligned to the tiling, a kernel over its VMEM or SMEM
budget, a kernel that cannot be partitioned. Interpret mode shows none of
that. Nothing runs, so these say nothing about results or times
(``chip_smoke.py`` phase 2 is where the same kernels meet their oracles).

Everything that touches the topology happens inside fixtures and tests,
never at import: only one process may hold libtpu, and every xdist worker
imports this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from rag_llm_k8s_tpu.ops import attention as A
from rag_llm_k8s_tpu.ops.knn import knn_topk_pallas

# Llama-3.1-8B: 32 query heads over 8 KV heads of 128, 32 layers; slots of
# 4352 tokens (the 4096 bucket + 256), so 272 blocks of 16 / 136 of 32 a row
H, K, HD, L, T = 32, 8, 128, 32, 4352
BF16, I8, F32, I32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or it logs under /tmp
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu from describing it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def uncached():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip (the next one warns and compiles
    again): keep the cache out of these compiles."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _arena(q8: bool, n_blocks: int, bs: int):
    payload = ((L, n_blocks, K, bs, HD), I8 if q8 else BF16)
    if not q8:
        return [payload, payload]
    scale = ((L, n_blocks, K, bs), F32)
    return [payload, payload, scale, scale]


def _paged(kernel, q8: bool, B: int, chunk: int = 0):
    """(fn, [(shape, dtype), ...]) for one paged kernel. The arena is what
    one chip's HBM leaves for it next to 8B weights (eight full rows plus
    the null block), whatever the batch: rows share the pool."""
    bs = 32 if q8 else 16
    mb = T // bs
    args = [((B, chunk or 1, H, HD), BF16), *_arena(q8, 8 * mb + 1, bs),
            ((B, mb), I32), ((B,), I32), ((), I32)]
    if chunk:
        args.append(((B,), I32))  # per-row write_index
    return kernel, args


def _dense_cache(q8: bool, B: int):
    payload = ((L, B, K, T, HD), I8 if q8 else BF16)
    if not q8:
        return [payload, payload]
    scale = ((L, B, K, T), F32)
    return [payload, payload, scale, scale]


def _flash(S: int, heads: int, kv_heads: int, hd: int, B: int, causal: bool):
    def fn(q, k, v, kv_len):
        return A.flash_attention(q, k, v, kv_len=kv_len, causal=causal)

    qkv = [((B, S, h, hd), BF16) for h in (heads, kv_heads, kv_heads)]
    return fn, [*qkv, ((B,), I32)]


def _rerotate(dtype):
    """``rope_rerotate`` over one cached segment's K plane. fp32 is here on
    purpose: slicing and concatenating the halves aborted the TPU compiler
    for fp32 at hd >= 64 (found on the chip, PR 21)."""
    inv = ((HD // 2,), F32)
    return A.rope_rerotate, [((L, 1, K, 256, HD), dtype), ((), I32), inv]


CASES = {
    "rope_rerotate[bf16]": _rerotate(BF16),
    "rope_rerotate[fp32]": _rerotate(F32),
    "rope_rerotate_q8": (
        A.rope_rerotate_q8,
        [((L, 1, K, 256, HD), I8), ((L, 1, K, 256), F32), ((), I32), ((HD // 2,), F32)],
    ),
    **{
        f"{name}[B={B}]": _paged(getattr(A, name), "q8" in name, B, chunk)
        for name, chunk in (
            ("paged_decode_attention", 0),
            ("paged_decode_attention_q8", 0),
            ("paged_chunk_attention", 256),
            ("paged_chunk_attention_q8", 256),
        )
        for B in (8, 64)
    },
    "flash_attention[4096]": _flash(4096, H, K, HD, 1, True),
    "flash_attention[encoder hd=64]": _flash(1536, 16, 16, 64, 8, False),
    # the encoder's other ingest buckets (a row of 1024 keys is ONE step,
    # as the 1536 are), and a prompt whose K/V strips do not fit VMEM (the
    # key blocks are streamed)
    "flash_attention[encoder S=1024]": _flash(1024, 16, 16, 64, 32, False),
    "flash_attention[encoder S=2048]": _flash(2048, 16, 16, 64, 32, False),
    "flash_attention[16384 streamed]": _flash(16384, H, K, HD, 1, True),
    "decode_attention": (
        A.decode_attention,
        [((8, 1, H, HD), BF16), *_dense_cache(False, 8), ((8,), I32), ((8,), I32), ((), I32)],
    ),
    "decode_attention_q8": (
        A.decode_attention_q8,
        [((8, 1, H, HD), BF16), *_dense_cache(True, 8), ((8,), I32), ((8,), I32), ((), I32)],
    ),
    # Mistral-Nemo under tp=4: what ONE device's kernel sees inside the
    # shard_map (2 of 8 KV heads, 4 rows, 40 layers): a step of 1024 slots
    "decode_attention[tp4 local heads]": (
        A.decode_attention,
        [((4, 1, 8, HD), BF16), ((40, 4, 2, T, HD), BF16), ((40, 4, 2, T, HD), BF16),
         ((4,), I32), ((4,), I32), ((), I32)],
    ),
    "chunk_prefill_attention": (
        A.chunk_prefill_attention,
        [((1, 512, H, HD), BF16), *_dense_cache(False, 1),
         ((1,), I32), ((1,), I32), ((), I32), ((), I32)],
    ),
    "chunk_prefill_attention_q8": (
        A.chunk_prefill_attention_q8,
        [((1, 512, H, HD), BF16), *_dense_cache(True, 1),
         ((1,), I32), ((1,), I32), ((), I32), ((), I32)],
    ),
    # the speculative verify step of the benchmark's solo cell: 16 draft
    # positions of one row, so G*S = 64 query rows a KV head ([K, 64, 1] scratch)
    "chunk_attention_grouped[S=16]": (
        A.chunk_attention_grouped,
        [((1, 16, H, HD), BF16), *_dense_cache(False, 1),
         ((1,), I32), ((1,), I32), ((), I32), ((), I32)],
    ),
    "chunk_attention_grouped_q8[S=16]": (
        A.chunk_attention_grouped_q8,
        [((1, 16, H, HD), BF16), *_dense_cache(True, 1),
         ((1,), I32), ((1,), I32), ((), I32), ((), I32)],
    ),
    "knn_topk_pallas": (
        lambda q, emb, norms: knn_topk_pallas(q, emb, norms, k=5),
        [((8, 1024), F32), ((131072, 1024), F32), ((1, 131072), F32)],
    ),
}


def _latent(fn_name: str, args, **static):
    import functools

    from rag_llm_k8s_tpu.ops import mla, moe

    fn = getattr(mla, fn_name, None) or getattr(moe, fn_name)
    return functools.partial(fn, **static), args


# the latent-attention sparse-expert family at its published widths: 128
# heads of 128 nope + 64 rope keys against 128-wide values, a latent cache of
# rank 512 + 64, 16 held experts of 7168 x 2048 stacked over 4 layers
CASES.update({
    "mla_flash_attention[4096]": _latent(
        "mla_flash_attention",
        [((1, 4096, 128, 192), BF16), ((1, 4096, 128, 192), BF16), ((1, 4096, 128, 128), BF16),
         ((1,), I32), ((1,), I32)], scale=0.1),
    "mla_decode_attention[B=8]": _latent(
        "mla_decode_attention",
        [((8, 1, 128, 512), BF16), ((8, 1, 128, 64), BF16), ((5, 8, T, 512), BF16),
         ((5, 8, T, 64), BF16), ((8,), I32), ((8,), I32), ((), I32)], scale=0.1),
    # the shortcut-connected configuration: 64 heads over 8 cache planes
    "mla_decode_attention[B=8, 64 heads]": _latent(
        "mla_decode_attention",
        [((8, 1, 64, 512), BF16), ((8, 1, 64, 64), BF16), ((8, 8, T, 512), BF16),
         ((8, 8, T, 64), BF16), ((8,), I32), ((8,), I32), ((), I32)], scale=0.1),
    "grouped_matmul[prefill up]": _latent(
        "grouped_matmul",
        [((32768, 7168), BF16), ((4, 16, 7168, 2048), BF16), ((16,), I32), ((), I32)]),
    "grouped_matmul[prefill down]": _latent(
        "grouped_matmul",
        [((32768, 2048), BF16), ((4, 16, 2048, 7168), BF16), ((16,), I32), ((), I32)]),
    "grouped_matmul[decode up]": _latent(
        "grouped_matmul",
        [((128, 7168), BF16), ((4, 16, 7168, 2048), BF16), ((16,), I32), ((), I32)]),
})


def _flash_window(heads: int):
    def fn(q, k, v, kv_start, kv_len):
        return A.flash_attention(q, k, v, kv_start, kv_len, window=512)

    qkv = [((1, 4096, h, HD), BF16) for h in (heads, K, K)]
    return fn, [*qkv, ((1,), I32), ((1,), I32)]


# the windowed-attention sparse-expert family at its published widths: 72
# query heads (sliding layers, window 512) and 48 (full layers) over 8 KV heads
# of 128, group sizes 9 and 6 (no power of two: 576 / 768 folded rows a step,
# 9 / 6 query rows a KV head in the decode kernel's [K, G, hd] block), 17
# planes; 16 held experts of 3072 x 1024 stacked over 16 layers
CASES.update({
    "flash_attention_window[72 heads]": _flash_window(72),
    "flash_attention_window[48 heads]": _flash_window(48),
    "flash_attention[72 heads]": _flash(4096, 72, K, HD, 1, True),
    "flash_attention[48 heads]": _flash(4096, 48, K, HD, 1, True),
    **{f"decode_attention[{heads} heads]": (
        A.decode_attention,
        [((8, 1, heads, HD), BF16), ((17, 8, K, T, HD), BF16), ((17, 8, K, T, HD), BF16),
         ((8,), I32), ((8,), I32), ((), I32)]) for heads in (72, 48)},
    "grouped_matmul[small experts, prefill up]": _latent(
        "grouped_matmul",
        [((40960, 3072), BF16), ((16, 16, 3072, 1024), BF16), ((16,), I32), ((), I32)]),
    "grouped_matmul[small experts, decode down]": _latent(
        "grouped_matmul",
        [((128, 1024), BF16), ((16, 16, 1024, 3072), BF16), ((16,), I32), ((), I32)]),
})


# every expert held (64 of 2048 x 1536, 8 sparse layers), one row's prefill:
# 16384 rows in groups of about 256, so ``grouped_blocks`` gives 256-row tiles
# and the whole k: a [2048, 512] / [1536, 1024] weight block, double buffered,
# inside the scoped VMEM limit as it stands; so are a decode step's 128 rows
# over it, and the largest tile the rule gives a whole k (32 held: groups of 512)
CASES.update({
    "grouped_matmul[all experts held, prefill up]": _latent(
        "grouped_matmul",
        [((16384, 2048), BF16), ((8, 64, 2048, 1536), BF16), ((64,), I32), ((), I32)]),
    "grouped_matmul[all experts held, prefill down]": _latent(
        "grouped_matmul",
        [((16384, 1536), BF16), ((8, 64, 1536, 2048), BF16), ((64,), I32), ((), I32)]),
    "grouped_matmul[all experts held, decode up]": _latent(
        "grouped_matmul",
        [((128, 2048), BF16), ((8, 64, 2048, 1536), BF16), ((64,), I32), ((), I32)]),
    "grouped_matmul[all experts held, decode down]": _latent(
        "grouped_matmul",
        [((128, 1536), BF16), ((8, 64, 1536, 2048), BF16), ((64,), I32), ((), I32)]),
    "grouped_matmul[groups of 512, whole k, down]": _latent(
        "grouped_matmul",
        [((16384, 1536), BF16), ((8, 32, 1536, 2048), BF16), ((32,), I32), ((), I32)]),
})


def _combine(N: int, C: int, D: int):
    from rag_llm_k8s_tpu.ops import moe

    def fn(acc, y, weight, token, group):
        return moe.expert_combine(acc, y, weight, token, group, groups=16,
                                  blocks=moe.combine_blocks(N, C, D, 2))

    return fn, [((N, D), BF16), ((C, D), BF16), ((C,), F32), ((C,), I32), ((C,), I32)]


# the held experts' combine at the three sparse-expert cells' prefill shapes
# (batch 8 of a 4096 bucket; a pass's rows by ``rows_per_pass``), at the
# rule's tiles: 256 tokens x 1792 / 3072 / 3072 columns a grid cell
CASES.update({
    "expert_combine[32768 rows, 7168]": _combine(32768, 32768, 7168),
    "expert_combine[16384 rows, 6144]": _combine(32768, 16384, 6144),
    "expert_combine[40960 rows, 3072]": _combine(32768, 40960, 3072),
})


def _route(E: int, N: int = 32768, **rule):
    from rag_llm_k8s_tpu.ops import moe

    def fn(logits, bias):
        return moe.route(logits, bias, scaling=2.5, **rule, impl="pallas")

    return fn, [((N, E), F32), ((E,), F32)]


# the router at the three sparse-expert cells' prefill shape (batch 8 of a
# 4096 bucket): group-limited sigmoid, softmax over 768 outputs, plain
# sigmoid; and the smallest shape the rule gives the kernel (one row of a
# 1024 bucket). A decode step's 8 tokens keep the jnp body: no kernel to lower
CASES.update({
    "route[256, top-8 of 4 of 8 groups]": _route(256, top_k=8, n_group=8, topk_group=4),
    "route[768, top-12, softmax]": _route(768, top_k=12, n_group=1, topk_group=1, normalize=False, scoring="softmax"),
    "route[256, top-10]": _route(256, top_k=10, n_group=1, topk_group=1),
    "route[256, top-10, 1024 tokens]": _route(256, 1024, top_k=10, n_group=1, topk_group=1),
})


def _pool(shape):
    from rag_llm_k8s_tpu.ops import block_window as bw

    def fn(k, v, mu, phi):
        return bw.pool_chunks(k, v, mu, phi, 16, "pallas")

    return fn, [(shape, BF16), (shape, BF16), ((32, HD), BF16), ((32, HD), BF16)]


def _pool_in_place():
    from rag_llm_k8s_tpu.ops import block_window as bw

    def fn(k_plane, v_plane, mu, phi, layer, src, dst):
        return bw.pool_ring_chunks(k_plane, v_plane, mu, phi, layer, src, dst, 16, "pallas")

    plane = ((8, 8, 32, 1408 + 2048, HD), BF16)
    return fn, [plane, plane, ((32, HD), BF16), ((32, HD), BF16), ((), I32), ((8,), I32), ((8,), I32)]


# the block-window family's pooling at the byte-model cell's shapes: a prompt
# row of 20480 positions (32 heads, 1280 chunks of 16 each), a scorer's 2032
# positions over a batch of 8, and a decode step's eight rows in the planes
CASES.update({
    "chunk_pool[a prompt row]": _pool((32, 20480, HD)),
    "chunk_pool[a chunk call, 128 chunks a row]": _pool((8, 32, 2048, HD)),
    "chunk_pool_in_place[batch 8]": _pool_in_place(),
})


def _window_summary(S, window, dtype):
    from rag_llm_k8s_tpu.ops import block_window as bw

    def fn(q, k, v, sk, sv, live):
        return bw.window_summary_flash_attention(q, k, v, sk, sv, live, window=window, chunk=16)

    row, pooled = ((32, S, HD), dtype), ((32, S // 16, HD), dtype)
    return fn, [row, row, row, pooled, pooled, ((), I32)]


# the block-window family's prefill kernel at the byte-model cell's shape (one
# prompt row of 20480 positions, 32 heads, windows of 2048, chunks of 16): a
# query block's window as ONE slice of 512 x 2048 scores beside the strips and
# 1536 summaries (``window_summary_plan``: a scoped-VMEM refusal shows here,
# not on the chip); and the walk over key blocks, which the same rule keeps
# for a window of 8192 and for float32 operands
CASES.update({
    "window_summary[a prompt row: the window in one step]": _window_summary(20480, 2048, BF16),
    "window_summary[a window of 8192: the walk]": _window_summary(16384, 8192, BF16),
    "window_summary[float32: the walk]": _window_summary(20480, 2048, F32),
})


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, uncached):
    fn, args = CASES[name]
    avals = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in args]
    text = jax.jit(fn).lower(*avals).compile().as_text()
    if not name.startswith("rope_"):  # plain XLA ops, no Pallas
        assert "tpu_custom_call" in text, f"{name}: no Mosaic kernel"
    if "flash_attention" in name:
        # the benchmark's roofline readers find the prefill kernels by name
        # and by a rank-3 result [B*H, S, value width]; the scoped VMEM limit
        # is the default's (a kernel over it does not get this far)
        kernel = name.split("[")[0]
        heads, S, width = {"flash_attention[4096]": (H, 4096, HD), "mla_flash_attention[4096]": (128, 4096, 128),
                           "flash_attention[encoder hd=64]": (8 * 16, 1536, 64),
                           "flash_attention[encoder S=1024]": (32 * 16, 1024, 64),
                           "flash_attention[encoder S=2048]": (32 * 16, 2048, 64),
                           "flash_attention[16384 streamed]": (H, 16384, HD),
                           "flash_attention_window[72 heads]": (72, 4096, HD),
                           "flash_attention_window[48 heads]": (48, 4096, HD),
                           "flash_attention[72 heads]": (72, 4096, HD),
                           "flash_attention[48 heads]": (48, 4096, HD)}[name]
        assert re.search(rf"%{kernel}(\.\d+)? = bf16\[{heads},{S},{width}\]\S* custom-call\(", text), name
        assert '"scoped_memory_configs":[]' in text, f"{name}: asks for more than the default scoped VMEM"
    if name.startswith("route["):
        # the kernel by its name, inside the default scoped VMEM, and neither a sort
        # nor a gather left beside it
        assert "%route_topk" in text and '"scoped_memory_configs":[]' in text, name
        assert not re.search(r" (sort|gather)\(", text), name
    if name.startswith("expert_combine"):
        # in place (the tile read and written is the accumulator's), inside
        # the default scoped VMEM, and no sort in front of it (a sort of a
        # pass's keys takes this compiler 18 s a program)
        assert '"scoped_memory_configs":[]' in text, f"{name}: asks for more than the default scoped VMEM"
        assert "output_to_operand_aliasing" in text and not re.search(r" sort\(", text), name
    if name.startswith("window_summary"):
        # the roofline's reader finds the kernel by its name and a result [heads,
        # bucket, head_dim], whatever form runs; inside the default scoped VMEM
        from rag_llm_k8s_tpu.ops import block_window as bw

        (_, S, _), dtype = args[0]
        window = 8192 if "8192" in name else 2048
        assert bw.window_summary_plan(S, window, 16, HD, jnp.dtype(dtype).itemsize)[2] == ("one step" in name), name
        kind = "bf16" if dtype == BF16 else "f32"
        assert re.search(rf"%window_summary_flash_attention(\.\d+)? = {kind}\[32,{S},{HD}\]\S* custom-call\(", text), name
        assert '"scoped_memory_configs":[]' in text, f"{name}: asks for more than the default scoped VMEM"
    if name.startswith("chunk_pool"):
        # by name, inside the default scoped VMEM, no float32 of the operands'
        # size beside it; the in-place form's planes alias its results
        assert f"%{name.split('[')[0]}" in text and '"scoped_memory_configs":[]' in text, name
        assert not re.search(r"f32\[(8,)?32,\d{4,},128\]", text), f"{name}: a float32 copy of the keys"
        assert ("output_to_operand_aliasing" in text) == ("in_place" in name), name
    if re.match(r"(mla_)?decode_attention", name):
        # the benchmark finds the decode kernels by name and by result shape
        # (``mla_decode_attention_roofline``: [rows, heads, rank]; the phases'
        # ``decode_attention_q8 [8,8,4,128]``): a walk of the kernel's own
        # copies must leave both as they were, inside the default scoped VMEM
        shape = {"decode_attention": "8,8,4,128", "decode_attention_q8": "8,8,4,128",
                 "decode_attention[tp4 local heads]": "4,2,4,128",
                 "mla_decode_attention[B=8]": "8,128,512",
                 "mla_decode_attention[B=8, 64 heads]": "8,64,512",
                 "decode_attention[72 heads]": "8,8,9,128", "decode_attention[48 heads]": "8,8,6,128"}[name]
        assert re.search(rf"%{name.split('[')[0]}(\.\d+)? = bf16\[{shape}\]\S* custom-call\(", text), name
        assert '"scoped_memory_configs":[]' in text, f"{name}: asks for more than the default scoped VMEM"


def test_sharded_paged_decode_compiles_for_four_chips(topo, uncached):
    """The serving layout at tp=4: the paged decode kernel ``shard_map``'d
    over a 4-device mesh with ``paged_partition_specs`` — each device runs
    the kernel on its 8 query / 2 KV heads of every block."""
    mesh = Mesh(list(topo.devices), ("tp",))
    in_specs, out_spec = A.paged_partition_specs("decode", q8=False)
    fn = jax.shard_map(
        A.paged_decode_attention, mesh=mesh, in_specs=in_specs,
        out_specs=out_spec, check_vma=False,
    )
    _, args = _paged(A.paged_decode_attention, False, 8)
    args[-1] = ((1,), I32)  # the model hands the layer index as a [1] vector
    avals = [
        jax.ShapeDtypeStruct(s, d, sharding=NamedSharding(mesh, spec))
        for (s, d), spec in zip(args, in_specs)
    ]
    compiled = jax.jit(fn).lower(*avals).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # heads are independent: no collective belongs in this program
    assert "all-reduce" not in text and "all-gather" not in text
    per_device = compiled.memory_analysis().argument_size_in_bytes
    arena_bytes = 2 * L * (8 * 272 + 1) * K * 16 * HD * 2
    assert per_device < arena_bytes / 4 * 1.05, (per_device, arena_bytes)


def test_sharded_grouped_chunk_compiles_for_four_chips(topo, uncached):
    """A speculative verify step at tp=4 (a round the admission race left one
    caller alone in): the grouped chunk kernel ``shard_map``'d over the tp
    axis as ``LlamaModel._attend`` does it — each device its 8 query / 2 KV
    heads of the dense bf16 cache, 64 query rows a KV head."""
    from jax.sharding import PartitionSpec as P

    mesh = Mesh(list(topo.devices), ("tp",))
    heads, cache = P(None, None, "tp", None), P(None, None, "tp", None, None)
    in_specs = (heads, cache, cache, P(None), P(None), P(None), P(None))
    fn = jax.shard_map(
        A.chunk_attention_grouped, mesh=mesh, in_specs=in_specs,
        out_specs=heads, check_vma=False,
    )
    args = [((1, 16, H, HD), BF16), *_dense_cache(False, 1),
            ((1,), I32), ((1,), I32), ((1,), I32), ((1,), I32)]
    avals = [
        jax.ShapeDtypeStruct(s, d, sharding=NamedSharding(mesh, spec))
        for (s, d), spec in zip(args, in_specs)
    ]
    text = jax.jit(fn).lower(*avals).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" not in text and "all-gather" not in text


def test_latent_moe_generate_program_compiles_with_its_kernels(one_chip, uncached):
    """The second decoder family's batched generate program, at toy widths,
    through the Pallas path: the flash prefill over expanded latents, the
    absorbed decode kernel over the latent cache, and the grouped expert
    matmul all lower for the chip inside one program."""
    from rag_llm_k8s_tpu.core.config import (
        DTypePolicy, EngineConfig, GoodputConfig, LatentMoEConfig, SamplingConfig,
    )
    from rag_llm_k8s_tpu.engine import engine as engine_mod
    from rag_llm_k8s_tpu.models.latent_moe import init_latent_moe_params
    cfg = LatentMoEConfig.tiny(
        vocab_size=512, hidden_size=256, intermediate_size=512, moe_intermediate_size=128,
        num_heads=4, q_lora_rank=128, kv_lora_rank=128, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, max_seq_len=1024)
    dt = DTypePolicy()
    shapes = jax.eval_shape(lambda: init_latent_moe_params(jax.random.PRNGKey(0), cfg, dt))
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), shapes)
    ec = EngineConfig(prompt_buckets=(256,), max_seq_len=512, attn_impl="pallas", speculative="off",
                      goodput=GoodputConfig(enabled=False))
    eng = engine_mod.InferenceEngine(
        cfg, params, sampling=SamplingConfig(do_sample=False, max_new_tokens=8),
        engine_config=ec, dtypes=dt)
    fn = eng._make_gen(2, 256, 8)
    tok = jax.ShapeDtypeStruct((2, 256), I32, sharding=one_chip)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    text = jax.jit(fn).lower(params, tok, tok, rng).compile().as_text()
    for kernel in ("mla_flash_attention", "mla_decode_attention", "grouped_matmul"):
        assert kernel in text, f"{kernel}: not in the compiled program"


def test_hybrid_live_suffix_branches_copy_neither_stream_nor_stacked_weight(one_chip, uncached):
    """The batch-8 prefill of the hybrid state-space family at the 4096
    bucket, every width and all 28 layers as published (a small vocabulary):
    a trip's matmuls and norms run in the rung's branch (``models/hybrid_ssm.py
    live_trip``), which reads its weights from the STACKED leaves at the
    trip's index and writes its rows of the residual stream in place; the
    scan kernel stands outside the rungs, once a program, at the bucket's
    shape. A copy of the stream ``bf16[8,4096,2560]`` in a branch (a four-way
    ``lax.switch`` compiles to one in its third branch, twice a trip: PERF.md,
    PR 41; the two rungs of ``live_rungs`` are one ``lax.cond``), or of a stacked
    SwiGLU or mixer kernel anywhere but the entry computation, fails here. The
    depth is the published one because the compiler's copies depend on it (a
    loop of two trips is unrolled, and compiles to other copies)."""
    from rag_llm_k8s_tpu.core.config import (
        DTypePolicy, EngineConfig, GoodputConfig, HybridSSMConfig, PrefixCacheConfig, SamplingConfig,
    )
    from rag_llm_k8s_tpu.engine import engine as engine_mod
    from rag_llm_k8s_tpu.models.hybrid_ssm import init_hybrid_ssm_params

    cfg = HybridSSMConfig(vocab_size=1024, tie_word_embeddings=False)
    dt = DTypePolicy()
    shapes = jax.eval_shape(lambda: init_hybrid_ssm_params(jax.random.PRNGKey(0), cfg, dt))
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), shapes)
    ec = EngineConfig(prompt_buckets=(4096,), max_seq_len=4096 + 256, attn_impl="pallas", speculative="off",
                      goodput=GoodputConfig(enabled=False), prefix_cache=PrefixCacheConfig(enabled=False))
    eng = engine_mod.InferenceEngine(
        cfg, params, sampling=SamplingConfig(do_sample=False, max_new_tokens=2), engine_config=ec, dtypes=dt)
    tok = jax.ShapeDtypeStruct((8, 4096), I32, sharding=one_chip)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    text = jax.jit(eng._make_gen(8, 4096, 2)).lower(params, tok, tok, rng).compile().as_text()
    assert " conditional(" in text
    assert "bf16[8,4096,40,128]" in text and "bf16[8,3072,40,128]" not in text  # the scan kernel: once, the bucket
    assert "bf16[8,3072,8192]" in text  # a SwiGLU on the suffix behind a quarter of the bucket
    L, M = cfg.num_layers, cfg.num_state_layers
    unwanted = re.compile(rf"= bf16\[(8,4096,2560|{L},\d+,\d+|{M},(2560|5120),\d+)\]\S* copy\(")
    for computation in text.split("\n\n"):
        head = computation.lstrip()
        if head.startswith(("ENTRY", "%fused", "fused")):  # once a call, as before / inside a fusion: no pass of its own
            continue
        found = [line.strip()[:160] for line in computation.splitlines() if unwanted.search(line)]
        assert not found, found
    # What ``benchmark/lib/phases.py`` counts a prefill's rows by: the median
    # executions of the instructions whose scope path holds one loop and no
    # branch. The compiler files what it puts in a branch for the branch's
    # operands (a relayout of a small stacked leaf sliced there) under the
    # OPERAND's path, which holds no branch: two of those beside the two
    # state writes read 23.1 rows for 24 on the chip (PERF.md, PR 41). So a
    # trip's small leaves are sliced in the loop's body, behind a barrier, and
    # the body's such instructions outnumber every other computation's.
    from benchmark.lib.phases import one_loop_beneath

    counted = {}
    for computation in text.split("\n\n"):
        lines = computation.strip().splitlines()
        if not lines or lines[0].lstrip().startswith(("%fused", "fused")):
            continue
        paths = (re.search(r'op_name="([^"]*)"', line) for line in lines[1:] if re.search(r" (fusion|copy)\(", line))
        units = [one_loop_beneath(m.group(1)) for m in paths if m]
        counted[lines[0].split(" ")[0]] = sum(1 for u in units if u and u[0] == "prefill")
    body, *others = sorted(counted.values(), reverse=True)
    assert body >= 8 and 4 * sum(others) <= body, counted


def test_block_window_live_blocks_loop_without_branch_or_copy(one_chip, uncached):
    """The block-window family's prefill at the served bucket and the
    published widths (two rows of three layers: the fewest at which the
    layers' loop stays rolled; +7 s of tier 1): a row's layer runs its norms,
    projections, rotation and FFN in two loops over the row's live blocks
    (``models/block_window.py LIVE_BLOCK``; trip counts read from ``kv_start``)
    around the two kernels. No branch anywhere; no copy of the stream
    ``f32[1,20480,4096]`` or of a row buffer ``bf16[32,20480,128]`` outside
    the entry computation (left to itself the compiler carries q's and k's
    buffers in the rotation's layout and re-lays all of them behind the loop,
    twice a layer-row: PERF.md, PR 42), none of a layer's kernel in an inner
    loop's body (once a block, not once a layer-row); and what
    ``benchmark/lib/phases.py`` counts a prefill's rows by, the instructions one
    loop beneath ``prefill``, are the layers' loop body's: the kernels, the
    planes' writes, the ring's slices and the weights' slices stayed there."""
    from rag_llm_k8s_tpu.core.config import (
        BlockWindowConfig, DTypePolicy, EngineConfig, GoodputConfig, SamplingConfig,
    )
    from rag_llm_k8s_tpu.engine import engine as engine_mod
    from rag_llm_k8s_tpu.models.block_window import init_block_window_params

    cfg = BlockWindowConfig(num_hidden_layers=3)
    dt = DTypePolicy()
    shapes = jax.eval_shape(lambda: init_block_window_params(jax.random.PRNGKey(0), cfg, dt))
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), shapes)
    ec = EngineConfig(prompt_buckets=(20480,), max_seq_len=20992, attn_impl="pallas", speculative="off",
                      goodput=GoodputConfig(enabled=False))
    eng = engine_mod.InferenceEngine(
        cfg, params, sampling=SamplingConfig(do_sample=False, max_new_tokens=2), engine_config=ec, dtypes=dt)
    tok = jax.ShapeDtypeStruct((2, 20480), I32, sharding=one_chip)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    text = jax.jit(eng._make_gen(2, 20480, 2)).lower(params, tok, tok, rng).compile().as_text()
    assert " conditional(" not in text
    from benchmark.lib.phases import one_loop_beneath

    row = re.compile(r"= (f32\[1,20480,4096\]|bf16\[32,20480,128\])\S* copy\(")
    kernel = re.compile(r"= bf16\[(4096,4096|4096,11008|11008,4096)\]\S* copy\(")
    computations, layer_bodies, counted = {}, [], {}
    for computation in text.split("\n\n"):
        lines = computation.strip().splitlines()
        if not lines or lines[0].lstrip().startswith(("%fused", "fused")):
            continue
        name = lines[0].lstrip().split(" ")[0]
        computations[name] = lines
        if any("%window_summary_flash_attention" in line and " custom-call(" in line for line in lines):
            layer_bodies.append(name)
        if not name.startswith("ENTRY"):
            found = [line.strip()[:160] for line in lines if row.search(line)]
            assert not found, found
        paths = (re.search(r'op_name="([^"]*)"', line) for line in lines[1:]
                 if re.search(r" (fusion|copy|custom-call)\(", line))
        units = [one_loop_beneath(m.group(1)) for m in paths if m]
        counted[name] = sum(1 for u in units if u and u[0] == "prefill")
    assert len(layer_bodies) == 2, layer_bodies  # a row each, three trips
    for body in layer_bodies:
        inner = re.findall(r"body=(%[\w.\-]+)", "\n".join(computations[body]))
        assert len(inner) == 2, inner  # in front of the kernels, and behind them
        for name in inner:
            lines = computations[name]
            assert any(" convolution(" in line or "convolution" in line for line in lines), name  # the matmuls are here
            found = [line.strip()[:160] for line in lines if kernel.search(line)]
            assert not found, found
    others = sum(n for name, n in counted.items() if name not in layer_bodies)
    assert all(counted[b] >= 8 and 4 * others <= counted[b] for b in layer_bodies), counted


@pytest.mark.parametrize("batch", [1, 8])
def test_live_suffix_branches_copy_no_stacked_weight(batch, one_chip, uncached):
    """The prefill of a bucket with rungs (``models/llama.py live_offsets``)
    at Mistral-7B's widths, int8, four layers: its branches read the MLP's
    weights from the STACKED tree at their layer. Asked the same of
    attention's projections the compiler re-laid the whole stack inside a
    branch, every layer (``s8[L,4096,4096] copy`` in the full branch: PR 28);
    they keep the scan's own slices. A copy of a stacked kernel anywhere but
    the entry computation (once a call, as before) fails here."""
    from rag_llm_k8s_tpu.core.config import (
        DTypePolicy, EngineConfig, GoodputConfig, LlamaConfig, SamplingConfig,
    )
    from rag_llm_k8s_tpu.engine import engine as engine_mod
    from rag_llm_k8s_tpu.models.llama import init_llama_params, quantize_llama_params

    layers = 4
    cfg = LlamaConfig(vocab_size=32768, hidden_size=4096, intermediate_size=14336, num_layers=layers,
                      num_heads=32, num_kv_heads=8, head_dim=128, max_seq_len=32768, rope_theta=1e6,
                      rope_scaling=None, tie_word_embeddings=False)
    dt = DTypePolicy()
    shapes = jax.eval_shape(
        lambda: quantize_llama_params(init_llama_params(jax.random.PRNGKey(0), cfg, dt)))
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), shapes)
    ec = EngineConfig(prompt_buckets=(4096,), max_seq_len=4352, attn_impl="pallas", speculative="off",
                      weight_quant="int8", kv_quant="int8", goodput=GoodputConfig(enabled=False))
    eng = engine_mod.InferenceEngine(
        cfg, params, sampling=SamplingConfig(do_sample=False, max_new_tokens=2),
        engine_config=ec, dtypes=dt)
    tok = jax.ShapeDtypeStruct((batch, 4096), I32, sharding=one_chip)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    text = jax.jit(eng._make_gen(batch, 4096, 2)).lower(params, tok, tok, rng).compile().as_text()
    assert "flash_attention" in text and " conditional(" in text
    stacked_copy = re.compile(rf"= s8\[{layers},\d+,\d+\]\S* copy\(")
    for computation in text.split("\n\n"):
        if not computation.lstrip().startswith("ENTRY"):
            found = [line.strip()[:160] for line in computation.splitlines() if stacked_copy.search(line)]
            assert not found, found
