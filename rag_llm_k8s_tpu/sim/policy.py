"""The continuous scheduler's decision core, as pure functions.

Every *decision* the continuous engine makes that is not device work —
block-allocation arithmetic, admission verdicts and headroom, prefill
grouping, sync-window growth planning, mixed-window budget splits,
preemption victim ordering, resubmit folding — lives here, and
``engine/continuous.py`` (plus ``engine/kv_pool.py``) delegates to these
functions on the live path. That seam is what makes the journal-replay
harness honest: ``sim/replay.py`` re-drives a recorded trace and
``sim/simulator.py`` steps a virtual engine through the SAME arithmetic,
so a simulated admission or preemption is the one the real scheduler
would have made, not a parallel reimplementation that drifts.

Import discipline: stdlib-only, no package-internal imports — this file
is loaded by path on hosts with no jax (flightview, capacity-planning
scripts); ragcheck's SIM-PURITY rule pins it. Sibling sim modules load
each other through ``load_sibling`` for the same reason.
"""

from __future__ import annotations

import importlib.util
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple


def load_sibling(name: str):
    """Load a sibling module of this package by FILE PATH (no package
    import, so no package ``__init__`` side effects and no jax) —
    ``load_sibling("replay")`` works on a bare-stdlib host. Relative
    paths reach outside the package too: ``load_sibling("../obs/goodput")``
    is how the simulator prices windows with the ledger's roofline."""
    here = os.path.dirname(os.path.abspath(__file__))
    path = os.path.normpath(os.path.join(here, name + ".py"))
    modname = "_rag_sim_" + os.path.basename(name)
    spec = importlib.util.spec_from_file_location(modname, path)
    if spec is None or spec.loader is None:  # pragma: no cover - bad path
        raise ImportError(f"cannot load sibling module {name!r} from {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ----------------------------------------------------------------------
# block arithmetic (mirrors engine/kv_pool.py, which delegates here)
# ----------------------------------------------------------------------

def blocks_for(tokens: int, block_size: int) -> int:
    """Blocks covering ``tokens`` KV positions (ceil; 0 for 0)."""
    return max(0, -(-int(tokens) // int(block_size)))


def admission_blocks(prompt_len: int, block_size: int) -> int:
    """Admission-time block cost of a prompt (an empty prompt still
    admits one BOS-like token, hence the floor at 1)."""
    return blocks_for(max(int(prompt_len), 1), block_size)


def window_blocks(kv_ub: int, horizon: int, block_size: int,
                  max_blocks_per_row: int) -> int:
    """Total blocks a row must have mapped before a window that writes
    ``horizon`` new positions past ``kv_ub`` — capped at the row's table
    size (the executable clamps ``kv_ub`` the same way)."""
    return min(blocks_for(int(kv_ub) + int(horizon), block_size),
               int(max_blocks_per_row))


# ----------------------------------------------------------------------
# admission
# ----------------------------------------------------------------------

def admission_verdict(
    need: int, usable: int, interleave_on: bool, max_blocks_per_row: int
) -> Tuple[str, int]:
    """The pool-pressure admission decision, minus the stateful reclaim
    loop: returns ``("never", 0)`` when the prompt alone outsizes the
    whole pool, ``("ok", 0)`` when incremental (interleaved) admission
    needs no up-front reservation, else ``("check", want)`` — the caller
    must find ``want`` allocatable blocks (reclaiming re-buildable
    registrations if it has any). ``want`` carries the +1 headroom so the
    first decode window can open the next block without instantly
    preempting what admission just placed, capped at the row table size
    (a prompt that exactly fills a row needs no headroom at all)."""
    if need > usable:
        return "never", 0
    if interleave_on:
        return "ok", 0
    return "check", min(int(need) + 1, int(max_blocks_per_row))


def bucket_len(n: int, buckets: Sequence[int]) -> int:
    """Smallest bucket >= n, clamping to the largest (the engine's
    prompt-shape ladder; mirrors utils/buckets.py, restated here so the
    decision core stays importable with zero package imports)."""
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def clamp_max_new(max_new: int, bucket: int, max_seq_len: int) -> int:
    """Clamp a request's budget to the cache room past its bucket — the
    prompt is never cut to make room for generation."""
    return max(1, min(int(max_new), int(max_seq_len) - int(bucket)))


def admission_chunks(
    bucketed: Sequence[Tuple[int, int]], max_batch: int
) -> List[Tuple[int, List[int]]]:
    """Group prepared admissions into prefill chunks: same-bucket
    requests batch together (one forward each chunk), chunk sizes stay
    powers of two so the executable ladder needs no fresh warmups, and
    both bucket order and in-bucket order preserve arrival order.
    ``bucketed`` is ``(item_index, bucket)`` per request; returns
    ``(bucket, [item_index, ...])`` chunks in execution order."""
    by_bucket: Dict[int, List[int]] = {}
    for idx, s in bucketed:
        by_bucket.setdefault(int(s), []).append(idx)
    chunks: List[Tuple[int, List[int]]] = []
    for s, group in by_bucket.items():
        pos = 0
        while pos < len(group):
            n = 1
            while n * 2 <= min(len(group) - pos, int(max_batch)):
                n *= 2
            chunks.append((s, group[pos:pos + n]))
            pos += n
    return chunks


# ----------------------------------------------------------------------
# sync-window growth + preemption ordering
# ----------------------------------------------------------------------

def grow_shortfall(
    rows: Iterable[Tuple[int, int, int, int]],  # (admit_seq, row, kv_ub, have)
    default_horizon: int,
    horizon: Optional[Dict[int, int]],
    block_size: int,
    max_blocks_per_row: int,
) -> List[Tuple[int, int, int, int]]:
    """Which active rows must grow their block tables before the next
    window, ordered oldest-admission-first (the growth priority the
    preemption discipline inverts): ``(admit_seq, row, missing, have)``.
    ``horizon`` overrides the per-row token horizon (speculative verify
    windows write ragged lengths); rows absent from an explicit map
    default to 1 — they still advance their frontier by the correction
    token."""
    short: List[Tuple[int, int, int, int]] = []
    for admit_seq, row, kv_ub, have in rows:
        h = default_horizon if horizon is None else horizon.get(row, 1)
        need_total = window_blocks(kv_ub, h, block_size, max_blocks_per_row)
        if need_total > have:
            short.append((admit_seq, row, need_total - have, have))
    short.sort()
    return short


def reclaim_registration(
    prefix_keys: Iterable, tier_of: Dict, gen_of: Dict
):
    """Growth-pressure registration victim: the least valuable prefix
    registration — non-hot before hot (a warm chunk costs one re-scatter
    to bring back, a hot one a proven-shared re-stage), oldest
    registration generation first within a tier."""
    keys = list(prefix_keys)
    if not keys:
        return None
    return min(keys, key=lambda k: (tier_of.get(k, "hot") == "hot",
                                    gen_of.get(k, 0)))


def preempt_victim(
    active: Iterable[Tuple[int, int]]  # (admit_seq, row)
) -> Tuple[int, int]:
    """Pool-exhaustion preemption victim: the NEWEST-admitted active row
    (vLLM-style recompute preemption — its emitted tokens go back to the
    scheduler, which resubmits once blocks free). Returns the winning
    ``(admit_seq, row)``."""
    victims = sorted(active)
    return victims[-1]


# ----------------------------------------------------------------------
# mixed (unified ragged) window planning
# ----------------------------------------------------------------------

def plan_mixed_window(
    admissions: Sequence[Tuple[int, int, int]],  # (rid, prompt_len, progress)
    window_budget: int,
    n_decode: int,
    chunk_tokens: int,
) -> List[Tuple[int, int, int, bool]]:
    """Budget split for one unified ragged window: active decode lanes
    cost one token each; the remainder slices pending admissions FIFO
    (oldest first — the request closest to its first token wins the
    leftover), at most ``chunk_tokens`` per admission per window.
    Returns ``(rid, offset, take, final)`` slices in schedule order; the
    caller allocates each slice's blocks and stops at the first slice
    the pool cannot stage (pool pressure idles the YOUNGER admissions
    for the window — later slices are exactly the ones dropped)."""
    remaining = max(0, int(window_budget) - int(n_decode))
    sched: List[Tuple[int, int, int, bool]] = []
    for rid, prompt_len, progress in admissions:
        if remaining <= 0:
            break
        left = int(prompt_len) - int(progress)
        take = min(int(chunk_tokens), remaining, left)
        if take <= 0:
            continue
        final = progress + take >= prompt_len
        sched.append((rid, int(progress), take, final))
        remaining -= take
    return sched


# ----------------------------------------------------------------------
# resubmission (reset recovery / pool-preemption resume)
# ----------------------------------------------------------------------

def resume_fits(prompt_len: int, n_emitted: int, max_bucket: int) -> bool:
    """Whether a preempted/reset request may resume from prompt+emitted:
    past the largest bucket, admission would left-truncate the context
    and the 'seamless continuation' would be conditioned on a different
    prompt — restarting from scratch is exact, resuming is not."""
    return n_emitted > 0 and prompt_len + n_emitted <= max_bucket
