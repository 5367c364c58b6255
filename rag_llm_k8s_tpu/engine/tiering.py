"""Hotness-aware KV tiering primitives (HA-RAG).

The prefix cache and block pool treat every cached chunk's KV identically:
all of it bf16 (or the engine's native kv dtype), all of it in HBM. That
caps the effective cache at whatever the HBM budget holds — fine for a demo
corpus, nowhere near the hot set of a million-user document base. HA-RAG
(PAPERS.md) closes the gap with hotness-driven mixed precision and data
placement; this module supplies the host-side primitives the cache layers
build tiering from:

- :class:`HotnessTracker` — an exponentially-decayed hit-frequency score
  per chunk key, fed by prefix-cache resolve hits, lookahead joins, and
  pool prestage registrations. The score is the ONE signal every tier
  decision reads: hot chunks stay in their native dtype, warm chunks
  quantize to int8 in place, cold chunks spill to host RAM.
- :class:`HostSpillStore` — a byte-budgeted host-RAM store of spilled
  chunk planes (numpy copies of the device arrays). A spilled chunk costs
  ZERO HBM and swaps back in with one ``device_put`` — orders of magnitude
  cheaper than re-prefilling it (swap-in is bandwidth; prefill is flops
  over every layer), and the swap-in rides the lookahead pipeline so it
  overlaps the previous request's decode instead of stalling admission.
- ``quantize_planes`` / ``dequantize_planes`` — the warm tier's in-place
  int8 conversion of a cached ``(k, v)`` plane pair (the same per-(token,
  kv-head) symmetric scales the ``_q8`` attention kernels dequantize at,
  via :func:`ops.attention.quantize_kv`), with NO re-prefill: the bytes
  halve, the dequant error is bounded at max|x|/254 per element, and the
  pinned-tolerance tests hold decoded streams to it.

Everything here is host bookkeeping plus tiny jit'd conversions; the tier
POLICY (when to demote, what a transition must preserve) lives with the
caches that own the entries (engine/prefix_cache.py, engine/continuous.py).
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from rag_llm_k8s_tpu.obs import flight

__all__ = [
    "TIERS",
    "HotnessTracker",
    "HostSpillStore",
    "quantize_planes",
    "dequantize_planes",
]

TIERS = ("hot", "warm", "cold")


class HotnessTracker:
    """Decayed hit-frequency per chunk key.

    ``touch(key, w)`` adds ``w`` to the key's score; scores decay
    exponentially with the configured half-life, evaluated lazily at read
    time (no decay thread — a score is ``raw * 2^(-age/half_life)``).
    Thread-safe; the clock is injectable so tests pin exact decay math.
    """

    def __init__(self, half_life_s: float = 60.0, clock=time.monotonic):
        if half_life_s <= 0:
            raise ValueError(f"half_life_s={half_life_s}: expected > 0")
        self.half_life_s = float(half_life_s)
        self._clock = clock
        self._lock = threading.Lock()
        self._scores: Dict[object, Tuple[float, float]] = {}  # key -> (raw, t)

    def _decayed(self, raw: float, t: float, now: float) -> float:
        return raw * 2.0 ** (-(now - t) / self.half_life_s)

    def touch(self, key, weight: float = 1.0) -> float:
        """Record a use; returns the key's new (decayed) score."""
        now = self._clock()
        with self._lock:
            raw, t = self._scores.get(key, (0.0, now))
            score = self._decayed(raw, t, now) + float(weight)
            self._scores[key] = (score, now)
            return score

    def score(self, key) -> float:
        now = self._clock()
        with self._lock:
            entry = self._scores.get(key)
            if entry is None:
                return 0.0
            return self._decayed(entry[0], entry[1], now)

    def forget(self, key) -> None:
        with self._lock:
            self._scores.pop(key, None)

    def prune(self, floor: float = 1e-3) -> int:
        """Drop keys whose decayed score fell under ``floor`` (the tracker
        must not grow with every chunk ever seen). Returns pruned count."""
        now = self._clock()
        with self._lock:
            dead = [
                k for k, (raw, t) in self._scores.items()
                if self._decayed(raw, t, now) < floor
            ]
            for k in dead:
                del self._scores[k]
            return len(dead)

    def __len__(self) -> int:
        with self._lock:
            return len(self._scores)


class HostSpillStore:
    """Byte-budgeted host-RAM store of cold-spilled chunk planes.

    Values are tuples of numpy arrays (host copies of the device planes)
    plus opaque metadata the owning cache round-trips. Inserts past the
    budget evict oldest-first — a cold chunk falling off the host store
    simply recomputes on its next miss, exactly like a never-cached chunk.
    Thread-safe (the cache calls under its own lock too, but scrapes and
    tests read concurrently).
    """

    def __init__(self, budget_mb: int = 1024):
        if budget_mb < 1:
            raise ValueError(f"budget_mb={budget_mb}: expected >= 1")
        self.budget_bytes = int(budget_mb) * (1 << 20)
        self._lock = threading.Lock()
        self._data: "Dict[object, Tuple[Tuple[np.ndarray, ...], dict, int]]" = {}
        self._order: list = []  # insertion order (oldest first)
        self.bytes = 0
        # cumulative counters (tier stats)
        self.spills = 0
        self.evictions = 0

    def put(self, key, planes: Tuple, meta: Optional[dict] = None) -> int:
        """Store host copies of ``planes``; returns bytes now held for the
        key. Oldest entries evict until the budget holds (the entry being
        inserted is never its own victim)."""
        host = tuple(np.asarray(p) for p in planes)
        nbytes = int(sum(p.nbytes for p in host))
        evicted = 0
        with self._lock:
            self._drop_locked(key)
            self._data[key] = (host, dict(meta or {}), nbytes)
            self._order.append(key)
            self.bytes += nbytes
            self.spills += 1
            while self.bytes > self.budget_bytes and len(self._order) > 1:
                victim = self._order[0]
                if victim == key:
                    break
                self._drop_locked(victim)
                self.evictions += 1
                evicted += 1
        if evicted:
            # a budget-evicted cold chunk can never swap back in — its
            # next use is a plain recompute; the journal names the moment
            flight.emit("host_spill_evict", evicted=evicted, bytes=self.bytes)
        return nbytes

    def get(self, key) -> Optional[Tuple[Tuple[np.ndarray, ...], dict]]:
        with self._lock:
            entry = self._data.get(key)
            if entry is None:
                return None
            return entry[0], dict(entry[1])

    def _drop_locked(self, key) -> bool:
        entry = self._data.pop(key, None)
        if entry is None:
            return False
        try:
            self._order.remove(key)
        except ValueError:
            pass
        self.bytes -= entry[2]
        return True

    def drop(self, key) -> bool:
        """Release one spilled entry's host buffer."""
        with self._lock:
            return self._drop_locked(key)

    def clear(self) -> None:
        with self._lock:
            self._data.clear()
            self._order.clear()
            self.bytes = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._data)

    def __contains__(self, key) -> bool:
        with self._lock:
            return key in self._data

    def manifest(self) -> list:
        """The store's inventory, oldest first: ``{key, nbytes, meta}``
        per spilled entry. Host buffers die with the process, but the
        manifest's identity (which keys were cold-but-kept, how big)
        feeds the prefix cache's warmth manifest (ISSUE 19): the chunks
        a crashed replica had spilled are exactly the ones a warm
        restart re-stages first."""
        with self._lock:
            return [
                {"key": key, "nbytes": self._data[key][2],
                 "meta": dict(self._data[key][1])}
                for key in self._order
            ]


@jax.jit
def _quantize_pair(k, v):
    from rag_llm_k8s_tpu.ops.attention import quantize_kv

    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    return kq, vq, ks, vs


@functools.partial(jax.jit, static_argnames=("dtype",))
def _dequantize_pair(kq, vq, ks, vs, *, dtype):
    k = (kq.astype(jnp.float32) * ks[..., None]).astype(dtype)
    v = (vq.astype(jnp.float32) * vs[..., None]).astype(dtype)
    return k, v


def quantize_planes(planes: Tuple) -> Optional[Tuple]:
    """Warm-tier conversion of a cached KV plane tuple: ``(k, v)`` native
    payloads become ``(k_q, v_q, k_scale, v_scale)`` — int8 payloads with
    one fp32 symmetric scale per (token, kv-head) vector, the exact layout
    every ``_q8`` kernel dequantizes at. NO re-prefill happens: the bytes
    already in HBM are converted in place (old planes freed by the caller
    dropping its reference). Returns None when the tuple is already
    quantized (an int8-KV engine's entries — warm is a tier label there,
    not a byte change)."""
    if len(planes) != 2:
        return None  # already (payload, payload, scale, scale)
    k, v = planes
    if getattr(k, "dtype", None) == jnp.int8:
        return None
    return tuple(_quantize_pair(k, v))


def dequantize_planes(planes: Tuple, dtype) -> Tuple:
    """Inverse of :func:`quantize_planes`: rebuild ``(k, v)`` in ``dtype``
    from a warm entry's int8 payloads + scales (the splice/scatter paths
    consume native-dtype planes). The int8 round trip is the warm tier's
    bounded quality cost — max|x|/254 per element, pinned by the
    forced-demotion tolerance tests."""
    if len(planes) == 2:
        return planes
    kq, vq, ks, vs = planes
    return tuple(_dequantize_pair(kq, vq, ks, vs, dtype=jnp.dtype(dtype)))
