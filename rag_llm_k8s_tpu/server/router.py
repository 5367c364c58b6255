"""Prefix-affinity front tier over N engine replicas (ISSUE 20).

The paper's service scales by adding identical pods behind L2
load-balancing — every replica re-prefills every hot chunk, and the
MFU-bound prefill work contends with the bandwidth-bound decode work on
the same arena. This module is the multi-replica control plane that
makes the split pay:

- **replica handles** (:class:`Replica`): an in-process
  ``ContinuousScheduler`` + engine pair today; an HTTP handle implements
  the same small surface (submit / submit_migrated / health / load)
  tomorrow. A replica's ROLE comes from its engine
  (``EngineConfig.pool_role``): ``prefill`` engines run admission only
  and export each request as a migration packet; ``decode`` engines
  import packets and run the bandwidth-bound tail; ``unified`` replicas
  serve either side (and are the fallback when a tier is empty).
- **affinity scoring** (:meth:`Router.select`): candidates are scored
  ``affinity_weight * chunk_affinity + load_weight * free_capacity``.
  Chunk affinity is the fraction of the request's retrieved-chunk keys
  already hot on the replica, tracked by a bounded per-replica LRU the
  router maintains from its own routing decisions — the same keys the
  replica's prefix cache uses, so routing a repeat composition to the
  replica that prefilled its chunks turns PR 12's chunk-granular reuse
  into a FLEET property instead of a per-pod accident. Session
  stickiness (``session_ttl_s``) pins a conversation to the replica
  holding its KV.
- **health**: a replica whose breaker is open, whose admission gate is
  draining, or whose scheduler has stopped takes no new work —
  readiness is the same signal Kubernetes drains on, so the in-process
  router and the k8s Service agree about who is servable.
- **shedding**: an optional admission gate (PR 4's
  ``AdmissionController``) fronts the whole tier; with tenants flowing
  through it, its fair-share displacement (ISSUE 20) is what sheds when
  every replica is saturated.

Every routing decision journals as a ``route_decision`` flight event
(``flightview --router`` aggregates affinity hit rate and migration
latency offline). docs/ROUTER.md walks the protocol end to end.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

from rag_llm_k8s_tpu.core.config import RouterConfig
from rag_llm_k8s_tpu.obs import flight

__all__ = ["NoReplicaAvailable", "Replica", "Router"]

#: hard cap on tracked sessions — TTL expiry is the normal bound; the cap
#: only matters under a flood of single-shot session ids
_MAX_SESSIONS = 4096


class NoReplicaAvailable(RuntimeError):
    """Every candidate replica is unhealthy (breaker open / draining /
    stopped). The edge maps this to 503 + Retry-After — the same shape a
    single pod's breaker produces, so clients need no new handling."""

    def __init__(self, role: str):
        super().__init__(f"no healthy replica for role {role!r}")
        self.role = role


class Replica:
    """One engine behind the router.

    Wraps an in-process :class:`ContinuousScheduler`; the surface is
    deliberately small (submit / submit_migrated via ``scheduler``,
    ``role``, ``healthy``, ``load``) so an HTTP handle can implement it
    without the router changing. ``breaker`` and ``admission`` are the
    replica's OWN resilience objects when it runs inside a service —
    optional here so raw engine pairs (tests) route too.
    """

    def __init__(self, name: str, scheduler, breaker=None, admission=None):
        self.name = name
        self.scheduler = scheduler
        self.breaker = breaker
        self.admission = admission

    @property
    def engine(self):
        return self.scheduler.engine

    @property
    def role(self) -> str:
        return getattr(self.engine, "pool_role", "unified")

    def healthy(self) -> bool:
        """Breaker/draining readiness — the SAME signal /healthz serves,
        so the router and the Kubernetes Service agree on who takes new
        work."""
        if self.breaker is not None and self.breaker.open:
            return False
        if self.admission is not None and self.admission.draining:
            return False
        stop = getattr(self.scheduler, "_stop", None)
        if stop is not None and stop.is_set():
            return False
        return True

    def load(self) -> float:
        """Free-capacity fraction in [0, 1]: the mean of free decode rows
        and free pool blocks. Gauge-grade — read off the scheduler
        thread's host mirrors without a lock, like every scrape-path
        reader of engine state."""
        eng = self.engine
        rows = len(eng.free_slots()) / max(1, eng.B)
        pool = getattr(eng, "kv_pool", None)
        if pool is None:
            return rows
        usable = max(1, pool.usable_blocks())
        blocks = (pool.usable_blocks() - pool.blocks_in_use()) / usable
        return 0.5 * (rows + max(0.0, blocks))


class Router:
    """Front tier over N replica handles: score, route, hand off.

    Thread-safe: HTTP threads call :meth:`submit` concurrently; the
    affinity/session registries mutate under one lock, and everything
    engine-side goes through the replicas' own schedulers (each
    serializes its engine). In-process replicas share one flight journal
    and one process-global request-id counter, so a migrated request's
    lifecycle reads as ONE timeline across both engines.
    """

    def __init__(self, replicas: Sequence[Replica],
                 config: RouterConfig = RouterConfig(),
                 admission=None):
        if not replicas:
            raise ValueError("Router needs at least one replica")
        names = [r.name for r in replicas]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate replica names: {names}")
        config.validate()
        self.config = config
        self.replicas: List[Replica] = list(replicas)
        # the tier-wide gate (PR 4): fair-share shedding for the whole
        # fleet — None keeps the router standalone (tests)
        self.admission = admission
        self._lock = threading.Lock()
        # per-replica hot-chunk LRU: chunk key -> None, newest last;
        # bounded by config.hot_chunks per replica. Fed by ROUTING
        # decisions (what was sent where), not replica introspection —
        # an HTTP replica needs no new endpoint for affinity to work.
        self._hot: Dict[str, "OrderedDict"] = {
            r.name: OrderedDict() for r in self.replicas
        }
        # session -> (replica name, last-routed stamp); TTL-expired
        # entries drop on touch
        self._sessions: "OrderedDict[str, Tuple[str, float]]" = OrderedDict()

    # -- scoring -----------------------------------------------------------
    def _healthy(self, roles: Tuple[str, ...]) -> List[Replica]:
        return [r for r in self.replicas if r.role in roles and r.healthy()]

    def _affinity_locked(self, name: str, chunk_keys: Sequence) -> float:
        if not chunk_keys:
            return 0.0
        hot = self._hot[name]
        return sum(1 for k in chunk_keys if k in hot) / len(chunk_keys)

    def _note_locked(self, name: str, chunk_keys: Sequence) -> None:
        hot = self._hot[name]
        for k in chunk_keys:
            if k in hot:
                hot.move_to_end(k)
            else:
                hot[k] = None
        while len(hot) > self.config.hot_chunks:
            hot.popitem(last=False)

    def select(self, role: str = "prefill", chunk_keys: Sequence = (),
               session: Optional[str] = None) -> Tuple[Replica, float, float]:
        """Pick the replica to run ``role`` work for a request touching
        ``chunk_keys``. Returns ``(replica, score, affinity)`` and
        records the decision (hot-chunk LRU + session map) so the NEXT
        request with the same composition scores the winner higher —
        affinity is self-reinforcing by construction. A live session
        within its TTL short-circuits scoring entirely: the replica
        already holds the conversation's KV. Raises
        :class:`NoReplicaAvailable` when no candidate is healthy
        (``unified`` replicas back-fill an empty prefill tier; an empty
        decode tier is the caller's signal to not disaggregate)."""
        if role == "prefill":
            cands = self._healthy(("prefill", "unified"))
        elif role == "decode":
            cands = self._healthy(("decode",))
        else:
            cands = self._healthy(("unified",))
        if not cands:
            raise NoReplicaAvailable(role)
        now = time.monotonic()
        cfg = self.config
        with self._lock:
            if session is not None:
                entry = self._sessions.get(session)
                if entry is not None:
                    name, stamp = entry
                    if now - stamp <= cfg.session_ttl_s:
                        for r in cands:
                            if r.name == name:
                                aff = self._affinity_locked(name, chunk_keys)
                                self._note_locked(name, chunk_keys)
                                self._sessions[session] = (name, now)
                                return r, cfg.affinity_weight * 1.0, aff
                    self._sessions.pop(session, None)
            best, best_score, best_aff = None, float("-inf"), 0.0
            for r in cands:
                aff = self._affinity_locked(r.name, chunk_keys)
                score = (cfg.affinity_weight * aff
                         + cfg.load_weight * r.load())
                if score > best_score:
                    best, best_score, best_aff = r, score, aff
            self._note_locked(best.name, chunk_keys)
            if session is not None:
                self._sessions[session] = (best.name, now)
                while len(self._sessions) > _MAX_SESSIONS:
                    self._sessions.popitem(last=False)
        return best, best_score, best_aff

    # -- serving -----------------------------------------------------------
    def submit(
        self,
        prompt: Sequence[int],
        max_new_tokens: Optional[int] = None,
        seed: Optional[int] = None,
        timeout: Optional[float] = None,
        deadline=None,
        info: Optional[Dict] = None,
        tenant: Optional[str] = None,
        chunk_keys: Sequence = (),
        session: Optional[str] = None,
    ) -> List[int]:
        """Route one request through the tier and block until its stream
        completes. Disaggregated path: the chosen prefill-role replica
        admits and returns a migration packet; the chosen decode-role
        replica imports it and finishes the stream — byte-identical to a
        unified run (the packet carries the row's exact sampling state).
        With no healthy decode tier the request runs entirely on a
        unified replica; either way the caller sees one token list.

        ``chunk_keys`` are the request's retrieved-chunk cache keys (the
        affinity unit); ``session`` pins a conversation. The optional
        tier-wide admission gate sheds BEFORE any replica is touched —
        with tenants, its fair-share displacement is the fleet's
        overload policy."""
        if self.admission is not None:
            with self.admission.admit(deadline=deadline, tenant=tenant):
                return self._submit_routed(
                    prompt, max_new_tokens, seed, timeout, deadline,
                    info, tenant, chunk_keys, session,
                )
        return self._submit_routed(
            prompt, max_new_tokens, seed, timeout, deadline, info, tenant,
            chunk_keys, session,
        )

    def _submit_routed(self, prompt, max_new_tokens, seed, timeout,
                       deadline, info, tenant, chunk_keys, session):
        # decode tier first: a prefill-role engine with no decode tier
        # behind it would export packets nobody can land, so without one
        # the request must route to a unified replica outright
        dec: Optional[Replica] = None
        try:
            dec, _, _ = self.select("decode")
        except NoReplicaAvailable:
            dec = None
        if dec is not None:
            pre, score, aff = self.select("prefill", chunk_keys, session)
        else:
            pre, score, aff = self.select("unified", chunk_keys, session)
        mode = "disagg" if (pre.role == "prefill" and dec is not None) \
            else "unified"
        pinfo = info if info is not None else {}
        toks = pre.scheduler.submit(
            prompt, max_new_tokens=max_new_tokens, seed=seed,
            timeout=timeout, deadline=deadline, info=pinfo, tenant=tenant,
        )
        packet = pinfo.pop("migrate_packet", None)
        flight.emit(
            "route_decision", pinfo.get("request_id"),
            prefill=pre.name,
            decode=dec.name if (dec is not None and packet is not None)
            else "",
            mode="disagg" if packet is not None else "unified",
            affinity=round(aff, 4), affinity_hit=bool(aff > 0.0),
            candidates=len(self.replicas), score=round(score, 4),
        )
        if packet is None:
            # unified replica, a request that finished at its admission
            # token, or an export that degraded to local decode — the
            # stream is already complete
            return toks
        # the packet's stream continues on the decode replica: it returns
        # the FULL token list (admission token included), so the prefill
        # half's return value is subsumed
        return dec.scheduler.submit_migrated(
            packet, timeout=timeout, deadline=deadline, info=pinfo,
            tenant=tenant,
        )

    # -- introspection -----------------------------------------------------
    def stats(self) -> Dict:
        """Router-level snapshot for /healthz-style surfaces: per-replica
        role/health/load plus registry occupancy (gauge-grade)."""
        with self._lock:
            hot = {n: len(d) for n, d in self._hot.items()}
            sessions = len(self._sessions)
        return {
            "replicas": [
                {
                    "name": r.name, "role": r.role,
                    "healthy": r.healthy(),
                    "load": round(r.load(), 4),
                    "hot_chunks": hot.get(r.name, 0),
                }
                for r in self.replicas
            ],
            "sessions": sessions,
        }
