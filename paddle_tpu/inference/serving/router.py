"""Multi-replica serving router: failover the client never sees.

``ServingRouter`` fronts N :class:`PagedServingEngine` replicas, each
behind a :class:`ReplicaHandle` circuit breaker (``replica.py``). The
design lifts the scheduler's preemption invariant one level up: a
preempted sequence already resumes with bit-exact recompute inside one
engine, so a request replayed onto a DIFFERENT replica of the same
weights must regenerate the same tokens — replica death becomes a retry,
not a dropped stream.

**Failover by replay-and-confirm.** When a replica dies mid-stream
(chaos kill, step failure, strike-out, lease expiry), every live stream
assigned to it is re-queued and resubmitted to a healthy replica with
its ORIGINAL prompt, sampling knobs and seed. Determinism (per-sequence
PRNG keys + batch-independent per-row compute, the property the
preemption parity tests pin down) means the new replica regenerates the
already-streamed prefix token-for-token; the router CONFIRMS each
regenerated token against what the client already saw (a divergence is
:class:`FailoverMismatchError` — loud, never silent corruption),
suppresses the duplicates, and the client iterator continues without
observing the switch.

**Placement** is prefix-cache-aware: prefer the replica whose rolling-
hash block table already holds the longest prompt prefix
(:meth:`BlockManager.lookup_prefix` — no allocation, just the chain
walk), fall back to least-loaded. **Admission** is per-tenant weighted
round-robin with per-tenant queue caps, so one tenant's storm sheds
that tenant, not the fleet. **Drain** (`router.drain(i)`) stops new
assignments, migrates streams still in prefill (nothing emitted yet →
replay is a plain resubmit), and lets decodes finish in place.

Observability: ``paddle_router_*`` counters/gauges via the usual
``emit`` choke point, fleet digest in ``summary()["router"]`` (TTFT/
TPOT aggregate across replicas by construction — all engines feed the
same process-wide serving histograms), and a ``router`` section in
distress dumps via ``observability.register_distress_section``.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Deque, Dict, Iterator, List, Optional

from ...core import flags
from ...observability import emit as _emit
from ...observability import register_distress_section
from ...observability import tracing as _tracing
from .adapters import AdapterMissingError
from .engine import PagedServingEngine, TokenEvent
from .replica import (DEAD, DEGRADED, DRAINED, DRAINING, HEALTHY,
                      ReplicaHandle, ReplicaKilledError)
from .scheduler import Completion, DeadlineExceededError, RejectedError

__all__ = ["ServingRouter", "RouterRequest", "FailoverMismatchError"]

flags.define_flag("router_num_replicas", 2,
                  "Default replica count for ServingRouter "
                  "(tools/bench use this; the constructor arg wins)")
flags.define_flag("router_ttl_s", 5.0,
                  "Replica heartbeat lease TTL: a replica with work whose "
                  "last good step is older than this is declared dead "
                  "(same judgment as elastic membership)")
flags.define_flag("router_stall_timeout_s", 5.0,
                  "A single engine step slower than this is a stall "
                  "strike (healthy -> degraded -> dead)")
flags.define_flag("router_dead_after", 2,
                  "Strikes before a degraded replica is declared dead")
flags.define_flag("router_probation_s", 0.25,
                  "Seconds a dead replica stays dead before probation "
                  "re-admit with a fresh engine")
flags.define_flag("router_tenant_max_queue", 64,
                  "Per-tenant router admission cap: submissions beyond "
                  "this many unplaced requests for one tenant raise "
                  "RejectedError (that tenant sheds, others don't)")
flags.define_flag("router_max_failovers", 2,
                  "Failovers allowed per stream before it is shed "
                  "(guards against a request that kills every replica)")

FINISHED = "finished"


class FailoverMismatchError(RuntimeError):
    """A replayed stream diverged from what was already sent to the
    client — determinism is broken (wrong weights? nondeterministic
    kernel?). The stream fails loudly; silent corruption is never an
    option."""


@dataclass(eq=False)
class RouterRequest:
    """Router-side record of one client stream (router rids are the
    client-visible ids; engine rids are per-replica and change across
    failovers)."""
    rid: int
    tenant: str
    prompt: List[int]
    max_new_tokens: int
    eos: int = -1
    priority: int = 0
    deadline: Optional[float] = None    # absolute time.monotonic()
    temperature: Optional[float] = None
    top_p: Optional[float] = None
    seed: int = 0
    adapter: Optional[str] = None       # LoRA adapter the stream decodes through
    # live state
    emitted: List[int] = field(default_factory=list)  # client-visible
    events: List[TokenEvent] = field(default_factory=list)
    replica: Optional[int] = None
    engine_rid: Optional[int] = None
    confirmed: int = 0        # replay progress through `emitted`
    confirm_target: int = 0   # len(emitted) at failover time
    failovers: int = 0
    migrations: int = 0
    status: str = "waiting"
    finish_reason: Optional[str] = None
    # span context: the client-visible request is the trace root; every
    # engine-side span (queue.wait, prefill.chunk, decode.tick, cow.copy)
    # parents to root_span, so one stream's whole life — across replicas
    # and failovers — shares one trace_id. Plain host ints; never jitted.
    trace_id: int = 0
    root_span: int = 0
    _root: Optional[object] = None           # open "request" Span
    _failover_span: Optional[object] = None  # open "failover.replay" Span

    def confirming(self) -> bool:
        return self.confirmed < self.confirm_target


def _flag_or(value, name):
    return value if value is not None else flags.flag_value(name)


class ServingRouter:
    """Health-checked fan-out over N identical serving replicas::

        router = ServingRouter(lambda: PagedServingEngine(cfg, params,
                                                          ...),
                               num_replicas=2)
        rid = router.submit([1, 2, 3], max_new_tokens=32,
                            tenant="batch")
        for tok in router.stream(rid):   # survives a replica kill
            ...
        done = router.run()

    ``engine_factory`` must build identical engines (same weights and
    step signature) — failover correctness rests on any replica
    regenerating any other replica's tokens exactly.
    """

    def __init__(self, engine_factory: Callable[[], PagedServingEngine],
                 num_replicas: Optional[int] = None,
                 ttl: Optional[float] = None,
                 stall_timeout_s: Optional[float] = None,
                 dead_after: Optional[int] = None,
                 probation_s: Optional[float] = None,
                 tenant_max_queue: Optional[int] = None,
                 tenant_weights: Optional[Dict[str, int]] = None,
                 max_failovers: Optional[int] = None,
                 adapter_transport=None):
        n = int(_flag_or(num_replicas, "router_num_replicas"))
        if n < 1:
            raise ValueError("num_replicas must be >= 1")
        # kept for subclasses that add replicas at runtime (the disagg
        # autoscaler grows the decode pool through the same breaker knobs)
        self.engine_factory = engine_factory
        self.replica_kw = dict(
            ttl=float(_flag_or(ttl, "router_ttl_s")),
            stall_timeout_s=float(
                _flag_or(stall_timeout_s, "router_stall_timeout_s")),
            dead_after=int(_flag_or(dead_after, "router_dead_after")),
            probation_s=float(_flag_or(probation_s, "router_probation_s")))
        self.replicas = [
            ReplicaHandle(i, engine_factory, **self.replica_kw)
            for i in range(n)]
        self.tenant_max_queue = int(
            _flag_or(tenant_max_queue, "router_tenant_max_queue"))
        self.tenant_weights = dict(tenant_weights or {})
        self.max_failovers = int(
            _flag_or(max_failovers, "router_max_failovers"))
        self._pending: Dict[str, Deque[RouterRequest]] = {}
        self._reqs: Dict[int, RouterRequest] = {}
        self._live: set = set()           # rids not yet finished
        # replica_id -> {engine_rid -> RouterRequest}
        self._assigned: Dict[int, Dict[int, RouterRequest]] = {
            h.replica_id: {} for h in self.replicas}
        self._wrr_pos = 0
        self._next_rid = 0
        self._completions: List[Completion] = []
        # store-backed AdapterTransport: replicas missing a requested
        # adapter prefetch its wire pack instead of shedding the stream
        self.adapter_transport = adapter_transport
        self.stats = {"admitted": 0, "shed": 0, "assigned": 0,
                      "failovers": 0, "failover_exhausted": 0,
                      "migrations": 0, "drains": 0, "mismatches": 0,
                      "adapter_routed": 0, "adapter_prefetches": 0}
        # fleet state lands in every distress dump (latest router wins)
        register_distress_section("router", self.snapshot)

    # -- client API -------------------------------------------------------
    def submit(self, tokens, max_new_tokens: int = 32,
               eos_token_id: Optional[int] = None, tenant: str = "default",
               priority: int = 0, deadline_s: Optional[float] = None,
               temperature: Optional[float] = None,
               top_p: Optional[float] = None, seed: int = 0,
               adapter: Optional[str] = None) -> int:
        """Enqueue a stream. Raises RejectedError when `tenant`'s router
        queue is at its cap (other tenants are unaffected), ValueError
        when the request can never fit a replica."""
        prompt = [int(t) for t in tokens]
        probe = next((h.engine for h in self.replicas
                      if h.engine is not None), None)
        if probe is not None:
            total = len(prompt) + max(int(max_new_tokens), 0)
            if total > probe.max_len:
                raise ValueError(
                    f"prompt {len(prompt)} + new {max_new_tokens} exceeds "
                    f"replica max_len {probe.max_len}")
            if probe.blocks.blocks_needed(total) > probe.num_blocks:
                raise ValueError(
                    f"request needs "
                    f"{probe.blocks.blocks_needed(total)} KV blocks but "
                    f"each replica pool has {probe.num_blocks}")
        q = self._pending.setdefault(tenant, deque())
        if len(q) >= self.tenant_max_queue:
            self.stats["shed"] += 1
            _emit("router.shed", tenant=tenant, queue_depth=len(q))
            raise RejectedError(
                f"router queue for tenant {tenant!r} full ({len(q)} >= "
                f"{self.tenant_max_queue}); request shed — back off")
        rid = self._next_rid
        self._next_rid += 1
        req = RouterRequest(
            rid, tenant, prompt, int(max_new_tokens),
            eos=-1 if eos_token_id is None else int(eos_token_id),
            priority=int(priority),
            deadline=(time.monotonic() + float(deadline_s)
                      if deadline_s is not None else None),
            temperature=temperature, top_p=top_p, seed=int(seed),
            adapter=adapter)
        root = _tracing.new_trace("request", rid=rid, tenant=tenant,
                                  prompt_len=len(prompt))
        if root is not None:
            req.trace_id = root.trace_id
            req.root_span = root.span_id
            req._root = root
        self._reqs[rid] = req
        self._live.add(rid)
        self.stats["admitted"] += 1
        _emit("router.admit", tenant=tenant, rid=rid,
              prompt_len=len(prompt))
        if max_new_tokens <= 0:
            # no engine step will ever produce an event for this request;
            # finish it here (generate(max_new_tokens=0) parity)
            self._finish(req, "length")
            return rid
        q.append(req)
        return rid

    def cancel(self, rid: int) -> bool:
        req = self._reqs.get(rid)
        if req is None or req.status == FINISHED:
            return False
        if req.replica is not None:
            h = self.replicas[req.replica]
            self._assigned[req.replica].pop(req.engine_rid, None)
            if h.engine is not None:
                h.engine.cancel(req.engine_rid)
        else:
            try:
                self._pending[req.tenant].remove(req)
            except ValueError:
                pass
        self._finish(req, "cancelled")
        return True

    def has_work(self) -> bool:
        return bool(self._live)

    def run(self) -> List[Completion]:
        while self.has_work():
            self.step()
        out, self._completions = self._completions, []
        return out

    def stream(self, rid: int) -> Iterator[int]:
        """Yield rid's tokens as they are produced, driving the whole
        router (replica failovers happen under this loop without the
        iterator observing them). Typed failures mirror the engine:
        DeadlineExceededError / RejectedError / FailoverMismatchError."""
        req = self._reqs.get(rid)
        if req is None:
            raise KeyError(f"unknown rid {rid}")
        i = 0
        while True:
            while i < len(req.events):
                ev = req.events[i]
                i += 1
                if ev.token >= 0:
                    yield ev.token
                if ev.finished:
                    if ev.reason == "deadline":
                        raise DeadlineExceededError(
                            f"request {rid} expired mid-stream after "
                            f"{len(req.emitted)} tokens")
                    if ev.reason in ("shed", "failover_exhausted",
                                     "adapter_missing"):
                        raise RejectedError(
                            f"request {rid} shed mid-stream "
                            f"(reason={ev.reason})")
                    if ev.reason == "failover_mismatch":
                        raise FailoverMismatchError(
                            f"request {rid}: replayed continuation "
                            f"diverged from streamed prefix")
                    return
            if req.status == FINISHED:
                return
            self.step()

    # -- the router tick --------------------------------------------------
    def step(self) -> int:
        """One tick: probation re-admits, WRR admission, guarded replica
        steps with failover, drain progress, gauges. Returns the number
        of harvested engine events (a progress signal for callers)."""
        for h in self.replicas:
            h.maybe_readmit()
        self._admit()
        progress = 0
        for h in self.replicas:
            if not h.steppable():
                continue
            try:
                h.check_lease()
            except ReplicaKilledError:
                self._failover(h)
                continue
            if h.engine.has_work():
                try:
                    events = h.guarded_step()
                except ReplicaKilledError:
                    self._failover(h)
                    continue
                progress += self._harvest(h, events)
            else:
                h.beat()
            h.drain_tick()
        self._update_gauges()
        return progress

    # -- admission / placement --------------------------------------------
    def _weight(self, tenant: str) -> int:
        return max(int(self.tenant_weights.get(tenant, 1)), 1)

    def _admit(self):
        tenants = sorted(t for t, q in self._pending.items() if q)
        if not tenants:
            return
        if not any(h.accepts_new() for h in self.replicas):
            # no placement target now; shed only when none can ever come
            # back (every replica drained/draining — dead ones get a
            # probation re-admit, so they still count as hope)
            if not any(h.state == DEAD for h in self.replicas):
                for t in tenants:
                    while self._pending[t]:
                        req = self._pending[t].popleft()
                        self.stats["shed"] += 1
                        _emit("router.shed", tenant=t, reason="no_replicas")
                        self._finish(req, "shed")
            return
        # weighted round-robin: rotate the tenant cycle each tick, give
        # each tenant up to `weight` placements per pass
        start = self._wrr_pos % len(tenants)
        order = tenants[start:] + tenants[:start]
        self._wrr_pos += 1
        for t in order:
            q = self._pending[t]
            for _ in range(self._weight(t)):
                if not q or not self._place(q[0]):
                    break
                q.popleft()

    def _placement_candidates(self,
                              req: RouterRequest) -> List[ReplicaHandle]:
        """Replicas eligible to receive `req` right now (subclass hook:
        the disagg router narrows this to the request's pool)."""
        return [h for h in self.replicas
                if h.accepts_new() and h.engine is not None]

    def _prefix_signal(self, req: RouterRequest, h: ReplicaHandle) -> int:
        """Prefix-affinity score for placing `req` on `h` (subclass
        hook: the disagg router folds in the fleet-global index)."""
        return h.engine.blocks.lookup_prefix(req.prompt)

    def _submit_budget(self, req: RouterRequest) -> int:
        """max_new_tokens for the engine submit (subclass hook: the
        disagg router caps prefill-phase placements at one token)."""
        return req.max_new_tokens

    def _prepare_submit(self, req: RouterRequest, h: ReplicaHandle):
        """Runs just before `req` is submitted to `h` (subclass hook:
        the disagg router pulls migrated pages here)."""

    def _adapter_signal(self, req: RouterRequest, h: ReplicaHandle) -> int:
        """Adapter-affinity score: 2 = device-resident (zero-cost hit),
        1 = host-registered (a slot write away), 0 = absent (needs a
        transport prefetch or the stream can't run there)."""
        if req.adapter is None:
            return 0
        mgr = h.engine.adapters
        if not mgr.registered(req.adapter):
            return 0
        try:
            mgr.slot_of(req.adapter)
            return 2
        except AdapterMissingError:
            return 1

    def publish_adapter(self, adapter) -> None:
        """Register a LoRA adapter on every live replica and (when a
        transport is wired) publish its wire pack so future/probation
        replicas can prefetch it."""
        for h in self.replicas:
            if h.engine is not None:
                h.engine.adapters.register(adapter)
        if self.adapter_transport is not None:
            self.adapter_transport.publish(adapter)

    def _place(self, req: RouterRequest) -> bool:
        """Prefix- and adapter-affinity placement with least-loaded
        fallback; False when no accepting replica has room right now
        (the request stays queued — engine-level backpressure, not a
        shed)."""
        cands = self._placement_candidates(req)
        if not cands:
            return False

        # On a mixed int8/fp fleet, equal outstanding work can hide very
        # different device pressure (an int8-cache replica's pages are
        # 2-4x cheaper than an fp replica's), so actual KV bytes break
        # the tie. Adapter residency skews bytes the same way (a replica
        # stuffed with slot packs pays real HBM), so an uneven adapter
        # footprint also arms the byte tiebreak — bytes_in_use() already
        # folds adapter bytes in via the block manager's extra-bytes
        # callback. Homogeneous fleets keep the pure depth ordering.
        mixed = (len({h.engine.kv_page_bytes for h in cands}) > 1
                 or len({h.engine.adapters.bytes_in_use()
                         for h in cands}) > 1)

        def load(h):
            return (h.engine.scheduler.queue_depth()
                    + h.engine.scheduler.num_running(),
                    h.engine.blocks.bytes_in_use() if mixed else 0)

        scored = [(self._prefix_signal(req, h),
                   self._adapter_signal(req, h), h) for h in cands]
        best_prefix = max(s for s, _, _ in scored)
        if best_prefix > 0:
            # prefix affinity stays the primary signal (paid-for KV beats
            # a cheap slot write); adapter residency breaks prefix ties
            order = sorted(scored,
                           key=lambda sh: (-sh[0], -sh[1], load(sh[2]),
                                           sh[2].replica_id))
        elif req.adapter is not None and any(a for _, a, _ in scored):
            order = sorted(scored,
                           key=lambda sh: (-sh[1], load(sh[2]),
                                           sh[2].replica_id))
        else:
            order = sorted(scored,
                           key=lambda sh: (load(sh[2]), sh[2].replica_id))
        adapter_missing = 0
        for prefix, ad_sig, h in order:
            deadline_s = None
            if req.deadline is not None:
                deadline_s = req.deadline - time.monotonic()
            if (req.adapter is not None and ad_sig == 0
                    and self.adapter_transport is not None):
                # least-loaded fallback landed on a replica without the
                # adapter: pull the wire pack over the store transport
                if h.engine.adapters.prefetch(
                        req.adapter, self.adapter_transport) == "ok":
                    self.stats["adapter_prefetches"] += 1
            self._prepare_submit(req, h)
            try:
                engine_rid = h.engine.submit(
                    req.prompt, max_new_tokens=self._submit_budget(req),
                    eos_token_id=None if req.eos < 0 else req.eos,
                    priority=req.priority, deadline_s=deadline_s,
                    temperature=req.temperature, top_p=req.top_p,
                    seed=req.seed, adapter=req.adapter,
                    trace=((req.trace_id, req.root_span)
                           if req.trace_id else None))
            except RejectedError:
                continue   # this replica's queue is full; try the next
            except AdapterMissingError:
                adapter_missing += 1
                continue   # not registered here and no transport copy
            req.replica = h.replica_id
            req.engine_rid = engine_rid
            req.status = "assigned"
            self._assigned[h.replica_id][engine_rid] = req
            h.beat()   # accepting work refreshes the lease: the age
            #            clock starts from placement, not construction
            self.stats["assigned"] += 1
            if req.adapter is not None:
                self.stats["adapter_routed"] += 1
            _emit("router.assign", tenant=req.tenant, rid=req.rid,
                  replica=h.replica_id, prefix_hit=prefix,
                  adapter_hit=ad_sig, replay=req.confirm_target)
            return True
        if adapter_missing == len(order):
            # every eligible replica refused for the same terminal
            # reason: the adapter isn't registered anywhere and the
            # transport has no copy. Queue-full is transient, this is
            # not — leaving it pending would livelock run().
            self.stats["shed"] += 1
            _emit("router.shed", tenant=req.tenant,
                  reason="adapter_missing", adapter=req.adapter)
            self._finish(req, "adapter_missing")
            return True
        return False

    # -- failover / drain -------------------------------------------------
    def _failover(self, h: ReplicaHandle):
        """The dead replica's streams re-queue for replay; the client
        iterators keep waiting on the same router events."""
        orphans = self._assigned[h.replica_id]
        self._assigned[h.replica_id] = {}
        for req in orphans.values():
            if req.status == FINISHED:
                continue
            req.failovers += 1
            if req.failovers > self.max_failovers:
                self.stats["failover_exhausted"] += 1
                _emit("router.shed", tenant=req.tenant,
                      reason="failover_exhausted")
                self._finish(req, "failover_exhausted")
                continue
            req.replica = None
            req.engine_rid = None
            req.confirm_target = len(req.emitted)
            req.confirmed = 0
            req.status = "waiting"
            # the replay rides the ORIGINAL trace: same trace_id, a
            # failover.replay span under the request root that stays open
            # until the survivor has re-confirmed every streamed token
            _tracing.end_span(req._failover_span, outcome="superseded")
            req._failover_span = _tracing.start_span(
                "failover.replay", req.trace_id, req.root_span,
                rid=req.rid, from_replica=h.replica_id,
                why=h.death_reason or "dead", replay=len(req.emitted))
            # resume ahead of new arrivals, like a preempted sequence
            self._pending.setdefault(req.tenant, deque()).appendleft(req)
            self.stats["failovers"] += 1
            _emit("router.failover", tenant=req.tenant, rid=req.rid,
                  replica=h.replica_id, emitted=len(req.emitted),
                  why=h.death_reason or "dead")

    def drain(self, replica_id: int):
        """Graceful drain: no new assignments, streams still in prefill
        (nothing emitted yet) migrate to other replicas, decodes finish
        in place; the replica reads DRAINED once idle."""
        h = self.replicas[replica_id]
        h.start_drain()
        self.stats["drains"] += 1
        _emit("router.drain", replica=replica_id)
        amap = self._assigned[replica_id]
        for engine_rid, req in list(amap.items()):
            if req.emitted or req.status == FINISHED:
                continue   # decoding (or done): let it finish here
            amap.pop(engine_rid)
            if h.engine is not None:
                h.engine.cancel(engine_rid)   # event is unmapped: ignored
            req.replica = None
            req.engine_rid = None
            req.confirm_target = 0
            req.confirmed = 0
            req.status = "waiting"
            req.migrations += 1
            self._pending.setdefault(req.tenant, deque()).appendleft(req)
            self.stats["migrations"] += 1
            _emit("router.migrate", tenant=req.tenant, rid=req.rid,
                  replica=replica_id)
        h.drain_tick()

    # -- harvest ----------------------------------------------------------
    def _harvest(self, h: ReplicaHandle, events: List[TokenEvent]) -> int:
        amap = self._assigned[h.replica_id]
        n = 0
        for ev in events:
            req = amap.get(ev.rid)
            if req is None:
                continue   # unmapped (migrated/cancelled) engine stream
            n += 1
            self._process_event(h, amap, req, ev)
        return n

    def _process_event(self, h: ReplicaHandle, amap: Dict[int,
                                                          "RouterRequest"],
                       req: RouterRequest, ev: TokenEvent):
        if req.confirming():
            if ev.token >= 0 and not ev.finished \
                    and ev.token == req.emitted[req.confirmed]:
                req.confirmed += 1   # duplicate confirmed and suppressed
                if not req.confirming() and req._failover_span is not None:
                    # the survivor regenerated the whole streamed prefix:
                    # replay complete, new tokens flow from here
                    _tracing.end_span(req._failover_span,
                                      replica=h.replica_id,
                                      confirmed=req.confirmed)
                    req._failover_span = None
                return
            if ev.finished and ev.token < 0 \
                    and ev.reason in ("deadline", "shed", "cancelled"):
                # the replay itself was expired/shed before catching up —
                # a typed terminal outcome, not a determinism failure
                amap.pop(ev.rid, None)
                req.events.append(TokenEvent(req.rid, -1, True, ev.reason))
                self._finish(req, ev.reason, terminal_logged=True)
                return
            # anything else mid-confirm is a divergence: wrong token, or
            # the replay terminated before reaching the streamed prefix
            amap.pop(ev.rid, None)
            if h.engine is not None and not ev.finished:
                h.engine.cancel(ev.rid)
            self.stats["mismatches"] += 1
            _emit("router.mismatch", tenant=req.tenant, rid=req.rid,
                  replica=h.replica_id, confirmed=req.confirmed,
                  target=req.confirm_target,
                  got=ev.token, want=req.emitted[req.confirmed])
            self._finish(req, "failover_mismatch")
            return
        if ev.token >= 0:
            req.emitted.append(ev.token)
            req.events.append(TokenEvent(req.rid, ev.token, ev.finished,
                                         ev.reason))
        if ev.finished:
            amap.pop(ev.rid, None)
            if ev.token < 0:
                req.events.append(TokenEvent(req.rid, -1, True, ev.reason))
            self._finish(req, ev.reason or "stop", terminal_logged=True)

    def _finish(self, req: RouterRequest, reason: str,
                terminal_logged: bool = False):
        if req.status == FINISHED:
            return
        req.status = FINISHED
        req.finish_reason = reason
        self._live.discard(req.rid)
        if req._failover_span is not None:   # finished mid-replay
            _tracing.end_span(req._failover_span, outcome=reason)
            req._failover_span = None
        if req._root is not None:
            _tracing.end_span(req._root, reason=reason,
                              generated=len(req.emitted),
                              failovers=req.failovers)
            req._root = None
        if not terminal_logged:
            req.events.append(TokenEvent(req.rid, -1, True, reason))
        self._completions.append(Completion(req.rid, list(req.prompt),
                                            list(req.emitted), reason))
        _emit("router.complete", tenant=req.tenant, rid=req.rid,
              reason=reason, generated=len(req.emitted),
              failovers=req.failovers)

    # -- introspection ----------------------------------------------------
    def _update_gauges(self):
        counts = {HEALTHY: 0, DEGRADED: 0, DEAD: 0, DRAINING: 0,
                  DRAINED: 0}
        for h in self.replicas:
            counts[h.state] += 1
            util = (h.engine.blocks.utilization()
                    if h.engine is not None else 0.0)
            kv_bytes = (h.engine.blocks.bytes_in_use()
                        if h.engine is not None else 0)
            _emit("router.replica", replica=h.replica_id, state=h.state,
                  kv_utilization=util, kv_bytes_in_use=kv_bytes)
        _emit("router.gauges",
              pending=sum(len(q) for q in self._pending.values()),
              live_streams=len(self._live), **counts)

    def snapshot(self) -> Dict[str, Any]:
        """Operator/distress view: per-replica breaker state + fleet
        queue picture (registered as the 'router' distress section)."""
        return {
            "replicas": {str(h.replica_id): h.snapshot()
                         for h in self.replicas},
            "pending_by_tenant": {t: len(q)
                                  for t, q in self._pending.items() if q},
            "live_streams": len(self._live),
            **self.stats,
        }

    @property
    def router_stats(self) -> dict:
        return dict(self.stats)
