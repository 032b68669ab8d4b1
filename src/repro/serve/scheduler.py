"""Continuous-batching scheduler: a request queue with admission by slot
availability and per-step join/evict of finished requests.

Ordering reuses the ``core.policies`` abstractions (a policy only ORDERS the
queue — the same separation Synergy draws for training jobs): FCFS maps onto
``policies.FIFO`` (arrival order) and SJF onto ``policies.SRTF`` (least
remaining work = prompt + generation budget still owed). ``ServeRequest``
exposes the ``arrival_time`` / ``remaining`` / ``job_id`` attributes those
policies sort by.

The clock is the engine's decode-step counter: open-loop arrival processes
set ``arrival_time`` in steps and a request becomes admissible once the
engine clock passes it. Static batching is the degenerate configuration —
every request arrives at step 0 and the pool has one slot per request, so the
first admission round admits everything and no join/evict ever happens
mid-flight.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from repro.core.policies import FIFO, SRTF, Policy
from repro.obs import NULL_TRACER
from repro.serve.cache import CachePool

#: serve-queue ordering policies (names per the serving literature).
#: "slo" (SLO-slack ordering) is constructed by the engine — it needs a
#: ``tenant.TenantRegistry`` — and arrives here as a Policy instance.
SERVE_POLICIES = {"fcfs": FIFO, "sjf": SRTF}


@dataclass(eq=False)                   # identity equality: prompts are arrays
class ServeRequest:
    prompt: np.ndarray                 # [S] int32
    max_new_tokens: int = 16
    job_id: int = 0
    arrival_time: float = 0.0          # engine decode-step clock
    #: tenant tag — resolved against the engine's ``TenantRegistry`` for
    #: SLO slack, per-tenant budgets, and per-tenant stats (see
    #: serve/tenant.py). Untagged requests share the "default" tenant.
    tenant: str = "default"
    output: List[int] = field(default_factory=list)
    slot: Optional[int] = None
    admitted_at: Optional[float] = None
    finished_at: Optional[float] = None
    #: set when the engine stops the request before its budget (EOS token):
    #: ``done`` then holds even though fewer than max_new_tokens were emitted.
    finished_early: bool = False
    #: times this request was preempted under pool pressure (each bounce
    #: regenerates its tokens identically after re-admission)
    n_preempted: int = 0
    # -- fault recovery (serve/chaos.py; all idle without an injector) ------
    #: admission retries burned after a pool_shrink left the request
    #: unservable (bounded retry-with-backoff), and the step the next
    #: retry is due at
    n_retries: int = 0
    next_retry: float = 0.0
    #: set when a fault-recovery path gave up on the request: dropped
    #: requests are excluded from slo_attainment's denominator and counted
    #: separately from ``unfinished`` (see ServeStats)
    dropped: bool = False
    drop_cause: Optional[str] = None
    # wall clocks (perf_counter): t_arrived is stamped when the engine clock
    # first passes arrival_time (NOT at admission), so latency_s includes
    # queue wait. t_admitted is the FIRST admission and t_first_token the
    # first token a prefill picked (a preempted request keeps both), so a
    # request's time splits into queued [due, t_admitted), prefill
    # [t_admitted, t_first_token) and decode [t_first_token, t_finished].
    t_arrived: Optional[float] = None
    t_admitted: Optional[float] = None
    t_first_token: Optional[float] = None
    t_finished: Optional[float] = None
    #: the prompt's last-position logits [vocab] (f32), kept only by an
    #: engine built with ``record_logits=True`` (a correctness check's hook)
    prefill_logits: Optional[np.ndarray] = None

    @property
    def done(self) -> bool:
        return self.finished_early or len(self.output) >= self.max_new_tokens

    @property
    def remaining(self) -> float:
        """Work still owed (SJF key): prompt prefill + tokens left."""
        return float(len(self.prompt) + self.max_new_tokens - len(self.output))

    @property
    def latency_steps(self) -> Optional[float]:
        if self.finished_at is None:
            return None
        return self.finished_at - self.arrival_time

    @property
    def latency_s(self) -> Optional[float]:
        """Wall seconds from becoming admissible to finishing (incl. queue)."""
        if self.t_finished is None or self.t_arrived is None:
            return None
        return self.t_finished - self.t_arrived


class ContinuousScheduler:
    """Admission + eviction over a ``CachePool``, ordered by a queue policy.

    ``policy`` is a registered name or a ``core.policies.Policy`` instance
    (the engine passes ``tenant.SLOSlack`` for SLO-slack ordering).
    ``allocation`` (a ``tenant.TenantAllocation``) adds a per-tenant
    cache-unit budget check at admission: a request over its tenant's
    budget is skipped — NOT queued-blocking, so other tenants' admissible
    requests behind it still admit this round.

    ``tracer`` (an ``obs.Tracer``) records every admission decision —
    admit / budget_skip / defer / preempt — as structured events; the
    default ``NULL_TRACER`` is falsy, so tracing off costs one branch per
    decision.
    """

    def __init__(self, pool: CachePool, policy="fcfs", allocation=None,
                 tracer=NULL_TRACER):
        if isinstance(policy, Policy):
            self.policy: Policy = policy
        elif policy in SERVE_POLICIES:
            self.policy = SERVE_POLICIES[policy]()
        else:
            raise KeyError(f"unknown serve policy {policy!r}; "
                           f"known: {sorted(SERVE_POLICIES)}")
        self.pool = pool
        self.allocation = allocation
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.n_preempted = 0           # cumulative preemptions this run
        self.waiting: List[ServeRequest] = []
        self.active: Dict[int, ServeRequest] = {}
        #: admitted-but-not-yet-prefilled requests: the engine drains this
        #: queue into its prefill lanes, so joins admitted in one round are
        #: co-scheduled into shared chunk-round dispatches.
        self.prefill_queue: deque = deque()
        self.step: int = 0

    def submit(self, req: ServeRequest) -> None:
        if hasattr(self.pool, "validate_request"):
            self.pool.validate_request(req)      # paged: blocks + table span
        elif len(req.prompt) + req.max_new_tokens > self.pool.max_len:
            raise ValueError(
                f"request needs {len(req.prompt) + req.max_new_tokens} cache "
                f"positions but the pool holds {self.pool.max_len}")
        self.waiting.append(req)

    def park(self, req: ServeRequest) -> None:
        """Queue a request the CURRENT pool cannot validate but scheduled
        capacity — a pending restore/join fault, or proactive scale-up
        headroom — will later cover: it waits for the engine's bounded
        retry admission instead of being rejected at submit. Safe because
        ``admit`` re-checks capacity every round (``alloc_for`` simply
        fails while the pool is still small), so a parked request can
        never corrupt the pool — only wait for it."""
        self.waiting.append(req)

    @property
    def has_work(self) -> bool:
        return bool(self.waiting or self.active)

    def next_arrival(self) -> Optional[float]:
        return min((r.arrival_time for r in self.waiting), default=None)

    def admit(self, hold=None) -> List[ServeRequest]:
        """Admit policy-ordered admissible requests while slots are free.

        ``hold`` (chaos.FaultInjector admission stalls) maps a request to
        a defer cause or None: a held request skips this round — emitted
        as a ``defer`` event — without blocking the requests behind it.
        """
        ready = [r for r in self.waiting if r.arrival_time <= self.step]
        now = time.perf_counter()
        for r in ready:
            if r.t_arrived is None:
                r.t_arrived = now
        admitted = []
        tr = self.tracer
        for req in self.policy.order(ready, float(self.step)):
            if hold is not None:
                cause = hold(req)
                if cause is not None:
                    if tr:
                        tr.emit("defer", req=req.job_id, tenant=req.tenant,
                                cause=cause)
                    continue
            # tenant budget: a request past its tenant's cache-unit budget
            # is skipped (its tenant already holds its allocated share) —
            # other tenants' requests behind it still admit this round.
            if (self.allocation is not None
                    and not self.allocation.admissible(req, self.active,
                                                       self.pool)):
                if tr:
                    why = self.allocation.last_decision or {}
                    tr.emit("budget_skip", req=req.job_id, tenant=req.tenant,
                            held=why.get("held"), need=why.get("need"),
                            budget=why.get("budget"))
                continue
            # paged pools admit by free *blocks* (length-proportional, with a
            # watermark reserve); slot pools by free slots.
            slot = (self.pool.alloc_for(req)
                    if hasattr(self.pool, "alloc_for") else self.pool.alloc())
            if slot is None:
                # a prefix-cache deferral (donor still prefilling) parks only
                # THAT request — unrelated admissible requests behind it must
                # not wait a round; pool exhaustion still ends the scan.
                if getattr(self.pool, "deferred_last_alloc", False):
                    if tr:
                        tr.emit("defer", req=req.job_id, tenant=req.tenant,
                                cause="prefix_unready")
                    continue
                break
            req.slot = slot
            req.admitted_at = float(self.step)
            if req.t_admitted is None:
                req.t_admitted = time.perf_counter()
            self.active[slot] = req
            self.waiting.remove(req)
            self.prefill_queue.append(req)
            admitted.append(req)
            if tr:
                units = (self.pool.owned_blocks(slot)
                         if hasattr(self.pool, "owned_blocks") else 1)
                tr.emit("admit", req=req.job_id, tenant=req.tenant, slot=slot,
                        prompt_len=len(req.prompt),
                        max_new=req.max_new_tokens,
                        wait_steps=float(self.step) - req.arrival_time,
                        units=units)
        return admitted

    def drain_prefill(self) -> List[ServeRequest]:
        """All admitted requests awaiting prefill (clears the queue)."""
        items = list(self.prefill_queue)
        self.prefill_queue.clear()
        return items

    def preempt(self, req: ServeRequest, cause: str = "pool_pressure") -> None:
        """Return an active request to the queue under block-pool pressure.

        Its slot and blocks are freed and its generated tokens discarded;
        after re-admission the deterministic prefill + greedy decode
        regenerate them identically, so preemption is invisible in outputs.
        Its wall stamps of the first admission and first token stay.
        """
        if req.slot is None or self.active.get(req.slot) is not req:
            raise ValueError("can only preempt an active request")
        self.n_preempted += 1
        if self.tracer:
            self.tracer.emit("preempt", req=req.job_id, tenant=req.tenant,
                             slot=req.slot, cause=cause,
                             n_preempted=self.n_preempted)
        self.pool.free(req.slot)
        del self.active[req.slot]
        req.slot = None
        req.admitted_at = None
        req.output = []
        req.finished_early = False
        req.n_preempted += 1
        self.waiting.append(req)

    def evict_finished(self) -> List[ServeRequest]:
        """Release slots of finished requests (the per-step evict half)."""
        done = [r for r in self.active.values() if r.done]
        for req in done:
            # the engine may have pre-stamped the exact finishing step (a
            # multi-step decode horizon evicts only at horizon boundaries);
            # only fill in the boundary step when it has not.
            if req.finished_at is None:
                req.finished_at = float(self.step)
            req.t_finished = time.perf_counter()
            self.pool.free(req.slot)
            del self.active[req.slot]
            req.slot = None
        return done
