"""Serving engine: prefill + decode over the unified model API.

The engine sits on top of the serve subsystem's cache mechanisms:

  * ``cache.CachePool``   — one padded cache buffer, per-slot alloc/free
    (the ``contiguous`` backend: every request owns a full max_len row).
  * ``paged.BlockManager`` — one block-pool buffer, per-request block tables
    (the ``paged`` backend: a request owns ceil(len / block_size) blocks),
    optionally with ref-counted content-hashed prefix caching.
  * ``scheduler.ContinuousScheduler`` — admission + per-step join/evict,
    FCFS/SJF queue ordering; paged pools admit by free *blocks*; admitted
    requests pass through the scheduler's prefill queue.

Every mode is the same engine loop. *Static* batching is the degenerate
scheduler configuration (all requests arrive at step 0 into a pool with one
slot per request, so there is exactly one admission round and no mid-flight
join/evict); *continuous* batching bounds the pool and lets the scheduler
join/evict per step. TP/DP-sharded decode is the same loop again with a
``sharded.ServeSharding`` plan installed (see serve/sharded.py).

Prefill (contiguous): attention-family models (dense / vlm / moe) run ONE
full forward pass capturing the per-layer K/V via ``return_cache``; ssm
(mamba2) runs ONE chunked-SSD pass that also returns the recurrent state;
the other recurrent families (hybrid / encdec) scan decode steps. Prefill is
per-request at the exact prompt length — no cross-request padding — so a
request's output never depends on what it was batched with, which is what
makes continuous and static batching produce identical per-request outputs.

Prefill (paged): prompts prefill in ``block_size`` chunks through each
request's block table, and chunks from up to ``prefill_lanes`` joining
requests pack into ONE jitted ``[P, block_size]`` dispatch per chunk-round
(padded lanes masked) — admitting N requests costs O(chunk-rounds)
dispatches instead of O(N x chunks). Lanes never interact: each lane writes
through its own table, pad positions write nothing, and MoE lanes carry
per-lane expert counts and per-lane routing capacity so batched chunked
routing equals each request's solo one-pass routing. With the prefix cache
on, a lane starts at its first non-cached block and skips the compute for
shared prompt blocks entirely.

Decode (the hot path): one jitted *horizon* dispatch runs up to
``decode_horizon`` steps entirely on device — ``lax.scan`` over the
single-step decode with on-device token selection (greedy argmax, or the
per-slot RNG lanes), token feedback, per-row ``pos`` advance, and per-row
budget/EOS stop masks (a finished row freezes: its token and position stop
advancing and its KV writes are masked) — returning only the ``[W, K]``
int32 token block. The scheduler intervenes at horizon boundaries instead
of every token, so host<->device traffic per generated token drops from a
full ``[B, vocab]`` logits fetch plus state re-uploads to ``1/K``-th of one
``[W, K]`` int32 fetch.

Between horizons the decode state is device-resident (``_DecodeState``):
last tokens, per-row ``pos``, per-row stop positions, and (paged) the block
tables live on device and receive *delta* scatters only at admission,
block growth, eviction, and preemption — never a per-step re-upload.

Both backends compact the decode batch to the live slots: the width is the
smallest power of two covering the active rows, rounded up to a multiple of
the mesh 'data' axis so the bucket shards evenly (see
``ServeSharding.bucket_shardings``). The paged bucket addresses the cache
through gathered block tables (compaction is free); the contiguous bucket
gathers/scatters the pool rows inside the same jitted horizon — on one
device or SPMD-sharded over the mesh. The saved work is reported as
``decode_rows_saved``.

Token selection: greedy by default (the exactness/verify path). With
``temperature > 0`` each slot samples on its own RNG lane —
``jax.random.fold_in`` on the slot id and the decode step — optionally
top-k-truncated, so lanes never interact across slots; the fold is
identical on- and off-horizon, so ``decode_horizon=1`` degenerates to the
classic one-step loop token for token.
"""
from __future__ import annotations

import contextlib
import functools
import math
import time
from collections import Counter, deque
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.configs.base import ArchConfig
from repro.models.api import Model, build_model
from repro.obs import NULL_PROFILER, NULL_TRACER, RunObs
from repro.serve.cache import CachePool
from repro.serve.elastic import ScalePlan, pool_capacity
from repro.serve.paged import BlockManager
from repro.serve.scheduler import ContinuousScheduler, ServeRequest
from repro.serve.tenant import SLOSlack, TenantAllocation, TenantRegistry

#: back-compat alias — the original single-file engine exported ``Request``
Request = ServeRequest

_ATTN_PREFILL_FAMILIES = ("dense", "vlm", "moe")
CACHE_BACKENDS = ("contiguous", "paged")


def _pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def _pow2_floor(n: int) -> int:
    b = 1
    while b * 2 <= n:
        b *= 2
    return b


def _bucket(n: int, cap: int, multiple: int = 1) -> int:
    """Compacted decode width: smallest power of two >= n, rounded up to a
    multiple of the mesh 'data' axis size (so bucketed rows shard evenly),
    capped at the pool width — a bounded number of XLA programs covers
    every live-slot count."""
    b = _pow2(max(n, 1))
    if multiple > 1:
        b = -(-b // multiple) * multiple
    return min(b, cap)


@dataclass
class ServeStats:
    n_requests: int
    new_tokens: int
    steps: int
    wall_s: float
    tokens_per_s: float
    slot_utilization: float           # mean active/n_slots over decode steps
    mean_latency_steps: float
    p95_latency_steps: float
    mean_latency_s: float
    max_active: int = 0               # peak concurrently-decoding requests
    # -- completion accounting -------------------------------------------------
    unfinished: int = 0               # NON-dropped requests that never
                                      # finished (or finished without
                                      # wall-clock stamps — e.g. evicted at
                                      # driver shutdown); they count as SLO
                                      # misses so silent losses can never
                                      # inflate attainment
    slo_attainment: float = 1.0       # fraction of NON-dropped requests
                                      # meeting their tenant's SLO (1.0 when
                                      # no tenant carries one). Dropped
                                      # requests are excluded from the
                                      # denominator — and surfaced in
                                      # ``dropped`` — so injected kills can
                                      # neither inflate nor deflate it.
    #: per-tenant latency + SLO summary (tenant id -> dict with
    #: p50/p99_latency_steps, p50/p99_latency_s, slo_attainment,
    #: n_requests, unfinished, preemptions) — None without tenant tags
    tenants: Optional[dict] = field(default=None)
    decode_rows_saved: float = 0.0    # live-slot compaction: fraction of
                                      # pool rows never decoded
    preemptions: int = 0              # paged: requests bounced on pool
                                      # pressure (regenerated exactly)
    block_report: Optional[dict] = field(default=None)
    # -- phase split + dispatch accounting ------------------------------------
    prefill_s: float = 0.0            # wall seconds inside prefill dispatch
    decode_s: float = 0.0             # wall seconds inside decode dispatch
    prefill_dispatches: int = 0       # jitted prefill calls (paged: one per
                                      # chunk-round across ALL joining lanes)
    decode_dispatches: int = 0        # jitted decode horizons (each covers
                                      # up to decode_horizon steps)
    # -- decode horizon -------------------------------------------------------
    decode_horizon: int = 1           # configured K: decode steps per
                                      # jitted dispatch
    host_syncs: int = 0               # device->host sync points (one [W, K]
                                      # int32 fetch per horizon + one id
                                      # fetch per prefill pick round)
    # -- prefix cache ---------------------------------------------------------
    prefix_blocks_total: int = 0      # prompt blocks allocated (paged)
    prefix_blocks_hit: int = 0        # of those, served from the cache
    prefix_hit_rate: float = 0.0
    # -- boundary-sampled series (obs.MetricsRegistry; live with tracing off) --
    mean_queue_depth: float = 0.0     # waiting requests at horizon boundaries
    max_queue_depth: int = 0
    mean_occupancy: float = 0.0       # pool occupancy at horizon boundaries
    max_occupancy: float = 0.0        # (paged: used blocks; contig: slots)
    # -- dispatch profiling (obs.prof; None with profiling off) ----------------
    decode_util: Optional[float] = None  # mean measured-vs-roofline
                                      # utilization over execute decode
                                      # dispatches; None = not measured (no
                                      # profiler, or a device without peaks)
    # -- fault injection (serve/chaos.py; all 0 without an injector) -----------
    faults_injected: int = 0          # faults applied at horizon boundaries
    recoveries: int = 0               # recovery actions taken (regenerate /
                                      # retry / restore / rescale / drop)
    dropped: int = 0                  # requests given up on by a recovery
                                      # path (bounded retries exhausted, or
                                      # the shrunken pool can never hold
                                      # them) — counted SEPARATELY from
                                      # unfinished
    # -- elastic reshapes (serve/elastic.py; all 0 without reshapes) -----------
    scale_ups: int = 0                # applied scale_up reshapes
    scale_downs: int = 0              # applied scale_down reshapes
    migrated_blocks: int = 0          # live blocks migrated across a
                                      # physical pool growth (grow_physical)
    replans: int = 0                  # allocator re-plans at reshape
                                      # boundaries (measured-rate refresh)
    #: RunObs.span totals, name -> {"s": seconds, "n": count}, of every
    #: span closed before the stats were built (all but ``serve.run``)
    spans: dict = field(default_factory=dict)


@dataclass
class _PrefillLane:
    """One live lane of the batched paged prefill: a joining request, its
    chunk cursor (starting past any prefix-cache hits), and its carried
    cross-chunk state (MoE expert counts; None for dense/vlm)."""
    req: ServeRequest
    prompt: np.ndarray
    ptr: int
    cap_row: int
    state: Optional[np.ndarray]


class _DecodeState:
    """Device-resident decode-loop state.

    The last token, per-row ``pos``, and per-row freeze position ``stop``
    (plus the paged block tables) stay on device between horizon
    dispatches; the host scatters *deltas* at admission, growth, eviction,
    and preemption only. ``stop`` is the position at which a row freezes
    (``prompt_len + max_new - 1`` — the budget's last write position + 1);
    a row is live while ``pos < stop``, so zeroed rows (idle slots, frozen
    evictees) are inert horizon padding. Sharded engines keep these arrays
    replicated — a few int32 per slot, delta-updated from the host — and
    the horizon gathers each bucket with the width's NamedSharding.
    """

    def __init__(self, n_slots: int, max_blocks: Optional[int] = None,
                 sharding=None):
        rep = sharding.replicated() if sharding is not None else None
        put = (lambda x: jax.device_put(x, rep)) if rep is not None \
            else (lambda x: x)
        self.tok = put(jnp.zeros((n_slots, 1), jnp.int32))
        self.pos = put(jnp.zeros((n_slots,), jnp.int32))
        self.stop = put(jnp.zeros((n_slots,), jnp.int32))
        self.tables = (put(jnp.full((n_slots, max_blocks), -1, jnp.int32))
                       if max_blocks else None)

    def set_rows(self, slots, toks, pos, stop) -> None:
        """Install freshly-prefilled rows (paged table rows arrive via
        ``set_tables`` from the pool's dirty-slot drain — admission marks
        its slots dirty, so the rows upload exactly once)."""
        idx = jnp.asarray(np.asarray(slots, np.int32))
        self.tok = self.tok.at[idx].set(
            jnp.asarray(np.asarray(toks, np.int32)[:, None]))
        self.pos = self.pos.at[idx].set(
            jnp.asarray(np.asarray(pos, np.int32)))
        self.stop = self.stop.at[idx].set(
            jnp.asarray(np.asarray(stop, np.int32)))

    def set_tables(self, slots, rows) -> None:
        idx = jnp.asarray(np.asarray(slots, np.int32))
        self.tables = self.tables.at[idx].set(
            jnp.asarray(np.asarray(rows, np.int32)))

    def freeze(self, slots) -> None:
        """stop=0 for vacated slots: frozen rows never advance, never write
        KV, and (paged) never scatter through a stale block table."""
        slots = sorted(slots)
        if slots:
            idx = jnp.asarray(np.asarray(slots, np.int32))
            self.stop = self.stop.at[idx].set(0)


def _scan_horizon(step_fn, pick, eos, cache, t, p, s, idx, step0, h):
    """The shared horizon scan: up to ``h`` decode steps on device over a
    gathered bucket — one ``step_fn(cache, tokens, pos, active)`` per step
    (contiguous or paged, the only difference between the backends' horizon
    programs), on-device selection, token feedback, per-row pos advance,
    and the budget/EOS stop masks. A row is live while ``p < s``; frozen
    rows keep (token, pos) and emit the -1 sentinel. Returns
    (cache, t, p, s, token block [W, h])."""
    def body(carry, k):
        cache, t, p, s = carry
        active = p < s
        logits, cache = step_fn(cache, t, p, active)
        nxt = pick(logits[:, -1], idx, step0 + k)
        emitted = jnp.where(active, nxt, -1)
        t = jnp.where(active[:, None], nxt[:, None], t)
        p = p + active.astype(jnp.int32)
        if eos is not None:
            s = jnp.where(active & (nxt == eos), p, s)
        return (cache, t, p, s), emitted

    (cache, t, p, s), toks = jax.lax.scan(
        body, (cache, t, p, s), jnp.arange(h, dtype=jnp.int32))
    return cache, t, p, s, toks.T


class ServeEngine:
    """Serving engine for any architecture family.

    ``n_slots=None`` (default) sizes the pool to the request set at each
    ``run``/``generate`` call — classic static batching. A fixed ``n_slots``
    bounds the pool and turns on continuous batching: the scheduler queues
    the overflow and joins/evicts requests per decode step.

    ``cache="paged"`` (attention families) swaps the per-slot max_len rows
    for the block-pool cache: admission becomes block-granular (a request
    costs blocks proportional to its length), prefill is chunked and
    lane-batched across joining requests (``prefill_lanes``), shared prompt
    prefixes hit the content-addressed block cache (``prefix_cache``), and
    decode compacts to the live slots. Outputs stay token-identical to
    contiguous.

    ``decode_horizon=K`` runs up to K decode steps per jitted dispatch, all
    on device (``decode_horizon=1`` is the classic per-token loop; any K is
    token-identical under greedy decoding). ``eos_token`` stops a row early
    when it emits that token (the EOS half of the per-row stop mask; budget
    stops always apply).

    ``tenants`` + ``allocation`` turn on Synergy-style multi-tenant serving
    (serve/tenant.py): requests carry tenant tags, ``policy="slo"`` orders
    admission by SLO slack, preemption victims are picked by LARGEST slack,
    and a ``TenantAllocation`` adds per-tenant cache-unit budgets at
    admission, per-tenant watermark headroom, prefill-lane shares, and a
    per-boundary horizon cap from the allocator's K knee. Every mechanism
    is ordering/allocation only — per-request outputs stay token-identical
    to the single-tenant engine (the exactness invariant ``--verify``
    checks end to end).

    ``tracer`` (an ``obs.Tracer``) turns on structured event tracing:
    admissions, evictions, preemptions (with cause), prefill rounds,
    decode-horizon dispatches, and block-pool traffic land in the ring
    buffer (see ``obs.EVENT_SCHEMA``). Tracing never touches computation —
    outputs are identical with it on or off — and with it off every hook
    is a single falsy check. A per-run ``obs.MetricsRegistry`` is always
    live regardless: counters/gauges sampled every ``metrics_every``
    horizon boundaries feed ``ServeStats`` and its queue-depth/occupancy
    summaries.

    Spans (``RunObs.span``) time each layer of the loop whatever the
    tracer: ``serve.run``, ``serve.step``, ``serve.admit``,
    ``serve.prefill``, ``serve.prefill_round``, ``serve.upload``,
    ``serve.grow``, ``serve.horizon``, ``serve.fetch`` and
    ``serve.unpack``. Each is a ``jax.profiler`` annotation (so a device
    trace shows it beside the device's operations) and a count and total
    in ``ServeStats.spans``; ``prefill_s`` and ``decode_s`` are the
    ``serve.prefill`` and ``serve.horizon`` totals.

    ``profiler`` (an ``obs.DispatchProfiler``) turns on dispatch-level
    profiling: every jitted hot path that ends in a host fetch —
    per-request contiguous prefill, K-step decode horizons (the
    compaction gather/scatter runs inside the horizon program, tagged by
    its ``full`` flag) — records wall time with compile-vs-execute
    attribution, an analytic roofline utilization ratio, and per-tenant
    cost shares. Read-only like tracing (outputs identical on or off; off
    costs one falsy check per site); held per-ENGINE, not per-run, so the
    seen-signature set spans warm-up runs.
    """

    def __init__(self, cfg: ArchConfig, params=None, max_len: int = 256,
                 rng=None, n_slots: Optional[int] = None,
                 policy: str = "fcfs", sharding=None,
                 cache: str = "contiguous", block_size: int = 16,
                 n_blocks: Optional[int] = None, watermark: float = 0.05,
                 temperature: float = 0.0, top_k: int = 0,
                 sample_seed: int = 0, prefill_lanes: int = 4,
                 prefix_cache: bool = True, decode_horizon: int = 8,
                 eos_token: Optional[int] = None,
                 tenants: Optional[TenantRegistry] = None,
                 allocation: Optional[TenantAllocation] = None,
                 tracer=None, metrics_every: int = 1, profiler=None,
                 injector=None, max_admit_retries: int = 4,
                 elastic=None, profile_store=None,
                 record_logits: bool = False):
        if cache not in CACHE_BACKENDS:
            raise ValueError(f"unknown cache backend {cache!r}; "
                             f"known: {CACHE_BACKENDS}")
        if cache == "paged":
            if cfg.family not in _ATTN_PREFILL_FAMILIES:
                raise ValueError(
                    f"cache='paged' needs an attention family "
                    f"(got {cfg.family!r}: recurrent state is O(1))")
            cfg = cfg.replace(decode_attention="paged")
        self.cfg = cfg
        self.model: Model = build_model(cfg)
        self.max_len = max_len
        self.n_slots = n_slots
        self.policy = policy
        self.sharding = sharding
        self.cache_kind = cache
        self.block_size = block_size
        self.n_blocks = n_blocks
        self.watermark = watermark
        self.prefill_lanes = max(int(prefill_lanes), 1)
        self.prefix_cache = bool(prefix_cache)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.decode_horizon = max(int(decode_horizon), 1)
        self.eos_token = None if eos_token is None else int(eos_token)
        self.tenants = tenants
        self.allocation = allocation
        #: event tracer (obs.Tracer) — defaults to the falsy NullTracer, so
        #: every hook below is one truthiness check when tracing is off.
        self.tracer = tracer if tracer is not None else NULL_TRACER
        #: dispatch profiler (obs.DispatchProfiler) — same falsy-default
        #: contract; engine-lifetime (not per-run) so first-call-per-
        #: signature compile attribution survives warm-up runs.
        self.profiler = profiler if profiler is not None else NULL_PROFILER
        #: sample the metrics gauges into time series every N decode
        #: boundaries (0 disables the series; the gauges still update, so
        #: the stats' queue/occupancy summaries survive via the fallback).
        self.metrics_every = max(int(metrics_every), 0)
        #: fault injector (chaos.FaultInjector) — None in production runs.
        #: With one installed the engine polls it at every horizon
        #: boundary, applies due faults, audits block conservation after
        #: each, and swaps its crash-on-exhaustion paths for graceful
        #: degradation (bounded retry-with-backoff, then drop).
        self.injector = injector
        self.max_admit_retries = max(int(max_admit_retries), 1)
        #: elastic controller (elastic.ElasticController) — None disables
        #: proactive reshapes. Polled at every horizon boundary after fault
        #: application; an emitted ScalePlan is applied in place (pool
        #: shrink/expand + mesh re-bucket + allocator re-plan) without
        #: dropping in-flight requests.
        self.elastic = elastic
        #: measured-rate store (obs.prof.ProfileStore) — when installed
        #: alongside a profiler, every reshape re-plan folds this run's
        #: dispatch profile in and re-fits per-token decode rates, so the
        #: allocator's knee model tracks measurement instead of analytic
        #: constants (ROADMAP item 1's first slice).
        self.profile_store = profile_store
        #: keep each request's prompt logits on ``prefill_logits`` — one
        #: extra [vocab] fetch per prefill, for checks against a reference
        self.record_logits = bool(record_logits)
        #: the allocation as constructed — reshapes re-plan in place, so
        #: ``run`` restores this before every run to keep warm runs
        #: identical.
        self._allocation0 = allocation
        self._dmult_full = (sharding.axis_size("data")
                            if sharding is not None else 1)
        self._dmult = self._dmult_full
        #: the most recent run's cache pool (set by ``run``): the audit
        #: surface for chaos tests and replay harnesses.
        self.pool = None
        if policy == "slo" and tenants is None:
            raise ValueError("policy='slo' needs a TenantRegistry "
                             "(tenants=...) to compute slack")
        if allocation is not None and tenants is None:
            raise ValueError("a TenantAllocation needs its TenantRegistry "
                             "(tenants=...) installed too")
        self._sample_key = jax.random.key(sample_seed)
        rng = rng if rng is not None else jax.random.key(0)
        with self._rules():
            self.params = (params if params is not None
                           else self.model.init(rng))
        if sharding is not None:
            self.params = jax.device_put(self.params, sharding.param_sharding)
        self._pick_device = self._pick_fn()
        self._pick = jax.jit(self._pick_device)
        if cache == "paged":
            self._prefill = self._paged_prefill_fn()
            self._horizon = self._paged_horizon_fn()
        else:
            self._prefill = jax.jit(self._prefill_fn())
            self._horizon = self._contiguous_horizon_fn()

    def _rules(self):
        """Logical-axis rules context (no-op off-mesh / unsharded)."""
        return (self.sharding.rules() if self.sharding is not None
                else contextlib.nullcontext())

    # -- prefill ---------------------------------------------------------------
    def _prefill_fn(self):
        """(params, tokens[B, S]) -> (last logits [B, 1, V], cache pytree).
        Each variant is named ``serve_prefill``, so its XLA module (and the
        device trace's module line) reads ``jit_serve_prefill``."""
        cfg, model, max_len = self.cfg, self.model, self.max_len

        if cfg.family in _ATTN_PREFILL_FAMILIES:
            def serve_prefill(params, tokens):
                """One-pass attention prefill via the ``return_cache`` hook."""
                logits, (k, v) = model.module.forward(cfg, params, tokens,
                                                      return_cache=True)
                pad = max_len - tokens.shape[1]
                widths = ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))
                return logits[:, -1:], {"k": jnp.pad(k, widths),
                                        "v": jnp.pad(v, widths)}
            return serve_prefill

        if cfg.family == "ssm":
            def serve_prefill(params, tokens):
                """Chunked-SSD prefill: one pass over the prompt that
                seeds the recurrent state (mamba2.prefill)."""
                return model.module.prefill(cfg, params, tokens)
            return serve_prefill

        def serve_prefill(params, tokens):
            """Recurrent prefill: scan decode steps (O(1) state per step)."""
            b, s = tokens.shape
            cache = model.init_cache(b, max_len)
            logits0 = jnp.zeros((b, 1, cfg.vocab_size), jnp.dtype(cfg.dtype))

            def body(carry, t):
                cache, _ = carry
                logits, cache = model.decode_step(
                    params, cache, tokens[:, t][:, None], t)
                return (cache, logits), None

            (cache, logits), _ = jax.lax.scan(body, (cache, logits0),
                                              jnp.arange(s))
            return logits, cache
        return serve_prefill

    def _paged_prefill_fn(self):
        """Jitted lane-batched chunk prefill; ``cap`` is static (it sizes
        the MoE dispatch buffers — per-lane effective capacity is the traced
        ``cap_rows``, so one program covers every prompt length). The pool
        ``buffers`` are donated: the returned pool is the same memory,
        written in place. Its XLA module is ``jit_serve_prefill_round``."""
        mod, cfg = self.model.module, self.cfg

        @functools.partial(jax.jit, static_argnames=("cap",),
                           donate_argnames=("buffers",))
        def serve_prefill_round(params, buffers, tokens, starts, n_valid,
                                tables, state, cap_rows, cap):
            return mod.paged_prefill_chunk(cfg, params, buffers, tokens,
                                           starts, tables, state, cap,
                                           n_valid=n_valid,
                                           cap_rows=cap_rows)
        return serve_prefill_round

    # -- token selection (greedy / per-slot RNG lanes) -------------------------
    def _pick_fn(self):
        """On-device token selection: logits [N, V] -> token ids [N] int32.

        Greedy argmax unless ``temperature > 0``; sampling folds (slot id,
        decode step) into per-slot RNG lanes. Traced both inside the decode
        horizon's scan body and as the stand-alone jitted ``self._pick`` the
        prefill sites call — only the [N] int32 ids ever cross to the host,
        never the [N, vocab] logits."""
        temp, tk, base = self.temperature, self.top_k, self._sample_key

        def pick(logits, slots, step):
            if temp <= 0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            key = jax.random.fold_in(base, step)
            keys = jax.vmap(lambda s: jax.random.fold_in(key, s))(slots)
            scaled = logits.astype(jnp.float32) / temp
            if tk:
                kth = jax.lax.top_k(scaled, tk)[0][..., -1:]
                scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
            return jax.vmap(jax.random.categorical)(keys,
                                                    scaled).astype(jnp.int32)
        return pick

    def _select_tokens(self, logits, slots, step, c=None) -> np.ndarray:
        """logits [N, V] -> next tokens [N] (host). Selection runs on device
        (jitted ``_pick``) and only the int32 ids transfer. Prefill call
        sites pass ``~step`` (the complement lane) so a slot's
        prefill-sampled token and its first decode token — which happen at
        the same scheduler step — never draw on the same key."""
        ids = self._pick(logits, jnp.asarray(np.asarray(slots, np.int32)),
                         jnp.int32(step))
        if c is not None:
            c.inc("host_syncs")
        return np.asarray(ids, np.int32)

    # -- decode horizons -------------------------------------------------------
    def _contiguous_horizon_fn(self):
        """Jitted multi-step decode horizon over the pooled cache: gather
        the bucket's rows (cache + state) once, ``lax.scan`` up to ``h``
        decode steps with on-device selection / token feedback / stop
        masks, scatter the rows back. Rows decode independently, so the
        gathered rows' outputs equal a full-pool decode's — the
        gather-decode-scatter compaction, now inside the horizon and also
        SPMD-sharded when a plan is installed."""
        model, max_len = self.model, self.max_len
        from repro.serve.cache import _batch_axis
        probe_a = jax.eval_shape(lambda: model.init_cache(3, max_len))
        probe_b = jax.eval_shape(lambda: model.init_cache(5, max_len))
        axes = jax.tree_util.tree_map(_batch_axis, probe_a, probe_b)
        pick = self._pick_device
        masked = self.cfg.family in _ATTN_PREFILL_FAMILIES
        eos = self.eos_token
        plan = self.sharding

        def serve_decode_horizon(params, buffers, tok, pos, stop, idx, step0,
                                 h, full):
            if full:
                # identity bucket: every slot decodes (idle rows are frozen
                # and inert), so skip the gather/scatter copies of the pool
                # the old full-width decode path never paid.
                sub, t, p, s = buffers, tok, pos, stop
            else:
                sub = jax.tree_util.tree_map(
                    lambda b, ax: jnp.take(b, idx, axis=ax), buffers, axes)
                t, p, s = tok[idx], pos[idx], stop[idx]
            if plan is not None:
                bsh = plan.bucket_shardings(idx.shape[0])
                if plan.cache_pspec is not None:
                    sub = jax.tree_util.tree_map(
                        lambda x, sp: jax.lax.with_sharding_constraint(
                            x, NamedSharding(plan.mesh, sp)),
                        sub, plan.cache_pspec)
                t = jax.lax.with_sharding_constraint(t, bsh["tokens"])
                p = jax.lax.with_sharding_constraint(p, bsh["pos"])
                s = jax.lax.with_sharding_constraint(s, bsh["pos"])

            def step_fn(sub, t, p, active):
                if masked:        # frozen rows stop writing KV
                    return model.decode_step(params, sub, t, p,
                                             write_valid=active)
                # recurrent state has no positional write to mask: frozen
                # rows recompute garbage state, discarded at slot reuse.
                return model.decode_step(params, sub, t, p)

            sub, t, p, s, blk = _scan_horizon(step_fn, pick, eos, sub,
                                              t, p, s, idx, step0, h)
            if full:
                return sub, t, p, s, blk
            buffers = jax.tree_util.tree_map(
                lambda b, nb, ax: b.at[(slice(None),) * ax + (idx,)].set(nb),
                buffers, sub, axes)
            tok = tok.at[idx].set(t)
            pos = pos.at[idx].set(p)
            stop = stop.at[idx].set(s)
            return buffers, tok, pos, stop, blk

        return self._jit_horizon(serve_decode_horizon)

    def _paged_horizon_fn(self):
        """Jitted multi-step decode horizon over the block pool: gather the
        bucket's tokens/pos/stop/tables (compaction through block tables is
        free), ``lax.scan`` up to ``h`` steps, scatter the state back.
        Frozen rows mask their KV writes, so a vacated slot's stale table
        can never scatter into a recycled block."""
        model = self.model
        pick = self._pick_device
        eos = self.eos_token
        plan = self.sharding

        def serve_decode_horizon(params, buffers, tok, pos, stop, tables, idx,
                                 step0, h, full):
            if full:
                t, p, s, tb = tok, pos, stop, tables
            else:
                t, p, s, tb = tok[idx], pos[idx], stop[idx], tables[idx]
            if plan is not None:
                bsh = plan.bucket_shardings(idx.shape[0])
                t = jax.lax.with_sharding_constraint(t, bsh["tokens"])
                p = jax.lax.with_sharding_constraint(p, bsh["pos"])
                s = jax.lax.with_sharding_constraint(s, bsh["pos"])
                tb = jax.lax.with_sharding_constraint(tb, bsh["tables"])

            def step_fn(buffers, t, p, active):
                return model.paged_decode_step(params, buffers, t, p, tb,
                                               write_valid=active)

            buffers, t, p, s, blk = _scan_horizon(step_fn, pick, eos,
                                                  buffers, t, p, s, idx,
                                                  step0, h)
            if full:
                return buffers, t, p, s, blk
            tok = tok.at[idx].set(t)
            pos = pos.at[idx].set(p)
            stop = stop.at[idx].set(s)
            return buffers, tok, pos, stop, blk

        return self._jit_horizon(serve_decode_horizon, donate=True)

    def _jit_horizon(self, horizon, donate: bool = False):
        """jit with ``h`` (scan length) and ``full`` (identity bucket —
        no gather/scatter) static; sharded plans pin the cache to its
        NamedSharding and the state arrays to replicated so input
        shardings stay stable across calls. ``donate`` gives the cache
        ``buffers`` to the program, so the returned pool is written in
        place (the paged pool; the contiguous horizon's pool is not
        donated). Both backends name the function
        ``serve_decode_horizon``: one XLA module name,
        ``jit_serve_decode_horizon``, for every horizon program."""
        plan = self.sharding
        kw = dict(static_argnames=("h", "full"),
                  donate_argnames=("buffers",) if donate else ())
        if plan is not None:
            rep = plan.replicated()
            kw["out_shardings"] = (plan.cache_sharding, rep, rep, rep, rep)
        return jax.jit(horizon, **kw)

    # -- the engine loop ---------------------------------------------------------
    def run(self, requests: List[ServeRequest]
            ) -> Tuple[List[ServeRequest], ServeStats]:
        """Serve ``requests`` to completion; returns (requests, stats).
        The whole call, stats included, is the ``serve.run`` span."""
        reqs = list(requests)
        c = RunObs(self.tracer)
        with c.span("serve.run", requests=len(reqs)):
            stats = self._run(reqs, c)
        return reqs, stats

    def _run(self, reqs: List[ServeRequest], c: RunObs) -> ServeStats:
        n_slots = self.n_slots if self.n_slots else max(len(reqs), 1)
        if self.injector is not None:
            # re-arm per run: warm-up double-runs and determinism checks
            # must replay identical chaos (same schedule, same RNG stream)
            self.injector.bind(vocab_size=self.cfg.vocab_size,
                               max_len=self.max_len, n_slots=n_slots)
            self.injector.reset()
        if self.elastic is not None:
            self.elastic.reset()
        # reshapes re-plan the allocation in place mid-run: restore the
        # constructed plan so warm-up double-runs replay identically.
        self.allocation = self._allocation0
        #: live mesh bucketing multiple — a device_fail reshape collapses
        #: it to 1 (non-divisible buckets fall back to replicated
        #: shardings: degraded but exact), a device_join restores it.
        self._dmult_full = (self.sharding.axis_size("data")
                            if self.sharding is not None else 1)
        self._dmult = self._dmult_full
        tr = c.tracer
        if tr:
            tr.step = 0.0
            tr.emit("run_start", backend=self.cache_kind, n_slots=n_slots,
                    horizon=self.decode_horizon, n_requests=len(reqs))
        t0 = time.perf_counter()
        with self._rules():
            if self.cache_kind == "paged":
                self._run_paged(reqs, n_slots, c)
            else:
                self._run_contiguous(reqs, n_slots, c)

        wall = time.perf_counter() - t0
        if tr:
            tr.emit("run_end", steps=c.value("steps"), wall_s=wall)
        return self._stats(reqs, c, n_slots, wall)

    # -- stats aggregation -----------------------------------------------------
    def _finished(self, r: ServeRequest) -> bool:
        """A request counts as finished only with BOTH clocks stamped:
        ``latency_s is None`` (evicted mid-run at driver shutdown, or
        never admitted) makes it ``unfinished`` — explicitly counted, and
        an SLO miss, so drops can never inflate attainment."""
        return (r.done and r.latency_steps is not None
                and r.latency_s is not None)

    def _meets_slo(self, r: ServeRequest) -> bool:
        """Whether ``r`` finished inside its tenant's SLO (both clocks
        when both targets are set; unfinished is always a miss; a tenant
        without targets only asks for completion)."""
        if not self._finished(r):
            return False
        t = self.tenants.get(r.tenant) if self.tenants is not None else None
        if t is None:
            return True
        if t.slo_steps is not None and r.latency_steps > t.slo_steps:
            return False
        if t.slo_s is not None and r.latency_s > t.slo_s:
            return False
        return True

    def _tenant_stats(self, reqs) -> Optional[dict]:
        """Per-tenant p50/p99 latency (steps + wall) and SLO attainment —
        None when neither a registry nor a non-default tag is present."""
        tids = sorted({r.tenant for r in reqs})
        if self.tenants is None and tids in ([], ["default"]):
            return None
        out = {}
        for tid in tids:
            all_rs = [r for r in reqs if r.tenant == tid]
            rs = [r for r in all_rs if not r.dropped]   # scored set
            steps = [r.latency_steps for r in rs if self._finished(r)]
            walls = [r.latency_s for r in rs if self._finished(r)]
            t = self.tenants.get(tid) if self.tenants is not None else None
            met = sum(1 for r in rs if self._meets_slo(r))
            out[tid] = {
                "n_requests": len(all_rs),
                "unfinished": sum(1 for r in rs if not self._finished(r)),
                "dropped": len(all_rs) - len(rs),
                "preemptions": sum(r.n_preempted for r in rs),
                "p50_latency_steps": (float(np.percentile(steps, 50))
                                      if steps else 0.0),
                "p99_latency_steps": (float(np.percentile(steps, 99))
                                      if steps else 0.0),
                "p50_latency_s": (float(np.percentile(walls, 50))
                                  if walls else 0.0),
                "p99_latency_s": (float(np.percentile(walls, 99))
                                  if walls else 0.0),
                "slo_steps": t.slo_steps if t is not None else None,
                "slo_s": t.slo_s if t is not None else None,
                "slo_attainment": met / len(rs) if rs else 1.0,
            }
        return out

    def _stats(self, reqs, c: RunObs, n_slots, wall) -> ServeStats:
        """Fold the run's metrics registry (plus the per-request latency
        stamps, which stay authoritative) into a ``ServeStats``."""
        m = c.metrics
        new_tokens = sum(len(r.output) for r in reqs)
        lat_steps = [r.latency_steps for r in reqs
                     if r.latency_steps is not None]
        lat_wall = [r.latency_s for r in reqs if r.latency_s is not None]
        steps = int(m.value("steps"))
        rows_possible = steps * n_slots
        hit, total = int(m.value("prefix_hits")), int(m.value("prefix_total"))
        # fault-dropped requests leave the scored set entirely: they are
        # counted in ``dropped``, not ``unfinished``, and excluded from
        # slo_attainment's denominator — an injected kill must neither
        # inflate attainment (drop the misses) nor deflate it (score
        # requests the injector made unservable).
        scored = [r for r in reqs if not r.dropped]
        met = sum(1 for r in scored if self._meets_slo(r))
        qd_mean, qd_max = m.series_stats("queue_depth")
        occ_mean, occ_max = m.series_stats("occupancy")
        stats = ServeStats(
            n_requests=len(reqs),
            new_tokens=new_tokens,
            steps=steps,
            wall_s=wall,
            tokens_per_s=new_tokens / wall if wall > 0 else 0.0,
            slot_utilization=m.value("util_acc") / steps if steps else 0.0,
            mean_latency_steps=float(np.mean(lat_steps)) if lat_steps else 0.0,
            p95_latency_steps=(float(np.percentile(lat_steps, 95))
                               if lat_steps else 0.0),
            mean_latency_s=float(np.mean(lat_wall)) if lat_wall else 0.0,
            max_active=int(m.value("max_active")),
            decode_rows_saved=(1.0 - m.value("rows_decoded") / rows_possible
                               if rows_possible else 0.0),
            preemptions=int(m.value("preemptions")),
            block_report=c.block_report,
            prefill_s=m.value("span_s[serve.prefill]"),
            decode_s=m.value("span_s[serve.horizon]"),
            prefill_dispatches=int(m.value("prefill_dispatches")),
            decode_dispatches=int(m.value("decode_dispatches")),
            decode_horizon=self.decode_horizon,
            host_syncs=int(m.value("host_syncs")),
            prefix_blocks_total=total,
            prefix_blocks_hit=hit,
            prefix_hit_rate=hit / total if total else 0.0,
            unfinished=sum(1 for r in scored if not self._finished(r)),
            slo_attainment=met / len(scored) if scored else 1.0,
            faults_injected=int(m.value("faults_injected")),
            recoveries=int(m.value("recoveries")),
            dropped=len(reqs) - len(scored),
            tenants=self._tenant_stats(reqs),
            mean_queue_depth=qd_mean,
            max_queue_depth=int(qd_max),
            mean_occupancy=occ_mean,
            max_occupancy=occ_max,
            decode_util=(m.series_stats("util[decode]")[0]
                         if "util[decode]" in m.gauges else None),
            scale_ups=int(m.value("scale_ups")),
            scale_downs=int(m.value("scale_downs")),
            migrated_blocks=int(m.value("migrated_blocks")),
            replans=int(m.value("replans")),
            spans=c.spans(),
        )
        return stats

    def _sample_boundary(self, sched, pool, c: RunObs, n_slots: int) -> None:
        """Update the live gauges after a decode boundary and, every
        ``metrics_every`` boundaries, snapshot them (and every counter)
        into the registry's time series — the substrate for the stats'
        queue/occupancy summaries and ``trace_report``'s timelines. Always
        on: a handful of float stores per horizon (not per token)."""
        m = c.metrics
        c.boundaries += 1
        m.set("queue_depth", len(sched.waiting))
        m.set("active", len(sched.active))
        if self.cache_kind == "paged":
            occ = (1.0 - pool.free_blocks / pool.n_blocks
                   if pool.n_blocks else 0.0)
        else:
            # live capacity, not physical slots: a reshape-revoked slot no
            # longer counts as headroom the elastic controller could fill.
            cap = getattr(pool, "capacity", n_slots)
            occ = len(sched.active) / cap if cap else 0.0
        m.set("occupancy", occ)
        every = self.metrics_every
        if every and c.boundaries % every == 0:
            if self.tenants is not None:
                live = list(sched.waiting) + list(sched.active.values())
                for t in self.tenants:
                    slk = min((self._slack(r, sched.step) for r in live
                               if r.tenant == t.tenant_id),
                              default=math.inf)
                    if math.isfinite(slk):
                        m.set(f"slack[{t.tenant_id}]", slk)
            m.sample(sched.step)

    # -- horizon scheduling helpers (host side) --------------------------------
    def _make_sched(self, pool) -> ContinuousScheduler:
        """The scheduler for one run: SLO-slack ordering when asked for
        (``policy='slo'`` resolves against the tenant registry) and the
        per-tenant budget check when an allocation is installed."""
        policy = (SLOSlack(self.tenants) if self.policy == "slo"
                  else self.policy)
        return ContinuousScheduler(pool, policy, allocation=self.allocation,
                                   tracer=self.tracer)

    def _slack(self, req, step) -> float:
        """SLO slack in decode steps (+inf without a registry or SLO)."""
        if self.tenants is None:
            return math.inf
        return self.tenants.slack(req, step)

    def _evict(self, sched, state: _DecodeState, c: RunObs):
        """Evict finished requests and freeze their device rows, so a
        vacated slot gathered as horizon padding can never decode as live
        (or, paged, write KV through a stale block table)."""
        done_slots = [s for s, r in sched.active.items() if r.done]
        out = sched.evict_finished()
        self._upload(c, state.freeze, done_slots)
        if c.tracer:
            for slot, r in zip(done_slots, out):
                t = (self.tenants.get(r.tenant)
                     if self.tenants is not None else None)
                c.tracer.emit(
                    "evict", req=r.job_id, tenant=r.tenant, slot=slot,
                    latency_steps=r.latency_steps,
                    finished_early=r.finished_early,
                    slo_steps=t.slo_steps if t is not None else None,
                    met=self._meets_slo(r))
        return out

    @staticmethod
    def _upload(c: RunObs, fn, slots, *rows) -> None:
        """One ``_DecodeState`` delta scatter (``set_rows``, ``set_tables``
        or ``freeze``) over ``slots``, as a ``serve.upload`` span; none
        when there are no rows to scatter."""
        if len(slots):
            with c.span("serve.upload", rows=len(slots)):
                fn(slots, *rows)

    # -- fault injection + recovery (serve/chaos.py) ---------------------------
    def _fault_hold(self, sched):
        """The admission-hold hook (``tenant_slowdown`` / ``defer_storm``
        windows): None — the common case — costs the scheduler nothing."""
        inj = self.injector
        if inj is None or not inj.has_holds(sched.step):
            return None
        return lambda r: inj.hold_cause(r, sched.step)

    def _drop(self, sched, req, c: RunObs, cause: str) -> None:
        """Give up on a waiting request (a recovery path exhausted): it
        leaves the queue with ``dropped`` set so stats score it separately
        from unfinished work."""
        if req in sched.waiting:
            sched.waiting.remove(req)
        req.dropped = True
        req.drop_cause = cause
        c.inc("recoveries")
        if c.tracer:
            c.tracer.emit("recover", kind=cause, action="drop",
                          req=req.job_id, detail=req.n_retries)

    def _pending_units(self, pool, step) -> int:
        """Capacity units scheduled to ARRIVE after ``step``: pending
        ``pool_restore`` / ``device_join`` faults plus the elastic
        controller's unexercised scale-up headroom — the difference
        between "this pool will never hold it" (drop) and "capacity is
        coming back" (hold under bounded retry)."""
        pend = 0
        if self.injector is not None and step is not None:
            pend += self.injector.pending_capacity(step)
        if self.elastic is not None:
            pend += self.elastic.pending_units(pool)
        return pend

    def _can_ever_admit(self, pool, req, step=None) -> bool:
        """Whether the pool capacity — current PLUS capacity scheduled to
        return (pending restores/joins, proactive scale-up headroom) —
        could ever admit ``req``: the difference between "wait for blocks"
        (retry/hold) and "will never hold it" (drop). Mirrors
        ``validate_request``'s arithmetic against the live ``n_blocks``.
        Conservative on prefix hits: a request droppable by this rule
        might have admitted via cached blocks, but bounded retries have
        already been burned by then."""
        if not hasattr(pool, "blocks_for"):
            return True                      # contiguous slots never vanish
        need = len(req.prompt) + req.max_new_tokens
        if need > pool.max_len:
            return False                     # no capacity fixes the span
        cap = pool.n_blocks + self._pending_units(pool, step)
        return (pool.blocks_for(need) <= cap
                and pool.blocks_for(len(req.prompt)) + pool.watermark_blocks
                <= cap)

    def _chaos_admission(self, sched, pool, c: RunObs) -> None:
        """Bounded retry-with-backoff for waiting requests a ``pool_shrink``
        left unservable: each due retry re-checks capacity (a restore
        resets the clock), backs off exponentially, and after
        ``max_admit_retries`` the request drops instead of wedging the
        queue forever."""
        for r in list(sched.waiting):
            if r.arrival_time > sched.step:
                continue
            if self._can_ever_admit(pool, r, step=sched.step):
                r.n_retries = 0              # capacity is back (or coming
                continue                     # back): clean slate
            if sched.step < r.next_retry:
                continue
            r.n_retries += 1
            if r.n_retries > self.max_admit_retries:
                self._drop(sched, r, c, cause="pool_shrink")
                continue
            r.next_retry = sched.step + float(2 ** r.n_retries)
            c.inc("recoveries")
            if c.tracer:
                c.tracer.emit("recover", kind="pool_shrink", action="retry",
                              req=r.job_id, detail=r.n_retries)

    def _next_unblock(self, sched) -> Optional[float]:
        """The earliest future step at which a stalled queue could move
        again: an arrival, a hold release, a pending fault, or a backoff
        retry — where the idle clock jumps to instead of crashing when
        chaos has made every waiting request momentarily inadmissible."""
        cands = [r.arrival_time for r in sched.waiting
                 if r.arrival_time > sched.step]
        cands += [r.next_retry for r in sched.waiting
                  if r.next_retry > sched.step]
        inj = self.injector
        if inj is not None:
            for s in (inj.release_step(sched.step),
                      inj.next_fault_step(sched.step)):
                if s is not None and s > sched.step:
                    cands.append(s)
        return min(cands, default=None)

    def _apply_faults(self, sched, pool, state, c: RunObs, n_slots: int,
                      reqs: List[ServeRequest]) -> None:
        """Apply every due fault at this boundary, then audit block
        conservation (paged) — a fault that corrupts pool accounting must
        fail HERE, at the injection site, not decodes later."""
        for f in self.injector.due(sched.step):
            self._apply_fault(f, sched, pool, state, c, n_slots, reqs)
            self.injector.injected.append((f.kind, float(sched.step)))
            c.inc("faults_injected")
            if isinstance(pool, BlockManager):
                pool.audit()

    def _apply_fault(self, f, sched, pool, state, c: RunObs, n_slots: int,
                     reqs: List[ServeRequest]) -> None:
        tr = c.tracer
        inj = self.injector
        paged = isinstance(pool, BlockManager)
        if f.kind == "pool_shrink":
            took = pool.shrink(f.blocks) if paged else 0
            if tr:
                tr.emit("fault_inject", kind=f.kind, target=None, mag=took)
            if took and f.restore_after is not None:
                inj.defer_restore(f, float(sched.step), took)
            if took and self.allocation is not None:
                pool.tenant_reserves = self.allocation.rescaled_reserves(
                    pool.n_blocks)
                c.inc("recoveries")
                if tr:
                    tr.emit("recover", kind=f.kind, action="reserve_rescale",
                            req=None, detail=sum(
                                pool.tenant_reserves.values()))
        elif f.kind == "pool_restore":
            got = pool.expand(f.blocks) if paged else 0
            if got and self.allocation is not None:
                pool.tenant_reserves = self.allocation.rescaled_reserves(
                    pool.n_blocks)
            c.inc("recoveries")
            if tr:
                tr.emit("recover", kind="pool_shrink", action="restore",
                        req=None, detail=got)
        elif f.kind == "device_fail":
            # a data-parallel device leaves: its share of the pool is
            # revoked AND the mesh bucketing multiple collapses to 1, so
            # subsequent buckets fall back to replicated shardings
            # (degraded but exact). In-flight rows keep their device
            # state — the reshape is reorder-only.
            took = self._apply_scale(sched, pool, state, c, ScalePlan(
                kind="scale_down", units=f.blocks, reason="device_fail",
                step=float(sched.step), dmult=1))
            if tr:
                tr.emit("fault_inject", kind=f.kind, target=None, mag=took)
            if f.restore_after is not None:
                # schedule the join even when 0 blocks were revocable —
                # the mesh multiple must still be restored.
                inj.defer_restore(f, float(sched.step), took)
        elif f.kind == "device_join":
            got = self._apply_scale(sched, pool, state, c, ScalePlan(
                kind="scale_up", units=f.blocks, reason="device_join",
                step=float(sched.step), dmult=self._dmult_full))
            c.inc("recoveries")
            if tr:
                tr.emit("recover", kind="device_fail", action="restore",
                        req=None, detail=got)
        elif f.kind == "slot_kill":
            slot = inj.pick_slot(list(sched.active), f.slot)
            if slot is None:
                if tr:
                    tr.emit("fault_inject", kind=f.kind, target=None, mag=0)
                return
            victim = sched.active[slot]
            if tr:
                tr.emit("fault_inject", kind=f.kind, target=slot, mag=1)
            # the device state is declared lost: preempt-and-regenerate is
            # exactly the recovery — blocks freed, the row frozen, tokens
            # regenerated identically after re-admission (deterministic
            # prefill + greedy decode), so outputs stay token-identical.
            sched.preempt(victim, cause="slot_kill")
            state.freeze([slot])
            c.inc("preemptions")
            c.inc("recoveries")
            if tr:
                tr.emit("recover", kind=f.kind, action="regenerate",
                        req=victim.job_id, detail=victim.n_preempted)
        elif f.kind in ("tenant_slowdown", "defer_storm"):
            tenant = f.tenant if f.kind == "tenant_slowdown" else None
            inj.hold(tenant, float(sched.step) + f.duration)
            if tr:
                tr.emit("fault_inject", kind=f.kind, target=tenant,
                        mag=f.duration)
        elif f.kind == "arrival_burst":
            burst = inj.burst_requests(f)
            if tr:
                tr.emit("fault_inject", kind=f.kind, target=f.tenant,
                        mag=len(burst))
            for r in burst:
                r.job_id = len(reqs)
                r.arrival_time = float(sched.step)
                reqs.append(r)          # stats score the injected load too
                try:
                    sched.submit(r)
                except ValueError:
                    # the CURRENT pool can never fit it — but a scheduled
                    # restore/join may bring that capacity back: hold it
                    # for the bounded-retry path instead of dropping.
                    if self._can_ever_admit(pool, r, step=sched.step):
                        sched.park(r)
                        c.inc("recoveries")
                        if tr:
                            tr.emit("recover", kind=f.kind, action="retry",
                                    req=r.job_id, detail=0)
                    else:
                        self._drop(sched, r, c, cause="burst_unservable")
        elif f.kind == "prefix_flush":
            flushed = pool.flush_prefix() if paged else 0
            if tr:
                tr.emit("fault_inject", kind=f.kind, target=None,
                        mag=flushed)

    # -- elastic reshapes (serve/elastic.py) -----------------------------------
    def _apply_scale(self, sched, pool, state, c: RunObs, plan) -> int:
        """Apply one ``ScalePlan`` at a horizon boundary — the ONLY place
        reshapes happen, so every device-resident row (KV blocks, block
        tables, decode tok/pos/stop) is at a consistent step when capacity
        moves. Scale-down revokes idle capacity (in-flight rows keep their
        state); scale-up returns revoked capacity first and, paged, grows
        the pool PAST its constructed size via ``grow_physical`` — the
        live blocks migrate into the reallocated buffers, timed and traced
        as a ``migrate`` event. A ``dmult`` change re-buckets the mesh
        'data' axis for every subsequent dispatch (widths that stop
        dividing it fall back to replicated shardings — degraded but
        exact). Afterwards tenant reserves re-split against the new
        capacity and the allocator re-plans (``_replan``). Returns the
        capacity units actually moved."""
        tr = c.tracer
        paged = isinstance(pool, BlockManager)
        old_dmult = self._dmult
        if plan.kind == "scale_down":
            moved = pool.shrink(plan.units)
        else:
            moved = pool.expand(plan.units)  # revoked ledger first
            extra = plan.units - moved
            if extra > 0 and paged:
                live = (pool._total_blocks - len(pool._free_blocks)
                        - len(pool._revoked))
                t0 = time.perf_counter()
                sh = (self.sharding.cache_sharding
                      if self.sharding is not None else None)
                added = pool.grow_physical(extra, sharding=sh)
                if added:
                    moved += added
                    c.inc("migrated_blocks", live)
                    if tr:
                        tr.emit("migrate", blocks=live, added=added,
                                dur_s=time.perf_counter() - t0)
        if plan.dmult is not None:
            self._dmult = max(int(plan.dmult), 1)
        if not moved and self._dmult == old_dmult:
            return 0                         # nothing applied: no event
        c.inc("scale_ups" if plan.kind == "scale_up" else "scale_downs")
        if tr:
            tr.emit(plan.kind, units=moved, capacity=pool_capacity(pool),
                    dmult=self._dmult, reason=plan.reason)
        if moved and paged and self.allocation is not None:
            pool.tenant_reserves = self.allocation.rescaled_reserves(
                pool.n_blocks)
        if moved:
            self._replan(sched, pool, c)
        if self.elastic is not None:
            self.elastic.note_scale(sched.step, plan)
        if paged:
            pool.audit()                     # conservation must hold HERE,
                                             # after every migration
        return moved

    def _replan(self, sched, pool, c: RunObs) -> None:
        """Re-run the profile + allocate pipeline against the reshaped
        capacity: tenant demand is re-profiled from the LIVE request mix,
        per-token decode rates come from the measured ``ProfileStore`` fit
        when one is installed (this run's dispatch profile folds in first,
        so the fit reads the freshest rates), and the allocator re-plans
        budgets, K-knees, and lane shares for the new pool — calibration
        tracks measurement across every reshape instead of the one plan
        struck at startup. A tenant-carrying engine that started WITHOUT a
        plan gets its first one here (capacity just changed under it, so
        the slack-only scheduler now wants budgets). Allocation-only:
        outputs stay token-identical."""
        if self.tenants is None:
            return
        from repro.serve.tenant import (plan_allocation, profile_class,
                                        profiles_from_requests)
        max_k = (self.allocation.max_k if self.allocation is not None
                 else self.decode_horizon)
        store = self.profile_store
        if store is not None and self.profiler:
            store.add_run(self.profiler, arch=self.cfg.arch_id,
                          backend=self.cache_kind)
        total = pool_capacity(pool)
        live = list(sched.waiting) + list(sched.active.values())
        units_for = ((lambda r: pool.blocks_for(len(r.prompt)
                                                + r.max_new_tokens))
                     if hasattr(pool, "blocks_for") else None)
        profiles = profiles_from_requests(
            self.tenants, live, total_units=total, units_for=units_for,
            max_k=max_k, store=store, arch=self.cfg.arch_id,
            backend=self.cache_kind)
        for t in self.tenants:
            if t.tenant_id not in profiles:  # drained tenant: keep a
                profiles[t.tenant_id] = profile_class(  # minimal profile
                    t.tenant_id, units_per_req=1, concurrency=1,
                    total_units=total, max_k=max_k,
                    store=store, arch=self.cfg.arch_id,
                    backend=self.cache_kind)
        wm = (pool.watermark_blocks if hasattr(pool, "watermark_blocks")
              else 0)
        self.allocation = plan_allocation(
            self.tenants, profiles, total, total_lanes=self.prefill_lanes,
            max_k=max_k, watermark_units=wm)
        sched.allocation = self.allocation
        if isinstance(pool, BlockManager):
            pool.tenant_reserves = self.allocation.reserves()
        c.inc("replans")
        if c.tracer:
            c.tracer.emit("recover", kind="reshape", action="replan",
                          req=None, detail=int(total))

    def _submit_all(self, sched, pool, reqs) -> None:
        """Submit the run's initial requests. A request the CONSTRUCTED
        pool cannot validate is parked instead of rejected when scheduled
        capacity (a pending ``device_join``/``pool_restore``, or elastic
        scale-up headroom) will cover it — the bounded-retry admission
        path then holds it until the capacity arrives. Without pending
        capacity the submit error propagates exactly as before."""
        for i, r in enumerate(reqs):
            r.job_id = i
            try:
                sched.submit(r)
            except ValueError:
                if not self._can_ever_admit(pool, r, step=float(sched.step)):
                    raise
                sched.park(r)

    def _elastic_poll(self, sched, pool, state, c: RunObs) -> None:
        """Ask the elastic controller for a proactive reshape at this
        boundary (None without a controller, inside its cooldown, or when
        every signal sits between the thresholds)."""
        if self.elastic is None:
            return
        plan = self.elastic.decide(sched.step, pool, c.metrics)
        if plan is not None:
            self._apply_scale(sched, pool, state, c, plan)

    def _could_admit_arrival(self, sched) -> bool:
        """Whether shortening the horizon for the next arrival could pay
        off: the pool must actually be able to admit a waiting request —
        free slots for the contiguous pool, watermark-clearing blocks for
        the paged pool (``can_admit`` is cache-blind, matching the
        admission rule; the cap is a heuristic either way)."""
        pool = sched.pool
        if hasattr(pool, "can_admit"):
            return any(pool.can_admit(len(r.prompt)) for r in sched.waiting)
        return getattr(pool, "n_free", 0) > 0

    def _pick_h(self, sched, act) -> int:
        """Horizon length for this dispatch: at most ``decode_horizon``,
        capped to the longest remaining budget (every scanned step then
        serves at least one live row) and to the next open-loop arrival
        when the pool could admit it — the scheduler only intervenes at
        horizon boundaries.

        Tenant-aware boundaries (serve/tenant.py): the allocator's
        per-tenant horizon knee caps ``h`` (the LARGEST knee among the
        active tenants — a K past every knee buys no throughput), and when
        a QUEUED request's SLO slack is shorter than the horizon, ``h``
        shrinks toward that slack so the boundary — where eviction frees
        capacity and slack-ordered admission runs — lands before the
        deadline pressure instead of after it.

        The result is quantized DOWN to a power of two: ``h`` is a static
        jit argument, so free-running values would compile one K-step
        program per (width, h) pair — quantization bounds the program set
        to log2(K) entries per width."""
        rem = max(sched.active[s].max_new_tokens - len(sched.active[s].output)
                  for s in act)
        h = max(1, min(self.decode_horizon, rem))
        if self.allocation is not None:
            h = min(h, max(1, self.allocation.k_cap_for(
                {sched.active[s].tenant for s in act})))
        nxt = sched.next_arrival()
        if (nxt is not None and nxt > sched.step
                and self._could_admit_arrival(sched)):
            h = max(1, min(h, int(math.ceil(nxt - sched.step))))
        if self.tenants is not None and sched.waiting:
            urgent = min(self._slack(r, sched.step) for r in sched.waiting)
            if math.isfinite(urgent):
                h = max(1, min(h, int(max(1.0, urgent))))
        if self.injector is not None:
            # land the next boundary on the next pending fault, so a fault
            # keyed to step s applies at the first boundary >= s instead
            # of drifting up to a full horizon late.
            nf = self.injector.next_fault_step(sched.step)
            if nf is not None and nf > sched.step:
                h = max(1, min(h, int(math.ceil(nf - sched.step))))
        return _pow2_floor(h)

    def _decode_boundary(self, sched, pool, state, c, n_slots, dmult,
                         h) -> List[int]:
        """One horizon dispatch at a scheduler boundary (both backends):
        bucket the live rows, run the jitted horizon, unpack the [W, h]
        token block, update the counters and the scheduler clock. Returns
        the per-row emitted counts in sorted-active order. The dispatch and
        its fetch are the ``serve.horizon`` span (``decode_s``), the fetch
        alone ``serve.fetch``, the bookkeeping after it ``serve.unpack``."""
        act = sorted(sched.active)
        h = _pow2_floor(min(h, max(sched.active[s].max_new_tokens
                                   - len(sched.active[s].output)
                                   for s in act)))
        bc = _bucket(len(act), n_slots, dmult)
        full = bc == n_slots
        if full:
            idx = np.arange(n_slots, dtype=np.int32)
            rows = act                       # block rows are slot-indexed
        else:
            idle = [s for s in range(n_slots) if s not in sched.active]
            idx = np.asarray(act + idle[:bc - len(act)], np.int32)
            rows = list(range(len(act)))     # compacted row order
        args = (self.params, pool.buffers, state.tok, state.pos, state.stop)
        if state.tables is not None:
            args += (state.tables,)
        with c.span("serve.horizon", "decode_horizon", step=sched.step, k=h,
                    width=len(idx), active=len(act), full=full) as span:
            pool.buffers, state.tok, state.pos, state.stop, blk = \
                self._horizon(*args, jnp.asarray(idx), jnp.int32(sched.step),
                              h=h, full=full)
            c.inc("decode_dispatches")
            with c.span("serve.fetch"):
                blk = np.asarray(blk)        # the ONE [W, h] int32 fetch
            c.inc("host_syncs")
        prof = self.profiler
        if prof:
            # KV positions at dispatch start (outputs not yet extended);
            # tenants maps tenant -> live rows for the cost-share split.
            kv = sum(len(sched.active[s].prompt) + len(sched.active[s].output)
                     for s in act)
            prof.record("decode", span.dur_s, width=len(idx), k=h,
                        full=full, kv_pos_sum=kv,
                        tenants=Counter(sched.active[s].tenant for s in act),
                        obs=c)
        with c.span("serve.unpack"):
            counts = self._unpack_horizon(sched, act, rows, blk, h, n_slots,
                                          c)
            c.inc("rows_decoded", len(idx) * h)
            c.hi("max_active", len(act))
            c.inc("steps", h)
            sched.step += h
            if c.tracer:
                c.tracer.step = sched.step
            self._sample_boundary(sched, pool, c, n_slots)
        return counts

    def _unpack_horizon(self, sched, act, rows, blk, h, n_slots,
                        c) -> List[int]:
        """Distribute a horizon's [W, h] token block: the row of active
        slot ``act[i]`` is ``rows[i]``; its first min(h, remaining)
        entries are its tokens (the device freezes finished rows and emits
        -1), truncated at the engine's EOS token. Returns the per-row
        emitted counts (in ``act`` order)."""
        counts = []
        step0 = sched.step
        for slot, row in zip(act, rows):
            r = sched.active[slot]
            m = min(h, r.max_new_tokens - len(r.output))
            toks = [int(x) for x in blk[row, :m]]
            if self.eos_token is not None and self.eos_token in toks:
                toks = toks[:toks.index(self.eos_token) + 1]
                r.finished_early = True
            r.output.extend(toks)
            counts.append(len(toks))
            if r.done and r.finished_at is None:
                # exact finishing step (eviction only happens at the
                # boundary): last token emitted at step0 + count - 1.
                r.finished_at = float(step0 + len(toks))
        for k in range(h):
            c.inc("util_acc", sum(1 for m in counts if m > k) / n_slots)
        return counts

    def _run_contiguous(self, reqs, n_slots, c: RunObs):
        self.pool = pool = CachePool(self.model, n_slots, self.max_len)
        if self.sharding is not None:
            pool.buffers = self.sharding.reshard_cache(pool.buffers)
        sched = self._make_sched(pool)
        self._submit_all(sched, pool, reqs)

        state = _DecodeState(n_slots, sharding=self.sharding)
        while sched.has_work:
            with c.span("serve.step"):
                admitted = self._admit(sched, pool, state, c, n_slots, reqs)
                if admitted:
                    with c.span("serve.prefill", requests=len(admitted)):
                        for r in admitted:
                            self._contiguous_prefill(pool, r, sched.step, c)
                    self._upload(
                        c, state.set_rows, [r.slot for r in admitted],
                        [r.output[-1] for r in admitted],
                        [len(r.prompt) for r in admitted],
                        [len(r.prompt) + r.max_new_tokens - 1
                         for r in admitted])
                self._evict(sched, state, c)  # satisfied by prefill / EOS
                if not sched.active:
                    nxt = sched.next_arrival()
                    if nxt is None:
                        break
                    if self.injector is not None and nxt <= sched.step:
                        # everything waiting is held (a slowdown/storm
                        # window): jump to the next event that could
                        # unstall admission.
                        unb = self._next_unblock(sched)
                        nxt = unb if unb is not None else sched.step + 1
                    sched.step = max(sched.step + 1, int(math.ceil(nxt)))
                    if c.tracer:
                        c.tracer.step = sched.step
                    continue

                # pool.write's eager scatter loses the NamedSharding
                # layout; restore it only on rounds that actually admitted
                # (the horizon's out_shardings keeps the cache sharded
                # otherwise).
                if self.sharding is not None and admitted:
                    pool.buffers = self.sharding.reshard_cache(pool.buffers)

                with c.span("serve.grow") as span:
                    h = self._pick_h(sched, sorted(sched.active))
                    span.set(h=h)
                self._decode_boundary(sched, pool, state, c, n_slots,
                                      self._dmult, h)
        self._evict(sched, state, c)

    def _admit(self, sched, pool, state, c: RunObs, n_slots: int,
               reqs: List[ServeRequest]) -> List[ServeRequest]:
        """The admission half of a boundary (both backends), as the
        ``serve.admit`` span: due faults and reshapes, eviction, the
        scheduler's admission, chaos retries. Returns the requests
        admitted, in prefill order."""
        with c.span("serve.admit") as span:
            if self.injector is not None:
                self._apply_faults(sched, pool, state, c, n_slots, reqs)
            self._elastic_poll(sched, pool, state, c)
            self._evict(sched, state, c)
            sched.admit(hold=self._fault_hold(sched))
            if self.injector is not None or self.elastic is not None:
                self._chaos_admission(sched, pool, c)
            admitted = sched.drain_prefill()
            span.set(admitted=len(admitted))
        return admitted

    def _contiguous_prefill(self, pool: CachePool, r: ServeRequest, step,
                            c: RunObs) -> None:
        """One request's exact-length prefill up to its first token's
        fetch, as a ``serve.prefill_round`` span (the Tracer's ``prefill``
        event, and the profiler's ``prefill`` record, share its interval)."""
        with c.span("serve.prefill_round", "prefill", req=r.job_id,
                    tenant=r.tenant, slot=r.slot,
                    prompt_len=len(r.prompt)) as span:
            tokens = jnp.asarray(np.asarray(r.prompt, np.int32))[None, :]
            logits, row = self._prefill(self.params, tokens)
            c.inc("prefill_dispatches")
            pool.write(r.slot, row)
            tok = int(self._select_tokens(logits[:, -1], [r.slot], ~step,
                                          c)[0])
            self._first_token(r, tok)
            if self.record_logits:
                r.prefill_logits = np.asarray(
                    logits[0, -1].astype(jnp.float32))
        if self.profiler:
            # contiguous prefill jits one program per prompt length — seq
            # is the static half of the signature.
            self.profiler.record("prefill", span.dur_s, seq=len(r.prompt),
                                 tokens=len(r.prompt),
                                 tenants={r.tenant: 1}, obs=c)

    def _first_token(self, r: ServeRequest, tok: int) -> None:
        """Append the token a prefill picked; the first one a request ever
        receives stamps ``t_first_token`` (a preempted request keeps it)."""
        r.output.append(tok)
        if r.t_first_token is None:
            r.t_first_token = time.perf_counter()
        if self.eos_token is not None and tok == self.eos_token:
            r.finished_early = True

    # -- paged loop --------------------------------------------------------------
    def _next_lane_req(self, queue: deque, lanes) -> ServeRequest:
        """Pick the next request to fill a freed prefill lane.

        With a tenant allocation and a mixed-tenant queue, a tenant
        already holding its lane share (``allocation.lane_share``) yields
        the lane to the first queued request of an under-share tenant —
        a burst of one tenant's long prompts cannot monopolize every lane
        while another tenant's request waits. Work-conserving: when every
        queued tenant sits at its share (or the queue is single-tenant)
        the head proceeds anyway, so lanes never idle. Lane order only —
        outputs are unchanged (prefill is per-request exact-length)."""
        if self.allocation is None or len(queue) == 1:
            return queue.popleft()
        held = Counter(ln.req.tenant for ln in lanes)
        if len({r.tenant for r in queue} | set(held)) <= 1:
            return queue.popleft()
        for i, r in enumerate(queue):
            if held[r.tenant] < self.allocation.lane_share(r.tenant):
                del queue[i]
                return r
        return queue.popleft()

    def _batched_paged_prefill(self, pool: BlockManager, reqs, step: int,
                               c: RunObs) -> None:
        """Prefill all joining requests through up to ``prefill_lanes``
        lanes in lockstep chunk-rounds: one jitted ``[P, block_size]``
        dispatch per round covers one chunk of every live lane. A lane
        starts at its request's first non-cached position (prefix hits skip
        both blocks and compute), commits each completed full block to the
        prefix cache, and on its final chunk samples the request's first
        token from its last-valid-position logits; the freed lane is then
        refilled from the queue so long prompts never serialize behind
        short ones. Each round is a ``serve.prefill_round`` span; its
        Tracer ``prefill_round`` event's ``dur_s`` is the round's host time
        (packing, dispatch, block commits, the token pick's fetch), and the
        dispatch alone is not timed: it returns before the device ends."""
        is_moe = self.cfg.family == "moe"
        if is_moe:
            from repro.models.moe import capacity as moe_capacity
        queue = deque(reqs)
        lanes: List[_PrefillLane] = []
        while queue or lanes:
            while queue and len(lanes) < self.prefill_lanes:
                r = self._next_lane_req(queue, lanes)
                prompt = np.asarray(r.prompt, np.int32)
                state = pool.resume_state(r.slot)
                if is_moe and state is None:
                    state = np.asarray(self.model.paged_prefill_state(1))
                lanes.append(_PrefillLane(
                    req=r, prompt=prompt, ptr=pool.cached_tokens(r.slot),
                    cap_row=(moe_capacity(self.cfg, len(prompt))
                             if is_moe else 0),
                    state=state))
            w = _bucket(len(lanes), self.prefill_lanes)
            with c.span("serve.prefill_round", "prefill_round",
                        lanes=len(lanes), width=w):
                lanes = self._paged_prefill_round(pool, lanes, w, step, c)

    def _paged_prefill_round(self, pool: BlockManager,
                             lanes: List[_PrefillLane], w: int, step: int,
                             c: RunObs) -> List[_PrefillLane]:
        """One chunk round of ``_batched_paged_prefill`` at lane width
        ``w``: pack and dispatch one chunk of every lane, commit full
        blocks, and pick (one fetch) the first token of every lane whose
        prompt ended. Returns the lanes still prefilling."""
        bs, mb = pool.block_size, pool.max_blocks
        is_moe = self.cfg.family == "moe"
        tokens = np.zeros((w, bs), np.int32)
        starts = np.zeros((w,), np.int32)
        nv = np.zeros((w,), np.int32)
        caps = np.zeros((w,), np.int32)
        tables = np.full((w, mb), -1, np.int32)
        for i, ln in enumerate(lanes):
            n = min(bs, len(ln.prompt) - ln.ptr)
            tokens[i, :n] = ln.prompt[ln.ptr:ln.ptr + n]
            starts[i], nv[i], caps[i] = ln.ptr, n, ln.cap_row
            tables[i] = pool.tables[ln.req.slot]
        state = None
        if is_moe:
            cols = [ln.state for ln in lanes]
            cols += [np.zeros_like(cols[0])] * (w - len(lanes))
            state = jnp.asarray(np.concatenate(cols, axis=1))
        logits, pool.buffers, new_state = self._prefill(
            self.params, pool.buffers, jnp.asarray(tokens),
            jnp.asarray(starts), jnp.asarray(nv), jnp.asarray(tables),
            state, jnp.asarray(caps), cap=self.max_len if is_moe else 0)
        c.inc("prefill_dispatches")
        if new_state is not None:
            new_state = np.asarray(new_state)
        done_idx: List[int] = []
        live: List[_PrefillLane] = []
        for i, ln in enumerate(lanes):
            n = int(nv[i])
            if new_state is not None:
                ln.state = new_state[:, i:i + 1]
            if n == bs:        # a full block is final: cacheable
                pool.commit_block(
                    ln.req.slot, ln.ptr // bs,
                    None if ln.state is None else ln.state.copy())
            ln.ptr += n
            if ln.ptr >= len(ln.prompt):
                done_idx.append(i)
            else:
                live.append(ln)
        if done_idx:
            slots = [lanes[i].req.slot for i in done_idx]
            toks = self._select_tokens(
                logits[np.asarray(done_idx), -1], slots, ~step, c)
            for t, i in zip(toks, done_idx):
                self._first_token(lanes[i].req, int(t))
                if self.record_logits:
                    lanes[i].req.prefill_logits = np.asarray(
                        logits[i, -1].astype(jnp.float32))
        return live

    def _growth_blocks_needed(self, sched, pool: BlockManager, pos_np,
                              stop_np, h: int) -> int:
        """Fresh blocks a horizon of ``h`` steps would allocate across the
        active rows (each row writes positions [pos, min(pos+h, stop)))."""
        need = 0
        for s in sched.active:
            want = pool.blocks_for(min(int(pos_np[s]) + h, int(stop_np[s])))
            need += max(0, want - pool.owned_blocks(s))
        return need

    def _ensure_growth(self, sched, pool: BlockManager, pos_np, stop_np,
                       h: int, c: RunObs):
        """Guarantee blocks for up to ``h`` decode tokens per active row
        before a horizon dispatch (the host cannot intervene mid-horizon).
        Shrinks the horizon toward 1 before resorting to preemption — a
        pool sized for the classic one-step loop still runs, just at
        shorter horizons — and preempts the most recently admitted request
        only while even one step cannot be covered.
        Returns (h, n_preempted, victim_slots)."""
        victims = []
        tr = c.tracer
        while True:
            h0 = h
            while h > 1 and (self._growth_blocks_needed(
                    sched, pool, pos_np, stop_np, h) > pool.free_blocks):
                h = max(1, h // 2)
            if tr and h < h0:
                tr.emit("horizon_shrink", from_k=h0, to_k=h,
                        cause="pool_pressure")
            blocked = next(
                (s for s in sorted(sched.active)
                 if not pool.ensure(s, min(int(pos_np[s]) + h,
                                           int(stop_np[s])))),
                None)
            if blocked is None:
                return h, len(victims), victims
            if len(sched.active) == 1:
                if self.injector is None and self.elastic is None:
                    raise RuntimeError(
                        "paged KV pool exhausted with a single active "
                        "request; grow n_blocks or lower max_new_tokens")
                # graceful horizon degradation: the budget vanished mid-
                # horizon (pool_shrink) under the LAST active request —
                # drop it instead of crashing the run.
                victim = sched.active[blocked]
                victims.append(victim.slot)
                sched.preempt(victim, cause="pool_exhausted")
                self._drop(sched, victim, c, cause="pool_exhausted")
                return h, len(victims), victims
            # victim choice: with a tenant registry the LARGEST SLO slack
            # goes first (a batch tenant without an SLO has infinite
            # slack), so pool pressure lands on whoever can absorb the
            # regeneration; without tenants, recency (the original rule).
            if self.tenants is not None:
                victim = max(sched.active.values(),
                             key=lambda r: (self._slack(r, sched.step),
                                            r.admitted_at, r.slot))
            else:
                victim = max(sched.active.values(),
                             key=lambda r: (r.admitted_at, r.slot))
            victims.append(victim.slot)
            sched.preempt(victim, cause="pool_pressure")

    def _run_paged(self, reqs, n_slots, c: RunObs):
        #: the pool outlives the run on ``self.pool`` so chaos tests and
        #: replay harnesses can audit block conservation after the fact
        self.pool = pool = BlockManager(self.model, n_slots, self.max_len,
                                        block_size=self.block_size,
                                        n_blocks=self.n_blocks,
                                        watermark=self.watermark,
                                        prefix_cache=self.prefix_cache,
                                        tracer=self.tracer)
        if self.sharding is not None:
            pool.buffers = self.sharding.reshard_cache(pool.buffers)
        if self.allocation is not None:
            # per-tenant watermark headroom: a tenant's admissions may
            # spend its OWN reserve (insensitive tenants donate theirs
            # implicitly — see BlockManager._blocks_clear_watermark).
            pool.tenant_reserves = self.allocation.reserves()
        sched = self._make_sched(pool)
        self._submit_all(sched, pool, reqs)

        state = _DecodeState(n_slots, max_blocks=pool.max_blocks,
                             sharding=self.sharding)
        pos_np = np.zeros((n_slots,), np.int64)
        stop_np = np.zeros((n_slots,), np.int64)
        peak_report = pool.report()

        while sched.has_work:
            with c.span("serve.step"):
                admitted = self._admit(sched, pool, state, c, n_slots, reqs)
                if admitted:
                    with c.span("serve.prefill", requests=len(admitted)):
                        self._batched_paged_prefill(pool, admitted,
                                                    sched.step, c)
                    slots = [r.slot for r in admitted]
                    for r in admitted:
                        pos_np[r.slot] = len(r.prompt)
                        stop_np[r.slot] = len(r.prompt) + r.max_new_tokens - 1
                    self._upload(c, state.set_rows, slots,
                                 [r.output[-1] for r in admitted],
                                 [int(pos_np[s]) for s in slots],
                                 [int(stop_np[s]) for s in slots])
                    snap = pool.report()     # pool pressure peaks can be
                                             # prefill-only (max_new == 1)
                    if snap["used_blocks"] >= peak_report["used_blocks"]:
                        peak_report = snap
                self._evict(sched, state, c)  # satisfied by prefill / EOS
                if not sched.active:
                    nxt = sched.next_arrival()
                    if nxt is None:
                        break
                    if not admitted and nxt <= sched.step:
                        if self.injector is None and self.elastic is None:
                            raise RuntimeError(
                                "paged KV pool cannot admit any waiting "
                                "request; grow n_blocks or lower the "
                                "watermark")
                        # graceful degradation: a shrink/hold made
                        # everything momentarily inadmissible — advance to
                        # the next event that could unstall (hold release,
                        # backoff retry, pending fault, later arrival);
                        # retries bound the stall, dropping what the pool
                        # can never hold.
                        unb = self._next_unblock(sched)
                        nxt = unb if unb is not None else sched.step + 1
                    sched.step = max(sched.step + 1, int(math.ceil(nxt)))
                    if c.tracer:
                        c.tracer.step = sched.step
                    continue

                if self.sharding is not None and admitted:
                    pool.buffers = self.sharding.reshard_cache(pool.buffers)

                with c.span("serve.grow") as span:
                    h = self._pick_h(sched, sorted(sched.active))
                    h, n_pre, victims = self._ensure_growth(
                        sched, pool, pos_np, stop_np, h, c)
                    c.inc("preemptions", n_pre)
                    span.set(h=h)
                self._upload(c, state.freeze, victims)
                if not sched.active:    # chaos: sole request dropped on
                    continue            # exhaustion — back to admission
                # delta-sync the device table mirror: only rows dirtied by
                # admission / growth (freed rows stay stale — they are
                # frozen and write-masked, so the staleness is
                # unobservable).
                dirty = [s for s in pool.drain_dirty() if s in sched.active]
                self._upload(c, state.set_tables, dirty,
                             pool.tables[np.asarray(dirty, np.int64)])

                act = sorted(sched.active)
                counts = self._decode_boundary(sched, pool, state, c,
                                               n_slots, self._dmult, h)
                for slot, m in zip(act, counts):
                    pos_np[slot] += m
                snap = pool.report()
                if snap["used_blocks"] >= peak_report["used_blocks"]:
                    peak_report = snap      # the pool at peak pressure
        self._evict(sched, state, c)
        c.block_report = peak_report
        c.inc("prefix_hits", pool.prefix_blocks_hit)
        c.inc("prefix_total", pool.prefix_blocks_total)

    def generate(self, requests: List[ServeRequest]) -> List[ServeRequest]:
        """Run a batch of requests to completion; returns them."""
        return self.run(requests)[0]


def serve_step_fn(cfg: ArchConfig):
    """The (params, cache, tokens, pos) -> (logits, cache) step the dry-run
    lowers for decode shapes."""
    model = build_model(cfg)

    def serve_step(params, cache, tokens, pos):
        return model.decode_step(params, cache, tokens, pos)

    return serve_step
