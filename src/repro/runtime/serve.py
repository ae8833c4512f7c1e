"""Serving engine: continuous batching over a slot-pooled or paged KV cache.

The engine keeps ONE persistent pooled decode state: every layer's cache has
a leading `slots` dimension (dense mode) or is a shared physical block pool
with per-slot page tables (paged mode, `paged=True`). The scheduler admits
queued requests by prefilling them individually (prefill is compute-bound
and shape-varying) and writing the batch=1 result into a free slot; after
that, every tick is exactly ONE fused jitted decode call that advances all
active slots at once under an active-slot mask. Finished sequences free
their slot (and, in paged mode, return their blocks to the free list) and
the next queued request takes it over.

Paged mode is the serving-scale memory model: instead of reserving a dense
`max_seq` stripe per slot, admission allocates `ceil(prompt/block_size)`
physical blocks from a shared free list, decode grows the slot's page list
one block at a time as its cursor crosses block boundaries, and completion
returns the blocks — so a 256-token request costs 256 tokens of HBM, not
max_seq, and mixed 1k/100k requests pack into one pool (the AccLLM /
SparseAccelerate argument). If the free list is empty when a slot must grow,
the request is finished with an ``overflow`` stop reason (the dropped write
is counted — never silently clipped).

Prefix sharing (``prefix_sharing=True``, paged mode only) maps identical
prompt prefixes — system prompts, few-shot headers — onto the SAME physical
blocks. The engine keeps a radix map from cumulative token-id hashes of
full-block prefixes (plus exact-full-prompt partial blocks) to the physical
block holding them; admission matches the longest shared prefix, charges
only the divergent tail against the free list, and installs the shared
blocks by reference (`prefill_into_pages(..., n_shared)` maps without
writing). Blocks are refcounted; completion decrements and only a count of
zero returns a block to the free list. Shared blocks are copy-on-write:
before a tick, any slot whose cursor points into a block with refcount > 1
gets a private copy (`cow_block`) so the shared bytes are never mutated.
Sharing is disabled per request when its prefill derives a different
heavy-channel set than the prefix owner's (the packed feature stream is
encoded against that set, so aliasing would corrupt selection) — the
request falls back to private blocks, keeping outputs bit-identical to an
unshared run in every case.

The persistent prefix cache (``prefix_cache=True``) makes the radix map a
real cross-request cache: when a prefix block's last resident owner frees
it, the engine keeps it mapped under a host-side cache pin (refcount stays
0; the allocator just never gets the id back) instead of returning it to
the free list, so a later request with the same prefix admits by reference
with zero prefill for the shared span — a fully-cached prompt whose
first-token logits row is retained adopts its blocks via a metadata-only
``adopt_pages`` call and TTFT collapses to the divergent tail. Eviction is
LRU-by-last-hit under allocator pressure, deepest blocks first on ties so a
radix chain never loses an ancestor before its descendants; a cache-pinned
block is the cheapest thing to reclaim, so admission, growth and CoW drain
the cold end of the cache before host-spill demotion or preemption ever
fires. With ``host_spill`` the pinned blocks demote to a host cold tier
under pressure instead of being evicted outright, promoting back on the
next radix hit. Greedy outputs stay bit-identical to a cold-cache engine on
every hit: the retained bytes are exactly what a cold prefill would write.

Sharded page pools (paged mode with a mesh ``ctx``): the physical block
pool splits across the decode mesh axes — each device owns
``num_blocks / n_shards`` blocks, a decode tick runs shard-locally around
two tiny collectives (psum'd additive histograms → one global Top-K
threshold; online-softmax merge of the per-shard partial attention), and a
single request's blocks may SPAN shards, so admitted long-context capacity
scales with shard count at fixed per-device pool size. The engine keeps one
free list per shard (`ShardedBlockAllocator`): admission charges a
request's blocks to the least-loaded shards (greedy, most-free first —
spilling across shards is what lets one context exceed one device's pool);
decode growth and CoW copies prefer the shard that owns the slot's tail
block (the appending shard keeps writing locally), falling back to the
least-loaded shard when it is empty. Outputs are bit-identical to the
unsharded paged engine — the sharded tick's selection is exact by
construction (see ``core.sp_decode``).

Tiered KV memory: ``kv_pool_dtype`` picks the block pool's exact-K/V
storage precision per engine (fp16 / int8 / int4, dequantized inside the
decode gather — the selection's 2-bit feature stream is
precision-independent), and ``host_spill=True`` adds a host tier: private
blocks the selection histograms stop touching for ``demote_after`` ticks
(outside the ``spill_keep_recent`` recency window) demote to a numpy
mirror in storage format — bit-exact both ways — freeing their physical
block; they promote back, highest historical-relevance first, when the
pool has ``promote_headroom`` free blocks. Demotion also fires under
pressure (admission and growth with a dry free list, coldest first), which
lets a prompt whose footprint exceeds the whole device pool admit in
free-pool-sized waves. Spilled blocks are unselectable
(`mapped_valid_mask`) rather than garbage-read.

Latency accounting separates queue wait (submit→admit), TTFT
(submit→first token, i.e. queue wait + prefill), and decode (per tick and
per token).

Failure model (see docs/serving.md "Failure model & graceful degradation"):
every failure path degrades instead of crashing. Requests carry an optional
``deadline_ms`` (expiry finishes them with ``stop_reason="deadline"`` and
full block/stash/radix cleanup, wherever they live — queued, mid-chunked-
prefill, or resident), ``cancel(request_id)`` works on queued and resident
requests alike, and a bounded queue (``max_queue``) sheds new submits with
``stop_reason="rejected"`` instead of growing without bound. Failed spill
transfers retry with capped exponential backoff; a promotion that exhausts
its retries pins the block cold — Salca's `mapped_valid_mask` makes it
unselectable, so decode continues with sparser attention (quality, not
availability, degrades; `stats.degraded_ticks` counts these). A slot whose
logits come back NaN/Inf is quarantined (`stop_reason="error"`) without
touching the fused tick's other slots. A seeded `FaultPlan`
(``faults=``, see `runtime.faults`) injects all of these deterministically,
and ``audit_every`` runs the `PagedSalcaCache.check_invariants` integrity
audit (refcounts == page-table references == host mirror, free ∩ mapped =
∅, cursor bounds, spill-mirror consistency) as a production self-check.
"""

from __future__ import annotations

import dataclasses
import hashlib
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import get_model
from repro.models.blocks import DecodeCtx
from repro.runtime.faults import FaultPlan
from repro.runtime.monitor import NaNGuard, StepMonitor
from repro.runtime.tracing import HostSpans

# `_slot_blocks` sentinel for a logical block whose data lives in the host
# tier (its page-table entry is -1 and its rows sit in the numpy mirror).
SPILLED = -1

# `_prefix_nodes` sentinel for a persistent-cache entry whose rows were
# demoted to the host cold tier (`_cold_cache`) under HBM pressure: the
# radix key stays matchable and promotes back to a fresh block on a hit.
CACHE_COLD = -2


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (T,) int32
    max_new_tokens: int = 16
    stop_token: int | None = None      # finish early when sampled
    temperature: float = 0.0           # 0 = greedy; >0 = per-slot sampling
    deadline_ms: float | None = None   # wall-clock budget measured from
                                       # submit; expiry stops the request
                                       # wherever it lives (queued/resident)
    submitted: float = field(default_factory=time.time)
    admitted: float | None = None      # FIRST admission's work start
    first_token_time: float | None = None
    done_time: float | None = None
    # Terminal outcome. Normal: "length" | "stop". Capacity: "overflow"
    # (paged pool contention without preempt; dense max_seq). Lifecycle:
    # "deadline" (deadline_ms expired) | "cancelled" (cancel()) |
    # "rejected" (bounded-queue shed at submit). Fault: "error" (slot
    # quarantined on non-finite logits). See docs/serving.md.
    stop_reason: str | None = None
    output: list = field(default_factory=list)
    shared_blocks: int = 0             # blocks admitted by prefix sharing
    preemptions: int = 0               # times evicted and requeued
    token_times: list = field(default_factory=list)  # wall time per fresh token
    # Small host-side stashes kept across head-of-line retries: prompt
    # prefix digests and heavy-set bytes (a few hundred bytes — these never
    # pin device memory; the prefill STATE stash is engine-owned and bounded
    # to one request, see `ServingEngine._ensure_prefill`).
    _digests: Any = field(default=None, repr=False, compare=False)
    _heavy: Any = field(default=None, repr=False, compare=False)
    # Preemption/replay bookkeeping: recorded output to force-feed after
    # re-prefill (KV for generated tokens is regenerated by replaying them
    # through decode ticks — bit-exact even under temperature sampling),
    # accumulated queue wait across admission cycles, the requeue timestamp,
    # and whether the current admission cycle already counted its wait.
    _replay: Any = field(default=None, repr=False, compare=False)
    _queue_wait: float = field(default=0.0, repr=False, compare=False)
    _requeued_at: Any = field(default=None, repr=False, compare=False)
    _cycle_started: bool = field(default=False, repr=False, compare=False)

    @property
    def queue_wait_s(self) -> float | None:
        """Total time spent waiting in the queue, summed over the initial
        submission and every preemption requeue. None until work starts."""
        return None if self.admitted is None else self._queue_wait

    @property
    def ttft_s(self) -> float | None:
        """Submit → first token. Never reset by preemption: the first token
        streams to the caller once, whatever happens to the KV afterwards."""
        if self.first_token_time is None:
            return None
        return self.first_token_time - self.submitted

    def stats(self) -> dict:
        """Per-request stats (exposed so callers can log completions)."""
        return {
            "rid": self.rid,
            "prompt_tokens": int(len(self.prompt)),
            "output_tokens": len(self.output),
            "stop_reason": self.stop_reason,
            "queue_wait_s": self.queue_wait_s,
            "ttft_s": self.ttft_s,
            "preemptions": self.preemptions,
        }


class ShardedBlockAllocator:
    """Host-side per-shard free lists over a block pool whose physical block
    dim is split into ``n_shards`` contiguous ranges (shard of block ``b`` =
    ``b // (num_blocks // n_shards)`` — the same ownership rule
    `core.cache.local_block_range` applies device-side).

    Invariants (property-tested): the per-shard lists are disjoint, every id
    stays inside its shard's range, no id appears twice, and an allocated
    block is in no list until released — a physical block can never be
    handed to two owners or aliased across shards. ``n_shards=1`` reproduces
    the previous single-free-list behavior exactly.
    """

    def __init__(self, num_blocks: int, n_shards: int = 1):
        if num_blocks % n_shards:
            raise ValueError(f"num_blocks {num_blocks} must divide evenly "
                             f"across {n_shards} shards")
        self.num_blocks = num_blocks
        self.n_shards = n_shards
        self.blocks_per_shard = num_blocks // n_shards
        self._free = [list(range(s * self.blocks_per_shard,
                                 (s + 1) * self.blocks_per_shard))
                      for s in range(n_shards)]

    def shard_of(self, block: int) -> int:
        return block // self.blocks_per_shard

    @property
    def total_free(self) -> int:
        return sum(len(f) for f in self._free)

    def free_counts(self) -> list[int]:
        return [len(f) for f in self._free]

    def free_ids(self) -> list[int]:
        """Flat view of every free block id (read-only snapshot)."""
        return [b for f in self._free for b in f]

    def alloc(self, need: int, prefer: int | None = None) -> list[int] | None:
        """Pop ``need`` blocks, or None (nothing popped) if the pool can't
        cover them. ``prefer`` drains that shard first — growth/CoW locality
        (the shard owning a slot's tail keeps its writes local); otherwise
        blocks come from the least-loaded shards (most free first), spilling
        across shards so one request can exceed one shard's pool."""
        if need > self.total_free:
            return None
        order = sorted(range(self.n_shards), key=lambda s: -len(self._free[s]))
        if prefer is not None:
            order = [prefer] + [s for s in order if s != prefer]
        out: list[int] = []
        for s in order:
            while self._free[s] and len(out) < need:
                out.append(self._free[s].pop())
            if len(out) == need:
                break
        return out

    def release(self, block: int) -> None:
        self._free[self.shard_of(block)].append(block)

    def take(self, block: int) -> None:
        """Remove a specific id from its shard's list (tests/simulation)."""
        self._free[self.shard_of(block)].remove(block)


@dataclass
class ServeStats:
    # Host time per phase of a scheduler iteration, one `runtime.tracing`
    # span each: admission (prefill chunks, adoptions and monolithic
    # prefills, with any wait on the device they block on), then the
    # tick's prep (expiry, promotion, growth), dispatch (uploads and the
    # jitted call), wait (the blocking read of tokens) and post (per-slot
    # bookkeeping, spill policy, audits).
    admit_s: float = 0.0
    tick_prep_s: float = 0.0
    tick_dispatch_s: float = 0.0
    tick_wait_s: float = 0.0
    tick_post_s: float = 0.0
    decode_s: float = 0.0      # dispatch + wait of every decoding tick
    decode_steps: int = 0      # per-slot token decodes (Σ active over ticks)
    ticks: int = 0             # scheduler iterations that decoded
    decode_calls: int = 0      # jitted decode dispatches (== ticks by design)
    completed: int = 0
    tokens_generated: int = 0  # includes the prefill-produced first token
    queue_wait_s: float = 0.0  # summed over admission cycles (see admissions)
    ttft_s: float = 0.0        # summed over first tokens (see ttft_count)
    admissions: int = 0        # admission cycles begun (re-admissions count)
    ttft_count: int = 0        # requests that produced a first token
    peak_active_slots: int = 0
    overflows: int = 0         # requests finished with stop_reason="overflow"
    dropped_writes: int = 0    # KV writes that could not be stored
    # Continuous batching (zero unless prefill_chunk / preempt are set):
    preemptions: int = 0       # slots evicted to free blocks and requeued
    replayed_tokens: int = 0   # recorded tokens force-fed after re-prefill
    prefill_chunks: int = 0    # budgeted chunk steps executed
    chunk_stalls: int = 0      # chunk ticks that waited on the block pool
    prefill_tokens: int = 0    # prompt tokens prefilled (monolithic + chunks)
    # Paged-pool bookkeeping (zero in dense mode):
    block_pool_size: int = 0
    block_size: int = 0
    blocks_in_use: int = 0
    peak_blocks_in_use: int = 0
    # Sharded-pool bookkeeping (1 / 0 unless the pool is mesh-sharded):
    shards: int = 1
    peak_shard_blocks_in_use: int = 0   # hottest single shard at peak
    # Prefix sharing (zero unless prefix_sharing=True). `shared_blocks` /
    # `prefix_hits` count INTRA-FLIGHT sharing only: blocks whose source
    # still had a resident owner at match time. Cross-request hits served
    # from the persistent cache are the `cache_*` counters below.
    shared_blocks: int = 0     # blocks admitted by reference instead of copy
    cow_copies: int = 0        # shared blocks privatized on first write
    prefix_hits: int = 0       # requests that shared ≥ 1 resident block
    # Persistent prefix cache (zero unless prefix_cache=True):
    cache_hits: int = 0        # requests that adopted ≥ 1 cache-pinned block
    cache_hit_blocks: int = 0  # pinned blocks adopted (refcount 0 → 1)
    cache_evictions: int = 0   # pinned/cold entries dropped under pressure
    cache_pinned_blocks: int = 0   # current pin count (last sample)
    peak_cache_blocks: int = 0
    zero_prefill_hits: int = 0 # full-prompt hits admitted with NO prefill
    # Tiered KV memory (zero unless host_spill=True):
    host_spill: bool = False
    hot_blocks: int = 0        # device-resident blocks in use (last sample)
    cold_blocks: int = 0       # host-resident spilled blocks (last sample)
    peak_cold_blocks: int = 0
    demotions: int = 0         # block moves device → host
    promotions: int = 0        # block moves host → device
    pcie_bytes: int = 0        # predicted transfer = block_bytes · moves
    # Request lifecycle & fault tolerance (the robustness layer). The
    # accounting invariant extends unchanged: `admissions` still equals
    # `completed + preemptions` at drain — deadline/cancel/error stops of
    # requests whose admission cycle began count in `completed`, while
    # pure queue-side terminations (rejected at submit, shed or cancelled
    # before any work started) never touched `admissions` and are tracked
    # only by their own counters below.
    deadline_stops: int = 0    # deadline expiries (queued, inflight, resident)
    cancellations: int = 0     # cancel() calls that terminated a request
    rejections: int = 0        # submits shed by the bounded queue (max_queue)
    errors: int = 0            # slots quarantined on non-finite logits
    retries: int = 0           # failed transfer/chunk attempts left to retry
    degraded_ticks: int = 0    # ticks served degraded (stalled slot or a
                               # cold-pinned block on an active slot)
    faults_injected: int = 0   # FaultPlan injections that fired
    audits: int = 0            # integrity audits run (audit_every)
    audit_failures: int = 0    # audits that reported violations
    straggler_ticks: int = 0   # ticks the StepMonitor EWMA flagged slow
    tick_ewma_s: float = 0.0   # monitor's EWMA of whole scheduler iterations
                               # (admit through post; 0 = no monitor)

    def summary(self) -> dict:
        out = {
            "completed": self.completed,
            "admit_s": round(self.admit_s, 4),
            "decode_s": round(self.decode_s, 4),
            "decode_steps": self.decode_steps,
            "ticks": self.ticks,
            "decode_calls": self.decode_calls,
            "tokens_generated": self.tokens_generated,
            "decode_ms_per_step": round(1e3 * self.decode_s / max(self.decode_steps, 1), 3),
            "decode_ms_per_tick": round(1e3 * self.decode_s / max(self.ticks, 1), 3),
            # Means divide by the population that contributed a sample:
            # queue waits are logged once per admission cycle (a preempted
            # request waits again), TTFT once per request that produced a
            # first token — NOT by `completed`, which undercounts whenever
            # requests are still in flight and overcounts re-admissions.
            "mean_queue_wait_s": round(self.queue_wait_s / max(self.admissions, 1), 4),
            "mean_ttft_s": round(self.ttft_s / max(self.ttft_count, 1), 4),
            "admissions": self.admissions,
            "peak_active_slots": self.peak_active_slots,
            "overflows": self.overflows,
            "dropped_writes": self.dropped_writes,
            "preemptions": self.preemptions,
            "replayed_tokens": self.replayed_tokens,
            "prefill_chunks": self.prefill_chunks,
            "chunk_stalls": self.chunk_stalls,
            "deadline_stops": self.deadline_stops,
            "cancellations": self.cancellations,
            "rejections": self.rejections,
            "errors": self.errors,
            "retries": self.retries,
            "degraded_ticks": self.degraded_ticks,
        }
        if self.faults_injected:
            out["faults_injected"] = self.faults_injected
        if self.audits:
            out["audits"] = self.audits
            out["audit_failures"] = self.audit_failures
        if self.tick_ewma_s:
            out["straggler_ticks"] = self.straggler_ticks
            out["tick_ewma_ms"] = round(1e3 * self.tick_ewma_s, 3)
        if self.block_pool_size:
            out["block_pool_size"] = self.block_pool_size
            out["peak_blocks_in_use"] = self.peak_blocks_in_use
            out["block_utilization"] = round(
                self.peak_blocks_in_use / self.block_pool_size, 3)
            if self.shards > 1:
                out["shards"] = self.shards
                out["peak_shard_blocks_in_use"] = self.peak_shard_blocks_in_use
                out["shard_block_utilization"] = round(
                    self.peak_shard_blocks_in_use
                    / (self.block_pool_size // self.shards), 3)
            out["shared_blocks"] = self.shared_blocks
            out["cow_copies"] = self.cow_copies
            out["prefix_hits"] = self.prefix_hits
            # Effective memory saved: every shared/adopted admission avoided
            # one block allocation; every CoW later paid one back. The
            # intra-flight vs cross-request split is gross (pre-CoW).
            saved = self.shared_blocks + self.cache_hit_blocks - self.cow_copies
            out["effective_blocks_saved"] = saved
            out["memory_saved_tokens"] = saved * self.block_size
            if self.cache_hits or self.cache_evictions or self.peak_cache_blocks:
                out["cache_hits"] = self.cache_hits
                out["cache_hit_blocks"] = self.cache_hit_blocks
                out["cache_saved_tokens"] = self.cache_hit_blocks * self.block_size
                out["cache_evictions"] = self.cache_evictions
                out["cache_pinned_blocks"] = self.cache_pinned_blocks
                out["peak_cache_blocks"] = self.peak_cache_blocks
                out["zero_prefill_hits"] = self.zero_prefill_hits
            if self.host_spill:
                out["hot_blocks"] = self.hot_blocks
                out["cold_blocks"] = self.cold_blocks
                out["peak_cold_blocks"] = self.peak_cold_blocks
                out["demotions"] = self.demotions
                out["promotions"] = self.promotions
                out["pcie_bytes"] = self.pcie_bytes
        return out


@dataclass
class _InflightPrefill:
    """One chunked prefill in flight: the engine admits at most one at a
    time (the chunk budget is per tick, so a second in-flight prefill could
    not make progress anyway) — which also bounds the device-state stash to
    a single cursor. The slot is reserved (popped from `_free`) but stays
    masked OFF until the final chunk installs; `_slot_blocks`/`_slot_pos`
    track the covered blocks so preemption releases exactly what was
    charged."""
    req: Request
    slot: int
    cursor: Any                         # PrefillCursor pytree (device)
    consumed: int = 0                   # prompt tokens prefilled so far
    n_shared: int = 0                   # radix-matched prefix blocks
    n_cache: int = 0                    # of those, adopted from the pin cache
    shared_ids: list = field(default_factory=list)
    pages: np.ndarray | None = None     # page row mapped so far (-1 beyond)


class ServingEngine:
    """Slot-pooled continuous-batching driver (single device or mesh ctx).

    ``paged=True`` switches the attention-cache substrate to the paged block
    pool: ``num_blocks`` physical blocks of ``block_size`` tokens are shared
    by all slots, the engine owns the free list, and per-request HBM is
    proportional to tokens actually held. ``block_size`` must divide
    ``max_seq`` so the paged logical capacity (and hence the selection
    parameters) match the dense path exactly — that is the paged-vs-
    contiguous parity contract.

    ``prefix_sharing=True`` (paged only) admits identical prompt prefixes by
    reference: matched full blocks are mapped, refcounted and not rewritten;
    only the divergent tail is charged against the free list. Completion is
    decref-based and shared blocks are copy-on-write (see module docstring).

    ``fused_decode`` pins the paged decode-tick data path: ``True`` fuses
    the page-table walk into the decode kernels (physical-block streaming —
    O(active + selected) pool traffic per tick), ``False`` forces the
    gather baseline (logical-view rebuild per tick), ``None`` (default)
    follows the global ``flags.PERF`` switch. On a mesh-sharded pool the
    knob steers the sharded island (``PERF.sharded_fused_decode``:
    fully-pipelined per-shard kernels vs the logical-gather island);
    unsharded it steers ``PERF.paged_fused_decode``. Outputs are
    bit-identical between the two paths (same selection; greedy tokens
    match), so the knob is purely a performance/benchmarking control.

    A paged engine given a mesh ``ctx`` (``ctx.axis`` set) shards the block
    pool across the mesh: ``num_blocks`` is the GLOBAL pool (must divide
    evenly across the shards) and each device holds
    ``num_blocks / n_shards`` blocks, so a context larger than one device's
    pool spans shards and still decodes shard-locally (module docstring;
    the per-shard free lists live in `ShardedBlockAllocator`).
    """

    def __init__(self, cfg: ModelConfig, params: Any, max_seq: int,
                 slots: int = 4, ctx: DecodeCtx | None = None,
                 greedy: bool = True, seed: int = 0, paged: bool = False,
                 block_size: int = 32, num_blocks: int | None = None,
                 prefix_sharing: bool = False,
                 prefix_cache: bool = False,
                 fused_decode: bool | None = None,
                 kv_pool_dtype: str | None = None,
                 host_spill: bool = False, demote_after: int = 4,
                 spill_keep_recent: int = 2, promote_headroom: int = 1,
                 prefill_chunk: int | None = None, preempt: bool = False,
                 max_queue: int | None = None,
                 faults: FaultPlan | None = None,
                 audit_every: int | None = None,
                 spill_max_retries: int = 3, spill_backoff_base: int = 1,
                 spill_backoff_cap: int = 8,
                 monitor: StepMonitor | None = None,
                 heartbeat_path: str | None = None):
        # Per-engine override of the block pool's storage precision (the
        # tiered-KV first tier). Parameter shapes don't depend on the knob,
        # so the same params serve any pool precision.
        if kv_pool_dtype is not None and kv_pool_dtype != cfg.kv_pool_dtype:
            if not paged:
                raise ValueError("kv_pool_dtype override requires paged=True "
                                 "(the knob names the paged pool's storage)")
            cfg = dataclasses.replace(cfg, kv_pool_dtype=kv_pool_dtype)
        self.cfg = cfg
        self.params = params
        self.max_seq = max_seq
        self.slots = slots
        self.ctx = ctx
        self.greedy = greedy
        self.api = get_model(cfg)
        self.paged = paged
        self.n_shards = 1           # pool shards (paged + mesh ctx only)
        self.stats = ServeStats()
        self._spans = HostSpans(self.stats)
        self._rng = np.random.default_rng(seed)
        # -- fault tolerance / request lifecycle -----------------------
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        if audit_every is not None and audit_every < 1:
            raise ValueError(f"audit_every must be >= 1, got {audit_every}")
        if spill_max_retries < 0 or spill_backoff_base < 1 \
                or spill_backoff_cap < spill_backoff_base:
            raise ValueError("need spill_max_retries >= 0 and "
                             "1 <= spill_backoff_base <= spill_backoff_cap")
        self.max_queue = max_queue
        self._faults = faults
        self.audit_every = audit_every
        self.last_audit = None      # most recent InvariantReport
        self._audited_tick = -1     # dedup: audit each tick index once
        self.spill_max_retries = spill_max_retries
        self.spill_backoff_base = spill_backoff_base
        self.spill_backoff_cap = spill_backoff_cap
        # Per-slot NaN/Inf quarantine: patience 1 — a non-finite logits
        # row cannot yield a token, so the first hit quarantines the slot.
        self._nan_guard = NaNGuard(patience=1)
        self.monitor = monitor
        if self.monitor is None and heartbeat_path is not None:
            self.monitor = StepMonitor(heartbeat_path=heartbeat_path)
        elif self.monitor is not None and heartbeat_path is not None \
                and self.monitor.heartbeat_path is None:
            self.monitor.heartbeat_path = heartbeat_path
        # Slots whose growth was denied by an injected spurious-exhaustion
        # or failed-demote fault: masked off for ONE tick (no decode, no
        # cursor advance — the token stream pauses, nothing is lost) and
        # re-armed at tick end to retry.
        self._stalled: set[int] = set()
        # Slots whose rows the last fused decode actually advanced — the
        # audit may only compare host cursors against device lengths for
        # these (the decode tick zeroes masked-off slots' lengths).
        self._last_decoded: set[int] = set()
        # Spill-transfer retry state, keyed (slot, logical): consecutive
        # failures, the tick a retry unblocks at, and the pinned outcomes
        # after retries exhaust (cold = stays spilled + masked, hot =
        # stays resident).
        self._xfer_attempts: dict[tuple[int, int], int] = {}
        self._xfer_retry_at: dict[tuple[int, int], int] = {}
        self._pinned_cold: set[tuple[int, int]] = set()
        self._pinned_hot: set[tuple[int, int]] = set()
        self._queue: deque[Request] = deque()
        self._active: dict[int, Request] = {}       # slot -> request
        self._free: list[int] = sorted(range(slots), reverse=True)  # pop() → lowest
        # Host-side per-slot buffers: next token to feed, and the mask.
        self._tokens = np.zeros((slots,), np.int32)
        self._mask = np.zeros((slots,), bool)
        donate = jax.default_backend() != "cpu"
        dn = (0,) if donate else ()
        if prefix_sharing and not paged:
            raise ValueError("prefix_sharing requires paged=True")
        self.prefix_sharing = prefix_sharing
        if prefix_cache and not prefix_sharing:
            raise ValueError("prefix_cache requires prefix_sharing=True "
                             "(the cache retains radix-mapped blocks past "
                             "their last resident owner)")
        if prefix_cache and cfg.kv_pool_dtype == "int4":
            raise ValueError(
                "prefix_cache does not support int4 pools: the in-place "
                "append requantizes a whole partial block, so retained "
                "prefix bytes would diverge from a cold prefill")
        self.prefix_cache = prefix_cache
        self._adopt = None
        if paged:
            if self.api.init_paged_state is None:
                raise ValueError(f"{cfg.name}: paged serving not supported "
                                 "for this model family")
            if max_seq % block_size != 0:
                raise ValueError(
                    f"block_size {block_size} must divide max_seq {max_seq} "
                    "(paged-vs-contiguous parity contract)")
            self.block_size = block_size
            self.max_blocks = max_seq // block_size
            # Default pool = dense-equivalent token budget (slots × max_seq);
            # the point of paging is that callers pass much less.
            self.num_blocks = num_blocks or slots * self.max_blocks
            self.stats.block_pool_size = self.num_blocks
            self.stats.block_size = block_size
            # Mesh-sharded pool: one free list per shard; the device-side
            # ownership rule (contiguous global-id ranges) and this host-side
            # split agree by construction.
            self.n_shards = self._mesh_shards(ctx)
            if self.num_blocks % self.n_shards:
                raise ValueError(
                    f"num_blocks {self.num_blocks} must split evenly across "
                    f"{self.n_shards} pool shards")
            self.stats.shards = self.n_shards
            self._alloc = ShardedBlockAllocator(self.num_blocks, self.n_shards)
            self._slot_blocks: dict[int, list[int]] = {}
            self._slot_pos: dict[int, int] = {}     # next write position
            # Host mirror of the per-block refcount (the device arrays carry
            # the same counts; the mirror drives scheduling without a sync).
            self._refcount = np.zeros((self.num_blocks,), np.int64)
            # Radix map: sha1 of the token-id bytes of a full-block prefix
            # (or an exact full prompt ending in a partial block) → the
            # physical block holding it + the owner's heavy-channel bytes.
            self._prefix_nodes: dict[bytes, tuple[int, bytes]] = {}
            self._block_keys: dict[int, bytes] = {}  # block → its radix key
            # Persistent prefix cache (prefix_cache=True): blocks whose last
            # resident owner released but whose radix entry survives. The
            # pin is HOST-ONLY — device refcount stays 0 (nothing references
            # the block), the allocator simply never gets the id back —
            # block id → last-hit stamp (monotonic `_cache_clock`, drives
            # LRU eviction under allocator pressure). `_node_depth` records
            # each registered block's logical index so eviction can order
            # equal-stamp blocks deepest-first (never orphaning a radix
            # chain); `_logits_cache` keeps the first-token logits row per
            # full-prompt key (what makes a full hit admit with ZERO
            # prefill); `_cold_cache` is the host tier for pinned blocks
            # demoted under HBM pressure (prefix_cache × host_spill):
            # radix key → (payload, heavy, depth, stamp), with the node's
            # block id set to the CACHE_COLD sentinel while demoted.
            self._cached: dict[int, int] = {}
            self._cache_clock = 0
            self._node_depth: dict[int, int] = {}
            self._logits_cache: dict[bytes, np.ndarray] = {}
            self._cold_cache: dict[bytes, tuple] = {}
            self._state = self.api.init_paged_state(
                slots, max_seq, block_size, self.num_blocks)
            self._write = jax.jit(self.api.write_into_pages, donate_argnums=dn)
            self._map_block = jax.jit(self.api.map_block, donate_argnums=dn)
            self._cow_block = jax.jit(self.api.cow_block, donate_argnums=dn)
            if prefix_cache and self.api.adopt_pages is not None \
                    and self.api.static_heavy is not None \
                    and cfg.salca_static_channels \
                    and self.api.prefill_chunk_unsupported is not None \
                    and self.api.prefill_chunk_unsupported() is None:
                # Zero-prefill warm admission. Metadata-only adoption is
                # sound exactly where chunked prefill is: all-"A" stacks
                # (no dense per-slot substate that a prefill would have to
                # rebuild) encoded against the static heavy-channel set the
                # retained rows carry. Other configs still hit the cache —
                # they just re-prefill and map the matched blocks by
                # reference (n_shared), which is the same bytes-saved, not
                # the same latency.
                self._adopt = jax.jit(self.api.adopt_pages,
                                      donate_argnums=(1,) if donate else ())
        else:
            if host_spill:
                raise ValueError("host_spill requires paged=True (the host "
                                 "tier holds physical pool blocks)")
            # The one persistent pooled decode state (slots × max_seq caches).
            self._state = self.api.init_state(slots, max_seq)
            self._write = jax.jit(self.api.write_into_slot, donate_argnums=dn)

        # Tiered KV memory: the second (host) tier. Rarely-selected private
        # blocks demote to a numpy mirror — storage format, so the round
        # trip is bit-exact — freeing their physical block; a spilled block
        # is unselectable (`mapped_valid_mask`) until promoted back.
        self.host_spill = host_spill
        if host_spill:
            if self.n_shards > 1:
                raise ValueError(
                    "host_spill is not supported on a mesh-sharded pool: the "
                    "sharded decode island does not record selection "
                    "histograms (leave the mesh ctx off or spill unsharded)")
            # prefix_sharing may combine with host_spill: resident
            # radix-published blocks are excluded from demotion (the map
            # must keep pointing at live device bytes — see
            # `_demote_candidates`), and cache-pinned blocks (zero resident
            # owners) demote through their own cold tier (`_cold_cache`),
            # promoting back on a radix hit.
            if self.api.read_block is None:
                raise ValueError(f"{cfg.name}: host spill not supported "
                                 "for this model family")
            if demote_after < 1 or spill_keep_recent < 1:
                raise ValueError("demote_after and spill_keep_recent must be "
                                 ">= 1 (the cursor block must stay hot)")
            self.demote_after = demote_after
            self.spill_keep_recent = spill_keep_recent
            self.promote_headroom = promote_headroom
            # Read must NOT donate — the state stays live; write may.
            self._read_block = jax.jit(self.api.read_block)
            self._write_block = jax.jit(self.api.write_block,
                                        donate_argnums=dn)
            self._sel_hist_fn = jax.jit(self.api.selection_hist)
            self._spilled: dict[tuple[int, int], Any] = {}
            self._spill_score: dict[tuple[int, int], float] = {}
            self._hist_snap = np.zeros((slots, self.max_blocks), np.int64)
            self._cold_streak = np.zeros((slots, self.max_blocks), np.int32)
            # Bytes one logical block's data rows occupy across every paged
            # layer — the PCIe unit for the predicted-transfer accounting.
            shapes = jax.eval_shape(self.api.read_block, self._state,
                                    jnp.int32(0))
            self._block_bytes = int(sum(
                int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
                for l in jax.tree_util.tree_leaves(shapes)))
            self.stats.host_spill = True

        # ``fused_decode`` pins the paged decode data path for this engine
        # (None → follow the global PERF flags). The flags are read at trace
        # time, so wrapping the tick trace is sufficient — jit caches the
        # traced program. On a mesh-sharded pool the knob steers
        # PERF.sharded_fused_decode (fully-pipelined island vs the PR 5
        # logical-gather island); unsharded it steers
        # PERF.paged_fused_decode (in-kernel page-table walk vs the PR 3
        # gather path). Either way both settings produce the same selection
        # bit-for-bit, so the knob stays a performance/benchmarking control.
        self.fused_decode = fused_decode
        _fused_flag = ("sharded_fused_decode" if paged and self.n_shards > 1
                       else "paged_fused_decode")

        def _tick_fn(p, s, tok, act):
            if self.fused_decode is None:
                logits, s2 = self.api.decode_step(p, s, tok, ctx, active=act)
            else:
                from repro.flags import perf_flags
                with perf_flags(**{_fused_flag: self.fused_decode}):
                    logits, s2 = self.api.decode_step(p, s, tok, ctx, active=act)
            with jax.named_scope("dense"):
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                # Per-slot quarantine signal: one (slots,) bool riding the
                # existing device→host sync — a poisoned slot is detected
                # without fetching the full logits and without an extra sync.
                finite = jnp.isfinite(logits).all(axis=-1)
            return nxt, logits, finite, s2

        # One fused program per tick. jax.jit caches by shape, so the mask
        # flipping values never retraces. The pooled state is donated into
        # every consumer (decode / write / reset / map_block) so XLA updates
        # the KV pool in place instead of copying it per tick — except on
        # CPU, where donation is unimplemented and only warns.
        self._decode = jax.jit(_tick_fn, donate_argnums=(1,) if donate else ())
        self._prefill = jax.jit(
            lambda p, toks: self.api.prefill(p, {"tokens": toks}, self.max_seq))
        self._reset = jax.jit(self.api.reset_slot, donate_argnums=dn)

        # Bounded prefill stash: AT MOST ONE head-of-line request keeps a
        # batch=1 device prefill state between admission attempts (it used
        # to live on every queued Request, pinning a full state per blocked
        # request — a queued burst could exhaust HBM before admission).
        self._stash: tuple[Request, tuple] | None = None

        # -- continuous batching: chunked prefill + preemption ----------
        self.prefill_chunk = prefill_chunk
        self.preempt = preempt
        self._inflight: _InflightPrefill | None = None
        self._static_heavy_cache: bytes | None = None
        if preempt and not paged:
            raise ValueError("preempt requires paged=True (preemption frees "
                             "pool blocks; dense slots have nothing to free)")
        if prefill_chunk is not None:
            if prefill_chunk < 1:
                raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
            if not paged:
                raise ValueError("prefill_chunk requires paged=True (chunks "
                                 "stream into a partially-filled paged slot)")
            if host_spill:
                raise ValueError("prefill_chunk cannot combine with "
                                 "host_spill's wave admission (one pressure "
                                 "valve per engine; use preempt instead)")
            if self.api.prefill_chunk is None:
                raise ValueError(f"{cfg.name}: chunked prefill not supported "
                                 "for this model family")
            reason = self.api.prefill_chunk_unsupported()
            if reason is not None:
                raise ValueError(f"chunked prefill unsupported: {reason}")
            if cfg.kv_pool_dtype == "int4":
                raise ValueError("chunked prefill does not support int4 "
                                 "pools (per-block requantization is not "
                                 "chunk-incremental)")
            # donate the pool state so the streaming install is in place;
            # the cursor is NOT donated — a fresh cursor's zero K/V buffers
            # can alias each other (XLA constant dedup) and donating aliased
            # buffers is an error. `final` is static (two programs per
            # chunk shape).
            self._chunk_step = jax.jit(
                lambda p, s, toks, cur, slot, pages, nsh, final: \
                    self.api.prefill_chunk(p, s, toks, cur, slot, pages, nsh,
                                           self.max_seq, final=final),
                static_argnames=("final",),
                donate_argnums=(1,) if donate else ())

    @staticmethod
    def _mesh_shards(ctx: DecodeCtx | None) -> int:
        """Pool shard count = product of the mesh sizes of ctx.axis."""
        if ctx is None or ctx.axis is None or ctx.mesh is None:
            return 1
        axes = ctx.axis if isinstance(ctx.axis, (tuple, list)) else (ctx.axis,)
        n = 1
        for a in axes:
            n *= ctx.mesh.shape[a]
        return n

    @property
    def _free_blocks(self) -> list[int]:
        """Flat free-block snapshot (kept for tests/introspection; mutations
        go through `self._alloc`)."""
        return self._alloc.free_ids()

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> bool:
        """Enqueue a request. Returns False when the bounded queue shed it
        (``stop_reason="rejected"``) — load shedding keeps queue wait (and
        hence TTFT for everyone admitted) bounded instead of letting the
        deque grow without limit under overload. Malformed requests still
        raise: a config error is a bug, not load."""
        if len(req.prompt) + req.max_new_tokens > self.max_seq:
            raise ValueError(
                f"request {req.rid}: prompt({len(req.prompt)}) + "
                f"max_new_tokens({req.max_new_tokens}) exceeds max_seq={self.max_seq}")
        if self.paged:
            # Lifetime need: KV is written for prompt + (max_new - 1) tokens
            # (the final sampled token's KV is never stored). A request that
            # exceeds the whole pool can never complete even when alone —
            # that is a config error, rejected here like the dense max_seq
            # guard. Overflow stops remain for pool *contention*.
            lifetime = len(req.prompt) + max(req.max_new_tokens - 1, 0)
            if not self.host_spill \
                    and self._blocks_for(lifetime) > self.num_blocks:
                # With the host tier, a context larger than the device pool
                # is exactly the case spilling exists for — admitted in
                # waves, cold blocks live on the host.
                raise ValueError(
                    f"request {req.rid}: needs {self._blocks_for(lifetime)} "
                    f"blocks over its lifetime but the pool only has "
                    f"{self.num_blocks}")
        if self.max_queue is not None and len(self._queue) >= self.max_queue:
            req.done_time = time.time()
            req.stop_reason = "rejected"
            self.stats.rejections += 1
            return False
        self._queue.append(req)
        return True

    def cancel(self, rid: int) -> bool:
        """Cancel a request by id wherever it lives: still queued (removed,
        no admission cycle to settle), mid-chunked-prefill (the reserved
        slot, charged blocks and device cursor are released), or resident
        (finished through the normal decref path). Returns False when no
        live request has that id (already finished or never submitted)."""
        now = time.time()
        for req in self._queue:
            if req.rid == rid:
                self._queue.remove(req)
                self._terminate_queued(req, now, "cancelled")
                self.stats.cancellations += 1
                return True
        if self._inflight is not None and self._inflight.req.rid == rid:
            self._abort_inflight(now, "cancelled")
            self.stats.cancellations += 1
            return True
        for slot, req in list(self._active.items()):
            if req.rid == rid:
                self._finish(slot, req, now, "cancelled")
                self.stats.cancellations += 1
                return True
        return False

    # -- deadlines & queue-side termination ----------------------------

    @staticmethod
    def _expired(req: Request, now: float) -> bool:
        return req.deadline_ms is not None \
            and (now - req.submitted) * 1e3 >= req.deadline_ms

    def _terminate_queued(self, req: Request, now: float, reason: str) -> None:
        """Settle a request that dies while still queued. If its admission
        cycle already began (the prefix-sharing gate prefill can start work
        on the queue head before blocks are available), the cycle is closed
        in `completed` so `admissions == completed + preemptions` holds at
        drain; a request that never started work touches no cycle counter."""
        req.done_time = now
        req.stop_reason = reason
        self._drop_stash(req)
        if req._cycle_started:
            self.stats.completed += 1

    def _abort_inflight(self, now: float, reason: str) -> None:
        """Tear down the in-flight chunked prefill: the reserved slot, the
        blocks its chunks charged, and the device cursor are all released;
        the admission cycle (opened when the prefill started) closes in
        `completed`."""
        inf = self._inflight
        self._inflight = None           # drop the cursor (device buffers)
        req = inf.req
        req.done_time = now
        req.stop_reason = reason
        self.stats.completed += 1
        self._free.append(inf.slot)
        self._free.sort(reverse=True)
        self._release_blocks(inf.slot)
        self._state = self._reset(self._state, jnp.int32(inf.slot))

    def _shed_expired_queue(self) -> None:
        """Drop queued requests whose deadline already passed — spending
        prefill on a request nobody is waiting for anymore only delays the
        live ones behind it."""
        if not any(r.deadline_ms is not None for r in self._queue):
            return
        now = time.time()
        keep: deque[Request] = deque()
        while self._queue:
            req = self._queue.popleft()
            if self._expired(req, now):
                self._terminate_queued(req, now, "deadline")
                self.stats.deadline_stops += 1
            else:
                keep.append(req)
        self._queue = keep

    # -- fault-injection plumbing --------------------------------------

    def _fault(self, site: str, **ctx) -> bool:
        """Consult the engine's FaultPlan at one injection site."""
        if self._faults is not None and self._faults.fires(site, **ctx):
            self.stats.faults_injected += 1
            return True
        return False

    def _alloc_blocks(self, need: int,
                      prefer: int | None = None) -> list[int] | None:
        """Allocator front-end with the ``alloc_exhausted`` injection site:
        a fired fault makes this call spuriously report an empty pool —
        callers then take the same degraded paths a genuinely dry pool
        exercises (admission waits, chunk stalls, growth stalls the slot)."""
        if need > 0 and self._fault("alloc_exhausted", need=need):
            return None
        return self._alloc.alloc(need, prefer)

    def _stall(self, slot: int) -> None:
        """Pause one active slot for the current tick: masked off, so the
        fused decode neither reads nor writes it and its cursor holds; the
        token stream resumes, bit-identical, once the fault clears."""
        self._mask[slot] = False
        self._stalled.add(slot)

    def _xfer_failed(self, key: tuple[int, int], pin: str) -> None:
        """Record one failed spill transfer: capped exponential backoff in
        ticks (base·2^(n-1), capped), then — retries exhausted — pin the
        block where it is: ``cold`` (stays spilled AND masked; decode
        continues with sparser attention over the resident blocks) or
        ``hot`` (stays device-resident; only spill capacity degrades)."""
        self.stats.retries += 1
        n = self._xfer_attempts.get(key, 0) + 1
        self._xfer_attempts[key] = n
        if n > self.spill_max_retries:
            (self._pinned_cold if pin == "cold" else self._pinned_hot).add(key)
            self._xfer_retry_at.pop(key, None)
        else:
            delay = min(self.spill_backoff_base * (2 ** (n - 1)),
                        self.spill_backoff_cap)
            self._xfer_retry_at[key] = self.stats.ticks + delay

    def _xfer_ok(self, key: tuple[int, int]) -> None:
        self._xfer_attempts.pop(key, None)
        self._xfer_retry_at.pop(key, None)

    def _xfer_blocked(self, key: tuple[int, int]) -> bool:
        """True while a key is backing off (retry not due yet)."""
        return self._xfer_retry_at.get(key, -1) > self.stats.ticks

    def _blocks_for(self, tokens: int) -> int:
        return max(1, -(-tokens // self.block_size))

    def _note_block_usage(self) -> None:
        used = self.num_blocks - self._alloc.total_free
        self.stats.blocks_in_use = used
        self.stats.peak_blocks_in_use = max(self.stats.peak_blocks_in_use, used)
        if self.host_spill:
            self.stats.hot_blocks = used
            self.stats.cold_blocks = len(self._spilled)
            self.stats.peak_cold_blocks = max(self.stats.peak_cold_blocks,
                                              len(self._spilled))
        if self.n_shards > 1:
            hot = max(self._alloc.blocks_per_shard - f
                      for f in self._alloc.free_counts())
            self.stats.peak_shard_blocks_in_use = max(
                self.stats.peak_shard_blocks_in_use, hot)

    def _sample(self, req: Request, logits_row: np.ndarray) -> int:
        """Per-slot sampling from a (V_pad,) logits row."""
        temp = 0.0 if self.greedy else req.temperature
        if temp <= 0.0:
            return int(np.argmax(logits_row))
        z = logits_row.astype(np.float64) / temp
        g = self._rng.gumbel(size=z.shape)
        return int(np.argmax(z + g))

    # -- prefix sharing helpers ----------------------------------------

    def _request_digests(self, req: Request):
        """Cumulative SHA-1 digests of the prompt's token-id bytes — one per
        full block, plus one for the whole prompt when it ends in a partial
        block. Computed incrementally (O(prompt) total, vs O(blocks²) for
        per-prefix re-hashing) and memoized on the request across
        head-of-line retries. digest j == sha1(prompt[:(j+1)·BS]) exactly.
        """
        if req._digests is None:
            bs, prompt = self.block_size, req.prompt
            buf = np.ascontiguousarray(prompt, np.int32).tobytes()
            h = hashlib.sha1()
            full_keys = []
            for j in range(len(prompt) // bs):
                h.update(buf[j * bs * 4:(j + 1) * bs * 4])
                full_keys.append(h.copy().digest())
            partial_key = None
            if len(prompt) % bs:
                h.update(buf[len(full_keys) * bs * 4:])
                partial_key = h.digest()
            req._digests = (full_keys, partial_key)
        return req._digests

    def _ensure_prefill(self, req: Request):
        """Prefill once per request; the result is stashed ENGINE-side so
        head-of-line retries (waiting on blocks) and the heavy-channel gate
        don't pay it twice. The stash holds at most ONE request's batch=1
        device state — only the queue head can be waiting on blocks, so a
        bigger stash would just pin HBM for requests that cannot admit yet.
        A different request taking the head (preemption requeue) replaces
        the stash; `_drop_stash` clears it on requeue and admission."""
        if self._stash is not None and self._stash[0] is req:
            return self._stash[1]
        logits, state1 = self._prefill(
            self.params, jnp.asarray(req.prompt[None]))
        logits_row = np.asarray(logits)[0]              # blocks until ready
        self.stats.prefill_tokens += len(req.prompt)
        self._stash = (req, (logits_row, state1))
        return self._stash[1]

    def _drop_stash(self, req: Request | None = None) -> None:
        """Free the engine prefill stash (all requests, or only `req`'s)."""
        if self._stash is not None and (req is None or self._stash[0] is req):
            self._stash = None

    def _begin_cycle(self, req: Request, t0: float) -> None:
        """Account the start of one admission cycle: close the queue-wait
        segment that began at submit (first cycle) or at the preemption
        requeue (later cycles). Re-admission must NOT reset `submitted` —
        TTFT keeps measuring from the original submit — and queue wait
        accumulates across cycles. Idempotent within a cycle: the gate
        prefill may start work on an attempt that then waits for blocks."""
        if req._cycle_started:
            return
        req._cycle_started = True
        since = req.submitted if req._requeued_at is None else req._requeued_at
        wait = max(t0 - since, 0.0)
        req._queue_wait += wait
        self.stats.queue_wait_s += wait
        self.stats.admissions += 1
        if req.admitted is None:
            req.admitted = t0

    def _heavy_bytes(self, state1) -> bytes:
        """Concatenated heavy-channel index bytes of every attention cache
        in a batch=1 prefill state — the sharing gate's identity. The packed
        feature blocks are encoded against these sets, so two requests may
        alias blocks only when every layer's set matches bit-exactly."""
        from repro.core.cache import SalcaCache
        parts = []
        for st in list(state1.period_states) + list(state1.tail_states):
            if isinstance(st, SalcaCache):
                parts.append(np.asarray(st.heavy_idx).tobytes())
        return b"".join(parts)

    def _match_tokens(self, req: Request) -> list[tuple[bytes, int, bytes]]:
        """Longest-prefix radix match on token ids alone (the cheap gate,
        run before prefill): full blocks first, then — only when every full
        block matched — an exact-full-prompt partial block. Returns
        [(key, block_id, owner_heavy_bytes), ...]."""
        full_keys, partial_key = self._request_digests(req)
        out = []
        for key in full_keys:
            node = self._prefix_nodes.get(key)
            if node is None:
                return out
            out.append((key,) + node)
        if partial_key is not None:
            node = self._prefix_nodes.get(partial_key)
            if node is not None:
                out.append((partial_key,) + node)
        return out

    def _register_blocks(self, req: Request, blocks: list[int],
                         n_shared: int, heavy: bytes,
                         logits_row: np.ndarray | None = None) -> None:
        """Publish this request's PRIVATE blocks into the radix map so later
        requests can share them. Shared blocks are already published. With
        the persistent cache on, the first-token logits row is retained
        under the full-prompt key so an identical later prompt can admit
        with zero prefill (`_try_adopt`)."""
        full_keys, partial_key = self._request_digests(req)
        keys = full_keys + ([partial_key] if partial_key is not None else [])
        for j in range(n_shared, self._blocks_for(len(req.prompt))):
            key = keys[j]
            if key not in self._prefix_nodes and blocks[j] not in self._block_keys:
                self._prefix_nodes[key] = (blocks[j], heavy)
                self._block_keys[blocks[j]] = key
                self._node_depth[blocks[j]] = j
        if self.prefix_cache and logits_row is not None and keys \
                and keys[-1] in self._prefix_nodes:
            # The row is a pure function of the prompt (prefill is
            # deterministic), so serving it on a warm hit is bit-exact by
            # construction; it is dropped whenever its key leaves the map.
            self._logits_cache[keys[-1]] = np.array(logits_row, copy=True)

    def _prune_node(self, block: int) -> None:
        """Remove a block's radix registration and every dependent cached
        artifact (logits row, depth, any cold payload under the same key)."""
        key = self._block_keys.pop(block, None)
        if key is not None:
            self._prefix_nodes.pop(key, None)
            self._logits_cache.pop(key, None)
            self._cold_cache.pop(key, None)
        self._node_depth.pop(block, None)

    def _release_blocks(self, slot: int) -> None:
        """Decref every block the slot references; blocks reaching zero
        return to the free list and leave the radix map — unless the
        persistent cache is on and the block is radix-published, in which
        case the engine retains it under a cache pin (host-only: device
        refcount stays 0, the allocator never sees the id) so a later
        same-prefix request can adopt it. Releasing a slot that holds
        nothing (double free: overflow finish racing a reset) is a no-op —
        the free list is never corrupted."""
        blocks = self._slot_blocks.pop(slot, None)
        if blocks is None:
            return
        stamp = None                # one LRU stamp per release event: the
        for b in blocks:            # chain's depth order breaks the tie
            if b == SPILLED:
                continue                    # host-tier entry: no device block
            self._refcount[b] -= 1
            assert self._refcount[b] >= 0, f"block {b} refcount underflow"
            if self._refcount[b] == 0:
                if self.prefix_cache and b in self._block_keys:
                    if stamp is None:
                        self._cache_clock += 1
                        stamp = self._cache_clock
                    self._cached[b] = stamp
                    self._note_cache_usage()
                else:
                    self._alloc.release(b)  # back to its owner shard's list
                    self._prune_node(b)
        self._slot_pos.pop(slot, None)
        if self.host_spill:
            for key in [k for k in self._spilled if k[0] == slot]:
                del self._spilled[key]
                self._spill_score.pop(key, None)
            self._hist_snap[slot] = 0
            self._cold_streak[slot] = 0
            # Transfer retry/pin state is per-occupancy: the next request
            # in this slot starts with a clean record.
            for d in (self._xfer_attempts, self._xfer_retry_at):
                for key in [k for k in d if k[0] == slot]:
                    del d[key]
            self._pinned_cold = {k for k in self._pinned_cold
                                 if k[0] != slot}
            self._pinned_hot = {k for k in self._pinned_hot if k[0] != slot}
        self._note_block_usage()

    # -- persistent prefix cache ---------------------------------------

    def _note_cache_usage(self) -> None:
        n = len(self._cached)
        self.stats.cache_pinned_blocks = n
        self.stats.peak_cache_blocks = max(self.stats.peak_cache_blocks, n)

    def _cache_victim(self, protect=frozenset()) -> int | None:
        """LRU victim among the cache pins: oldest last-hit stamp first,
        DEEPEST logical index first on ties. Owners of a child block always
        own its ancestors, so a parent's last release (= pin stamp) happens
        at-or-after every child's — this order never reclaims an ancestor
        while a pinned descendant remains, so a radix chain can't orphan."""
        cand = [(stamp, -self._node_depth.get(b, 0), b)
                for b, stamp in self._cached.items() if b not in protect]
        return min(cand)[2] if cand else None

    def _evict_cache_block(self, protect=frozenset()) -> bool:
        """Reclaim ONE cache-pinned block outright: prune its radix node
        (plus logits row) and return the id to the allocator. The block is
        already fully unmapped (refcount 0) — eviction is pure bookkeeping,
        which is why the scheduler drains the cache before it ever demotes
        or preempts. Returns False when nothing is evictable."""
        b = self._cache_victim(protect)
        if b is None:
            return False
        del self._cached[b]
        self._prune_node(b)
        self._alloc.release(b)
        self.stats.cache_evictions += 1
        self._note_cache_usage()
        self._note_block_usage()
        return True

    def _demote_cache_block(self, protect=frozenset()) -> bool:
        """Move ONE cache-pinned block's rows to the host cold tier
        (prefix_cache × host_spill): the radix key stays matchable under the
        CACHE_COLD sentinel and promotes back on the next hit, so HBM
        pressure squeezes the cache without forgetting it. Preferred over
        outright eviction whenever the host tier exists."""
        b = self._cache_victim(protect)
        if b is None:
            return False
        key = self._block_keys[b]
        heavy = self._prefix_nodes[key][1]
        payload = jax.tree_util.tree_map(
            np.asarray, self._read_block(self._state, jnp.int32(b)))
        self._cold_cache[key] = (payload, heavy,
                                 self._node_depth.get(b, 0), self._cached[b])
        del self._cached[b]
        del self._block_keys[b]         # the physical id is about to be reused
        self._node_depth.pop(b, None)
        self._prefix_nodes[key] = (CACHE_COLD, heavy)
        self._alloc.release(b)
        self.stats.demotions += 1
        self.stats.pcie_bytes += self._block_bytes
        # Bound the host tier to one pool's worth of entries: beyond that
        # the LRU-oldest cold entry is dropped outright.
        if len(self._cold_cache) > self.num_blocks:
            victim = min(self._cold_cache,
                         key=lambda k: (self._cold_cache[k][3],
                                        -self._cold_cache[k][2]))
            self._prefix_nodes.pop(victim, None)
            self._logits_cache.pop(victim, None)
            del self._cold_cache[victim]
            self.stats.cache_evictions += 1
        self._note_cache_usage()
        self._note_block_usage()
        return True

    def _promote_cached(self, key: bytes,
                        protect=frozenset()) -> int | None:
        """Rehydrate one cold cache entry to a device block (radix hit on a
        demoted prefix): allocate, write the mirrored rows back (bit-exact —
        storage format both ways) and re-pin hot under its original stamp.
        A dry allocator first drains OTHER cache pins (`protect` carries the
        blocks the in-progress match depends on — a hit must never reclaim
        itself). Returns None when the pool still can't supply a block —
        callers truncate the match there and the request re-prefills that
        span (still bit-exact, just colder)."""
        if key not in self._cold_cache:
            return None         # LRU-dropped by a reclaim mid-match
        payload, heavy, depth, stamp = self._cold_cache[key]
        fresh = self._alloc.alloc(1)
        if fresh is None:
            self._reclaim_cache(1, protect=protect)
            if key not in self._cold_cache:
                return None     # the squeeze dropped this very entry
            fresh = self._alloc.alloc(1)
        if fresh is None:
            return None
        b = fresh[0]
        self._state = self._write_block(self._state, jnp.int32(b),
                                        jax.device_put(payload))
        del self._cold_cache[key]
        self._prefix_nodes[key] = (b, heavy)
        self._block_keys[b] = key
        self._node_depth[b] = depth
        self._cached[b] = stamp         # pinned hot until a hit adopts it
        self.stats.promotions += 1
        self.stats.pcie_bytes += self._block_bytes
        self._note_cache_usage()
        self._note_block_usage()
        return b

    def _reclaim_cache(self, need: int, protect=frozenset()) -> None:
        """Drain the cold (LRU) end of the prefix cache until the allocator
        can cover `need` blocks, or the cache is dry. A cache-pinned block
        is the CHEAPEST reclaim — no resident request loses state — so
        every pressure path (admission, chunk charging, growth, CoW,
        preemption) calls this before host-spill demotion or the preemption
        machinery fires. With the host tier available, pinned blocks demote
        to the cold cache (the entry stays warm across the squeeze) instead
        of being evicted outright."""
        while self._alloc.total_free < need:
            if self.host_spill and self._demote_cache_block(protect):
                continue
            if not self._evict_cache_block(protect):
                return

    def flush_prefix_cache(self) -> int:
        """Drop every persistent-cache entry (hot pins and cold payloads);
        returns the number flushed. Resident requests and their radix
        entries are untouched — this only forgets finished prefixes."""
        if not self.paged:
            return 0
        n = 0
        while self._evict_cache_block():
            n += 1
        for key in list(self._cold_cache):
            self._prefix_nodes.pop(key, None)
            self._logits_cache.pop(key, None)
            del self._cold_cache[key]
            self.stats.cache_evictions += 1
            n += 1
        return n

    # -- tiered KV memory: host spill of cold blocks -------------------

    def demote_block(self, slot: int, logical: int,
                     _inject: bool = True) -> bool:
        """Move one mapped PRIVATE block device → host: copy its storage-
        format data rows into the numpy mirror, unmap the page-table entry
        (the block becomes unselectable via `mapped_valid_mask` — never
        garbage-read) and return the physical id to the free list.

        Returns False when the transfer fails (``spill_transfer`` fault):
        the block stays resident and intact, and the key backs off /
        eventually pins hot. ``_inject=False`` bypasses the injection site
        (wave admission — one atomic multi-wave transaction whose internal
        demotes are not an injection point)."""
        held = self._slot_blocks[slot]
        blk = held[logical]
        assert blk >= 0 and self._refcount[blk] == 1, \
            f"demote needs a mapped private block, got (slot={slot}, " \
            f"logical={logical}) -> {blk} rc={self._refcount[max(blk, 0)]}"
        if _inject and self._fault("spill_transfer", direction="demote",
                                   slot=slot, logical=logical):
            self._xfer_failed((slot, logical), pin="hot")
            return False
        self._xfer_ok((slot, logical))
        payload = jax.tree_util.tree_map(
            np.asarray, self._read_block(self._state, jnp.int32(blk)))
        self._spilled[(slot, logical)] = payload
        # Resurrect priority = the block's historical relevance: cumulative
        # selected-token count at demotion time (the paper's additive
        # histograms, repurposed as the tier policy's score estimate).
        self._spill_score[(slot, logical)] = float(
            self._hist_snap[slot, logical])
        self._state = self._map_block(self._state, jnp.int32(slot),
                                      jnp.int32(logical), jnp.int32(-1))
        self._refcount[blk] -= 1
        self._alloc.release(blk)
        held[logical] = SPILLED
        self.stats.demotions += 1
        self.stats.pcie_bytes += self._block_bytes
        self._note_block_usage()
        return True

    def promote_block(self, slot: int, logical: int) -> bool:
        """Move one spilled block host → device: allocate a physical block,
        `jax.device_put` the mirrored rows back (bit-exact — storage format
        both ways) and remap it. Returns False when no block is free OR the
        transfer fails (``spill_transfer`` fault) — a failed transfer backs
        the key off and, with retries exhausted, pins it cold: the mirror
        payload is untouched, the block stays masked, decode continues."""
        payload = self._spilled.get((slot, logical))
        assert payload is not None, f"({slot}, {logical}) is not spilled"
        if self._fault("spill_transfer", direction="promote",
                       slot=slot, logical=logical):
            self._xfer_failed((slot, logical), pin="cold")
            return False
        fresh = self._alloc_blocks(1)
        if fresh is None:
            return False
        self._xfer_ok((slot, logical))
        blk = fresh[0]
        self._state = self._write_block(self._state, jnp.int32(blk),
                                        jax.device_put(payload))
        self._state = self._map_block(self._state, jnp.int32(slot),
                                      jnp.int32(logical), jnp.int32(blk))
        self._refcount[blk] += 1
        self._slot_blocks[slot][logical] = blk
        del self._spilled[(slot, logical)]
        self._spill_score.pop((slot, logical), None)
        self._cold_streak[slot, logical] = 0
        self.stats.promotions += 1
        self.stats.pcie_bytes += self._block_bytes
        self._note_block_usage()
        return True

    def _update_cold_streaks(self) -> None:
        """Diff the device-side selection histograms against the last
        snapshot: a (slot, block) whose count did not move went one more
        tick unselected."""
        hist = np.asarray(self._sel_hist_fn(self._state)).astype(np.int64)
        touched = (hist - self._hist_snap) > 0
        self._hist_snap = hist
        self._cold_streak[touched] = 0
        self._cold_streak[~touched] += 1

    def _demote_candidates(self) -> list[tuple[int, int, int]]:
        """Eligible demotions, coldest first: (-streak, slot, logical) for
        every mapped PRIVATE block outside the per-slot recency window
        (`spill_keep_recent` trailing blocks — the cursor block among them —
        always stay hot)."""
        out = []
        for slot in self._active:
            held = self._slot_blocks[slot]
            n_blocks = self._blocks_for(max(self._slot_pos[slot], 1))
            hot_limit = max(n_blocks - self.spill_keep_recent, 0)
            for j in range(min(hot_limit, len(held))):
                b = held[j]
                if b == SPILLED or self._refcount[b] != 1:
                    continue
                if b in self._block_keys:
                    # Radix-published: the map must keep pointing at live
                    # device bytes while a resident owner exists. Only the
                    # cache tier (zero owners) demotes published blocks,
                    # through `_demote_cache_block`'s cold path.
                    continue
                if (slot, j) in self._pinned_hot or self._xfer_blocked((slot, j)):
                    continue            # demote retries exhausted / backing off
                out.append((-int(self._cold_streak[slot, j]), slot, j))
        out.sort()
        return out

    def _spill_policy(self) -> None:
        """Post-tick demotion pass: every private block outside the recency
        window that no layer selected for `demote_after` consecutive ticks
        moves to the host tier."""
        if not (self.host_spill and self._active):
            return
        self._update_cold_streaks()
        for neg_streak, slot, j in self._demote_candidates():
            if -neg_streak >= self.demote_after:
                self.demote_block(slot, j)

    def _promote_resurrected(self) -> None:
        """Pre-tick promotion pass: while the pool has headroom beyond
        `promote_headroom`, bring back each slot's spilled block with the
        highest resurrect score — at most one per slot per tick, bounding
        the PCIe traffic a tick can incur."""
        if not (self.host_spill and self._spilled):
            return
        best: dict[int, tuple[float, int]] = {}
        for (slot, j), score in self._spill_score.items():
            if slot in self._active:
                if (slot, j) in self._pinned_cold \
                        or self._xfer_blocked((slot, j)):
                    continue        # degraded to cold-and-masked / backing off
                cur = best.get(slot)
                if cur is None or (score, -j) > (cur[0], -cur[1]):
                    best[slot] = (score, j)
        for slot in sorted(best):
            if self._alloc.total_free <= self.promote_headroom:
                break
            self.promote_block(slot, best[slot][1])

    # -- admission -----------------------------------------------------

    def _admit(self) -> None:
        """FIFO-admit queued requests into free slots: per-request prefill,
        then write the batch=1 state into the slot's pooled cache region.
        Paged mode first secures `ceil(prompt/block_size)` physical blocks
        from the free list — minus any prefix-shared blocks, which are
        mapped by reference — and waits head-of-line if the pool can't
        cover the divergent tail, keeping admission FIFO.

        With `prefill_chunk` set, admission instead advances the chunked
        scheduler by one budgeted chunk per call (interleaved with decode
        ticks by `run`), so a long prompt can no longer head-of-line block
        the decode stream.

        All of it runs in the ``serve.admit`` span, tagged with the id of
        the request at the head of admission (in flight or queued)."""
        head = (self._inflight.req if self._inflight is not None
                else self._queue[0] if self._queue else None)
        ids = {} if head is None else {"rid": head.rid}
        with self._spans("serve.admit", **ids):
            self._shed_expired_queue()
            if self.prefill_chunk is not None:
                self._advance_prefill()
            else:
                self._admit_whole()

    def _admit_whole(self) -> None:
        """Monolithic admission: prefill each admitted prompt whole."""
        while self._queue and self._free:
            req = self._queue[0]
            pages = None
            n_shared = 0
            # Admission-processing start: the cycle's queue-wait segment is
            # closed at the FIRST attempt that starts work on this request
            # (the gate prefill may run on an attempt that then waits for
            # blocks), so queue_wait and prefill stay disjoint segments of
            # TTFT — nothing is counted in both.
            t0 = time.time()
            if self.paged:
                plen = len(req.prompt)
                need_full = self._blocks_for(plen)
                shared_ids: list[int] = []
                n_cache = 0
                if self.prefix_sharing:
                    cand = self._match_tokens(req)
                    # Feasibility counts what pressure could reclaim: every
                    # pin outside the matched span is evictable, and each
                    # cold-matched entry costs one block to rehydrate.
                    cand_blocks = {b for _, b, _ in cand if b >= 0}
                    n_cold = sum(1 for _, b, _ in cand if b == CACHE_COLD)
                    reclaim = sum(1 for b in self._cached
                                  if b not in cand_blocks)
                    if need_full - len(cand) + n_cold \
                            > self._alloc.total_free + reclaim:
                        break              # can't cover even if fully gated in
                    if self._try_adopt(req, cand, t0):
                        continue           # zero-prefill warm hit admitted
                    self._begin_cycle(req, t0)  # gate prefill: work begins
                    _, state1 = self._ensure_prefill(req)
                    if req._heavy is None:
                        req._heavy = self._heavy_bytes(state1)
                    heavy = req._heavy
                    # Heavy-channel gate: alias only while the owner's sets
                    # match; the first mismatch truncates the share. Cold
                    # cache entries rehydrate to a fresh block on the way
                    # (other pins may be squeezed out to make room — the
                    # match's own blocks are protected).
                    hot = set(cand_blocks)
                    for key, block, owner_heavy in cand:
                        if owner_heavy != heavy:
                            break
                        if block == CACHE_COLD:
                            block = self._promote_cached(key, protect=hot)
                            if block is None:
                                break      # pool too tight to rehydrate
                            hot.add(block)
                        shared_ids.append(block)
                need = need_full - len(shared_ids)
                if need > self._alloc.total_free:
                    # Cheapest reclaim first: drain the cache's LRU end
                    # (matched blocks protected — an admission must never
                    # evict its own hit) before host-spill demotion or the
                    # head-of-line wait ever triggers.
                    self._reclaim_cache(need, protect=set(shared_ids))
                if self.host_spill and need > self._alloc.total_free:
                    # Admission pressure: evict cold blocks of active slots
                    # to the host tier before making the queue wait on the
                    # device pool — the tier exists so admission is bounded
                    # by host memory, not HBM.
                    for _ in range(need - self._alloc.total_free):
                        dc = self._demote_candidates()
                        if not dc:
                            break
                        self.demote_block(dc[0][1], dc[0][2])
                if self.host_spill and need > self._alloc.total_free:
                    # Wave admission: the prompt exceeds the free device
                    # pool even after eviction, so its blocks are written
                    # in free-pool-sized waves and every wave but the last
                    # (the recency tail) is demoted as soon as it lands.
                    if self._alloc.total_free < 1:
                        break              # wait for at least one hot block
                    pages = None           # marks the wave path below
                    blocks = []
                    # Wave admission rewrites the whole prompt privately —
                    # matched blocks were never increfed, so dropping the
                    # share here leaks nothing.
                    shared_ids = []
                else:
                    fresh = self._alloc_blocks(need)  # least-loaded first
                    if fresh is None:
                        break              # wait for blocks to free up
                    n_shared = len(shared_ids)
                    blocks = shared_ids + fresh
                    pages = np.full((self.max_blocks,), -1, np.int32)
                    pages[:need_full] = blocks
            self._queue.popleft()
            slot = self._free.pop()
            self._begin_cycle(req, t0)
            logits_row, state1 = self._ensure_prefill(req)
            if self.paged and pages is None:
                # Wave admission (host_spill): write the prompt into the
                # pool one free-pool-sized wave at a time, demoting each
                # wave to the host before the next lands; the final wave —
                # the recency tail holding the cursor block — stays hot.
                held = [SPILLED] * need_full
                self._slot_blocks[slot] = held
                self._slot_pos[slot] = plen
                self._hist_snap[slot] = 0
                self._cold_streak[slot] = 0
                lo = 0
                while lo < need_full:
                    w = min(self._alloc.total_free, need_full - lo)
                    ids = self._alloc.alloc(w)
                    wave = np.full((self.max_blocks,), -1, np.int32)
                    wave[lo:lo + w] = ids
                    for j, b in zip(range(lo, lo + w), ids):
                        held[j] = b
                        self._refcount[b] += 1
                    self._note_block_usage()
                    self._state = self._write(
                        self._state, state1, jnp.int32(slot),
                        jnp.asarray(wave), jnp.int32(0))
                    lo += w
                    if lo < need_full:     # not the tail: spill the wave
                        for j in range(lo - w, lo):
                            self.demote_block(slot, j, _inject=False)
            elif self.paged:
                for b in blocks:           # shared: n → n+1; fresh: 0 → 1
                    if self._cached.pop(b, None) is not None:
                        n_cache += 1       # pin → resident (cache hit)
                    self._refcount[b] += 1
                self._note_cache_usage()
                self._slot_blocks[slot] = list(blocks)
                self._slot_pos[slot] = len(req.prompt)
                if self.host_spill:
                    self._hist_snap[slot] = 0
                    self._cold_streak[slot] = 0
                self._note_block_usage()
                self._state = self._write(self._state, state1, jnp.int32(slot),
                                          jnp.asarray(pages),
                                          jnp.int32(n_shared))
                if self.prefix_sharing:
                    req.shared_blocks = n_shared
                    self.stats.shared_blocks += n_shared - n_cache
                    self.stats.cache_hit_blocks += n_cache
                    self.stats.prefix_hits += 1 if n_shared - n_cache else 0
                    self.stats.cache_hits += 1 if n_cache else 0
                    self._register_blocks(req, blocks, n_shared, req._heavy,
                                          logits_row)
            else:
                self._state = self._write(self._state, state1, jnp.int32(slot))
            self._drop_stash(req)       # free the batch=1 device state
            self._activate(req, slot, logits_row)

    def _try_adopt(self, req: Request, cand, t0: float) -> bool:
        """Zero-prefill warm admission: when the radix match covers the FULL
        prompt and the first-token logits row for it is retained, install
        the cached blocks by reference (`adopt_pages` — metadata only, no
        data movement, no prefill) and activate the slot immediately, so
        TTFT collapses to the adopt dispatch. Falls back to the normal
        prefill path (returns False) when any precondition is missing:
        adoption unsupported for the config (`self._adopt is None`), a
        partial match, a heavy-set mismatch, a missing logits row, or a
        cold entry the pool cannot rehydrate. The fallback still maps every
        matched block by reference — same bytes saved, just re-prefilled."""
        if self._adopt is None or not cand \
                or len(cand) < self._blocks_for(len(req.prompt)):
            return False
        logits_row = self._logits_cache.get(cand[-1][0])
        if logits_row is None:
            return False
        heavy = self._static_heavy_bytes()
        if any(owner_heavy != heavy for _, _, owner_heavy in cand):
            return False
        blocks: list[int] = []
        hot = {b for _, b, _ in cand if b >= 0}
        for key, block, _ in cand:
            if block == CACHE_COLD:
                block = self._promote_cached(key, protect=hot)
                if block is None:
                    return False    # rehydrated span stays pinned hot; the
                hot.add(block)      # prefill path picks it up next attempt
            blocks.append(block)
        self._queue.popleft()
        slot = self._free.pop()
        self._begin_cycle(req, t0)
        req._heavy = heavy
        n_cache = 0
        for b in blocks:
            if self._cached.pop(b, None) is not None:
                n_cache += 1        # pin → resident (cross-request hit)
            self._refcount[b] += 1
        self._note_cache_usage()
        plen = len(req.prompt)
        pages = np.full((self.max_blocks,), -1, np.int32)
        pages[:len(blocks)] = blocks
        self._slot_blocks[slot] = list(blocks)
        self._slot_pos[slot] = plen
        if self.host_spill:
            self._hist_snap[slot] = 0
            self._cold_streak[slot] = 0
        self._note_block_usage()
        self._state = self._adopt(self.params, self._state, jnp.int32(slot),
                                  jnp.asarray(pages), jnp.int32(plen))
        req.shared_blocks = len(blocks)
        self.stats.shared_blocks += len(blocks) - n_cache
        self.stats.cache_hit_blocks += n_cache
        if len(blocks) - n_cache:
            self.stats.prefix_hits += 1
        if n_cache:
            self.stats.cache_hits += 1
        self.stats.zero_prefill_hits += 1
        self._drop_stash(req)
        self._activate(req, slot, logits_row)
        return True

    def _next_token(self, req: Request, logits_row: np.ndarray | None,
                    greedy_tok: int | None = None) -> int:
        """The next output token: a recorded one while the request is inside
        its preemption replay window (forced-feed — never re-sampled, so the
        continuation is exact even under temperature), a fresh sample
        otherwise. Replayed tokens don't re-count as generated and don't
        restamp latency."""
        idx = len(req.output)
        if req._replay is not None and idx < len(req._replay):
            tok = int(req._replay[idx])
            self.stats.replayed_tokens += 1
        else:
            req._replay = None
            tok = int(greedy_tok) if logits_row is None \
                else self._sample(req, logits_row)
            self.stats.tokens_generated += 1
            req.token_times.append(time.time())
        req.output.append(tok)
        return tok

    def _activate(self, req: Request, slot: int, logits_row: np.ndarray) -> None:
        """Make a fully-prefilled request live: emit its first (or replayed)
        token, mask the slot on, and apply the stop rules the first token
        may already satisfy. Shared by monolithic admission and the final
        chunk of a chunked prefill."""
        tok = self._next_token(req, logits_row)
        if req.first_token_time is None:
            req.first_token_time = time.time()
            self.stats.ttft_s += req.ttft_s
            self.stats.ttft_count += 1
        self._active[slot] = req
        self._tokens[slot] = tok
        self._mask[slot] = True
        self.stats.peak_active_slots = max(self.stats.peak_active_slots,
                                           int(self._mask.sum()))
        # The prefill-produced token may already satisfy the stop rule.
        if req.stop_token is not None and tok == req.stop_token:
            self._finish(slot, req, time.time(), "stop")
        elif req.max_new_tokens <= 1:
            self._finish(slot, req, time.time(), "length")

    def _finish(self, slot: int, req: Request, now: float, reason: str) -> None:
        if self._active.get(slot) is not req:
            return                      # already finished (racing finishers)
        req.done_time = now
        req.stop_reason = reason
        self.stats.completed += 1
        del self._active[slot]
        self._mask[slot] = False
        self._stalled.discard(slot)
        self._nan_guard.reset_slot(slot)
        self._free.append(slot)
        self._free.sort(reverse=True)
        if self.paged:
            self._release_blocks(slot)  # decref; 0 → free list + radix prune
        self._state = self._reset(self._state, jnp.int32(slot))

    # -- preemption ----------------------------------------------------

    def _pick_victim(self) -> int | None:
        """Lowest-priority occupant of the pool: the LATEST-submitted
        request (ties broken by highest rid) among active slots and the
        in-flight chunked prefill. FIFO fairness — the newest arrival gives
        its blocks back first and loses the least progress."""
        cands: list[tuple[float, int, int]] = [
            (req.submitted, req.rid, slot)
            for slot, req in self._active.items()]
        if self._inflight is not None:
            inf = self._inflight
            cands.append((inf.req.submitted, inf.req.rid, inf.slot))
        if not cands:
            return None
        return max(cands)[2]

    def _preempt_slot(self, slot: int) -> None:
        """Evict one slot and requeue its request at the head of the queue.

        The unmap goes through the same decref-idempotent path as overflow
        finish — `_release_blocks` host-side and the `free_pages` form of
        `reset_slot` device-side — so a preempt racing an overflow finish or
        a reset on the same slot is a no-op, never a double free. Device
        stashes are cleared; the recorded output becomes the replay window:
        re-admission re-prefills the PROMPT only (cheap when the radix map
        still holds the prefix) and force-feeds the recorded tokens through
        normal decode ticks, regenerating identical KV — so outputs stay
        bit-identical to a never-preempted run."""
        now = time.time()
        if self._inflight is not None and self._inflight.slot == slot:
            req = self._inflight.req
            self._inflight = None       # drop the cursor (device buffers)
        else:
            req = self._active.pop(slot)
        self._mask[slot] = False
        self._stalled.discard(slot)
        self._nan_guard.reset_slot(slot)
        self.stats.preemptions += 1
        req.preemptions += 1
        # Keep the LONGEST recorded output: a request preempted again while
        # replaying must not truncate its replay window to the replayed part.
        if not (req._replay is not None
                and len(req._replay) >= len(req.output)):
            req._replay = list(req.output) or None
        req.output = []
        req.shared_blocks = 0
        req._requeued_at = now
        req._cycle_started = False
        self._drop_stash(req)
        self._free.append(slot)
        self._free.sort(reverse=True)
        self._release_blocks(slot)
        self._state = self._reset(self._state, jnp.int32(slot))
        self._queue.appendleft(req)

    def _preempt_for_blocks(self, needy_slot: int | None = None) -> bool:
        """Free pool blocks by preempting victims until the allocator has
        at least one (a victim's blocks may all be shared — keep going).
        Returns False once `needy_slot` itself was preempted (its request is
        gone from the pool; the caller must stop growing it) or no victim
        remains."""
        while not self._alloc.total_free:
            # Cache pins are cheaper than any victim: drain them first.
            # This also guarantees termination when a victim's released
            # blocks land straight back in the pin cache — the next
            # iteration reclaims them instead of hunting another victim.
            if self.host_spill and self._demote_cache_block():
                continue
            if self._evict_cache_block():
                continue
            victim = self._pick_victim()
            if victim is None:
                return False
            self._preempt_slot(victim)
            if victim == needy_slot:
                return False
        return True

    # -- chunked prefill -----------------------------------------------

    def _static_heavy_bytes(self) -> bytes:
        """Heavy-set identity bytes for the sharing gate when prefill is
        chunked: derived once from the weights (chunked prefill requires
        static channels, so every request's sets are identical by
        construction) in the same layer order and layout `_heavy_bytes`
        reads off a dense prefill state — the two admission paths publish
        interchangeable radix entries."""
        if self._static_heavy_cache is None:
            parts = self.api.static_heavy(self.params, self.max_seq)
            self._static_heavy_cache = b"".join(
                np.asarray(p).tobytes() for p in parts)
        return self._static_heavy_cache

    def _advance_prefill(self) -> None:
        """One budgeted prefill chunk per scheduler iteration.

        At most one prefill is in flight. Starting one reserves a slot
        (masked OFF until the final chunk), radix-matches the prompt, and
        pins every shared-prefix block up front (increfed immediately —
        lazy increfs would let the radix owner finish mid-prefill and free
        a block this prefill still plans to map by reference); each call
        then charges the FRESH blocks the next `prefill_chunk` tokens
        cover — incrementally, not the whole prompt up front — and runs one
        chunk step, which streams the chunk's K/V into the paged slot. A
        dry free list stalls the chunk (decode keeps running and will free
        or preempt blocks) rather than self-preempting: the in-flight
        request is the newest occupant, so evicting others for it would
        invert priority. The final chunk yields the first-token logits and
        activates the slot exactly like monolithic admission."""
        if self._inflight is not None \
                and self._expired(self._inflight.req, time.time()):
            self.stats.deadline_stops += 1
            self._abort_inflight(time.time(), "deadline")
        if self._inflight is None:
            if not (self._queue and self._free):
                return
            if self.prefix_sharing and self._try_adopt(
                    self._queue[0], self._match_tokens(self._queue[0]),
                    time.time()):
                return                  # zero-prefill warm hit admitted
            req = self._queue.popleft()
            self._begin_cycle(req, time.time())
            slot = self._free.pop()
            shared_ids: list[int] = []
            n_cache = 0
            if self.prefix_sharing:
                heavy = self._static_heavy_bytes()
                req._heavy = heavy
                cand = self._match_tokens(req)
                hot = {b for _, b, _ in cand if b >= 0}
                for key, block, owner_heavy in cand:
                    if owner_heavy != heavy:
                        break           # unreachable with static channels
                    if block == CACHE_COLD:
                        block = self._promote_cached(key, protect=hot)
                        if block is None:
                            break       # pool too tight to rehydrate
                        hot.add(block)
                    if block in self._cached:
                        del self._cached[block]
                        n_cache += 1    # pin → resident (cross-request hit)
                    shared_ids.append(block)
                self._note_cache_usage()
            inf = _InflightPrefill(
                req, slot, self.api.prefill_begin(len(req.prompt)),
                n_shared=len(shared_ids), n_cache=n_cache,
                shared_ids=shared_ids,
                pages=np.full((self.max_blocks,), -1, np.int32))
            # Pin the shared prefix NOW; the device mirrors this incref on
            # the first chunk (`prefill_chunk_into_pages` charges all
            # n_shared blocks when start == 0).
            for j, b in enumerate(shared_ids):
                inf.pages[j] = b
                self._refcount[b] += 1
            self._inflight = inf
            self._slot_blocks[slot] = list(shared_ids)
            self._slot_pos[slot] = 0

        inf = self._inflight
        req, slot = inf.req, inf.slot
        if self._fault("prefill_chunk", rid=req.rid, consumed=inf.consumed):
            # The chunk step failed before executing: nothing was charged
            # or written, so the next scheduler pass retries it exactly.
            self.stats.retries += 1
            return
        plen = len(req.prompt)
        c = min(self.prefill_chunk, plen - inf.consumed)
        held = self._slot_blocks[slot]
        span = self._blocks_for(inf.consumed + c)   # blocks covered after
        fresh_needed = max(span - len(held), 0)     # held ⊇ shared prefix
        if fresh_needed and self._alloc.total_free < fresh_needed:
            # Chunk charging drains the cache's LRU end before stalling —
            # a pin is cheaper than a lost prefill tick (the in-flight
            # request's own shared prefix is protected from eviction).
            self._reclaim_cache(fresh_needed, protect=set(inf.shared_ids))
        fresh = self._alloc_blocks(fresh_needed) if fresh_needed else []
        if fresh is None:
            self.stats.chunk_stalls += 1            # pool dry: try next tick
            return
        it = iter(fresh)
        for j in range(len(held), span):
            b = next(it)
            inf.pages[j] = b
            self._refcount[b] += 1
            held.append(b)
        self._note_block_usage()
        final = inf.consumed + c == plen
        toks = jnp.asarray(req.prompt[None, inf.consumed:inf.consumed + c])
        logits, self._state, inf.cursor = self._chunk_step(
            self.params, self._state, toks, inf.cursor, jnp.int32(slot),
            jnp.asarray(inf.pages), jnp.int32(inf.n_shared), final=final)
        inf.consumed += c
        self._slot_pos[slot] = inf.consumed
        self.stats.prefill_chunks += 1
        self.stats.prefill_tokens += c
        if not final:
            return
        logits_row = np.asarray(logits)[0]          # blocks until ready
        self._inflight = None
        if self.prefix_sharing:
            req.shared_blocks = inf.n_shared
            self.stats.shared_blocks += inf.n_shared - inf.n_cache
            self.stats.cache_hit_blocks += inf.n_cache
            self.stats.prefix_hits += 1 if inf.n_shared - inf.n_cache else 0
            self.stats.cache_hits += 1 if inf.n_cache else 0
            self._register_blocks(req, held, inf.n_shared, req._heavy,
                                  logits_row)
        self._activate(req, slot, logits_row)

    def _grow_or_overflow(self) -> None:
        """Before a tick, every active slot must be able to land its next KV
        write privately. Paged slots whose cursor crossed a block boundary
        take one block from the free list (`map_block` updates every layer's
        page table); slots whose cursor points into a SHARED block (refcount
        > 1) take one block and get a private copy (`cow_block`) — the
        copy-on-write fault `append_token_paged` would otherwise drop. If no
        block is free — or a dense slot hit max_seq — the request finishes
        with an ``overflow`` stop reason and the write that could not be
        stored is counted, instead of `append_token`'s silent clip.

        With ``preempt=True`` a dry free list preempts the lowest-priority
        pool occupant (possibly this very slot) instead of overflowing:
        every `submit` guarantees one request alone fits the pool, so a
        preempting engine never emits an ``overflow`` stop."""
        now = time.time()
        for slot, req in list(self._active.items()):
            if self._active.get(slot) is not req:
                continue                # preempted by an earlier iteration
            if self.paged:
                pos = self._slot_pos[slot]
                held = self._slot_blocks[slot]
                logical = pos // self.block_size
                if pos < self.max_seq and logical < len(held) \
                        and held[logical] >= 0 \
                        and self._refcount[held[logical]] <= 1:
                    continue                       # private capacity in place
                if pos < self.max_seq and not self._alloc.total_free:
                    # Pressure-relief order: the prefix cache's LRU end is
                    # the cheapest reclaim (no resident request loses
                    # state), so growth and CoW drain it BEFORE host-spill
                    # demotion or preemption ever fires.
                    self._reclaim_cache(1)
                if pos < self.max_seq and not self._alloc.total_free \
                        and self.host_spill:
                    # Growth pressure under the host tier: demote the
                    # coldest eligible block instead of overflowing. A
                    # FAILED demote (injected spill_transfer fault) is
                    # transient — stall the slot one tick and retry,
                    # rather than overflowing a recoverable request.
                    cand = self._demote_candidates()
                    if cand and not self.demote_block(cand[0][1], cand[0][2]):
                        self._stall(slot)
                        continue
                if pos < self.max_seq and not self._alloc.total_free \
                        and self.preempt:
                    # Growth pressure under preemption: evict the newest
                    # occupant(s) instead of overflowing anyone.
                    if not self._preempt_for_blocks(slot):
                        continue        # this slot itself was evicted
                    if logical < len(held) and held[logical] >= 0 \
                            and self._refcount[held[logical]] <= 1:
                        continue        # victim release privatized our block
                if pos < self.max_seq and self._alloc.total_free:
                    # Growth continues the slot's tail; CoW privatizes the
                    # faulted block. Either way, prefer the shard already
                    # holding that block so the appending shard keeps its
                    # writes local (falls back to the least-loaded shard).
                    near = held[logical] if logical < len(held) else held[-1]
                    prefer = self._alloc.shard_of(near) if near >= 0 else None
                    got = self._alloc_blocks(1, prefer=prefer)
                    if got is None:
                        # Spurious exhaustion (alloc_exhausted fault) with
                        # a non-empty free list: the slot cannot land its
                        # next KV write, so it pauses for one tick — not
                        # an overflow, nothing is lost, the stream resumes
                        # bit-identically when the allocator recovers.
                        self._stall(slot)
                        continue
                    blk = got[0]
                    self._refcount[blk] += 1       # 0 → 1
                    if logical == len(held):       # growth: map a fresh block
                        held.append(blk)
                        self._state = self._map_block(
                            self._state, jnp.int32(slot), jnp.int32(logical),
                            jnp.int32(blk))
                    else:                          # CoW: privatize the block
                        old = held[logical]
                        assert old >= 0, "cursor landed in a spilled block"
                        self._refcount[old] -= 1
                        held[logical] = blk
                        self.stats.cow_copies += 1
                        self._state = self._cow_block(
                            self._state, jnp.int32(slot), jnp.int32(logical),
                            jnp.int32(blk))
                    self._note_block_usage()
                    continue
            else:
                if self._slot_written(slot) < self.max_seq:
                    continue
            self.stats.overflows += 1
            self.stats.dropped_writes += 1
            self._finish(slot, req, now, "overflow")

    def _slot_written(self, slot: int) -> int:
        """Tokens stored for a dense slot = prompt + decoded-and-written."""
        req = self._active[slot]
        return len(req.prompt) + len(req.output) - 1

    def _tick(self) -> None:
        """ONE fused decode call advancing every active slot, in four host
        spans (`runtime.tracing`): prep, dispatch, wait and post."""
        with self._spans("serve.tick.prep"):
            now = time.time()
            for slot, req in list(self._active.items()):
                if self._active.get(slot) is req and self._expired(req, now):
                    self.stats.deadline_stops += 1
                    self._finish(slot, req, now, "deadline")
            self._promote_resurrected()
            self._grow_or_overflow()
            if not self._active:
                return
            decode = bool(self._mask.any())   # not all slots stalled
            if decode:
                self.stats.peak_active_slots = max(
                    self.stats.peak_active_slots, int(self._mask.sum()))
                self._last_decoded = set(np.flatnonzero(self._mask).tolist())
        if decode:
            with self._spans("serve.tick.dispatch") as dispatch:
                nxt, logits, finite, self._state = self._decode(
                    self.params, self._state, jnp.asarray(self._tokens),
                    jnp.asarray(self._mask))
            with self._spans("serve.tick.wait") as wait:
                nxt_host = np.asarray(nxt)              # blocks until ready
                finite_host = np.array(finite)          # writable host copy
            self.stats.decode_s += dispatch.seconds + wait.seconds
        with self._spans("serve.tick.post"):
            if decode:
                self._advance_slots(nxt_host, logits, finite_host)
            # Degraded-mode accounting: a tick that ran with a stalled slot
            # or with a cold-pinned block on an active slot served degraded
            # — available, but paused or at reduced attention quality.
            if self._stalled or any(k[0] in self._active
                                    for k in self._pinned_cold):
                self.stats.degraded_ticks += 1
            # Re-arm stalled slots: the stall lasts exactly one tick, then
            # the growth path retries (the fault may have cleared or backed
            # off).
            for slot in self._stalled:
                if slot in self._active:
                    self._mask[slot] = True
            self._stalled.clear()
            self._spill_policy()
            if self.audit_every and self.stats.ticks != self._audited_tick \
                    and self.stats.ticks % self.audit_every == 0:
                self._audited_tick = self.stats.ticks
                self.stats.audits += 1
                rep = self.check_invariants()
                self.last_audit = rep
                if not rep.ok:
                    self.stats.audit_failures += 1
                    raise RuntimeError(
                        f"integrity audit failed at tick {self.stats.ticks}: "
                        f"{rep}")
        if decode and self.monitor is not None:
            # The whole scheduler iteration, admission included, so a host
            # stall anywhere in it is flagged — and blamed on its phase.
            phases = self._spans.phases
            rec = self.monitor.record(self.stats.ticks, sum(phases.values()),
                                      phases=phases)
            self.stats.tick_ewma_s = float(rec["ewma"] or 0.0)
            if rec["flagged"]:
                self.stats.straggler_ticks += 1

    def _advance_slots(self, nxt_host: np.ndarray, logits,
                       finite_host: np.ndarray) -> None:
        """Consume one decoded tick: quarantine non-finite slots, take each
        slot's next token and finish the requests that are done."""
        self.stats.decode_calls += 1
        self.stats.ticks += 1
        self.stats.decode_steps += int(self._mask.sum())
        # decode_logits injection: poison selected slots' rows after the
        # fact — the detection below is the same path a real non-finite
        # matmul result takes.
        if self._faults is not None:
            for slot, req in list(self._active.items()):
                if slot not in self._stalled and self._fault(
                        "decode_logits", rid=req.rid, slot=slot,
                        tick=self.stats.ticks):
                    finite_host[slot] = False
        logits_host = None                          # fetched only if sampling
        now = time.time()
        for slot in list(self._active):
            req = self._active[slot]
            if slot in self._stalled:
                continue        # paused this tick: nothing advanced
            if self.paged:
                self._slot_pos[slot] += 1
            if not bool(finite_host[slot]):
                # Per-slot quarantine: this slot's logits are NaN/Inf. Its
                # request ends with a truthful "error" stop; the other
                # slots' rows are independent and proceed untouched — one
                # poisoned slot never contaminates the fused tick.
                if self._nan_guard.check_slot(slot, False):
                    self.stats.errors += 1
                    self._finish(slot, req, now, "error")
                    continue
            if self.greedy or req.temperature <= 0.0:
                tok = self._next_token(req, None,
                                       greedy_tok=int(nxt_host[slot]))
            else:
                if logits_host is None:
                    logits_host = np.asarray(logits)
                tok = self._next_token(req, logits_host[slot])
            self._tokens[slot] = tok
            if req.stop_token is not None and tok == req.stop_token:
                self._finish(slot, req, now, "stop")
            elif len(req.output) >= req.max_new_tokens:
                self._finish(slot, req, now, "length")

    # -- runtime integrity audit ---------------------------------------

    def check_invariants(self):
        """Audit the engine's bookkeeping against the device state: every
        paged layer's pool passes `PagedSalcaCache.check_invariants` (device
        refcount == page-table references == the engine's numpy mirror,
        free ∩ mapped = ∅, no leaked blocks, cursor bounds), the host-side
        `_slot_blocks` rows agree entry-for-entry with the device page
        table, `_slot_pos` cursors agree with the device lengths, and —
        under host spill — the SPILLED sentinels and the numpy mirror's
        payload keys describe exactly the same set of cold blocks.

        Returns an `InvariantReport`; `audit_every` runs this after every
        N-th tick and raises on violations (an unclean audit is a bug, not
        load — fail loudly before corruption spreads)."""
        from repro.core.cache import InvariantReport, PagedSalcaCache
        rep = InvariantReport()
        if not self.paged:
            rep.checked["paged"] = 0    # dense engines: nothing to audit
            return rep
        if self._inflight is not None and self._inflight.consumed == 0 \
                and self._inflight.n_shared > 0:
            # Transient pin window: the shared prefix is increfed host-side
            # at inflight creation but the device mirrors the charge on the
            # FIRST chunk — which hasn't run yet (fault/stall). Skip rather
            # than report the expected one-pass divergence.
            rep.checked["skipped"] = "inflight shared-pin window"
            return rep
        free = self._alloc.free_ids()
        # Allocator structure: every free id inside its owner shard's range.
        for s in range(self.n_shards):
            for b in self._alloc._free[s]:
                if self._alloc.shard_of(b) != s:
                    rep.fail(f"free id {b} filed under shard {s}, owned by "
                             f"{self._alloc.shard_of(b)}")
        pools = [st for st in (list(self._state.period_states)
                               + list(self._state.tail_states))
                 if isinstance(st, PagedSalcaCache)]
        rep.checked["pools"] = len(pools)
        for i, pool in enumerate(pools):
            rep.merge(pool.check_invariants(
                free_blocks=free, host_refcount=self._refcount,
                allow_holes=self.host_spill,
                cache_pinned=self._cached.keys()), prefix=f"pool[{i}]: ")
        if not pools:
            rep.fail("paged engine with no PagedSalcaCache substates")
            return rep
        # Persistent prefix cache: a pin is an engine-held reference to a
        # fully-unmapped, radix-published block; a cold entry is a payload
        # whose radix node carries the CACHE_COLD sentinel. Both directions
        # of each correspondence must hold.
        free_set = set(free)
        for b in self._cached:
            if self._refcount[b] != 0:
                rep.fail(f"cache-pinned block {b} has host refcount "
                         f"{int(self._refcount[b])} (pins hold zero "
                         f"resident owners by definition)")
            if b not in self._block_keys:
                rep.fail(f"cache-pinned block {b} has no radix registration")
            if b in free_set:
                rep.fail(f"cache-pinned block {b} is on the free list")
        for key, (b, _) in self._prefix_nodes.items():
            if b == CACHE_COLD:
                if key not in self._cold_cache:
                    rep.fail("cold radix node without a cold-cache payload")
            elif self._block_keys.get(b) != key:
                rep.fail(f"radix node block {b} not back-registered in "
                         f"_block_keys")
        for key in self._cold_cache:
            node = self._prefix_nodes.get(key)
            if node is None or node[0] != CACHE_COLD:
                rep.fail("cold-cache payload without a CACHE_COLD radix node")
        # Host ↔ device page-table agreement, on layer 0 of the first pool
        # (cross-layer/cross-pool lockstep is checked above).
        s, mb = self.slots, self.max_blocks
        pt = np.asarray(pools[0].page_table).reshape(-1, s, mb)[0]
        ln = np.asarray(pools[0].length).reshape(-1, s)[0]
        for slot in range(s):
            held = self._slot_blocks.get(slot)
            if held is None:
                if (pt[slot] >= 0).any():
                    rep.fail(f"slot {slot} holds no blocks host-side but "
                             f"has mapped page-table entries")
                continue
            for j in range(mb):
                want = -1 if j >= len(held) or held[j] == SPILLED else held[j]
                if pt[slot, j] != want:
                    rep.fail(f"slot {slot} logical {j}: host says "
                             f"{want}, device page table says "
                             f"{int(pt[slot, j])}")
                    break
            # Cursor agreement, only where the device length is
            # authoritative: the fused decode writes length = pos+1 for
            # slots it advanced and ZERO for masked-off slots, so only the
            # last tick's decoded-and-still-active slots can be compared.
            pos = self._slot_pos.get(slot)
            if pos is not None and slot in self._active \
                    and slot in self._last_decoded \
                    and int(ln[slot]) != pos:
                rep.fail(f"slot {slot}: host cursor {pos} != device "
                         f"length {int(ln[slot])}")
            if pos is not None and not 0 <= pos <= self.max_seq:
                rep.fail(f"slot {slot}: host cursor {pos} out of "
                         f"[0, {self.max_seq}]")
        if self.host_spill:
            cold = {(slot, j)
                    for slot, held in self._slot_blocks.items()
                    for j, b in enumerate(held) if b == SPILLED}
            if cold != set(self._spilled):
                rep.fail(f"spill-mirror mismatch: SPILLED sentinels "
                         f"{sorted(cold)} vs mirror payloads "
                         f"{sorted(self._spilled)}")
        return rep

    def run(self, max_ticks: int = 10_000) -> ServeStats:
        ticks = 0
        while (self._queue or self._active or self._inflight is not None) \
                and ticks < max_ticks:
            self._spans.phases.clear()
            self._admit()
            if self._active:
                self._tick()
            ticks += 1
        return self.stats
