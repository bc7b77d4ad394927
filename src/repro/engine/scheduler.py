"""Continuous-batching scheduler: request queue → slots → engine steps.

The ``Engine`` drives serving as a sequence of *engine steps*; each step
either admits queued prompts into free KV slots (chunked prefill, one
jitted call per chunk) or runs one fused multi-token decode block across
all active slots.  Slots free mid-flight — a request finishing inside a
decode block releases its slot for the next admission while the remaining
slots keep decoding — which is what distinguishes continuous batching from
the legacy lockstep ``Server``.

KV storage is block-paged (``repro.engine.block_pool``): admission maps
the longest block-aligned prompt prefix already present in the radix
index onto shared physical blocks — those *cached* tokens are never
prefilled — and charges only the cache-miss suffix to chunked prefill.
When the pool cannot supply the blocks a request needs (after evicting
cold index entries), admission stalls: the request waits in the queue
until running requests release blocks (admission backpressure).

Every step appends a :class:`TraceEvent`; the trace is both the measured
run's structure and the input replayed by the analytical twin
(``repro.engine.forecast_twin``) to forecast the same serving schedule —
including how many prompt tokens each admission served from cache.

The engine's host work is marked with profiler spans, all named
``engine.*``: ``engine.step`` (one :meth:`Engine.step`), ``engine.admit``
(one admission, or one bucketed group), ``engine.prefill`` /
``engine.decode`` / ``engine.verify`` (one jitted dispatch, with the
index ``ev`` of the TraceEvent it produces), ``engine.harvest``,
``engine.free`` and ``engine.sync`` (each point where the host waits for
the device, with ``what`` it reads).  They are
``jax.profiler.TraceAnnotation`` spans: they land on the profiler's host
plane, on the device trace's clock, and record nothing when no profiler
runs.  Their arguments are host values only.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.configs.base import ArchConfig
from repro.core.workload import DEFAULT_KV_BLOCK_SIZE
from repro.runtime.sharding import ShardingPolicy, pp_degree, tp_degree

from .block_pool import BlockPool, RadixIndex
from .kv_cache import BlockPagedKVCache
from .decode_loop import (ATTN_IMPLS, make_engine_fns,
                          make_prefill_batch_fn, make_verify_fn, sample)


_span = jax.profiler.TraceAnnotation


def _sync(what: str, read):
    """Return ``read()``, which waits for the device, inside an
    ``engine.sync`` span.  Every point where the scheduler blocks on the
    device goes through here, so the spans count the syncs."""
    with _span("engine.sync", what=what):
        return read()


@functools.partial(jax.jit, donate_argnums=(0,))
def _pool_write(bufs, facs, pslot: jax.Array):
    """Write one tenant's factor tensors into pool slot ``pslot`` of the
    ``(L, P, ...)`` device buffers — all eight factors in ONE dispatch.

    The slot index is traced, not baked in: one compile serves every
    pool slot — an eager ``.at[:, pslot].set`` constant-folds the slot
    and recompiles per (slot, shape) pair, which put ~seconds of XLA
    compiles inside the measured serving window on every adapter miss.
    Fusing the eight per-projection writes into a single jitted call
    keeps a pool miss at one dispatch instead of eight."""
    return tuple(
        jax.lax.dynamic_update_slice_in_dim(b, f[:, None], pslot, axis=1)
        for b, f in zip(bufs, facs))


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_slots: int                      # concurrent requests
    max_len: int                        # max prompt+budget tokens per request
    chunk_size: int = 32                # chunked-prefill admission chunk
    decode_block: int = 8               # tokens per fused decode dispatch
    block_size: int = DEFAULT_KV_BLOCK_SIZE  # tokens per KV block (paging)
    n_blocks: Optional[int] = None      # pool size (default: slots worth)
    prefix_cache: bool = True           # radix prefix caching across requests
    kv_dtype: str = "bf16"              # bf16 | int8 (KV compression §3.3.3)
    attn_impl: str = "gather"           # gather (XLA ref) | paged (Pallas)
    temperature: float = 0.0            # 0 = greedy
    eos_id: Optional[int] = None        # stop token (None: budget only)
    spec_k: int = 0                     # draft tokens/step (0 = no speculation)
    prefill_batch: int = 1              # bucketed batched admission (1 = off)
    seed: int = 0
    # multi-tenant LoRA serving: > 0 enables the device adapter pool;
    # tenant t gets rank lora_ranks[t % len(lora_ranks)] (mixed-rank
    # population).  lora_slots bounds concurrently resident adapters
    # (default: one per engine slot, so admission never stalls on the
    # adapter pool; smaller values exercise LRU eviction/backpressure).
    lora_tenants: int = 0
    lora_ranks: Tuple[int, ...] = ()
    lora_slots: Optional[int] = None

    def __post_init__(self):
        for name in ("max_slots", "max_len", "chunk_size", "decode_block",
                     "block_size", "prefill_batch"):
            if getattr(self, name) < 1:
                raise ValueError(f"{name} must be >= 1, "
                                 f"got {getattr(self, name)}")
        if self.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {self.spec_k}")
        # explicit 0 must not silently fall back to the default pool
        if self.n_blocks is not None and self.n_blocks < 1:
            raise ValueError(f"n_blocks must be >= 1 when given, "
                             f"got {self.n_blocks}")
        if self.attn_impl not in ATTN_IMPLS:
            raise ValueError(f"attn_impl must be one of {ATTN_IMPLS}, "
                             f"got {self.attn_impl!r}")
        if self.lora_tenants < 0:
            raise ValueError(f"lora_tenants must be >= 0, "
                             f"got {self.lora_tenants}")
        object.__setattr__(self, "lora_ranks",
                           tuple(int(r) for r in self.lora_ranks))
        if self.lora_tenants > 0 and not self.lora_ranks:
            object.__setattr__(self, "lora_ranks", (8,))
        if self.lora_ranks and min(self.lora_ranks) < 1:
            raise ValueError(f"lora_ranks must all be >= 1, "
                             f"got {self.lora_ranks}")
        if self.lora_slots is not None and self.lora_slots < 1:
            raise ValueError(f"lora_slots must be >= 1 when given, "
                             f"got {self.lora_slots}")

    @property
    def adapter_pool_slots(self) -> int:
        """Device adapter-pool size (0 when multi-tenant LoRA is off)."""
        if self.lora_tenants <= 0:
            return 0
        if self.lora_slots is not None:
            return self.lora_slots
        return min(self.max_slots, self.lora_tenants)

    @property
    def blocks_per_seq(self) -> int:
        return -(-self.max_len // self.block_size)

    @property
    def pool_blocks(self) -> int:
        if self.n_blocks is not None:
            return self.n_blocks
        return self.max_slots * self.blocks_per_seq


@dataclasses.dataclass
class Request:
    rid: int
    prompt: Sequence[int]               # token ids
    max_new: int                        # generation budget
    arrival_step: int = 0               # engine step at which it may admit
    adapter_id: Optional[int] = None    # LoRA tenant (None = base model)

    def __post_init__(self):
        if len(self.prompt) == 0:
            raise ValueError(f"request {self.rid}: empty prompt")


@dataclasses.dataclass
class RequestResult:
    rid: int
    tokens: List[int]                   # generated tokens (incl. first)
    prompt_len: int
    cached_tokens: int = 0              # prompt tokens served from the cache
    # measured wall-clock timestamps (s, engine-relative)
    arrival: float = 0.0
    admitted: float = 0.0               # prefill started (left the queue)
    first_token: float = 0.0            # TTFT reference point
    finished: float = 0.0

    @property
    def queue_time(self) -> float:
        return self.admitted - self.arrival

    @property
    def ttft(self) -> float:
        """Admission → first token: the prefill cost, queue-EXCLUSIVE —
        the same quantity the analytical twin forecasts, so
        measured-vs-forecast compares like with like."""
        return self.first_token - self.admitted

    @property
    def ttft_queued(self) -> float:
        """Arrival → first token, queue-INCLUSIVE — what a user
        experiences under load; the quantity SLOs are judged on."""
        return self.first_token - self.arrival

    @property
    def tpot(self) -> float:
        """Mean seconds per output token after the first."""
        n = len(self.tokens)
        if n <= 1:
            return 0.0
        return (self.finished - self.first_token) / (n - 1)


@dataclasses.dataclass(frozen=True)
class TraceEvent:
    """One engine step, hardware-agnostic — the twin's replay unit.

    kind == "engine": trace header emitted once per run, recording the
        engine knobs the twin needs — ``chunk`` is the configured
        ``chunk_size`` (so ``cold_trace`` backfills cache-hit prefixes at
        the engine's true chunk granularity even when every admission was
        a warm hit with a small tail suffix), ``n_steps`` the configured
        ``decode_block``, ``tp``/``pp`` the mesh's tensor- and
        pipeline-parallel degrees the run executed at,
        ``attn_impl``/``block_size``/``spec_k`` the
        attention path, KV paging granularity and speculation depth (so
        the twin defaults its pricing from the trace itself instead of
        out-of-band constructor args); zero workload, skipped by replay.
    kind == "prefill_chunk": one prompt chunk of ``rid`` into ``slot``
        (batch 1, ``chunk`` new tokens on top of ``past_len`` cached);
        ``cached`` is the request's prefix-cache hit length (constant
        across its chunks — the first chunk has ``past_len == cached``),
        and ``last`` marks the chunk that produces the first token.
    kind == "prefill_batch": one bucket-batched prefill-and-insert
        dispatch admitting chunks of several requests at once (traffic
        admission with ``prefill_batch > 1``); ``members`` holds one
        ``(rid, slot, chunk, past_len, cached, last)`` tuple per live
        member of the dispatch — the same fields a ``prefill_chunk``
        event carries, so the twin prices the group with weight reads
        amortized across members.
    kind == "decode_block": ``n_steps`` fused steps over the active slots;
        ``slots`` holds (rid, past_len, remaining_budget) per active slot
        at block start, enough for the twin to replay per-step attrition.
    kind == "spec_step": one speculative verify dispatch over the active
        slots — each slot's pending token plus ``spec_k`` drafts verified
        in a single (k+1)-query pass; ``proposed``/``accepted`` record the
        drafts offered / accepted per slot (aligned with ``slots``), so
        acceptance is a *measured* per-step quantity the twin replays
        against the assumed-α forecast.
    """
    kind: str
    rid: int = -1
    slot: int = -1
    chunk: int = 0
    past_len: int = 0
    cached: int = 0
    last: bool = False
    n_steps: int = 0
    slots: Tuple[Tuple[int, int, int], ...] = ()
    tp: int = 1
    pp: int = 1                         # header only (pipeline degree)
    attn_impl: str = ""                 # header only (twin replay default)
    block_size: int = 0                 # header only
    spec_k: int = 0                     # header + spec_step
    proposed: Tuple[int, ...] = ()      # spec_step: drafts verified per slot
    accepted: Tuple[int, ...] = ()      # spec_step: drafts accepted per slot
    # prefill_batch: (rid, slot, chunk, past_len, cached, last) per member
    members: Tuple[Tuple[int, int, int, int, int, bool], ...] = ()
    # multi-tenant LoRA: per-slot adapter rank this step computed against
    # (0 = base model).  decode_block/spec_step: aligned with ``slots``;
    # prefill_chunk: one element; prefill_batch: aligned with ``members``.
    # The header carries the engine's tenant config instead.
    adapter_ranks: Tuple[int, ...] = ()
    lora_tenants: int = 0               # header only
    lora_ranks: Tuple[int, ...] = ()    # header only


@dataclasses.dataclass
class _Allocation:
    """Outcome of block accounting for one admission."""
    table: List[int]                    # physical block ids, virtual order
    cached: int                         # prompt tokens mapped from the index
    cow: Optional[Tuple[int, int]]      # (src, dst) partial-block fork


class Engine:
    """Continuous-batching serving engine over a block-paged KV cache."""

    def __init__(self, cfg: ArchConfig, params, mesh: Mesh,
                 policy: ShardingPolicy, ec: EngineConfig,
                 drafter=None):
        if ec.chunk_size > ec.max_len:
            raise ValueError("chunk_size exceeds max_len")
        self.cfg, self.params, self.ec = cfg, params, ec
        self.mesh = mesh
        self.tp = tp_degree(mesh, policy)
        self.pp = pp_degree(mesh, policy)
        self.adapter_store = self.adapter_pool = None
        if ec.lora_tenants > 0:
            from .adapter_pool import AdapterPool, AdapterStore
            self.adapter_store = AdapterStore(
                cfg, ec.lora_tenants, ec.lora_ranks, seed=ec.seed)
            self.adapter_pool = AdapterPool(ec.adapter_pool_slots)
        self.cache = BlockPagedKVCache(
            cfg, ec.max_slots, n_blocks=ec.pool_blocks,
            block_size=ec.block_size,
            max_blocks_per_seq=ec.blocks_per_seq, kv_dtype=ec.kv_dtype,
            lora_slots=ec.adapter_pool_slots,
            lora_max_rank=(self.adapter_store.max_rank
                           if self.adapter_store else 0))
        self.pool = BlockPool(ec.pool_blocks, ec.block_size)
        self.index = RadixIndex(self.pool) if ec.prefix_cache else None
        self.prefill_fn, self.decode_fn, self.shardings = make_engine_fns(
            cfg, mesh, policy, self.cache, chunk_size=ec.chunk_size,
            decode_block=ec.decode_block, temperature=ec.temperature,
            eos_id=ec.eos_id, attn_impl=ec.attn_impl)
        self.verify_fn = self.drafter = None
        if ec.spec_k > 0:
            from .drafter import make_drafter
            self.verify_fn = make_verify_fn(cfg, mesh, policy, self.cache,
                                            attn_impl=ec.attn_impl)
            self.drafter = drafter if drafter is not None else make_drafter()
        self.prefill_batch_fn = None
        if ec.prefill_batch > 1:
            self.prefill_batch_fn = make_prefill_batch_fn(
                cfg, mesh, policy, self.cache, attn_impl=ec.attn_impl)
        self._np_rng = np.random.default_rng(ec.seed + 1)
        # speculative-decoding counters over the run
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_steps = 0
        # commit params and the fresh state to their serving shardings up
        # front: params placed elsewhere would be moved on every call, and
        # uncommitted state makes the first jitted step compile a second
        # executable once its (committed) outputs feed the next call
        self.params = jax.device_put(params, self.shardings["params"])
        self.state = jax.device_put(self.cache.init_state(),
                                    self.shardings["state"])
        self._rng = jax.random.PRNGKey(ec.seed)
        self.queue: Deque[Request] = collections.deque()
        self.free_slots: List[int] = list(range(ec.max_slots))
        self.running: Dict[int, Request] = {}      # slot -> request
        self.results: Dict[int, RequestResult] = {}  # rid -> result
        self.trace: List[TraceEvent] = []
        self.step_idx = 0
        self._t0 = time.perf_counter()
        self._arrivals: Dict[int, Optional[float]] = {}
        # (step_idx, wall_s, arrived-but-waiting) sampled every step
        self.queue_depth: List[Tuple[int, float, int]] = []
        self.step_period: Optional[float] = None
        self._slot_blocks: Dict[int, List[int]] = {}   # slot -> owned refs
        self._slot_adapter: Dict[int, int] = {}        # slot -> adapter_id
        # prefix-cache counters over the run
        self.prefix_hit_tokens = 0
        self.prompt_tokens = 0
        self.peak_blocks_in_use = 0

    # ------------------------------------------------------------------
    def _now(self) -> float:
        return time.perf_counter() - self._t0

    def submit(self, req: Request) -> None:
        if req.max_new < 1:
            raise ValueError(f"request {req.rid}: max_new must be >= 1 "
                             f"(the first token comes from prefill)")
        if len(req.prompt) + req.max_new > self.ec.max_len:
            raise ValueError(
                f"request {req.rid}: prompt+budget "
                f"{len(req.prompt)}+{req.max_new} exceeds per-request "
                f"capacity {self.ec.max_len}")
        if self._blocks_needed(req) > self.pool.n_blocks:
            raise ValueError(
                f"request {req.rid}: needs {self._blocks_needed(req)} KV "
                f"blocks but the pool only has {self.pool.n_blocks}")
        if req.adapter_id is not None:
            if self.adapter_store is None:
                raise ValueError(
                    f"request {req.rid}: adapter_id={req.adapter_id} but "
                    f"the engine has no tenants (EngineConfig.lora_tenants)")
            self.adapter_store.rank_of(req.adapter_id)  # range check
        self.queue.append(req)
        # a deferred request (open-loop traffic feed) has not "arrived"
        # yet: its timestamp is stamped when its step gate opens
        self._arrivals[req.rid] = (None if req.arrival_step > self.step_idx
                                   else self._now())

    @property
    def n_active(self) -> int:
        return len(self.running)

    @property
    def done(self) -> bool:
        return not self.queue and not self.running

    @property
    def blocks_in_use(self) -> int:
        return self.pool.in_use

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of offered prompt tokens served from shared blocks."""
        return self.prefix_hit_tokens / max(self.prompt_tokens, 1)

    # ------------------------------------------------------------------
    # block accounting: prefix match → evict → allocate (or stall)
    # ------------------------------------------------------------------
    def _blocks_needed(self, req: Request) -> int:
        # positions written: prompt plus all but the final sampled token
        bs = self.ec.block_size
        return -(-(len(req.prompt) + req.max_new - 1) // bs)

    def _allocate(self, req: Request) -> Optional[_Allocation]:
        """Map the request onto physical blocks, or None (backpressure).

        The longest indexed full-block prefix is mapped read-only into the
        table; if the usable prefix ends mid-block (a hit capped at
        ``prompt_len - 1`` so at least one token feeds the LM head), the
        partial block is copy-on-write forked.  Fresh blocks cover the
        suffix and the generation budget.  If the COW attempt cannot get
        blocks (the fork's source pin can occupy the last free block of an
        exactly-sized pool), the hit is aligned down to full blocks and
        retried before admission stalls.
        """
        bs = self.ec.block_size
        prompt = [int(t) for t in req.prompt]
        hits = self.index.match(prompt) if self.index is not None else []
        # at least one prompt token must be computed to produce logits
        cached = min(len(hits) * bs, len(prompt) - 1)
        alloc = self._try_allocate(req, hits, cached)
        if alloc is None and cached % bs:
            alloc = self._try_allocate(req, hits, (cached // bs) * bs)
        return alloc

    def _try_allocate(self, req: Request, hits: List[int], cached: int
                      ) -> Optional[_Allocation]:
        bs = self.ec.block_size
        keep, cow_src = hits[:cached // bs], None
        if cached % bs:
            cow_src = hits[cached // bs]
        for b in keep + ([cow_src] if cow_src is not None else []):
            self.pool.incref(b)      # pin against eviction while we build
        n_total = self._blocks_needed(req)
        n_new = n_total - len(keep)
        if self.pool.n_free < n_new and self.index is not None:
            self.index.evict(n_new - self.pool.n_free)
        if self.pool.n_free < n_new:
            for b in keep + ([cow_src] if cow_src is not None else []):
                self.pool.decref(b)
            return None              # stall: wait for running requests
        fresh = [self.pool.alloc() for _ in range(n_new)]
        cow = None
        if cow_src is not None:
            cow = (cow_src, fresh[0])
            self.pool.decref(cow_src)   # only the fork is kept in the table
        return _Allocation(table=keep + fresh, cached=cached, cow=cow)

    # ------------------------------------------------------------------
    # multi-tenant LoRA: adapter residency around admission
    # ------------------------------------------------------------------
    def _adapter_admissible(self, req: Request) -> bool:
        """Admission gate: can the request's adapter be pinned now?
        False is backpressure, exactly like KV-pool exhaustion."""
        if self.adapter_pool is None or req.adapter_id is None:
            return True
        return self.adapter_pool.can_acquire(req.adapter_id)

    def _bind_adapter(self, req: Request, slot: int) -> None:
        """Pin the request's adapter and point its engine slot at the
        adapter's pool slot; on a pool miss, load the tenant's factors
        from the host store into the (LRU-evicted) device slot."""
        if self.adapter_pool is None or req.adapter_id is None:
            return
        from .adapter_pool import LORA_FACTORS
        pslot, loaded = self.adapter_pool.acquire(req.adapter_id)
        if loaded:
            factors = self.adapter_store.factors(req.adapter_id)
            keys = ["lora_" + name for name in LORA_FACTORS]
            new = _pool_write(tuple(self.state[k] for k in keys),
                              tuple(factors[n] for n in LORA_FACTORS),
                              jnp.int32(pslot))
            for k, b in zip(keys, new):
                self.state[k] = b
        self.state["adapter_slots"] = (
            self.state["adapter_slots"].at[slot].set(pslot))
        self._slot_adapter[slot] = req.adapter_id

    def _slot_rank(self, slot: int) -> int:
        """Adapter rank slot ``slot`` decodes with (0 = base model)."""
        aid = self._slot_adapter.get(slot)
        return 0 if aid is None else self.adapter_store.rank_of(aid)

    @property
    def adapter_hit_rate(self) -> float:
        """Adapter-pool hit rate over the run (1.0 when LoRA is off)."""
        return 1.0 if self.adapter_pool is None else (
            self.adapter_pool.hit_rate)

    # ------------------------------------------------------------------
    # admission: chunked prefill of the cache-miss suffix into one slot
    # ------------------------------------------------------------------
    def _admit(self, req: Request, slot: int, alloc: _Allocation) -> None:
        ec = self.ec
        prompt = np.asarray(req.prompt, np.int32)
        n, cached = len(prompt), alloc.cached
        self._slot_blocks[slot] = alloc.table
        self.prefix_hit_tokens += cached
        self.prompt_tokens += n
        if alloc.cow is not None:
            self.state = self.cache.copy_block(self.state, *alloc.cow)
        row = np.zeros((self.cache.max_blocks_per_seq,), np.int32)
        row[:len(alloc.table)] = alloc.table
        self.state["block_tables"] = (
            self.state["block_tables"].at[slot].set(jnp.asarray(row)))
        self.state["pos"] = self.state["pos"].at[slot].set(cached)
        self._bind_adapter(req, slot)
        res = RequestResult(rid=req.rid, tokens=[], prompt_len=n,
                            cached_tokens=cached,
                            arrival=self._arrivals.get(req.rid) or 0.0,
                            admitted=self._now())
        logits = None
        for off in range(cached, n, ec.chunk_size):
            piece = prompt[off:off + ec.chunk_size]
            valid = len(piece)
            if valid < ec.chunk_size:
                piece = np.pad(piece, (0, ec.chunk_size - valid))
            last = off + valid >= n
            with _span("engine.prefill", ev=len(self.trace)):
                logits, self.state = self.prefill_fn(
                    self.params, self.state, jnp.asarray(piece[None]),
                    jnp.int32(slot), jnp.int32(off), jnp.int32(valid))
            self.trace.append(TraceEvent(
                kind="prefill_chunk", rid=req.rid, slot=slot,
                chunk=valid, past_len=off, cached=cached, last=last,
                adapter_ranks=(self._slot_rank(slot),)))
        if self.index is not None:
            # the prompt's full blocks are now populated and immutable:
            # publish them for future admissions (dedupe keeps first-comer)
            self.index.insert(prompt[:(n // ec.block_size) * ec.block_size],
                              alloc.table[:n // ec.block_size])
        self.peak_blocks_in_use = max(self.peak_blocks_in_use,
                                      self.pool.in_use)
        # the request's first token is sampled from the final prefill logits
        self._rng, sub = jax.random.split(self._rng)
        first = _sync("first_token", lambda: int(
            sample(logits[None], ec.temperature, sub)[0]))
        now = self._now()
        res.first_token = now
        res.tokens.append(first)
        self.state["tok"] = self.state["tok"].at[slot].set(first)
        self.running[slot] = req
        self.results[req.rid] = res
        if req.max_new <= 1 or (ec.eos_id is not None and first == ec.eos_id):
            res.finished = now
            self._free(slot)

    # ------------------------------------------------------------------
    # bucketed batched admission: same-bucket FIFO runs prefill together
    # ------------------------------------------------------------------
    def _bucket_chunks(self, req: Request) -> int:
        """Prefill-length bucket: chunk count of the cache-miss suffix.

        A *preview* using the current index state (allocation may later
        align the hit down under pool pressure — the batched dispatch
        pads ragged members, so a rare mismatch only costs padding).
        """
        n = len(req.prompt)
        cached = 0
        if self.index is not None:
            hits = self.index.match([int(t) for t in req.prompt])
            cached = min(len(hits) * self.ec.block_size, n - 1)
        return -(-(n - cached) // self.ec.chunk_size)

    def _take_bucket_group(self) -> List[Tuple[Request, int, _Allocation]]:
        """Pop the maximal same-bucket FIFO run that can admit now.

        Only the contiguous queue head is considered (no skipping, so
        bucketing never starves a request), capped by free slots and
        ``prefill_batch``.  Returns [] if even the head cannot allocate
        blocks (backpressure).
        """
        group: List[Tuple[Request, int, _Allocation]] = []
        key = self._bucket_chunks(self.queue[0])
        cap = min(len(self.free_slots), self.ec.prefill_batch)
        while (len(group) < cap and self.queue
               and self.queue[0].arrival_step <= self.step_idx
               and self._bucket_chunks(self.queue[0]) == key):
            if not self._adapter_admissible(self.queue[0]):
                break
            alloc = self._allocate(self.queue[0])
            if alloc is None:
                break
            group.append((self.queue.popleft(), self.free_slots.pop(0),
                          alloc))
        return group

    def _admit_batch(self,
                     group: List[Tuple[Request, int, _Allocation]]) -> None:
        """Admit a same-bucket group with batched prefill-and-insert.

        Per-request block accounting and bookkeeping mirror
        :meth:`_admit`; the prefill chunks run as ONE batched dispatch
        per chunk index across the group (``make_prefill_batch_fn``),
        padded to the static ``prefill_batch`` width.  Each member's
        first token is sampled from its own logits row of its final
        chunk's dispatch, in queue order — at temperature 0 the admitted
        tokens are identical to unbucketed admission (tested).
        """
        ec = self.ec
        pb = ec.prefill_batch
        members = []                    # [req, slot, prompt, cached, res]
        for req, slot, alloc in group:
            prompt = np.asarray(req.prompt, np.int32)
            n, cached = len(prompt), alloc.cached
            self._slot_blocks[slot] = alloc.table
            self.prefix_hit_tokens += cached
            self.prompt_tokens += n
            if alloc.cow is not None:
                self.state = self.cache.copy_block(self.state, *alloc.cow)
            row = np.zeros((self.cache.max_blocks_per_seq,), np.int32)
            row[:len(alloc.table)] = alloc.table
            self.state["block_tables"] = (
                self.state["block_tables"].at[slot].set(jnp.asarray(row)))
            self.state["pos"] = self.state["pos"].at[slot].set(cached)
            self._bind_adapter(req, slot)
            res = RequestResult(rid=req.rid, tokens=[], prompt_len=n,
                                cached_tokens=cached,
                                arrival=self._arrivals.get(req.rid) or 0.0,
                                admitted=self._now())
            members.append([req, slot, prompt, cached, res])
        n_chunks = max(-(-(len(p) - c) // ec.chunk_size)
                       for _, _, p, c, _ in members)
        first_logits: List[Optional[np.ndarray]] = [None] * len(members)
        for ci in range(n_chunks):
            qtoks = np.zeros((pb, ec.chunk_size), np.int32)
            # padding members duplicate a real slot id; their valid=0
            # drops KV writes and cursor advances inside the dispatch
            slots_arr = np.full((pb,), members[0][1], np.int32)
            valids = np.zeros((pb,), np.int32)
            ev_members, ev_ranks = [], []
            for i, (req, slot, prompt, cached, res) in enumerate(members):
                slots_arr[i] = slot
                off = cached + ci * ec.chunk_size
                n = len(prompt)
                if off >= n:
                    continue            # ragged member: already done
                piece = prompt[off:off + ec.chunk_size]
                valids[i] = len(piece)
                qtoks[i, :len(piece)] = piece
                ev_members.append((req.rid, slot, len(piece), off, cached,
                                   off + len(piece) >= n))
                ev_ranks.append(self._slot_rank(slot))
            with _span("engine.prefill", ev=len(self.trace)):
                out, self.state = self.prefill_batch_fn(
                    self.params, self.state, jnp.asarray(qtoks),
                    jnp.asarray(slots_arr), jnp.asarray(valids))
            logits = _sync("logits", lambda: np.asarray(jax.device_get(out)))
            for i, (req, slot, prompt, cached, res) in enumerate(members):
                off = cached + ci * ec.chunk_size
                if off < len(prompt) and off + valids[i] >= len(prompt):
                    first_logits[i] = logits[i]
            self.trace.append(TraceEvent(kind="prefill_batch",
                                         chunk=ec.chunk_size,
                                         members=tuple(ev_members),
                                         adapter_ranks=tuple(ev_ranks)))
        now = self._now()
        for i, (req, slot, prompt, cached, res) in enumerate(members):
            n = len(prompt)
            if self.index is not None:
                self.index.insert(
                    prompt[:(n // ec.block_size) * ec.block_size],
                    self._slot_blocks[slot][:n // ec.block_size])
            self._rng, sub = jax.random.split(self._rng)
            first = _sync("first_token", lambda: int(sample(
                first_logits[i][None], ec.temperature, sub)[0]))
            res.first_token = now
            res.tokens.append(first)
            self.state["tok"] = self.state["tok"].at[slot].set(first)
            self.running[slot] = req
            self.results[req.rid] = res
            if req.max_new <= 1 or (ec.eos_id is not None
                                    and first == ec.eos_id):
                res.finished = now
                self._free(slot)
        self.peak_blocks_in_use = max(self.peak_blocks_in_use,
                                      self.pool.in_use)

    def _free(self, slot: int) -> None:
        with _span("engine.free", slot=slot):
            del self.running[slot]
            for b in self._slot_blocks.pop(slot):
                self.pool.decref(b)    # index refs keep shared blocks warm
            aid = self._slot_adapter.pop(slot, None)
            if aid is not None:
                # the adapter stays resident (warm for the tenant's next
                # request) until pool pressure LRU-evicts it
                self.adapter_pool.release(aid)
            self.state = self.cache.reset_slot(self.state, slot)
            self.free_slots.append(slot)

    # ------------------------------------------------------------------
    # one engine step: admissions, then one fused decode block
    # ------------------------------------------------------------------
    def step(self) -> None:
        """One engine step: admissions, then one fused decode block (or
        one speculative verify) over every running slot."""
        with _span("engine.step", step=self.step_idx, ev=len(self.trace)):
            self._step()

    def _step(self) -> None:
        ec = self.ec
        if not self.trace:
            # header: the engine knobs the twin's replay/cold_trace need
            self.trace.append(TraceEvent(kind="engine", chunk=ec.chunk_size,
                                         n_steps=ec.decode_block,
                                         tp=self.tp, pp=self.pp,
                                         attn_impl=ec.attn_impl,
                                         block_size=ec.block_size,
                                         spec_k=ec.spec_k,
                                         lora_tenants=ec.lora_tenants,
                                         lora_ranks=ec.lora_ranks))
        # deferred (open-loop) requests arrive when their gate opens
        now = self._now()
        waiting = 0
        for r in self.queue:
            if r.arrival_step <= self.step_idx:
                waiting += 1
                if self._arrivals.get(r.rid) is None:
                    self._arrivals[r.rid] = now
        self.queue_depth.append((self.step_idx, now, waiting))
        while (self.free_slots and self.queue
               and self.queue[0].arrival_step <= self.step_idx):
            if not self._adapter_admissible(self.queue[0]):
                break                  # all adapter slots pinned: backpressure
            if ec.prefill_batch > 1:
                group = self._take_bucket_group()
                if not group:
                    break              # pool exhausted: admission backpressure
                head, slot, _ = group[0]
                with _span("engine.admit", rid=head.rid, slot=slot,
                           n=len(group)):
                    self._admit_batch(group)
                continue
            alloc = self._allocate(self.queue[0])
            if alloc is None:
                break                  # pool exhausted: admission backpressure
            req, slot = self.queue.popleft(), self.free_slots.pop(0)
            with _span("engine.admit", rid=req.rid, slot=slot):
                self._admit(req, slot, alloc)
        if self.running and ec.spec_k > 0:
            self._spec_step()
        elif self.running:
            slots_meta, slot_ranks = [], []
            active = np.zeros((ec.max_slots,), bool)
            remaining = np.zeros((ec.max_slots,), np.int32)
            for slot, req in sorted(self.running.items()):
                budget = req.max_new - len(self.results[req.rid].tokens)
                slots_meta.append((req.rid, self._cursor(slot), budget))
                slot_ranks.append(self._slot_rank(slot))
                active[slot] = True
                remaining[slot] = budget
            slots_meta = tuple(slots_meta)
            self._rng, sub = jax.random.split(self._rng)
            with _span("engine.decode", ev=len(self.trace)):
                toks, produced, _, self.state = self.decode_fn(
                    self.params, self.state, jnp.asarray(active),
                    jnp.asarray(remaining), sub)
            _sync("ready", lambda: jax.block_until_ready(toks))
            self.trace.append(TraceEvent(
                kind="decode_block", n_steps=ec.decode_block,
                slots=slots_meta, adapter_ranks=tuple(slot_ranks)))
            self._harvest(_sync("tokens", lambda: np.asarray(toks)),
                          _sync("produced", lambda: np.asarray(produced)))
        self.step_idx += 1

    def _cursor(self, slot: int) -> int:
        """Slot ``slot``'s KV cursor, read from the device."""
        return _sync("cursor", lambda: int(self.state["pos"][slot]))

    def _harvest(self, toks: np.ndarray, produced: np.ndarray) -> None:
        """Collect the block's sampled tokens; free completed slots."""
        with _span("engine.harvest"):
            now = self._now()
            for slot, req in list(self.running.items()):
                res = self.results[req.rid]
                for t in range(toks.shape[0]):
                    if not produced[t, slot]:
                        break
                    res.tokens.append(int(toks[t, slot]))
                hit_eos = (self.ec.eos_id is not None and res.tokens
                           and res.tokens[-1] == self.ec.eos_id)
                if len(res.tokens) >= req.max_new or hit_eos:
                    res.finished = now
                    self._free(slot)

    # ------------------------------------------------------------------
    # speculative decoding: draft k, verify k+1 queries, accept a prefix
    # ------------------------------------------------------------------
    def _spec_step(self) -> None:
        """One speculative step: per active slot, propose ``spec_k`` draft
        tokens from the request's own history, verify the pending token
        plus the drafts in ONE batched (k+1)-query pass through the
        block-paged cache, then accept a prefix by rejection sampling.

        The KV cursor only rolls *forward* by the accepted count: the
        rejected tail's K/V stays in the slot's preallocated blocks,
        causally unreachable (keys past the cursor are masked) and
        overwritten by the next step — no block-table surgery needed
        because admission already owns blocks for the full budget.
        Per-slot ``valid_q = 1 + min(k, budget-1)`` caps speculation at
        the generation budget, so the highest written position never
        exceeds the allocated ``prompt + max_new - 1`` region.
        """
        ec = self.ec
        k = ec.spec_k
        qtoks = np.zeros((ec.max_slots, k + 1), np.int32)
        active = np.zeros((ec.max_slots,), bool)
        valid_q = np.ones((ec.max_slots,), np.int32)
        drafts: Dict[int, List[int]] = {}
        slots_meta, proposed = [], []
        order = sorted(self.running.items())
        slot_ranks = [self._slot_rank(s) for s, _ in order]
        for slot, req in order:
            res = self.results[req.rid]
            budget = req.max_new - len(res.tokens)
            # history = prompt + everything emitted; the last emitted token
            # is exactly the pending token (in ``tok``, not yet in KV)
            d = self.drafter.propose(
                [int(t) for t in req.prompt] + res.tokens, k)
            drafts[slot] = d
            slots_meta.append((req.rid, self._cursor(slot), budget))
            active[slot] = True
            valid_q[slot] = 1 + min(k, budget - 1)
            proposed.append(int(valid_q[slot]) - 1)
            qtoks[slot, 0] = res.tokens[-1]
            qtoks[slot, 1:] = d
        with _span("engine.verify", ev=len(self.trace)):
            out, self.state = self.verify_fn(
                self.params, self.state, jnp.asarray(qtoks),
                jnp.asarray(active), jnp.asarray(valid_q))
        logits = _sync("logits",                          # (S, k+1, V)
                       lambda: np.asarray(jax.device_get(out)))
        now = self._now()
        accepted = []
        for slot, req in order:
            res = self.results[req.rid]
            vq = int(valid_q[slot])
            emitted = self._accept(logits[slot, :vq], drafts[slot][:vq - 1])
            accepted.append(len(emitted) - 1)
            if ec.eos_id is not None and ec.eos_id in emitted:
                emitted = emitted[:emitted.index(ec.eos_id) + 1]
            res.tokens.extend(emitted)
            self.state["pos"] = self.state["pos"].at[slot].add(len(emitted))
            self.state["tok"] = (
                self.state["tok"].at[slot].set(emitted[-1]))
            hit_eos = ec.eos_id is not None and res.tokens[-1] == ec.eos_id
            if len(res.tokens) >= req.max_new or hit_eos:
                res.finished = now
                self._free(slot)
        self.trace.append(TraceEvent(
            kind="spec_step", n_steps=1, slots=tuple(slots_meta),
            spec_k=k, proposed=tuple(proposed), accepted=tuple(accepted),
            adapter_ranks=tuple(slot_ranks)))
        self.spec_proposed += sum(proposed)
        self.spec_accepted += sum(accepted)
        self.spec_steps += 1

    def _accept(self, logits: np.ndarray, drafts: List[int]) -> List[int]:
        """Standard speculative rejection sampling against the verify
        logits (``(vq, V)`` — row i scores the token *after* query i).

        Returns the emitted tokens: the accepted draft prefix plus one —
        the bonus token on full acceptance, or the corrected sample at
        the first rejection.  Exact w.r.t. the target distribution; at
        temperature 0 it degenerates to the longest greedy-matching
        prefix plus the greedy next token, which makes spec decode
        bit-identical to plain greedy decode (tested).
        """
        temp = self.ec.temperature
        if temp <= 0.0:
            targets = np.argmax(logits, axis=-1)
            a = 0
            while a < len(drafts) and drafts[a] == int(targets[a]):
                a += 1
            return [int(t) for t in targets[:a + 1]]
        # the n-gram/greedy drafter is a point mass at d: accept with
        # probability p(d); on rejection sample the residual p \ {d}
        x = logits.astype(np.float64) / temp
        x -= x.max(axis=-1, keepdims=True)
        p = np.exp(x)
        p /= p.sum(axis=-1, keepdims=True)
        out: List[int] = []
        for i, d in enumerate(drafts):
            if self._np_rng.random() < p[i, d]:
                out.append(int(d))
                continue
            q = p[i].copy()
            q[d] = 0.0
            s = q.sum()
            if s <= 0.0:               # target IS the point mass: accept
                out.append(int(d))
                continue
            out.append(int(self._np_rng.choice(q.shape[0], p=q / s)))
            return out
        out.append(int(self._np_rng.choice(p.shape[-1], p=p[len(drafts)])))
        return out

    @property
    def spec_acceptance(self) -> float:
        """Measured mean draft-acceptance rate over the run."""
        return self.spec_accepted / max(self.spec_proposed, 1)

    @property
    def spec_tokens_per_step(self) -> float:
        """Measured mean tokens a slot emits per speculative step
        (accepted drafts + the bonus/corrected token) — the measured
        counterpart of the forecast's expected tokens/step Σ α^i."""
        slot_steps = sum(len(ev.slots) for ev in self.trace
                         if ev.kind == "spec_step")
        if not slot_steps:
            return 0.0
        return self.spec_accepted / slot_steps + 1.0

    # ------------------------------------------------------------------
    def run(self, requests: Optional[Sequence[Request]] = None,
            max_steps: int = 100_000) -> List[RequestResult]:
        """Drain the queue (plus ``requests``) to completion."""
        for r in requests or ():
            self.submit(r)
        steps = 0
        while not self.done:
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError("engine did not drain (scheduler stuck?)")
        return [self.results[rid] for rid in sorted(self.results)]

    # ------------------------------------------------------------------
    def reset_metrics(self) -> None:
        """Clear results/trace/clock while keeping compiled functions,
        cache blocks and the prefix index — call after a warm-up run so
        measured wall-clock excludes one-time XLA compilation."""
        if not self.done:
            raise RuntimeError("reset_metrics with requests in flight")
        self.results.clear()
        self.trace.clear()
        self._arrivals.clear()
        self.queue_depth.clear()
        self.step_idx = 0
        self.prefix_hit_tokens = 0
        self.prompt_tokens = 0
        self.peak_blocks_in_use = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_steps = 0
        self._t0 = time.perf_counter()

    def warmup(self) -> None:
        """Compile prefill + decode paths with a throwaway request."""
        prompt_len = min(self.ec.chunk_size,
                         self.ec.max_len - self.ec.decode_block - 2)
        # a multi-tenant engine also warms the adapter-miss path (factor
        # generation + the jitted pool write) — without a bound adapter
        # those compiles land inside the measured serving window
        aid = 0 if self.adapter_pool is not None else None
        self.run([Request(rid=-1, prompt=[0] * max(prompt_len, 1),
                          max_new=self.ec.decode_block + 1,
                          adapter_id=aid)])
        if self.index is not None:
            # drop the throwaway prompt's index entries so the measured
            # run starts with a cold cache and an empty pool
            self.index.evict(self.pool.n_blocks)
        if self.adapter_pool is not None:
            # fresh pool: the throwaway tenant's residency and stats must
            # not leak into the measured run's hit/miss accounting
            from .adapter_pool import AdapterPool
            self.adapter_pool = AdapterPool(self.adapter_pool.n_slots)
        self.reset_metrics()

    def calibrate_step_period(self, gen_tokens: int = 16) -> float:
        """Measured wall seconds per engine step, post-compilation.

        Runs a short throwaway serve (call after :meth:`warmup` so the
        jitted paths are compiled), evicts its index entries and resets
        metrics, then stores and returns ``wall / steps``.  The open
        -loop traffic feed uses this to convert a trace's arrival
        seconds into ``Request.arrival_step`` gates
        (``repro.traffic.feed.arrival_steps``).
        """
        if not self.done:
            raise RuntimeError("calibrate_step_period with requests "
                               "in flight")
        prompt_len = max(min(self.ec.chunk_size,
                             self.ec.max_len - self.ec.decode_block - 2), 1)
        gen = max(min(gen_tokens, self.ec.max_len - prompt_len), 1)
        t0 = time.perf_counter()
        self.run([Request(rid=-2, prompt=[0] * prompt_len, max_new=gen)])
        wall = time.perf_counter() - t0
        steps = self.step_idx
        if self.index is not None:
            self.index.evict(self.pool.n_blocks)
        self.reset_metrics()
        self.step_period = wall / max(steps, 1)
        return self.step_period

    def aggregate_tps(self) -> float:
        """Measured generated-tokens/s over the whole run."""
        finished = [r for r in self.results.values() if r.finished > 0]
        if not finished:
            return 0.0
        total = sum(len(r.tokens) for r in finished)
        span = max(r.finished for r in finished)
        return total / max(span, 1e-9)
