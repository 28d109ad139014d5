"""Continuous-batching LLM serving engine over a model's own kind of cache.

Reference counterparts: the inference product around
``paddle/fluid/inference/api/analysis_predictor.cc:427`` and the paged
serving kernel ``paddle/phi/kernels/fusion/gpu/
block_multi_head_attention_kernel.cu:1`` (block tables, dynamic batching).

TPU-native design:

- **One family of compiled programs, not a graph pass pipeline.** A bucketed
  *prefill* program (the model's dense forward over the padded prompt, what
  it left in the cache written into the admitted slots afterwards;
  same-bucket admissions batch through one call on the backend's size
  ladder, 4/2/1 for pages), a batched *decode-chunk* program (the model's
  serving forward, sampling fused in; paged attention runs the block-table
  Pallas kernel) and a *chunk-prefill* program for a prefix hit's suffix or
  a long prompt's pieces.  Static shapes everywhere: the decode batch is
  always ``max_batch`` wide with inactive slots masked by ``lengths == 0``.
- **Chunked on-device decode.** One compiled call runs ``k`` decode steps as
  a ``lax.scan`` (k from a power-of-two ladder), so per-call costs amortize
  over ``k`` tokens.  A sequence whose budget ends mid-chunk simply stops
  being collected; its tail sub-steps decode into its own about-to-be-freed
  blocks (or the trash block) and are discarded.  ``decode_chunk`` caps
  ``k``; the scheduler picks it each round from its own state
  (``_pick_chunk``, ``K_SHORT``).
- **Sync only when token VALUES are needed.** A host readback waits for
  everything dispatched before it, an async dispatch does not.  So the
  scheduler never reads tokens back per step — the ``last``-token
  vector lives ON DEVICE (threaded chunk→chunk, prefilled slots scattered
  in), every prefill/chunk call is dispatched asynchronously in device
  order, and an ownership ledger records at dispatch time which request
  owns which (sub-step, slot) cell.  Token values are materialized in ONE
  fused readback at a sync point: finish emission, an eviction that must
  fold generated tokens back into a prompt, or drain end.  Without eos
  the whole schedule is host-deterministic, so ``run_to_completion``
  dispatches everything and syncs once; with eos in play each round syncs
  so stop-tokens can cut sequences (the chunk tail past an eos is
  discarded).
- **Host-side scheduler, device-side math.** Admission, block allocation,
  growth, eviction, and finish detection are plain Python over a numpy block
  table (shipped to the device each chunk — [max_batch, max_blocks] int32 is
  tiny); everything per-token runs in the compiled programs.
- **Preemption over OOM.** When a sequence needs a block and the pool is
  empty, the youngest running sequence is evicted back to the waiting queue
  (recompute-style preemption) — admission control the reference does with
  its block manager.

Pools are donated through the decode step, and the per-token write
(``kernels.decode_attention.write_paged_token``) leaves them in the row-major
layout the decode kernels read, so XLA updates them in place: the compiled
chunk copies no pool, per layer or around its scan
(``tests/test_chip_compile.py`` holds it to that).

**Cache backends.** What a sequence's "cache" IS is a policy, not a fact,
and all of it lives behind the ``CacheBackend`` seam (``cache_backend.py``):
the block and slot bookkeeping on the host AND the device arrays, as one
pytree (``backend.device``) that every program takes donated and hands
back.  The engine keeps the scheduler, the token ledger and the program
skeletons and knows no cache kind by name: a program asks the backend for
the ``cache`` dict the model's forward takes, and gives it the forward's
``new_cache`` to take the new arrays out of or to write a prefill from.
The backend is made from ``model.cache_spec()``.  Attention models ride
``PagedKV`` (refcounted blocks + prefix cache, K/V pools); the SSD family
rides ``RecurrentState`` — constant-size per-slot decode state, no blocks,
no growth, no prefix hashing, one prompt a prefill call — and hybrid stacks
ride both at once.  What a cache cannot do (prefix reuse, chunked prefill,
batched prefill) the backend says, and the engine degrades to what is left.

Dispatch staging is what the engine does, not an option: the decode call's
scheduler inputs stay on the device while the schedule is unchanged.
"""

from __future__ import annotations

import collections
import hashlib
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .cache_backend import (CacheBackend, LatentKV, PagedKV, RecurrentState,
                            make_backend)
from .. import obs

__all__ = ["Engine", "GenRequest", "RequestOutput", "prefix_block_hashes",
           "CacheBackend", "PagedKV", "LatentKV", "RecurrentState",
           "make_backend"]

NEG_INF = -1e30

# The longest decode chunk of a streaming round (``Engine.step()``) while an
# arrival could be admitted at once.  A request that arrives mid-``step()``
# waits for the rest of it and holds its first token at the end of the next,
# so the chunk sets time to first token; the price is the host's round trip
# between chunks (7 ms against 10.5-12 ms a decode step on a v5e at
# Mistral-7B widths), paid more often.  Ten round trips of device time a
# chunk would give 8.  In paired chip runs (PERF.md section 6, PR 33) 8 cut
# ``ttft_p95_ms`` from 857 to 255-283 ms but raised ``tpot_p95_ms`` by 10-12%;
# 16 gives 431-489 ms for 2-4%: the shorter one that keeps TPOT within 5%.
K_SHORT = 16

# What a decode chunk's slot-steps (``serve.decode_slot_steps``) and a
# prefill call's rows (``serve.prefill_rows``) were spent on
_SLOT_USES = ("kept", "tail", "prefilling", "empty", "cut")
_ROW_USES = ("prompt", "pad")


def prefix_block_hashes(ids, block_size: int) -> List[bytes]:
    """Chain hashes of the FULL blocks of ``ids[:-1]`` — the cacheable
    prefix of a prompt.  Hash ``i`` commits to blocks ``0..i`` (vLLM-style
    chaining), so an index hit on hash ``i`` means the whole prefix through
    block ``i`` is resident.  The last prompt token is never cached: at
    least one suffix token always prefills, producing the first output's
    logits.  Shared by the engine and the router (prefix-affinity routing).
    """
    ids = np.ascontiguousarray(np.asarray(ids, np.int32))
    n = max((len(ids) - 1) // block_size, 0)
    out: List[bytes] = []
    h = b""
    for i in range(n):
        h = hashlib.sha1(
            h + ids[i * block_size:(i + 1) * block_size].tobytes()).digest()
        out.append(h)
    return out


@dataclass
class GenRequest:
    """One generation request (reference: the llm/ serving request shape)."""
    prompt_ids: np.ndarray                 # int32 [P]
    max_new_tokens: int = 64
    temperature: float = 0.0               # <= 0 -> greedy
    top_k: int = 0                         # 0 -> no top-k filter
    top_p: float = 1.0                     # 1.0 -> no nucleus filter
    eos_token_id: Optional[int] = None
    request_id: Optional[str] = None
    # eviction bookkeeping (internal): the user-visible prompt, and tokens
    # generated before a preemption folded them into ``prompt_ids``
    orig_prompt_ids: Optional[np.ndarray] = None
    prior_output: List[int] = field(default_factory=list)
    # deferred-sync bookkeeping (internal): token values materialize here at
    # sync time; counts are tracked on the slot at dispatch time
    _out_vals: List[int] = field(default_factory=list)
    _stopped: bool = field(default=False)
    _emitted: bool = field(default=False)
    _prefill_dt: float = field(default=0.0)
    # perf_counter stamps: add_request, the dispatch of the first prefill,
    # the first token on the host (kept through an eviction's requeue)
    _queued_t: float = field(default=0.0)
    _dispatch_t: float = field(default=0.0)
    _first_t: float = field(default=0.0)


@dataclass
class RequestOutput:
    request_id: str
    prompt_ids: np.ndarray
    output_ids: List[int]
    finish_reason: str                     # "stop" | "length"
    prefill_time: float = 0.0
    finish_time: float = 0.0
    ttft_s: float = 0.0        # add_request -> first token on the host


@dataclass(eq=False)
class _Slot:
    idx: int = 0
    req: Optional[GenRequest] = None
    length: int = 0                        # tokens in cache (prompt + generated)
    blocks: List[int] = field(default_factory=list)
    out_count: int = 0                     # tokens emitted (incl. pending sync)
    admit_seq: int = 0                     # admission order (eviction priority)
    # chunked/suffix prefill: prompt tokens not yet written to the cache
    # (None once fully prefilled; such a slot decodes normally)
    prefill_left: Optional[np.ndarray] = None
    hashes: List[bytes] = field(default_factory=list)  # cacheable-prefix chain


class Engine:
    """Continuous-batching generation over a paged KV cache.

    ::

        eng = Engine(model, max_batch=8, num_blocks=256)
        eng.add_request(GenRequest(prompt_ids, max_new_tokens=128))
        while eng.has_work():
            for out in eng.step():
                print(out.output_ids)

    ``step()`` syncs every round (streaming semantics);
    ``run_to_completion()`` defers syncs while no active request uses eos,
    dispatching the whole schedule asynchronously.

    ``decode_chunk`` is a cap on the decode steps of one compiled call, not
    their number.  A round's chunk is the largest power of two within that
    cap and the longest remaining budget, and within ``K_SHORT`` too in a
    ``step()`` round during which an arrival could be admitted at once (a
    slot is free and the pool holds nobody back): the caller polls
    ``step()`` between tokens, and a request it adds meanwhile waits for the
    running round.  With every slot taken, with the queue held back by the
    pool, and in ``run_to_completion()``, only the cap and the budget bind.
    The counter ``serve.decode_chunks{k, why}`` says which rule chose each
    chunk.
    """

    def __init__(self, model, max_batch: int = 8, num_blocks: int = 256,
                 block_size: int = 128,
                 prefill_buckets: Tuple[int, ...] = (128, 256, 512, 1024),
                 max_prefill_overhead: float = 1.0, decode_chunk: int = 32,
                 hbm_budget_bytes: Optional[int] = None,
                 prefix_cache: bool = True,
                 prefill_chunk: Optional[int] = None):
        self.model = model
        self.cfg = model.config
        self.max_batch = max_batch
        self.block_size = block_size
        self.num_blocks = num_blocks
        if prefill_buckets == "auto":
            # proven ladder (framework.dim_expr): padding waste stays under
            # max_prefill_overhead for any admitted prompt length
            from ..framework.dim_expr import synthesize_buckets

            prefill_buckets, self.prefill_waste_bound = synthesize_buckets(
                1, block_size * 8, max_overhead=max_prefill_overhead,
                align=block_size)
        else:
            from ..framework.dim_expr import verify_buckets

            self.prefill_waste_bound = verify_buckets(
                prefill_buckets, 1, max(prefill_buckets))
        self.prefill_buckets = tuple(sorted(prefill_buckets))
        # longest admissible sequence (prompt + generated) per slot
        self.max_blocks_per_seq = max(
            (b // block_size for b in self.prefill_buckets)) * 2

        self._params = {n: p._data for n, p in model.named_parameters()}
        self._buffers = {n: b._data for n, b in model.named_buffers()}
        self.hbm_budget_bytes = hbm_budget_bytes

        # the CacheBackend seam: per-layer cache kinds + byte quantities
        # from the model, everything about the cache from the backend.  What
        # a cache cannot do is the backend's to say, and degrades gracefully:
        # no block chain to hash (pure SSD) or a hit that would restore only
        # the attention half (hybrid); no block-aligned context offset for a
        # chunked prefill to ride
        self.backend = make_backend(model.cache_spec(), num_blocks,
                                    block_size, max_batch,
                                    prefix_cache=prefix_cache)
        self.prefix_cache = self.backend.supports_prefix_cache
        if not self.backend.supports_chunked_prefill:
            prefill_chunk = None
        if prefill_chunk is not None:
            # chunks must be block-aligned so every chunk starts on a block
            # boundary (write_paged_chunk's precondition)
            prefill_chunk = max(1, -(-int(prefill_chunk) // block_size)) \
                * block_size
        self.prefill_chunk = prefill_chunk
        self._slots = [_Slot(idx=i) for i in range(max_batch)]
        self._tbl = np.zeros((max_batch, self.max_blocks_per_seq), np.int32)
        self._waiting: collections.deque = collections.deque()
        self._admit_counter = 0
        self._req_counter = 0
        self._tok_seg_rows = 1024
        # a chunk must fit one token segment buffer (dynamic_update_slice
        # cannot write an update larger than its operand)
        self.decode_chunk = max(1, min(int(decode_chunk), self._tok_seg_rows))
        self._decode_fns: Dict[int, object] = {}
        self._prefill_fns: Dict[Tuple[int, int], object] = {}
        self._chunk_fns: Dict[Tuple[int, bool], object] = {}
        # device-resident last-token vector: threaded chunk -> chunk, so no
        # decode round trip is ever needed to BUILD the next decode's inputs
        self._last_dev = jnp.zeros((max_batch,), jnp.int32)
        # device-side token accumulators: each program WRITES its sampled
        # tokens into a segment buffer (chunk rows / prefill firsts), so a
        # sync reads back a handful of segment arrays instead of one array
        # per call
        self._tok_buf = jnp.zeros((self._tok_seg_rows, max_batch), jnp.int32)
        self._tok_row = 0
        self._first_seg = 512
        self._first_buf = jnp.zeros((self._first_seg,), jnp.int32)
        self._first_idx = 0
        # static HBM sizing BEFORE the pool allocation: params + KV pools +
        # tables + program workspace, refused up front when the budget can't
        # fit — the OOM happens here, in Python, with a component breakdown,
        # not mid-serving inside XLA
        if hbm_budget_bytes is not None:
            plan = self.memory_plan()
            if plan["total_bytes"] > hbm_budget_bytes:
                detail = ", ".join(f"{k}={v / 1e6:.1f}MB"
                                   for k, v in plan.items()
                                   if k != "total_bytes"
                                   and isinstance(v, (int, float)))
                raise ValueError(
                    f"serving memory plan {plan['total_bytes'] / 1e6:.1f}MB "
                    f"exceeds hbm_budget_bytes={hbm_budget_bytes / 1e6:.1f}MB"
                    f" ({detail}); reduce num_blocks (kv_pool_bytes scales "
                    f"linearly with it) or max_batch")
        # the cache's device arrays: written by the prefill programs and
        # threaded through the decode scan (donated, updated in place)
        self.backend.device = self.backend.init_device(model)
        # dispatch staging (host-dispatch overlap): device copies of the
        # decode call's scheduler inputs, reused while the scheduler state
        # they snapshot is unchanged — steady-state decode then uploads
        # NOTHING per call (the lengths vector advances ON DEVICE and is
        # re-staged from the program's own output)
        self._sched_version = 0
        self._staged = None                    # (version, tbl, lengths, ...)
        self._last_dispatch_t: Optional[float] = None
        # recent decode-visible gaps (bounded: a server runs for ever)
        self._decode_gaps = collections.deque(maxlen=4096)
        self._full_tok_bufs: List[object] = []
        self._full_first_bufs: List[object] = []
        # deferred-sync state: dispatch-ordered ledger of unmaterialized
        # tokens, dispatch-decided finishes, and finished outputs to drain
        self._pending: List[tuple] = []
        self._finish_order: List[GenRequest] = []
        self._ready: List[RequestOutput] = []
        self.stats = {"decode_steps": 0, "prefills": 0, "evictions": 0,
                      "generated_tokens": 0, "decode_time": 0.0,
                      "prefill_time": 0.0, "prefill_tokens": 0,
                      "decode_calls": 0, "syncs": 0, "sync_time": 0.0,
                      # prefix cache: blocks probed / blocks served from
                      # cache (hit tokens = blocks * block_size saved from
                      # prefill); chunk_prefills counts chunk-program calls
                      "prefix_lookup_blocks": 0, "prefix_hit_blocks": 0,
                      "prefix_hit_tokens": 0, "chunk_prefills": 0}
        # observability: the router stamps a replica id so registry
        # families split per replica; standalone engines stay unlabeled
        self.obs_replica: Optional[int] = None
        # {family: ((registry generation, replica), {use: Counter})}
        self._use_ctrs: Dict[str, tuple] = {}

    # -- observability -------------------------------------------------------

    def _obs_labels(self) -> dict:
        if self.obs_replica is None:
            return {}
        return {"replica": self.obs_replica}

    def _use_counters(self, name: str, uses: Tuple[str, ...]) -> dict:
        """``{use: Counter}`` of the family ``name{use, replica}``, fetched
        from the registry once per label set (again after a reset or a new
        replica label), so a chunk pays adds and no lookups."""
        reg = obs.registry()
        key = (reg.generation, self.obs_replica)
        got = self._use_ctrs.get(name)
        if got is None or got[0] != key:
            got = self._use_ctrs[name] = (key, {
                u: reg.counter(name, use=u, **self._obs_labels())
                for u in uses})
        return got[1]

    def _count_prefill_rows(self, rows: int, tokens: int) -> None:
        c = self._use_counters("serve.prefill_rows", _ROW_USES)
        c["prompt"].inc(tokens)
        c["pad"].inc(rows - tokens)

    def _obs_mark(self, req: GenRequest, phase: str, **args) -> None:
        """Phase mark on the request's lifecycle chain.  Tracing-only
        (no-op when the tracer is off) and host-metadata-only, so traced
        serving output is bit-identical to untraced.  ``lifecycle_begin``
        dedups, so whichever layer sees the request first (router submit
        or engine add_request) opens the chain."""
        tr = obs.tracer()
        if tr is None or req.request_id is None:
            return
        if self.obs_replica is not None:
            args.setdefault("replica", self.obs_replica)
        tr.lifecycle_begin(req.request_id)
        tr.lifecycle_mark(req.request_id, phase, args=args or None)

    def _obs_dispatched(self, req: GenRequest, t0: float) -> None:
        """A prefill of ``req`` is dispatched at ``t0``; the first one ends
        its wait for a slot, blocks and the running step."""
        if req._dispatch_t or not req._queued_t:
            return
        req._dispatch_t = t0
        obs.registry().histogram(
            "serve.queue_wait_ms", **self._obs_labels()).observe(
                (t0 - req._queued_t) * 1e3)

    def _obs_first_token(self, req: GenRequest, now: float) -> None:
        """The first token of ``req`` is on the host at ``now``: the ONE
        place TTFT is taken (once a request; an evicted request's requeue
        keeps the stamp)."""
        if req._first_t or not req._queued_t:
            return
        req._first_t = now
        obs.registry().histogram(
            "serve.ttft_ms", **self._obs_labels()).observe(
                (now - req._queued_t) * 1e3)
        self._obs_mark(req, "first-token")

    # -- public API ---------------------------------------------------------

    def memory_plan(self) -> Dict[str, int]:
        """Static HBM sizing of everything the engine keeps resident plus
        the transient residency of its two program families — pure
        arithmetic over the config, safe before any device allocation.

        ``total_bytes`` = resident state + max(decode, prefill) workspace
        (the two program families never run concurrently on one device).
        The workspace terms are the analytic dominators: hidden states +
        logits for a full-width decode chunk step; activations + attention
        scores + logits at the largest prefill bucket on the widest ladder
        rung.  ``analysis.lint_memory`` on the lowered programs is the
        exact cross-check (``tests/test_serving.py`` runs it on the decode
        chunk)."""
        import numpy as np

        cfg = self.cfg
        itemsize = jnp.dtype(cfg.dtype).itemsize
        params_b = sum(int(np.prod(v.shape)) * jnp.dtype(v.dtype).itemsize
                       for v in self._params.values())
        buffers_b = sum(int(np.prod(v.shape)) * jnp.dtype(v.dtype).itemsize
                        for v in self._buffers.values())
        # pool + per-slot state residency come from the backend (for the
        # attention-only PagedKV case this is EXACTLY the historical
        # 2 * layers * kv_heads * bs * head_dim * itemsize * num_blocks)
        kv_pool_b = self.backend.pool_bytes()
        state_b = self.backend.state_bytes()
        table_b = (self.max_batch * self.max_blocks_per_seq * 4
                   + self._tok_seg_rows * self.max_batch * 4
                   + self._first_seg * 4 + self.max_batch * 4)
        decode_b = self.max_batch * (4 * cfg.hidden_size
                                     + cfg.vocab_size) * itemsize
        Pb = max(self.prefill_buckets)
        n_pf = min(4, self.max_batch)
        prefill_b = n_pf * (2 * Pb * cfg.hidden_size
                            + cfg.num_attention_heads * Pb * Pb
                            + Pb * cfg.vocab_size) * itemsize
        # prefix-cache metadata: sha1 digest (20B) + hash-index entry +
        # refcount + LRU node per block — host-side, but counted so
        # hbm_budget_bytes admission stays honest with caching on
        prefix_b = self.num_blocks * 64 if self.prefix_cache else 0
        # chunk-prefill workspace (chunked prefill / cache-hit suffix
        # prefill, B=1): chunk activations + the full-capacity context
        # gather + scores + final-chunk logits
        chunk_b = 0
        if self.prefix_cache or self.prefill_chunk is not None:
            C = self.max_blocks_per_seq * self.block_size
            chunk_b = (2 * Pb * cfg.hidden_size * itemsize
                       + 2 * C * cfg.kv_heads * cfg.head_dim * itemsize
                       + cfg.num_attention_heads * Pb * C * 4
                       + Pb * cfg.vocab_size * itemsize)
        plan = {"params_bytes": params_b, "buffers_bytes": buffers_b,
                "kv_pool_bytes": kv_pool_b, "state_bytes": state_b,
                "table_bytes": table_b,
                "prefix_cache_bytes": prefix_b,
                "decode_workspace_bytes": decode_b,
                "prefill_workspace_bytes": prefill_b,
                "chunk_workspace_bytes": chunk_b}
        plan["total_bytes"] = (params_b + buffers_b + kv_pool_b + state_b
                               + table_b + prefix_b
                               + max(decode_b, prefill_b, chunk_b))
        # the flat-vs-linear story, straight from the backend: one
        # sequence's cache footprint at growing context lengths (flat for
        # recurrent state, ~linear in blocks for paged KV, summed for
        # hybrid) — what capacity planning actually compares across model
        # families
        plan["per_seq_cache_bytes"] = {
            ctx: self.backend.seq_bytes(ctx)
            for ctx in (4096, 16384, 65536)}
        return plan

    def add_request(self, req: GenRequest) -> str:
        if req.request_id is None:
            self._req_counter += 1
            req.request_id = f"req-{self._req_counter}"
        P = len(req.prompt_ids)
        # block-granular capacity checks only bind when the model's cache
        # actually pages (a pure-recurrent sequence needs zero blocks: no
        # block chain and no per-slot KV capacity to exceed)
        if self.backend.blocks_for(P + req.max_new_tokens) > \
                self.max_blocks_per_seq:
            raise ValueError(
                f"prompt ({P}) + max_new_tokens ({req.max_new_tokens}) "
                f"exceeds the per-slot capacity "
                f"{self.max_blocks_per_seq * self.block_size}")
        need = self.backend.blocks_for(self._bucket(P))
        if need > self.num_blocks - 1:
            raise ValueError(
                f"prompt needs {need} blocks but the pool only has "
                f"{self.num_blocks - 1} usable; raise num_blocks")
        req._queued_t = time.perf_counter()
        self._obs_mark(req, "queued", prompt_len=P)
        self._waiting.append(req)
        return req.request_id

    def has_work(self) -> bool:
        return bool(self._waiting) or any(s.req is not None for s in self._slots)

    def step(self) -> List[RequestOutput]:
        """Admit + prefill new requests, run one decode chunk, sync, and
        return any requests that finished (streaming semantics: every step
        materializes its tokens).  The chunk is at most ``K_SHORT`` decode
        steps while an arrival could be admitted at once, so that a request
        added between two calls waits for a short round; otherwise up to
        ``decode_chunk`` (``_pick_chunk``)."""
        with obs.span("serve.step", cat="serve"):
            self._round(streaming=True)
            self._sync_pending()
            obs.registry().gauge("serve.queue_depth",
                                 **self._obs_labels()).set(len(self._waiting))
            return self._drain_ready()

    def token_counts(self) -> Dict[str, int]:
        """``{request_id: tokens on the host}`` of the requests that hold a
        slot: what a streaming client may read after a ``step()``."""
        return {s.req.request_id: len(s.req.prior_output) + len(s.req._out_vals)
                for s in self._slots if s.req is not None}

    def run_to_completion(self) -> List[RequestOutput]:
        """Drain the queue.  While no ACTIVE request uses eos the schedule is
        host-deterministic, so rounds are dispatched back-to-back with no
        readback and one final sync materializes everything.  Nobody reads
        a token before the end and nothing arrives meanwhile, so chunks run
        as long as ``decode_chunk`` and the budgets allow."""
        while self.has_work():
            self._round(streaming=False)
            if any(s.req is not None and s.req.eos_token_id is not None
                   for s in self._slots):
                self._sync_pending()
        self._sync_pending()
        return self._drain_ready()

    # -- scheduling ---------------------------------------------------------

    def _round(self, streaming: bool):
        self._admit()
        self._advance_prefills()
        # slots mid-chunked-prefill don't decode this round; decode rounds
        # interleave BETWEEN their chunks (the point of chunked prefill)
        active = [s for s in self._slots
                  if s.req is not None and s.prefill_left is None]
        if not active:
            return
        k, why = self._pick_chunk(active, streaming)
        obs.registry().counter("serve.decode_chunks", k=k, why=why,
                               **self._obs_labels()).inc()
        self._ensure_decode_blocks(k)
        self._dispatch_chunk(k)

    # -- block pool (delegated to the CacheBackend) -------------------------
    # The engine's historical introspection surface (_free/_ref/_index/
    # _hash_of/_lru) stays readable where the backend is a block pool —
    # tests poke these directly — but the structures LIVE on the backend.

    @property
    def _free(self):
        return self.backend._free

    @property
    def _ref(self):
        return self.backend._ref

    @property
    def _index(self):
        return self.backend._index

    @property
    def _hash_of(self):
        return self.backend._hash_of

    @property
    def _lru(self):
        return self.backend._lru

    def _available(self) -> int:
        """Blocks an allocation can claim: truly free + ref-0 cached."""
        return self.backend.available()

    def _register_prompt_blocks(self, slot: _Slot):
        """Publish a slot's cacheable prompt blocks in the hash index.
        Path A (dense prefill) registers at ADMIT time — its whole prompt
        dispatches this round, before any later reader's program — while
        chunked prefill registers only at the FINAL chunk (earlier rounds
        haven't dispatched the later blocks' writes yet, so a hit would
        read garbage)."""
        if not self.prefix_cache:
            return
        self.backend.register(slot.hashes, slot.blocks)

    def _pick_chunk(self, active, streaming: bool) -> Tuple[int, str]:
        """The round's chunk length and the rule that chose it (the ``why``
        of ``serve.decode_chunks``).  Always the largest power of two within
        the LONGEST remaining budget and ``decode_chunk``: short-remaining
        sequences stop being collected mid-chunk, and their tail sub-steps
        are wasted compute, bounded by the chunk length — the trade against
        the per-call overhead the chunk amortizes.  Called after this
        round's admission, so the scheduler's state says what an arrival
        would meet:

        - ``admissible``: a streaming round with a free slot and nobody
          waiting.  A request added before the next ``step()`` would be
          admitted at once and waits only for this chunk, so the chunk is
          also held to ``K_SHORT``.
        - ``blocked``: a streaming round with every slot taken, or with the
          queue's head held back by the pool.  A short chunk buys no first
          token there.
        - ``batch``: ``run_to_completion()``.
        """
        cap = self.decode_chunk
        if not streaming:
            why = "batch"
        elif self._waiting or all(s.req is not None for s in self._slots):
            why = "blocked"
        else:
            why = "admissible"
            cap = min(cap, K_SHORT)
        rem = max(s.req.max_new_tokens - s.out_count for s in active)
        k = min(max(rem, 1), cap)
        return 1 << (k.bit_length() - 1), why

    def _bucket(self, n: int) -> int:
        for b in self.prefill_buckets:
            if b >= n:
                return b
        # beyond the configured buckets (e.g. an evicted request whose merged
        # prompt grew past them): buckets are only compile keys, so synthesize
        # the next block-multiple on demand
        return -(-n // self.block_size) * self.block_size

    def _admit(self):
        with obs.span("serve.admit", cat="serve") as sp:
            n = self._admit_waiting()
            sp.set(admitted=n, waiting=len(self._waiting))

    def _admit_waiting(self) -> int:
        """Admit waiting requests into free slots, then prefill them in
        same-bucket BATCHES (size ladder 4/2/1): 16 admissions as 16 single
        prefills would pay 16 dispatches where ~5 batched ones do.  Each
        admission's
        program inputs are snapshotted at admit time (the padding blocks are
        released immediately after — unallocated table entries write to the
        trash block, which the length mask never attends).  Returns how many
        it admitted."""
        bs = self.block_size
        admitted = []      # (slot, req, Pb, ids_row, blocks_row, P)
        n_chunked = 0      # admitted on path B
        for slot in self._slots:
            if not self._waiting:
                break
            if slot.req is not None:
                continue
            req = self._waiting[0]
            P = len(req.prompt_ids)
            hashes = (prefix_block_hashes(req.prompt_ids, bs)
                      if self.prefix_cache else [])
            n_hit = self.backend.lookup_chain(hashes)
            self.stats["prefix_lookup_blocks"] += len(hashes)
            chunked = (self.prefill_chunk is not None
                       and P - n_hit * bs > self.prefill_chunk)
            if n_hit == 0 and not chunked:
                # -- path A: dense batched prefill of the whole prompt
                Pb = self._bucket(P)
                n_blocks = self.backend.blocks_for(Pb)
                if n_blocks > self.num_blocks - 1:
                    # an evicted request's merged prompt outgrew the whole
                    # pool: no schedule can ever run it — fail loudly
                    raise RuntimeError(
                        f"request {req.request_id} needs {n_blocks} blocks "
                        f"but the pool only has {self.num_blocks - 1} usable")
                if self._available() < n_blocks:
                    break                  # pool pressure: stop admitting
                self._waiting.popleft()
                blocks = [self.backend.alloc() for _ in range(n_blocks)]
                self._admit_counter += 1
                self.backend.acquire_slot(slot.idx)
                slot.req = req
                slot.length = P
                slot.blocks = blocks
                slot.out_count = 1
                slot.admit_seq = self._admit_counter
                slot.hashes = hashes
                # release bucket-padding blocks beyond the prompt's true
                # need BEFORE snapshotting the program's block row: batched
                # dispatch reorders prefills across buckets, so a freed
                # padding block id left in the row could overwrite a later
                # admission's real K/V (the padded tail's garbage goes to
                # trash block 0 instead, which the length mask never attends)
                needed = -(-slot.length // bs)
                while len(slot.blocks) > max(needed, 1):
                    self.backend.release(slot.blocks.pop())
                self._write_tbl_row(slot)
                # eager registration is safe for path A: this admission's
                # prefill dispatches within this _admit call, and any hit
                # on these blocks dispatches its reader strictly later
                self._register_prompt_blocks(slot)
                ids_row = np.zeros((Pb,), np.int32)
                ids_row[:P] = req.prompt_ids
                blocks_row = np.zeros((n_blocks,), np.int32)
                blocks_row[:len(slot.blocks)] = slot.blocks
                admitted.append((slot, req, Pb, ids_row, blocks_row, P))
                self._obs_mark(req, "admitted", path="dense", bucket=Pb)
                continue
            # -- path B: prefix-hit suffix and/or chunked prefill — admit
            # the slot now; its chunks dispatch in _advance_prefills,
            # interleaved with decode rounds
            hit_blocks = [self.backend.gather(h) for h in hashes[:n_hit]]
            n_sblocks = -(-P // bs) - n_hit
            if self._available() < n_sblocks:
                # roll the hit refs back and stop admitting (the request
                # stays at the queue head for the next round)
                for b in hit_blocks:
                    self.backend.release(b)
                break
            self._waiting.popleft()
            suffix_blocks = [self.backend.alloc() for _ in range(n_sblocks)]
            self._admit_counter += 1
            self.backend.acquire_slot(slot.idx)
            slot.req = req
            slot.length = n_hit * bs       # context already resident
            slot.blocks = hit_blocks + suffix_blocks
            slot.out_count = 0             # first token comes at final chunk
            slot.admit_seq = self._admit_counter
            slot.hashes = hashes
            slot.prefill_left = np.asarray(
                req.prompt_ids[n_hit * bs:], np.int32)
            self._write_tbl_row(slot)
            self.stats["prefix_hit_blocks"] += n_hit
            self.stats["prefix_hit_tokens"] += n_hit * bs
            if n_hit:
                obs.registry().counter(
                    "serve.prefix_hit_blocks",
                    **self._obs_labels()).inc(n_hit)
            self._obs_mark(req, "admitted", path="chunked",
                           hit_blocks=n_hit)
            n_chunked += 1
        by_bucket: Dict[int, list] = {}
        for entry in admitted:
            by_bucket.setdefault(entry[2], []).append(entry)
        for Pb, group in by_bucket.items():
            while group:
                n = next(r for r in self.backend.prefill_ladder
                         if r <= len(group))
                self._prefill_batch(group[:n], Pb)
                group = group[n:]
        for slot, req, *_ in admitted:
            if slot.req is req and slot.out_count >= req.max_new_tokens:
                self._finish_order.append(req)
                self._release(slot)
        return len(admitted) + n_chunked

    def _write_tbl_row(self, slot: _Slot):
        i = slot.idx
        row = np.zeros((self.max_blocks_per_seq,), np.int32)
        row[:len(slot.blocks)] = slot.blocks
        self._tbl[i] = row
        self._sched_version += 1

    def _advance_prefills(self):
        """Dispatch ONE prefill chunk per mid-prefill slot (admission
        order), so decode rounds interleave between a long prompt's chunks
        instead of stalling behind its whole prefill."""
        for slot in sorted((s for s in self._slots
                            if s.req is not None
                            and s.prefill_left is not None),
                           key=lambda s: s.admit_seq):
            self._prefill_chunk_step(slot)

    def _prefill_chunk_step(self, slot: _Slot):
        """One chunk of a path-B prefill: write ``take`` prompt tokens at
        the slot's block-aligned context offset.  Non-final chunks are
        exactly ``prefill_chunk`` tokens (a block multiple, keeping the
        next chunk aligned); the final chunk is ragged, samples the first
        output token, and registers the prompt's cacheable blocks."""
        from ..framework import random as rnd

        req = slot.req
        ids = slot.prefill_left
        total = len(ids)
        take = (total if self.prefill_chunk is None
                else min(total, self.prefill_chunk))
        final = take == total
        Cb = self._bucket(take)
        fn = self._get_chunk_fn(Cb, final)
        ids_row = np.zeros((Cb,), np.int32)
        ids_row[:take] = ids[:take]
        if final:
            if self._first_idx + 1 > self._first_seg:
                self._full_first_bufs.append(self._first_buf)
                self._first_buf = jnp.zeros((self._first_seg,), jnp.int32)
                self._first_idx = 0
            fidx0 = self._first_idx
            self._first_idx += 1
        else:
            fidx0 = self._first_idx        # unused by the non-final program
        self._obs_mark(req, "prefill-chunk", take=take, final=final)
        t0 = time.perf_counter()
        self._obs_dispatched(req, t0)
        with obs.span("serve.prefill-chunk", cat="serve",
                      args={"bucket": Cb, "final": final, "tokens": take}):
            self._first_buf, self._last_dev, self.backend.device = fn(
                self._params, self._buffers, self.backend.device,
                self._last_dev, jnp.asarray(slot.idx, jnp.int32),
                jnp.asarray(ids_row),
                jnp.asarray(self._tbl[slot.idx].copy()),
                jnp.asarray(slot.length, jnp.int32),
                jnp.asarray(take, jnp.int32), rnd.next_key(),
                jnp.asarray(req.temperature, jnp.float32),
                jnp.asarray(req.top_k, jnp.int32),
                jnp.asarray(req.top_p, jnp.float32),
                self._first_buf, jnp.asarray(fidx0, jnp.int32))
        dt = time.perf_counter() - t0      # dispatch cost only
        req._prefill_dt += dt
        slot.length += take
        slot.prefill_left = None if final else ids[take:]
        self._sched_version += 1           # host lengths moved off-device
        self.stats["prefill_time"] += dt
        self.stats["prefill_tokens"] += Cb
        self.stats["chunk_prefills"] += 1
        self._count_prefill_rows(Cb, take)
        if final:
            slot.out_count = 1
            self._pending.append(
                ("prefill", req, len(self._full_first_bufs), fidx0))
            self.stats["prefills"] += 1
            self.stats["generated_tokens"] += 1
            self._register_prompt_blocks(slot)
            if slot.out_count >= req.max_new_tokens:
                self._finish_order.append(req)
                self._release(slot)

    def _ensure_decode_blocks(self, k: int = 1):
        """The next ``k`` decode steps write positions ``length`` through
        ``length + k - 1`` — allocate every block that window touches, per
        slot clipped to its remaining budget (evicting the youngest sequence
        on pressure).  Writes past a finished sequence's window land in the
        trash block (unallocated table entries are 0) or its own about-to-be
        -freed blocks — never in another sequence's memory."""
        if not self.backend.blocks_for(1):
            return                 # recurrent state never grows: no blocks
        for slot in sorted((s for s in self._slots if s.req is not None),
                           key=lambda s: s.admit_seq):
            if slot.req is None:
                continue           # evicted by an earlier slot's growth
            if slot.prefill_left is not None:
                continue           # mid-prefill: doesn't decode this round
            w = min(k, max(slot.req.max_new_tokens - slot.out_count, 1))
            need_idx = (slot.length + w - 1) // self.block_size
            while slot.req is not None and need_idx >= len(slot.blocks):
                b = self.backend.alloc()
                if b is not None:
                    slot.blocks.append(b)
                    continue
                actives = [s for s in self._slots if s.req is not None]
                if len(actives) == 1 and actives[0] is slot:
                    # truly alone and still out of blocks: a genuine
                    # capacity error
                    raise RuntimeError(
                        "paged KV pool exhausted by a single sequence; "
                        "increase num_blocks")
                # preempt the youngest active sequence — possibly THIS one
                # (it requeues and retries once older work finishes)
                victim = max(actives, key=lambda s: s.admit_seq)
                self._evict(victim)
            if slot.req is not None:
                self._write_tbl_row(slot)

    def _evict(self, slot: _Slot):
        """Recompute-style preemption: requeue the request (with its already
        generated tokens prepended to the prompt) and free its blocks.  The
        merge needs token VALUES, so a deferred-sync backlog materializes
        here first."""
        free_before = self._available()
        self._sync_pending()
        req = slot.req
        if req is None:
            # the sync itself released this slot (the victim's pending first
            # token was its eos): nothing left to requeue
            return
        if self._available() > free_before:
            # the sync released eos-finished slots and refilled the pool:
            # the pressure that chose this victim is gone — abort the
            # preemption (the caller's allocation loop re-checks _free and
            # takes these blocks instead of recomputing the victim)
            return
        merged = np.concatenate(
            [np.asarray(req.prompt_ids, np.int32),
             np.asarray(req._out_vals, np.int32)]) if req._out_vals else \
            np.asarray(req.prompt_ids, np.int32)
        requeued = GenRequest(
            prompt_ids=merged,
            max_new_tokens=req.max_new_tokens - len(req._out_vals),
            temperature=req.temperature, top_k=req.top_k, top_p=req.top_p,
            eos_token_id=req.eos_token_id,
            request_id=req.request_id,
            orig_prompt_ids=(req.orig_prompt_ids if req.orig_prompt_ids
                             is not None else req.prompt_ids),
            prior_output=req.prior_output + list(req._out_vals),
            _queued_t=req._queued_t, _dispatch_t=req._dispatch_t,
            _first_t=req._first_t)
        self._waiting.appendleft(requeued)
        self._release(slot)
        self.stats["evictions"] += 1

    def _release(self, slot: _Slot):
        for b in slot.blocks:
            self.backend.release(b)  # shared blocks just drop a ref
        if slot.req is not None:
            self.backend.release_slot(slot.idx)
        self._sched_version += 1
        slot.req = None
        slot.length = 0
        slot.blocks = []
        slot.out_count = 0
        slot.prefill_left = None
        slot.hashes = []
        self._tbl[slot.idx] = 0                  # point at the trash block

    # -- compiled programs --------------------------------------------------

    def _get_prefill_fn(self, Pb: int, n: int):
        fn = self._prefill_fns.get((Pb, n))
        if fn is None:
            fn = self._prefill_fns[(Pb, n)] = jax.jit(
                self._build_prefill(Pb, n), donate_argnums=(2, 3, 12))
        return fn

    def _get_decode_fn(self, k: int):
        fn = self._decode_fns.get(k)
        if fn is None:
            fn = self._decode_fns[k] = jax.jit(
                self._build_decode(k), donate_argnums=(2, 5, 10))
        return fn

    def _get_chunk_fn(self, Cb: int, final: bool):
        fn = self._chunk_fns.get((Cb, final))
        if fn is None:
            fn = self._chunk_fns[(Cb, final)] = jax.jit(
                self._build_chunk_prefill(Cb, final),
                donate_argnums=(2, 3, 13))
        return fn

    def _build_chunk_prefill(self, Cb: int, final: bool):
        """B=1 chunk prefill over the cache's blocks: write a ``Cb``-token
        chunk at the slot's block-aligned context offset and attend
        context + chunk in one gather (``paged_chunk_attention_fn``).  Only
        the FINAL chunk computes an output: the first sampled token at the
        prompt's true last position ``n_valid - 1`` (non-final variants
        skip sampling entirely — XLA drops the lm_head for them).  Pad-tail
        positions past ``n_valid`` write to later table entries, which the
        next chunk's dispatch-ordered writes overwrite (non-final) or the
        trash block absorbs (final)."""
        from ..jit import functional_call

        model, backend = self.model, self.backend

        def chunk(params, buffers, device, last, sidx, ids, tbl_row, ctx,
                  n_valid, key, temp, top_k, top_p, firstbuf, fidx0):
            cache = {**backend.step_cache(device, tbl_row[None, :],
                                          ctx[None]),
                     "n_valid": n_valid[None]}
            out = functional_call(model, params, buffers, ids[None, :],
                                  cache=cache, rng_key=key)
            logits, new_cache = out[0], out[-1]
            if final:
                lg = _logits_at(logits, (n_valid - 1)[None])
                nxt = _sample_batch(lg, jax.random.fold_in(key, 1),
                                    temp[None], top_k[None], top_p[None])
                last = last.at[sidx].set(nxt[0])
                firstbuf = jax.lax.dynamic_update_slice(
                    firstbuf, nxt, (fidx0,))
            return firstbuf, last, backend.take_device(new_cache)

        return chunk

    def _prefill_batch(self, group, Pb: int):
        """Dense-causal prefill of ``n`` same-bucket requests in ONE call;
        the backend writes what it left in the cache, first tokens sampled and
        scattered into the device-resident last-token vector in-program.
        Dispatched asynchronously; the ledger materializes the sampled
        tokens at the next sync."""
        from ..framework import random as rnd

        n = len(group)
        fn = self._get_prefill_fn(Pb, n)
        ids = np.stack([e[3] for e in group])            # [n, Pb]
        blocks = np.stack([e[4] for e in group])         # [n, nb]
        P = np.array([e[5] for e in group], np.int32)
        sidx = np.array([e[0].idx for e in group], np.int32)
        temps = np.array([e[1].temperature for e in group], np.float32)
        top_ks = np.array([e[1].top_k for e in group], np.int32)
        top_ps = np.array([e[1].top_p for e in group], np.float32)
        if self._first_idx + n > self._first_seg:
            self._full_first_bufs.append(self._first_buf)
            self._first_buf = jnp.zeros((self._first_seg,), jnp.int32)
            self._first_idx = 0
        fidx0 = self._first_idx
        self._first_idx += n
        tokens = int(P.sum())
        for _slot, req, *_rest in group:
            self._obs_mark(req, "prefill", bucket=Pb, batch=n)
        t0 = time.perf_counter()
        with obs.span("serve.prefill", cat="serve",
                      args={"bucket": Pb, "n": n, "tokens": tokens}):
            self._first_buf, self._last_dev, self.backend.device = fn(
                self._params, self._buffers, self.backend.device,
                self._last_dev, jnp.asarray(sidx), jnp.asarray(ids),
                jnp.asarray(blocks), jnp.asarray(P), rnd.next_key(),
                jnp.asarray(temps), jnp.asarray(top_ks), jnp.asarray(top_ps),
                self._first_buf, jnp.asarray(fidx0, jnp.int32))
        dt = time.perf_counter() - t0                    # dispatch cost only
        for j, (_slot, req, *_rest) in enumerate(group):
            req._prefill_dt = dt
            self._obs_dispatched(req, t0)
            self._pending.append(
                ("prefill", req, len(self._full_first_bufs), fidx0 + j))
        self.stats["prefills"] += n
        self.stats["prefill_time"] += dt
        self.stats["prefill_tokens"] += n * Pb
        self.stats["generated_tokens"] += n
        self._count_prefill_rows(n * Pb, tokens)

    def _build_prefill(self, Pb: int, n: int):
        from ..jit import functional_call

        model, backend = self.model, self.backend

        def prefill(params, buffers, device, last, sidx, ids, blocks, P,
                    key, temps, top_ks, top_ps, firstbuf, fidx0):
            cache = backend.prefill_cache(model.init_cache(n, Pb), P)
            out = functional_call(model, params, buffers, ids, cache=cache,
                                  rng_key=key)
            logits, new_cache = out[0], out[-1]
            device = backend.write_prefill(device, new_cache, sidx, blocks)
            # causality makes row j's logits at P[j]-1 independent of the
            # padded tail, so the batched result matches the n=1 program
            lg = _logits_at(logits, P - 1)                        # [n, V]
            nxt = _sample_batch(lg, jax.random.fold_in(key, 1),
                                temps, top_ks, top_ps)            # [n]
            last = last.at[sidx].set(nxt)
            firstbuf = jax.lax.dynamic_update_slice(firstbuf, nxt, (fidx0,))
            return firstbuf, last, device

        return prefill

    def _dispatch_chunk(self, k: int):
        with obs.span("serve.dispatch", cat="serve") as sp:
            staged, live, kept = self._dispatch_decode(k)
            sp.set(k=k, staged=staged, live=live, kept=kept,
                   width=self.max_batch)

    def _dispatch_decode(self, k: int):
        """Dispatch one k-sub-step decode chunk asynchronously and account
        for it: ownership ledger, host length mirrors, dispatch-decided
        finishes (a finish frees its blocks NOW — the chunk's garbage tail
        writes land before any later prefill reuses them, because device
        execution preserves dispatch order).  Returns whether the staged
        scheduler arrays were reused, how many slots decoded and how many of
        their slot-steps fall inside their requests' budgets.

        The chunk's ``k x max_batch`` slot-steps are counted by use
        (``serve.decode_slot_steps``): ``kept`` inside a request's budget,
        ``tail`` run past it to the chunk's end, ``prefilling`` in a slot
        mid-chunked-prefill, ``empty`` in a slot with no request."""
        from ..framework import random as rnd

        # slots mid-chunked-prefill are NOT decoded: masked inactive
        # (length 0) and their table rows zeroed in the dispatched
        # snapshot, so a decode write at their context offset can't land
        # in their real blocks
        def _dec(s):
            return s.req is not None and s.prefill_left is None
        # dispatch staging: in steady-state decode (no admissions,
        # finishes, or block growth since the last chunk) the scheduler
        # inputs are bit-reusable device arrays — the lengths vector was
        # advanced ON DEVICE by the previous chunk and rides back in, so
        # the call uploads nothing
        staged = (self._staged is not None
                  and self._staged[0] == self._sched_version)
        if staged:
            _, tbl_dev, len_dev, temps_dev, topk_dev, topp_dev = self._staged
        else:
            lengths = np.array([s.length if _dec(s) else 0
                                for s in self._slots], np.int32)
            temps = np.array([s.req.temperature if _dec(s) else 0.0
                              for s in self._slots], np.float32)
            top_ks = np.array([s.req.top_k if _dec(s) else 0
                               for s in self._slots], np.int32)
            top_ps = np.array([s.req.top_p if _dec(s) else 1.0
                               for s in self._slots], np.float32)
            # _tbl MUST be snapshotted: jnp.asarray may alias long-lived
            # host memory (zero-copy on CPU), and with async dispatch the
            # scheduler mutates _tbl while this chunk is still in flight
            tbl = self._tbl.copy()
            for s in self._slots:
                if s.req is not None and s.prefill_left is not None:
                    tbl[s.idx] = 0
            tbl_dev = jnp.asarray(tbl)
            len_dev = jnp.asarray(lengths)
            temps_dev = jnp.asarray(temps)
            topk_dev = jnp.asarray(top_ks)
            topp_dev = jnp.asarray(top_ps)
        if self._tok_row + k > self._tok_seg_rows:
            self._full_tok_bufs.append(self._tok_buf)
            self._tok_buf = jnp.zeros(
                (self._tok_seg_rows, self.max_batch), jnp.int32)
            self._tok_row = 0
        row0 = self._tok_row
        self._tok_row += k
        t0 = time.perf_counter()
        if self._last_dispatch_t is not None:
            gap = t0 - self._last_dispatch_t
            self._decode_gaps.append(gap)
            obs.registry().histogram(
                "serve.decode_gap_ms",
                **self._obs_labels()).observe(gap * 1e3)
        with obs.span("serve.decode-chunk", cat="serve",
                      args={"k": k, "staged": staged}):
            fn = self._get_decode_fn(k)
            self._tok_buf, lst, self.backend.device, lens_out = fn(
                self._params, self._buffers, self.backend.device,
                tbl_dev, len_dev, self._last_dev, rnd.next_key(),
                temps_dev, topk_dev, topp_dev,
                self._tok_buf, jnp.asarray(row0, jnp.int32))
        self._last_dev = lst
        self._last_dispatch_t = time.perf_counter()
        # version is captured BEFORE the post-chunk finish releases below —
        # a finish bumps it, correctly invalidating this entry
        self._staged = (self._sched_version, tbl_dev, lens_out,
                        temps_dev, topk_dev, topp_dev)
        self.stats["decode_time"] += time.perf_counter() - t0
        self.stats["decode_steps"] += k
        self.stats["decode_calls"] += 1
        recs = []
        kept = prefilling = 0
        for s in self._slots:
            if s.req is None:
                continue
            if s.prefill_left is not None:
                prefilling += 1
                continue
            take = min(k, s.req.max_new_tokens - s.out_count)
            kept += take
            recs.append((s.req, s.idx, take))
            s.out_count += take
            s.length += k
            self.stats["generated_tokens"] += take
            self._obs_mark(s.req, "decode-round", k=take)
            if s.out_count >= s.req.max_new_tokens:
                self._finish_order.append(s.req)
                self._release(s)
        self._pending.append(
            ("chunk", len(self._full_tok_bufs), row0, k, recs))
        live = len(recs)
        c = self._use_counters("serve.decode_slot_steps", _SLOT_USES)
        c["kept"].inc(kept)
        c["tail"].inc(k * live - kept)
        c["prefilling"].inc(k * prefilling)
        c["empty"].inc(k * (self.max_batch - live - prefilling))
        return staged, live, kept

    def _build_decode(self, k: int):
        from ..jit import functional_call

        model, backend = self.model, self.backend

        def decode(params, buffers, device, tbl, lengths, last, key, temps,
                   top_ks, top_ps, tokbuf, row0):
            def substep(carry, i):
                dev, lens, lst = carry
                cache = backend.step_cache(dev, tbl, lens)
                out = functional_call(model, params, buffers, lst[:, None],
                                      cache=cache,
                                      rng_key=jax.random.fold_in(key, 2 * i))
                logits, new_cache = out[0], out[-1]
                nxt = _sample_batch(logits[:, 0],
                                    jax.random.fold_in(key, 2 * i + 1),
                                    temps, top_ks, top_ps)
                # inactive slots (lengths 0) hold their state: the model's
                # cached forward leaves their length at 0, their writes land
                # in the trash block and their slot state stays bit-exact
                lst = jnp.where(lens > 0, nxt, lst)
                return (backend.take_device(new_cache),
                        new_cache["lengths"], lst), lst

            (dev, lens, lst), toks = jax.lax.scan(
                substep, (device, lengths, last), jnp.arange(k))
            tokbuf = jax.lax.dynamic_update_slice(
                tokbuf, toks, (row0, jnp.zeros((), row0.dtype)))
            # final lengths ride back out so dispatch staging can reuse
            # them as the NEXT chunk's input without a host round trip
            return tokbuf, lst, dev, lens

        return decode

    def _decode_dummy_args(self):
        """Throwaway inputs of a decode-chunk program: lengths 0, so the
        trash block absorbs every write and every slot holds its state."""
        from ..framework import random as rnd

        zeros = np.zeros((self.max_batch,), np.int32)
        return (self._params, self._buffers, self.backend.device,
                jnp.asarray(self._tbl.copy()), jnp.asarray(zeros),
                jnp.asarray(zeros), rnd.next_key(),
                jnp.asarray(zeros, jnp.float32), jnp.asarray(zeros),
                jnp.ones((self.max_batch,), jnp.float32),
                jnp.zeros((self._tok_seg_rows, self.max_batch), jnp.int32),
                jnp.asarray(0, jnp.int32))

    def lower_decode(self, k: int = 1):
        """The ``k``-step decode-chunk program, lowered and not run: what
        ``compile()`` turns into its text, cost and memory analyses."""
        return self._get_decode_fn(k).lower(*self._decode_dummy_args())

    def warmup(self):
        """Execute every program the engine can hit — prefill at each bucket
        and the decode-chunk ladder — on throwaway inputs (lengths 0, the
        trash block absorbing all writes), so no XLA compile lands inside a
        serving window.  Dummy EXECUTION rather than AOT ``.lower().compile()``
        because only a real call warms jit's dispatch cache."""
        t0 = time.perf_counter()
        n = self._run_warmup()
        reg = obs.registry()
        lbl = self._obs_labels()
        reg.gauge("serve.warmup_s", **lbl).set(time.perf_counter() - t0)
        reg.gauge("serve.warmup_programs", **lbl).set(n)

    def _run_warmup(self) -> int:
        """Run the ladder; returns how many engine programs it called."""
        from ..framework import random as rnd

        n_prog = 0
        k = 1
        while k <= self.decode_chunk:
            buf, _lst, self.backend.device, _lens = self._get_decode_fn(k)(
                *self._decode_dummy_args())
            jax.block_until_ready(buf)
            n_prog += 1
            k *= 2
        for Pb in self.prefill_buckets:
            for n in sorted(self.backend.prefill_ladder):
                if n > self.max_batch:
                    break
                fn = self._get_prefill_fn(Pb, n)
                _buf, self._last_dev, self.backend.device = fn(
                    self._params, self._buffers, self.backend.device,
                    self._last_dev, jnp.zeros((n,), jnp.int32),
                    jnp.zeros((n, Pb), jnp.int32),
                    jnp.zeros((n, self.backend.blocks_for(Pb)), jnp.int32),
                    jnp.ones((n,), jnp.int32), rnd.next_key(),
                    jnp.zeros((n,), jnp.float32),
                    jnp.zeros((n,), jnp.int32), jnp.ones((n,), jnp.float32),
                    jnp.zeros((self._first_seg,), jnp.int32),
                    jnp.asarray(0, jnp.int32))
                n_prog += 1
        if self.prefix_cache or self.prefill_chunk is not None:
            # chunk-prefill family: final variant at every bucket (suffix
            # prefill picks its bucket by suffix length), non-final only at
            # the chunk bucket (non-final chunks are always prefill_chunk)
            variants = [(Pb, True) for Pb in self.prefill_buckets]
            if self.prefill_chunk is not None:
                variants.append((self._bucket(self.prefill_chunk), False))
            for Cb, final in variants:
                fn = self._get_chunk_fn(Cb, final)
                _b, self._last_dev, self.backend.device = fn(
                    self._params, self._buffers, self.backend.device,
                    self._last_dev, jnp.asarray(0, jnp.int32),
                    jnp.zeros((Cb,), jnp.int32),
                    jnp.zeros((self.max_blocks_per_seq,), jnp.int32),
                    jnp.asarray(0, jnp.int32), jnp.asarray(1, jnp.int32),
                    rnd.next_key(), jnp.asarray(0.0, jnp.float32),
                    jnp.asarray(0, jnp.int32), jnp.asarray(1.0, jnp.float32),
                    jnp.zeros((self._first_seg,), jnp.int32),
                    jnp.asarray(0, jnp.int32))
                n_prog += 1
        jax.block_until_ready(self.backend.device)
        return n_prog

    # -- deferred-sync materialization --------------------------------------

    def _sync_pending(self):
        """Materialize every pending token in ONE fused readback per kind,
        walk the ledger in dispatch order filling request values (honoring
        eos cuts), and emit finished outputs into the ready queue."""
        if not self._pending and not self._finish_order:
            return
        if self._pending:
            self.stats["syncs"] += 1
            t0 = time.perf_counter()
            # the programs accumulated every sampled token into device-side
            # segment buffers, so the backlog materializes in a handful of
            # reads no matter how many calls were dispatched; the host is
            # blocked on the device for as long as these reads take
            with obs.span("serve.readback", cat="serve"):
                tok_segs = [np.asarray(b)
                            for b in (*self._full_tok_bufs, self._tok_buf)]
                first_segs = [np.asarray(b) for b in
                              (*self._full_first_bufs, self._first_buf)]
                self.backend.read_counters(**self._obs_labels())
            t_read = time.perf_counter()
        with obs.span("serve.absorb", cat="serve") as sp:
            n_tok, n_ready, cut = 0, len(self._ready), 0
            for e in self._pending:
                if e[0] == "prefill":
                    _, req, seg, fidx = e
                    self._obs_first_token(req, t_read)
                    self.stats["generated_tokens"] -= self._absorb(
                        req, [int(first_segs[seg][fidx])])
                    n_tok += 1
                else:
                    _, seg, row0, kk, recs = e
                    rows = tok_segs[seg][row0:row0 + kk]
                    for req, idx, take in recs:
                        cut += self._absorb(req, rows[:take, idx].tolist())
                        n_tok += take
            if cut:
                # counted at DISPATCH as output tokens and kept slot-steps
                self.stats["generated_tokens"] -= cut
                c = self._use_counters("serve.decode_slot_steps", _SLOT_USES)
                c["kept"].inc(-cut)
                c["cut"].inc(cut)
            if self._pending:
                self._pending.clear()
                self._full_tok_bufs.clear()
                self._full_first_bufs.clear()
                self._tok_row = 0
                self._first_idx = 0
                self.stats["sync_time"] = (self.stats.get("sync_time", 0.0)
                                           + time.perf_counter() - t0)
            for req in self._finish_order:
                if not req._emitted:
                    self._ready.append(self._emit(req, "length"))
            self._finish_order.clear()
            sp.set(tokens=n_tok, finished=len(self._ready) - n_ready)

    def _absorb(self, req: GenRequest, vals: List[int]) -> int:
        """Append materialized tokens to a request, cutting at eos (the
        cut releases the slot if the request still owns one and emits the
        stop output; later ledger cells for the request are ignored).

        Returns how many of ``vals`` it discarded (the eos itself and
        everything after the cut): ``generated_tokens`` and the kept
        slot-steps counted them at DISPATCH time, one per ledger cell, and
        the caller un-counts them so the stats equal emitted
        ``output_ids``."""
        for i, tok in enumerate(vals):
            if req._stopped or req._emitted:
                return len(vals) - i
            if req.eos_token_id is not None and tok == req.eos_token_id:
                req._stopped = True
                for s in self._slots:
                    if s.req is req:
                        self._release(s)
                        break
                self._ready.append(self._emit(req, "stop"))
                return len(vals) - i
            req._out_vals.append(tok)
        return 0

    def _emit(self, req: GenRequest, reason: str) -> RequestOutput:
        req._emitted = True
        tr = obs.tracer()
        if tr is not None and req.request_id is not None:
            tr.lifecycle_end(
                req.request_id,
                args={"reason": reason,
                      "tokens": len(req.prior_output) + len(req._out_vals)})
        obs.registry().counter(
            "serve.requests", **self._obs_labels()).inc()
        return RequestOutput(
            request_id=req.request_id,
            prompt_ids=np.asarray(
                req.orig_prompt_ids if req.orig_prompt_ids is not None
                else req.prompt_ids),
            output_ids=req.prior_output + list(req._out_vals),
            finish_reason=reason,
            prefill_time=req._prefill_dt,
            finish_time=time.time(),
            ttft_s=(req._first_t - req._queued_t
                    if req._first_t and req._queued_t else 0.0))

    def _drain_ready(self) -> List[RequestOutput]:
        out, self._ready = self._ready, []
        return out


def _logits_at(logits, pos):
    """Row ``pos[j]`` of sequence j's logits ``[n, S, V]``.  A model that
    was told the true lengths (``n_valid`` in its cache) may hand back that
    one row, ``[n, 1, V]``: a wide head over every position of a long bucket
    is gigabytes of logits of which one row a prompt is read."""
    if logits.shape[1] == 1:
        return logits[:, 0]
    return jnp.take_along_axis(logits, pos[:, None, None], axis=1)[:, 0]


def _sample_batch(logits, key, temps, top_ks, top_ps):
    """Per-request sampling over a [B, V] logits batch: greedy rows
    (temp <= 0) always take argmax; sampling rows apply temperature,
    then top-k, then nucleus top-p filtering (mirroring
    ``LlamaForCausalLM._build_generate_pure``'s sampler, but with the
    knobs as TRACED per-row values so mixed batches share one program).
    The two V-wide sorts only run when the batch contains a sampling
    request — a batch-level ``lax.cond`` keeps pure-greedy serving on the
    cheap path at runtime."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def sampled(lg0):
        lg = lg0 / jnp.maximum(temps, 1e-6)[:, None]
        V = lg.shape[-1]
        srt = jnp.sort(lg, axis=-1)[:, ::-1]
        kth = jnp.take_along_axis(
            srt, jnp.clip(top_ks - 1, 0, V - 1)[:, None], axis=-1)
        lg = jnp.where((top_ks[:, None] > 0) & (lg < kth), NEG_INF, lg)
        srt2 = jnp.sort(lg, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(srt2, axis=-1)
        csum = jnp.cumsum(probs, axis=-1)
        # floor at a tiny positive value: the exclusive cumsum of the top
        # token is exactly 0, so any positive p keeps it; p <= 0 would keep
        # NOTHING and collapse to uniform-over-vocab
        keep = (csum - probs) < jnp.maximum(top_ps, 1e-9)[:, None]
        thresh = jnp.min(jnp.where(keep, srt2, jnp.inf), axis=-1,
                         keepdims=True)
        lg = jnp.where(lg < thresh, NEG_INF, lg)
        return jax.random.categorical(key, lg, axis=-1).astype(jnp.int32)

    toks = jax.lax.cond(jnp.any(temps > 0.0), sampled,
                        lambda lg0: greedy, logits.astype(jnp.float32))
    return jnp.where(temps > 0.0, toks, greedy)
