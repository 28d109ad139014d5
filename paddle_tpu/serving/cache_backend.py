"""CacheBackend — what a sequence's cache IS, on the host and on the device.

A sequence's "cache" used to mean one thing: a chain of paged KV blocks.
The SSD model family (``models/ssd.py``) breaks that assumption — its decode
state is a CONSTANT-size per-layer tensor, so there is nothing to page, hash
or grow.  This module holds the whole cache policy behind one protocol, so
the engine keeps the scheduler and ONE family of programs and knows no
cache kind by name:

- :class:`PagedKV` — the refcounted block pool + vLLM-style prefix cache,
  and the per-layer K/V pools those blocks address.
- :class:`RecurrentState` — fixed per-slot state residency: ``alloc`` is a
  no-op returning zero blocks, ``seq_bytes`` is FLAT in context length, and
  prefix caching / block hashing / chunked prefill are structurally
  unsupported (the router degrades to headroom+load scoring); on the device,
  one state dict per SSD layer, ``max_slots`` wide.
- :class:`WindowKV` — a BOUNDED per-slot ring for sliding-window attention
  layers: the last ``window`` positions of a sequence a layer, so a window
  layer holds ``window`` tokens however long the context; the slot ledger
  and the flat ``seq_bytes`` are :class:`RecurrentState`'s, and like
  :class:`LatentKV` it carries the model's device-side ``counters``.
- :class:`HybridCache` — pages and a per-slot part at once, for a stack
  that mixes full attention with SSD layers or with window layers: every
  verb is answered by composing the two parts.
- :class:`LatentKV` — paged like :class:`PagedKV` (the same blocks,
  refcounts and prefix hashing) for latent attention: a token's cache is
  ONE row a layer (the compressed latent and the shared rotated key), no
  heads and no separate k and v, in one ``latent`` pool a layer; it also
  carries the model's device-side ``counters`` to the host.

**Host side.** The verbs ``alloc`` / ``append`` / ``gather`` / ``release`` /
``acquire_slot`` / ``release_slot`` / ``migrate`` / ``plan_bytes`` are what
the engine, ``memory_plan()``, the prefix cache and the router go through;
``migrate`` only PLANS today (the byte/unit manifest a future disaggregated
tier would ship — ROADMAP item 1).

**Device side.** The backend also owns the cache's device arrays, as ONE
pytree in ``device`` (a dict keyed like the model's serving cache: ``k`` and
``v`` for pools, ``ssd`` for slot states — an attention-only model's pytree
holds its pools and nothing else).  ``init_device`` builds it from the
model; inside a program ``step_cache`` turns it into the ``cache`` dict the
model's forward takes for a decode sub-step or a prefill chunk,
``take_device`` reads the new arrays back out of the forward's
``new_cache``, and ``write_prefill`` moves a dense prefill's ``new_cache``
into the admitted slots.  ``prefill_ladder`` says how many same-bucket
prompts one prefill call may take, and ``read_counters`` (called inside the
engine's token readback) publishes what the device state counts.  The engine donates ``device`` through
every program and stores what comes back.

Backends are constructed from a model's ``cache_spec()`` dict (every model
the engine serves answers it: ``LlamaForCausalLM.cache_spec``,
``SSDForCausalLM.cache_spec``): per-layer kinds plus the two byte
quantities — ``kv_bytes_per_token_layer`` and ``state_bytes_per_slot`` —
that fully determine footprint arithmetic without any model knowledge.
``KINDS`` maps a layer kind to the backend that caches it: a new kind of
cache is one class here and one entry there.
"""

from __future__ import annotations

import collections
from typing import Callable, Dict, List, Optional, Tuple

__all__ = ["CacheBackend", "PagedKV", "LatentKV", "RecurrentState",
           "WindowKV", "HybridCache", "KINDS", "make_backend"]


class CacheBackend:
    """Protocol base.  ``kind`` names the policy; ``supports_prefix_cache``
    gates block-chain hashing (the router checks it before scoring
    prefix affinity)."""

    kind: str = "abstract"
    supports_prefix_cache: bool = False
    # chunked / suffix prefill rides a block-aligned context offset
    supports_chunked_prefill: bool = False
    # sizes of one prefill call, widest first: same-bucket admissions batch
    # through the widest rung they fill
    prefill_ladder: Tuple[int, ...] = (4, 2, 1)
    # the entries of the model's serving ``cache`` dict this backend keeps
    state_keys: Tuple[str, ...] = ()
    # the device arrays themselves: one pytree, donated through every program
    device: Optional[Dict] = None

    # -- block-granular bookkeeping (no-ops for blockless backends) ---------

    def blocks_for(self, n_tokens: int) -> int:
        """Blocks an ``n_tokens`` context needs (0 on a blockless backend)."""
        return 0

    def available(self) -> int:
        """Blocks an allocation could claim right now."""
        return 0

    def alloc(self) -> Optional[int]:
        """Claim one block (None under pressure)."""
        return None

    def append(self) -> Optional[int]:
        """Claim one GROWTH block for an already-resident sequence — same
        pool as :meth:`alloc`, split out so policies could prioritize."""
        return self.alloc()

    def release(self, block: int) -> None:
        """Drop one ownership ref on ``block``.  Exactly-once per ref:
        releasing a block with no live refs raises."""
        raise RuntimeError(f"release on blockless backend (block {block})")

    # -- prefix reuse -------------------------------------------------------

    def gather(self, h: bytes) -> Optional[int]:
        """Take a live ref on the cached block registered under hash ``h``
        (a prefix hit), or None."""
        return None

    def register(self, hashes: List[bytes], blocks: List[int]) -> None:
        """Publish a sequence's cacheable prefix blocks under their chain
        hashes (first writer wins)."""

    def lookup_chain(self, hashes: List[bytes]) -> int:
        """Longest consecutive resident prefix (in blocks)."""
        return 0

    # -- slot residency (no-ops for backends with nothing per slot) ---------

    def acquire_slot(self, idx: int) -> None:
        """Slot ``idx`` takes a sequence."""

    def release_slot(self, idx: int) -> None:
        """Slot ``idx`` lets its sequence go.  Exactly-once, like blocks."""

    # -- device state -------------------------------------------------------

    def init_device(self, model) -> Dict:
        """The zeroed device arrays of this cache, built from ``model``."""
        return {}

    def step_cache(self, device: Dict, block_table, lengths) -> Dict:
        """The ``cache`` the model's forward takes to advance the slots in
        ``block_table`` / ``lengths`` (a decode sub-step, a prefill chunk)."""
        return {**device, "block_table": block_table, "lengths": lengths}

    def take_device(self, new_cache: Dict) -> Dict:
        """The device arrays out of that forward's ``new_cache``."""
        return {k: new_cache[k] for k in self.state_keys}

    def prefill_cache(self, cache: Dict, n_valid) -> Dict:
        """The dense ``model.init_cache`` of a whole-prompt prefill, with
        what this cache needs to know of the prompts' true lengths
        ``n_valid`` ([n] int32)."""
        return cache

    def write_prefill(self, device: Dict, new_cache: Dict, slots,
                      blocks) -> Dict:
        """Move a dense prefill's ``new_cache`` (n rows) into ``device``:
        row j belongs to slot ``slots[j]`` and owns ``blocks[j]``."""
        return device

    def read_counters(self, gauges=None, **labels) -> None:
        """Publish to ``obs`` what the device state counts.  The engine
        calls it inside its token readback, when everything dispatched has
        run: a backend that keeps counters on the device reads them here
        and waits for nothing.  ``gauges``: what another part of a composed
        cache wants published beside this part's own."""

    def gauges(self) -> Dict[str, float]:
        """Host-side readings of the cache as it stands (``obs`` gauges)."""
        return {}

    # -- accounting ---------------------------------------------------------

    def pool_bytes(self) -> int:
        """Resident bytes of the device pool this backend addresses."""
        return 0

    def state_bytes(self) -> int:
        """Resident bytes of fixed per-slot state across all slots."""
        return 0

    def seq_bytes(self, ctx_len: int) -> int:
        """Per-sequence cache footprint at context length ``ctx_len`` —
        THE curve: linear for paged KV, flat for recurrent state."""
        return 0

    def headroom_bytes(self) -> int:
        """Bytes new admissions could still claim (router scoring)."""
        return 0

    def migrate(self, ctx_len: int) -> Dict:
        """Manifest for moving one sequence's cache to a peer replica:
        total bytes plus the unit list a transfer engine would ship.
        Planning only — no device traffic happens here."""
        return {"kind": self.kind, "bytes": 0, "units": []}

    def plan_bytes(self) -> Dict[str, int]:
        """The backend's contribution to ``Engine.memory_plan()``."""
        return {"kv_pool_bytes": self.pool_bytes(),
                "state_bytes": self.state_bytes()}


class PagedKV(CacheBackend):
    """Refcounted paged-KV block pool with the prefix-cache LRU.

    Extracted from the engine's block bookkeeping verbatim: block 0 is the
    shared trash block, ``_free`` holds virgin blocks, a block serving live
    slots carries a refcount in ``_ref``, and a REGISTERED block whose
    refcount drops to 0 parks in the ``_lru`` (hash -> block, oldest first)
    where a later admission can ``gather`` it (skip its prefill) or
    allocation pressure can reclaim it.
    """

    kind = "paged_kv"
    supports_chunked_prefill = True
    state_keys = ("k", "v")

    def __init__(self, num_blocks: int, block_size: int,
                 bytes_per_token: int, prefix_cache: bool = True,
                 write_prefill=None):
        self.num_blocks = num_blocks
        self.block_size = block_size
        # summed over KV layers: 2 (K and V) * kv_heads * head_dim * itemsize
        self.bytes_per_token = bytes_per_token
        self.supports_prefix_cache = bool(prefix_cache)
        # how a prefilled sequence's K/V go into its blocks, where the
        # model's pools are not in the serving layout (its cache_spec()'s
        # ``kv_write_prefill``; same signature as ``write_paged_prefill``)
        self._write_prefill = write_prefill
        self._ref: Dict[int, int] = {}        # block -> live-owner count
        self._index: Dict[bytes, int] = {}    # chain-hash -> block
        self._hash_of: Dict[int, bytes] = {}  # block -> registered hash
        self._lru: "collections.OrderedDict[bytes, int]" = \
            collections.OrderedDict()         # ref-0 cached blocks
        self._free = collections.deque(range(1, num_blocks))

    @property
    def block_bytes(self) -> int:
        return self.bytes_per_token * self.block_size

    def blocks_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.block_size)

    def available(self) -> int:
        return len(self._free) + len(self._lru)

    def alloc(self) -> Optional[int]:
        """The free pool first, then reclaim the oldest ref-0 cached block
        (deregistering it — cache state is disposable)."""
        if self._free:
            b = self._free.popleft()
        elif self._lru:
            h, b = self._lru.popitem(last=False)
            del self._index[h]
            del self._hash_of[b]
        else:
            return None
        self._ref[b] = 1
        return b

    def release(self, block: int) -> None:
        """Drop one ref; at 0 the block parks in the prefix-cache LRU (if
        registered) or returns to the free pool.  A block shared by several
        live slots just decrements — this is what makes eviction skip
        shared blocks.  Releasing an unowned block is a double-free bug in
        the CALLER's ledger and raises rather than corrupting the pool."""
        n = self._ref.get(block)
        if n is None:
            raise RuntimeError(
                f"double release of block {block}: no live refs")
        if n > 1:
            self._ref[block] = n - 1
            return
        del self._ref[block]
        h = self._hash_of.get(block)
        if h is not None:
            self._lru[h] = block
            self._lru.move_to_end(h)
        else:
            self._free.append(block)

    def gather(self, h: bytes) -> Optional[int]:
        """Live ref on the block registered under ``h``: shared live blocks
        gain a ref, parked blocks leave the LRU."""
        b = self._index.get(h)
        if b is None:
            return None
        if b in self._ref:
            self._ref[b] += 1
        else:
            self._lru.pop(h, None)
            self._ref[b] = 1
        return b

    def register(self, hashes: List[bytes], blocks: List[int]) -> None:
        if not self.supports_prefix_cache:
            return
        for h, b in zip(hashes, blocks):
            if h in self._index or b in self._hash_of:
                continue                       # first writer wins
            self._index[h] = b
            self._hash_of[b] = h

    def lookup_chain(self, hashes: List[bytes]) -> int:
        """Longest consecutive resident prefix (in blocks)."""
        n = 0
        for h in hashes:
            if h not in self._index:
                break
            n += 1
        return n

    def init_device(self, model) -> Dict:
        k, v = model.init_paged_pools(self.num_blocks, self.block_size)
        return {"k": k, "v": v}

    def write_prefill(self, device, new_cache, slots, blocks):
        """Scatter each row's bucket-padded K/V into its blocks (a freed
        padding block's id is 0 by then: the trash block takes the tail)."""
        write_paged_prefill = self._write_prefill
        if write_paged_prefill is None:
            from ..kernels.decode_attention import write_paged_prefill

        n, n_blocks = blocks.shape
        Pb = n_blocks * self.block_size
        k_pools, v_pools = list(device["k"]), list(device["v"])
        for li, (k_c, v_c) in enumerate(new_cache["kv"]):
            for j in range(n):
                k_pools[li], v_pools[li] = write_paged_prefill(
                    k_pools[li], v_pools[li], blocks[j],
                    k_c[j, :Pb], v_c[j, :Pb])
        return {**device, "k": tuple(k_pools), "v": tuple(v_pools)}

    def gauges(self) -> Dict[str, float]:
        return {"cache.kv_bytes_per_token": self.bytes_per_token,
                "cache.kv_blocks_live": len(self._ref)}

    def pool_bytes(self) -> int:
        return self.num_blocks * self.block_bytes

    def seq_bytes(self, ctx_len: int) -> int:
        return self.blocks_for(ctx_len) * self.block_bytes

    def headroom_bytes(self) -> int:
        return self.available() * self.block_bytes

    def migrate(self, ctx_len: int) -> Dict:
        n = self.blocks_for(ctx_len)
        return {"kind": self.kind, "bytes": n * self.block_bytes,
                "units": [{"unit": "kv_block", "count": n,
                           "bytes_each": self.block_bytes}]}


class _DeviceCounters:
    """The model counts on the device (``counters``, int32 sums: what its
    expert layers routed, the pairs its prefills attended) and the cache
    carries the vector through every program; at a readback what was added
    since the last one becomes increments of the ``obs`` counters named in
    the model's ``cache_spec()["counters"]``."""

    counters: Tuple[str, ...] = ()

    def _init_counters(self, counters) -> None:
        self.counters = tuple(counters)
        self._published = [0] * len(self.counters)

    def _zero_counters(self):
        import jax.numpy as jnp

        return jnp.zeros((len(self.counters),), jnp.int32)

    def _publish(self, gauges: Dict[str, float], labels: Dict,
                 in_span: bool = False) -> None:
        import numpy as np

        from .. import obs

        reg = obs.registry()
        # the span carries the running totals: a trace's reader takes the
        # difference of two of them for what a stretch of the run counted
        with obs.span("cache.counters", cat="serve") as sp:
            now = np.asarray(self.device["counters"]).astype(np.uint32)
            sp.set(**{n: int(v) for n, v in zip(self.counters, now)},
                   **(gauges if in_span else {}))
        # the int32 sums wrap; what was added since the last read does not
        for name, d in zip(self.counters,
                           now - np.asarray(self._published, np.uint32)):
            if d:
                reg.counter(name, **labels).inc(int(d))
        self._published = now
        for name, v in gauges.items():
            reg.gauge(name, **labels).set(v)


class LatentKV(_DeviceCounters, PagedKV):
    """Paged latent cache: :class:`PagedKV`'s block bookkeeping (blocks,
    refcounts, prefix-cache LRU, byte accounting) over pools that hold one
    row a token a layer, ``rank + rope`` values wide, which every head
    reads (``kernels/mla_attention.py`` has the layout and the writes).
    ``bytes_per_token`` is that row's bytes summed over the layers.  It
    carries the model's ``counters`` (:class:`_DeviceCounters`)."""

    kind = "latent_kv"
    state_keys = ("latent", "counters")

    def __init__(self, num_blocks: int, block_size: int,
                 bytes_per_token: int, rank: int, counters=(),
                 prefix_cache: bool = True):
        super().__init__(num_blocks, block_size, bytes_per_token,
                         prefix_cache=prefix_cache)
        self.rank = rank
        self._init_counters(counters)

    def init_device(self, model) -> Dict:
        return {"latent": model.init_latent_pools(self.num_blocks,
                                                  self.block_size),
                "counters": self._zero_counters()}

    def prefill_cache(self, cache, n_valid):
        # the model skips the padded tail's experts and computes the logits
        # of the last valid position only
        return {**cache, "n_valid": n_valid}

    def write_prefill(self, device, new_cache, slots, blocks):
        from ..kernels.mla_attention import write_latent_prefill

        Pb = blocks.shape[1] * self.block_size
        pools = list(device["latent"])
        for li, rows in enumerate(new_cache["latent"]):
            for j in range(blocks.shape[0]):
                pools[li] = write_latent_prefill(pools[li], blocks[j],
                                                 rows[j, :Pb], self.rank)
        return {"latent": tuple(pools),
                "counters": device["counters"] + new_cache["counters"]}

    def gauges(self) -> Dict[str, float]:
        per_layer = max(1, len(self.device["latent"]))
        return {"cache.latent_bytes_per_token":
                    self.bytes_per_token // per_layer,
                "cache.latent_blocks_live": len(self._ref)}

    def read_counters(self, gauges=None, **labels) -> None:
        self._publish({**self.gauges(), **(gauges or {})}, labels)


class RecurrentState(CacheBackend):
    """Constant-size per-slot decode state (the SSD layers' residency).

    There are no blocks: ``blocks_for`` is 0, prefix caching is
    structurally unsupported (no block chain to hash), and ``seq_bytes`` is
    FLAT — the whole point.  Slot occupancy is tracked so release is
    exactly-once, mirroring the paged pool's ledger discipline."""

    kind = "recurrent"
    supports_prefix_cache = False
    # the recurrent forward masks the padded tail by ONE scalar n_valid
    prefill_ladder = (1,)
    state_keys = ("ssd",)
    # the entry of the model's cache that holds one state dict a layer
    slot_key = "ssd"

    def __init__(self, max_slots: int, state_bytes_per_slot: int):
        self.max_slots = max_slots
        self.state_bytes_per_slot = int(state_bytes_per_slot)
        self._live: Dict[int, bool] = {}

    def acquire_slot(self, idx: int) -> None:
        if self._live.get(idx):
            raise RuntimeError(f"slot {idx} already live")
        self._live[idx] = True

    def release_slot(self, idx: int) -> None:
        if not self._live.pop(idx, False):
            raise RuntimeError(f"double release of slot {idx}")

    def free_slots(self) -> int:
        return self.max_slots - len(self._live)

    def init_device(self, model) -> Dict:
        return {"ssd": model.init_recurrent_slots(self.max_slots)}

    def prefill_cache(self, cache, n_valid):
        # exact: projections zeroed past n_valid are no-ops on the scan
        return {**cache, "n_valid": n_valid[0]}

    def write_prefill(self, device, new_cache, slots, blocks):
        key = self.slot_key
        return {**device, key: tuple(
            {name: cur[name].at[slots].set(new[name]) for name in cur}
            for cur, new in zip(device[key], new_cache[key]))}

    def state_bytes(self) -> int:
        return self.max_slots * self.state_bytes_per_slot

    def seq_bytes(self, ctx_len: int) -> int:
        return self.state_bytes_per_slot      # flat, by construction

    def headroom_bytes(self) -> int:
        return self.free_slots() * self.state_bytes_per_slot

    def migrate(self, ctx_len: int) -> Dict:
        return {"kind": self.kind, "bytes": self.state_bytes_per_slot,
                "units": [{"unit": "slot_state", "count": 1,
                           "bytes_each": self.state_bytes_per_slot}]}


class WindowKV(_DeviceCounters, RecurrentState):
    """The window layers' residency: per slot and window layer ONE ring of
    the last ``window`` positions' keys and values (the model's
    ``init_window_rings``: position ``p`` at place ``p % window``, so after
    the write of ``p`` the ring holds exactly ``p - window + 1 .. p``).
    Bounded, so everything :class:`RecurrentState` says of a per-slot state
    holds: no blocks, ``seq_bytes`` flat in context length, the slot ledger
    released exactly once, one prompt a prefill call.  A prefill hands the
    rows its ring must hold (the last ``window`` VALID positions of the
    prompt, not of the padded bucket: the model gathers them, it knows the
    true length) and the write puts them into the slot.  It also carries
    the model's ``counters`` (:class:`_DeviceCounters`)."""

    kind = "window_kv"
    state_keys = ("window", "counters")
    slot_key = "window"

    def __init__(self, max_slots: int, state_bytes_per_slot: int,
                 counters=()):
        super().__init__(max_slots, state_bytes_per_slot)
        self._init_counters(counters)

    def init_device(self, model) -> Dict:
        return {"window": model.init_window_rings(self.max_slots),
                "counters": self._zero_counters()}

    def prefill_cache(self, cache, n_valid):
        # the model keeps the padded tail out of the rings and the experts
        # and computes the logits of the last valid position only
        return {**cache, "n_valid": n_valid}

    def write_prefill(self, device, new_cache, slots, blocks):
        device = super().write_prefill(device, new_cache, slots, blocks)
        return {**device,
                "counters": device["counters"] + new_cache["counters"]}

    def gauges(self) -> Dict[str, float]:
        return {"cache.window_bytes_per_slot": self.state_bytes_per_slot,
                "cache.window_slots_live": len(self._live)}

    def read_counters(self, gauges=None, **labels) -> None:
        self._publish({**self.gauges(), **(gauges or {})}, labels,
                      in_span=True)


class HybridCache(CacheBackend):
    """Paged KV for the full-attention layers + a per-slot part for the
    others of one hybrid stack: recurrent state for SSD layers
    (:class:`RecurrentState`), bounded rings for window layers
    (:class:`WindowKV`).  Block verbs forward to the paged side, slot verbs
    to the per-slot side; byte accounting sums both; prefix caching is OFF —
    a prefix-cache hit would restore only the paged half of the context (the
    SSD state or the rings for those tokens are not block-addressable),
    which is silently wrong, so the backend refuses rather than degrades.
    Chunked prefill is unsupported for the same reason: a chunk's context is
    its blocks, and the per-slot part has none."""

    kind = "hybrid"
    supports_prefix_cache = False
    _device = None

    def __init__(self, pages: PagedKV, state: RecurrentState):
        self.pages = pages
        self.state = state
        self.prefill_ladder = tuple(
            n for n in pages.prefill_ladder if n in state.prefill_ladder)
        self.state_keys = pages.state_keys + state.state_keys

    # the parts read their own entries of the one pytree
    @property
    def device(self):
        return self._device

    @device.setter
    def device(self, value):
        self._device = self.pages.device = self.state.device = value

    def read_counters(self, gauges=None, **labels) -> None:
        self.state.read_counters(
            gauges={**self.pages.gauges(), **(gauges or {})}, **labels)

    def gauges(self) -> Dict[str, float]:
        return {**self.pages.gauges(), **self.state.gauges()}

    def blocks_for(self, n_tokens: int) -> int:
        return self.pages.blocks_for(n_tokens)

    def available(self) -> int:
        return self.pages.available()

    def alloc(self) -> Optional[int]:
        return self.pages.alloc()

    def release(self, block: int) -> None:
        self.pages.release(block)

    def acquire_slot(self, idx: int) -> None:
        self.state.acquire_slot(idx)

    def release_slot(self, idx: int) -> None:
        self.state.release_slot(idx)

    def init_device(self, model) -> Dict:
        return {**self.pages.init_device(model),
                **self.state.init_device(model)}

    def prefill_cache(self, cache, n_valid):
        return self.state.prefill_cache(
            self.pages.prefill_cache(cache, n_valid), n_valid)

    def write_prefill(self, device, new_cache, slots, blocks):
        return self.state.write_prefill(
            self.pages.write_prefill(device, new_cache, slots, blocks),
            new_cache, slots, blocks)

    def pool_bytes(self) -> int:
        return self.pages.pool_bytes()

    def state_bytes(self) -> int:
        return self.state.state_bytes()

    def seq_bytes(self, ctx_len: int) -> int:
        return self.pages.seq_bytes(ctx_len) + self.state.seq_bytes(ctx_len)

    def headroom_bytes(self) -> int:
        return self.pages.headroom_bytes() + self.state.headroom_bytes()

    def migrate(self, ctx_len: int) -> Dict:
        p = self.pages.migrate(ctx_len)
        s = self.state.migrate(ctx_len)
        return {"kind": self.kind, "bytes": p["bytes"] + s["bytes"],
                "units": p["units"] + s["units"]}


def _paged(spec, num_blocks, block_size, max_slots, prefix_cache):
    return PagedKV(num_blocks, block_size,
                   spec["kv_layers"] * spec["kv_bytes_per_token_layer"],
                   prefix_cache=prefix_cache,
                   write_prefill=spec.get("kv_write_prefill"))


def _recurrent(spec, num_blocks, block_size, max_slots, prefix_cache):
    return RecurrentState(max_slots, spec["state_bytes_per_slot"])


def _latent(spec, num_blocks, block_size, max_slots, prefix_cache):
    return LatentKV(num_blocks, block_size,
                    spec["kv_layers"] * spec["kv_bytes_per_token_layer"],
                    rank=spec["latent_rank"],
                    counters=spec.get("counters", ()),
                    prefix_cache=prefix_cache)


def _window(spec, num_blocks, block_size, max_slots, prefix_cache):
    return WindowKV(max_slots, spec["state_bytes_per_slot"],
                    counters=spec.get("counters", ()))


# layer kind (an entry of ``cache_spec()["kinds"]``) -> the backend that
# caches layers of that kind
KINDS: Dict[str, Callable[..., CacheBackend]] = {
    "attention": _paged, "ssd": _recurrent, "latent": _latent,
    "window": _window}


def make_backend(spec: Dict, num_blocks: int, block_size: int,
                 max_slots: int, prefix_cache: bool = True) -> CacheBackend:
    """Build the backend a model's ``cache_spec()`` calls for.

    All-attention -> :class:`PagedKV` (prefix cache as configured);
    all-SSD -> :class:`RecurrentState`; all-latent -> :class:`LatentKV`;
    attention mixed with SSD or with window layers -> :class:`HybridCache`
    of the pages and that per-slot part (prefix cache forced off — see the
    class docstring)."""
    unknown = set(spec["kinds"]) - set(KINDS)
    if unknown:
        raise ValueError(f"no cache backend for layer kinds {sorted(unknown)}")
    present = [k for k in KINDS if k in spec["kinds"]]
    parts = [KINDS[k](spec, num_blocks, block_size, max_slots,
                      prefix_cache and len(present) == 1) for k in present]
    return parts[0] if len(parts) == 1 else HybridCache(*parts)
