"""CacheBackend conformance: the seam between the serving engine and its
cache policy.  Both concrete backends (paged KV blocks, recurrent state
slots) plus the hybrid composition must honor the same ledger discipline —
exactly-once release, pressure-driven reclaim, honest byte accounting —
and ``make_backend`` must pick the right policy from a model's
``cache_spec()``.  These tests are pure host-side bookkeeping (no jit),
but the last: a cache kind the tree has never heard of, defined here, is
served through the engine by adding one entry to ``KINDS``."""

import types

import numpy as np
import pytest

from paddle_tpu.serving import cache_backend
from paddle_tpu.serving.cache_backend import (
    CacheBackend, HybridCache, PagedKV, RecurrentState, WindowKV,
    make_backend)


def _spec(kinds, state=0, kv_layers=0, kv_bpt=0):
    return {"kinds": tuple(kinds), "state_bytes_per_slot": state,
            "kv_layers": kv_layers, "kv_bytes_per_token_layer": kv_bpt}


# ---------------------------------------------------------------- PagedKV --

class TestPagedKV:
    def test_block_zero_is_trash(self):
        be = PagedKV(num_blocks=8, block_size=16, bytes_per_token=4)
        claimed = [be.alloc() for _ in range(7)]
        assert 0 not in claimed and be.alloc() is None

    def test_blocks_for_rounds_up(self):
        be = PagedKV(8, 16, 4)
        assert [be.blocks_for(n) for n in (1, 16, 17, 32)] == [1, 1, 2, 2]

    def test_alloc_release_roundtrip(self):
        be = PagedKV(4, 16, 4)
        b = be.alloc()
        assert be._ref[b] == 1
        be.release(b)
        assert b in be._free and b not in be._ref

    def test_release_is_exactly_once(self):
        be = PagedKV(4, 16, 4)
        b = be.alloc()
        be.release(b)
        with pytest.raises(RuntimeError, match="double release"):
            be.release(b)

    def test_shared_block_release_decrements(self):
        be = PagedKV(4, 16, 4)
        b = be.alloc()
        be.register([b"h0"], [b])
        assert be.gather(b"h0") == b and be._ref[b] == 2
        be.release(b)
        assert be._ref[b] == 1            # still owned by the other slot
        be.release(b)
        assert b not in be._ref and be._lru[b"h0"] == b  # parks, registered

    def test_gather_revives_parked_block(self):
        be = PagedKV(4, 16, 4)
        b = be.alloc()
        be.register([b"h0"], [b])
        be.release(b)                     # ref 0 -> parks in LRU
        assert be.gather(b"h0") == b and be._ref[b] == 1
        assert b"h0" not in be._lru

    def test_pressure_reclaims_oldest_cached(self):
        be = PagedKV(4, 16, 4)            # 3 usable blocks
        blocks = [be.alloc() for _ in range(3)]
        be.register([b"h0", b"h1", b"h2"], blocks)
        for b in blocks:
            be.release(b)                 # all parked, oldest first = h0
        fresh = be.alloc()
        assert fresh == blocks[0]         # LRU victim, deregistered
        assert b"h0" not in be._index and be.lookup_chain([b"h1"]) == 1

    def test_lookup_chain_longest_consecutive(self):
        be = PagedKV(8, 16, 4)
        bs = [be.alloc() for _ in range(3)]
        be.register([b"a", b"b", b"c"], bs)
        assert be.lookup_chain([b"a", b"b", b"x", b"c"]) == 2
        assert be.lookup_chain([b"x"]) == 0

    def test_prefix_cache_off_ignores_register(self):
        be = PagedKV(8, 16, 4, prefix_cache=False)
        b = be.alloc()
        be.register([b"h"], [b])
        assert be._index == {} and not be.supports_prefix_cache

    def test_byte_accounting_linear(self):
        be = PagedKV(8, 16, bytes_per_token=4)
        assert be.block_bytes == 64
        assert be.pool_bytes() == 8 * 64
        assert be.seq_bytes(1) == 64 and be.seq_bytes(33) == 3 * 64
        assert be.headroom_bytes() == be.available() * 64
        m = be.migrate(33)
        assert m["bytes"] == 3 * 64
        assert m["units"] == [{"unit": "kv_block", "count": 3,
                               "bytes_each": 64}]
        assert be.plan_bytes() == {"kv_pool_bytes": 512, "state_bytes": 0}


# --------------------------------------------------------- RecurrentState --

class TestRecurrentState:
    def test_blockless(self):
        be = RecurrentState(4, 1000)
        assert be.blocks_for(10_000) == 0 and be.available() == 0
        assert be.alloc() is None and be.append() is None
        assert not be.supports_prefix_cache and be.gather(b"h") is None

    def test_slot_ledger_exactly_once(self):
        be = RecurrentState(2, 1000)
        be.acquire_slot(0)
        with pytest.raises(RuntimeError, match="already live"):
            be.acquire_slot(0)
        be.release_slot(0)
        with pytest.raises(RuntimeError, match="double release"):
            be.release_slot(0)

    def test_flat_seq_bytes(self):
        be = RecurrentState(4, 1000)
        assert be.seq_bytes(1) == be.seq_bytes(65536) == 1000  # THE point
        assert be.state_bytes() == 4000
        be.acquire_slot(0)
        assert be.headroom_bytes() == 3000
        m = be.migrate(65536)
        assert m["bytes"] == 1000
        assert m["units"] == [{"unit": "slot_state", "count": 1,
                               "bytes_each": 1000}]


# ------------------------------------------------------------ HybridCache --

class TestHybridCache:
    def _make(self):
        return HybridCache(PagedKV(4, 16, 4), RecurrentState(2, 1000))

    def test_blocks_ride_paged_side(self):
        be = self._make()
        b = be.alloc()
        assert be.pages._ref[b] == 1 and be.blocks_for(17) == 2
        be.release(b)
        with pytest.raises(RuntimeError, match="double release"):
            be.release(b)

    def test_prefix_cache_structurally_off(self):
        # a hit would restore only the attention half of the context
        assert not self._make().supports_prefix_cache

    def test_bytes_sum_both_sides(self):
        be = self._make()
        assert be.pool_bytes() == 4 * 64
        assert be.state_bytes() == 2000
        assert be.seq_bytes(32) == 2 * 64 + 1000
        assert be.headroom_bytes() == 3 * 64 + 2000
        m = be.migrate(32)
        assert m["bytes"] == 2 * 64 + 1000
        assert {u["unit"] for u in m["units"]} == {"kv_block", "slot_state"}


# --------------------------------------------------------------- WindowKV --

class _RingModel:
    """What ``WindowKV`` asks of a model: one ring a window layer."""

    def init_window_rings(self, max_slots):
        import jax.numpy as jnp

        return tuple({"k": jnp.zeros((max_slots, 2, 4, 6)),
                      "v": jnp.zeros((max_slots, 2, 4, 3))}
                     for _ in range(2))

    def init_paged_pools(self, num_blocks, block_size):
        import jax.numpy as jnp

        pool = jnp.zeros((num_blocks, 2, block_size, 3))
        return (pool,), (pool,)


class TestWindowKV:
    def test_slot_ledger_exactly_once(self):
        be = WindowKV(2, 1000, counters=("a", "b"))
        be.acquire_slot(1)
        with pytest.raises(RuntimeError, match="already live"):
            be.acquire_slot(1)
        be.release_slot(1)
        with pytest.raises(RuntimeError, match="double release"):
            be.release_slot(1)

    def test_bounded_whatever_the_context(self):
        be = WindowKV(4, 1000)
        assert be.seq_bytes(1) == be.seq_bytes(1 << 20) == 1000
        assert be.state_bytes() == 4000 and be.pool_bytes() == 0
        assert be.blocks_for(1 << 20) == 0 and be.alloc() is None
        assert be.prefill_ladder == (1,)
        assert not be.supports_prefix_cache
        assert not be.supports_chunked_prefill
        be.acquire_slot(0)
        assert be.headroom_bytes() == 3000
        assert be.gauges() == {"cache.window_bytes_per_slot": 1000,
                               "cache.window_slots_live": 1}

    def test_prefill_write_sets_the_slots_rings_and_adds_the_counts(self):
        import jax.numpy as jnp

        be = WindowKV(3, 1000, counters=("a", "b"))
        dev = be.init_device(_RingModel())
        assert sorted(dev) == ["counters", "window"] == sorted(be.state_keys)
        cache = be.prefill_cache({"window": ()}, jnp.asarray([7]))
        assert list(cache["n_valid"]) == [7]
        new = {"window": tuple({"k": jnp.full((1, 2, 4, 6), i + 1.0),
                                "v": jnp.full((1, 2, 4, 3), i + 5.0)}
                               for i in range(2)),
               "counters": jnp.asarray([3, 4], jnp.int32)}
        dev = be.write_prefill(dev, new, jnp.asarray([2]), None)
        dev = be.write_prefill(dev, new, jnp.asarray([0]), None)
        k1 = np.asarray(dev["window"][1]["k"])
        assert (k1[[0, 2]] == 2.0).all() and not k1[1].any()
        assert list(np.asarray(dev["counters"])) == [6, 8]

    def test_state_keys_round_trip_through_a_step(self):
        """``step_cache`` hands the model every array under its key beside
        the table and the lengths; ``take_device`` takes the same keys back
        out of what the forward returns; a composed cache's parts read
        their own entries of the one pytree."""
        import jax.numpy as jnp

        be = make_backend(_spec(["attention", "window", "window"],
                                state=1000, kv_layers=1, kv_bpt=8)
                          | {"counters": ("a",)},
                          num_blocks=4, block_size=16, max_slots=3)
        be.device = be.init_device(_RingModel())
        assert be.state_keys == ("k", "v", "window", "counters")
        assert be.state.device is be.device and be.pages.device is be.device
        step = be.step_cache(be.device, jnp.zeros((3, 2), jnp.int32),
                             jnp.zeros((3,), jnp.int32))
        assert sorted(step) == sorted(be.state_keys + ("block_table",
                                                       "lengths"))
        back = be.take_device({**step, "lengths": step["lengths"] + 1})
        assert sorted(back) == sorted(be.state_keys)
        assert all(back[k] is be.device[k] for k in be.state_keys)


# ------------------------------------------------------------ make_backend --

class TestMakeBackend:
    def test_attention_and_window_is_pages_and_rings(self):
        def write(*a):
            return "the model's own layout"

        spec = _spec(["attention", "window", "window", "window"], state=1000,
                     kv_layers=1, kv_bpt=8) | {"counters": ("a", "b"),
                                               "kv_write_prefill": write}
        be = make_backend(spec, num_blocks=8, block_size=16, max_slots=4,
                          prefix_cache=True)
        assert isinstance(be, HybridCache)
        assert type(be.pages) is PagedKV and type(be.state) is WindowKV
        assert be.pages._write_prefill is write
        assert be.state.counters == ("a", "b") and be.state.max_slots == 4
        # a hit would restore the full layers' half only; a chunk's context
        # is its blocks and a ring has none
        assert not be.supports_prefix_cache
        assert not be.pages.supports_prefix_cache
        assert not be.supports_chunked_prefill
        assert be.prefill_ladder == (1,)
        assert be.seq_bytes(16) == 16 * 8 + 1000
        assert be.seq_bytes(1600) - be.seq_bytes(16) == 99 * 16 * 8
        be.acquire_slot(0)
        b = be.alloc()
        assert be.gauges() == {
            "cache.kv_bytes_per_token": 8, "cache.kv_blocks_live": 1,
            "cache.window_bytes_per_slot": 1000, "cache.window_slots_live": 1}
        be.release(b)
        be.release_slot(0)
        with pytest.raises(RuntimeError, match="double release"):
            be.release_slot(0)

    def test_all_attention_is_paged(self):
        be = make_backend(_spec(["attention"] * 2, kv_layers=2, kv_bpt=8),
                          num_blocks=8, block_size=16, max_slots=4)
        assert isinstance(be, PagedKV) and be.supports_prefix_cache
        assert be.bytes_per_token == 16

    def test_all_ssd_is_recurrent(self):
        be = make_backend(_spec(["ssd"] * 2, state=1000),
                          num_blocks=8, block_size=16, max_slots=4)
        assert isinstance(be, RecurrentState)
        assert be.state_bytes_per_slot == 1000 and be.max_slots == 4

    def test_mixed_is_hybrid_prefix_forced_off(self):
        be = make_backend(_spec(["ssd", "attention"], state=1000,
                                kv_layers=1, kv_bpt=8),
                          num_blocks=8, block_size=16, max_slots=4,
                          prefix_cache=True)
        assert isinstance(be, HybridCache)
        assert not be.supports_prefix_cache
        assert not be.pages.supports_prefix_cache

    def test_abstract_base_refuses_release(self):
        with pytest.raises(RuntimeError, match="blockless"):
            CacheBackend().release(3)

    def test_unknown_kind_is_refused(self):
        with pytest.raises(ValueError, match="no cache backend"):
            make_backend(_spec(["attention", "conv"], kv_layers=1, kv_bpt=8),
                         num_blocks=8, block_size=16, max_slots=4)


# ----------------------------------------------------------- a third kind --

class RunningSum(CacheBackend):
    """A slot's cache is ONE int32: the sum of every token it has seen.
    Neither pages nor the SSD state — no blocks, nothing to hash, and a
    prefill call takes a whole rung of prompts at once."""

    kind = "running_sum"
    state_keys = ("sum",)

    def __init__(self, max_slots):
        self.max_slots = max_slots
        self.live = set()

    def acquire_slot(self, idx):
        assert idx not in self.live
        self.live.add(idx)

    def release_slot(self, idx):
        self.live.remove(idx)

    def init_device(self, model):
        import jax.numpy as jnp

        return {"sum": jnp.zeros((self.max_slots,), jnp.int32)}

    def write_prefill(self, device, new_cache, slots, blocks):
        return {"sum": device["sum"].at[slots].set(new_cache["sum"])}

    def state_bytes(self):
        return 4 * self.max_slots


def _toy_model(vocab=31):
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.framework.dispatch import apply_op
    from paddle_tpu.framework.tensor import Tensor
    from paddle_tpu.nn.initializer import Normal
    from paddle_tpu.nn.layers import Layer

    def raw(x):
        return x._data if isinstance(x, Tensor) else x

    class SumLM(Layer):
        """Next-token logits are row ``(sum of the tokens so far) % vocab``
        of one table."""

        def __init__(self):
            super().__init__()
            self.config = types.SimpleNamespace(
                vocab_size=vocab, hidden_size=8, num_attention_heads=1,
                dtype="float32")
            self.table = self.create_parameter(
                [vocab, vocab], dtype="float32",
                default_initializer=Normal(0.0, 1.0))

        def cache_spec(self):
            return {"kinds": ("running_sum",), "state_bytes_per_slot": 4,
                    "kv_layers": 0, "kv_bytes_per_token_layer": 0}

        def init_cache(self, batch_size, max_len, dtype=None):
            return {"sum": jnp.zeros((batch_size,), jnp.int32)}

        def forward(self, input_ids, position_ids=None, cache=None):
            ids = raw(input_ids)
            acc = raw(cache["sum"])
            if "block_table" in cache:       # decode: one token a live slot
                lengths = raw(cache["lengths"])
                live = lengths > 0
                sums = jnp.where(live, acc + ids[:, 0], acc)[:, None]
                new_cache = {"sum": sums[:, 0], "lengths": lengths + live}
            else:                            # prefill: pad tokens are 0
                sums = acc[:, None] + jnp.cumsum(ids, axis=1)
                new_cache = {"sum": sums[:, -1]}
            logits = apply_op("toy_head", lambda t: t[sums % vocab],
                              (self.table,), {})
            return logits, new_cache

    paddle.seed(0)
    return SumLM()


def test_third_cache_kind_serves_through_engine(monkeypatch):
    """A new kind of cache is one class and one ``KINDS`` entry: the engine
    serves it token for token like its own step-by-step reference, with
    batched prefills, slot reuse and a mid-run admission, and nothing in
    ``serving/__init__.py`` is patched."""
    from paddle_tpu.serving import Engine, GenRequest

    monkeypatch.setitem(
        cache_backend.KINDS, "running_sum",
        lambda spec, num_blocks, block_size, max_slots, prefix_cache:
            RunningSum(max_slots))
    model = _toy_model()
    vocab = model.config.vocab_size
    table = np.asarray(model.table._data)

    def reference(prompt, n_new):
        acc, out = int(prompt.sum()), []
        for _ in range(n_new):
            out.append(int(np.argmax(table[acc % vocab])))
            acc += out[-1]
        return out

    eng = Engine(model, max_batch=4, num_blocks=4, block_size=16,
                 prefill_buckets=(16, 32), decode_chunk=4)
    assert isinstance(eng.backend, RunningSum)
    assert not eng.prefix_cache and eng.prefill_chunk is None
    rng = np.random.default_rng(0)
    lengths = (5, 9, 12, 3, 20, 27, 7)       # 7 requests on 4 slots
    news = (6, 3, 9, 1, 5, 8, 4)
    prompts = [rng.integers(1, vocab, size=n).astype(np.int32)
               for n in lengths]
    for i, (p, n) in enumerate(zip(prompts, news)):
        eng.add_request(GenRequest(prompt_ids=p, max_new_tokens=n,
                                   request_id=f"r{i}"))
    outs = {o.request_id: list(o.output_ids)
            for o in eng.run_to_completion()}
    for i, (p, n) in enumerate(zip(prompts, news)):
        assert outs[f"r{i}"] == reference(p, n), f"r{i}"
    assert eng.stats["prefills"] == 7 and eng.stats["evictions"] == 0
    assert (16, 4) in eng._prefill_fns       # the first four went as one call
    assert eng.backend.live == set()
    assert eng.memory_plan()["state_bytes"] == 16
    assert eng.backend.device["sum"].shape == (4,)
