"""What ``serving.Engine`` tells a client and an operator about time: the
first token's arrival on the host (``serve.ttft_ms``, ``RequestOutput.
ttft_s``), the wait before the first prefill (``serve.queue_wait_ms``), the
tokens a live request holds (``Engine.token_counts()``), ``warmup()``'s own
gauges."""
import time
import types

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import obs, serving
from paddle_tpu.models import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.serving import Engine, GenRequest

from benchmarks.adapters import engine_tokens


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    return LlamaForCausalLM(llama_tiny_config())


@pytest.fixture(autouse=True)
def _clean_metrics():
    obs.reset_metrics()
    yield
    obs.reset_metrics()


def _engine(model, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("num_blocks", 16)
    kw.setdefault("block_size", 128)
    kw.setdefault("prefill_buckets", (128, 256))
    kw.setdefault("decode_chunk", 4)
    return Engine(model, **kw)


def _requests(cfg, lens, max_new, seed=0):
    rng = np.random.default_rng(seed)
    return [GenRequest(prompt_ids=rng.integers(
        1, cfg.vocab_size, size=n).astype(np.int32), max_new_tokens=m)
        for n, m in zip(lens, max_new)]


def _private_counts(engine):
    """The adapter's reading before the engine offered one."""
    return {s.req.request_id: len(s.req.prior_output) + len(s.req._out_vals)
            for s in engine._slots if s.req is not None}


def _hist(name):
    return obs.registry().snapshot().get(name, {"count": 0, "sum": 0.0})


def _drive(eng, reqs):
    """Step to the end as the benchmark does; per request the outside times
    of the ``step()`` returns around its first token, and every output."""
    sent = {eng.add_request(r): time.perf_counter() for r in reqs}
    before, after, outs = {}, {}, {}
    t_prev = time.perf_counter()
    while eng.has_work():
        n_before = _hist("serve.ttft_ms")["count"]
        finished = eng.step()
        t = time.perf_counter()
        counts = eng.token_counts()
        assert counts == _private_counts(eng)
        assert counts == engine_tokens.token_counts(eng)
        for o in finished:
            counts[o.request_id] = len(o.output_ids)
            outs[o.request_id] = o
        fresh = [rid for rid, c in counts.items()
                 if c > 0 and rid not in after]
        for rid in fresh:
            before[rid], after[rid] = t_prev, t
        # observed at the step whose return first shows the token: as many
        # observations as requests that got their first token in it
        assert _hist("serve.ttft_ms")["count"] - n_before == len(fresh)
        t_prev = t
    return sent, before, after, outs


def test_ttft_is_taken_once_a_request_when_the_token_is_on_the_host(model):
    eng = _engine(model)
    reqs = _requests(model.config, (20, 45, 33, 150), (6, 9, 5, 7))
    sent, before, after, outs = _drive(eng, reqs)
    assert len(outs) == 4
    snap = _hist("serve.ttft_ms")
    assert snap["count"] == 4
    for rid, o in outs.items():
        # the engine's own seconds lie between the outside clocks of the
        # step() returns around the token, counted from add_request
        assert before[rid] - sent[rid] <= o.ttft_s <= after[rid] - sent[rid]
    assert snap["sum"] == pytest.approx(
        1e3 * sum(o.ttft_s for o in outs.values()))


def test_ttft_is_not_observed_at_dispatch(model):
    eng = _engine(model)
    (req,) = _requests(model.config, (40,), (6,))
    eng.add_request(req)
    eng._round(streaming=False)     # admit, prefill, one chunk: no sync
    assert _hist("serve.queue_wait_ms")["count"] == 1
    assert _hist("serve.ttft_ms")["count"] == 0 and req._first_t == 0.0
    eng._sync_pending()
    assert _hist("serve.ttft_ms")["count"] == 1 and req._first_t > 0.0


def test_queue_wait_and_first_token_time_add_up_to_ttft(model):
    eng = _engine(model, max_batch=1)    # the second request waits for a slot
    reqs = _requests(model.config, (30, 60), (5, 5), seed=3)
    _, _, _, outs = _drive(eng, reqs)
    wait = _hist("serve.queue_wait_ms")
    assert wait["count"] == 2
    for r in reqs:
        o = outs[r.request_id]
        assert r._queued_t < r._dispatch_t < r._first_t
        assert ((r._dispatch_t - r._queued_t) + (r._first_t - r._dispatch_t)
                == pytest.approx(o.ttft_s))
    assert wait["sum"] == pytest.approx(
        1e3 * sum(r._dispatch_t - r._queued_t for r in reqs))
    # the one that waited for the slot waited at least the first one's run
    assert (reqs[1]._dispatch_t - reqs[1]._queued_t
            > reqs[0]._first_t - reqs[0]._queued_t)


def test_ttft_once_through_an_eviction(model):
    """A preempted request is prefilled again; its first token was on the
    host before that and is not counted twice."""
    eng = _engine(model, num_blocks=4)
    reqs = _requests(model.config, (120, 120), (140, 140), seed=5)
    _, _, _, outs = _drive(eng, reqs)
    assert eng.stats["evictions"] >= 1
    assert _hist("serve.ttft_ms")["count"] == 2
    assert _hist("serve.queue_wait_ms")["count"] == 2
    assert all(o.ttft_s > 0 and len(o.output_ids) == 140
               for o in outs.values())


def test_warmup_reports_its_seconds_and_programs(model):
    eng = _engine(model, prefill_buckets=(128,), prefix_cache=False)
    t0 = time.perf_counter()
    eng.warmup()
    took = time.perf_counter() - t0
    snap = obs.registry().snapshot()
    # decode chunks 1, 2, 4 and the one bucket at batch 1 and 2
    assert snap["serve.warmup_programs"]["value"] == 5
    assert 0 < snap["serve.warmup_s"]["value"] <= took


def test_decode_gaps_are_bounded(model):
    eng = _engine(model)
    assert eng._decode_gaps.maxlen is not None
    eng._decode_gaps.extend(range(2 * eng._decode_gaps.maxlen))
    assert len(eng._decode_gaps) == eng._decode_gaps.maxlen
    assert sorted(eng._decode_gaps)[0] == eng._decode_gaps.maxlen


def test_ttft_of_a_mid_stream_arrival_is_a_short_chunk_and_its_prefill(
        model, monkeypatch):
    """The engine's clock counts what it has dispatched (one a decode step,
    one a prefill), so ``ttft_s`` reads in steps: a request that arrives
    while another streams and a slot is free waits for its prefill and one
    chunk of ``K_SHORT``, not of ``decode_chunk``."""
    eng = _engine(model, max_batch=3, decode_chunk=32)
    monkeypatch.setattr(serving, "time", types.SimpleNamespace(
        perf_counter=lambda: 1.0 + eng.stats["decode_steps"]
        + eng.stats["prefills"], time=time.time))
    first, late = _requests(model.config, (40, 60), (120, 40), seed=7)
    eng.add_request(first)
    eng.step()
    eng.step()                           # the first request is mid-stream
    assert eng.token_counts() == {first.request_id: 1 + 2 * serving.K_SHORT}
    rid = eng.add_request(late)
    eng.step()                           # its prefill and one short chunk
    assert eng.token_counts()[rid] == 1 + serving.K_SHORT
    outs = {}
    while eng.has_work():
        outs.update((o.request_id, o) for o in eng.step())
    assert late._dispatch_t - late._queued_t == 0       # admitted at once
    assert outs[rid].ttft_s == 1 + serving.K_SHORT
    assert _hist("serve.ttft_ms")["sum"] == pytest.approx(
        1e3 * sum(o.ttft_s for o in outs.values()))
