"""What ``serving.Engine`` counts of the work it dispatches: each decode
chunk's ``k x max_batch`` slot-steps by use (``serve.decode_slot_steps``:
kept, tail, prefilling, empty; cut after an eos) and each prefill's rows
(``serve.prefill_rows``: prompt, pad), and the same numbers as args of the
``serve.dispatch`` / ``serve.prefill`` / ``serve.prefill-chunk`` spans,
which the benchmark's ``decode_slot_use_share``, ``decode_slot_empty_share``
and ``prefill_row_use_share`` read."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import obs
from paddle_tpu.models import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.serving import Engine, GenRequest

SLOT_USES = ("kept", "tail", "prefilling", "empty", "cut")

# name: (prompt lengths, max_new_tokens, Engine arguments, streaming)
RUNS = {
    # three requests for two slots: empty slots while the queue drains,
    # budgets that end inside a chunk
    "step": ((20, 45, 33), (6, 19, 11), {}, True),
    "to_completion": ((20, 45, 33), (6, 19, 11), {}, False),
    # two buckets in one admission round, a prefill call of two each
    "buckets": ((20, 200, 45, 150), (6, 4, 7, 2),
                {"max_batch": 4, "num_blocks": 24}, False),
    # a long prompt prefills in chunks while the other slot decodes
    "chunked": ((16, 230), (16, 6),
                {"prefill_chunk": 128, "decode_chunk": 4}, True),
}


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
    kw.setdefault("decode_chunk", 8)
    return Engine(model, **kw)


def _counts(family, uses, **labels):
    snap = obs.registry().snapshot()
    key = ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
    out = {}
    for u in uses:
        lbl = ",".join(sorted([f"use={u}"] + ([key] if key else [])))
        out[u] = snap.get(f"{family}{{{lbl}}}", {"value": 0.0})["value"]
    return out


def _slot_steps(**labels):
    return _counts("serve.decode_slot_steps", SLOT_USES, **labels)


def _prefill_rows(**labels):
    return _counts("serve.prefill_rows", ("prompt", "pad"), **labels)


def _watch(eng):
    """Per decode chunk ``(k, what it returned, the counters' increments)``;
    per prefill call the rows its program runs (``n x bucket`` or the
    chunk's bucket), taken from the program it asks for."""
    chunks, rows = [], []
    dispatch, get_prefill, get_chunk = (eng._dispatch_decode,
                                        eng._get_prefill_fn,
                                        eng._get_chunk_fn)

    def dispatch_decode(k):
        before = _slot_steps()
        got = dispatch(k)
        after = _slot_steps()
        chunks.append((k, got, {u: after[u] - before[u] for u in after}))
        return got

    def prefill_fn(Pb, n):
        rows.append(Pb * n)
        return get_prefill(Pb, n)

    def chunk_fn(Cb, final):
        rows.append(Cb)
        return get_chunk(Cb, final)

    eng._dispatch_decode = dispatch_decode
    eng._get_prefill_fn = prefill_fn
    eng._get_chunk_fn = chunk_fn
    return chunks, rows


def _requests(cfg, lens, max_new, seed=0, **kw):
    rng = np.random.default_rng(seed)
    return [GenRequest(prompt_ids=rng.integers(
        1, cfg.vocab_size, size=n).astype(np.int32), max_new_tokens=m,
        request_id=f"r{i}", **kw)
        for i, (n, m) in enumerate(zip(lens, max_new))]


def _serve(eng, reqs, streaming):
    if not streaming:
        for r in reqs:
            eng.add_request(r)
        return {o.request_id: o.output_ids for o in eng.run_to_completion()}
    outs, pending = {}, list(reqs)
    while eng.has_work() or pending:
        if pending:                    # one arrival a round, mid-decode
            eng.add_request(pending.pop(0))
        for o in eng.step():
            outs[o.request_id] = o.output_ids
    return outs


@pytest.mark.parametrize("name", sorted(RUNS))
def test_each_chunk_splits_its_slot_steps_exactly(model, name):
    lens, max_new, kw, streaming = RUNS[name]
    eng = _engine(model, **kw)
    chunks, _ = _watch(eng)
    outs = _serve(eng, _requests(model.config, lens, max_new), streaming)
    assert chunks
    for k, (_staged, live, kept), inc in chunks:
        assert sum(inc[u] for u in SLOT_USES[:4]) == k * eng.max_batch
        assert inc["kept"] == kept and inc["cut"] == 0
        assert inc["tail"] + inc["kept"] == k * live
        assert 0 <= inc["tail"] < k * max(live, 1)
    total = _slot_steps()
    # no eos: every kept slot-step is a token a request received, beside
    # the first token each prefill samples
    assert total["kept"] == sum(map(len, outs.values())) - len(outs)
    assert total["kept"] == eng.stats["generated_tokens"] - len(outs)
    assert sum(total.values()) == eng.max_batch * eng.stats["decode_steps"]
    if name == "chunked":
        assert total["prefilling"] > 0
    if name in ("step", "to_completion"):
        assert total["tail"] > 0
    if name == "step":
        assert total["empty"] > 0


@pytest.mark.parametrize("eos_at, kept", [(3, 2), (0, 0)])
def test_an_eos_moves_its_cut_cells_to_cut(model, eos_at, kept):
    """The eos at generated position ``eos_at`` (0: the prefill's own
    token): what the chunk ran past it is discarded when the tokens are
    read, and moves from ``kept`` to ``cut``."""
    cfg = model.config
    ref = _engine(model)
    ref.add_request(_requests(cfg, (24,), (32,), seed=5)[0])
    (out,) = ref.run_to_completion()
    eos = out.output_ids[eos_at]
    obs.reset_metrics()
    eng = _engine(model, decode_chunk=16)
    chunks, _ = _watch(eng)
    (req,) = _requests(cfg, (24,), (32,), seed=5, eos_token_id=eos)
    eng.add_request(req)
    (stopped,) = eng.run_to_completion()
    assert stopped.finish_reason == "stop"
    assert stopped.output_ids == out.output_ids[:eos_at]
    dispatched = sum(got[2] for _k, got, _inc in chunks)
    total = _slot_steps()
    assert total["kept"] == kept == max(len(stopped.output_ids) - 1, 0)
    assert total["cut"] == dispatched - kept > 0
    assert eng.stats["generated_tokens"] == len(stopped.output_ids)


@pytest.mark.parametrize("name", sorted(RUNS))
def test_prefill_rows_are_prompt_or_padding(model, name):
    lens, max_new, kw, streaming = RUNS[name]
    eng = _engine(model, **kw)
    _, rows = _watch(eng)
    _serve(eng, _requests(model.config, lens, max_new), streaming)
    got = _prefill_rows()
    assert got["prompt"] + got["pad"] == sum(rows)
    assert got["prompt"] == sum(lens)
    assert got["pad"] > 0


def test_counters_follow_a_reset_and_the_replica_label(model):
    """The engine holds its counters between chunks: after a registry reset
    or once a router labels it, it counts into the new instruments."""
    cfg = model.config
    eng = _engine(model)
    _serve(eng, _requests(cfg, (20,), (5,)), False)
    first = _slot_steps()["kept"]
    assert first == 4
    obs.reset_metrics()
    _serve(eng, _requests(cfg, (30,), (3,), seed=1), False)
    assert _slot_steps()["kept"] == 2
    eng.obs_replica = 3
    _serve(eng, _requests(cfg, (30,), (6,), seed=2), False)
    assert _slot_steps()["kept"] == 2
    assert _slot_steps(replica=3)["kept"] == 5
    assert _prefill_rows(replica=3) == {"prompt": 30, "pad": 98}


@pytest.fixture
def profiler(tmp_path):
    import jax

    class _P:
        on = False

        def start(self):
            jax.profiler.start_trace(str(tmp_path))
            self.on = True

        def stop(self):
            jax.profiler.stop_trace()
            self.on = False

    p = _P()
    yield p
    if p.on:
        jax.profiler.stop_trace()
    obs.profiled_events()          # the first call after a session ends it


@pytest.mark.parametrize("name", ["buckets", "chunked"])
def test_spans_carry_the_counts_and_change_no_token(model, profiler, name):
    """Under a profiler session (what the benchmark's ``--trace 1`` opens)
    the spans carry ``kept`` / ``width`` / ``tokens``; their sums are the
    counters' and the tokens are those of a run with no session."""
    lens, max_new, kw, streaming = RUNS[name]

    def run():
        eng = _engine(model, **kw)
        return eng, _serve(eng, _requests(model.config, lens, max_new),
                           streaming)

    _, outs_off = run()
    obs.reset_metrics()
    profiler.start()
    eng, outs_on = run()
    profiler.stop()
    assert outs_on == outs_off, "a profiler session changed the tokens"
    evs = [e for e in obs.profiled_events() if e["ph"] == "X"]
    slot, rows = _slot_steps(), _prefill_rows()

    dispatch = [e["args"] for e in evs if e["name"] == "serve.dispatch"]
    assert dispatch and all(a["width"] == eng.max_batch for a in dispatch)
    assert sum(a["kept"] for a in dispatch) == slot["kept"]
    assert sum(a["k"] * (a["width"] - a["live"]) for a in dispatch) == (
        slot["empty"] + slot["prefilling"])
    assert sum(a["k"] * a["width"] for a in dispatch) == sum(slot.values())

    prefill = [e["args"] for e in evs if e["name"] == "serve.prefill"]
    chunk = [e["args"] for e in evs if e["name"] == "serve.prefill-chunk"]
    assert prefill and all("tokens" in a for a in prefill + chunk)
    assert bool(chunk) == (name == "chunked")
    assert sum(a["tokens"] for a in prefill + chunk) == rows["prompt"]
    assert (sum(a["bucket"] * a["n"] for a in prefill)
            + sum(a["bucket"] for a in chunk)) == rows["prompt"] + rows["pad"]
