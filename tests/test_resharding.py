"""Property/fuzz suite for the unified resharding engine (PR 9 tentpole).

Three contracts, over the same spec catalog as ``test_spec_fuzz.py``:

1. **Peak bound** — for every (src spec, dst spec, dst mesh) the planner's
   modeled per-step peak memory stays within ``2 * max(src_shard,
   dst_shard)`` and the plan reports ``bounded`` (the all-gather last
   resort is the only thing allowed to break it, and must say so).
2. **Collective subset** — the plan's emitted collective kinds are a
   SUBSET of ``spec_algebra.expected_collectives`` for the pair: the
   planner never moves data with a collective the static analyzer would
   flag as unintended.
3. **Bit identity** — executing the plan yields the same values under the
   destination layout, and the return trip restores the source bitwise.

A seeded sample executes in tier-1; the exhaustive execution sweep is
``slow``.  The file-backed variant and the launch/env wiring are unit
tested at the bottom.
"""

import itertools
import json
import os
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.analysis.spec_algebra import expected_collectives
from paddle_tpu.distributed.resharding import (
    ChunkRef, execute, plan_file_reshard, plan_reshard, read_shard, reshard)

_ENTRIES = [None, "x", "y", ("x", "y"), ("y", "x")]


def _axes_of(e):
    if e is None:
        return set()
    return {e} if isinstance(e, str) else set(e)


_SPECS = [P(a, b) for a, b in itertools.product(_ENTRIES, _ENTRIES)
          if not (_axes_of(a) & _axes_of(b))]

needs_8 = pytest.mark.skipif(len(jax.devices()) < 8,
                             reason="needs 8 (fake) CPU devices")


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    return Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("x", "y"))


@pytest.fixture(scope="module")
def shrunk_meshes(mesh):
    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    return [Mesh(devs[:, :2].reshape(2, 2), ("x", "y")),
            Mesh(devs[:, :1].reshape(2, 1), ("x", "y"))]


SHAPE = (16, 16)


# ---------------------------------------------------------------------------
# 1+2. plan-level properties: full catalog, pure python (no compiles)


def test_plan_peak_bound_and_collective_subset_full_catalog(mesh,
                                                            shrunk_meshes):
    bad = []
    for src, dst in itertools.product(_SPECS, _SPECS):
        for dmesh in [mesh] + shrunk_meshes:
            plan = plan_reshard(mesh, src, dmesh, dst, SHAPE, "float32")
            denom = max(plan.src_shard_bytes, plan.dst_shard_bytes)
            if not plan.bounded or plan.peak_bytes > plan.bound_bytes \
                    or plan.peak_bytes > 2 * denom:
                bad.append(("peak", src, dst, tuple(dmesh.shape.values()),
                            plan.summary()))
            extra = plan.collective_kinds() - expected_collectives(
                [(src, dst, 2)], mesh)
            if extra:
                bad.append(("kinds", src, dst,
                            tuple(dmesh.shape.values()), sorted(extra)))
    assert not bad, "\n".join(map(str, bad[:20]))


def test_gather_fallback_is_flagged_unbounded(mesh, shrunk_meshes):
    # both END layouts are realizable (6 divides by x=2 and by the small
    # mesh's y=2) but no candidate admits a bounded collective program
    # (dim 0 = 6 is not divisible by the intermediate x*y tiling): the
    # planner must fall back to gather-then-slice AND say so
    plan = plan_reshard(mesh, P("x"), shrunk_meshes[0], P("y"), (6, 8),
                        "float32")
    assert not plan.bounded
    assert "all-gather" in plan.collective_kinds()
    assert plan.note

    # the fallback surfaces through the analyzer catalogue so lint
    # consumers can rank it with everything else
    rep = plan.findings()
    assert [f.code for f in rep] == ["reshard-unbounded"]
    assert rep.by_code("reshard-unbounded")[0].bytes == plan.peak_bytes

    # a bounded plan is lint-clean
    assert not plan_reshard(mesh, P("x"), mesh, P("y"), SHAPE,
                            "float32").findings()

    # an UNREALIZABLE destination layout (6 not divisible by y=4) is a hard
    # error, not a silent fallback
    from paddle_tpu.distributed.resharding import PlanError
    with pytest.raises(PlanError):
        plan_reshard(mesh, P("x"), mesh, P("y"), (6, 8), "float32")


def test_plan_shrink_keeps_spec_single_remesh(mesh, shrunk_meshes):
    # same spec, smaller mesh: pure data movement — no collective kinds at
    # all, just the host-assembled remesh
    plan = plan_reshard(mesh, P("x", "y"), shrunk_meshes[0], P("x", "y"),
                        SHAPE, "float32")
    assert plan.bounded and not plan.collective_kinds()


# ---------------------------------------------------------------------------
# 3. execution bit-identity


def _global(shape=SHAPE, seed=0):
    return np.random.default_rng(seed).standard_normal(shape).astype(
        np.float32)


def _check_pair(mesh, src, dmesh, dst):
    ref = _global()
    arr = jax.device_put(jnp.asarray(ref), NamedSharding(mesh, src))
    plan = plan_reshard(mesh, src, dmesh, dst, ref.shape, ref.dtype)
    out = execute(plan, arr)
    assert out.sharding.is_equivalent_to(NamedSharding(dmesh, dst), ref.ndim)
    np.testing.assert_array_equal(np.asarray(out), ref)
    # return trip: bitwise restoration on the original layout
    back = execute(plan_reshard(dmesh, dst, mesh, src, ref.shape, ref.dtype),
                   out)
    assert back.sharding.is_equivalent_to(NamedSharding(mesh, src), ref.ndim)
    np.testing.assert_array_equal(np.asarray(back), ref)


def test_execute_roundtrip_sampled(mesh, shrunk_meshes):
    rng = random.Random(0)
    for _ in range(6):
        _check_pair(mesh, rng.choice(_SPECS), mesh, rng.choice(_SPECS))
    for _ in range(3):
        _check_pair(mesh, rng.choice(_SPECS), shrunk_meshes[0],
                    rng.choice(_SPECS))


def test_reshard_convenience_api(mesh, shrunk_meshes):
    ref = _global(seed=3)
    arr = jax.device_put(jnp.asarray(ref), NamedSharding(mesh, P("x", "y")))
    out, plan = reshard(arr, NamedSharding(shrunk_meshes[1], P(None, "x")),
                        return_plan=True)
    assert plan.bounded
    assert out.sharding.is_equivalent_to(
        NamedSharding(shrunk_meshes[1], P(None, "x")), ref.ndim)
    np.testing.assert_array_equal(np.asarray(out), ref)


@pytest.mark.slow
def test_execute_roundtrip_exhaustive(mesh, shrunk_meshes):
    for src, dst in itertools.product(_SPECS, _SPECS):
        _check_pair(mesh, src, mesh, dst)
    rng = random.Random(1)
    for dmesh in shrunk_meshes:
        for _ in range(20):
            _check_pair(mesh, rng.choice(_SPECS), dmesh, rng.choice(_SPECS))


# ---------------------------------------------------------------------------
# file-backed variant (streaming checkpoint shards across topologies)


def _grid_chunks(ref, splits):
    """Cut ``ref`` into a grid of chunk dicts, ``splits`` pieces per dim."""
    chunks, data = [], {}
    steps = [s // n for s, n in zip(ref.shape, splits)]
    for idx in itertools.product(*(range(n) for n in splits)):
        off = tuple(i * st for i, st in zip(idx, steps))
        key = f"c{'_'.join(map(str, idx))}"
        chunks.append(ChunkRef(f"{sum(idx) % 2}_0.distcp.npz", key, off,
                               tuple(steps)))
        data[key] = ref[tuple(slice(o, o + st)
                              for o, st in zip(off, steps))].copy()
    return chunks, data


def test_file_reshard_roundtrip_bounded():
    ref = _global((8, 12), seed=5)
    chunks, data = _grid_chunks(ref, (4, 1))  # written at a 4-way topology
    # read back at a 2-way topology (plus one unaligned region)
    regions = [((0, 0), (4, 12)), ((4, 0), (4, 12)), ((2, 3), (4, 6))]
    plan = plan_file_reshard("w", chunks, ref.shape, "float32", regions)
    assert plan.bounded and plan.peak_bytes <= plan.bound_bytes
    for (off, shape), prog in plan.programs.items():
        got = read_shard(prog, lambda c: data[c.key], np.float32)
        want = ref[tuple(slice(o, o + s) for o, s in zip(off, shape))]
        np.testing.assert_array_equal(got, want)


def test_file_reshard_missing_chunk_fails_at_plan_time():
    ref = _global((8, 8), seed=6)
    chunks, _ = _grid_chunks(ref, (4, 1))
    with pytest.raises(ValueError, match="do not cover"):
        plan_file_reshard("w", chunks[:-1], ref.shape, "float32",
                          [((0, 0), (8, 8))])


def test_file_reshard_prefer_files_wins_overlaps():
    ref = _global((4, 4), seed=7)
    # two full replicas in different files, holding different bytes — the
    # preferred file must win every overlapped element
    chunks = [ChunkRef("0_0.distcp.npz", "a", (0, 0), (4, 4)),
              ChunkRef("1_0.distcp.npz", "b", (0, 0), (4, 4))]
    data = {"a": np.zeros_like(ref), "b": ref}
    plan = plan_file_reshard("w", chunks, ref.shape, "float32",
                             [((0, 0), (4, 4))],
                             prefer_files=("1_0.distcp.npz",))
    prog = next(iter(plan.programs.values()))
    got = read_shard(prog, lambda c: data[c.key], np.float32)
    np.testing.assert_array_equal(got, ref)


@needs_8
def test_checkpoint_save_then_shrink_load_streams(tmp_path):
    """End-to-end: save a dp=4-sharded state dict, load it into a dp=2
    layout — values exact, modeled read peak within bound, and the stats
    surface the stream (what CheckpointManager.resume prints)."""
    from paddle_tpu.distributed.checkpoint import (load_state_dict,
                                                   save_state_dict)

    ref = _global((8, 16), seed=9)
    m4 = Mesh(np.array(jax.devices()[:4]), ("dp",))
    m2 = Mesh(np.array(jax.devices()[:2]), ("dp",))
    src = {"w": jax.device_put(jnp.asarray(ref), NamedSharding(m4, P("dp")))}
    save_state_dict(src, str(tmp_path / "ck"))

    dst = {"w": jax.device_put(jnp.zeros(ref.shape, jnp.float32),
                               NamedSharding(m2, P("dp")))}
    stats = {}
    load_state_dict(dst, str(tmp_path / "ck"), stats=stats)
    np.testing.assert_array_equal(np.asarray(dst["w"]), ref)
    assert stats["bounded"] and 0 < stats["peak_bytes"] <= stats["bound_bytes"]
    assert stats["tensors"] == 1 and stats["reads"] > 0


# ---------------------------------------------------------------------------
# launcher wiring: shrink peer records -> child env -> prev_rank


def test_child_env_exports_shrink_peers():
    from argparse import Namespace

    from paddle_tpu.distributed.launch import _child_env

    peers = [{"rank": 0, "host": "a", "prev_rank": 0, "prev_nnodes": 3},
             {"rank": 1, "host": "b", "prev_rank": 2, "prev_nnodes": 3}]
    args = Namespace(nproc_per_node=1, nnodes=2, rank=1, master=None,
                     _shrink_peers=peers)
    env = _child_env(args, 0, coordinator="127.0.0.1:1")
    assert env["PADDLE_PREV_RANK"] == "2"
    assert json.loads(env["PADDLE_SHRINK_PEERS"]) == peers

    # no shrink: the variables must not leak into the child
    args2 = Namespace(nproc_per_node=1, nnodes=2, rank=1, master=None)
    env2 = {k: v for k, v in _child_env(args2, 0, "127.0.0.1:1").items()
            if k.startswith("PADDLE_SHRINK") or k == "PADDLE_PREV_RANK"}
    assert not {k: v for k, v in env2.items()
                if k not in os.environ}


def test_shrink_prev_rank_resolution(monkeypatch):
    from paddle_tpu.distributed.fleet.elastic import CheckpointManager

    peers = [{"rank": 0, "host": "a", "prev_rank": 1}]
    assert CheckpointManager._shrink_prev_rank(peers) == 1
    monkeypatch.setenv("PADDLE_SHRINK_PEERS",
                       '[{"rank": 0, "prev_rank": 3}]')
    assert CheckpointManager._shrink_prev_rank(None) == 3
    monkeypatch.delenv("PADDLE_SHRINK_PEERS")
    monkeypatch.setenv("PADDLE_PREV_RANK", "5")
    assert CheckpointManager._shrink_prev_rank(None) == 5
    monkeypatch.delenv("PADDLE_PREV_RANK")
    assert CheckpointManager._shrink_prev_rank(None) is None
