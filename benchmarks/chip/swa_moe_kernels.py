"""The window/full attention kernels against their XLA references at the
cell's shapes, on the chip, in one process:

    chiprun -- python3 -m benchmarks.chip.swa_moe_kernels

``gqa_prefill_attn`` of both layer kinds at 2,048 positions against the
reference, and at 16,384 against what causality and the band make checkable
without a 16,384-square score matrix (a causal layer's first 2,048 positions
are those of a 2,048-token call; a window layer's last 256 depend on its last
383 only); ``gqa_paged_decode`` over 32 slots of 1-17,408 live tokens in a
4,608-block pool; the token write against the prefill write.  One JSON line
a case, ``{"ok": ...}`` at the end; bf16 operands, float32 references.
"""
from __future__ import annotations

import json
import sys

import numpy as np


def main():
    import jax
    import jax.numpy as jnp

    from benchmarks import harness
    from paddle_tpu.kernels import gqa_attention as gqa

    harness.require_device(1, False)
    bf, ok = jnp.bfloat16, True
    rng = np.random.default_rng(7)

    def say(case, got, want, tol):
        nonlocal ok
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - want.astype(jnp.float32))))
        ok &= err <= tol
        print(json.dumps({"case": case, "max_abs_err": err, "tol": tol,
                          "passes": err <= tol}), flush=True)

    def qkv(S, hk):
        mk = lambda *s: jnp.asarray(rng.normal(size=s), bf)      # noqa: E731
        return (mk(1, S, 64, 192), mk(1, S, hk, 192), mk(1, S, hk, 128),
                jnp.asarray(rng.normal(size=(64,)), jnp.float32))

    scale = 192 ** -0.5
    for window, hk in ((None, 4), (128, 8)):
        kind = "window" if window else "full"
        q, k, v, b = qkv(2048, hk)
        b = b if window else None
        got = gqa.gqa_prefill_attention(q, k, v, scale, window, b)
        with jax.default_matmul_precision("highest"):
            want = gqa._prefill_reference(q, k, v, scale, window, b)
        say(f"prefill_{kind}_2048", got, want, 2e-2)
        q, k, v, b = qkv(16384, hk)
        b = b if window else None
        long = gqa.gqa_prefill_attention(q, k, v, scale, window, b)
        if window:
            with jax.default_matmul_precision("highest"):
                tail = gqa._prefill_reference(q[:, -384:], k[:, -384:],
                                              v[:, -384:], scale, window, b)
            say("prefill_window_16384_last_256", long[:, -256:],
                tail[:, -256:], 2e-2)
        else:
            head = gqa.gqa_prefill_attention(q[:, :2048], k[:, :2048],
                                             v[:, :2048], scale)
            say("prefill_full_16384_first_2048", long[:, :2048], head, 1e-2)
        assert bool(jnp.all(jnp.isfinite(long.astype(jnp.float32))))

    # decode: 32 slots over a 4,608-block pool, tables of 136 blocks
    nb, bs, hk, maxb = 4608, 128, 4, 136
    lens = np.asarray([17408, 1, 0, 127, 128, 129, 4096, 5000] + list(
        rng.integers(1024, 17408, size=24)), np.int32)
    k_pool, v_pool = gqa.init_kv_pools(nb, bs, hk, 192, 128, bf)
    tbl, free = np.zeros((32, maxb), np.int32), iter(
        rng.permutation(np.arange(1, nb)))
    fill = jax.jit(gqa.write_kv_prefill, donate_argnums=(0, 1))
    for s, n in enumerate(lens):
        n_blocks = -(-int(n) // bs)
        if not n_blocks:
            continue
        tbl[s, :n_blocks] = [next(free) for _ in range(n_blocks)]
        pad = maxb * bs
        k_seq = jnp.asarray(rng.normal(size=(pad, hk, 192)), bf)
        v_seq = jnp.asarray(rng.normal(size=(pad, hk, 128)), bf)
        blocks = np.zeros((maxb,), np.int32)
        blocks[:n_blocks] = tbl[s, :n_blocks]
        k_pool, v_pool = fill(k_pool, v_pool, jnp.asarray(blocks), k_seq,
                              v_seq)
    q = jnp.asarray(rng.normal(size=(32, 64, 192)), bf)
    args = (q, k_pool, v_pool, jnp.asarray(tbl), jnp.asarray(lens), scale)
    got = gqa.gqa_paged_decode_attention(*args)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(gqa._decode_reference)(*args)
    say("paged_decode_32_slots", got, want, 2e-2)
    # the token write lands where the prefill write would have put it
    new_k = jnp.asarray(rng.normal(size=(32, hk, 192)), bf)
    new_v = jnp.asarray(rng.normal(size=(32, hk, 128)), bf)
    at = jnp.asarray(np.where(lens > 0, lens - 1, 0), jnp.int32)
    k2, v2 = jax.jit(gqa.write_kv_token)(k_pool, v_pool, jnp.asarray(tbl),
                                         at, new_k, new_v)
    back = gqa.unpack_k_blocks(k2[jnp.asarray(tbl[0, 135])])     # [hk, bs, D]
    say("token_write_last_place", back[:, 127], new_k[0], 0.0)
    say("token_write_values", v2[jnp.asarray(tbl[0, 135])][:, 127], new_v[0],
        0.0)
    print(json.dumps({"ok": bool(ok)}), flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
