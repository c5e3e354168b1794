"""Pallas TPU flash-attention forward kernel (online softmax).

Target: TPU v5e — MXU 128x128, ~16 MB VMEM/core.  Blocking: (block_q x hd)
query tiles stream against (block_kv x hd) key/value tiles; the running
max / normalizer / accumulator live in fp32 VMEM scratch.  Causal and
sliding-window masks are applied per-tile from the absolute block offsets;
fully-masked tiles still occupy grid slots (Mosaic schedules a static
grid) but skip the matmuls under ``pl.when``.

Layout: inputs are (BH, S, hd) with batch*heads folded — the wrapper in
ops.py folds GQA groups into BH.  VMEM per step at the default
block_q = block_kv = 128, hd = 128:
    q/k/v tiles 3 * 128*128*2B = 96 KiB + fp32 acc/stats ~ 66 KiB  << 16 MB,
leaving Mosaic room to double-buffer the HBM streams.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_fwd"]

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               scale: float, causal: bool, window: int | None,
               block_q: int, block_kv: int, n_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_kv

    # tile-level reachability: skip tiles fully above the causal diagonal
    # or fully left of the sliding window
    reachable = True
    if causal:
        reachable = k_start <= q_start + block_q - 1
    if window is not None:
        # newest key this tile offers vs oldest key any query here may see
        reachable = jnp.logical_and(
            reachable, k_start + block_kv - 1 >= q_start - window + 1)

    @pl.when(reachable)
    def _compute():
        q = q_ref[0].astype(jnp.float32)            # (block_q, hd)
        k = k_ref[0].astype(jnp.float32)            # (block_kv, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_kv), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_kv), 1)
        mask = jnp.ones((block_q, block_kv), jnp.bool_)
        if causal:
            mask &= qpos >= kpos
        if window is not None:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                         # (block_q, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, -1, keepdims=True)
        m_scr[...] = m_new
        v = v_ref[0].astype(jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == n_kv - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_kv", "interpret"))
def flash_attention_fwd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        causal: bool = True, window: int | None = None,
                        block_q: int = 128, block_kv: int = 128,
                        interpret: bool) -> jnp.ndarray:
    """q, k, v: (BH, S, hd) -> (BH, S, hd)."""
    bh, s, hd = q.shape
    bq = min(block_q, s)
    bkv = min(block_kv, s)
    assert s % bq == 0 and s % bkv == 0, (s, bq, bkv)
    n_q, n_kv = s // bq, s // bkv
    scale = hd ** -0.5

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        block_q=bq, block_kv=bkv, n_kv=n_kv)

    return pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, hd), q.dtype),
        scratch_shapes=[
            # fp32 running stats + accumulator in VMEM
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
