"""jit'd public wrapper for the flash-attention kernel.

Accepts model-layout tensors (B, S, H, hd) with GQA (Hkv dividing Hq),
folds (B, H) into the kernel's BH axis, and dispatches kernel vs oracle.
``interpret=True`` is the validated CPU mode; on a real TPU the same call
runs compiled (interpret=False).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ref import attention_ref

__all__ = ["flash_attention"]


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int | None = None,
                    block_q: int = 128, block_kv: int = 128,
                    use_kernel: bool = True,
                    interpret: bool) -> jnp.ndarray:
    """q: (B, S, Hq, hd); k, v: (B, S, Hkv, hd) -> (B, S, Hq, hd)."""
    b, s, hq, hd = q.shape
    hkv = k.shape[2]
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    qf = jnp.swapaxes(q, 1, 2).reshape(b * hq, s, hd)
    kf = jnp.swapaxes(k, 1, 2)                       # (B, Hkv, S, hd)
    if g > 1:
        kf = jnp.broadcast_to(kf[:, :, None], (b, hkv, g, s, hd))
    kf = kf.reshape(b * hq, s, hd)
    vf = jnp.swapaxes(v, 1, 2)
    if g > 1:
        vf = jnp.broadcast_to(vf[:, :, None], (b, hkv, g, s, hd))
    vf = vf.reshape(b * hq, s, hd)
    if use_kernel:
        of = flash_attention_fwd(qf, kf, vf, causal=causal, window=window,
                                 block_q=block_q, block_kv=block_kv,
                                 interpret=interpret)
    else:
        of = attention_ref(qf, kf, vf, causal=causal, window=window)
    return jnp.swapaxes(of.reshape(b, hq, s, hd), 1, 2)
