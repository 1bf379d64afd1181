"""jit wrapper: [B, S, H, Dh] layout, padding, GQA flattening."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.flash_attention.kernel import flash_attention_bhsd


def flash_attention(q, k, v, *, causal: bool = True, q_offset: int = 0,
                    block: int = 128, interpret: bool | None = None):
    """q [B, Sq, Hq, Dh]; k/v [B, Skv, Hkv, Dh] -> [B, Sq, Hq, Dh]."""
    if interpret is None:
        interpret = interpret_mode()
    b, sq, hq, dh = q.shape
    _, skv, hkv, _ = k.shape
    block_q = min(block, max(8, sq))
    block_k = min(block, max(8, skv))
    pad_q = (-sq) % block_q
    pad_k = (-skv) % block_k

    # [B, S, H, D] -> [B*H, S, D] with q heads grouped by kv head.
    g = hq // hkv
    q_t = q.transpose(0, 2, 1, 3)                      # [B, Hq, Sq, Dh]
    q_t = q_t.reshape(b * hq, sq, dh)
    k_t = k.transpose(0, 2, 1, 3).reshape(b * hkv, skv, dh)
    v_t = v.transpose(0, 2, 1, 3).reshape(b * hkv, skv, dh)
    if pad_q:
        q_t = jnp.pad(q_t, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k_t = jnp.pad(k_t, ((0, 0), (0, pad_k), (0, 0)))
        v_t = jnp.pad(v_t, ((0, 0), (0, pad_k), (0, 0)))
    out = flash_attention_bhsd(
        q_t, k_t, v_t, causal=causal, block_q=block_q, block_k=block_k,
        q_offset=int(q_offset), kv_len=skv, interpret=interpret)
    out = out[:, :sq].reshape(b, hq, sq, dh).transpose(0, 2, 1, 3)
    return out
