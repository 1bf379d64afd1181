"""jit wrapper: engine-layout in/out; compiled on TPU, interpreted on CPU
(:func:`repro.kernels.interpret_mode`)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import INF
from repro.kernels import interpret_mode
from repro.kernels.subset_combine.kernel import subset_combine_t


def _pad_nodes(s_t: jax.Array, block_v: int) -> tuple[jax.Array, int]:
    v = s_t.shape[-1]
    pad = (-v) % block_v
    if pad:
        s_t = jnp.pad(s_t, ((0, 0), (0, 0), (0, pad)),
                      constant_values=INF)
    return s_t, v


def subset_combine(S: jax.Array, m: int, n_passes_unused: int = 0,
                   block_v: int = 512, interpret: bool | None = None) -> jax.Array:
    """Engine layout S [V, 2^m, K] -> closed table, via the Pallas kernel.

    One kernel pass reaches closure (in-kernel sequential popcount sweep),
    so ``n_passes_unused`` from the jnp path is ignored.
    """
    if interpret is None:
        interpret = interpret_mode()
    s_t = jnp.transpose(S, (1, 2, 0))          # [2^m, K, V]
    s_t, v = _pad_nodes(s_t, block_v)
    out = subset_combine_t(s_t, m, block_v=block_v, interpret=interpret)
    out = out[:, :, :v]
    return jnp.transpose(out, (2, 0, 1))
