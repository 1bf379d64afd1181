"""Pallas TPU kernels for the compute hot-spots.

Each kernel ships three files: ``kernel.py`` (pl.pallas_call + BlockSpec),
``ops.py`` (jit'd wrapper; :func:`interpret_mode` picks interpret or
compiled),
``ref.py`` (pure-jnp oracle).  Tests sweep shapes/dtypes and assert_allclose
against the oracle with interpret=True.

- subset_combine:  DKS per-node min-plus subset convolution (paper Sec. 5.1,
                   the "most compute intensive task") — single-pass closure
                   in VMEM vs. ceil(log2 m) XLA passes.
- segment_minplus: DKS edge relaxation reduce on a padded-CSR layout with
                   hub splitting (degree decomposition).
- flash_attention: LM train/prefill causal GQA attention.
- embedding_bag:   recsys multi-hot gather-reduce.
"""

import jax


def interpret_mode() -> bool:
    """Whether the Pallas kernels run under the interpreter: yes on the
    ``cpu`` platform (where the tests run), no on ``tpu`` (compiled by
    Mosaic).  Any other platform is an error rather than a silent
    emulation, so a run that cannot see the chip never passes for one
    that ran there."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the default backend is {platform!r}")
