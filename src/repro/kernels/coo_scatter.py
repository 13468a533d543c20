"""Pallas TPU kernel: COO decode — scatter nnz values into a flat buffer.

GPU COO decode is an atomic scatter; TPUs have no scatter unit. The
TPU-native adaptation: iterate output tiles sequentially and build each
tile from a (K, T) hit mask on the vector unit,

    out[t*T + j] = sum_k where(idx[k] == t*T + j, values[k], 0)

summed in float32. Every term but the matching one is an exact zero, so
each output is its value bit for bit (inf and NaN stay in their own
column). A one-hot matmul on the MXU is not exact: on a v5e it rounded
float32 values to bfloat16 precision. The full index/value vectors stay
resident in VMEM across grid steps, and Mosaic fits the kernel in the
16 MiB of scoped VMEM only up to a K that does not depend on the output
size or on T: compiled for a TPU v5e, float32 values compile up to
K = 22,280 and bfloat16 up to K = 28,888 (``MAX_K`` is the smaller).
Out-of-range indices — the padding convention of
``repro.core.device.coo_encode`` — fall outside every tile and drop
naturally. Duplicate indices accumulate, matching scatter-add semantics.
Values must be float32 or bfloat16 (Mosaic loads no float16 vectors on a
v5e); ``ops.coo_scatter`` sends other dtypes to a jnp scatter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

MAX_K = 22_280  # largest nnz count that compiles for v5e (float32)


def _coo_scatter_kernel(idx_ref, vals_ref, o_ref, *, tile: int):
    t = pl.program_id(0)
    start = t * tile
    local = idx_ref[...] - start                     # (K,)
    vals = vals_ref[...].astype(jnp.float32)
    k = local.shape[0]
    cols = jax.lax.broadcasted_iota(jnp.int32, (k, tile), 1)
    hit = local[:, None] == cols                     # (K, T)
    o_ref[...] = jnp.sum(jnp.where(hit, vals[:, None], 0.0), axis=0,
                         keepdims=True).astype(o_ref.dtype)


def coo_scatter(flat_idx: jax.Array, values: jax.Array, size: int,
                *, tile: int = 512, interpret: bool = False) -> jax.Array:
    """flat_idx: (K,) int32; values: (K,); returns (size,) dense.

    size % tile == 0 (callers pad; tile a multiple of 128 lanes).
    """
    assert size % tile == 0, (size, tile)
    (k,) = values.shape
    out = pl.pallas_call(
        functools.partial(_coo_scatter_kernel, tile=tile),
        grid=(size // tile,),
        in_specs=[pl.BlockSpec((k,), lambda t: (0,)),
                  pl.BlockSpec((k,), lambda t: (0,))],
        out_specs=pl.BlockSpec((1, tile), lambda t: (0, t)),
        out_shape=jax.ShapeDtypeStruct((1, size), values.dtype),
        interpret=interpret,
    )(flat_idx.astype(jnp.int32), values)
    return out[0]
