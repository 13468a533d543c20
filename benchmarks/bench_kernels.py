"""Kernel microbenchmarks: the paper's encode/decode hot loops (Eq. 8).

On a CPU host the *compiled* path is the jnp reference (Pallas interpret
mode is a correctness tool, not a perf path), so timings compare the
vectorized encode/decode against a naive per-element baseline and report
achieved effective bandwidth — the TPU kernels are validated separately in
tests/test_kernels.py. ``kernel_coo_scatter`` times ``ops.coo_scatter``,
the read path's own call, at a real slice shape: run on a TPU host
(``PYTHONPATH=src python -m benchmarks.bench_kernels``) it times the
device scatter.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import device as dev
from repro.kernels import ops, ref

from .common import row


def _time(fn, *args, repeats=5):
    fn(*args)  # compile
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def run():
    lines = []
    rng = np.random.default_rng(0)

    # BSGS block gather/scatter (encode/decode hot loop)
    x = jnp.asarray(rng.standard_normal((1024, 1024)), jnp.float32)
    ids = jnp.asarray(rng.choice(8192, 512, replace=False), jnp.int32)
    bs = (8, 128)
    t = _time(lambda a, i: ref.block_gather(a, i, bs), x, ids)
    moved = 512 * 8 * 128 * 4
    lines.append(row("kernel_block_gather", t * 1e6,
                     f"eff_GBps={moved/t/1e9:.2f}"))
    blocks = ref.block_gather(x, ids, bs)
    t = _time(lambda a, i, b: ref.block_scatter(a, i, b), x, ids, blocks)
    lines.append(row("kernel_block_scatter", t * 1e6,
                     f"eff_GBps={(moved + x.nbytes)/t/1e9:.2f}"))

    # block norms (gradient-compression reduction)
    bv = jnp.asarray(rng.standard_normal((8192, 1024)), jnp.float32)
    t = _time(ref.block_norms, bv)
    lines.append(row("kernel_block_norms", t * 1e6,
                     f"eff_GBps={bv.nbytes/t/1e9:.2f}"))

    # COO scatter (decode) of one X[i] of the paper's Uber tensor: a
    # (1, 24, 1140, 1717) float32 slice from a mean day's 17,851 pairs, in
    # unsorted order; eff_GBps counts the least HBM traffic (the slice
    # written once, 8 bytes a pair read)
    shape = (1, 24, 1140, 1717)
    size = int(np.prod(shape))
    k = 17_851
    idx = jnp.asarray(rng.choice(size, k, replace=False), jnp.int32)
    vals = jnp.asarray(rng.standard_normal(k), jnp.float32)
    t = _time(lambda i, v: ops.coo_scatter(i, v, shape), idx, vals)
    lines.append(row("kernel_coo_scatter", t * 1e6,
                     f"nnz={k};size={size};eff_GBps={(4 * size + 8 * k)/t/1e9:.2f}"))

    # device codecs end-to-end (fixed-capacity encode+decode roundtrip)
    xs = jnp.asarray(rng.standard_normal((512, 512)), jnp.float32)
    xs = jnp.where(jnp.abs(xs) > 2.0, xs, 0.0)  # ~5% density

    def roundtrip(a):
        c = dev.bsgs_encode(a, (8, 128), 256)
        return dev.bsgs_decode(c, a.shape, (8, 128))

    t = _time(roundtrip, xs)
    lines.append(row("kernel_bsgs_roundtrip", t * 1e6,
                     f"density={float(jnp.mean(xs != 0)):.4f}"))
    return lines


if __name__ == "__main__":
    for line in run():
        print(line)
