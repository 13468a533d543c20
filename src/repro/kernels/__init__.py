"""Pallas TPU kernels for the paper's encode/decode hot loops.

Structure per kernel: <name>.py holds the pl.pallas_call + BlockSpec body,
ops.py the jit'd public wrappers (TPU: compiled; CPU: ref fallback or
interpret=True under test), ref.py the pure-jnp oracles.
"""
from . import ops, ref
from .ops import (block_gather, block_norms, block_scatter, block_topk,
                  coo_scatter, unshuffle, unshuffle_host)


def install_unshuffle_kernel(force: bool = False) -> bool:
    """Route ``compression.byte_unshuffle``'s plane transpose through the
    Pallas kernel. Auto-installed on TPU hosts at import; ``force=True``
    installs on any backend (tests run it through the interpreter)."""
    from ..lake import compression
    if force or ops._on_tpu():
        compression.set_unshuffle_kernel(unshuffle_host)
        return True
    return False


install_unshuffle_kernel()

__all__ = ["ops", "ref", "block_gather", "block_norms", "block_scatter",
           "block_topk", "coo_scatter", "unshuffle", "unshuffle_host",
           "install_unshuffle_kernel"]
