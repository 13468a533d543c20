"""Sparse COO tensors, written in one ``put``."""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import ml_dtypes
import numpy as np

from ..data import uber
from . import Built, Spec, full_spec

GENERATORS = {"uber": uber.generate}

# one int32 flat index and one value per non-zero, the least a scatter
# can be handed
INDEX_BYTES = 4


def _bf16(x: np.ndarray) -> np.ndarray:
    """float32 rounded through bfloat16: the step below float32."""
    return x.astype(ml_dtypes.bfloat16).astype(x.dtype)


def build(cfg: Dict[str, Any], seed: int, root: str) -> Built:
    """Generate the COO tensor from the seed and ``put`` it."""
    from repro.core import DeltaTensorStore
    from repro.core.encodings.base import SparseCOO
    from repro.lake import LocalFSObjectStore

    gen = GENERATORS[cfg["generator"]]
    shape = tuple(int(d) for d in cfg["shape"])
    nnz = int(cfg["nnz"])
    idx, val = gen(seed, shape, nnz, cfg["structure"])
    store = DeltaTensorStore(LocalFSObjectStore(root), "tensors",
                             compression=cfg["codec"])
    tid = cfg["name"]
    store.put(SparseCOO(idx, val, shape), tensor_id=tid, layout="coo")
    logical = int(idx.nbytes + val.nbytes)
    itemsize = val.dtype.itemsize
    del idx, val

    # the reference makes the tensor again from the seed, once, after the
    # window: nothing the store was handed is kept
    made: Dict[str, Optional[Tuple[np.ndarray, np.ndarray]]] = {"t": None}

    def reference(spec: Spec) -> np.ndarray:
        if made["t"] is None:
            made["t"] = gen(seed, shape, nnz, cfg["structure"])
        return uber.dense_slice(*made["t"], shape, spec)

    per_day = uber.day_counts(shape[0], nnz, cfg["structure"]["weekly"])

    def kernel_bytes(spec: Spec) -> Optional[int]:
        # counted for slices of whole days, whose non-zeros the fixed
        # per-day counts give; None for any other slice
        (lo, hi), *rest = full_spec(shape, spec)
        if any(s != (0, d) for s, d in zip(rest, shape[1:])):
            return None
        out = (hi - lo) * int(np.prod(shape[1:])) * itemsize
        return out + int(per_day[lo:hi].sum()) * (INDEX_BYTES + itemsize)

    return Built(store=store, tensor_id=tid, shape=shape, logical_bytes=logical,
                 reference=reference, control=_bf16, kernel_bytes=kernel_bytes)
