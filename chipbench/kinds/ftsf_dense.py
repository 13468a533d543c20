"""Dense FTSF tensors, streamed in through the store's ingest writer."""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from ..data import ffhq
from . import Built, Spec, full_spec

GENERATORS = {"ffhq": ffhq.rows}


def _int4(x: np.ndarray) -> np.ndarray:
    """uint8 kept to its top four bits: the int4 step below int8."""
    return x & np.uint8(0xF0)


def build(cfg: Dict[str, Any], seed: int, root: str) -> Built:
    """Write ``cfg["rows"]`` generated rows, ``file_rows`` to a part file."""
    from repro.core import DeltaTensorStore
    from repro.lake import LocalFSObjectStore

    gen = GENERATORS[cfg["generator"]]
    row_shape = tuple(int(d) for d in cfg["row_shape"])
    n_rows, file_rows = int(cfg["rows"]), int(cfg["file_rows"])
    shape = (n_rows,) + row_shape
    row_bytes = int(np.prod(row_shape)) * np.dtype(cfg["dtype"]).itemsize
    store = DeltaTensorStore(LocalFSObjectStore(root), "tensors",
                             compression=cfg["codec"])
    tid = cfg["name"]
    # the writer splits a flush into part files of about target bytes
    # (row payload plus a little metadata): half a row of slack keeps
    # file_rows rows to a file
    with store.ingest(tid, watermark_rows=file_rows,
                      target_file_bytes=int((file_rows + 0.5) * row_bytes)
                      ) as writer:
        for lo in range(0, n_rows, file_rows):
            writer.append_rows(gen(seed, lo, min(file_rows, n_rows - lo),
                                   row_shape))

    def reference(spec: Spec) -> np.ndarray:
        (lo, hi), *rest = full_spec(shape, spec)
        x = gen(seed, lo, hi - lo, row_shape)
        return x[(slice(None),) + tuple(slice(a, b) for a, b in rest)]

    return Built(store=store, tensor_id=tid, shape=shape,
                 logical_bytes=n_rows * row_bytes, reference=reference,
                 control=_int4, kernel_bytes=lambda spec: 0)
