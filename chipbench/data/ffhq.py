"""FFHQ-like uint8 image rows, one row at a time from the seed.

The structure follows the program's ``repro.data.synthetic.ffhq_like``
(copied here so that a change to the program cannot change the data the
benchmark measures): a uniform 8x8-block base image, a horizontal gradient
of 0..24 across the width and small noise, clipped to uint8. The noise is
uniform on -2..2 in integer arithmetic, and every row draws from its own
generator keyed by ``(seed, row)``, so any set of rows can be made again
without making the others.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np


def rows(seed: int, lo: int, n: int, row_shape: Sequence[int]) -> np.ndarray:
    """Rows ``lo .. lo + n - 1`` of the tensor, shape ``(n, *row_shape)``."""
    c, h, w = (int(d) for d in row_shape)
    out = np.empty((n, c, h, w), np.uint8)
    grad = (np.arange(w, dtype=np.int16) * 24) // max(w - 1, 1)
    for r in range(n):
        rng = np.random.default_rng([int(seed), int(lo + r)])
        base = rng.integers(0, 256, (c, -(-h // 8), -(-w // 8)),
                            dtype=np.int16)
        img = np.repeat(np.repeat(base, 8, axis=1), 8, axis=2)[:, :h, :w]
        img = img + grad
        img += rng.integers(-2, 3, (c, h, w), dtype=np.int16)
        np.clip(img, 0, 255, out=img)
        out[r] = img
    return out
