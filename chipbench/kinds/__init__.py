"""Store writers, one module per configuration ``kind``.

A configuration file names its ``kind``; the harness imports
``chipbench.kinds.<kind>`` and calls ``build(cfg, seed, root)``, which
writes the store from the seed through the program and returns a
:class:`Built`. A new kind of tensor is a new module here.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import numpy as np

Spec = Tuple[Tuple[int, int], ...]


@dataclass
class Built:
    """A store written for one run, and what the check needs of it.

    ``reference(spec)`` is the plain numpy answer to a read of ``spec``,
    made again from the seed by the benchmark's own generator.
    ``control(answer)`` is that answer in the nearest precision below the
    configuration's. ``kernel_bytes(spec)`` is the least HBM traffic of the
    read's device work, for a roofline (0 where the read runs no kernel,
    None where it cannot be counted). ``shape`` is what the kind's loop
    needs of the tensor (a tree of tensors may give ``()``).
    ``counters()``, where the kind gives it, returns counters of its own
    that ``ReadStats`` does not hold (a gateway's loads, say); the harness
    puts their window deltas into the record's ``counters``.
    """

    store: Any
    tensor_id: str
    shape: Tuple[int, ...]
    logical_bytes: int
    reference: Callable[[Spec], np.ndarray]
    control: Callable[[np.ndarray], np.ndarray]
    kernel_bytes: Callable[[Spec], Optional[int]]
    counters: Optional[Callable[[], Dict[str, float]]] = None


def full_spec(shape: Sequence[int], spec: Spec) -> Spec:
    """``spec`` on the leading dimensions, padded with whole trailing ones."""
    return tuple(tuple(s) for s in spec) + tuple(
        (0, int(d)) for d in shape[len(spec):])
