"""Device-side read assembly: staged host buffers -> accelerator arrays.

The last hop of the read pipeline (fetch -> staged decode -> **device**).
Codecs route decoded chunk payloads here instead of materializing a full
host tensor:

* :class:`ChunkAssembler` — a preallocated ``(n_slots, row_elems)`` staging
  buffer that chunk frames are written into via ``memoryview`` writes,
  each straight into its output row whatever order the pipeline delivers
  them in; ``gather()`` then moves the buffer to the device with one
  ``jax.device_put``. The staging write is the only host copy.
* :func:`scatter_coo` — COO decode straight to a dense *device* buffer via
  the jitted ``coo_scatter`` (XLA's scatter-add): indices/values are the
  only host arrays; the dense tensor first exists on the device.
* the ``store.h2d`` span around each transfer call (its host time; it
  carries ``bytes=``) and ``store.dispatch`` around the scatter's call.
* :func:`to_device` / :func:`device_dtype_exact` — the jax boundary.
  ``jax.device_put`` silently downcasts 64-bit dtypes unless
  ``jax_enable_x64`` is set, so anything that cannot round-trip bit-exactly
  stays in numpy (the uniform fallback also covers hosts without jax).

This module deliberately imports nothing from ``repro.core`` (the codecs in
``core/encodings`` call down into it) and defers the jax import until a
device path actually runs, so ``import repro.lake`` stays cheap.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Tuple

import numpy as np

from . import spans


@functools.lru_cache(maxsize=None)
def _mods() -> Tuple[Any, Any]:
    """(jax, repro.kernels.ops), or (None, None) on a host without jax.

    Only a missing jax means "no device": a broken ``repro.kernels``
    raises here instead of turning every read into a silent numpy path.
    """
    try:
        import jax
    except ImportError:
        return None, None
    from ..kernels import ops
    return jax, ops


def is_device_array(x: Any) -> bool:
    """True when ``x`` lives on a jax device (vs. the numpy fallback)."""
    jx, _ = _mods()
    return jx is not None and isinstance(x, jx.Array)


def device_dtype_exact(dtype: Any) -> bool:
    """True when jax holds ``dtype`` bit-exactly under the current config.

    Without ``jax_enable_x64``, ``device_put`` canonicalizes f64 -> f32 /
    i64 -> i32 — a silent precision loss the read path must never commit.
    """
    jx, _ = _mods()
    if jx is None:
        return False
    dt = np.dtype(dtype)
    try:
        return np.dtype(jx.dtypes.canonicalize_dtype(dt)) == dt
    except TypeError:
        return False


def to_device(arr: np.ndarray) -> Any:
    """``jax.device_put`` when bit-exact; the numpy array itself otherwise."""
    jx, _ = _mods()
    if jx is not None and device_dtype_exact(arr.dtype):
        with spans.span("store.h2d", bytes=arr.nbytes):
            return jx.device_put(arr)
    return arr


@dataclass
class DeviceReadInfo:
    """Accounting for one device read, for stats and the zero-copy gate.

    ``path`` names how the tensor reached the device: ``"staged"``
    (ordered chunk staging + one transfer), ``"coo_scatter"`` (sparse pairs
    scattered on device), or ``"host_fallback"`` (host decode then one
    transfer — layouts without a device kernel, or dtypes jax cannot hold).
    ``host_staged_bytes`` is every byte the read materialized on the host
    en route — the zero-full-tensor-copy claim is ``host_staged_bytes``
    not exceeding the payload actually read (never ordered-copy doubled,
    and for slice/sparse reads strictly less than the dense tensor).
    """

    path: str
    host_staged_bytes: int
    device_bytes: int
    on_device: bool


class ChunkAssembler:
    """Ordered chunk staging + one transfer.

    ``add(out_pos, blob)`` writes a chunk payload into staging row
    ``out_pos`` via a ``memoryview`` write, so chunks may arrive in any
    order and the buffer is already in output order when the last one
    lands; ``gather()`` device-puts it once. Dtypes the device cannot hold
    bit-exactly come back as the numpy buffer itself.
    """

    def __init__(self, n_slots: int, row_elems: int, dtype: Any):
        self.dtype = np.dtype(dtype)
        self.n_slots = int(n_slots)
        self.row_elems = max(1, int(row_elems))
        self._buf = np.empty((self.n_slots, self.row_elems), dtype=self.dtype)
        self._rows = self._buf.view(np.uint8).reshape(self.n_slots, -1)
        self.count = 0

    @property
    def staged_bytes(self) -> int:
        return self.count * self._rows.shape[1]

    def add(self, out_pos: int, blob: Any) -> None:
        """Stage one chunk payload into output row ``out_pos``."""
        self._rows[out_pos] = np.frombuffer(blob, dtype=np.uint8)
        self.count += 1

    def gather(self) -> Any:
        """The ``(n_slots, row_elems)`` array in output order (device when
        possible), transferring the staging buffer exactly once."""
        if self.count != self.n_slots:
            raise ValueError(
                f"assembled {self.count} of {self.n_slots} chunks")
        return to_device(self._buf)


def scatter_coo(flat_idx: np.ndarray, values: np.ndarray,
                shape: Tuple[int, ...]) -> Any:
    """Dense array of ``shape`` from COO pairs with row-major flat indices
    — on device when the dtype allows, else a numpy ``np.add.at``
    scatter."""
    shape = tuple(int(d) for d in shape)
    size = int(np.prod(shape))
    jx, kops = _mods()
    if (kops is not None and size > 0 and size < 2**31
            and device_dtype_exact(values.dtype)):
        jnp = jx.numpy
        if len(flat_idx) == 0:
            return jnp.zeros(shape, dtype=values.dtype)
        with spans.span("store.h2d", bytes=4 * len(flat_idx) + values.nbytes):
            idx = jnp.asarray(flat_idx, dtype=jnp.int32)
            vals = jnp.asarray(values)
        with spans.span("store.dispatch"):
            return kops.coo_scatter(idx, vals, shape)
    out = np.zeros(size, dtype=values.dtype)
    if len(flat_idx):
        np.add.at(out, flat_idx, values)
    return out.reshape(shape)
