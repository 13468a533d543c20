"""ModelRepo: snapshot-pinned handle over one prefix of serve weights.

The old serving surface was a pair of stringly-typed free functions
(``serve.engine.save_weights(store, params, prefix=...)`` /
``load_weights(store, template, prefix=...)``): every call re-spelled the
prefix, nothing was pinned between calls, and the delta-variant write path
(``store.put_variant``) had no weights-level wrapper at all.
:class:`ModelRepo` is the handle redesign, mirroring
:class:`~repro.core.catalog.TensorRef`:

* ``store.models(prefix)`` returns a repo **pinned to one catalog
  snapshot** and holding a lease on it — concurrent re-saves and vacuum
  cannot change (or delete) what this handle reads. ``save`` advances the
  pin to the just-committed generation; ``refresh()`` re-pins at latest.
* ``repo.save(params)`` persists a param pytree — one FTSF tensor per
  leaf under ``<prefix>/<leaf>``, ONE atomic commit, old generation
  replaced in the same commit (a reader never sees two generations).
* ``repo.load(template)`` reads the whole tree through ONE merged
  :meth:`~repro.core.catalog.Catalog.read_many` fetch plan against the
  pinned catalog — deduplicated chunk keys, per-leaf decode overlapping
  in-flight fetches; ``load(template, device=True)`` lands every leaf in
  device memory.
* ``repo.open_variant(name)`` returns a repo for ``<prefix>~<name>``
  whose ``save`` stores each leaf via
  :meth:`~repro.core.batch.WriteBatch.put_variant` against this repo's
  leaves — fine-tunes land as XOR byte-deltas of the base's chunks (the
  content-addressed variant path), and load back transparently.

The old free functions survive as deprecated shims over this class.
"""

from __future__ import annotations

import weakref
from typing import (TYPE_CHECKING, Any, Dict, List, Optional, Sequence,
                    Tuple)

import jax
import numpy as np

from ..core.catalog import Catalog
from ..dist.sharding import _path_str as _leaf_name
from ..lake.io import ReadExecutor
from ..lake.log import ObjectNotFoundError

if TYPE_CHECKING:  # pragma: no cover - import cycle is typing-only
    from ..core.store import DeltaTensorStore, VersionArg


class ModelRepo:
    """Snapshot-pinned, lease-holding handle to one model's weight tree.

    Construct via ``store.models(prefix)``. The repo pins the store's
    catalog at construction (latest, or an explicit ``version=``) and
    leases that version vector until ``close()`` / context-manager exit /
    garbage collection — the same lifecycle every ``TensorRef`` and
    ``StreamLoader`` has. A repo over a prefix with no saved weights is
    valid (``exists()`` is False); the first ``save`` pins it.
    """

    def __init__(self, store: "DeltaTensorStore", prefix: str, *,
                 version: "VersionArg" = None,
                 base: Optional["ModelRepo"] = None):
        if not prefix:
            raise ValueError("model prefix must be a non-empty string")
        self.store = store
        self.prefix = prefix
        self._base = base
        self._catalog: Optional[Catalog] = None
        self._lease = None
        self._finalizer = weakref.finalize(self, lambda: None)
        self._pin(version)

    # -- lifecycle -------------------------------------------------------------

    def _pin(self, version: "VersionArg") -> None:
        """(Re)pin the repo's catalog snapshot, swapping the held lease."""
        try:
            catalog = self.store.catalog(version)
        except ObjectNotFoundError:
            if version is not None:
                raise
            catalog = None  # store has no table yet; first save pins
        old = self._finalizer
        if catalog is not None:
            self._catalog = catalog
            self._lease = self.store.leases.acquire(catalog.version_vector)
            self._finalizer = weakref.finalize(self, self._lease.release)
        else:
            self._finalizer = weakref.finalize(self, lambda: None)
        old()  # release the previous generation's lease (idempotent)

    def close(self) -> None:
        """Release the pinned snapshot lease (idempotent)."""
        self._finalizer()

    @property
    def closed(self) -> bool:
        """Whether the snapshot lease has been released."""
        return not self._finalizer.alive

    def __enter__(self) -> "ModelRepo":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()

    def refresh(self) -> "ModelRepo":
        """Re-pin at the store's latest snapshot; returns self."""
        self._pin(None)
        return self

    # -- introspection ---------------------------------------------------------

    @property
    def version(self):
        """Pinned version (vector on sharded stores); None before any pin."""
        return None if self._catalog is None else self._catalog.version

    @property
    def base(self) -> Optional["ModelRepo"]:
        """The base repo this one stores delta variants against, if any."""
        return self._base

    def _tid(self, name: str) -> str:
        return f"{self.prefix}/{name}"

    def leaf_ids(self) -> List[str]:
        """Sorted tensor ids under this prefix at the pinned snapshot."""
        if self._catalog is None:
            return []
        want = self.prefix + "/"
        return [tid for tid in self._catalog if tid.startswith(want)]

    def exists(self) -> bool:
        """Whether the pinned snapshot holds any weights for this prefix."""
        return bool(self.leaf_ids())

    def __repr__(self) -> str:
        kind = f" variant-of={self._base.prefix!r}" if self._base else ""
        return (f"ModelRepo({self.prefix!r}, version={self.version}{kind}, "
                f"{'closed' if self.closed else 'live'})")

    # -- writes ----------------------------------------------------------------

    def save(self, params: Any) -> List[str]:
        """Persist a param pytree: one FTSF tensor per leaf, ONE commit.

        Re-saving atomically replaces the previous generation (old files
        removed in the same commit). On a variant repo each leaf stages
        via ``put_variant`` against the base repo's same-named leaf —
        identical chunks dedup to references, changed chunks store as XOR
        deltas. The repo re-pins to the just-committed snapshot, so a
        following ``load`` reads what was saved. Returns the leaf ids.
        """
        leaves = jax.tree_util.tree_flatten_with_path(params)[0]
        with self.store.batch(op=f"SAVE WEIGHTS {self.prefix}") as batch:
            tids = []
            for path, leaf in leaves:
                name = _leaf_name(path)
                arr = np.asarray(leaf)
                if self._base is not None:
                    tids.append(batch.put_variant(
                        arr, base_tid=self._base._tid(name),
                        tensor_id=self._tid(name), overwrite=True))
                else:
                    tids.append(batch.put(arr, tensor_id=self._tid(name),
                                          layout="ftsf", overwrite=True))
        self._pin(None)
        return tids

    def open_variant(self, name: str, *,
                     version: "VersionArg" = None) -> "ModelRepo":
        """A repo for ``<prefix>~<name>`` storing deltas against this one.

        ``variant.save(params)`` writes each leaf as a delta variant of
        this repo's same-named leaf; ``variant.load`` reconstructs
        transparently (the read path XORs the base back). The variant is
        an ordinary model afterwards — same handles, deletes, vacuum
        refcounting.
        """
        return ModelRepo(self.store, f"{self.prefix}~{name}",
                         version=version, base=self)

    # -- reads -----------------------------------------------------------------

    def _requests(self, template: Any) -> Tuple[
            List[Tuple[str, Optional[Sequence]]], Any, List[Any]]:
        flat, treedef = jax.tree_util.tree_flatten_with_path(template)
        reqs = [(self._tid(_leaf_name(p)), None) for p, _ in flat]
        return reqs, treedef, [leaf for _, leaf in flat]

    def load(self, template: Any, *, device: bool = False,
             version: "VersionArg" = None,
             io: Optional[ReadExecutor] = None,
             cache_partition: Optional[str] = None) -> Any:
        """Load the weight tree shaped/typed like ``template``.

        ``template`` (e.g. ``jax.eval_shape`` of the init function, or a
        real params pytree) supplies tree structure and leaf dtypes; a
        leaf stored in another dtype raises ``TypeError`` (it is never
        cast). The whole tree loads through ONE merged fetch plan against
        the repo's pinned catalog — a consistent generation even if a
        re-save lands mid-load. ``device=True`` lands each leaf in
        device memory as a ``jax.Array`` (the plan's
        ``read_many(device=True)``: chunks staged and transferred once a
        leaf, no host copy of the whole tree); otherwise leaves are numpy
        arrays. ``version=`` reads a different pinned snapshot (time
        travel) without re-pinning the repo; ``io=`` overrides the
        store's shared executor; ``cache_partition`` routes the fetched
        blocks into that block-cache priority class (the gateway pins hot
        base models into a protected partition).
        """
        catalog = (self._catalog if version is None
                   else self.store.catalog(version))
        if catalog is None:
            raise KeyError(f"no weights saved under prefix "
                           f"{self.prefix!r} (empty store)")
        reqs, treedef, leaves = self._requests(template)
        arrays = catalog.read_many(reqs, io=io, device=device,
                                   cache_partition=cache_partition)
        for (tid, _), arr, leaf in zip(reqs, arrays, leaves):
            if np.dtype(arr.dtype) != np.dtype(leaf.dtype):
                raise TypeError(f"{tid} is stored as {np.dtype(arr.dtype)}, "
                                f"the template asks for "
                                f"{np.dtype(leaf.dtype)}")
        return jax.tree_util.tree_unflatten(treedef, arrays)

    def stats(self) -> Dict[str, Any]:
        """Pinned-snapshot inventory: leaf count and stored bytes."""
        leaves = self.leaf_ids()
        nbytes = 0
        if self._catalog is not None:
            nbytes = sum(self._catalog.entry(t).nbytes for t in leaves)
        return {"prefix": self.prefix, "version": self.version,
                "leaves": len(leaves), "stored_bytes": nbytes,
                "is_variant": self._base is not None}
