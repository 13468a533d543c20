"""Sharding rule engine: GSPMD partition specs for params, opt state, batch.

One place decides how every tensor lays out over the mesh:

* ``batch_axes(mesh)`` — the data-parallel axes (``("pod", "data")`` on the
  multi-pod mesh, ``"data"`` otherwise); batches shard their leading dim
  over them.
* ``params_shardings`` / ``opt_state_shardings`` — per-leaf NamedShardings.
  Profile ``tp`` shards each weight's largest divisible dim over ``model``;
  ``fsdp_tp`` additionally shards a second dim over the data axes (ZeRO-3
  style). Optimizer moments always take the data axes too (ZeRO-1): they
  are touched once per step, so gathers are off the critical path.
* ``constrain(x, axes)`` — in-graph sharding hints for model code.
  ``axes`` entries are ``"batch"`` (data axes), ``"model"``, a literal mesh
  axis name, or ``None``. First-divisible-wins: when several dims name the
  same mesh axis, the first whose extent divides the axis size takes it and
  the rest stay replicated (a mesh axis can partition only one dim).
  Outside a ``jax.set_mesh`` context (single-device runs) it is the
  identity.
* ``shard_map_batch(fn, *args)`` — run ``fn`` batch-locally via shard_map
  over the data axes (for ops GSPMD mispartitions, e.g. batched gathers in
  the MoE dispatch). Identity-wrapped when no mesh is active.
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence, Union

import jax
import numpy as np
from jax.sharding import AbstractMesh, Mesh, NamedSharding, PartitionSpec as P

MODEL = "model"
DATA = "data"
POD = "pod"


def _path_str(path) -> str:
    def part(k):
        for attr in ("key", "name", "idx"):
            if hasattr(k, attr):
                return str(getattr(k, attr))
        return str(k)
    return "/".join(part(k) for k in path)


def current_mesh() -> Optional[AbstractMesh]:
    """The ambient ``jax.set_mesh(mesh)`` context, or None."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def batch_axes(mesh: Mesh) -> tuple:
    """Mesh axes the batch dim shards over (pod-major on multi-pod meshes).

    Always a tuple: callers iterate it and splice it into PartitionSpecs
    (a tuple of names is a valid single-dim spec entry).
    """
    return tuple(a for a in (POD, DATA) if a in mesh.axis_names)


def _axes_size(mesh: Mesh, axes: Union[str, tuple, None]) -> int:
    if axes is None or axes == ():
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return int(np.prod([mesh.shape[a] for a in axes]))


# ---------------------------------------------------------------------------
# in-graph constraints
# ---------------------------------------------------------------------------


def _resolve_spec(shape: Sequence[int], axes: Sequence[Any], mesh: Mesh) -> P:
    spec: List[Any] = [None] * len(shape)
    used: set = set()
    for d, want in enumerate(axes[: len(shape)]):
        if want is None:
            continue
        resolved = batch_axes(mesh) if want == "batch" else want
        if resolved is None or resolved == ():
            continue
        names = (resolved,) if isinstance(resolved, str) else tuple(resolved)
        if any(n not in mesh.axis_names or n in used for n in names):
            continue
        size = _axes_size(mesh, names)
        # first-divisible-wins: an indivisible dim stays replicated rather
        # than erroring out of GSPMD (e.g. kv heads % model on GQA archs)
        if size <= 1 or shape[d] % size != 0:
            continue
        spec[d] = resolved
        used.update(names)
    return P(*spec)


def constrain(x: jax.Array, axes: Sequence[Any]) -> jax.Array:
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = _resolve_spec(x.shape, list(axes), mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def shard_map_batch(fn, *args):
    """Run ``fn`` with each arg's leading (batch) dim split over the data
    axes; outputs are reassembled on the same layout. Batch-local compute
    only — ``fn`` must not reduce across the batch dim."""
    mesh = current_mesh()
    if mesh is None:
        return fn(*args)
    axes = batch_axes(mesh)
    dsize = _axes_size(mesh, axes)
    if dsize <= 1 or any(a.shape[0] % dsize != 0 for a in args):
        return fn(*args)
    in_specs = tuple(P(axes, *([None] * (a.ndim - 1))) for a in args)
    out_shapes = jax.eval_shape(fn, *args)
    out_specs = jax.tree.map(
        lambda s: P(axes, *([None] * (len(s.shape) - 1))), out_shapes)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*args)


# ---------------------------------------------------------------------------
# state shardings
# ---------------------------------------------------------------------------


def _leaf_sharding(shape: Sequence[int], mesh: Mesh, *,
                   fsdp: bool) -> NamedSharding:
    nd = len(shape)
    spec: List[Any] = [None] * nd
    msize = mesh.shape.get(MODEL, 1)
    # tensor-parallel dim: largest extent divisible by the model axis
    if msize > 1 and nd >= 1:
        for d in sorted(range(nd), key=lambda d: -shape[d]):
            if shape[d] >= msize and shape[d] % msize == 0:
                spec[d] = MODEL
                break
    if fsdp:
        daxes = batch_axes(mesh)
        dsize = _axes_size(mesh, daxes)
        if dsize > 1:
            for d in sorted(range(nd), key=lambda d: -shape[d]):
                if spec[d] is None and shape[d] >= dsize and shape[d] % dsize == 0:
                    spec[d] = daxes
                    break
    return NamedSharding(mesh, P(*spec))


def params_shardings(params: Any, cfg: Any, mesh: Mesh,
                     profile: Optional[str] = None) -> Any:
    """Pytree of NamedShardings matching ``params``.

    ``profile`` overrides ``cfg.sharding_profile`` (``tp`` | ``fsdp_tp``).
    """
    profile = profile or getattr(cfg, "sharding_profile", "tp")
    fsdp = profile == "fsdp_tp"

    def leaf(path, x):
        return _leaf_sharding(tuple(x.shape), mesh, fsdp=fsdp)

    return jax.tree_util.tree_map_with_path(leaf, params)


def opt_state_shardings(tree: Any, cfg: Any, mesh: Mesh,
                        profile: Optional[str] = None) -> Any:
    """Adam moments: ZeRO-1 — always take the data axes on top of TP.

    Moments are read/written once per step (not per layer per microbatch),
    so sharding them over data costs one reduce-scatter/all-gather pair off
    the forward/backward critical path and divides optimizer-state HBM by
    the data-parallel degree.
    """
    def leaf(path, x):
        return _leaf_sharding(tuple(x.shape), mesh, fsdp=True)

    return jax.tree_util.tree_map_with_path(leaf, tree)
