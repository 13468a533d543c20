"""A model hub: one base weight tree and its fine-tuned variants, saved
through the store's ``ModelRepo`` (each variant as XOR-delta frames of the
base's part files) and loaded through a ``Gateway``."""

from __future__ import annotations

import inspect
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Dict

import jax
import ml_dtypes

from ..data import hub
from . import Built

# the base model's prefix in the store; variant v is at "<PREFIX>~v"
PREFIX = "hub"
# variant trees generated and saved at once
SAVERS = 4


@dataclass
class HubBuilt(Built):
    """What the hub's loop needs besides the store: the gateway, the tree's
    template and each model's prefix."""

    gateway: Any = None
    template: Any = None
    prefixes: Dict[str, str] = field(default_factory=dict)


def _e5m2(tree: Any) -> Any:
    """Each bfloat16 leaf rounded through float8_e5m2, the step below."""
    return jax.tree_util.tree_map(
        lambda x: x.astype(ml_dtypes.float8_e5m2).astype(x.dtype), tree)


def build(cfg: Dict[str, Any], seed: int, root: str) -> HubBuilt:
    """Save the base, then the variants ``SAVERS`` at a time, and open a
    gateway with its default policy."""
    from repro.core import DeltaTensorStore
    from repro.lake import LocalFSObjectStore
    from repro.serve import Gateway, ModelRepo

    # fail before minutes of set-up on a program that cannot load a tree
    # into device memory
    if "device" not in inspect.signature(ModelRepo.load).parameters:
        raise RuntimeError("ModelRepo.load has no device path")
    store = DeltaTensorStore(LocalFSObjectStore(root), "tensors",
                             compression=cfg["codec"])
    names = hub.models(cfg)
    prefixes = {"base": PREFIX}
    with store.models(PREFIX) as repo:
        base = hub.base_tree(cfg, seed)
        repo.save(hub.nest(base))

        def save(name: str) -> str:
            with repo.open_variant(name) as var:
                var.save(hub.nest(hub.variant_tree(cfg, seed, name, base)))
                return var.prefix

        # variants commit side by side (each save is one commit, rebased
        # over the others'), SAVERS trees in memory beside the base: about
        # half the set-up of saving them one by one
        with ThreadPoolExecutor(SAVERS) as pool:
            prefixes.update(zip(names[1:], pool.map(save, names[1:])))
    del base
    gateway = Gateway(store)
    template = hub.nest({leaf: jax.ShapeDtypeStruct(shape, hub.BF16)
                         for leaf, shape in hub.leaves(cfg).items()})

    def counters() -> Dict[str, float]:
        st = gateway.stats()
        return {"loads_run": st["loads_run"],
                "queue_wait_s": st["queue_wait_s"],
                "coalesced": st["coalesced_hits"],
                "requests": (st["flights_created"] + st["coalesced_hits"]
                             + st["rejected"])}

    return HubBuilt(
        store=store, tensor_id=PREFIX, shape=(),
        logical_bytes=len(names) * hub.tree_bytes(cfg),
        reference=lambda name: hub.tree(cfg, seed, name), control=_e5m2,
        kernel_bytes=lambda spec: 0, counters=counters, gateway=gateway,
        template=template, prefixes=prefixes)
