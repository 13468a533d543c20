"""A model hub's trees: one base model and its fine-tuned variants, from the
seed, with numpy (and ``ml_dtypes`` for bfloat16) alone.

The leaves are those of a dense decoder at the configuration's widths, named
and stacked as the program's ``transformer.init_params`` names them
(``blocks/attn/wq`` of shape ``(n_layers, d_model, n_heads * head_dim)``,
...), but made here, so that a change to the program cannot change the data
the benchmark measures.

* The base: every element a random bfloat16 bit pattern with a random sign,
  a random 7-bit mantissa and an exponent of -9 to -6, so magnitudes lie in
  [2**-9, 2**-5) (mean about 0.011): weights at scale about 0.02.
* A ``lora`` variant: ``W + B @ A`` on each target leaf, in float32 and
  rounded to bfloat16, with ``B`` (``d_in x rank``) and ``A``
  (``rank x d_out``) drawn N(0, scale**2) for each layer; every other leaf
  is the base's.
* A ``full`` variant: every leaf times ``1 + noise * N(0, 1)``, in float32
  and rounded to bfloat16.

Each leaf is drawn in blocks of :data:`BLOCK` elements, each block from its
own generator keyed by ``(seed, model, leaf, block)`` and on a pool of
threads, so a tree comes out the same whichever thread draws which block.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Tuple

import ml_dtypes
import numpy as np

BF16 = np.dtype(ml_dtypes.bfloat16)
# elements a block: 8 MB of bfloat16
BLOCK = 1 << 22
# bfloat16 bits: keep the sign, the 7-bit mantissa and the exponent's two
# low bits, then add 118 to the exponent: exponents 118..121 (2**-9..2**-6)
KEEP_BITS, EXPONENT_BASE = 0x81FF, 118 << 7
WORKERS = min(8, os.cpu_count() or 1)


def leaves(cfg: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    """Each leaf's name and shape, in name order."""
    n, d, f, v = (int(cfg[k]) for k in ("n_layers", "d_model", "d_ff",
                                        "vocab_size"))
    q = int(cfg["n_heads"]) * int(cfg["head_dim"])
    kv = int(cfg["n_kv_heads"]) * int(cfg["head_dim"])
    out = {"blocks/attn/wk": (n, d, kv), "blocks/attn/wo": (n, q, d),
           "blocks/attn/wq": (n, d, q), "blocks/attn/wv": (n, d, kv),
           "blocks/ln1/scale": (n, d), "blocks/ln2/scale": (n, d),
           "blocks/mlp/w_down": (n, f, d), "blocks/mlp/w_gate": (n, d, f),
           "blocks/mlp/w_up": (n, d, f), "embed": (v, d),
           "final_norm/scale": (d,)}
    if not cfg.get("tie_embeddings", False):
        out["unembed"] = (d, v)
    return dict(sorted(out.items()))


def models(cfg: Dict[str, Any]) -> List[str]:
    """The hub's models: ``base``, then the variants in the configuration's
    order."""
    return ["base"] + list(cfg["variants"])


def tree_bytes(cfg: Dict[str, Any]) -> int:
    """Bytes of one tree."""
    return sum(int(np.prod(s)) for s in leaves(cfg).values()) * BF16.itemsize


def nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    """``{"a/b": x}`` as ``{"a": {"b": x}}``."""
    out: Dict[str, Any] = {}
    for name, x in flat.items():
        *path, last = name.split("/")
        node = out
        for p in path:
            node = node.setdefault(p, {})
        node[last] = x
    return out


def _blocks(size: int) -> List[Tuple[int, int]]:
    return [(lo, min(lo + BLOCK, size)) for lo in range(0, size, BLOCK)]


def _base_block(seed: int, leaf: int, block: int, out: np.ndarray) -> None:
    rng = np.random.default_rng([int(seed), 0, leaf, block])
    bits = rng.integers(0, 1 << 16, out.size, dtype=np.uint16)
    np.bitwise_and(bits, KEEP_BITS, out=bits)
    bits += EXPONENT_BASE
    out.view(np.uint16)[:] = bits


def _full_block(seed: int, model: int, leaf: int, block: int, noise: float,
                base: np.ndarray, out: np.ndarray) -> None:
    rng = np.random.default_rng([int(seed), model, leaf, block])
    scale = rng.standard_normal(base.size, dtype=np.float32)
    scale *= np.float32(noise)
    scale += np.float32(1)
    scale *= base.astype(np.float32)
    out[:] = scale.astype(BF16)


def _lora(seed: int, model: int, leaf: int, rank: int, scale: float,
          base: np.ndarray) -> np.ndarray:
    rng = np.random.default_rng([int(seed), model, leaf])
    n, d_in, d_out = base.shape
    b = rng.standard_normal((n, d_in, rank), dtype=np.float32)
    a = rng.standard_normal((n, rank, d_out), dtype=np.float32)
    b *= np.float32(scale)
    a *= np.float32(scale)
    w = base.astype(np.float32)
    w += np.matmul(b, a)
    return w.astype(BF16)


def base_tree(cfg: Dict[str, Any], seed: int) -> Dict[str, np.ndarray]:
    """The base model's leaves, flat by name."""
    out = {name: np.empty(shape, BF16) for name, shape in leaves(cfg).items()}
    jobs = [(i, b, x.reshape(-1)[lo:hi])
            for i, x in enumerate(out.values())
            for b, (lo, hi) in enumerate(_blocks(x.size))]
    with ThreadPoolExecutor(WORKERS) as pool:
        list(pool.map(lambda j: _base_block(seed, *j), jobs))
    return out


def variant_tree(cfg: Dict[str, Any], seed: int, name: str,
                 base: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """The variant ``name``'s leaves, flat, from the base's (not changed)."""
    spec = cfg["variants"][name]
    model = models(cfg).index(name)
    if spec["kind"] == "lora":
        out = dict(base)
        for i, leaf in enumerate(base):
            if leaf.rsplit("/", 1)[-1] in spec["targets"]:
                out[leaf] = _lora(seed, model, i, int(spec["rank"]),
                                  float(spec["scale"]), base[leaf])
        return out
    if spec["kind"] != "full":
        raise ValueError(f"variant {name!r}: unknown kind {spec['kind']!r}")
    out = {leaf: np.empty_like(x) for leaf, x in base.items()}
    jobs = []
    for i, (leaf, x) in enumerate(base.items()):
        src, dst = x.reshape(-1), out[leaf].reshape(-1)
        for b, (lo, hi) in enumerate(_blocks(x.size)):
            jobs.append((i, b, src[lo:hi], dst[lo:hi]))
    noise = float(spec["noise"])
    with ThreadPoolExecutor(WORKERS) as pool:
        list(pool.map(lambda j: _full_block(seed, model, j[0], j[1], noise,
                                            j[2], j[3]), jobs))
    return out


def tree(cfg: Dict[str, Any], seed: int, name: str) -> Dict[str, Any]:
    """The model ``name``'s tree, nested as the program's parameters are."""
    base = base_tree(cfg, seed)
    if name == "base":
        return nest(base)
    return nest(variant_tree(cfg, seed, name, base))
