"""Drive the store's main path once on one TPU chip, at real sizes.

    python chip_smoke.py [--seed 0]

One process, three phases, each checked against a plain numpy reference:

1. dense (paper §V, ``configs/paper_store.py``): an FFHQ-like uint8 FTSF
   tensor ``(128, 3, 1024, 1024)`` (the paper has 5000 rows) streamed in
   from ``--seed`` in row batches, then ``X[0:100]`` read into HBM with
   ``read_device``; plus one float32 tensor under ``zlib+shuffle``, so
   that frame decode goes through the unshuffle kernel;
2. sparse (paper §V): the Uber-like COO tensor at its full shape
   ``(183, 24, 1140, 1717)`` with 0.038% of its cells drawn (colliding
   draws sum, which leaves about 1.5 M non-zeros), three ``X[i]`` slices
   scattered densely into HBM; plus a small COO tensor of random float32
   values, which the device scatter must reproduce bit for bit;
3. serve: ``phi3-mini-3.8b`` at full width with random weights, saved
   through ``store.models(prefix)`` and served by ``repro.launch.serve``
   from that store.

Each phase prints one JSON line (wall times, bytes, read paths). The last
line is ``{"ok": true, "device": {...}}``. Without a TPU, or on any
mismatch or exception, the script exits non-zero without that line. The
stores live under ``<checkout>/.chip_smoke`` and are removed at the end.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402

from repro.configs.paper_store import PAPER_STORE  # noqa: E402
from repro.core import DeltaTensorStore  # noqa: E402
from repro.core.encodings.base import SparseCOO  # noqa: E402
from repro.data.synthetic import ffhq_like, uber_like  # noqa: E402
from repro.lake import LocalFSObjectStore, compression  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import transformer  # noqa: E402
from repro.models.config import get_arch  # noqa: E402

SERVE_ARCH = "phi3-mini-3.8b"


class SmokeFailure(AssertionError):
    """A phase's result disagreed with its reference."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _store(root: str, name: str, **kw) -> DeltaTensorStore:
    return DeltaTensorStore(LocalFSObjectStore(os.path.join(root, name)),
                            "tensors", **kw)


def _check_on_device(out: Any, info: Any, path: str, what: str) -> None:
    check(isinstance(out, jax.Array), f"{what}: result is not a jax.Array")
    check(info.on_device and info.path == path,
          f"{what}: read took path {info.path!r}, on_device="
          f"{info.on_device}; wanted {path!r} on the device")
    check(out.devices() == {jax.devices()[0]},
          f"{what}: result is on {out.devices()}")


def phase_dense(root: str, *, seed: int, rows: int = 128, read: int = 100,
                side: int = 1024, batch: int = 16,
                shuffled: Sequence[int] = (64, 256, 256)) -> Dict[str, Any]:
    """Paper §V dense: FFHQ-like uint8 FTSF tensor, ``X[0:read]`` to HBM."""
    store = _store(root, "dense")
    shape = (rows, 3, side, side)
    want = np.empty((read,) + shape[1:], np.uint8)
    t0 = time.perf_counter()
    # row-chunked FTSF (chunk_dims=3), ingested a batch at a time so the
    # host holds one batch of the tensor, not all of it
    with store.ingest("ffhq", watermark_rows=batch) as writer:
        for lo in range(0, rows, batch):
            x = ffhq_like((min(batch, rows - lo),) + shape[1:],
                          seed=seed + lo)
            keep = x[: max(0, read - lo)]
            want[lo:lo + len(keep)] = keep
            writer.append_rows(x)
    write_s = time.perf_counter() - t0
    with store.open("ffhq") as ref:
        check(tuple(ref.shape) == shape, f"dense: stored shape {ref.shape}")
        t0 = time.perf_counter()
        out, info = ref.read_device([(0, read)], with_info=True)
        out.block_until_ready()
        read_s = time.perf_counter() - t0
    _check_on_device(out, info, "staged", "dense X[0:read]")
    check(out.dtype == np.uint8 and out.shape == want.shape,
          f"dense: got {out.dtype}{out.shape}")
    check(np.array_equal(np.asarray(out), want),
          "dense: X[0:read] differs from the numpy slice")
    res = {"phase": "dense", "shape": list(shape), "chunk_dims": 3,
           "slice": [0, read], "write_s": write_s, "read_device_s": read_s,
           "device_bytes": info.device_bytes,
           "host_staged_bytes": info.host_staged_bytes, "path": info.path}
    del out, want

    # a shuffled float32 tensor: frame decode runs the unshuffle hook
    rng = np.random.default_rng(seed)
    y = rng.standard_normal(shuffled).astype(np.float32)
    y = (y.view(np.uint32) & np.uint32(0xFFFF0000)).view(np.float32)
    store.put(y, tensor_id="shuffled", layout="ftsf",
              compression="zlib+shuffle")
    store.io.stats.reset()
    with store.open("shuffled") as ref:
        out, info = ref.read_device(with_info=True)
    _check_on_device(out, info, "staged", "shuffled f32")
    check(np.array_equal(np.asarray(out).view(np.uint32), y.view(np.uint32)),
          "dense: shuffled float32 tensor differs from what was written")
    frames = store.io.stats.frames_decoded
    check(frames > 0, "dense: no compressed frame was decoded")
    res.update(shuffled_shape=list(shuffled), shuffled_frames=frames,
               unshuffle_kernel=compression.get_unshuffle_kernel() is not None)
    return res


def phase_sparse(root: str, *, seed: int,
                 shape: Sequence[int] = PAPER_STORE["sparse"]["shape"],
                 nnz_ratio: float = PAPER_STORE["sparse"]["nnz_ratio"],
                 probe: Sequence[int] = (64, 4096)) -> Dict[str, Any]:
    """Paper §V sparse: Uber-like COO tensor, three ``X[i]`` to HBM."""
    shape = tuple(shape)
    t0 = time.perf_counter()
    t = uber_like(shape, nnz_ratio, seed=seed)
    store = _store(root, "sparse")
    store.put(t, tensor_id="uber", layout="coo")
    write_s = time.perf_counter() - t0
    reads: List[Dict[str, Any]] = []
    for i in (0, shape[0] // 2, shape[0] - 1):
        spec = ((i, i + 1),) + tuple((0, d) for d in shape[1:])
        want = t.slice(spec)
        t0 = time.perf_counter()
        with store.open("uber") as ref:
            out, info = ref.read_device([(i, i + 1)], with_info=True)
        out.block_until_ready()
        read_s = time.perf_counter() - t0
        _check_on_device(out, info, "coo_scatter", f"sparse X[{i}]")
        check(np.array_equal(np.asarray(out), want.to_dense()),
              f"sparse: X[{i}] differs from SparseCOO.slice().to_dense()")
        reads.append({"i": i, "nnz": want.nnz, "read_device_s": read_s,
                      "device_bytes": info.device_bytes,
                      "host_staged_bytes": info.host_staged_bytes})
        del out

    # random float32 values: the scatter must be exact, not just close
    rng = np.random.default_rng(seed)
    n = int(np.prod(probe))
    flat = rng.choice(n, size=n // 100, replace=False)
    idx = np.stack(np.unravel_index(flat, probe), axis=1).astype(np.int64)
    p = SparseCOO(idx, rng.standard_normal(len(flat)).astype(np.float32),
                  tuple(probe))
    store.put(p, tensor_id="probe", layout="coo")
    with store.open("probe") as ref:
        out, info = ref.read_device(with_info=True)
    _check_on_device(out, info, "coo_scatter", "sparse probe")
    check(np.array_equal(np.asarray(out).view(np.uint32),
                         p.to_dense().view(np.uint32)),
          "sparse: random float32 values did not scatter bit-exactly")
    return {"phase": "sparse", "shape": list(shape), "nnz": t.nnz,
            "write_s": write_s, "reads": reads, "path": info.path,
            "probe_nnz": p.nnz}


def _leaf_sums(tree: Any) -> List[str]:
    return [hashlib.blake2b(np.ascontiguousarray(leaf).view(np.uint8).data,
                            digest_size=16).hexdigest()
            for leaf in jax.tree.leaves(tree)]


def phase_serve(root: str, *, seed: int, arch: str = SERVE_ARCH,
                reduced: bool = False, requests: int = 4, max_new: int = 8,
                max_len: int = 128) -> Dict[str, Any]:
    """Save random full-width weights to the store, serve them from it."""
    cfg = get_arch(arch)
    if reduced:
        cfg = cfg.reduced()
    wdir = os.path.join(root, "weights")
    t0 = time.perf_counter()
    params = jax.jit(transformer.init_params, static_argnums=0)(
        cfg, jax.random.key(seed))
    host = jax.device_get(params)
    del params  # the device copy: serving must load from the store
    sums = _leaf_sums(host)
    store = DeltaTensorStore(LocalFSObjectStore(wdir), "weights",
                             compression="zstd")
    with store.models("serve_weights") as repo:
        repo.save(host)
        stored = repo.stats()["stored_bytes"]
    n_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(host))
    del host
    save_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    argv = ["--arch", arch, "--weights-dir", wdir, "--requests",
            str(requests), "--slots", str(requests), "--max-new",
            str(max_new), "--max-len", str(max_len), "--seed", str(seed)]
    params, reqs = serve.main(argv + (["--reduced"] if reduced else []))
    serve_s = time.perf_counter() - t0

    leaves = jax.tree.leaves(params)
    check(all(isinstance(x, jax.Array) for x in leaves),
          "serve: the engine's params are not device arrays")
    check(_leaf_sums(params) == sums,
          "serve: loaded weights differ from the saved ones")
    prefill = jax.jit(lambda p, tok, caches: transformer.prefill(
        p, cfg, tok, caches))
    for r in reqs:
        toks = np.asarray(r.out_tokens)
        check(len(toks) == max_new and ((toks >= 0)
                                        & (toks < cfg.vocab_size)).all(),
              f"serve: request {r.rid} got tokens {r.out_tokens}")
        logits, _, _ = prefill(params, jax.numpy.asarray(r.prompt[None]),
                               transformer.init_caches(cfg, 1, max_len))
        first = int(jax.numpy.argmax(logits[0, -1]))
        check(r.out_tokens[0] == first,
              f"serve: request {r.rid} began with {r.out_tokens[0]}, "
              f"plain prefill gives {first}")
    return {"phase": "serve", "arch": cfg.name, "dtype": cfg.dtype,
            "param_bytes": n_bytes, "stored_bytes": stored,
            "leaves": len(leaves), "requests": len(reqs),
            "tokens": sum(len(r.out_tokens) for r in reqs),
            "init_save_s": save_s, "load_and_serve_s": serve_s}


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r}",
              file=sys.stderr)
        return 1
    enable_compile_cache()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(json.dumps({"device": device, "reduced": [
        "dense rows 5000 -> 128 (about 400 MB)",
        "sparse non-zeros about 1.5 M distinct, from 0.038% draws",
        "serve weights random, from --seed"]}), flush=True)
    root = os.path.join(ROOT, ".chip_smoke")
    shutil.rmtree(root, ignore_errors=True)
    try:
        for phase in (phase_dense, phase_sparse, phase_serve):
            t0 = time.perf_counter()
            res = phase(root, seed=args.seed)
            res["wall_s"] = time.perf_counter() - t0
            print(json.dumps(res), flush=True)
            # on a TPU host every shuffled frame decodes through the kernel
            check(res.get("unshuffle_kernel", True),
                  f"{res['phase']}: the unshuffle kernel is not installed")
    finally:
        shutil.rmtree(root, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
