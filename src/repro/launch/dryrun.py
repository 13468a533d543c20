import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST run before any other import (jax locks the device
count at first init). For every applicable cell this driver:

  1. builds the production mesh ((16,16) or (2,16,16));
  2. assembles abstract inputs + shardings from launch.specs;
  3. ``jax.jit(fn, in_shardings=..., ...).lower(...).compile()``;
  4. records memory_analysis / cost_analysis / per-collective bytes parsed
     from the optimized HLO into a JSON artifact under
     ``experiments/dryrun/`` (consumed by benchmarks/roofline.py).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch glm4-9b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh single|multi|both]
"""

import argparse
import json
import re
import time
import traceback
from typing import Any, Dict

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "experiments", "dryrun")

_COLL_RE = re.compile(
    r"(\w+)\[([\d,]*)\]\S*\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def collective_bytes(hlo_text: str) -> Dict[str, Any]:
    """Sum output-operand bytes of every collective in optimized HLO."""
    per_kind: Dict[str, float] = {}
    count: Dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        dt, dims, kind = m.group(1), m.group(2), m.group(3)
        if dt not in _DTYPE_BYTES:
            continue
        elems = 1
        if dims:
            for d in dims.split(","):
                if d:
                    elems *= int(d)
        nbytes = elems * _DTYPE_BYTES[dt]
        per_kind[kind] = per_kind.get(kind, 0) + nbytes
        count[kind] = count.get(kind, 0) + 1
    return {"bytes_by_kind": per_kind, "count_by_kind": count,
            "total_bytes": sum(per_kind.values())}


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: str,
             force: bool = False, profile: str = None,
             tag: str = "", remat: str = None) -> Dict[str, Any]:
    import jax
    from repro.launch import specs
    from repro.launch.mesh import make_production_mesh
    from repro.models.config import get_arch

    mesh_name = "multi" if multi_pod else "single"
    name = f"{arch}__{shape}__{mesh_name}" + (f"__{tag}" if tag else "")
    path = os.path.join(out_dir, name + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    record: Dict[str, Any] = {"arch": arch, "shape": shape, "mesh": mesh_name,
                              "tag": tag, "status": "running"}
    cfg = get_arch(arch)
    ok, why = specs.cell_applicable(cfg, shape)
    if not ok:
        record.update(status="skipped", reason=why)
        _write(path, record)
        return record

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        if profile or remat:
            import dataclasses
            from repro.models import config as config_mod
            kw = {}
            if profile:
                kw["sharding_profile"] = profile
            if remat:
                kw["remat_policy"] = remat
            cfg = dataclasses.replace(cfg, **kw)
            config_mod._REGISTRY[arch] = cfg
        cell = specs.make_cell(arch, shape, mesh)
        with jax.set_mesh(mesh):
            jitted = jax.jit(cell.fn,
                             in_shardings=cell.in_shardings,
                             out_shardings=cell.out_shardings,
                             donate_argnums=cell.donate)
            lowered = jitted.lower(*cell.args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

            mem = compiled.memory_analysis()
            mem_rec = {}
            for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                         "temp_size_in_bytes", "alias_size_in_bytes",
                         "generated_code_size_in_bytes"):
                mem_rec[attr] = getattr(mem, attr, None)
            cost = compiled.cost_analysis() or {}
            cost_rec = {k: float(v) for k, v in cost.items()
                        if isinstance(v, (int, float)) and
                        k in ("flops", "bytes accessed", "transcendentals",
                              "utilization operand 0 {}", "optimal_seconds")}
            # keep all numeric entries that look global
            for k, v in cost.items():
                if isinstance(v, (int, float)) and k.startswith("bytes accessed"):
                    cost_rec[k] = float(v)

            hlo = compiled.as_text()
            coll = collective_bytes(hlo)
            # loop-aware corrected costs (scan bodies × trip count)
            from repro.analysis import accounting, hlo_cost
            corrected = hlo_cost.analyze(hlo)
            info = specs.SHAPES[shape]
            analytic = accounting.model_flops(
                cfg, info["kind"], info["global_batch"],
                1 if info["kind"] == "decode" else info["seq_len"],
                cache_len=info["seq_len"])
            print(f"[{name}] memory_analysis: "
                  f"args={mem_rec.get('argument_size_in_bytes')} "
                  f"temp={mem_rec.get('temp_size_in_bytes')} "
                  f"out={mem_rec.get('output_size_in_bytes')}")
            print(f"[{name}] cost_analysis: flops={cost_rec.get('flops')} "
                  f"bytes={cost_rec.get('bytes accessed')}")
            print(f"[{name}] collectives: {coll['count_by_kind']} "
                  f"total={coll['total_bytes']/1e9:.3f} GB")
            print(f"[{name}] corrected: flops={corrected.flops:.3e} "
                  f"bytes={corrected.bytes:.3e} "
                  f"coll={corrected.total_coll_bytes:.3e}")
        record.update(
            status="ok", note=cell.note,
            lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
            memory=mem_rec, cost=cost_rec, collectives=coll,
            corrected={"flops": corrected.flops, "bytes": corrected.bytes,
                       "coll_bytes": corrected.coll_bytes,
                       "coll_count": corrected.coll_count},
            analytic=analytic,
            n_devices=int(np_prod(mesh.devices.shape)),
            mesh_shape=list(mesh.devices.shape),
            profile=profile or cfg.sharding_profile)
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        record.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-4000:])
        print(f"[{name}] FAILED: {e}")
    _write(path, record)
    return record


def np_prod(shape):
    out = 1
    for s in shape:
        out *= int(s)
    return out


def _write(path: str, record: Dict[str, Any]) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(record, f, indent=1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=[None, "train_4k",
                    "prefill_32k", "decode_32k", "long_500k"])
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--profile", default=None, help="override sharding profile")
    ap.add_argument("--remat", default=None, help="override remat policy")
    ap.add_argument("--tag", default="", help="artifact suffix for perf iters")
    ap.add_argument("--out", default=os.path.abspath(ARTIFACT_DIR))
    args = ap.parse_args()

    from repro.models.config import list_archs
    archs = [args.arch] if args.arch else list(list_archs())
    shapes = [args.shape] if args.shape else list(
        __import__("repro.launch.specs", fromlist=["SHAPES"]).SHAPES)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    results = []
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                r = run_cell(arch, shape, multi, args.out, force=args.force,
                             profile=args.profile, tag=args.tag,
                             remat=args.remat)
                results.append(r)
                print(f"== {arch} × {shape} × "
                      f"{'multi' if multi else 'single'}: {r['status']} "
                      f"({r.get('compile_s', '-')}s)")
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\nDRY-RUN SUMMARY: {n_ok} ok, {n_skip} skipped, {n_err} failed "
          f"of {len(results)}")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
