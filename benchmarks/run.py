"""Benchmark entry point — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  Fig. 12  -> bench_dense_ftsf      (dense: binary vs FTSF)
  Fig. 13-16 -> bench_sparse_formats (sparse: COO/CSR/CSF/BSGS vs PT)
  Eq. 8 hot loops -> bench_kernels
  DESIGN §2 wire compression -> bench_grad_compress
  §Roofline -> roofline (from dry-run artifacts, if present)
  read-path scaling -> bench_read_path (serial vs parallel vs cached)
  shard scale-out -> bench_shard_scale (commit throughput vs shard count)
  maintenance lifecycle -> bench_maintenance (churn reclaim, spilled index)
"""


def main() -> None:
    from . import (bench_dense_ftsf, bench_grad_compress, bench_kernels,
                   bench_maintenance, bench_read_path, bench_shard_scale,
                   bench_sparse_formats, roofline)
    print("name,us_per_call,derived")
    failed = []
    for mod in (bench_dense_ftsf, bench_sparse_formats, bench_kernels,
                bench_grad_compress, roofline, bench_read_path,
                bench_shard_scale, bench_maintenance):
        try:
            for line in mod.run():
                print(line)
        except Exception as e:  # run every module, then fail the harness
            print(f"{mod.__name__}_ERROR,0.0,{type(e).__name__}: {e}")
            failed.append(mod.__name__)
    if failed:
        raise SystemExit(f"benchmark modules failed: {', '.join(failed)}")


if __name__ == '__main__':
    main()
