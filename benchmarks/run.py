"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--small] [--only table1,...]

Prints ``name,us_per_call,derived`` CSV at the end (per the scaffold
contract). Roofline tables come from launch/dryrun + launch/report (they
need the 512-device environment, not this process).
"""
from __future__ import annotations

import argparse
import sys


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true",
                    help="CI-sized instances")
    ap.add_argument("--only", default="",
                    help="comma list: table1,fig5,table3,kernels,serve,"
                         "pipeline,many,service")
    args = ap.parse_args(argv)
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    want = set(args.only.split(",")) if args.only else {
        "table1", "fig5", "table3", "kernels", "serve", "pipeline", "many",
        "service"}

    csv = []
    if "table1" in want:
        print("== Table 1: drawing quality (CRE/NELD), Multi-GiLA vs "
              "centralized ==", flush=True)
        from benchmarks import quality_table1 as t1
        csv += t1.csv_rows(t1.run(small=args.small))
    if "fig5" in want:
        print("== Fig 5: hierarchy levels, distributed vs centralized "
              "merger ==", flush=True)
        from benchmarks import levels_fig5 as f5
        csv += f5.csv_rows(f5.run(small=args.small))
    if "table3" in want:
        print("== Table 3 / Fig 3: strong scalability ==", flush=True)
        from benchmarks import scaling_table3 as t3
        csv += t3.csv_rows(t3.run(small=args.small))
    if "kernels" in want:
        print("== Kernel + per-arch step micro-benchmarks ==", flush=True)
        from benchmarks import kernel_bench as kb
        csv += kb.csv_rows(kb.run(small=args.small))
    if "serve" in want:
        print("== Serving: batched viewport-query throughput ==", flush=True)
        from benchmarks import serve_bench as sb
        csv += sb.csv_rows(sb.run(small=args.small))
    if "pipeline" in want:
        print("== Pipeline: end-to-end multilevel driver, bucketed vs "
              "exact-shape compilation ==", flush=True)
        kind = "smoke" if args.small else "small"
        # the full-size pipeline suite (n up to 20k × 3 passes) is a
        # standalone run: python -m benchmarks.pipeline_bench
        print(f"[pipeline] running the '{kind}' suite here; use "
              "benchmarks.pipeline_bench directly for the full suite",
              flush=True)
        from benchmarks import pipeline_bench as pb
        csv += pb.csv_rows(pb.run(kind))

    if "many" in want:
        print("== Many: batched multi-graph layout vs sequential driver ==",
              flush=True)
        from benchmarks import many_bench as mb
        csv += mb.csv_rows(mb.run("smoke" if args.small else "full"))

    if "service" in want:
        print("== Service: continuous batching vs fixed window under "
              "Poisson load ==", flush=True)
        from benchmarks import service_bench as svb
        csv += svb.csv_rows(svb.run("smoke" if args.small else "full"))

    print("\nname,us_per_call,derived")
    for name, us, derived in csv:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
