"""Benchmark harness (deliverable d): one module per paper figure.
Prints ``name,us_per_call,derived`` CSV. ``--quick`` shrinks scales for CI.
``--json PATH`` additionally writes machine-readable results (the
perf-trajectory files, e.g. BENCH_kernels.json).

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig8a,...]
        [--json BENCH_kernels.json]
"""
import argparse
import json
import platform
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument("--json", default="",
                    help="write results to this path (BENCH_*.json)")
    args = ap.parse_args()
    from repro.envutil import enable_compile_cache
    enable_compile_cache()

    from . import (bench_algorithms, bench_data_scaling, bench_ipc,
                   bench_kernels, bench_machine_scaling, bench_serving,
                   common)

    benches = {
        "fig8a": lambda: bench_algorithms.main(
            scale=4000 if args.quick else 20000),
        "fig8b": lambda: bench_data_scaling.main(
            scales=(1000, 4000) if args.quick else (2000, 8000, 32000,
                                                    128000)),
        "fig8c": bench_machine_scaling.main,
        "fig8d": lambda: bench_ipc.main(scale=2000 if args.quick else 5000),
        "kernels": lambda: bench_kernels.main(quick=args.quick),
        "serving": lambda: bench_serving.main(quick=args.quick),
    }
    only = set(args.only.split(",")) if args.only else set(benches)
    unknown = only - set(benches)
    if unknown:
        print(f"unknown bench(es): {sorted(unknown)}; "
              f"known: {sorted(benches)}", file=sys.stderr)
        sys.exit(2)
    print("name,us_per_call,derived")
    failed = []
    for name, fn in benches.items():
        if name not in only:
            continue
        try:
            fn()
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if args.json:
        import jax
        payload = {
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "backend": jax.default_backend(),
            "platform": platform.platform(),
            "quick": bool(args.quick),
            "only": sorted(only),
            "failed": failed,
            "results": common.RESULTS,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {len(common.RESULTS)} rows to {args.json}",
              file=sys.stderr)
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
