"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [names...]

Re-execs itself with 8 forced host devices so traced distributed
benches run in-process; writes benchmarks/results.json."""

from __future__ import annotations

import json
import os
import sys
import time

_FLAG = "--xla_force_host_platform_device_count=8"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                               + _FLAG).strip()
    os.execv(sys.executable, [sys.executable, "-m", "benchmarks.run"]
             + sys.argv[1:])

BENCHES = [
    ("mm_costs", "Sec. III MM cost table", "benchmarks.bench_mm_costs"),
    ("tri_inv", "Sec. V inversion costs", "benchmarks.bench_tri_inv"),
    ("paper_table", "Sec. IX comparison table",
     "benchmarks.bench_paper_table"),
    ("tuning", "Sec. VIII tuning tables", "benchmarks.bench_tuning"),
    ("stability", "inversion stability (Du Croz/Higham)",
     "benchmarks.bench_stability"),
    ("gemm_fraction", "TPU MXU-eligible flop share",
     "benchmarks.bench_gemm_fraction"),
]


def main():
    import importlib

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    want = sys.argv[1:]
    results = {}
    failures = 0
    for name, desc, mod in BENCHES:
        if want and name not in want:
            continue
        print(f"\n=== {name}: {desc} ===", flush=True)
        t0 = time.time()
        try:
            m = importlib.import_module(mod)
            rows = m.run(lambda s: print("  " + s, flush=True))
            results[name] = {"status": "ok", "rows": rows,
                             "seconds": round(time.time() - t0, 1)}
        except Exception as e:
            import traceback
            traceback.print_exc()
            results[name] = {"status": "error", "error": repr(e)}
            failures += 1
    out = os.path.join(os.path.dirname(__file__), "results.json")
    with open(out, "w") as f:
        json.dump(results, f, indent=1, default=float)
    print(f"\nbenchmarks: {len(results) - failures}/{len(results)} ok; "
          f"results -> {out}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
