"""Benchmark driver: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]

Writes per-benchmark JSON to artifacts/bench/ and prints tables.
"""
from __future__ import annotations

import argparse
import json
import os
import time
import traceback

from repro.launch.compile_cache import enable_compile_cache

from benchmarks import (
    bench_ablation,
    bench_bank,
    bench_characterization,
    bench_end2end,
    bench_heavy,
    bench_inefficiency,
    bench_kernels,
    bench_multitenant,
    bench_sweeps,
    bench_table1,
    roofline_table,
)

BENCHES = {
    # ordering matters: characterization + bank CALIBRATE the simulator
    # (artifacts/ita_calibration.json) before the end-to-end runs
    "characterization": bench_characterization,   # Fig 2, Table 2
    "bank": bench_bank,                           # Fig 9, Fig 10
    "inefficiency": bench_inefficiency,           # Fig 3
    "end2end": bench_end2end,                     # Fig 7
    "heavy": bench_heavy,                         # Table 7
    "ablation": bench_ablation,                   # Table 8, Fig 8a/b
    "sweeps": bench_sweeps,                       # Fig 8c/d
    "multitenant": bench_multitenant,             # tenant mix x shard counts
    "table1": bench_table1,                       # Table 1
    "kernels": bench_kernels,                     # kernel paths
    "roofline": roofline_table,                   # §Roofline (dry-run)
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    args = ap.parse_args()
    enable_compile_cache()

    names = list(BENCHES)
    if args.only:
        names = [n for n in args.only.split(",") if n in BENCHES]

    summary = {}
    failures = 0
    for name in names:
        print(f"\n#### {name} " + "#" * (60 - len(name)))
        t0 = time.time()
        try:
            BENCHES[name].run(quick=args.quick)
            summary[name] = {"status": "ok",
                             "seconds": round(time.time() - t0, 1)}
        except Exception as e:       # noqa: BLE001 — keep the suite going
            traceback.print_exc()
            summary[name] = {"status": f"FAILED: {e!r}"[:200],
                             "seconds": round(time.time() - t0, 1)}
            failures += 1
    print("\n#### summary " + "#" * 50)
    for name, s in summary.items():
        print(f"{name:20s} {s['status']:10s} {s['seconds']:8.1f}s")
    os.makedirs("artifacts/bench", exist_ok=True)
    with open("artifacts/bench/summary.json", "w") as f:
        json.dump(summary, f, indent=1)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
