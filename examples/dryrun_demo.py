"""Dry-run demo: lower + compile one (arch x shape) on the production
16x16 mesh and print the roofline terms.

    PYTHONPATH=src python examples/dryrun_demo.py [--arch qwen2-7b]
                                                  [--shape decode_32k]

NOTE: must run as its own process — it forces 512 host-platform devices.
"""
import argparse
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
sys.path.insert(0, "src")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--shape", default="decode_32k")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.dryrun import dryrun_one

    enable_compile_cache()

    rec = dryrun_one(args.arch, args.shape, multi_pod=args.multi_pod)
    t = rec["roofline"]
    print(f"\narch {args.arch} x {args.shape} on {rec['mesh']} "
          f"({rec['n_devices']} chips):")
    print(f"  compute term    {t['compute_s']:.3f} s")
    print(f"  memory term     {t['memory_s']:.3f} s")
    print(f"  collective term {t['collective_s']:.3f} s")
    print(f"  bottleneck      {t['dominant']}")
    print(f"  useful-FLOPs ratio (6ND / HLO) {rec['useful_flops_ratio']:.2f}")
    m = rec["memory"]
    print(f"  HBM/device: args {m['argument_size_in_bytes'] / 1e9:.2f} GB, "
          f"temps {m['temp_size_in_bytes'] / 1e9:.2f} GB")


if __name__ == "__main__":
    main()
