import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("REPRO_DRYRUN_XLA", "--xla_force_host_platform_device_count=512")
)
# ^ MUST precede any jax import: jax locks the device count on first init.

"""Multi-pod dry-run driver (deliverable e).

For every (architecture x input shape) pair this lowers + compiles the
matching step function on the production mesh — 16x16 (single pod) and
2x16x16 (two pods) — and extracts:

  * ``compiled.memory_analysis()``  (bytes/device: proves it fits),
  * ``compiled.cost_analysis()``    (HLO FLOPs / bytes for the roofline),
  * collective bytes parsed from the HLO text (all-gather / all-reduce /
    reduce-scatter / all-to-all / collective-permute operand sizes).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multi --out artifacts/dryrun
Results are appended as JSON lines to ``--out`` (default
``artifacts/dryrun/<mesh>.jsonl``).
"""
import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax

from repro.config import INPUT_SHAPES, TuneConfig
from repro.configs import ASSIGNED_ARCHS, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_step
from repro.roofline.extract import (
    cost_summary,
    memory_summary,
    model_flops,
    roofline_terms,
)
from repro.roofline.hlo_analysis import analyze_hlo


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool = False,
               microbatches: int = 0, ce_chunk: int = 512,
               seq_shard: bool = False, keep_hlo: bool = False,
               verbose: bool = True) -> Dict[str, Any]:
    """Lower + compile one (arch, shape) on the production mesh."""
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    if seq_shard:
        cfg = cfg.with_overrides(seq_shard=True)
    if microbatches == 0:   # auto: grad accumulation keeps train in HBM,
        # but each microbatch must still give >= 1 row per data shard
        shape = INPUT_SHAPES[shape_name]
        if shape.kind == "train":
            data_ways = 32 if multi_pod else 16
            microbatches = max(1, min(16, shape.global_batch // data_ways))
        else:
            microbatches = 1
    fn, specs, shardings, model = build_step(
        cfg, shape_name, mesh, microbatches=microbatches, ce_chunk=ce_chunk
    )
    args = [jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        specs[k], shardings[k]) for k in specs]
    with mesh:
        # a train step compiles its save rungs here, so for it lower_s
        # holds the compile and compile_s is ~0
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    hlo = compiled.as_text()
    hc = analyze_hlo(hlo)           # trip-count-aware per-device costs
    coll = hc["collectives"]
    shape = INPUT_SHAPES[shape_name]
    terms = roofline_terms(hc["flops"], hc["bytes"], coll["total_bytes"])
    mf = model_flops(cfg, shape, backward=(shape.kind == "train"))
    mf_dev = mf / mesh.devices.size
    rec: Dict[str, Any] = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "seq_shard": seq_shard,
        "n_devices": int(mesh.devices.size),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": memory_summary(compiled),
        "cost_raw": cost_summary(compiled),   # XLA view (scan bodies x1)
        "hlo_cost": {k: v for k, v in hc.items() if k != "collectives"},
        "collectives": coll,
        "roofline": terms,
        "model_flops_per_dev": mf_dev,
        "useful_flops_ratio": mf_dev / hc["flops"] if hc["flops"] else 0.0,
    }
    if keep_hlo:
        rec["hlo_text"] = hlo
    if verbose:
        m = rec["memory"]
        print(f"[dryrun] {arch} x {shape_name} ({rec['mesh']}): "
              f"lower {t_lower:.1f}s compile {t_compile:.1f}s | "
              f"args/dev {m.get('argument_size_in_bytes', 0)/1e9:.2f}GB "
              f"temp/dev {m.get('temp_size_in_bytes', 0)/1e9:.2f}GB | "
              f"comp {terms['compute_s']:.3f}s mem {terms['memory_s']:.3f}s "
              f"coll {terms['collective_s']:.3f}s -> {terms['dominant']} | "
              f"useful {rec['useful_flops_ratio']:.2f}")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--microbatches", type=int, default=0)
    ap.add_argument("--ce-chunk", type=int, default=512)
    ap.add_argument("--seq-shard", action="store_true",
                    help="beyond-paper: context-parallel activations")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    multi = args.mesh == "multi"
    pairs = []
    if args.all:
        for a in ASSIGNED_ARCHS:
            for s in INPUT_SHAPES:
                pairs.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        pairs = [(args.arch, args.shape)]

    suffix = "_seqshard" if args.seq_shard else ""
    out_path = args.out or os.path.join(
        "artifacts", "dryrun", f"{args.mesh}{suffix}.jsonl"
    )
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    done = set()
    if os.path.exists(out_path):
        with open(out_path) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    if "error" not in r:
                        done.add((r["arch"], r["shape"]))
                except json.JSONDecodeError:
                    pass

    failures = 0
    with open(out_path, "a") as f:
        for arch, shape in pairs:
            if (arch, shape) in done:
                print(f"[dryrun] skip {arch} x {shape} (already recorded)")
                continue
            try:
                rec = dryrun_one(arch, shape, multi_pod=multi,
                                 microbatches=args.microbatches,
                                 ce_chunk=args.ce_chunk,
                                 seq_shard=args.seq_shard)
            except Exception as e:      # noqa: BLE001 — record and continue
                traceback.print_exc()
                rec = {"arch": arch, "shape": shape,
                       "mesh": "2x16x16" if multi else "16x16",
                       "error": repr(e)[:500]}
                failures += 1
            f.write(json.dumps(rec) + "\n")
            f.flush()
    print(f"[dryrun] complete; {failures} failures -> {out_path}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
