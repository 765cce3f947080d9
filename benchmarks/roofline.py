"""SSRoofline: aggregate the dry-run artifacts into the three-term table.

Reads experiments/dryrun/*.json (written by launch/dryrun.py), prints the
per-(arch x shape x mesh) roofline terms, flags the dominant bottleneck, and
nominates the three hillclimb cells: worst roofline fraction, most
collective-bound, and most representative of the paper's technique (the
expert-placement MoE cell).

`--kernels` switches to the evaluation-pipeline roofline: an analytic
fused-vs-unfused bytes/flops model of the placement evaluation at the
workload shape recorded in the bench JSON's `kernels` section (achieved
evals/sec vs the memory- and compute-bound peaks).  Both paths pay for
materialising the gathered endpoint and unit-coordinate tensors in HBM
(written by the XLA gather, read back by the kernel): the TPU kernel
compiler has no in-kernel lane gather, so the fused kernel saves a launch,
not those bytes.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
from typing import Dict, List

PEAK_FLOPS = 197e12
HBM_BW = 819e9


def load(dirname: str = "experiments/dryrun") -> List[Dict]:
    rows = []
    for path in sorted(glob.glob(os.path.join(dirname, "*.json"))):
        with open(path) as f:
            rows.append(json.load(f))
    return rows


def table(rows: List[Dict], mesh: str = "pod16x16") -> None:
    print("arch,shape,mesh,status,peak_GiB,compute_s,memory_s,collective_s,"
          "dominant,useful_ratio,roofline_fraction")
    for r in rows:
        if r["mesh"] != mesh:
            continue
        t = r.get("roofline", {})
        if r["status"] != "ok" or "compute_s" not in t:
            # skipped cells and non-LM cells (vu_systolic executes the EA
            # live rather than lowering a step; no roofline terms)
            print(f"{r['arch']},{r['shape']},{r['mesh']},{r['status']},,,,,"
                  f",,")
            continue
        peak = r["memory"]["peak_estimate_bytes"] / 2 ** 30
        # roofline fraction: useful-compute time / achievable step bound
        bound = max(t["compute_s"], t["memory_s"], t["collective_s"])
        chips = 512 if "2x16" in r["mesh"] else 256
        ideal = t["model_flops"] / (chips * PEAK_FLOPS)
        frac = ideal / bound if bound else 0.0
        print(f"{r['arch']},{r['shape']},{r['mesh']},ok,{peak:.2f},"
              f"{t['compute_s']:.4f},{t['memory_s']:.4f},"
              f"{t['collective_s']:.4f},{t['dominant']},"
              f"{t['useful_ratio']:.3f},{frac:.4f}")


def nominate(rows: List[Dict]) -> None:
    ok = [r for r in rows if r["status"] == "ok"
          and r["mesh"] == "pod16x16"
          and "compute_s" in r.get("roofline", {})]

    def frac(r):
        t = r["roofline"]
        bound = max(t["compute_s"], t["memory_s"], t["collective_s"])
        return (t["model_flops"] / (256 * PEAK_FLOPS)) / bound if bound else 0

    def coll_share(r):
        t = r["roofline"]
        tot = t["compute_s"] + t["memory_s"] + t["collective_s"]
        return t["collective_s"] / tot if tot else 0

    worst = min(ok, key=frac)
    collb = max(ok, key=coll_share)
    moe = [r for r in ok
           if r["arch"] == "deepseek-moe-16b" and r["shape"] == "train_4k"]
    print("\n# hillclimb nominations:")
    print(f"#  worst roofline fraction: {worst['arch']} x {worst['shape']} "
          f"({frac(worst):.4f})")
    print(f"#  most collective-bound:   {collb['arch']} x {collb['shape']} "
          f"({100*coll_share(collb):.1f}% of step)")
    if moe:
        print("#  paper-representative:    deepseek-moe-16b x train_4k "
              "(expert placement == hard-block placement)")


def kernel_roofline(bench_path: str = "BENCH_placement.json") -> None:
    """Analytic fused-vs-unfused roofline for the evaluation pipeline.

    Shape comes from the bench JSON's `kernels` section; bytes/flops are
    derived, not measured, so this runs anywhere (no jax import).
    """
    with open(bench_path) as f:
        report = json.load(f)
    k = report.get("kernels")
    if not k:
        print(f"# {bench_path} has no kernels section; re-run "
              "PYTHONPATH=src python -m benchmarks.bench_service first")
        return
    p, n, u, g = (k["pop_size"], k["n_nets"], k["n_units"], k["n_gids"])
    b = g // u                                      # blocks per unit
    f4 = 4                                          # f32/int32 bytes
    # both paths read the same operands once and write two scalars/row
    base = f4 * (2 * p * g + 3 * n + u * b + 2 * p)
    # both paths also materialise the gathered endpoint tensors
    # (x1,y1,x2,y2: [P,N] each) and unit tensors (ux,uy: [P,U,B] each),
    # each written by the XLA gather then read back by the kernel
    extra = f4 * 2 * (4 * p * n + 2 * p * u * b)
    flops = p * (9 * n + 6 * u * b)                 # Eq.1 + Eq.2 arithmetic
    print("path,bytes,flops,intensity_f_per_b,mem_bound_s,compute_bound_s,"
          "peak_evals_per_sec,achieved_evals_per_sec,fraction_of_peak")
    for name, nbytes, achieved in (
            ("fused", base + extra, k.get("evals_per_sec_fused")),
            ("unfused", base + extra, k.get("evals_per_sec_unfused"))):
        mem_s = nbytes / HBM_BW
        cmp_s = flops / PEAK_FLOPS
        peak = p / max(mem_s, cmp_s)
        frac = (achieved / peak) if achieved else 0.0
        print(f"{name},{nbytes},{flops},{flops / nbytes:.3f},"
              f"{mem_s:.3e},{cmp_s:.3e},{peak:.3e},"
              f"{achieved or ''},{frac:.2e}")
    print(f"# both paths move {base + extra} HBM bytes at equal flops; "
          f"{extra / (base + extra):.1%} of them are the gathered tensors "
          "the kernels read back.")


def main(dirname: str = "experiments/dryrun") -> None:
    rows = load(dirname)
    if not rows:
        print("# no dry-run artifacts found; run "
              "PYTHONPATH=src python -m repro.launch.dryrun --all first")
        return
    for mesh in ("pod16x16", "pod2x16x16"):
        table(rows, mesh)
        print()
    nominate(rows)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="experiments/dryrun")
    ap.add_argument("--kernels", action="store_true",
                    help="evaluation-pipeline roofline (fused vs unfused)")
    ap.add_argument("--bench", default="BENCH_placement.json",
                    help="bench JSON supplying the kernels workload shape")
    args = ap.parse_args()
    if args.kernels:
        kernel_roofline(args.bench)
    else:
        main(args.dir)
