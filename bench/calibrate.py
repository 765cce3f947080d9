"""Readings that set the benchmark's limits and rates, in one process.

    python3 bench/calibrate.py readings --workload W --seeds 1,2,3 --seconds S
    python3 bench/calibrate.py sweep --workload W --seed N --seconds S \\
        --rates 4,6,8

`--traffic FILE` runs another mix file in the cell's place (a mix the
benchmark once had, to repeat a reading); `--dump FILE` (readings) writes
the genotypes of the members the reference leaves out as ambiguous.

`readings` runs the cell once per seed, as `bench/run.py` would, and prints
for each the numbers `correct` compares, for the program and for the
control (the reference's Eqs. 1-2 in bfloat16 put in the program's place,
on the same sampled jobs).  The largest program reading and the smallest
control reading bound the limit (PERF.md, section 2).  A
seed whose window checked no job gives no reading: the command then exits
non-zero and prints no bounds.

`sweep` runs an open-loop cell at each rate and prints the median latency,
the queue at window open and close, the drain and the steps taken: the knee
is the highest rate whose queue does not grow through the window.

The benchmark's own runs never run this.  It needs the chip, as they do.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import generator, reference  # noqa: E402
from bench import run as B  # noqa: E402


def readings(bench, cell, config, mix, algo, seeds, seconds,
             dump=None) -> int:
    prob = reference.Problem(config["device"])
    metrics = B.cell_metrics(bench, cell, False)
    program, control, empty, ambiguous = [], [], [], []
    for seed in seeds:
        out = B.run_cell(cell, config, mix, algo, seed, seconds, False,
                         metrics)
        ctl = B.compare(algo, prob, out["copies"], control=True)
        print(json.dumps({"seed": seed, "correct": out["line"]["correct"],
                          "program": out["numbers"], "control": ctl,
                          "metrics": out["line"]["metrics"],
                          "notes": out["notes"]}), flush=True)
        if not (out["numbers"]["jobs_checked"] and ctl["jobs_checked"]):
            empty.append(seed)
            continue
        program.append(out["numbers"]["objective_gap"])
        control.append(ctl["objective_gap"])
        if dump:
            ambiguous += ambiguous_members(algo, prob, seed, out["copies"])
    if dump:
        Path(dump).parent.mkdir(parents=True, exist_ok=True)
        Path(dump).write_text(json.dumps(ambiguous))
    if empty:
        print(f"calibrate: no job checked for seeds {empty}: no reading; "
              "give the window more seconds", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"workload": cell["name"], "seeds": len(seeds),
                      "objective_gap_lower": max(program),
                      "objective_gap_control_min": min(control)}),
          flush=True)
    return 0


def ambiguous_members(algo, prob, seed, copies):
    """The genotypes of the checked jobs (the algorithm module's
    `candidates`) that the reference leaves out of `objective_gap`, with
    the program's objectives."""
    out = []
    for j, job in enumerate(copies):
        if job is None:
            continue
        for k, (g, objs) in enumerate(algo.candidates(job)):
            if reference.decode(prob, g)[2]:
                out.append({"seed": seed, "job": j, "member": k,
                            "objs": np.asarray(objs).tolist(),
                            **{part: [np.asarray(a).tolist() for a in g[part]]
                               for part in ("dist", "loc", "perm")}})
    return out


def sweep(bench, cell, config, mix, algo, seed, seconds, rates) -> None:
    metrics = B.cell_metrics(bench, cell, False)
    for rate in rates:
        out = B.run_cell(cell, config, dict(mix, rate_jobs_per_s=rate), algo,
                         seed, seconds, False, metrics)
        m = out["line"]["metrics"]
        print(json.dumps({
            "rate_jobs_per_s": rate, "correct": out["line"]["correct"],
            "attempted": out["line"]["attempted"],
            "failed": out["line"]["failed"],
            "p50_s": m.get("job_p50_s", {}).get("value"),
            "queue_at_open": out["notes"]["queue_at_open"],
            "queue_at_close": out["notes"]["queue_at_close"],
            "drain_s": out["notes"]["drain_s"],
            "late_ms_max": out["notes"].get("late_ms_max"),
            "steps": out["notes"]["steps"]}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("mode", choices=("readings", "sweep"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--traffic", default="")
    ap.add_argument("--dump", default="")
    args = ap.parse_args(argv)
    bench, cell, config, mix, algo = B.load_cell(args.workload)
    if args.traffic:
        mix = generator.load(args.traffic)
    B.start_chip(cell)
    if args.mode == "readings":
        return readings(bench, cell, config, mix, algo,
                        [int(s) for s in args.seeds.split(",")],
                        args.seconds, args.dump)
    sweep(bench, cell, config, mix, algo, args.seed, args.seconds,
          [float(r) for r in args.rates.split(",")])
    return 0


if __name__ == "__main__":
    sys.exit(main())
