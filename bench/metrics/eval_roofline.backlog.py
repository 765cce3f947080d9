"""eval_roofline.backlog: the evaluation kernels' share of their roofline.

The least time of the work they did in the traced slice
(`bench/roofline.py`, from the problem's shapes, over `bench/peaks.json`
for this chip) over their device time in the trace.  Kernels are found by
their instruction names, `%wirelength2_pallas.<n>` (Eq. 1) and
`%maxbbox_pallas.<n>` (Eq. 2).  A launch inside the pool's step program
evaluates slots x the placements a job evaluates per generation (the
algorithm module's `evals_per_gen`, as the pools report it; vacant slots
too: the kernel runs them); a launch in any other program, a job's init,
that count for one job.  Pools that evaluate different counts give no reading:
the trace does not say which pool a launch served.
"""
from bench import reference, roofline

WIRELENGTH, BBOX = "%wirelength2_pallas", "%maxbbox_pallas"
STEP = "jit__step"


def read(run):
    counts = {p["evals_per_gen"] for p in run.pools}
    if run.trace is None or run.peaks is None or len(counts) != 1:
        return None
    s = run.config["search"]
    (evals,) = counts
    prob = reference.Problem(run.config["device"])
    seconds = 0.0
    flops = nbytes = 0.0
    for key, (secs, count) in run.trace["ops"].items():
        module, op = key.split("/", 1)
        per_launch = evals * (s["n_slots"] if module == STEP else 1)
        if op.startswith(WIRELENGTH + "."):
            f, b = roofline.wirelength(per_launch * count, prob.n_nets)
        elif op.startswith(BBOX + "."):
            f, b = roofline.maxbbox(per_launch * count, prob.n_units,
                                    reference.BLOCKS_PER_UNIT)
        else:
            continue
        seconds, flops, nbytes = seconds + secs, flops + f, nbytes + b
    if seconds <= 0:
        return None
    least, bound = roofline.least_time(flops, nbytes, run.peaks)
    run.notes["eval_roofline_bound"] = bound
    return 100.0 * least / seconds
