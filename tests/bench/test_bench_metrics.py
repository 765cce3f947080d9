"""Metric arithmetic, the shape of BENCHMARK.json, and the result line."""
import io
import json
import math
import re
import sys
from contextlib import redirect_stderr, redirect_stdout
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import roofline  # noqa: E402
from bench import run as B  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def job(latency=None, due=10.0, failed=False, window=True):
    r = B.JobRecord(job={"index": 0, "budget": 16}, request=None, due=due,
                    submitted=due, window=window)
    if not failed:
        r.result, r.done = object(), due + latency
    else:
        r.error = "JobFailedError: gone"
    return r


def a_run(**kw):
    run = B.Run(cell={"name": "c"}, config={}, mix={})
    for k, v in kw.items():
        setattr(run, k, v)
    return run


def test_rates_are_over_the_whole_window():
    run = a_run(seconds=20.0, pools=[
        {"useful_gens": 1000, "evals_per_gen": 64, "steps": 40},
        {"useful_gens": 10, "evals_per_gen": 8, "steps": 10}])
    assert B.reader("evals_per_s")(run) == (1000 * 64 + 10 * 8) / 20.0
    assert B.reader("step_ms.backlog")(run) == 1e3 * 20.0 / 50
    assert B.reader("evals_per_s")(a_run(seconds=0.0)) is None


def test_percentiles_are_over_every_job_due_in_the_window():
    lat = [float(k) for k in range(1, 101)]             # 1 .. 100 s
    jobs = [job(x) for x in lat] + [job(999.0, window=False)]
    run = a_run(jobs=jobs)
    assert B.reader("job_p50_s")(run) == pytest.approx(50.0)
    # failed jobs miss every limit: half the jobs failed push the median
    # past all, and fewer only move it up
    run = a_run(jobs=jobs[:50] + [job(failed=True) for _ in range(50)])
    assert B.reader("job_p50_s")(run) == pytest.approx(50.0)
    run = a_run(jobs=jobs[:49] + [job(failed=True) for _ in range(51)])
    assert B.reader("job_p50_s")(run) is None
    run = a_run(jobs=jobs[10:] + [job(failed=True) for _ in range(10)])
    assert B.reader("job_p50_s")(run) == pytest.approx(60.0)
    assert math.isinf(job(failed=True).latency)


def test_queue_wait_reads_the_span_events():
    ev = SimpleNamespace
    a, b = job(1.0), job(1.0)
    a.handle = SimpleNamespace(request=SimpleNamespace(trace_id="t1"))
    b.handle = SimpleNamespace(request=SimpleNamespace(trace_id="t2"))
    events = [ev(name="job.submit", trace_id="t1", ts=1.0),
              ev(name="job.admitted", trace_id="t1", ts=1.004),
              ev(name="job.submit", trace_id="t2", ts=2.0),
              ev(name="job.queued", trace_id="t2", ts=2.001),
              ev(name="job.admitted", trace_id="t2", ts=2.010)]
    run = a_run(jobs=[a, b], events=events)
    assert B.reader("queue_wait_ms.open")(run) == pytest.approx(7.0)
    assert B.reader("queue_wait_ms.open")(a_run(jobs=[a, b])) is None


def test_roofline_counts_from_shapes():
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    f, b = roofline.wirelength(64, 1999)
    assert f == 8 * 64 * 1999 and b == 4 * 64 * (5 * 1999 + 1)
    f2, b2 = roofline.maxbbox(64, 80, 28)
    assert f2 == 4 * 64 * 80 * 28 and b2 == 4 * 64 * (2 * 80 * 28 + 1)
    t, bound = roofline.least_time(f + f2, b + b2, peaks)
    assert bound == "bytes" and t == pytest.approx((b + b2) / 819e9)


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench", "tests/bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert (ROOT / BENCH["command"][1]).is_file()
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
        assert json.loads((ROOT / c["file"]).read_text())["reduced"] == \
            c["reduced"]
    cells = {w["name"]: w for w in BENCH["workloads"]}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert len(w["why"]) <= 200
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        assert set(m.get("workloads", [])) <= set(cells)
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert "bound" not in m and m["moves"] in e2e
        movers = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", movers)) <= movers
    for name in cells:              # setup_s, one more e2e, one per-layer
        assert len(B.cell_metrics(BENCH, cells[name], False)) >= 2
        assert B.cell_metrics(BENCH, cells[name], True)


def test_the_result_line_is_last_and_checks_come_last_in_it():
    line = {"correct": True, "attempted": 3, "failed": 0,
            "metrics": {"setup_s": {"value": 1.5, "unit": "s"}},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                       "memory_peak_bytes": 1},
            "checks": {"objective_gap": {"value": 1e-7, "limit": 1e-4}}}
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        B.emit({"line": line, "notes": {"drain_s": 0.5}})
    last = json.loads(out.getvalue().strip().splitlines()[-1])
    assert list(last) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert err.getvalue().strip().splitlines()[-1] == \
        "check objective_gap 1e-07 limit 0.0001"
