"""`bench/run.py` without a chip: it refuses to measure, and the rest of a
run (set-up, window, drain, reference check, readers) works at
`xcvu_test` size on the CPU, where the control comes out as not correct."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import reference  # noqa: E402
from bench import run as B  # noqa: E402

TINY = {"name": "tiny", "algorithm": "nsga2",
        "device": {"name": "xcvu_test", "units_per_rect": 6,
                   "n_uram_cols": 2, "n_dsp_cols": 4, "n_bram_cols": 2,
                   "seed": 7},
        "search": {"algorithm": {"pop_size": 8}, "n_slots": 2,
                   "gens_per_step": 2}}
HYPER = {"sbx_eta": [5.0, 25.0], "real_mut_prob": [0.05, 0.3]}
CLOSED = {"loop": "closed", "clients": 4, "budget": [8, 8],
          "budget_multiple": 2, "hyper": HYPER}
OPEN = {"loop": "open", "rate_jobs_per_s": 6.0, "preroll_s": 0.5,
        "budget": [4, 32], "budget_multiple": 2, "hyper": HYPER}


def tiny_run(mix, seed=2 ** 31 + 5, metrics=("setup_s",), trace=False):
    return B.run_cell({"name": "tiny", "chips": 1}, TINY, mix, seed, 1.0,
                      trace, [{"name": m, "unit": "u"} for m in metrics],
                      check_share=1.0, check_jobs=6)


def no_chip_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    return env


def test_refuses_without_a_tpu():
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "vu11p_nsga2.backlog", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=no_chip_env(),
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_refuses_in_a_directory_of_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "vu11p_nsga2.open",
         "--seed", "3", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        env=no_chip_env(), capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and not p.stdout.strip()


@pytest.mark.parametrize("mix,metrics", [
    (CLOSED, ("evals_per_s", "setup_s", "step_ms.backlog", "compile_s")),
    (OPEN, ("job_p50_s", "setup_s"))])
def test_a_sound_run_is_correct(mix, metrics):
    out = tiny_run(mix, metrics=metrics)
    line = out["line"]
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == set(metrics)
    assert out["notes"]["window_compiles"] == 0
    assert out["notes"]["jobs_checked"] > 0
    assert list(line)[-1] == "checks"
    # the control, bfloat16 Eqs. 1-2 in the program's place, must fail
    control = B.compare(reference.Problem(TINY["device"]), out["copies"],
                        control=True)
    assert control["objective_gap"] > 3 * B.LIMITS["objective_gap"]
    assert control["objective_gap"] > 30 * out["numbers"]["objective_gap"]


def test_readings_without_a_checked_job_give_no_bounds(monkeypatch, capsys):
    from bench import calibrate
    nothing = B.compare(reference.Problem(TINY["device"]), [])
    monkeypatch.setattr(B, "run_cell", lambda *a, **k: {
        "numbers": nothing, "copies": [], "notes": {},
        "line": {"correct": False, "metrics": {}}})
    cell = {"name": "tiny", "chips": 1}
    rc = calibrate.readings({"end_to_end": [], "per_layer": []}, cell, TINY,
                            CLOSED, [1, 2], 1.0)
    assert rc != 0
    out = capsys.readouterr()
    assert "objective_gap_lower" not in out.out
    assert "no job checked" in out.err
