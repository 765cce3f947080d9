"""`bench/run.py` without a chip: it refuses to measure, and the rest of a
run (set-up, window, drain, reference check, readers) works at
`xcvu_test` size on the CPU, with the search algorithm taken from a
module file (`bench/algorithms/<algorithm>.py`) that it finds by name."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest
from jax.profiler import ProfileData

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from bench import reference, trace_reduce  # noqa: E402
from bench import run as B  # noqa: E402
from test_bench_trace import BUILT  # noqa: E402

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


NSGA2 = B.algorithm("nsga2")


def tiny_run(mix, seed=2 ** 31 + 5, metrics=("setup_s",), trace=False,
             config=TINY, algo=NSGA2):
    return B.run_cell({"name": "tiny", "chips": 1}, config, mix, algo, seed,
                      1.0, trace, [{"name": m, "unit": "u"} for m in metrics],
                      check_share=1.0, check_jobs=6)


def tiny_root(tmp, algorithm, source=None):
    """A checkout holding one tiny closed-loop cell whose configuration
    names `algorithm`, with `source` (if any) as that algorithm's module
    file."""
    files = [("bench/configs", "tiny.json",
              json.dumps(dict(TINY, algorithm=algorithm))),
             ("bench/traffic", "closed.json", json.dumps(CLOSED))]
    if source is not None:
        files.append(("bench/algorithms", f"{algorithm}.py", source))
    for d, name, text in files:
        (tmp / d).mkdir(parents=True, exist_ok=True)
        (tmp / d / name).write_text(text)
    (tmp / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "tiny", "file": "bench/configs/tiny.json"}],
        "workloads": [{"name": "tiny.closed", "config": "tiny",
                       "traffic": "closed", "chips": 1}]}))
    return tmp


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


def test_an_algorithm_from_a_new_file_runs(tmp_path):
    source = (ROOT / "bench" / "algorithms" / "nsga2.py").read_text()
    root = tiny_root(tmp_path, "nsga2_copy", source)
    _, cell, config, mix, algo = B.load_cell("tiny.closed", root=root)
    assert Path(algo.__file__) == root / "bench/algorithms/nsga2_copy.py"
    out = tiny_run(mix, config=config, algo=algo)
    line = out["line"]
    assert line["correct"], line["checks"]
    assert out["notes"]["jobs_checked"] > 0
    assert [(k, c["limit"]) for k, c in line["checks"].items()] == \
        list(B.limits(NSGA2).items())
    assert list(B.limits(NSGA2)) == [
        "objective_gap", "illegal_placements", "selection_misses",
        "rank_inversions", "unchecked_jobs"]
    # the same copies give the same numbers through either module
    prob = reference.Problem(config["device"])
    for control in (False, True):
        assert B.compare(algo, prob, out["copies"], control) == \
            B.compare(NSGA2, prob, out["copies"], control)


def test_an_unknown_algorithm_is_refused_before_jax(tmp_path):
    root = tiny_root(tmp_path / "root", "bogus")
    shutil.copytree(ROOT / "bench", root / "bench", dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__", "configs",
                                                  "traffic", "algorithms"))
    fake = tmp_path / "fake"                  # a jax that cannot be imported
    (fake / "jax").mkdir(parents=True)
    (fake / "jax" / "__init__.py").write_text(
        "raise ImportError('jax was imported')\n")
    with pytest.raises(SystemExit, match="bench/algorithms/bogus.py"):
        B.load_cell("tiny.closed", root=root)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "tiny.closed",
         "--seed", "3", "--seconds", "1", "--trace", "0"], cwd=root,
        env=dict(no_chip_env(), PYTHONPATH=str(fake)), capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0 and not p.stdout.strip()
    assert "bench/algorithms/bogus.py" in p.stderr
    assert "jax was imported" not in p.stderr


def test_evals_come_from_the_algorithm_module(tmp_path):
    source = (ROOT / "bench" / "algorithms" / "nsga2.py").read_text() + (
        "\n\ndef evals_per_gen(cfg, prob):\n    return 3 * cfg.pop_size\n")
    root = tiny_root(tmp_path / "root", "nsga2_x3", source)
    _, _, config, mix, algo = B.load_cell("tiny.closed", root=root)
    out = tiny_run(mix, metrics=("evals_per_s",), config=config, algo=algo)
    run, pop = out["run"], config["search"]["algorithm"]["pop_size"]
    assert [p["evals_per_gen"] for p in run.pools] == [3 * pop]
    gens = sum(p["useful_gens"] for p in run.pools)
    assert gens > 0
    assert out["line"]["metrics"]["evals_per_s"]["value"] == \
        pytest.approx(gens * 3 * pop / run.seconds)
    # one Eq. 1 launch in the step program: slots x the module's count
    (tmp_path / "t.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(BUILT))
    run.trace = trace_reduce.reduce(tmp_path)
    run.peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    nets = reference.Problem(config["device"]).n_nets
    want = 4 * config["search"]["n_slots"] * 3 * pop * (5 * nets + 1)
    assert B.reader("eval_roofline.backlog")(run) == pytest.approx(
        100 * want / 819e9 / 1e-6)


def test_readings_without_a_checked_job_give_no_bounds(monkeypatch, capsys):
    from bench import calibrate
    nothing = B.compare(NSGA2, reference.Problem(TINY["device"]), [])
    monkeypatch.setattr(B, "run_cell", lambda *a, **k: {
        "numbers": nothing, "copies": [], "notes": {},
        "line": {"correct": False, "metrics": {}}})
    cell = {"name": "tiny", "chips": 1}
    rc = calibrate.readings({"end_to_end": [], "per_layer": []}, cell, TINY,
                            CLOSED, NSGA2, [1, 2], 1.0)
    assert rc != 0
    out = capsys.readouterr()
    assert "objective_gap_lower" not in out.out
    assert "no job checked" in out.err
