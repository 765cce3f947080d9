"""`bench/trace_reduce.py`: exact arithmetic on a built trace, and a trace
recorded on a TPU v5e (trimmed) as the reduction meets it on the chip."""
import gzip
import sys
from pathlib import Path

import pytest
from jax.profiler import ProfileData

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run as B  # noqa: E402
from bench import trace_reduce  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"

BUILT = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 500000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 5000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 6000000 } }
  event_metadata { key: 1 value { id: 1
    name: "%while.1 = (s32[]) while(s32[] %p), body=%b" } }
  event_metadata { key: 2 value { id: 2
    name: "%fusion.1 = f32[8] fusion(f32[8] %x), kind=kLoop" } }
  event_metadata { key: 3 value { id: 3 name: "jit__step(7)" } }
  event_metadata { key: 4 value { id: 4
    name: "%wirelength2_pallas.7 = f32[8,128] custom-call(%a)" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 3500000 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "harvest" } } }
"""


def test_reduction_arithmetic_on_a_built_trace(tmp_path):
    (tmp_path / "t.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(BUILT))
    t = trace_reduce.reduce(tmp_path)
    # a while [1, 4] us with a fusion nested in it, a kernel at [6, 7] us
    assert t["busy_s"] == pytest.approx(4e-6)
    assert t["window_s"] == pytest.approx(6e-6)
    assert t["modules"] == {"jit__step": [pytest.approx(6e-6), 1]}
    assert t["ops"] == {"jit__step/%while.1": [pytest.approx(2e-6), 1],
                        "jit__step/%fusion.1": [pytest.approx(1e-6), 1],
                        "jit__step/%wirelength2_pallas.7":
                            [pytest.approx(1e-6), 1]}
    assert t["breakdown"]["device_ops"][0] == \
        ["jit__step/%while.1", pytest.approx(2e-6)]
    # the 2 us gap [4, 6] us is covered mostly by the host's "harvest"
    assert t["breakdown"]["idle_gaps"] == [["harvest", pytest.approx(2e-6)]]
    run = B.Run(cell={}, config={}, mix={}, trace=t)
    assert B.reader("device_idle_share.backlog")(run) == \
        pytest.approx(100 / 3)
    assert B.reader("step_device_ms.backlog")(run) == pytest.approx(6e-3)


def test_a_trace_without_device_operations_is_refused(tmp_path):
    text = BUILT.split("planes { id: 2")[0].replace(
        '"/device:TPU:0"', '"/device:CPU:0"')
    (tmp_path / "t.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    with pytest.raises(ValueError):
        trace_reduce.reduce(tmp_path)


def test_eval_roofline_arithmetic_on_a_built_trace(tmp_path):
    (tmp_path / "t.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(BUILT))
    config = B.load_cell("vu11p_nsga2.backlog")[2]
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    run = B.Run(cell={}, config=config, mix={}, peaks=peaks,
                trace=trace_reduce.reduce(tmp_path),
                pools=[{"evals_per_gen": 64}])
    # one Eq. 1 launch in the step program: 8 slots x 64 candidates
    want = 4 * 8 * 64 * (5 * 1999 + 1) / 819e9 / 1e-6
    assert B.reader("eval_roofline.backlog")(run) == pytest.approx(
        100 * want)
    assert run.notes["eval_roofline_bound"] == "bytes"


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    """One VU11P pool step as a TPU v5e recorded it in a traced backlog
    run (trimmed to that step's device and host events)."""
    d = tmp_path_factory.mktemp("v5e")
    (d / "step.xplane.pb").write_bytes(gzip.decompress(
        (DATA / "v5e_vu11p_backlog_step.xplane.pb.gz").read_bytes()))
    return trace_reduce.reduce(d)


def test_a_chip_trace_reduces_to_one_busy_step(chip_trace):
    t = chip_trace
    assert t["devices"] == 1
    assert t["modules"] == {"jit__step": [pytest.approx(0.157536483), 1]}
    assert t["busy_s"] == pytest.approx(0.157535966)
    assert 0 < t["busy_s"] <= t["window_s"]
    kernels = {k.split("/")[1].split(".")[0]: v for k, v in t["ops"].items()
               if "_pallas." in k}
    # one launch of each kernel per generation, four generations a step
    assert {k: v[1] for k, v in kernels.items()} == {
        "%wirelength2_pallas": 4, "%maxbbox_pallas": 4,
        "%domination_pallas": 4}
    assert sum(v[0] for v in t["ops"].values()) == pytest.approx(
        t["busy_s"], rel=1e-6)
    assert len(t["breakdown"]["device_ops"]) == trace_reduce.TOP


def test_chip_trace_metrics(chip_trace):
    _, cell, config, mix, _ = B.load_cell("vu11p_nsga2.backlog")
    run = B.Run(cell=cell, config=config, mix=mix, trace=chip_trace,
                peaks=B.peaks_for("TPU v5 lite"),
                pools=[{"evals_per_gen": 64}])
    assert B.reader("step_device_ms.backlog")(run) == \
        pytest.approx(157.536483)
    assert 0 <= B.reader("device_idle_share.backlog")(run) < 0.01
    assert B.reader("eval_roofline.backlog")(run) == \
        pytest.approx(4.7611366, rel=1e-6)
