"""The readers of the program's spans and scopes: `bench/spans.py`,
`bench/scope_reduce.py` and the metrics that use them, on built runs and
events, on built traces whose operations carry `tf_op` stats, and on the
trimmed v5e trace (whose program had no scopes)."""
import gzip
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest
from jax.profiler import ProfileData

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run as B  # noqa: E402
from bench import scope_reduce, spans, trace_reduce  # noqa: E402
from repro.serve.tracing import TraceEvent  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
T0 = 1000.0                       # window open, on the events' clock
PHASE_METRICS = ("rank_device_ms.backlog", "decode_device_ms.backlog",
                 "select_device_ms.backlog", "vary_device_ms.backlog")


def ev(name, kind, ts, tid=1, trace=None, **attrs):
    return TraceEvent(name=name, kind=kind, ts=ts, wall=ts, trace_id=trace,
                      tid=tid, attrs=attrs)


def span(name, t0, t1, tid=1, trace=None, parent=None, cpu_ms=0.0,
         **attrs):
    return [ev(name, "begin", t0, tid, trace, **attrs),
            ev(name, "end", t1, tid, trace, parent=parent, cpu_ms=cpu_ms,
               **attrs)]


def built_run(events, seconds=10.0, jobs=()):
    return B.Run(cell={}, config={}, mix={}, seconds=seconds,
                 setup_s=T0 - B.T_START, events=list(events),
                 jobs=list(jobs))


def step(pool, t, dispatch_ms, device_ms, host_ms, tid=1):
    """One pool step at `t`: dispatch, the readback waiting on the device,
    then `host_ms` of harvest-free host work inside the step."""
    d1 = t + dispatch_ms / 1e3
    r1 = d1 + device_ms / 1e3
    return ([ev("pool.step", "begin", t, tid, pool=pool)]
            + span("pool.dispatch", t, d1, tid, parent="pool.step",
                   cpu_ms=dispatch_ms, pool=pool)
            + span("pool.readback", d1, r1, tid, parent="pool.step",
                   cpu_ms=0.1, pool=pool)
            + [ev("pool.step", "end", r1 + host_ms / 1e3, tid,
                  parent=None, pool=pool)])


def job(trace_id, window=True):
    return B.JobRecord(job={}, request=None, window=window,
                       handle=SimpleNamespace(
                           request=SimpleNamespace(trace_id=trace_id)))


def test_spans_pair_per_thread_innermost_first():
    events = (span("a", 1.0, 4.0, tid=1)[:1]
              + span("a", 2.0, 3.0, tid=1, parent="a")
              + span("b", 2.5, 2.6, tid=2)
              + span("a", 1.0, 4.0, tid=1)[1:]
              + [ev("c", "end", 5.0)])          # no begin: dropped
    found = spans.spans(events)
    assert [(s.name, s.t0, s.t1, s.tid) for s in found] == [
        ("a", 2.0, 3.0, 1), ("b", 2.5, 2.6, 2), ("a", 1.0, 4.0, 1)]
    assert [s.name for s in spans.leaves(found)] == ["b"]
    assert found[0].attrs["parent"] == "a"


def test_the_window_is_set_up_plus_seconds_on_the_events_clock():
    run = built_run([], seconds=10.0)
    t_open, t_close = spans.window(run)
    assert t_open == pytest.approx(T0) and t_close == pytest.approx(T0 + 10)


def test_host_gap_is_readback_end_to_the_next_dispatch_end_per_pool():
    events = []
    # pool A: steps 500 ms apart with 2 ms dispatch and 120 ms, then
    # 100 ms, on the device: gaps 500 - 122 + 2 and 500 - 102 + 2 ms
    for k in range(3):
        events += step("A", T0 + 0.5 * k, 2.0, 120.0 if k == 0 else 100.0,
                       5.0)
    # pool B interleaved on another thread: steps 300 ms apart
    for k in range(2):
        events += step("B", T0 + 0.1 + 0.3 * k, 1.0, 50.0, 0.0, tid=2)
    # a step before the window opens: its readback is not counted
    events = step("A", T0 - 0.5, 2.0, 100.0, 0.0) + events
    run = built_run(events)
    gaps = [380.0, 400.0, 300.0 - 51.0 + 1.0]
    got = B.reader("host_gap_ms.backlog")(run)
    assert got == pytest.approx(sum(gaps) / len(gaps))
    assert run.notes["longest_leaf_span"] == "pool.readback"
    assert run.notes["longest_leaf_ms"] == pytest.approx(120.0)
    assert run.notes["longest_leaf_cpu_ms"] == pytest.approx(0.1)
    assert run.notes["longest_leaf_at_s"] == pytest.approx(0.002)


def test_harvest_and_init_are_means_over_the_window_jobs():
    events = (span("job.init", T0 + 1.0, T0 + 1.010, trace="j1",
                   cpu_ms=9.0)
              + span("job.init", T0 + 2.0, T0 + 2.030, trace="j2")
              + span("job.init", T0 + 3.0, T0 + 3.500, trace="late")
              + span("pool.harvest", T0 + 4.0, T0 + 4.020, trace="j1",
                     parent="pool.step")
              + span("pool.harvest", T0 + 5.0, T0 + 5.040, trace="j2",
                     parent="pool.step"))
    run = built_run(events, jobs=[job("j1"), job("j2"),
                                  job("late", window=False)])
    assert B.reader("job_init_ms.open")(run) == pytest.approx(20.0)
    assert B.reader("harvest_ms.open")(run) == pytest.approx(30.0)
    # the longest leaf of the window, whoever's job it was
    assert run.notes["longest_leaf_span"] == "job.init"
    assert run.notes["longest_leaf_ms"] == pytest.approx(500.0)


def test_span_readers_read_nothing_from_a_program_without_the_spans():
    # the events a program before the leaf spans recorded: pool.step and
    # job instants, no parent, no cpu_ms, no dispatch or readback
    events = [ev("pool.step", "begin", T0 + 1), ev("job.admitted",
                                                   "instant", T0 + 1.1,
                                                   trace="j1"),
              ev("pool.step", "end", T0 + 1.2)]
    run = built_run(events, jobs=[job("j1")])
    for name in ("host_gap_ms.backlog", "harvest_ms.open",
                 "job_init_ms.open"):
        assert B.reader(name)(run) is None


def op(key, hlo, tf_op=None, program="7"):
    stats = "" if tf_op is None else (
        f'stats {{ metadata_id: 90 str_value: "{tf_op}" }} ')
    stats += f"stats {{ metadata_id: 91 uint64_value: {program} }}"
    return (f"  event_metadata {{ key: {key} value {{ id: {key} "
            f'name: "{hlo}" {stats} }} }}\n')


# two executions of the step program 7 (5 us and 3 us) and one op of
# program 8 whose instruction name is also `%fusion.2`
BUILT = ("""
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 500000 }
    events { metadata_id: 4 offset_ps: 4500000 duration_ps: 500000 }
    events { metadata_id: 1 offset_ps: 6000000 duration_ps: 3000000 }
    events { metadata_id: 7 offset_ps: 9500000 duration_ps: 400000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 5 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 5 offset_ps: 6000000 duration_ps: 3000000 }
    events { metadata_id: 6 offset_ps: 9500000 duration_ps: 400000 } }
""" + op(1, "%fusion.1 = f32[8] fusion(f32[8] %x), kind=kLoop",
         "jit(_step)/vmap()/while/body/closed_call/rank/while/body/add:")
    + op(2, "%fusion.2 = s32[8] fusion(s32[8] %y), kind=kLoop",
         "jit(_step)/vmap()/while/body/closed_call/vary/scatter:")
    + op(3, "%wirelength2_pallas.7 = f32[8,128] custom-call(%a)",
         "jit(_step)/vmap()/jit(evaluate_population)/evaluate/"
         "wirelength2_pallas/pallas_call:")
    + op(4, "%copy.3 = f32[8] copy(f32[8] %x)")
    + op(7, "%fusion.2 = s32[8] fusion(s32[8] %z), kind=kLoop",
         "jit(member_init)/decode/gather:", program="8")
    + """  event_metadata { key: 5 value { id: 5 name: "jit__step(7)" } }
  event_metadata { key: 6 value { id: 6 name: "jit_member_init(8)" } }
  stat_metadata { key: 90 value { id: 90 name: "tf_op" } }
  stat_metadata { key: 91 value { id: 91 name: "program_id" } } }
""")


def traced_run(trace_dir, monkeypatch, text):
    (trace_dir / "t.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    monkeypatch.setattr(B, "TRACE_DIR", trace_dir)
    run = built_run([])
    run.trace = trace_reduce.reduce(trace_dir)
    return run


def test_phases_charge_each_step_operation_by_its_tf_op(tmp_path,
                                                        monkeypatch):
    run = traced_run(tmp_path, monkeypatch, BUILT)
    # two executions of the step: 8 us of device time, 4 us a step
    assert scope_reduce.phase_ms(run) == pytest.approx(
        {"rank": 3e-3, "vary": 0.5e-3, "evaluate": 0.25e-3,
         "select": 0.0, "decode": 0.0})
    assert B.reader("rank_device_ms.backlog")(run) == pytest.approx(3e-3)
    assert B.reader("vary_device_ms.backlog")(run) == pytest.approx(5e-4)
    assert B.reader("select_device_ms.backlog")(run) == 0.0
    # the other program's `%fusion.2` is in `decode`: not the step's
    assert B.reader("decode_device_ms.backlog")(run) == 0.0
    assert run.notes["scope_source"] == "tf_op"
    assert run.notes["evaluate_device_ms"] == pytest.approx(2.5e-4)
    # the step's 4 us less the phases' 3.75 us: the copy with no tf_op
    assert run.notes["unscoped_device_ms"] == pytest.approx(2.5e-4)


def test_a_string_stat_kept_by_reference_is_read(tmp_path, monkeypatch):
    text = BUILT.replace(
        'metadata_id: 90 str_value: "jit(_step)/vmap()/while/body/'
        'closed_call/rank/while/body/add:"', "metadata_id: 90 ref_value: 92")
    text = text.replace(
        "  stat_metadata { key: 90",
        '  stat_metadata { key: 92 value { id: 92 name: "jit(_step)/'
        'select/gather:" } }\n  stat_metadata { key: 90')
    run = traced_run(tmp_path, monkeypatch, text)
    assert B.reader("select_device_ms.backlog")(run) == pytest.approx(3e-3)
    assert B.reader("rank_device_ms.backlog")(run) == 0.0


def test_phases_read_nothing_from_a_program_without_scopes(tmp_path,
                                                          monkeypatch):
    run = traced_run(tmp_path, monkeypatch, BUILT.replace(
        "closed_call/rank/", "closed_call/").replace(
        "closed_call/vary/", "closed_call/").replace("/evaluate/", "/"))
    assert scope_reduce.phase_ms(run) is None
    for name in PHASE_METRICS:
        assert B.reader(name)(run) is None
    assert "scope_source" not in run.notes


def test_the_recorded_chip_trace_has_no_scopes_and_reads_none(
        tmp_path, monkeypatch):
    """The trimmed v5e trace comes from a program before the scopes, and
    was trimmed to names: every phase metric reads None, not a wrong
    number, while the step's device time still reads."""
    (tmp_path / "step.xplane.pb").write_bytes(gzip.decompress(
        (DATA / "v5e_vu11p_backlog_step.xplane.pb.gz").read_bytes()))
    monkeypatch.setattr(B, "TRACE_DIR", tmp_path)
    run = built_run([])
    run.trace = trace_reduce.reduce(tmp_path)
    assert B.reader("step_device_ms.backlog")(run) == pytest.approx(
        157.536483)
    for name in PHASE_METRICS:
        assert B.reader(name)(run) is None
