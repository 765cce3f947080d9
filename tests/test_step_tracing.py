"""The step program's phase scopes and the pool's leaf spans.

Every operation of the pool's step carries one of the phases of a
generation in its `op_name` (a device trace charges its time to it); a
traced `step()` records `pool.dispatch` / `pool.readback` /
`pool.harvest` leaf spans inside `pool.step`, each with its parent and
CPU time, and a job's admission a `job.init`; untraced, nothing is
recorded and no profiler annotation is built; and tracing changes no
result."""
import collections
import re

import jax
import numpy as np
import pytest

from repro.core.nsga2 import NSGA2Config
from repro.serve import tracing
from repro.serve.api import JobRequest
from repro.serve.placement_service import PlacementService

CFG = NSGA2Config(pop_size=8)
# the `jax.named_scope`s of a generation (`core/nsga2.py`,
# `core/objectives.py`), as `bench/scope_reduce.py` reads them
PHASES = ("rank", "select", "vary", "decode", "evaluate")


@pytest.fixture(scope="module")
def pool(small_problem):
    return PlacementService(small_problem, CFG, n_slots=2, gens_per_step=2)


@pytest.fixture()
def traced():
    assert not tracing.enabled()
    tracing.enable()
    tracing.tracer().clear()
    try:
        yield tracing.tracer()
    finally:
        tracing.tracer().clear()
        tracing.disable(close_sinks=False)


def _computations(hlo):
    head = re.compile(r"^(ENTRY )?%?([\w.\-]+) .*\{\s*$")
    comps, entry, cur = {}, None, None
    for line in hlo.splitlines():
        m = head.match(line)
        if m:
            cur = m.group(2)
            comps[cur] = []
            entry = cur if m.group(1) else entry
        elif line.startswith("  ") and cur:
            comps[cur].append(line)
    return comps, entry


def _op_phases(hlo):
    """The phase of every instruction of an unoptimised HLO module, each
    nested function's op names taken under its call site's (a loop body's
    names already hold their function's)."""
    comps, entry = _computations(hlo)
    out = collections.Counter()

    def walk(comp, prefix):
        for line in comps[comp]:
            m = re.search(r'op_name="([^"]*)"', line)
            name = "/".join(x for x in (prefix, m.group(1) if m else "") if x)
            phase = [p for p in name.split("/") if p in PHASES]
            out[phase[-1] if phase else None] += 1
            if re.search(r"[\s)]while\(", line):
                for k in ("condition", "body"):
                    walk(re.search(k + r"=%?([\w.\-]+)", line).group(1),
                         prefix)
            elif re.search(r"[\s)]call\(", line):
                walk(re.search(r"to_apply=%?([\w.\-]+)", line).group(1),
                     name)
    walk(entry, "")
    return out


def test_the_lowered_step_carries_a_phase_on_95_percent_of_its_ops(pool):
    hlo = pool.lowered_step().as_text(dialect="hlo", debug_info=True)
    count = _op_phases(hlo)
    total = sum(count.values())
    assert set(count) - {None} == set(PHASES)
    assert count[None] / total <= 0.05, count


def test_the_compiled_step_keeps_the_phases_in_its_op_names(pool):
    """What the profiler reports as each operation's `tf_op` is the
    compiled instruction's `op_name`: XLA's fusion and inlining keep the
    phases there."""
    text = pool.lowered_step().compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    found = {p for n in names for p in n.split("/") if p in PHASES}
    assert found == set(PHASES)


def test_a_traced_step_nests_its_leaf_spans_under_pool_step(pool, traced):
    jids = [pool.submit(JobRequest(cfg=CFG, seed=s, budget=2))
            for s in (3, 4)]
    assert None not in jids
    finished = pool.step()
    assert len(finished) == 2
    ends = [e for e in traced.events() if e.kind == "end"]
    by_name = collections.defaultdict(list)
    for e in ends:
        by_name[e.name].append(e)
    assert len(by_name["pool.step"]) == 1
    assert by_name["pool.step"][0].attrs["parent"] is None
    for name in ("pool.dispatch", "pool.readback"):
        (e,) = by_name[name]
        assert e.attrs["parent"] == "pool.step"
        assert e.attrs["cpu_ms"] >= 0
    harvests = by_name["pool.harvest"]
    assert sorted(e.trace_id for e in harvests) == sorted(
        j.trace_id for j in finished)
    assert all(e.attrs["parent"] == "pool.step" for e in harvests)
    inits = by_name["job.init"]
    assert sorted(e.trace_id for e in inits) == sorted(
        j.trace_id for j in finished)
    assert all(e.attrs["parent"] is None for e in inits)
    # the job's own trace now holds its init and harvest phases
    phases = dict(tracing.span_pairs(traced.events(finished[0].trace_id)))
    assert set(phases) == {"job.init", "pool.harvest"}


def test_leaf_spans_annotate_the_profiler_and_enclosing_ones_do_not(
        pool, traced, monkeypatch):
    built = []

    class Annotation:
        def __init__(self, name, **kw):
            built.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    pool.submit(JobRequest(cfg=CFG, seed=5, budget=2))
    pool.step()
    assert built == ["job.init", "pool.dispatch", "pool.readback",
                     "pool.harvest"]


def test_untraced_steps_record_nothing_and_build_no_annotation(
        pool, monkeypatch):
    assert not tracing.enabled()

    def refuse(*a, **k):
        raise AssertionError("TraceAnnotation built with tracing off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    before = len(tracing.tracer().events())
    pool.submit(JobRequest(cfg=CFG, seed=6, budget=2))
    assert len(pool.step()) == 1
    assert len(tracing.tracer().events()) == before
    assert tracing.tracer().span("pool.step") is tracing.tracer().span("x")


def test_tracing_changes_no_result(pool):
    req = JobRequest(cfg=CFG, seed=11, budget=4)
    (off,) = pool.run_jobs([req])
    tracing.enable()
    try:
        (on,) = pool.run_jobs([req])
    finally:
        tracing.tracer().clear()
        tracing.disable(close_sinks=False)
    np.testing.assert_array_equal(off.best_objs, on.best_objs)
    for t in off.genotype:
        for a, b in zip(off.genotype[t], on.genotype[t]):
            np.testing.assert_array_equal(a, b)


def test_stats_serve_the_step_histogram_of_the_pools_label(pool):
    """`stats()["step_ms_hist"]` is the registry's `repro_service_step_ms`
    under the pool's label: one observation per step taken."""
    s = pool.stats()
    assert s["step_ms_hist"]["count"] == pool.total_steps > 0
    from repro.runtime import telemetry
    text = telemetry.registry().prometheus_text()
    assert f'repro_service_step_ms_count{{pool="{pool.label}"}}' in text
