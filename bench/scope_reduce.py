"""Charge the step program's device time to the phases of a generation.

The program runs each phase of an NSGA-II generation under a
`jax.named_scope` (`core/nsga2.py`, `core/objectives.py`): `rank`,
`select`, `vary`, `decode` and `evaluate`, so the `op_name` of every
operation holds one.  On a TPU the profiler keeps that name as the
`tf_op` stat of each operation's event metadata in the device plane
(`jit(_step)/vmap()/while/body/closed_call/rank/...`), beside the
operation's HLO text and its `program_id`.  `jax.profiler.ProfileData`
does not expose metadata stats, so this module reads them from the trace
file itself, through a copy of the few fields of the XSpace schema
(`tsl/profiler/protobuf/xplane.proto`) it needs.

The self time of each operation comes from `bench/trace_reduce.py`
(`run.trace["ops"]`); an operation of the step program (`jit__step`)
whose `tf_op` names no phase counts in the unscoped remainder.  Per
execution of the step program:

  phase_ms   {phase: device ms}
  notes      scope_source (the stat read), evaluate_device_ms,
             unscoped_device_ms (the step's device ms less the phases')
"""
from __future__ import annotations

import functools
import sys
from pathlib import Path
from typing import Dict, Optional

from bench import trace_reduce

PHASES = ("rank", "select", "vary", "decode", "evaluate")
STEP = "jit__step"
OP_NAME_STAT = "tf_op"
PROGRAM_STAT = "program_id"


@functools.cache
def _xspace_class():
    """A message class for the XSpace fields read here: planes, their
    event metadata (name, stats) and stat metadata (name)."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    pkg = "xplane_subset"

    def message(name, fields, map_entry=False):
        m = descriptor_pb2.DescriptorProto(name=name)
        m.options.map_entry = map_entry
        for number, fname, kind, ref, label in fields:
            f = m.field.add(name=fname, number=number, type=kind,
                            label=label)
            if ref:
                f.type_name = f".{pkg}.{ref}"
        return m

    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    i64, u64, text, msg = (F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_STRING,
                           F.TYPE_MESSAGE)
    plane = message("XPlane", [
        (2, "name", text, None, one),
        (4, "event_metadata", msg, "XPlane.EventMetadataEntry", many),
        (5, "stat_metadata", msg, "XPlane.StatMetadataEntry", many)])
    plane.nested_type.extend([
        message(f"{kind}MetadataEntry",
                [(1, "key", i64, None, one),
                 (2, "value", msg, f"X{kind}Metadata", one)],
                map_entry=True)
        for kind in ("Event", "Stat")])
    fd = descriptor_pb2.FileDescriptorProto(
        name=f"{pkg}.proto", package=pkg, syntax="proto3")
    fd.message_type.extend([
        message("XStat", [(1, "metadata_id", i64, None, one),
                          (3, "uint64_value", u64, None, one),
                          (4, "int64_value", i64, None, one),
                          (5, "str_value", text, None, one),
                          (7, "ref_value", u64, None, one)]),
        message("XEventMetadata", [(1, "id", i64, None, one),
                                   (2, "name", text, None, one),
                                   (5, "stats", msg, "XStat", many)]),
        message("XStatMetadata", [(1, "id", i64, None, one),
                                  (2, "name", text, None, one)]),
        plane,
        message("XSpace", [(1, "planes", msg, "XPlane", many)])])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{pkg}.XSpace"))


def _phase(op_name: str) -> Optional[str]:
    found = [p for p in op_name.split("/") if p in PHASES]
    return found[-1] if found else None


def _value(stat, names: Dict[int, str]) -> str:
    if stat.ref_value:                     # a string kept once, by id
        return names.get(stat.ref_value, "")
    return stat.str_value or str(stat.uint64_value or stat.int64_value)


def instruction_phases(path) -> Dict[str, str]:
    """{"%<instruction>": phase} for the operations of the step program
    (`jit__step(<program id>)`) in a trace file, from their `tf_op`."""
    space = _xspace_class().FromString(Path(path).read_bytes())
    out: Dict[str, str] = {}
    for plane in space.planes:
        if not trace_reduce.DEVICE.match(plane.name):
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        steps = {m.name[len(STEP) + 1:-1]
                 for m in plane.event_metadata.values()
                 if m.name.startswith(STEP + "(")}
        for meta in plane.event_metadata.values():
            stats = {names.get(s.metadata_id): _value(s, names)
                     for s in meta.stats}
            phase = _phase(stats.get(OP_NAME_STAT, ""))
            if phase and stats.get(PROGRAM_STAT) in steps:
                out[trace_reduce._op_name(meta.name)] = phase
    return out


def phase_ms(run) -> Optional[Dict[str, float]]:
    """Device ms of each phase per execution of the step program, or None
    when the trace holds no step or none of its operations names a phase
    (a program without the scopes)."""
    if run.trace is None or STEP not in run.trace["modules"]:
        return None
    trace_dir = sys.modules[type(run).__module__].TRACE_DIR
    phases = instruction_phases(trace_reduce.newest_trace(trace_dir))
    if not phases:
        return None
    secs, count = run.trace["modules"][STEP]
    own = dict.fromkeys(PHASES, 0.0)
    for key, (s, _) in run.trace["ops"].items():
        module, op = key.split("/", 1)
        if module == STEP and op in phases:
            own[phases[op]] += s
    ms = {p: 1e3 * v / count for p, v in own.items()}
    run.notes.update(scope_source=OP_NAME_STAT,
                     evaluate_device_ms=ms["evaluate"],
                     unscoped_device_ms=1e3 * secs / count
                     - sum(ms.values()))
    return ms
