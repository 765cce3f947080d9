"""host_gap_ms.backlog: mean milliseconds, over the window's steps of each
pool, from the end of a `pool.readback` span to the end of the same
pool's next `pool.dispatch` span (`serve/placement_service.py`, traced
runs): the host's time between steps in which no step was queued on the
device -- harvests, admissions and the front end's bookkeeping.  Read
from the program's spans over the whole window (`bench/spans.py`)."""
from bench import spans as S


def read(run):
    found = S.spans(run.events)
    t_open, t_close = S.window(run)
    gaps = []
    for pool in {s.attrs.get("pool") for s in found
                 if s.name == "pool.readback"}:
        reads = sorted(s.t1 for s in found
                       if s.name == "pool.readback"
                       and s.attrs.get("pool") == pool)
        sends = sorted(s.t1 for s in found
                       if s.name == "pool.dispatch"
                       and s.attrs.get("pool") == pool)
        for r in reads:
            if not t_open <= r < t_close:
                continue
            nxt = next((d for d in sends if d > r), None)
            if nxt is not None:
                gaps.append(nxt - r)
    S.note_longest_leaf(run)
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
