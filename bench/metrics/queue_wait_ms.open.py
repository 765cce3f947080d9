"""queue_wait_ms.open: mean milliseconds from `job.submit` (front end) to
`job.admitted` (pool slot) over the jobs due in the window, from the
program's span events (`serve/tracing.py`, on in traced runs)."""


def read(run):
    submit, admitted = {}, {}
    for ev in run.events:
        if ev.name == "job.submit":
            submit.setdefault(ev.trace_id, ev.ts)
        elif ev.name == "job.admitted":
            admitted.setdefault(ev.trace_id, ev.ts)
    waits = []
    for r in run.window_jobs:
        tid = r.handle.request.trace_id if r.handle is not None else None
        if tid in submit and tid in admitted:
            waits.append(admitted[tid] - submit[tid])
    return 1e3 * sum(waits) / len(waits) if waits else None
