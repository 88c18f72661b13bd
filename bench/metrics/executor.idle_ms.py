"""``executor.idle_ms``: device-idle milliseconds inside each
``run_query`` span, averaged over the traced window's queries.

Layer: the executor (``pipeline/executor.py``), whose stage loop
synchronises with the host after the embedding, after every stage and
after the head, and commits the stage bounds on every query.  Read from
the profiler trace: the ``run_query`` spans the benchmark records, less
the time inside them in which an operation ran on the device.  Should
move ``latency_p50_ms``.
"""
from devtrace import covered


def read(run):
    p = run.profile
    if p is None or not p.busy:
        return None
    spans = p.spans.get("run_query")
    if spans is None or not len(spans):
        return None
    idle = [(e - s) - covered(p.busy[0], int(s), int(e)) for s, e in spans]
    return 1e-6 * sum(idle) / len(idle)
