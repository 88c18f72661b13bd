"""``runloop.compiles``: backend compiles inside the window; set-up
warms every shape, so it should read 0.

Layer: the run loop and the engine's host code (``workloads/runner.py``,
``serving/engine.py``), where a first-seen shape would compile.  Read
from the program's ``jax.compile`` marks in the profiler trace
(``progspans``; ``repro.telemetry.spans`` marks each backend compile)
inside the ``api.run`` spans' window.  Should move ``latency_p95_ms``.
"""
import progspans


def read(run):
    ps = progspans.of(run)
    if ps is None:
        return None
    w = run.profile.window("api.run")
    if w is None:
        return ps.count("jax.compile")
    return ps.count("jax.compile", *w)
