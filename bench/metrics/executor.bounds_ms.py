"""``executor.bounds_ms``: host milliseconds per query spent committing
the stage bounds to the device.

Layer: the executor (``pipeline/executor.py``, ``_device_bounds``: two
scalars per stage, each put on the device and waited for).  Read from
the program's spans in the profiler trace (``progspans``): the
``executor.bounds`` spans' time over the window, per ``engine.query``
span.  Should move ``latency_p50_ms``.
"""
import progspans


def read(run):
    ps = progspans.of(run)
    if ps is None:
        return None
    return 1e-6 * ps.duration_ns(progspans.BOUNDS) / ps.queries
