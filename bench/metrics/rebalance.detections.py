"""``rebalance.detections``: rebalancing phases the detector started in
the window.

Layer: the rebalancer (``schedulers/runtime.py``, ODIN policy).  Read
from the program's ``rebalance.detect`` marks in the profiler trace
(``progspans``), one where a phase that costs serial queries starts.
In a cell without interference every one is a false alarm.  Should
move ``latency_p95_ms``.
"""
import progspans


def read(run):
    ps = progspans.of(run)
    if ps is None:
        return None
    return ps.count("rebalance.detect")
