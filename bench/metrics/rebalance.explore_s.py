"""``rebalance.explore_s``: mean seconds from the start of a rebalancing
phase to its commit.

Layer: the rebalancer (``schedulers/runtime.py``, ODIN policy): the
explorer's serial trial queries.  Read from the program's marks in the
profiler trace (``progspans``): each ``rebalance.detect`` to the next
``rebalance.commit``; a phase still open when the window closes is left
out.  With ``rebalance.recovery_s`` it splits recovery into the
detector's delay (the rest) and the exploration.  Should move
``latency_p95_ms``.
"""
import numpy as np

import progspans


def read(run):
    ps = progspans.of(run)
    if ps is None:
        return None
    commits = ps.intervals("rebalance.commit")[:, 0]
    out = []
    for t in ps.intervals("rebalance.detect")[:, 0]:
        later = commits[commits >= t]
        if len(later):
            out.append(int(later[0]) - int(t))
    return 1e-9 * float(np.mean(out)) if out else None
