"""``rebalance.trials``: queries of the window that ran as exploration
trials, serially, while the rebalancer searched for a split.

Layer: the rebalancer (``schedulers/runtime.py``, ODIN policy).  Read
from the program's own per-query ``serial_mask`` in the traces that
``repro.api.run`` returns.  In a cell without interference every trial
is a false alarm.  Should move ``latency_p95_ms``.
"""


def read(run):
    return sum(1 for r in run.records if r.serial)
