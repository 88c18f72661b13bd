"""``hybrid.recovery_s``: seconds from an interference onset to the
completion of the first query served under the split the rebalancer
committed in answer, averaged over the on-periods that end inside the
window, in a model whose layers differ in kind.

Layer: the rebalancer (``schedulers/runtime.py``, ODIN policy), moving
stage boundaries one layer at a time over layers of two kinds.  Read as
``rebalance.recovery_s`` reads it (that reader's code): the splits from
the program's traces, the times from the benchmark's host-clock stamps.
Should move ``latency_p95_ms``.
"""
from run import metric_reader


def read(run):
    return metric_reader("rebalance.recovery_s")(run)
