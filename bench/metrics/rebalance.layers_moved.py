"""``rebalance.layers_moved``: layers that change stage per committed
rebalance.

Layer: the rebalancer (``schedulers/runtime.py``, ODIN policy).  Read
from the program's ``rebalance.commit`` marks in the profiler trace
(``progspans``): the mean of their ``moved`` metadata, the layers whose
stage differs between the split before and after, over the marks whose
commit changed the split.  A commit of a model whose units are whole
layer periods moves a multiple of the period.  A program whose marks
carry no ``moved`` reports nothing.  Should move ``latency_p95_ms``.
"""
import progspans


def read(run):
    ps = progspans.of(run)
    if ps is None:
        return None
    moved = [m["moved"] for m in ps.meta.get("rebalance.commit", ())
             if "moved" in m and m.get("changed")]
    return sum(moved) / len(moved) if moved else None
