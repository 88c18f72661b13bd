"""``executor.host_syncs``: host synchronisations with the device per
query.

Layer: the executor (``pipeline/executor.py``).  Read from the program's
spans in the profiler trace (``progspans``): the ``syncs`` each of
``executor.bounds`` (two bound scalars per stage), ``executor.embed``,
``executor.stage`` and ``executor.head`` records, summed over the
window, per ``engine.query`` span.  An exact count: 3 x stages + 2.
Should move ``latency_p50_ms``.
"""
import progspans


def read(run):
    ps = progspans.of(run)
    if ps is None:
        return None
    return sum(ps.total(n, "syncs") for n in progspans.SYNCING) / ps.queries
