"""``runloop.host_ms``: client time per query spent outside the
program's ``run_query``, in milliseconds.

Layer: the run loop and the engine's host code
(``workloads/runner.py``, ``serving/engine.py``), with the rebalancer
it polls.  Read from the benchmark's host-clock stamps: a query's
client latency minus its ``run_query`` span.  Should move
``latency_p50_ms``.
"""


def read(run):
    if not run.records:
        return None
    outside = [lat - (r.t1 - r.t0)
               for lat, r in zip(run.latencies, run.records)]
    return 1e3 * sum(outside) / len(outside)
