"""``rebalance.recovery_s``: seconds from an interference onset to the
completion of the first query served under the split the rebalancer
committed in answer, averaged over the on-periods that end inside the
window.

Layer: the rebalancer (``schedulers/runtime.py``, ODIN policy).  The
splits come from the program's traces (a committed split is a
non-trial query's split that differs from the one in force at the
onset); the times from the benchmark's host-clock stamps.  An onset
with no commit before its on-period ends counts the whole on-period.
Should move ``latency_p95_ms``.
"""


def read(run):
    served = [r for r in run.records if not r.serial]
    out = []
    for on, off, _ in run.onsets:
        if off > run.window[1]:
            continue
        before = [r for r in served if r.t1 <= on]
        base = before[-1].config if before else run.balanced
        rec = off - on
        for r in served:
            if r.t0 >= on and r.config != base:
                rec = min(r.t1 - on, rec)
                break
        out.append(rec)
    return sum(out) / len(out) if out else None
