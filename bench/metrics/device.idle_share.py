"""``device.idle_share``: share of the traced window in which no
operation ran on the device, in %.

Layer: the device (XLA on the TPU).  Read from the profiler trace: one
minus the union of the device's busy intervals over the window, which
runs from the first ``api.run`` span's start to the last one's end.
Should move ``tokens_per_s``.
"""


def read(run):
    p = run.profile
    if p is None or not p.busy:
        return None
    w = p.window("api.run")
    if w is None:
        return None
    a, b = w
    return 100.0 * (1.0 - p.busy_ns(a, b) / (b - a))
