"""``hybrid.stage_fn_roofline``: the pipeline stage program's share of
its roofline in a model whose layers differ in kind, in %.

Layer: kernels, the one jitted ``stage_fn`` program that runs every
stage (``pipeline/executor.py``).  As ``stage_fn_roofline``, but each
stage's least time sums its own layers: the larger of the operations of
its layers over the peak rate and their weights' bytes (read once) plus
its activation in and out over the HBM bandwidth, with every layer's
operations and bytes counted from shapes by the configuration's
reference (``costs``: ``layer_flops``, ``layer_bytes``).  Their sum over
the traced window's stage calls is divided by the device time of the
``jit_stage_fn`` module.  A stage of no layers needs no work.  Should
move ``tokens_per_s``.
"""
import numpy as np


def read(run):
    p = run.profile
    c = run.costs
    if p is None or "layer_flops" not in c:
        return None
    device_s = p.module_time_ns("jit_stage_fn") * 1e-9
    rate = run.peak.get("flops_per_s", {}).get(run.cell.config["dtype"])
    bw = run.peak.get("hbm_bytes_per_s")
    if device_s <= 0 or not rate or not bw:
        return None
    flops = np.concatenate([[0], np.cumsum(c["layer_flops"], dtype=float)])
    weights = np.concatenate([[0], np.cumsum(c["layer_bytes"], dtype=float)])
    least = 0.0
    for r in run.records:
        edges = np.concatenate([[0], np.cumsum(r.config)])
        for lo, hi in zip(edges[:-1], edges[1:]):
            if hi > lo:
                least += max((flops[hi] - flops[lo]) / rate,
                             (weights[hi] - weights[lo]
                              + 2 * c["act_bytes"]) / bw)
    return 100.0 * least / device_s
