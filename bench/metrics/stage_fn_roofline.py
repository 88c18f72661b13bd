"""``stage_fn_roofline``: the pipeline stage program's share of its
roofline, in %.

Layer: kernels, here the one jitted ``stage_fn`` program that runs
every stage (``pipeline/executor.py``).  For each stage call of the
traced window the least time is the larger of its operations over the
peak rate and its bytes (the stage's block weights, read once, and its
activation in and out) over the HBM bandwidth, all counted from shapes
by the configuration's reference (``costs``), never from the compiled
program.  Their sum is divided by the device time of the
``jit_stage_fn`` module in the profiler trace.  A stage of no blocks
needs no work.  Should move ``tokens_per_s``.
"""


def read(run):
    p = run.profile
    if p is None:
        return None
    device_s = p.module_time_ns("jit_stage_fn") * 1e-9
    rate = run.peak.get("flops_per_s", {}).get(run.cell.config["dtype"])
    bw = run.peak.get("hbm_bytes_per_s")
    if device_s <= 0 or not rate or not bw:
        return None
    c = run.costs
    least = 0.0
    for r in run.records:
        for n in r.config:
            if n:
                least += max(n * c["block_flops"] / rate,
                             (n * c["block_bytes"] + 2 * c["act_bytes"]) / bw)
    return 100.0 * least / device_s
