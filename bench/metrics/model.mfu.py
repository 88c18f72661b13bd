"""``model.mfu``: the served model's share of the chip's peak, in %.

Layer: the model step (``models/``): operations a query needs, counted
from shapes by the configuration's reference (``costs``: every block,
causal attention, the head), times the queries the window completed,
over the window's host-clock seconds times the peak of the device kind
in the served dtype (``bench/peaks.json``).  Should move
``tokens_per_s``.
"""


def read(run):
    c = run.costs
    peak = run.peak.get("flops_per_s", {}).get(run.cell.config["dtype"])
    seconds = run.window[1] - run.window[0]
    if not run.records or not peak or seconds <= 0:
        return None
    flops = c["num_blocks"] * c["block_flops"] + c["head_flops"]
    return 100.0 * len(run.records) * flops / (seconds * peak)
