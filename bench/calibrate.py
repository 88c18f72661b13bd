"""Readings the check's limit is set from, at a cell's own size.

    python3 bench/calibrate.py --workload qwen3-4b.steady \
        --seeds 101 102 103

For each seed, in one process: the cell's weights and first prompts as
``run.py`` makes them; the served path's tokens (``run_query`` of the
program's executor, under the balanced split and under one with a
boundary moved); and the control, the
reference computed with fp8 operands, which the comparison has to
reject.  For each it prints the check's number: the widest gap by
which the f32 reference's logit of the token put first lies below the
reference's best.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    import jax
    import jax.numpy as jnp
    import numpy as np

    import run as bench
    from reference.common import weight_key
    from repro.configs import get_config
    from repro.core.pipeline_state import balanced_config
    from repro.pipeline.executor import LocalPipelineExecutor

    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}", flush=True)
    if dev.platform != "tpu":
        bench.fail("calibration reads the chip; no TPU found")
    cell = bench.load_cell(args.workload)
    c, tr = cell.config, cell.traffic
    ref = bench.reference_module(c)
    cfg = get_config(c["arch"])
    dtype = jnp.dtype(c["dtype"])
    seq = int(tr["prompt_tokens"])
    k = int(c["check_queries"])
    f32 = ref.make_forward(c)
    f8 = ref.make_forward(c, quant=True)
    served = jax.jit(lambda lg: jnp.argmax(lg, axis=-1).astype(jnp.int32))

    gap = jax.jit(bench.widest_gap)

    balanced = balanced_config(cfg.num_blocks, c["deployment"]["num_eps"])
    moved = list(balanced)
    moved[0] += balanced[1] // 2
    moved[1] -= balanced[1] // 2
    for seed in args.seeds:
        t0 = time.perf_counter()
        weights = ref.init_weights(c, weight_key(seed), dtype)
        rng = np.random.default_rng(seed)
        pool = rng.integers(0, c["vocab_size"],
                            (int(tr["prompt_pool"]), 1, seq))
        ex = LocalPipelineExecutor(cfg, weights)
        out = {"seed": seed, "program": {}, "control": 0.0}
        t_ref = 0.0
        for p in pool[:k].astype(np.int32):
            tokens = jnp.asarray(p)
            t1 = time.perf_counter()
            exact = f32(weights, tokens[0])
            exact.block_until_ready()
            t_ref += time.perf_counter() - t1
            low = served(f8(weights, tokens[0]))
            out["control"] = max(out["control"], float(gap(exact, low)))
            for split in (balanced, moved):
                logits, _ = ex.run_query(tokens, split)
                g = float(gap(exact, served(logits[0])))
                key = str(split)
                out["program"][key] = max(out["program"].get(key, 0.0), g)
            del exact
        out["reference_s_per_query"] = t_ref / k
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
        del weights, ex


if __name__ == "__main__":
    main()
