"""Chip smoke test: serve Qwen3-4B at full width through the live ODIN path.

    python chip_smoke.py            # on a TPU host: full width, bf16
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny   # CPU rehearsal

Drives the entry points a user calls -- ``ServingEngine`` under
``repro.api.run(RunSpec(engine=...))``, built by the same helpers as
``python -m repro.launch.serve`` -- with random bf16 weights from
``--seed``.  Phases, each of which ends the run non-zero if it fails:

1. the device: JAX's version and devices; not a TPU -> exit 1 (with
   ``--tiny`` the whole path runs first, then exits 1);
2. set-up: jitted weight init, then the pipeline's logits for one query
   under a balanced and an unbalanced stage split, each checked against
   jitted ``Model.forward`` on the same weights (moving a stage boundary
   must not change the answer);
3. serving: 16 queries, closed loop, ODIN over 4 execution places, with
   one EP slowed down for queries 6-11; every query must complete with
   finite logits, and ODIN must rebalance, moving a stage boundary
   inside that window.

The last line of a passing run on a TPU is one JSON object naming the
device.  Set-up and serve seconds are printed apart; set-up includes
compilation, so it drops on a second run with a warm compile cache
(``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import api  # noqa: E402
from repro.configs import get_config, get_smoke_config  # noqa: E402
from repro.core.pipeline_state import balanced_config  # noqa: E402
from repro.launch.serve import (  # noqa: E402
    build_engine,
    configure_compile_cache,
    init_params,
    slowdown_schedule,
)
from repro.models import Model  # noqa: E402
from repro.pipeline.executor import LocalPipelineExecutor  # noqa: E402

ARCH = "qwen3-4b"
NUM_EPS = 4
NUM_QUERIES = 16
#: One interference window: EP 1 runs 3x slower for queries 6-11.
WINDOW = (6, 12, 1, 3.0)
#: Blocks moved from stage 1 to stage 0 for the unbalanced split
#: ([9, 9, 9, 9] -> [14, 4, 9, 9] at 36 blocks).
SHIFT = 5
#: Pipeline logits vs the reference: max |diff| <= RTOL * max |ref|.
#: Both sides run the same bf16 ops; 2e-2 is about five bf16 ulps at
#: the largest logit, room for fusion order, not for a wrong layer.
RTOL = 2e-2


class CheckedExecutor(LocalPipelineExecutor):
    """Keeps one on-device ``all(isfinite(logits))`` flag per query
    (the engine itself drops the logits); read once, after serving."""

    def __init__(self, cfg, params):
        super().__init__(cfg, params)
        self.finite = []

    def run_query(self, tokens, config, slowdowns=None):
        logits, times = super().run_query(tokens, config, slowdowns)
        self.finite.append(jnp.isfinite(logits).all())
        return logits, times


@jax.jit
def _agreement(out, ref):
    out, ref = out.astype(jnp.float32), ref.astype(jnp.float32)
    return (jnp.max(jnp.abs(out - ref)), jnp.max(jnp.abs(ref)),
            jnp.isfinite(out).all())


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", flush=True)
    sys.exit(1)


def check_logits(executor, model, params, tokens, num_blocks) -> None:
    """Pipeline logits under two stage splits vs jitted Model.forward."""
    ref = jax.jit(model.forward)(params, tokens)[0]
    balanced = balanced_config(num_blocks, NUM_EPS)
    unbalanced = list(balanced)
    unbalanced[0] += SHIFT
    unbalanced[1] -= SHIFT
    outs = []
    for config in (balanced, unbalanced):
        out, _ = executor.run_query(tokens, config)
        diff, scale, finite = (float(v) for v in _agreement(out, ref))
        ok = bool(finite) and diff <= RTOL * scale
        print(f"logits {config}: max|diff|={diff:.6g} max|ref|={scale:.6g} "
              f"rel={diff / scale:.6g} tol={RTOL} finite={bool(finite)} "
              f"{'ok' if ok else 'MISMATCH'}", flush=True)
        if not ok:
            fail(f"pipeline logits under {config} disagree with "
                 f"Model.forward")
        outs.append(out)
    between = float(_agreement(outs[1], outs[0])[0])
    print(f"logits {balanced} vs {unbalanced}: max|diff|={between:.6g}",
          flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="smoke widths at full depth and 128-token "
                         "queries, for a CPU rehearsal (never prints the "
                         "ok line)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args()
    seq = 128 if args.tiny else 1024          # tokens per query

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs in /tmp
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"jax {jax.__version__}; devices {jax.devices()}; {device}",
          flush=True)
    if dev.platform != "tpu" and not args.tiny:
        fail(f"no TPU: JAX found {device}")
    print(f"compile cache: {configure_compile_cache()}", flush=True)

    cfg = get_config(ARCH)
    if args.tiny:
        cfg = dataclasses.replace(get_smoke_config(ARCH),
                                  num_layers=cfg.num_layers)
    print(f"{cfg.name}: {cfg.num_blocks} blocks, d_model {cfg.d_model}, "
          f"{cfg.num_heads}q/{cfg.num_kv_heads}kv heads of {cfg.head_dim}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
          f"{cfg.param_count() / 1e9:.3f} B params, bf16, seq {seq}",
          flush=True)

    t0 = time.perf_counter()
    params = init_params(cfg, args.seed, jnp.bfloat16)
    jax.block_until_ready(params)
    t_init = time.perf_counter() - t0
    rng = np.random.default_rng(args.seed)
    queries = [jnp.asarray(rng.integers(0, cfg.vocab_size, (1, seq)),
                           jnp.int32) for _ in range(NUM_QUERIES + 1)]
    executor = CheckedExecutor(cfg, params)
    check_logits(executor, Model(cfg), params, queries.pop(), cfg.num_blocks)
    eng = build_engine(cfg, params, [seq], num_eps=NUM_EPS, scheduler="odin",
                       alpha=10, executor=executor)
    executor.finite.clear()
    setup = time.perf_counter() - t0
    print(f"setup_s={setup:.3f} (weight init {t_init:.3f})", flush=True)

    t0 = time.perf_counter()
    trace = api.run(api.RunSpec(
        engine=eng, queries=queries,
        schedule=slowdown_schedule([WINDOW], NUM_EPS)))
    serve = time.perf_counter() - t0
    finite = [bool(f) for f in executor.finite]
    s = trace.summary()
    done = int(np.isfinite(trace.latencies).sum())
    print(f"serve_s={serve:.3f} completed={done}/{NUM_QUERIES} "
          f"finite_logits={sum(finite)}/{len(finite)} "
          f"p50_latency_s={s['p50_latency_s']:.6g} "
          f"p99_latency_s={s['p99_latency_s']:.6g} "
          f"mean_throughput_qps={s['mean_throughput_qps']:.6g} "
          f"num_rebalances={s['rebalances']}", flush=True)
    print(f"config per query: {[list(c) for c in trace.configs]}",
          flush=True)
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'not reported')}",
          flush=True)

    if done != NUM_QUERIES or trace.num_failed:
        fail(f"{done} of {NUM_QUERIES} queries completed")
    if len(finite) != NUM_QUERIES or not all(finite):
        fail(f"finite logits on {sum(finite)} of {len(finite)} executions")
    start, end = WINDOW[:2]
    initial = list(trace.configs[0])
    if s["rebalances"] < 1 or all(list(trace.configs[q]) == initial
                                  for q in range(start, end)):
        fail("ODIN moved no stage boundary during the slowdown window")
    if args.tiny or dev.platform != "tpu":
        fail(f"rehearsal on {device['platform']} passed; the ok line is "
             f"only printed for the full-width run on a TPU")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
