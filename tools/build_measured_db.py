"""Build a *measured* layer-time interference database (paper §3.3).

Faithful to the paper's methodology on THIS container as the "real
platform": time every block of a real JAX model executing alone
(column 0), then re-time it while co-located stressor processes run —
iBench-style CPU busy-loops and memory-bandwidth streamers at the
Table-1 thread counts — giving the m x (n+1) table the simulator and
serving benchmarks consume.

    PYTHONPATH=src python tools/build_measured_db.py \
        [--arch qwen3-4b] [--blocks 12] [--out results/measured_db.json]
"""
from __future__ import annotations

import argparse
import ctypes
import dataclasses
import multiprocessing as mp
import time

import numpy as np


def _cpu_stressor(stop):
    x = 1.0001
    while not stop.value:
        for _ in range(10000):
            x = x * 1.0000001 + 1e-9
    return x


def _membw_stressor(stop):
    a = np.zeros(64 * 1024 * 1024 // 8)  # 64 MiB stream
    b = np.ones_like(a)
    while not stop.value:
        a += b                            # streaming read+write
    return a


@dataclasses.dataclass
class Scenario:
    name: str
    kind: str      # "cpu" | "membw"
    procs: int


def scenarios_table1():
    out = [Scenario("none", "none", 0)]
    # Table-1 thread counts, capped at 16 on this container (32 heavily
    # oversubscribes the sandbox cores and just measures the scheduler)
    for n in (1, 2, 4, 8, 16):
        out.append(Scenario(f"ibench-cpu-{n}t", "cpu", n))
    for n in (1, 2, 4, 8, 16):
        out.append(Scenario(f"ibench-membw-{n}t", "membw", n))
    return out


def measure(arch: str, blocks: int, seq: int, repeats: int):
    import dataclasses as dc

    import jax
    import jax.numpy as jnp

    from repro.configs import get_smoke_config
    from repro.models import Model
    from repro.pipeline import LocalPipelineExecutor

    cfg = get_smoke_config(arch)
    if blocks:
        cfg = dc.replace(cfg, num_layers=blocks)
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    ex = LocalPipelineExecutor(cfg, params)
    tokens = jnp.zeros((1, seq), jnp.int32)
    ex.warmup(1, seq)

    table = []
    names = []
    for sc in scenarios_table1():
        ctx = mp.get_context("spawn")   # fork deadlocks multithreaded JAX
        stop = ctx.Value(ctypes.c_int, 0)
        procs = []
        target = _cpu_stressor if sc.kind == "cpu" else _membw_stressor
        for _ in range(sc.procs):
            p = ctx.Process(target=target, args=(stop,), daemon=True)
            p.start()
            procs.append(p)
        try:
            time.sleep(0.3)  # let stressors ramp
            times = ex.measure_block_times(tokens, repeats=repeats)
        finally:
            stop.value = 1
            for p in procs:
                p.join(timeout=2)
                if p.is_alive():
                    p.terminate()
        table.append(times)
        names.append(sc.name)
        print(f"  {sc.name:18s} mean_block={1e3 * times.mean():7.2f} ms "
              f"(x{times.mean() / table[0].mean():.2f})", flush=True)
    return np.stack(table, axis=1), names, cfg


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--blocks", type=int, default=12)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default="results/measured_db.json")
    args = ap.parse_args()

    print(f"measuring {args.arch} ({args.blocks} blocks) under Table-1 "
          f"stressor scenarios...")
    table, names, cfg = measure(args.arch, args.blocks, args.seq,
                                args.repeats)
    from repro.core import LayerDatabase
    db = LayerDatabase(table, names,
                       unit_names=[f"block{i}" for i in range(len(table))],
                       model_name=f"{cfg.name}-measured")
    import os
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    db.save(args.out)
    print(f"saved {table.shape[0]}x{table.shape[1]} database -> {args.out}")
    print(f"impact range: x{(table[:, 1:] / table[:, :1]).min():.2f} .. "
          f"x{(table[:, 1:] / table[:, :1]).max():.2f}")


if __name__ == "__main__":
    main()
