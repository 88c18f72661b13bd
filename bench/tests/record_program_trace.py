"""Record the small trace of the program that ``test_progspans.py``
reads.

    python3 bench/tests/record_program_trace.py <out_dir>

On a TPU: a Qwen3 smoke configuration of 4 blocks in bf16 served as 4
EPs by ODIN.  One 64-token query arms the detector; six more, in two
``repro.api.run`` calls with EP 1 slowed 5x, are traced under the
benchmark's own spans (``api.run`` around each call, ``run_query``
around each query), so the trace holds the program's spans and at
least one rebalancing phase from detection to commit.  Writes the trace
under ``<out_dir>``, and what the readers read of it to
``<out_dir>/program.xplane.pb`` (``prune``), and prints that path.
"""
from __future__ import annotations

import dataclasses
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

SEQ = 64
SLOW = [1.0, 5.0, 1.0, 1.0]


def main() -> None:
    out = sys.argv[1]
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_program_trace needs a TPU")
    from devtrace import find_xplane
    from repro import api
    from repro.configs import get_smoke_config
    from repro.launch.serve import build_engine
    from repro.models import Model
    from repro.pipeline.executor import LocalPipelineExecutor
    from run import executor_class

    cfg = dataclasses.replace(get_smoke_config("qwen3-4b"), num_layers=4)
    params = Model(cfg).init_params(jax.random.PRNGKey(0), jnp.bfloat16)
    rng = np.random.default_rng(0)
    queries = [jnp.asarray(rng.integers(0, cfg.vocab_size, (1, SEQ)),
                           jnp.int32) for _ in range(7)]
    executor = executor_class(LocalPipelineExecutor)(cfg, params,
                                                     annotate=True)
    eng = build_engine(cfg, params, [SEQ], num_eps=4, scheduler="odin",
                       alpha=2, executor=executor)
    # One query arms the detector, outside the trace.
    api.run(api.RunSpec(engine=eng, queries=queries[:1],
                        schedule=lambda q: [1.0] * 4))
    jax.block_until_ready([r.served for r in executor.records])
    executor.records.clear()
    jax.profiler.start_trace(out)
    for chunk in (queries[1:4], queries[4:]):
        with jax.profiler.TraceAnnotation("api.run"):
            api.run(api.RunSpec(engine=eng, queries=chunk,
                                schedule=lambda q: list(SLOW)))
    jax.block_until_ready([r.served for r in executor.records])
    jax.profiler.stop_trace()
    runtime = eng.runtime
    print(f"rebalances {runtime.num_rebalances}, commits "
          f"{len(runtime.mitigation_lengths)}", file=sys.stderr)
    path = os.path.join(out, "program.xplane.pb")
    prune(find_xplane(out), path)
    print(path)


def prune(src: str, dst: str) -> None:
    """Write to ``dst`` what the readers read of the trace ``src``: the
    device planes' ``XLA Modules`` and ``XLA Ops`` events (times and
    names) and the host spans of the program and of the benchmark, with
    their metadata.  Python calls, runtime threads, per-event device
    stats and source locations are left out, which keeps the file
    small."""
    import importlib.util

    from devtrace import DEVICE_PLANE, MODULES_LINE, OPS_LINE
    from progspans import NAMES
    from run import SPANS

    keep = set(NAMES) | set(SPANS)

    # The XPlane schema ships with TensorFlow; its generated module needs
    # only protobuf, so it is loaded without importing TensorFlow.
    tf = importlib.util.find_spec("tensorflow").submodule_search_locations[0]
    spec = importlib.util.spec_from_file_location(
        "xplane_pb2", os.path.join(tf, "tsl", "profiler", "protobuf",
                                   "xplane_pb2.py"))
    xplane_pb2 = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(xplane_pb2)
    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    out = xplane_pb2.XSpace()
    for plane in space.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not (device or plane.name.startswith("/host:")):
            continue
        kept = out.planes.add(id=plane.id, name=plane.name)
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [e for e in line.events if device
                      or plane.event_metadata[e.metadata_id].name in keep]
            if not events:
                continue
            new = kept.lines.add()
            new.CopyFrom(line)
            del new.events[:]
            for e in events:
                new.events.add().CopyFrom(e)
                if device:
                    del new.events[-1].stats[:]
        for i in {e.metadata_id for ln in kept.lines for e in ln.events}:
            kept.event_metadata[i].id = i
            kept.event_metadata[i].name = plane.event_metadata[i].name
        for i, meta in plane.stat_metadata.items():
            kept.stat_metadata[i].CopyFrom(meta)
    with open(dst, "wb") as f:
        f.write(out.SerializeToString())


if __name__ == "__main__":
    main()
