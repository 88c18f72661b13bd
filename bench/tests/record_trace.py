"""Record the small profiler trace that ``test_devtrace.py`` reads.

    python3 bench/tests/record_trace.py <out_dir>

On a TPU: three ``api.run`` spans, each with two ``run_query`` spans
that run a jitted matmul step three times; the host sleeps 5 ms between
steps inside ``run_query``, 20 ms inside ``api.run`` between queries
and 30 ms between the ``api.run`` spans, so every kind of idle gap is
there.  Writes the trace under ``<out_dir>`` and prints its path.
"""
from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def main() -> None:
    out = sys.argv[1]
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace needs a TPU")

    @jax.jit
    def matmul_step(a, b):
        return jnp.tanh(a @ b)

    a = jnp.ones((2048, 2048), jnp.bfloat16)
    b = jnp.full((2048, 2048), 1e-3, jnp.bfloat16)
    matmul_step(a, b).block_until_ready()
    jax.profiler.start_trace(out)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("api.run"):
            for _ in range(2):
                with jax.profiler.TraceAnnotation("run_query"):
                    x = a
                    for _ in range(3):
                        x = matmul_step(x, b)
                        x.block_until_ready()
                        time.sleep(0.005)
                time.sleep(0.02)
        time.sleep(0.03)
    jax.profiler.stop_trace()
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from devtrace import find_xplane
    print(find_xplane(out))


if __name__ == "__main__":
    main()
