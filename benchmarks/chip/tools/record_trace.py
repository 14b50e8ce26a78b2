"""Record a small profiler trace on the chip, with the harness's host spans.

    python3 benchmarks/chip/tools/record_trace.py <out_dir>

A few small matmuls and an idle sleep inside ``bench.window``, each in a
``bench.pump`` or ``bench.wait`` span, as the harness traces its window:
the fixture that ``tests/test_chip_bench.py`` reduces with ``trace.py``.
"""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> None:
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    f(x).block_until_ready()
    jax.profiler.start_trace(out_dir)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.pump"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(0.01)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
