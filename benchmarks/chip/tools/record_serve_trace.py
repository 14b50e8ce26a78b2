"""Record a small profiler trace on the chip with the harness's spans and
the serving path's ``serve.*`` spans nested inside ``bench.pump``.

    python3 benchmarks/chip/tools/record_serve_trace.py <out_dir>

Inside ``bench.window``, three times: a ``bench.pump`` that idles 3 ms
outside any ``serve.*`` span, then a ``serve.pass`` that dispatches a
program named ``decode_round`` (module ``jit_decode_round``) under
``serve.dispatch``, waits for it under ``serve.sync`` and idles 5 ms
under ``serve.land``; then 10 ms asleep under ``bench.wait``.  The
fixture that ``tests/test_spans.py`` reduces with ``spans.py``.
"""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation


def decode_round(x):
    for _ in range(8):
        x = jnp.tanh(x @ x)
    return x


def main(out_dir: str) -> None:
    f = jax.jit(decode_round)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    jax.profiler.start_trace(out_dir)
    with TraceAnnotation("bench.window"):
        for _ in range(3):
            with TraceAnnotation("bench.pump"):
                time.sleep(0.003)
                with TraceAnnotation("serve.pass"):
                    with TraceAnnotation("serve.dispatch"):
                        y = f(x)
                    with TraceAnnotation("serve.sync"):
                        y.block_until_ready()
                    with TraceAnnotation("serve.land"):
                        time.sleep(0.005)
            with TraceAnnotation("bench.wait"):
                time.sleep(0.01)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
