"""Record ``data/tiny_spans.xplane.pb``, the trace that
``test_chipbench_trace_spans.py`` reduces.

Two calls of one jitted matmul-and-tanh, each inside a
``bench.tiny_call`` host span and, inside that, the program spans
``repro.tiny.outer`` > (``repro.tiny.prep``, ``repro.tiny.dispatch``,
``repro.tiny.wait``); host-only sleeps make idle gaps of the device
under each of them.  A ``repro.tiny.before`` span comes first, outside
every ``bench.*`` span and before any device op: it must not move the
window.  Run on a TPU host, from the root of a checkout:

    python benchmarks/chip/tests/record_tiny_spans.py <out.xplane.pb>
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(_HERE)))
sys.path[:0] = [os.path.join(_ROOT, "src"), os.path.dirname(_HERE)]


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp

    from benchlib.trace import span as bench_span
    from repro.common.trace import span

    step = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((256, 256), jnp.bfloat16)
    step(x).block_until_ready()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        with span("tiny.before"):
            time.sleep(0.001)
        for _ in range(2):
            with bench_span("tiny_call"):
                with span("tiny.outer"):
                    with span("tiny.prep"):
                        time.sleep(0.002)
                    with span("tiny.dispatch"):
                        y = step(x)
                    with span("tiny.wait"):
                        y.block_until_ready()
                    time.sleep(0.001)
                time.sleep(0.001)
        jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        shutil.copy(path, out)
    print(out, os.path.getsize(out))


if __name__ == "__main__":
    main(sys.argv[1])
