"""Record the small profiler trace that ``test_trace.py`` pins.

Run on a machine with one TPU (it writes ``<out>/small.xplane.pb``):

    python3 bench/tests/record_trace.py <out>

Inside a ``bench.window`` annotation it runs twelve calls of a jitted
``_decode_fn`` (a 2048 x 2048 bfloat16 product), each inside a
``bench.drive`` annotation, with ``bench.pump`` host sleeps of 2 ms
between them, so the device is idle in known places.
"""

import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def _decode_fn(x, w):
    return jnp.tanh(x @ w) @ w


def main(out: str) -> None:
    if jax.devices()[0].platform != "tpu":
        sys.exit("no TPU")
    f = jax.jit(_decode_fn)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    w = jnp.full((2048, 2048), 0.01, jnp.bfloat16)
    f(x, w).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(12):
            with jax.profiler.TraceAnnotation("bench.drive"):
                f(x, w).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.pump"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(out, exist_ok=True)
    shutil.copy(src, os.path.join(out, "small.xplane.pb"))
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main(sys.argv[1])
