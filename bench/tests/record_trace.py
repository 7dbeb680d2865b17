"""Record the small chip trace that ``test_trace.py`` reads.

    python3 bench/tests/record_trace.py OUT_DIR     # on a machine with a TPU

It traces a few jitted matmuls, with host gaps between them, inside the
benchmark's window span and two marked host spans, and copies the
``.xplane.pb`` to ``OUT_DIR/small.xplane.pb``.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import reduce_trace  # noqa: E402


def main(out):
    f = jax.jit(lambda a: jnp.tanh(a @ a) @ a)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation(reduce_trace.WINDOW):
        for name in ("score", "submit", "score"):
            with jax.profiler.TraceAnnotation(name):
                for _ in range(3):
                    f(x).block_until_ready()
                time.sleep(0.02)
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    os.makedirs(out, exist_ok=True)
    shutil.copy(path, os.path.join(out, "small.xplane.pb"))
    print(os.path.getsize(path), "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
