"""Record a small profiler trace on the chip, for a test of the trace
reduction on a real device trace (kept as ``bench/tests/data/small.xplane.pb``).

    python bench/tests/record_trace.py <out.xplane.pb>

On the chip: three dispatches of a small jitted program inside the
benchmark's window span, each in a ``bench.dispatch`` span, with a 20 ms
``bench.host_sleep`` span between them, so the device idles while the host
sleeps.  Writes the trace's ``.xplane.pb`` to the given path.
"""
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[0:1] = [str(ROOT)]

if __name__ == "__main__":
    import jax
    import jax.numpy as jnp

    from bench import trace

    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((2048, 2048), jnp.float32)
    f(x).block_until_ready()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            for i in range(3):
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    f(x).block_until_ready()
                with jax.profiler.TraceAnnotation("bench.host_sleep"):
                    time.sleep(0.02)
        jax.profiler.stop_trace()
        shutil.copy(trace.find_xplane(d), sys.argv[1])
    print(trace.reduce(trace.load(sys.argv[1])))
