"""Record a small profiler trace with named device scopes and a program host
span on the chip, for a test of ``bench/scopes.py`` on a real device trace
(kept as ``bench/tests/data/scoped.xplane.pb``).

    python bench/tests/record_scoped_trace.py <out.xplane.pb>

On the chip: three dispatches of a jitted 4-step ``lax.scan`` whose body
has two named scopes (``heat.gather``, ``heat.ccl``), inside the
benchmark's window span.  Each dispatch runs in a ``bench.run_window`` span
and starts with a 20 ms host sleep inside a ``train.dispatch`` span, so the
device idles under a program span that lies inside a benchmark span.
Writes the trace's ``.xplane.pb`` to the given path.
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

    from bench import scopes, trace

    def body(x, _):
        with jax.named_scope("heat.gather"):
            y = jnp.tanh(x @ x)
        with jax.named_scope("heat.ccl"):
            z = jnp.sin(y @ x) * 0.5
        return z, None

    f = jax.jit(lambda x: jax.lax.scan(body, x, None, length=4)[0])  # heatlint: disable=HL103 -- x is dispatched again
    x = jnp.ones((2048, 2048), jnp.float32) / 2048
    f(x).block_until_ready()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            for i in range(3):
                with jax.profiler.TraceAnnotation("bench.run_window"):
                    with jax.profiler.TraceAnnotation("train.dispatch"):
                        time.sleep(0.02)
                        out = f(x)
                    out.block_until_ready()
        jax.profiler.stop_trace()
        shutil.copy(trace.find_xplane(d), sys.argv[1])
    print(scopes.reduce_file(sys.argv[1]))
