"""Record ``v5e_scoped_probe.xplane.pb``: a small jitted function with
``mega.*`` scopes in and around a while loop, run three times on a
thread under ``sched.*`` annotations while the profiler records. Run
it where a TPU is attached, from the repository's root:

    python bench/tests/fixtures/probe.py <out.xplane.pb>
"""
import glob
import shutil
import sys
import tempfile
import threading
import time

import jax
import jax.numpy as jnp
from jax import lax


@jax.jit
def f(x, n):
    with jax.named_scope("mega.roots"):
        x = x * 2.0 + 1.0

    def body(c):
        i, y = c
        with jax.named_scope("mega.refine"):
            y = jnp.tanh(y @ y.T)
        with jax.named_scope("mega.probe"):
            y = y + jnp.cumsum(y, axis=0) * 1e-3
        return i + 1, y
    with jax.named_scope("mega.select"):
        _, y = lax.while_loop(lambda c: c[0] < n, body, (0, x))
    with jax.named_scope("mega.drain"):
        return y.sum()


def main(out: str) -> None:
    x = jnp.ones((512, 512), jnp.float32)
    f(x, 3).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)

    def engine():
        for i in range(3):
            with jax.profiler.TraceAnnotation("sched.step"):
                with jax.profiler.TraceAnnotation("sched.submit",
                                                  query_id=i):
                    time.sleep(0.001)
                with jax.profiler.TraceAnnotation("sched.readback"):
                    f(x, 3).block_until_ready()
    t = threading.Thread(target=engine)
    t.start()
    t.join()
    jax.profiler.stop_trace()
    shutil.copy(sorted(glob.glob(f"{tmp}/**/*.xplane.pb",
                                 recursive=True))[-1], out)
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main(sys.argv[1])
