"""Record the small CPU trace the trace-reduction tests read.

    JAX_PLATFORMS=cpu python3 chipbench/tests/data/make_cpu_trace.py

Inside one ``chipbench.window`` span: three calls of a jitted program
(``toy_step``) each waited for, then a 50 ms sleep inside a
``chipbench.test.sleep`` span, during which the device is idle.
"""

import shutil
import time
from pathlib import Path

import jax
import jax.numpy as jnp

OUT = Path(__file__).resolve().parent / "cpu_trace"


def main() -> None:
    @jax.jit
    def toy_step(x):
        return jnp.tanh(x @ x) @ x

    x = jnp.ones((512, 512), jnp.float32)
    toy_step(x).block_until_ready()
    shutil.rmtree(OUT, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(OUT), profiler_options=opts)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("chipbench.test.call"):
                toy_step(x).block_until_ready()
        with jax.profiler.TraceAnnotation("chipbench.test.sleep"):
            time.sleep(0.05)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main()
