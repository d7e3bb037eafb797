"""Records ``data/cpu_trace.xplane.pb``, the small trace that
``test_trace_reduce.py`` reduces:

    JAX_PLATFORMS=cpu python3 bench/tests/record_cpu_trace.py

Three calls of one jitted program inside a ``client/solve`` span, then
a 50 ms sleep inside ``client/make_rhs`` with nothing on the device,
then one more call."""
import glob
import os
import shutil
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

OUT = Path(__file__).resolve().parent / "data" / "cpu_trace.xplane.pb"


@jax.jit
def program(x):
    return jnp.sin(x) @ x.T + 1.0


def main():
    x = jnp.ones((256, 256))
    program(x).block_until_ready()
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("segment/start"):
        time.sleep(1e-4)
    with jax.profiler.TraceAnnotation("client/solve"):
        for _ in range(3):
            program(x).block_until_ready()
    with jax.profiler.TraceAnnotation("client/make_rhs"):
        time.sleep(0.05)
    with jax.profiler.TraceAnnotation("client/await"):
        program(x).block_until_ready()
    with jax.profiler.TraceAnnotation("segment/stop"):
        time.sleep(1e-4)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(d, "plugins/profile/*/*.xplane.pb"))[0]
    shutil.copy(src, OUT)
    shutil.rmtree(d)
    print(OUT, OUT.stat().st_size)


if __name__ == "__main__":
    main()
