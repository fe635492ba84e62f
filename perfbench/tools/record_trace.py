"""Record the small profiler trace the trace reduction's test reads
(``tests/data/small.xplane.pb``): a ``perfbench.window`` span holding
three device programs, each inside a ``perfbench.verify`` span, with
host sleeps between them that leave the device idle. Run on the chip:

    python3 perfbench/tools/record_trace.py perfbench/tests/data
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time


def main() -> int:
    out_dir = sys.argv[1]
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    step = jax.jit(lambda a: jnp.tanh(a @ a) + 1.0)
    x = jnp.ones((1024, 1024), jnp.float32)
    step(x).block_until_ready()  # compile outside the trace
    tmp = tempfile.mkdtemp(dir=os.path.dirname(os.path.abspath(out_dir)))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with TraceAnnotation("perfbench.window"):
        for _ in range(3):
            with TraceAnnotation("perfbench.verify"):
                step(x).block_until_ready()
            time.sleep(0.02)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(src, os.path.join(out_dir, "small.xplane.pb"))
    shutil.rmtree(tmp)
    print(os.path.getsize(os.path.join(out_dir, "small.xplane.pb")))
    return 0


if __name__ == "__main__":
    sys.exit(main())
