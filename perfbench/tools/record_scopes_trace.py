"""Record the small profiler trace the scope reader's test reads
(``tests/data/scopes.xplane.pb``): inside a ``perfbench.window`` span,
one program span (``key_table.resolve``, made by the program's own
``tracing.span``) over a host sleep that leaves the device idle, then
two jitted programs whose operations carry named scopes the way the
staged verify program's do: ``bls.stage1`` with a loop under ``sqrt``,
and ``bls.stage3`` with loops under ``miller_loop`` and ``final_exp``.
Run on the chip:

    python3 perfbench/tools/record_scopes_trace.py perfbench/tests/data
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def _programs():
    import jax
    import jax.numpy as jnp
    from jax import lax

    def stage1(x):
        with jax.named_scope("bls.stage1"):
            with jax.named_scope("sqrt"):
                y = lax.fori_loop(0, 48, lambda i, v: jnp.tanh(v @ v), x)
            return y + 1.0

    def stage3(x):
        with jax.named_scope("bls.stage3"):
            with jax.named_scope("miller_loop"):
                y = lax.fori_loop(0, 32, lambda i, v: jnp.sin(v @ v), x)
            with jax.named_scope("final_exp"):
                return lax.fori_loop(0, 16, lambda i, v: jnp.cos(v @ v), y)

    return jax.jit(stage1), jax.jit(stage3)


def main() -> int:
    out_dir = sys.argv[1]
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from lighthouse_tpu.utils import tracing

    stage1, stage3 = _programs()
    x = jnp.full((512, 512), 0.001, jnp.float32)
    stage3(stage1(x)).block_until_ready()  # compile outside the trace
    tmp = tempfile.mkdtemp(dir=os.path.dirname(os.path.abspath(out_dir)))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with TraceAnnotation("perfbench.window"):
        with tracing.span("key_table.resolve", sets=3):
            time.sleep(0.02)
        stage3(stage1(x)).block_until_ready()
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(out_dir, exist_ok=True)
    dst = os.path.join(out_dir, "scopes.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp)
    print(os.path.getsize(dst))
    return 0


if __name__ == "__main__":
    sys.exit(main())
