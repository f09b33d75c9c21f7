"""Records ``small.xplane.pb`` beside this file, on the chip.

    python benchmarks/chip/fixtures/record.py

Five executions of a small jitted program, each followed by a host pause
under the annotation ``bench.pause``: a trace small enough to commit, with a
known shape for ``test_chipbench_trace.py`` (5 executions of one module, 4
idle gaps inside the window, each labelled ``bench.pause``).
"""
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

HERE = Path(__file__).resolve().parent


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("record.py: needs a TPU", file=sys.stderr)
        return 2
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    f(x).block_until_ready()
    out = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(out, profiler_options=opts)
    for _ in range(5):
        f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.pause"):
            time.sleep(0.01)
    jax.profiler.stop_trace()
    src = sorted(Path(out).rglob("*.xplane.pb"))[-1]
    shutil.copy(src, HERE / "small.xplane.pb")
    shutil.rmtree(out)
    print(f"wrote {HERE / 'small.xplane.pb'} "
          f"({(HERE / 'small.xplane.pb').stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
