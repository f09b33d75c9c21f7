"""Records ``scoped.xplane.pb`` and ``scoped.hlo.txt`` beside this file, on
the chip.

    python benchmarks/chip/fixtures/record_scoped.py

A small jitted program with two named scopes (``attn_core`` around a
matmul and its softmax, ``mlp`` around a second matmul and its tanh),
run four times, each under a ``train.step`` span with its ``step`` and
followed by a host pause under ``train.batch``: a trace small enough to
commit, with the real TPU op names and span stats, and the program's
optimized HLO, for ``test_chipbench_spans.py``.
"""
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[2]
STEPS = 4


def program(x, w):
    with jax.named_scope("attn_core"):
        h = jax.nn.softmax((x @ w).astype(jnp.float32), axis=-1)
    with jax.named_scope("mlp"):
        return jnp.tanh(h.astype(x.dtype) @ w)


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_scoped.py: needs a TPU", file=sys.stderr)
        return 2
    x = jnp.ones((512, 512), jnp.bfloat16)
    w = jnp.full((512, 512), 0.01, jnp.bfloat16)
    compiled = jax.jit(program).lower(x, w).compile()
    compiled(x, w).block_until_ready()
    out = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(out, profiler_options=opts)
    for s in range(STEPS):
        with TraceAnnotation("train.step", step=s):
            compiled(x, w).block_until_ready()
        with TraceAnnotation("train.batch", step=s + 1):
            time.sleep(0.005)
    jax.profiler.stop_trace()
    src = sorted(Path(out).rglob("*.xplane.pb"))[-1]
    shutil.copy(src, HERE / "scoped.xplane.pb")
    shutil.rmtree(out)
    # source files named relative to the checkout, wherever it lies
    (HERE / "scoped.hlo.txt").write_text(
        compiled.as_text().replace(f"{ROOT}/", ""))
    for name in ("scoped.xplane.pb", "scoped.hlo.txt"):
        print(f"wrote {HERE / name} ({(HERE / name).stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
