"""Quickstart: the CWS scheduling a real (tiny) training job on this machine.

    PYTHONPATH=src python examples/quickstart.py

What happens:
  1. a tiny qwen-family model + synthetic token pipeline are built;
  2. the training job is compiled into a *workflow DAG* (chunks of steps,
     with eval and checkpoint tasks branching off);
  3. the DAG is submitted through the CWSI to a CommonWorkflowScheduler
     running a local executor — the same scheduler that, in production,
     gang-schedules step-programs onto TPU slices;
  4. provenance + the online runtime predictor are printed at the end.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import ShapeConfig, TrainConfig
from repro.data import DataConfig, TokenPipeline
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.runtime.orchestrator import (
    LocalRuntime,
    SharedState,
    TrainJobSpec,
    build_training_workflow,
)
from repro.runtime.train import init_state, make_train_step


def main() -> None:
    cfg = get_config("qwen1.5-0.5b", smoke=True)
    model = build_model(cfg)
    B, S = 8, 128
    shape = ShapeConfig("quickstart", S, B)
    tcfg = TrainConfig(learning_rate=3e-3, warmup_steps=10,
                       microbatch_per_device=B)
    mesh = make_host_mesh()
    step, _, _, _ = make_train_step(model, tcfg, shape, mesh)
    jstep = jax.jit(step)
    pipe = TokenPipeline(DataConfig(vocab=cfg.vocab, seq_len=S,
                                    global_batch=B, seed=0))

    shared = SharedState(init_state(model, tcfg, jax.random.PRNGKey(0)))

    def run_chunk(sh: SharedState, start: int, stop: int):
        loss = np.nan
        for s in range(start, stop):
            batch = {k: jnp.asarray(v) for k, v in pipe.batch(s).items()}
            sh.state, m = jstep(sh.state, batch)
            loss = float(m["loss"])
        return {"step": stop, "loss": loss}

    def run_eval(sh: SharedState, step_no: int):
        batch = {k: jnp.asarray(v) for k, v in pipe.batch(10_000).items()}
        loss, _ = jax.jit(model.loss)(sh.state["params"], batch)
        return {"eval_step": step_no, "eval_loss": float(loss)}

    spec = TrainJobSpec(job_id="quickstart", n_steps=30, chunk=6,
                        eval_every=12)
    dag = build_training_workflow(spec, run_chunk, shared, run_eval=run_eval)
    print(f"workflow: {len(dag)} tasks "
          f"({sum(1 for t in dag.tasks.values() if t.name=='train_chunk')} "
          f"train chunks)")

    rt = LocalRuntime(n_nodes=2, strategy="rank_min_rr")
    rt.run(dag, timeout_s=900)

    losses = [m["loss"] for m in shared.metrics if "loss" in m]
    print(f"losses per chunk: {[round(l, 3) for l in losses]}")
    assert losses[-1] < losses[0], "loss should decrease"

    # what the CWS learned while running us (paper §5):
    mu, std = rt.client.predict_runtime("train_chunk")
    print(f"CWS learned train_chunk runtime: {mu:.2f}s ± {std:.2f}s")
    prov = rt.client.workflow_provenance("quickstart")
    print(f"provenance: makespan={prov['makespan']:.1f}s "
          f"queue={prov['queueTime']:.1f}s traces={prov['traces']}")
    rt.shutdown()
    print("OK")


if __name__ == "__main__":
    main()
