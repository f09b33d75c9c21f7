"""A cell's spec at a size the CPU tests can hold: the program's smoke
configuration of the same model, the benchmark's configuration file cut to
the same sizes, short rows, and the chunk1 cell's limits."""
import run as R

# configuration: (its sizes cut to the program's smoke preset, traffic,
# the traffic cut to short rows)
SMOKE = {
    "qwen1.5-0.5b": (
        dict(num_hidden_layers=2, hidden_size=128, num_attention_heads=4,
             num_key_value_heads=4, head_dim=32, intermediate_size=256,
             vocab_size=512),
        "train-b8x1024-chunk1", dict(batch=4, seq=32, microbatch=2)),
    "mamba2-370m": (
        dict(num_hidden_layers=2, hidden_size=128, state_size=16,
             head_dim=32, chunk_size=32, vocab_size=512),
        "train-b8x2048-chunk16", dict(batch=4, seq=64, microbatch=2,
                                      chunk=2)),
}
LIMITS_OF = "qwen1.5-0.5b.train-chunk1"


def smoke_spec(config: str) -> dict:
    from repro.configs import get_config
    conf, traffic, cut = SMOKE[config]
    base = R.load_spec(LIMITS_OF)
    spec = {
        "name": f"{config}.smoke", "chips": 1,
        "config": R._json(R.BENCH / "configs" / f"{config}.json"),
        "traffic": R._json(R.BENCH / "traffic" / f"{traffic}.json"),
        "cell": dict(base["cell"], step_s=0.05, trace_steps=2, ref_rows=2),
        "end_to_end": [m for m in base["end_to_end"]
                       if "workloads" not in m],
        "per_layer": [],
        "program_cfg": get_config(config, smoke=True),
        "peaks": {"cpu": {"bf16_flops_per_s": 1e12}},
    }
    spec["config"].update(conf)
    spec["traffic"].update(cut)
    return spec
