"""A cell's spec at a size the CPU tests can hold: the program's smoke
configuration of the same model, the benchmark's configuration file cut to
the same sizes, short rows, and the chunk1 cell's limits.

A configuration file that carries ``"smoke"`` gives the cut: ``sizes``
(its keys cut to the program's smoke preset), ``traffic`` (a traffic mix)
and ``cut`` (that mix cut to short rows). ``SMOKE`` holds every such file,
by its name, and the CPU tests run over it."""
import reference
import run as R

CONFIGS = R.BENCH / "configs"
_FILES = {p.stem: R._json(p) for p in sorted(CONFIGS.glob("*.json"))}
SMOKE = {name: conf["smoke"] for name, conf in _FILES.items()
         if "smoke" in conf}
LIMITS_OF = "qwen1.5-0.5b.train-chunk1"


def smoke_spec(config: str) -> dict:
    from repro.configs import get_config
    conf = _FILES[config]
    cut = conf["smoke"]
    base = R.load_spec(LIMITS_OF)
    spec = {
        "name": f"{config}.smoke", "chips": 1,
        "config": dict(conf, **cut["sizes"]),
        "traffic": dict(R._json(R.BENCH / "traffic"
                                / f"{cut['traffic']}.json"), **cut["cut"]),
        "cell": dict(base["cell"], step_s=0.05, trace_steps=2, ref_rows=2),
        "end_to_end": [m for m in base["end_to_end"]
                       if "workloads" not in m],
        "per_layer": [],
        "program_cfg": get_config(conf["registry"], smoke=True),
        "peaks": {"cpu": {"bf16_flops_per_s": 1e12}},
    }
    spec["reference"] = reference.load(spec["config"],
                                       source=CONFIGS / f"{config}.json")
    return spec
