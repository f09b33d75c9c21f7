"""Logical-axis sharding rules (MaxText-style) with divisibility degradation.

Every parameter and input dim carries a *logical* axis name; the rules map
logical axes → mesh axes (``data``, ``model``). Assignment degrades
gracefully: a mesh axis is only applied when the dim size is divisible by the
mesh extent and the axis isn't already used by another dim of the same tensor
— so one rule table serves all ten architectures (e.g. whisper's vocab 51865
is indivisible by 16 and silently replicates, gemma's 262144 shards).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

AxisAssign = Union[None, str, Tuple[str, ...]]
Rules = Dict[Optional[str], AxisAssign]


def _as_tuple(a: AxisAssign) -> Tuple[str, ...]:
    if a is None:
        return ()
    if isinstance(a, str):
        return (a,)
    return tuple(a)


def base_rules(family: str = "dense") -> Rules:
    """Default parameter rules: TP over "model", DP/ZeRO over "data".

    MoE expert weights dominate parameter bytes (mixtral: 264 of 280 GB) —
    model-axis TP alone leaves >17 GB/chip, so their hidden dim shards over
    the data axis too (2-D weight sharding ≈ FSDP on the expert tensors;
    XLA inserts the per-layer gathers)."""
    return {
        "vocab": "model",
        "heads": "model",
        "kv_heads": "model",
        "ff": ("data", "model") if family == "moe" else "model",
        "experts": None,
        "ssm_inner": "model",
        "embed": None,
        "layers": None,
        "pattern": None,
        None: None,
    }


def train_rules(family: str = "dense") -> Rules:
    return {**base_rules(family), "batch": "data"}


def spec_for(shape: Sequence[int], axes: Sequence[Optional[str]],
             rules: Rules, mesh: Mesh) -> PartitionSpec:
    """Resolve one tensor's PartitionSpec with divisibility degradation."""
    used: set = set()
    out = []
    for size, logical in zip(shape, axes):
        cands = _as_tuple(rules.get(logical, None))
        take = []
        ext = 1
        for ax in cands:
            if ax in used or ax not in mesh.shape:
                continue
            e = mesh.shape[ax]
            if size % (ext * e) == 0:
                take.append(ax)
                ext *= e
        for ax in take:
            used.add(ax)
        out.append(tuple(take) if len(take) > 1 else (take[0] if take else None))
    return PartitionSpec(*out)


def shardings_for_tree(shapes_tree: Any, axes_tree: Any, rules: Rules,
                       mesh: Mesh) -> Any:
    """Build NamedShardings for a pytree of ShapeDtypeStructs + axes tuples.

    The axes tree has *tuple* leaves (which jax would otherwise traverse as
    subtrees), so flatten the shapes tree first and match axes up to it.
    """
    is_sds = lambda x: isinstance(x, jax.ShapeDtypeStruct)  # noqa: E731
    flat_s, treedef = jax.tree.flatten(shapes_tree, is_leaf=is_sds)
    flat_a = treedef.flatten_up_to(axes_tree)
    out = [NamedSharding(mesh, spec_for(s.shape, a, rules, mesh))
           for s, a in zip(flat_s, flat_a)]
    return jax.tree.unflatten(treedef, out)


def input_axes(cfg) -> Dict[str, Any]:
    """Logical axes for the ``Model.input_specs()`` tree."""
    ax: Dict[str, Any] = {"tokens": ("batch", None),
                          "labels": ("batch", None)}
    if cfg.family == "vlm":
        ax["patches"] = ("batch", None, None)
    if cfg.family == "audio":
        ax["frames"] = ("batch", None, None)
    return ax
