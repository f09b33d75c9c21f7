"""Plain reference of the benchmarked training step, in float32.

Straightforward ``jax.numpy`` with every contraction at
``Precision.HIGHEST``: the model's loss, its gradient, global-norm clipping
and AdamW. The model itself is the module ``references/<name>.py`` that
the configuration names under ``"reference"`` (``load``): its parameter
layout and the loss of a block of rows; this file holds what every model
shares. It imports nothing of the program and reads its sizes from the
benchmark's own configuration files. Rows are processed in blocks, layers
under ``jax.checkpoint``, so a full-width step fits one chip beside nothing
else.

``mode="fp8"`` is the control: every contraction's operands (and, in the
backward pass, its cotangent) go through a per-tensor scaled
``float8_e4m3fn`` round trip, the precision below the bfloat16 that the
configurations state.

The parameters are drawn from the seed as the program draws them (same
layout, same key split, same per-leaf rule), so both start from one point.
"""
from __future__ import annotations

import importlib.util
import math
from functools import partial
from pathlib import Path
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
F8_MAX = 448.0            # largest finite float8_e4m3fn


# ---------------------------------------------------------------------------
# contractions
# ---------------------------------------------------------------------------
def _mm32(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _q8(x: jax.Array) -> jax.Array:
    """Per-tensor scaled float8_e4m3fn round trip, back in float32."""
    s = jnp.max(jnp.abs(x)) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm8(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return _mm32(spec, _q8(a), _q8(b))


def _mm8_fwd(spec, a, b):
    qa, qb = _q8(a), _q8(b)
    return _mm32(spec, qa, qb), (qa, qb)


def _mm8_bwd(spec, res, g):
    qa, qb = res
    _, pull = jax.vjp(partial(_mm32, spec), qa, qb)
    return pull(_q8(g))


_mm8.defvjp(_mm8_fwd, _mm8_bwd)

MATMUL = {"f32": _mm32, "fp8": _mm8}


# ---------------------------------------------------------------------------
# the model's own module, by the name its configuration gives
# ---------------------------------------------------------------------------
REFERENCES = Path(__file__).resolve().parent / "references"
MODULE = ("layout", "block_loss", "matmul_params", "mixer_flops_per_token",
          "SCOPES", "INITS")


def module_from(path: Path, name: str):
    """The Python file at ``path``, loaded as a module named ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load(cfg: Dict[str, Any], source: Optional[Path] = None):
    """``REFERENCES/<cfg["reference"]>.py``, the plain model of the
    configuration's kind. It gives ``layout(cfg)`` (``{path: (shape,
    init)}``, every parameter as the program lays it out), ``INITS`` (its
    own init kinds: ``{init: (key, shape) → bfloat16}``),
    ``block_loss(cfg, mm, params, tokens, labels)`` (the mean loss of a
    block of rows), ``matmul_params(cfg)`` and ``mixer_flops_per_token(cfg,
    seq)`` (its FLOP count, ``flops.py``), and ``SCOPES`` (the named scopes
    its program adds to the shared ones, ``spans.py``). An error names
    ``source``, the configuration's file, where it is given."""
    where = source or f"configuration {cfg.get('registry')!r}"
    if "reference" not in cfg:
        raise SystemExit(f"{where} names no 'reference' module "
                         f"(references/<name>.py)")
    path = REFERENCES / f"{cfg['reference']}.py"
    if not path.is_file():
        raise SystemExit(f"no reference module {path} for {where}")
    mod = module_from(path,
                      f"chipbench_reference_{path.stem.replace('.', '_')}")
    missing = [n for n in MODULE if not hasattr(mod, n)]
    if missing:
        raise SystemExit(f"reference module {path} lacks {missing}")
    return mod


# ---------------------------------------------------------------------------
# the draw from the seed
# ---------------------------------------------------------------------------
def _sorted_paths(lay: Dict[str, Any]) -> List[str]:
    """Depth-first with the keys of every level sorted: the order in which
    the program splits its key over the leaves."""
    tree: Dict[str, Any] = {}
    for path in lay:
        node = tree
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = path

    def walk(node):
        for k in sorted(node):
            v = node[k]
            yield from (walk(v) if isinstance(v, dict) else (v,))
    return list(walk(tree))


def _draw(shape, init, key, inits) -> jax.Array:
    if init in inits:
        return inits[init](key, shape)
    if init == "zeros":
        return jnp.zeros(shape, jnp.bfloat16)
    if init == "ones":
        return jnp.ones(shape, jnp.bfloat16)
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    z = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    return (z / math.sqrt(max(fan_in, 1))).astype(jnp.bfloat16)


def drawer(kind, cfg: Dict[str, Any]):
    """``key → {path: bfloat16 parameter}``, traceable."""
    lay = kind.layout(cfg)
    paths = _sorted_paths(lay)

    def draw(key):
        keys = jax.random.split(key, len(paths))
        return {p: _draw(*lay[p], k, kind.INITS)
                for p, k in zip(paths, keys)}
    return draw


def init_params(kind, cfg: Dict[str, Any],
                seed: int) -> Dict[str, jax.Array]:
    """The bfloat16 parameters that the seed gives, flat by path."""
    return jax.jit(drawer(kind, cfg))(jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# what the models share
# ---------------------------------------------------------------------------
def rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x: (S, H, D); rotates the two halves of each head."""
    S, _, D = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def lm_row_loss(mm, params, tokens, labels, layer, stack, eps, tied):
    """Mean cross-entropy of one row through the embedding, ``layer(x, p)``
    scanned over the stacked ``stack`` under ``jax.checkpoint``, the final
    RMSNorm and the head (the embedding's transpose where ``tied``)."""
    x = params["embed/table"][tokens]
    body = jax.checkpoint(lambda h, p: (layer(h, p), None))
    x, _ = lax.scan(body, x, stack)
    x = rmsnorm(x, params["final_norm"], eps)
    if tied:
        logits = mm("sd,vd->sv", x, params["embed/table"])
    else:
        logits = mm("sd,dv->sv", x, params["lm_head"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold)


def _loss_and_grad(kind, cfg, mode, rows, params, tokens, labels):
    """Mean loss over the batch and its gradient, ``rows`` rows at a time
    (the module's ``block_loss`` over each block)."""
    block_loss = partial(kind.block_loss, cfg, MATMUL[mode])
    nb = tokens.shape[0] // rows
    blocks = (tokens.reshape(nb, rows, -1), labels.reshape(nb, rows, -1))

    def body(acc, blk):
        loss, g = jax.value_and_grad(block_loss)(params, *blk)
        acc_l, acc_g = acc
        return (acc_l + loss / nb,
                jax.tree.map(lambda a, b: a + b / nb, acc_g, g)), None

    zeros = jax.tree.map(jnp.zeros_like, params)
    (loss, grads), _ = lax.scan(body, (jnp.zeros((), jnp.float32), zeros),
                                blocks)
    return loss, grads


SAMPLE = 65_536          # entries of a leaf compared one by one


def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def leaf_samples(tree):
    """Up to ``SAMPLE`` entries of each leaf at fixed, evenly strided
    positions, in float32: both sides take the same ones."""
    def take(v):
        flat = v.reshape(-1)
        return flat[:: max(1, flat.size // SAMPLE)][:SAMPLE].astype(
            jnp.float32)
    return {k: take(v) for k, v in tree.items()}


def _adamw(opt, total_steps, params, m, v, g, step):
    """One AdamW step with global-norm clipping and warm-up + cosine."""
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in g.values()))
    clip = opt["grad_clip"]
    scale = jnp.minimum(1.0, clip / (gnorm + 1e-9))
    g = {k: x * scale for k, x in g.items()}
    b1, b2 = opt["b1"], opt["b2"]
    m = {k: b1 * m[k] + (1 - b1) * g[k] for k in g}
    v = {k: b2 * v[k] + (1 - b2) * g[k] * g[k] for k in g}
    t = step.astype(jnp.float32)
    warm, peak, floor = opt["warmup_steps"], opt["lr"], opt["lr_floor"]
    frac = jnp.clip((t - warm) / max(total_steps - warm, 1), 0.0, 1.0)
    lr = jnp.where(t < warm, peak * t / max(warm, 1),
                   peak * (floor + (1 - floor) * 0.5
                           * (1 + jnp.cos(math.pi * frac))))
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
    new = {k: params[k] - lr * ((m[k] / bc1) / (jnp.sqrt(v[k] / bc2)
                                                 + opt["eps"])
                                + opt["weight_decay"] * params[k])
           for k in g}
    return new, m, v, leaf_norms(g), leaf_samples(g), gnorm


def train_first_steps(kind, cfg: Dict[str, Any], opt: Dict[str, Any],
                      batches, seed: int, total_steps: int, *,
                      mode: str = "f32", rows: int = 1,
                      keep_rows: Optional[int] = None) -> Dict[str, Any]:
    """Follow the program's first ``len(batches)`` steps from the seed.

    ``opt`` holds the optimizer's settings and ``lr``; ``keep_rows`` trains
    on the first rows of each batch only (the half-batch fault). Returns the
    loss of each step, the first step's global gradient norm before
    clipping, and the per-leaf norms and samples (``leaf_samples``) of the
    first step's clipped gradient (as the optimizer receives it) and of the
    parameters' change over all the steps, keyed by path.
    """
    lg = jax.jit(partial(_loss_and_grad, kind, cfg, mode, rows))
    step_fn = jax.jit(partial(_adamw, opt, total_steps),
                      donate_argnums=(0, 1, 2))
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          init_params(kind, cfg, seed))
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    for i, b in enumerate(batches):
        tok, lab = b["tokens"], b["labels"]
        if keep_rows:
            tok, lab = tok[:keep_rows], lab[:keep_rows]
        loss, g = lg(params, jnp.asarray(tok), jnp.asarray(lab))
        params, m, v, gn, gs, gnorm = step_fn(params, m, v, g,
                                              jnp.int32(i + 1))
        del g
        losses.append(float(loss))
        if first is None:
            first = ({k: float(x) for k, x in gn.items()},
                     {k: np.asarray(x) for k, x in gs.items()}, float(gnorm))
    del m, v
    cn, cs = change_from_seed(kind, cfg, params, seed)
    return {"losses": losses, "grad_norms": first[0], "grad_sample": first[1],
            "grad_norm": first[2], "change_norms": cn, "change_sample": cs}


def change_from_seed(kind, cfg: Dict[str, Any], params, seed: int):
    """Per-leaf norms and samples of ``params`` less the seed's
    parameters, drawn inside the same program so that they take no device
    memory of their own."""
    draw = drawer(kind, cfg)

    def f(p, key):
        d = {k: x.astype(jnp.float32) - q.astype(jnp.float32)
             for (k, x), q in zip(sorted(p.items()),
                                  (v for _, v in sorted(draw(key).items())))}
        return leaf_norms(d), leaf_samples(d)
    n, smp = jax.jit(f)(params, jax.random.PRNGKey(seed))
    return ({k: float(x) for k, x in n.items()},
            {k: np.asarray(x) for k, x in smp.items()})
