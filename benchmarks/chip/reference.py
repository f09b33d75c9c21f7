"""Plain reference of the benchmarked training step, in float32.

Straightforward ``jax.numpy`` with every contraction at
``Precision.HIGHEST``: the model's loss (a dense transformer with tied
embeddings, or a Mamba-2 stack whose SSD is the quadratic "attention" form
over the whole sequence), its gradient, global-norm clipping and AdamW. It
imports nothing of the program and reads its sizes from the benchmark's own
configuration files. Rows are processed in blocks, layers under
``jax.checkpoint``, so a full-width step fits one chip beside nothing else.

``mode="fp8"`` is the control: every contraction's operands (and, in the
backward pass, its cotangent) go through a per-tensor scaled
``float8_e4m3fn`` round trip, the precision below the bfloat16 that the
configurations state.

The parameters are drawn from the seed as the program draws them (same
layout, same key split, same per-leaf rule), so both start from one point.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

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
# parameter layout and the draw from the seed
# ---------------------------------------------------------------------------
def layout(cfg: Dict[str, Any]) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """``{path: (shape, init)}`` of every parameter, as the program lays
    them out (layers stacked on the leading axis)."""
    d, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    out: Dict[str, Tuple[Tuple[int, ...], str]] = {
        "embed/table": ((V, d), "normal"),
        "final_norm": ((d,), "ones"),
    }
    if cfg["family"] == "dense":
        H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        hd, ff = cfg["head_dim"], cfg["intermediate_size"]
        s = (L, 1)       # (layers, pattern of one full-attention layer)
        out.update({
            "blocks/ln1": (s + (d,), "ones"),
            "blocks/ln2": (s + (d,), "ones"),
            "blocks/attn/wq": (s + (d, H * hd), "normal"),
            "blocks/attn/wk": (s + (d, Hkv * hd), "normal"),
            "blocks/attn/wv": (s + (d, Hkv * hd), "normal"),
            "blocks/attn/wo": (s + (H * hd, d), "normal"),
            "blocks/ffn/w_gate": (s + (d, ff), "normal"),
            "blocks/ffn/w_up": (s + (d, ff), "normal"),
            "blocks/ffn/w_down": (s + (ff, d), "normal"),
        })
        if cfg["qkv_bias"]:
            out.update({"blocks/attn/bq": (s + (H * hd,), "zeros"),
                        "blocks/attn/bk": (s + (Hkv * hd,), "zeros"),
                        "blocks/attn/bv": (s + (Hkv * hd,), "zeros")})
        if not cfg["tie_word_embeddings"]:
            out["lm_head"] = ((d, V), "normal")
    elif cfg["family"] == "ssm":
        d_in, nh, G, N, W = _ssm_sizes(cfg)
        conv_ch = d_in + 2 * G * N
        out.update({
            "layers/ln": ((L, d), "ones"),
            "layers/in_proj": ((L, d, 2 * d_in + 2 * G * N + nh), "normal"),
            "layers/conv_w": ((L, W, conv_ch), "normal"),
            "layers/conv_b": ((L, conv_ch), "zeros"),
            "layers/a_log": ((L, nh), "ssm_a"),
            "layers/dt_bias": ((L, nh), "dt_bias"),
            "layers/d_skip": ((L, nh), "ones"),
            "layers/norm": ((L, d_in), "ones"),
            "layers/out_proj": ((L, d_in, d), "normal"),
        })
        if not cfg["tie_embeddings"]:
            out["lm_head"] = ((d, V), "normal")
    else:
        raise ValueError(f"no reference for family {cfg['family']!r}")
    return out


def _ssm_sizes(cfg):
    d_in = cfg["expand"] * cfg["hidden_size"]
    return (d_in, d_in // cfg["head_dim"], cfg["n_groups"], cfg["state_size"],
            cfg["conv_kernel"])


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


def _draw(shape, init, key) -> jax.Array:
    if init == "zeros":
        return jnp.zeros(shape, jnp.bfloat16)
    if init == "ones":
        return jnp.ones(shape, jnp.bfloat16)
    if init == "ssm_a":        # A = -exp(a_log), a_log = log U[1, 16]
        u = jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
        return jnp.log(u).astype(jnp.bfloat16)
    if init == "dt_bias":      # softplus^-1 of U[1e-3, 1e-1]
        u = jax.random.uniform(key, shape, jnp.float32, 1e-3, 1e-1)
        return jnp.log(jnp.expm1(u)).astype(jnp.bfloat16)
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    z = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    return (z / math.sqrt(max(fan_in, 1))).astype(jnp.bfloat16)


def drawer(cfg: Dict[str, Any]):
    """``key → {path: bfloat16 parameter}``, traceable."""
    lay = layout(cfg)
    paths = _sorted_paths(lay)

    def draw(key):
        keys = jax.random.split(key, len(paths))
        return {p: _draw(*lay[p], k) for p, k in zip(paths, keys)}
    return draw


def init_params(cfg: Dict[str, Any], seed: int) -> Dict[str, jax.Array]:
    """The bfloat16 parameters that the seed gives, flat by path."""
    return jax.jit(drawer(cfg))(jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# the models
# ---------------------------------------------------------------------------
def _rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (S, H, D); rotates the two halves of each head."""
    S, _, D = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _dense_layer(cfg, mm, x, p):
    """One pre-norm transformer layer on one row. x: (S, d)."""
    S = x.shape[0]
    H, Hkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = _rmsnorm(x, p["ln1"], eps)
    q = mm("sd,dh->sh", h, p["wq"])
    k = mm("sd,dh->sh", h, p["wk"])
    v = mm("sd,dh->sh", h, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = _rope(q.reshape(S, H, D), theta)
    k = _rope(k.reshape(S, Hkv, D), theta)
    v = v.reshape(S, Hkv, D)
    rep = H // Hkv
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = mm("shd,thd->hst", q, k) / math.sqrt(D)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = mm("hst,thd->shd", probs, v).reshape(S, H * D)
    x = x + mm("sh,hd->sd", attn, p["wo"])
    h = _rmsnorm(x, p["ln2"], eps)
    g = mm("sd,df->sf", h, p["w_gate"])
    u = mm("sd,df->sf", h, p["w_up"])
    return x + mm("sf,fd->sd", jax.nn.silu(g) * u, p["w_down"])


def _ssd(xh, dt, a, B, C, mm):
    """y[t] = Σ_{s≤t} (C_t·B_s) exp(Σ_{k=s+1..t} dt_k a) dt_s x_s, the
    quadratic form of the SSD recurrence over the whole row.
    xh: (S, H, P); dt: (S, H); a: (H,); B, C: (S, G, N)."""
    S, H, _ = xh.shape
    G = B.shape[1]
    cs = jnp.cumsum(dt * a, axis=0)                         # (S, H)
    causal = jnp.tril(jnp.ones((S, S), bool))[:, :, None]
    seg = jnp.where(causal, cs[:, None, :] - cs[None, :, :], 0.0)
    decay = jnp.where(causal, jnp.exp(seg), 0.0)            # (t, s, H)
    cb = mm("tgn,sgn->tsg", C, B)                           # (t, s, G)
    cb = jnp.repeat(cb, H // G, axis=2)                     # (t, s, H)
    return mm("tsh,shp->thp", decay * cb, xh * dt[..., None])


def _ssm_layer(cfg, mm, x, p):
    """One Mamba-2 layer (pre-norm, residual) on one row. x: (S, d)."""
    d_in, nh, G, N, W = _ssm_sizes(cfg)
    P_ = cfg["head_dim"]
    eps = cfg["norm_epsilon"]
    S = x.shape[0]
    h = _rmsnorm(x, p["ln"], eps)
    zxbcdt = mm("sd,dk->sk", h, p["in_proj"])
    z = zxbcdt[:, :d_in]
    xbc = zxbcdt[:, d_in: 2 * d_in + 2 * G * N]
    dt = zxbcdt[:, 2 * d_in + 2 * G * N:]
    pad = jnp.pad(xbc, ((W - 1, 0), (0, 0)))
    xbc = sum(pad[i: i + S] * p["conv_w"][i] for i in range(W)) + p["conv_b"]
    xbc = jax.nn.silu(xbc)
    xs = xbc[:, :d_in].reshape(S, nh, P_)
    B = xbc[:, d_in: d_in + G * N].reshape(S, G, N)
    C = xbc[:, d_in + G * N:].reshape(S, G, N)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["a_log"])
    y = _ssd(xs, dt, a, B, C, mm) + xs * p["d_skip"][None, :, None]
    y = _rmsnorm(y.reshape(S, d_in) * jax.nn.silu(z), p["norm"], eps)
    return x + mm("sk,kd->sd", y, p["out_proj"])


def _row_loss(cfg, mm, params, tokens, labels):
    """Mean cross-entropy of one row. params: flat by path, float32."""
    fam = cfg["family"]
    if fam == "dense":
        stack = {k.split("/")[-1]: v[:, 0] for k, v in params.items()
                 if k.startswith("blocks/")}
        layer, eps = _dense_layer, cfg["rms_norm_eps"]
        tied = cfg["tie_word_embeddings"]
    else:
        stack = {k.split("/")[-1]: v for k, v in params.items()
                 if k.startswith("layers/")}
        layer, eps = _ssm_layer, cfg["norm_epsilon"]
        tied = cfg["tie_embeddings"]
    x = params["embed/table"][tokens]
    body = jax.checkpoint(lambda h, p: (layer(cfg, mm, h, p), None))
    x, _ = lax.scan(body, x, stack)
    x = _rmsnorm(x, params["final_norm"], eps)
    if tied:
        logits = mm("sd,vd->sv", x, params["embed/table"])
    else:
        logits = mm("sd,dv->sv", x, params["lm_head"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold)


def _loss_and_grad(cfg, mode, rows, params, tokens, labels):
    """Mean loss over the batch and its gradient, ``rows`` rows at a time."""
    mm = MATMUL[mode]
    nb = tokens.shape[0] // rows
    blocks = (tokens.reshape(nb, rows, -1), labels.reshape(nb, rows, -1))

    def block_loss(p, tb, lb):
        return jnp.mean(jax.vmap(partial(_row_loss, cfg, mm, p))(tb, lb))

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


def train_first_steps(cfg: Dict[str, Any], opt: Dict[str, Any], batches,
                      seed: int, total_steps: int, *, mode: str = "f32",
                      rows: int = 1, keep_rows: Optional[int] = None,
                      ) -> Dict[str, Any]:
    """Follow the program's first ``len(batches)`` steps from the seed.

    ``opt`` holds the optimizer's settings and ``lr``; ``keep_rows`` trains
    on the first rows of each batch only (the half-batch fault). Returns the
    loss of each step, the first step's global gradient norm before
    clipping, and the per-leaf norms and samples (``leaf_samples``) of the
    first step's clipped gradient (as the optimizer receives it) and of the
    parameters' change over all the steps, keyed by path.
    """
    lg = jax.jit(partial(_loss_and_grad, cfg, mode, rows))
    step_fn = jax.jit(partial(_adamw, opt, total_steps),
                      donate_argnums=(0, 1, 2))
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          init_params(cfg, seed))
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
    cn, cs = change_from_seed(cfg, params, seed)
    return {"losses": losses, "grad_norms": first[0], "grad_sample": first[1],
            "grad_norm": first[2], "change_norms": cn, "change_sample": cs}


def change_from_seed(cfg: Dict[str, Any], params, seed: int):
    """Per-leaf norms and samples of ``params`` less the seed's
    parameters, drawn inside the same program so that they take no device
    memory of their own."""
    draw = drawer(cfg)

    def f(p, key):
        d = {k: x.astype(jnp.float32) - q.astype(jnp.float32)
             for (k, x), q in zip(sorted(p.items()),
                                  (v for _, v in sorted(draw(key).items())))}
        return leaf_norms(d), leaf_samples(d)
    n, smp = jax.jit(f)(params, jax.random.PRNGKey(seed))
    return ({k: float(x) for k, x in n.items()},
            {k: np.asarray(x) for k, x in smp.items()})
