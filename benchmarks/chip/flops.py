"""Model FLOPs of the benchmarked work, from a configuration's sizes.

Counted is the work the algorithm needs: every multiply-add of a matrix
product is two operations, a causal mixer counts only the positions at or
before each query, and a training token costs its forward pass three times
(forward, and a backward pass of twice its work). Work that remat repeats
is not counted, nor are elementwise operations, norms and the softmax.

What depends on the model's kind (which weights a token multiplies, which
sequence mixer it runs) is counted by the configuration's reference module
(``references/<name>.py``). Per-kernel counts (attention, the SSD scan, the
head with its loss) are here, with the bytes each must move at least, so
that a kernel roofline judges an XLA and a Pallas version on the same
work.
"""
from __future__ import annotations

from typing import Dict

import reference

TRAIN = 3            # forward + backward (twice the forward)


def matmul_params(cfg: Dict) -> int:
    """Weights that enter a matrix product once per token (the head
    included, the embedding lookup not), as the configuration's reference
    module counts them."""
    return reference.load(cfg).matmul_params(cfg)


def causal_context(seq: int) -> float:
    """Mean number of positions a causal query attends to."""
    return (seq + 1) / 2


def attention_flops_per_token(cfg: Dict, seq: int) -> float:
    """Forward QKᵀ and PV of one token over its causal context, all
    layers."""
    H, hd = cfg["num_attention_heads"], cfg["head_dim"]
    return cfg["num_hidden_layers"] * 2 * 2 * H * hd * causal_context(seq)


def ssd_flops_per_token(cfg: Dict, seq: int) -> float:
    """Forward chunked SSD of one token, all layers: within its chunk the
    causal C·Bᵀ and the decayed mix of inputs, across chunks its share of
    the chunk state (Bᵀx) and the state's read-out (C·h)."""
    nh = cfg["expand"] * cfg["hidden_size"] // cfg["head_dim"]
    P, N, G = cfg["head_dim"], cfg["state_size"], cfg["n_groups"]
    q = causal_context(min(cfg["chunk_size"], seq))
    per_layer = 2 * G * N * q + 2 * nh * P * q + 2 * (2 * nh * P * N)
    return cfg["num_hidden_layers"] * per_layer


def train_flops_per_token(cfg: Dict, seq: int) -> float:
    """The weights' matrix products and the configuration's reference
    module's sequence mixer (attention, the SSD scan...), forward and
    backward."""
    kind = reference.load(cfg)
    return TRAIN * (2.0 * kind.matmul_params(cfg)
                    + kind.mixer_flops_per_token(cfg, seq))


# --- per kernel: (forward FLOPs, least HBM bytes) for one call ---
def attention_kernel(cfg: Dict, batch: int, seq: int,
                     itemsize: int = 2) -> Dict[str, float]:
    """One layer's causal attention over ``batch`` rows: q, k, v read and
    the output written once."""
    H, Hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    flops = batch * seq * 2 * 2 * H * hd * causal_context(seq)
    bytes_ = batch * seq * hd * (2 * H + 2 * Hkv) * itemsize
    return {"flops": flops, "bytes": bytes_}


def ssd_kernel(cfg: Dict, batch: int, seq: int,
               itemsize: int = 2) -> Dict[str, float]:
    """One layer's SSD scan over ``batch`` rows: x, dt, B, C read and y
    written once."""
    nh = cfg["expand"] * cfg["hidden_size"] // cfg["head_dim"]
    P, gn = cfg["head_dim"], cfg["n_groups"] * cfg["state_size"]
    flops = batch * seq * ssd_flops_per_token(
        cfg, seq) / cfg["num_hidden_layers"]
    bytes_ = batch * seq * (2 * nh * P + nh + 2 * gn) * itemsize
    return {"flops": flops, "bytes": bytes_}


def head_loss_kernel(cfg: Dict, batch: int, seq: int,
                     itemsize: int = 2) -> Dict[str, float]:
    """The output head and its cross-entropy over ``batch`` rows: the
    weights and the final hidden states read once; the logits need not
    reach HBM."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    flops = batch * seq * 2 * d * V
    bytes_ = (d * V + batch * seq * d) * itemsize
    return {"flops": flops, "bytes": bytes_}
