"""flops.py against counts made by hand from the published sizes."""
import json
from pathlib import Path

import flops

HERE = Path(__file__).resolve().parent


def _conf(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def test_qwen_matmul_params_and_train_flops_by_hand():
    cfg = _conf("qwen1.5-0.5b")
    # per layer: q, k, v, o 4 x 1024 x 1024; gate, up, down 3 x 1024 x 2816
    layer = 4 * 1024 * 1024 + 3 * 1024 * 2816           # 12,845,056
    head = 1024 * 151936                                # tied, used once
    assert flops.matmul_params(cfg) == 24 * layer + head == 463_863_808
    # causal attention: QK^T and PV, 2 x 2 x 16 heads x 64 x 512.5 a layer
    attn = 24 * 4 * 1024 * 512.5                        # 50,380,800
    assert flops.attention_flops_per_token(cfg, 1024) == attn
    assert flops.train_flops_per_token(cfg, 1024) == \
        3 * (2 * 463_863_808 + attn) == 2_934_325_248


def test_qwen_matmul_params_are_the_programs_params_less_norms_and_bias():
    from repro.configs import get_config
    n = get_config("qwen1.5-0.5b").param_count()
    norms_bias = 24 * (2 * 1024 + 3 * 1024) + 1024
    assert flops.matmul_params(_conf("qwen1.5-0.5b")) + norms_bias == n


def test_mamba_matmul_params_and_train_flops_by_hand():
    cfg = _conf("mamba2-370m")
    # in_proj 1024 -> z, x (2 x 2048), B, C (2 x 128), dt (32 heads);
    # out_proj 2048 -> 1024
    layer = 1024 * (4096 + 256 + 32) + 2048 * 1024      # 6,586,368
    head = 1024 * 50280
    assert flops.matmul_params(cfg) == 48 * layer + head == 367_632_384
    # SSD per layer and token, chunk 256 (causal mean 128.5):
    # C.B^T 2 x 128 x 128.5, mix 2 x 32 x 64 x 128.5, B^T x and C.h
    # 2 x (2 x 32 x 64 x 128)
    ssd = 48 * (32_896 + 526_336 + 1_048_576)           # 77,174,784
    assert flops.ssd_flops_per_token(cfg, 2048) == ssd
    assert flops.train_flops_per_token(cfg, 2048) == \
        3 * (2 * 367_632_384 + ssd) == 2_437_318_656


def test_kernel_counts_share_the_per_token_counts():
    q, m = _conf("qwen1.5-0.5b"), _conf("mamba2-370m")
    att = flops.attention_kernel(q, 8, 1024)
    assert att["flops"] * 24 == 8 * 1024 * flops.attention_flops_per_token(
        q, 1024)
    assert att["bytes"] == 8 * 1024 * 64 * 64 * 2        # q, k, v, o bf16
    ssd = flops.ssd_kernel(m, 8, 2048)
    assert ssd["flops"] * 48 == 8 * 2048 * flops.ssd_flops_per_token(m, 2048)
    head = flops.head_loss_kernel(q, 8, 1024)
    assert head["flops"] == 8 * 1024 * 2 * 1024 * 151936
