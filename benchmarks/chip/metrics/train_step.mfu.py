"""Model FLOP/s over the chips' bf16 peak, in the traced window: the
configuration's training FLOPs per token (``flops.py``) times the tokens of
the step executions in the window, over the window's seconds."""


def read(rec):
    tr = rec["trace"]
    if not tr or tr["window_s"] <= 0 or not tr["steps"]:
        return None
    flops = rec["flops_per_token"] * tr["steps"] * rec["tokens_per_step"]
    peak = rec["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops / tr["window_s"] / (rec["chips"] * peak)
