"""The SSD scan's share of its roofline: the least time the chip needs for
a step's SSD work over the device time of the ``ssd`` scope a step
(``train_step.ssd_ms``). One layer's call over the step's rows
(``flops.ssd_kernel``) needs the larger of its FLOPs over the bf16 peak
and its least bytes over the HBM bandwidth, in each of the ``flops.TRAIN``
passes of every layer. Both counts are lower bounds (no recompute, each
operand moved once), so the share cannot pass 100%."""
import flops


def read(rec):
    scope = (rec.get("spans") or {}).get("scope_s") or {}
    if not scope.get("ssd"):
        return None
    cfg, peaks = rec["config"], rec["peaks"]
    k = flops.ssd_kernel(cfg, rec["batch"], rec["seq"])
    per_pass = max(k["flops"] / peaks["bf16_flops_per_s"],
                   k["bytes"] / peaks["hbm_bytes_per_s"])
    least = per_pass * cfg["num_hidden_layers"] * flops.TRAIN
    return 100.0 * least / scope["ssd"]
