"""Model FLOP/s utilization of training over the traced window, in %:
model FLOPs per token times the tokens of the train steps that ran in the
window (each step counted by the share of it inside the window, averaged
over the chips), over the window times chips times the bf16 peak.

Model FLOPs per token: 6 per matrix-product weight (forward and backward)
plus 3 x the forward attention FLOPs against every position of the
sequence. The program computes the whole S x S score matrix under a mask,
so no causal halving is applied; recomputation is not counted."""
from bench.lib.flops import attn_flops_per_key, matmul_params


def flops_per_token(cfg: dict, seq_len: int) -> float:
    return 6.0 * matmul_params(cfg) + 3.0 * attn_flops_per_key(cfg) * seq_len


def read(r):
    devs = r.trace.devices
    steps = sum(d.module_count("train_step") for d in devs) / len(devs)
    if steps <= 0:
        return None
    tokens = steps * r.mix["batch"] * r.mix["seq_len"]
    return 100.0 * flops_per_token(r.config, r.mix["seq_len"]) * tokens / (
        r.trace.window_s() * len(devs) * r.peaks["bf16_flops_per_s"])
