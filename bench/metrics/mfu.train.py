"""Model FLOP/s utilization of training over the traced window, in %:
model FLOPs per token times the tokens of the train steps that ran in the
window (each step counted by the share of it inside the window, averaged
over the chips), over the window times chips times the bf16 peak.

Model FLOPs per token: the configuration's family counts them
(``model_flops_per_token`` of ``bench/models/<family>.py``): forward and
backward, only the weights a token multiplies, plus attention."""


def read(r):
    devs = r.trace.devices
    steps = sum(d.module_count("train_step") for d in devs) / len(devs)
    if steps <= 0:
        return None
    tokens = steps * r.mix["batch"] * r.mix["seq_len"]
    flops = r.family.model_flops_per_token(r.config, r.mix["seq_len"])
    return 100.0 * flops * tokens / (
        r.trace.window_s() * len(devs) * r.peaks["bf16_flops_per_s"])
