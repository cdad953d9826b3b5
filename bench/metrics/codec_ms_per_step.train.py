"""Device time per step of every operation under the gradient codec
(``flat_qdq``: the Pallas kernels, the uniform draw, padding and
assembly), from the profiler trace, averaged over the chips."""

CODEC_SCOPE = "_qdq_flat_impl"


def codec_seconds_per_step(r):
    """Mean over chips of codec device seconds / steps; None without
    codec operations or steps in the trace."""
    per_chip = []
    for dev in r.trace.devices:
        steps = dev.module_count("train_step")
        t = dev.op_seconds(lambda op: CODEC_SCOPE in r.trace.scope(op))
        if steps > 0 and t > 0:
            per_chip.append(t / steps)
    return sum(per_chip) / len(per_chip) if per_chip else None


def read(r):
    s = codec_seconds_per_step(r)
    return None if s is None else 1e3 * s
