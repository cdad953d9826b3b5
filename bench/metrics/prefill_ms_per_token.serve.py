"""Device time of the engine's prefill programs per prompt token they
filled in the traced window."""

PREFILL_MODULE = "_prefill"


def read(r):
    tokens = r.facts["prefill_tokens"]
    dev = r.trace.devices[0]
    t = dev.module_seconds(PREFILL_MODULE)
    if tokens <= 0 or t <= 0:
        return None
    return 1e3 * t / tokens
