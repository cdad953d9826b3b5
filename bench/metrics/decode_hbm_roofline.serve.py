"""The decode step's share of its HBM roofline, in %.

Least bytes of one decode call: every weight once, and the keys and
values of the positions in use by the active slots, both at the
configuration's stated dtype, as its family counts them
(``bench/models/<family>.py``). Their sum over the traced decode calls,
divided by the HBM peak, over the device time of those calls."""
DECODE_MODULE = "_decode"
BYTES = {"bfloat16": 2, "float32": 4}


def least_bytes(family, cfg: dict, calls: int, positions: int) -> float:
    b = BYTES[cfg["torch_dtype"]]
    return calls * family.n_params(cfg) * b \
        + positions * family.kv_bytes_per_position(cfg, b)


def read(r):
    t = r.trace.devices[0].module_seconds(DECODE_MODULE)
    calls = r.facts["decode_calls"]
    if calls <= 0 or t <= 0:
        return None
    return 100.0 * least_bytes(r.family, r.config, calls,
                               r.facts["decode_positions"]) \
        / r.peaks["hbm_bytes_per_s"] / t
