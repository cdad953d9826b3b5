"""Gradient all-reduce time per step during which no other operation ran
on that chip, from the profiler trace, averaged over the chips."""


def read(r):
    per_chip = []
    for dev in r.trace.devices:
        steps = dev.module_count("train_step")
        coll = dev.intervals(lambda op: dev.is_collective(op))
        if steps <= 0 or not coll:
            continue
        rest = dev.intervals(lambda op: not dev.is_collective(op))
        per_chip.append(dev.exposed_seconds(coll, rest) / steps)
    return 1e3 * sum(per_chip) / len(per_chip) if per_chip else None
