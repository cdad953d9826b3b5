"""Share of the traced window in which no operation ran on the chip,
averaged over the chips, in %."""


def read(r):
    return 100.0 * r.trace.idle_share()
