"""Bandwidth of the gradient exchange, in GB/s (1e9 B): the message bytes
of one step's collectives as the compiled step holds them
(``repro.launch.hlo_analysis``: each collective's result, per chip) over
the collectives' device seconds per step (the union of their intervals,
``bench/lib/phases.py``), averaged over the chips. None where the step
holds no collective."""
from bench.lib import phases
from bench.lib import program  # noqa: F401  (puts the program on sys.path)


def read(r):
    from repro.launch.hlo_analysis import analyze_hlo
    hlo = r.facts.get("hlo_text", "")
    s = phases.seconds_per_step(r.trace, hlo, "exchange")
    nbytes = analyze_hlo(hlo).collective_bytes if hlo else 0.0
    if s is None or nbytes <= 0:
        return None
    return nbytes / s / 1e9
