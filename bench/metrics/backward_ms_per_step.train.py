"""Device time per train step of the backward pass: operations under
``transpose(`` of the program's ``train.forward`` scope, and the unscoped
copies XLA adds to feed them; collectives and the codec left out
(``bench/lib/phases.py``). Averaged over the chips."""
from bench.lib import phases


def read(r):
    return phases.ms_per_step(r, "backward")
