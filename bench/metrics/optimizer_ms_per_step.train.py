"""Device time per train step of the optimizer: operations under the
program's ``train.optimizer`` scope (AdamW's update, its application, the
step counter and the step's metrics), and the unscoped copies XLA adds to
feed them; collectives and the codec left out (``bench/lib/phases.py``).
Averaged over the chips."""
from bench.lib import phases


def read(r):
    return phases.ms_per_step(r, "optimizer")
