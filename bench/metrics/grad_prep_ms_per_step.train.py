"""Device time per train step of the gradient's preparation around the
codec: operations under the program's ``train.clip``, ``train.flatten``,
``train.error_feedback`` and ``train.unflatten`` scopes, and the unscoped
copies XLA adds to feed them; collectives and the codec left out
(``bench/lib/phases.py``). Averaged over the chips."""
from bench.lib import phases


def read(r):
    return phases.ms_per_step(r, "grad_prep")
