"""Device programs, the sparse attention: of the device time of the step
program's operations in the traced span, the share that ran under the
``dsa.*`` scopes (``dsa.index``: the indexer's projections; ``dsa.select``:
its scores and the selection; ``dsa.attn``: the attention's projections,
norms, rotations and kernels; ``dsa.kl``: the indexer's loss), forward and
backward: the trace's operations inside the step program's module joined with
the compiled program's scopes (drivers/keye_sweep.py). Moves trials_per_hour."""

from _scopes import scope_share


def read(m):
    return scope_share(m, "dsa")
