"""Device programs: of the device time of the step program's operations in
the traced span, the share that ran under the grouped-query attention
(``lfm2.attn``: the projections, the per-head norms, the rotations and the
fused attention's kernel calls, which the join finds across the lines their
``custom-call`` is printed over), forward and backward
(drivers/lfm2_sweep.py). Moves trials_per_hour."""

from _scopes import scope_share


def read(m):
    return scope_share(m, "attn")
