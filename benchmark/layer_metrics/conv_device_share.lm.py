"""Device programs: of the device time of the step program's operations in
the traced span, the share that ran under the gated short convolutions
(``lfm2.conv``: the two projections, the gates and the three-tap
convolution), forward and backward: the trace's operations inside the step
program's module joined with the compiled program's scopes
(drivers/lfm2_sweep.py). Moves trials_per_hour."""

from _scopes import scope_share


def read(m):
    return scope_share(m, "conv")
