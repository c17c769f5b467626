"""Device programs: of the device time of the step program's operations in
the traced span, the share that ran under the dense feed-forward part
(``lm.ffn``: the norm before it, the gated unit's three products and the
norm after it, of every layer visit), forward and backward, joined as the
other device shares are (drivers/ouro_sweep.py). Moves trials_per_hour."""

from _scopes import scope_share


def read(m):
    return scope_share(m, "ffn")
