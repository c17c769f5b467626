"""Device programs, a looped stack: the wall milliseconds one visit of one
layer costs, with the heads, the objective and the optimizer spread over the
visits: the window's ``train.epoch`` span seconds over the layer visits its
steps counted on the device (``count.loop.layer_calls``: passes x held layers
a step; drivers/ouro_sweep.py takes the warm-up trial's step off). None where
the program has no such counter. Moves trials_per_hour."""

from _spans import span_seconds


def read(m):
    calls = (m.get("counters") or {}).get("loop.layer_calls")
    seconds, n = span_seconds(m, "train.epoch")
    if not calls or calls <= 0 or n == 0:
        return None
    return 1000.0 * seconds / calls
