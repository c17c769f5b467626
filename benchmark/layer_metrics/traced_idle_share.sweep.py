"""Device: the share of the traced span in which no operation ran on the
device, 1 - union of device-operation intervals / span, from the
``jax.profiler`` trace by benchmark/trace_reduce.py. It is a share of that
span and of nothing else: the traffic's file lays the span (``trace``:
``start_s`` after the window opens, for ``seconds``) from inside one
round's epoch program to inside the next one's, so that it holds one whole
hand-over; inside an epoch program the device runs one while loop and does
not idle. The idle seconds of the span are in the result's ``device``
(``window_s`` - ``busy_s``). Moves trials_per_hour."""


def read(m):
    t = m.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
