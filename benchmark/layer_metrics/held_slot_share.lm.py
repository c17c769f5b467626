"""Sparse experts: of the token-slots the router filled in the window's
train steps (``moe.slots_total``: tokens x experts per token x sparse
layers), the share routed to experts this chip holds (``moe.slots_held``),
both counted on the device and carried by the epoch's metrics. Under uniform
routing it is held / experts (8 / 256 = 3.1%); what it reads above or below
is the router's skew towards this chip. Moves trials_per_hour."""


def read(m):
    c = m.get("counters") or {}
    if not c.get("moe.slots_total"):
        return None
    return 100.0 * c["moe.slots_held"] / c["moe.slots_total"]
