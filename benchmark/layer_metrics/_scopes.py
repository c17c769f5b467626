"""Helper of the device-share readers (not a metric: no entry names it)."""


def scope_share(m: dict, group: str):
    """Percent of the joined device seconds that ran under ``group``'s named
    scope: the trace's operations inside the epoch program's module, joined
    by instruction name to ``op_name`` in that program's compiled text
    (drivers/lm_sweep.py). None where the join found nothing."""
    s = m.get("scope_seconds")
    if not s or s.get("joined", 0.0) <= 0.0:
        return None
    return 100.0 * s.get(group, 0.0) / s["joined"]
