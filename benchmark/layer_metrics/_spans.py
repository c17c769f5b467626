"""Helpers shared by the span readers (not a metric: no entry names it)."""


def span_seconds(m: dict, name: str):
    """(summed seconds, count) of the window's spans of that name."""
    d = [s["dur_s"] for s in m["spans"] if s["name"] == name]
    return sum(d), len(d)


def share(m: dict, name: str):
    total, n = span_seconds(m, name)
    if n == 0 or m["window_s"] <= 0:
        return None
    return 100.0 * total / m["window_s"]
