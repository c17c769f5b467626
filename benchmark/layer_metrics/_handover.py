"""Helpers of the hand-over readers (not a metric: no entry names it).

A hand-over is the worker thread's stretch from the end of one pack round's
``trial_pack.evaluate`` to the start of the next ``train.packed_epoch`` on
the same thread, on the spans' monotonic stamps (``mono``, ``dur_s``,
``thread``). A program whose span records carry no such stamps, or no such
spans, has no hand-over to read: every reader then returns None."""


def _end(s: dict) -> float:
    return s["mono"] + s["dur_s"]


def handovers(m: dict):
    """[(thread, start, end)], one for each pair of consecutive rounds."""
    spans = [s for s in m["spans"] if "mono" in s and "thread" in s]
    epochs = sorted((s for s in spans if s["name"] == "train.packed_epoch"),
                    key=lambda s: s["mono"])
    out = []
    for ev in (s for s in spans if s["name"] == "trial_pack.evaluate"):
        nxt = next((p for p in epochs if p["thread"] == ev["thread"]
                    and p["mono"] >= _end(ev)), None)
        if nxt is not None:
            out.append((ev["thread"], _end(ev), nxt["mono"]))
    return sorted(out, key=lambda h: h[1])


def uncovered_seconds(m: dict, thread: str, a: float, b: float) -> float:
    """The part of [a, b) that no childless span of that thread covers."""
    parents = {s.get("parent_id") for s in m["spans"]}
    cover = sorted((max(a, s["mono"]), min(b, _end(s))) for s in m["spans"]
                   if s.get("thread") == thread and "mono" in s
                   and s["span_id"] not in parents
                   and s["mono"] < b and _end(s) > a)
    gap, at = 0.0, a
    for lo, hi in cover:
        gap += max(0.0, lo - at)
        at = max(at, hi)
    return gap + max(0.0, b - at)
