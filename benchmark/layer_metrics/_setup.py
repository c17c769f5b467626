"""Helper of the set-up readers (not a metric: no entry names it).

``measured["spans"]`` holds the window alone, so this helper takes the
process's own ring (``telemetry.span_records()``): the set-up's records are
those whose ``ts`` lies before the earliest ``ts`` of the window's. Set-up
is the ``setup_s`` seconds that end where the window's first record starts.
The records are laid on their threads by ``mono`` and every second of it is
counted ONCE: an instant belongs to the innermost record open on its thread
(so a ``compile.*`` or ``data.*`` record's seconds come out of the span that
encloses it, and nested ``compile.trace`` records count as their union), a
worker's thread (one that holds ``trial.total`` or ``trial_pack.total``) goes
before any other, and what no record of any thread covers is outside the
program. A program that writes no ``compile.*`` record (the parent of PR 35)
has no split: every reader then returns None.
"""

import sys

TRACE = ("compile.trace", "compile.lower", "compile.small")
BACKEND = ("compile.backend",)
INIT = ("train.init", "trial_pack.init")
DATA = ("data.load", "data.upload")
WORKER_SPANS = ("trial.total", "trial_pack.total")
RING = 4096


def process_records():
    from rafiki_tpu import telemetry

    return telemetry.span_records()


def _end(r):
    return r["mono"] + r["dur_s"]


def _log(text):
    print(f"[setup] {text}", file=sys.stderr, flush=True)


def _merged(intervals):
    """Sorted, disjoint intervals covering the same instants."""
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        elif hi > lo:
            out.append((lo, hi))
    return out


def _outside(lo, hi, cover):
    """The parts of [lo, hi) that the merged intervals ``cover`` leave."""
    out, at = [], lo
    for c_lo, c_hi in cover:
        if c_hi <= at:
            continue
        if c_lo >= hi:
            break
        if c_lo > at:
            out.append((at, c_lo))
        at = max(at, c_hi)
    if at < hi:
        out.append((at, hi))
    return out


def _innermost(records):
    """[(lo, hi, record)] for one thread: each instant that its records
    cover, given to the record that started last."""
    recs = sorted(records, key=lambda r: (r["mono"], -r["dur_s"]))
    bounds = sorted({r["mono"] for r in recs} | {_end(r) for r in recs})
    out, active, i = [], [], 0
    for lo, hi in zip(bounds, bounds[1:]):
        while i < len(recs) and recs[i]["mono"] <= lo:
            active.append(recs[i])
            i += 1
        active = [r for r in active if _end(r) > lo]
        if active:
            out.append((lo, hi, active[-1]))
    return out


def _kind(r, parents):
    name = r["name"]
    for kind, names in (("trace", TRACE), ("backend", BACKEND),
                        ("init", INIT), ("data", DATA)):
        if name in names:
            return kind
    if r.get("leaf") or r["span_id"] not in parents:
        return "named"
    return "unattributed"       # the own seconds of an enclosing span


def split(m):
    """The set-up's seconds by kind and by span name, or None; computed and
    logged once a run (kept in ``m``)."""
    if "setup_split" not in m:
        m["setup_split"] = _split(m)
    return m["setup_split"]


def _split(m):
    window = [r for r in m.get("spans", ()) if "mono" in r]
    setup_s = m.get("setup_s") or 0.0
    if not window or setup_s <= 0:
        return None
    first_ts = min(r["ts"] for r in window)
    opened = min(r["mono"] for r in window)
    began = opened - setup_s
    ring = [r for r in process_records() if "mono" in r and "thread" in r]
    recs = [dict(r, mono=max(r["mono"], began),
                 dur_s=min(_end(r), opened) - max(r["mono"], began))
            for r in ring if r["ts"] < first_ts and _end(r) > began]
    recs = [r for r in recs if r["dur_s"] > 0]
    if not any(r["name"] in TRACE + BACKEND for r in recs):
        return None
    parents = {r.get("parent_id") for r in recs}
    threads = {}
    for r in recs:
        threads.setdefault(r["thread"], []).append(r)
    order = sorted(threads, key=lambda t: (
        not any(r["name"] in WORKER_SPANS for r in threads[t]),
        -sum(hi - lo for lo, hi in _merged((r["mono"], _end(r))
                                           for r in threads[t]))))
    by_kind = dict.fromkeys(("trace", "backend", "init", "data", "named",
                             "unattributed"), 0.0)
    by_name, claimed = {}, []
    for t in order:
        for lo, hi, r in _innermost(threads[t]):
            s = sum(b - a for a, b in _outside(lo, hi, claimed))
            kind = _kind(r, parents)
            by_kind[kind] += s
            name = r["name"] if kind != "unattributed" else f"({r['name']})"
            by_name[name] = by_name.get(name, 0.0) + s
        claimed = _merged(claimed + [(r["mono"], _end(r)) for r in threads[t]])
    inside = sum(hi - lo for lo, hi in claimed)
    out = dict(by_kind, setup_s=setup_s, outside=setup_s - inside,
               by_name=by_name, records=len(recs),
               # where the seconds outside the program lie: before its first
               # record (imports, the device, the stores), after its last
               before=claimed[0][0] - began, after=opened - claimed[-1][1])
    _report(m, out, recs, len(ring))
    return out


def _report(m, out, recs, held):
    """One table a run, on standard error: set-up by span name, its longest
    compile stages by function, and every compile stage of the window."""
    setup_s = out["setup_s"]
    _log(f"set-up {setup_s:.2f} s: {out['records']} records of the ring's "
         f"{held} (it holds {RING}"
         + ("; FULL: the oldest records are lost" if held >= RING else "")
         + f"); outside the program {out['outside']:.2f} s "
         f"({100 * out['outside'] / setup_s:.1f}%: {out['before']:.2f} s before "
         f"its first record, {out['after']:.2f} s after its last), unattributed "
         f"{out['unattributed']:.2f} s ({100 * out['unattributed'] / setup_s:.1f}%)")
    _log("set-up by span name, each second once (enclosing spans' own "
         "seconds in brackets): " + "; ".join(
             f"{n} {s:.3f}" for n, s in
             sorted(out["by_name"].items(), key=lambda kv: -kv[1]) if s >= 0.0005))
    stages = [r for r in recs if r["name"] in TRACE + BACKEND]
    hits = [r for r in stages if r.get("tags", {}).get("cache_hit")]
    _log(f"compile stages in set-up: {len(stages)} records, "
         f"{sum(1 for r in stages if r['name'] in BACKEND)} backend "
         f"({len(hits)} cache hits, retrieval "
         f"{sum(r['tags'].get('retrieval_s', 0.0) for r in hits):.3f} s)")
    for r in sorted(stages, key=lambda r: -r["dur_s"])[:5]:
        _log("  " + _stage(r))
    in_window = [r for r in m["spans"] if r["name"] in TRACE + BACKEND]
    _log(f"compile stages in the window: {len(in_window)}")
    for r in in_window:
        _log("  " + _stage(r))


def _stage(r):
    tags = r.get("tags", {})
    return (f"{r['name']} {tags.get('fun')} {r['dur_s']:.3f} s"
            + (f" cache_hit {tags['cache_hit']}" if "cache_hit" in tags else "")
            + (f" retrieval {tags['retrieval_s']:.3f} s"
               if "retrieval_s" in tags else "")
            + f" [{r['thread']}, in {r.get('parent')}]")


def seconds(m, kind):
    s = split(m)
    return None if s is None else s[kind]


def share_of_setup(m, kind):
    s = split(m)
    return None if s is None else 100.0 * s[kind] / s["setup_s"]
