#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One new process per run. Everything that belongs to one cell, one
configuration, one traffic mix, one driver or one per-layer metric is a
file of its own that this program finds by the name in ``BENCHMARK.json``:

    workloads/<cell>.json      the cell's driver and its limits of ``correct``
                               (configuration, traffic and chips are the
                               manifest's alone)
    configs/<config>.json      the sizes as run, source, reduced, assumed
    references/<name>.py       the configuration's plain float32 reference
    traffic/<mix>.json         scheduler arguments, warm-up, the traced span
    drivers/<driver>.py        ``run(ctx)``: set-up, window, read-back;
                               ``verify(ctx, res)``: the comparison that
                               decides ``correct``; ``layer_inputs(ctx, res,
                               device)``: what its per-layer readers read
    layer_metrics/<metric>.py  ``read(measured)`` -> number, or None

The last line on standard output is the result's JSON object; everything
else goes to standard error. A run that finds no TPU, or fewer chips than
the cell asks for, exits 4 and prints no result (a CPU rehearsal is
reachable only from ``benchmark/tests``, through ``main(platform="cpu")``).
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
EXIT_NO_ACCELERATOR = 4
EXIT_FAILED = 1


def log(text: str) -> None:
    print(f"[bench] {text}", file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> "tuple[dict, dict, dict, dict]":
    """(manifest, cell, cfg, traffic) for a workload named in
    BENCHMARK.json: the manifest's entry, with what the cell's own file
    adds to it (driver, limits)."""
    manifest = load_json(REPO / "BENCHMARK.json")
    entry = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{[w['name'] for w in manifest['workloads']]}")
    cell = dict(load_json(HERE / "workloads" / f"{name}.json"), **entry)
    conf = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    cfg = load_json(REPO / conf["file"])
    traffic = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    return manifest, cell, cfg, traffic


def load_reader(metric: str):
    path = HERE / "layer_metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_of_cell(manifest: dict, cell_name: str, group: str) -> List[dict]:
    return [m for m in manifest[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def claim_stdout():
    """Keep a private handle on fd 1 and point fd 1 (and ``sys.stdout``)
    at standard error, so that nothing but the result reaches it."""
    sys.stdout.flush()
    out = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    sys.stdout = sys.stderr
    return out


def place_compile_cache() -> str:
    """jax's persistent compilation cache: where JAX_COMPILATION_CACHE_DIR
    says, else a fixed directory inside the checkout (the path is part of
    the cache's key). Every program is kept, however quick its compile, so
    that only a checkout's first run of a cell compiles."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(REPO / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


def find_devices(platform: str, chips: int) -> Optional[dict]:
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        log(f"no accelerator: {str(e).splitlines()[0]}")
        return None
    if devs[0].platform != platform or len(devs) < chips:
        log(f"jax found {len(devs)} x {devs[0].platform!r} "
            f"({devs[0].device_kind}); this cell needs {chips} x {platform!r}")
        return None
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main(argv: Optional[List[str]] = None, platform: str = "tpu",
         overrides: Optional[Dict[str, Any]] = None) -> int:
    """``platform`` and ``overrides`` (a tiny size, a planted fault) are
    for benchmark/tests only; the command line cannot reach them."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    overrides = overrides or {}

    for p in (str(HERE), str(HERE / "layer_metrics"), str(REPO)):
        if p not in sys.path:
            sys.path.insert(0, p)
    manifest, cell, cfg, traffic = load_cell(args.workload)
    cfg = overrides.get("cfg", lambda c: c)(cfg)
    traffic = overrides.get("traffic", lambda t: t)(traffic)
    out = overrides.get("out") or claim_stdout()

    try:
        import rafiki_tpu  # noqa: F401  the system under test
    except ImportError as e:
        log(f"the system under test is not in this directory: {e}")
        return EXIT_FAILED
    if platform == "cpu":
        from rafiki_tpu.utils.backend import force_cpu_backend

        force_cpu_backend()
    device = find_devices(platform, int(cell["chips"]))
    if device is None:
        return EXIT_NO_ACCELERATOR
    cache_dir = place_compile_cache()
    log(f"{args.workload} seed {args.seed} seconds {args.seconds} trace "
        f"{args.trace}; device {device}; compile cache {cache_dir}")

    ctx = types.SimpleNamespace(
        args=args, cell=cell, cfg=cfg, traffic=traffic, repo=REPO,
        t_start=T_START, log=log, platform=platform, overrides=overrides,
        trace_dir=None, cleanup=lambda: None)
    driver = importlib.import_module(f"drivers.{cell['driver']}")
    try:
        res = driver.run(ctx)
        measured = res["measured"]
        device["memory_peak_bytes"] = res["memory_peak_bytes"]

        # -- the trace, reduced (the traced run's per-layer numbers) ---------
        breakdown = None
        if args.trace:
            import trace_reduce

            if res["tracer_error"] or not res["traced"]:
                res["problems"].append(
                    f"no trace: {res['tracer_error'] or 'the window closed first'}")
            else:
                t0 = time.monotonic()
                planes = trace_reduce.load_xplane(ctx.trace_dir)
                log(f"trace read in {time.monotonic() - t0:.1f} s")
                for p in planes:
                    log(f"trace plane {p['name']}: " + ", ".join(
                        f"{ln['name']} ({len(ln['events'])})"
                        for ln in p["lines"][:12]))
                red = trace_reduce.reduce_trace(planes)
                if red is None:
                    res["problems"].append("the trace holds no device operation")
                else:
                    measured["trace"] = red
                    device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
                    breakdown = {"device_ops": red["device_ops"],
                                 "idle_gaps": red["idle_gaps"]}
                    log(f"trace: busy {red['busy_s']:.3f} s of "
                        f"{red['window_s']:.3f} s on {red['chips']} chip(s), "
                        f"reduced by {time.monotonic() - t0:.1f} s")
        ctx.cleanup()

        # -- metrics ----------------------------------------------------------
        metrics: Dict[str, dict] = {}
        if args.trace:
            driver.layer_inputs(ctx, res, device)
            for m in metrics_of_cell(manifest, args.workload, "per_layer"):
                value = load_reader(m["name"])(measured)
                if value is not None:
                    metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        else:
            for m in metrics_of_cell(manifest, args.workload, "end_to_end"):
                if m["name"] in res["metrics"]:
                    metrics[m["name"]] = {"value": float(res["metrics"][m["name"]]),
                                          "unit": m["unit"]}

        # -- correct: the driver's comparison with the reference -------------
        numbers: Dict[str, dict] = {}
        correct = not res["problems"]
        if correct:
            t0 = time.monotonic()
            verdict = driver.verify(ctx, res)
            numbers, correct = verdict["numbers"], verdict["correct"]
            log(f"check took {time.monotonic() - t0:.1f} s")
        for p in res["problems"]:
            log(f"NOT CORRECT: {p}")
        # (a number that could not be taken reads as 1e30, which is JSON)
        compared = {name: [n["value"] if math.isfinite(n["value"]) else 1e30,
                           n["limit"]] for name, n in numbers.items()}
        for name, (value, limit) in compared.items():
            log(f"compared {name}: {value:.6g} (limit {limit:g})"
                + ("" if value <= limit else "  <-- over"))
        line = {"correct": bool(correct), "attempted": int(res["attempted"]),
                "failed": int(res["failed"]), "metrics": metrics,
                "device": device}
        if breakdown is not None:
            line["breakdown"] = breakdown
        line["window_s"] = measured["window_s"]
        line["problems"] = res["problems"]
        line["compared"] = compared
        print(f"[bench] correct={correct} compared={json.dumps(compared)}",
              file=sys.stderr, flush=True)
        out.write(json.dumps(line) + "\n")
        out.flush()
        return 0
    finally:
        ctx.cleanup()


if __name__ == "__main__":
    sys.exit(main())
