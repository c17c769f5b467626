"""Search anatomy plane: the Advisor loop, made auditable.

The paper's core claim is proposal quality — the GP/TPE loop finds
better knobs faster than random — yet the search loop is the one part
of the stack the other observability planes never open up. This
package closes that:

* :mod:`~rafiki_tpu.obs.search.audit` — journal-record helpers every
  advisor ``_propose*``/``_feedback`` implementation calls (enforced
  by the RF011 checker), carrying acquisition internals (EI of the
  chosen candidate, posterior mean/std, pool size, constant-liar
  state, fit wall-time, seed) keyed by a knobs-hash joinable against
  ``event/trial_started`` and ``trial/epoch_eval`` records;
* :mod:`~rafiki_tpu.obs.search.ledger` — the ``search`` telemetry
  collector charging wall-time to proposed-but-doomed vs
  completed-and-scored trials (``search.effective_trials_per_hour``,
  ``search.regret``, ``search.best_score``);
* :mod:`~rafiki_tpu.obs.search.reconstruct` — rebuilds a whole sweep
  from journals alone (ordered proposals, scores, best-so-far/regret
  curve, advisor-lift-vs-random with a bootstrap CI) and fails loudly
  when a feedback has no matching proposal record;
* :mod:`~rafiki_tpu.obs.search.lineage` — stitches the already-
  journaled pack/evict/backfill/resume/repack events into explicit
  trial genealogy, with fleet-wide orphan reconciliation;
* :mod:`~rafiki_tpu.obs.search.stats` — the seeded bootstrap-CI
  helper the reconstruction uses.

Read through ``python -m rafiki_tpu.obs sweep`` / ``... lineage``
(docs/search_anatomy.md).
"""

from __future__ import annotations

import importlib

_LAZY = ("audit", "ledger", "lineage", "reconstruct", "stats", "cli")

__all__ = list(_LAZY)


def __getattr__(name: str):
    if name in _LAZY:
        mod = importlib.import_module(f"rafiki_tpu.obs.search.{name}")
        globals()[name] = mod
        return mod
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
