"""Persist: share of the window the worker's thread spends blocked behind
the saver (``trial.persist_wait``: ``submit`` on a full queue, the flush at
the job's end): persist's share of the critical path, where
persist_share.sweep is the saver thread's share of the wall. Moves
trials_per_hour."""
from _spans import share


def read(m):
    return share(m, "trial.persist_wait")
