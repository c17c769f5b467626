"""Advisor (rafiki_tpu/advisor/): share of the window inside
``trial.advisor_feedback`` spans, one a trial on the worker's thread (the
Gaussian process refits in each): what advisor_share.sweep, which reads
the draft alone, leaves out. Moves trials_per_hour."""
from _spans import share


def read(m):
    return share(m, "trial.advisor_feedback")
