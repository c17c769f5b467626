"""Advisor (rafiki_tpu/advisor/): share of the window inside
``trial.advisor_propose`` spans (host clock). Moves trials_per_hour."""
from _spans import share


def read(m):
    return share(m, "trial.advisor_propose")
