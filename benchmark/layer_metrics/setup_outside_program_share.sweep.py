"""Trial loop: of setup_s, the share that no span record of the program covers
on any thread: imports, device discovery, the harness's stores and generated
files, the scheduler between two jobs: what is not this system's trial loop
to shorten (_setup.py). Moves setup_s."""
from _setup import share_of_setup


def read(m):
    return share_of_setup(m, "outside")
