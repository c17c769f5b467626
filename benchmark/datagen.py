"""The benchmark's own copy of the task generator.

The sweep's inputs are ``synthetic://images`` URIs whose parameters come
from the configuration file and ``--seed``; the program turns a URI into
arrays with ``rafiki_tpu.model.dataset.synthetic_images``. The reference
may take nothing the program made, so it makes the same arrays here from
the same parameters (numpy's ``default_rng`` is reproducible by contract).
Copied from that function; ``benchmark/tests/test_references.py`` pins the
two against each other. bench.py's non-saturating task is noise 0.35,
flip 0.2: a perfect classifier scores (1 - flip) + flip / classes.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np


def image_uri(cfg: dict, n: int, seed: int) -> str:
    im = cfg["image"]
    return (f"synthetic://images?classes={im['classes']}&n={n}&w={im['w']}"
            f"&h={im['h']}&c={im['c']}&seed={seed}"
            f"&noise={cfg['noise']}&flip={cfg['flip']}")


def data_seeds(seed: int) -> Tuple[int, int]:
    """(train, validation) draw seeds of a run: they differ, the class
    templates (``dist`` 0) are shared."""
    return 2 * int(seed), 2 * int(seed) + 1


def synthetic_images(classes: int, w: int, h: int, c: int, n: int, seed: int,
                     noise: float, flip: float, dist: int = 0
                     ) -> Tuple[np.ndarray, np.ndarray]:
    th, tw = max(2, h // 4), max(2, w // 4)
    coarse = (np.random.default_rng(dist)
              .uniform(0.0, 1.0, size=(classes, th, tw, c)).astype(np.float32))
    templates = np.repeat(np.repeat(coarse, h // th + 1, axis=1),
                          w // tw + 1, axis=2)[:, :h, :w, :]
    rng = np.random.default_rng(seed + 1_000_003)
    y = rng.integers(0, classes, size=n).astype(np.int32)
    x = templates[y] + rng.normal(0.0, noise, size=(n, h, w, c)).astype(np.float32)
    x = np.clip(x, 0.0, 1.0).astype(np.float32)
    if flip > 0:
        flipped = rng.uniform(size=n) < flip
        y = np.where(flipped, rng.integers(0, classes, size=n), y).astype(np.int32)
    return x, y


def images_of(cfg: dict, n: int, seed: int) -> Tuple[np.ndarray, np.ndarray]:
    im = cfg["image"]
    return synthetic_images(im["classes"], im["w"], im["h"], im["c"], n, seed,
                            float(cfg["noise"]), float(cfg["flip"]))
