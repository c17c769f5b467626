"""Tests of the benchmark's own code (not tier-1: run them with
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``). They import the
program only to pin the benchmark's copies against it."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH), str(BENCH / "layer_metrics"), str(BENCH.parent)):
    if p not in sys.path:
        sys.path.insert(0, p)


def tiny(cfg: dict) -> dict:
    """A seconds-scale size for the CPU: narrow, shallow, 8x8 images."""
    cfg = json.loads(json.dumps(cfg))
    cfg["image"] = {"w": 8, "h": 8, "c": 3, "classes": 10}
    cfg["train_n"], cfg["eval_n"] = 512, 144
    cfg["knobs"]["batch_size"]["fixed"] = 32
    cfg["knobs"]["depth"]["fixed"] = 11
    cfg["knobs"]["width_mult"]["fixed"] = 0.25
    return cfg


def load_cfg(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.fixture(scope="session", autouse=True)
def _cpu():
    from rafiki_tpu.utils.backend import force_cpu_backend

    force_cpu_backend()
