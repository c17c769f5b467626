"""Test harness: fake an 8-chip pod on CPU.

Set platform/device-count flags BEFORE jax initialises (SURVEY.md §7
"faking the pod in CI"). Every test then sees 8 jax CPU devices, so
schedulers, meshes and collectives are exercised without TPU hardware.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")

# The explicit CPU request is applied before the first backend use.
from rafiki_tpu.utils.backend import force_cpu_backend  # noqa: E402

force_cpu_backend(n_devices=8)

import pytest  # noqa: E402


@pytest.fixture()
def tmp_config(tmp_path):
    """A Config rooted in a temp dir, installed as the process default."""
    from rafiki_tpu.config import Config, set_config, get_config

    cfg = Config(data_dir=tmp_path / "rafiki")
    cfg.ensure_dirs()
    prev = get_config()
    set_config(cfg)
    yield cfg
    set_config(prev)
