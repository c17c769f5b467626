"""What the four files of tests of the language-model template share
(tests/test_kimi_linear_layers.py, _kda.py, _model.py, _trials.py): the path to
the benchmark's reference and tiny configuration, the small subclass, the
seeded program and its reference parameters, and two fixtures.

Where a comparison is of the arithmetic (chunked against recurrent, sorted
ragged dispatch against a loop over experts, blocked loss against whole
logits) the template's matrix products are switched to float32 (``f32``) so
that the two must agree closely; one test keeps bfloat16 and asks for
closeness."""

import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
for p in (str(REPO / "benchmark"), str(REPO / "benchmark" / "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

import check  # noqa: E402
from lm_tiny import load_lm_cfg, template_knobs, tiny_lm  # noqa: E402
from references import kimi_linear as R  # noqa: E402

from rafiki_tpu import telemetry  # noqa: E402
from rafiki_tpu.model.dataset import dataset_utils  # noqa: E402
from rafiki_tpu.model.knobs import FixedKnob  # noqa: E402
from rafiki_tpu.models import kimi_linear as K  # noqa: E402

TRAIN = "synthetic://tokens?vocab=256&n=8&len=96&seed=20&follow=0.5"
VAL = "synthetic://tokens?vocab=256&n=4&len=96&seed=21&follow=0.5"


def small_class(cfg, seed=0, template=K.KimiLinear):
    pinned = {k: v["fixed"] for k, v in cfg["knobs"].items() if "fixed" in v}
    pinned["seed"] = seed

    class Small(template):
        @staticmethod
        def get_knob_config():
            base = template.get_knob_config()
            return {k: (FixedKnob(pinned[k], affects_shape=True)
                        if k in pinned and isinstance(base[k], FixedKnob) else base[k])
                    for k in base}

    return Small


@pytest.fixture(params=[16, 64], ids=["chunk16", "chunk64_ragged"])
def cfg(request):
    return tiny_lm(load_lm_cfg(), chunk=request.param)


@pytest.fixture
def f32(monkeypatch):
    """The template's matrix products in float32 at full precision."""
    def mm(a, b, spec, out=jnp.float32):
        return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                          precision="highest")

    monkeypatch.setattr(K, "_mm", mm)
    monkeypatch.setattr(K, "BF16", jnp.float32)
    with jax.default_matmul_precision("highest"):
        yield


def program_of(cfg, seed=3, template=K.KimiLinear, reference=R, **free):
    model = small_class(cfg, seed, template)(**template_knobs(cfg, seed=seed, **free))
    model._planned_steps = 4
    fns = model._loop_fns(int(cfg["vocab_size"]), (int(cfg["seq_len"]),))
    _step, init_key = check.trial_keys(seed)
    return model, fns, fns["init_fn"](init_key), reference.init(init_key, cfg)


def flat(params):
    from flax.traverse_util import flatten_dict

    return {"/".join(k): v for k, v in flatten_dict(params).items()}


def tokens(cfg, n=2, seed=5):
    ds = dataset_utils.load(f"synthetic://tokens?vocab={cfg['vocab_size']}&n={n}"
                            f"&len={cfg['seq_len']}&seed={seed}")
    return jnp.asarray(ds.x), jnp.asarray(ds.y)


def close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= tol * max(np.max(np.abs(b)), 1e-12)


def value_and_grads(fn, *args):
    """fn's result at ``args[:-1]`` and its gradients under the cotangent
    ``args[-1]``."""
    *xs, ct = args
    out, vjp = jax.vjp(lambda *xs: fn(*xs).astype(jnp.float32), *xs)
    return (out,) + vjp(ct)


@pytest.fixture
def interpreted():
    """Pallas' interpreter ran in this test. With what it leaves in jax's
    caches, a later test of this file (an eager ``lax.scan`` under the
    ``f32`` fixture) died of a segmentation fault in this jax (0.9.0), every
    time; with the caches cleared it does not."""
    yield
    jax.clear_caches()


def kda_operands(T=256, dtype=jnp.float32, B=1, H=2, d=K.KDA_KERNEL_WIDTH):
    """The chunk kernels' shapes: heads of 128, two blocks of two chunks of 64."""
    ks = jax.random.split(jax.random.PRNGKey(T), 6)
    q = (K.l2norm(jax.random.normal(ks[0], (B, T, H, d))) * d ** -0.5).astype(dtype)
    k = K.l2norm(jax.random.normal(ks[1], (B, T, H, d))).astype(dtype)
    v = jax.random.normal(ks[2], (B, T, H, d)).astype(dtype)
    a = -jnp.exp(jax.random.uniform(ks[3], (B, T, H, d), minval=np.log(1e-3),
                                    maxval=np.log(1.6)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    return (q, k, v, a, beta), jax.random.normal(ks[5], (B, T, H, d))


@functools.lru_cache(maxsize=None)
def step_metrics(seq_len, chunk=16):
    cfg = tiny_lm(load_lm_cfg(), chunk=chunk, seq_len=seq_len)
    _model, fns, params, _ref = program_of(cfg)
    x, y = tokens(cfg)
    _loss, metrics = jax.jit(fns["loss_fn"])(params, {"x": x, "y": y}, None,
                                             {"label_smoothing": jnp.float32(0.0)})
    return {k: float(v) for k, v in metrics.items()}
