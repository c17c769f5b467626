"""Plain float32 building blocks for the configurations' references.

Straightforward ``jax.numpy`` / ``jax.lax`` in float32 at "highest" matmul
precision: no flax, no kernels, nothing imported from the program. The only
thing taken from flax's *behaviour* is how it derives one PRNG key per
parameter (and per dropout call) from a module's key — a SHA-1 of the module
path folded into the key — because a reference that is to follow a trial of
the program has to start from the same initial parameters and draw the same
dropout masks. ``benchmark/tests/test_references.py`` pins both against the
program on the CPU.
"""

from __future__ import annotations

import hashlib
import math
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

#: A lower-precision matmul (the control): ``quant.inputs(x)`` rounds what
#: goes into one, ``quant.output(y)`` is the identity on what comes out and
#: rounds the gradient that flows back into it. None is the reference proper.
Quant = Optional[Any]


def fold_path(key, path: Sequence) -> jnp.ndarray:
    """The key flax hands to an initializer or a dropout call: ``key`` with
    the SHA-1 of the module path (strings) and the call counter (an int)
    folded in. Mirrors ``flax.core.scope._fold_in_static`` at its default
    configuration (no separator byte)."""
    m = hashlib.sha1()
    for x in path:
        if isinstance(x, str):
            m.update(x.encode("utf-8"))
        else:
            x = int(x)
            m.update(x.to_bytes((x.bit_length() + 7) // 8, byteorder="big"))
    return jax.random.fold_in(
        key, jnp.uint32(int.from_bytes(m.digest()[:4], byteorder="big")))


def lecun_normal(key, shape) -> jnp.ndarray:
    """flax's default kernel initializer (``jax.nn.initializers``, which is
    jax's own and not the program's): fan-in scaled truncated normal."""
    return jax.nn.initializers.lecun_normal()(key, shape, jnp.float32)


def kernel_init(key, path: Sequence[str], shape) -> jnp.ndarray:
    # A kernel is the first parameter its module creates: counter 1.
    return lecun_normal(fold_path(key, tuple(path) + (1,)), shape)


def _q(x, quant: Quant):
    return x if quant is None else quant.inputs(x)


def _qo(y, quant: Quant):
    return y if quant is None else quant.output(y)


def conv(x, kernel, quant: Quant = None) -> jnp.ndarray:
    """NHWC 'SAME' convolution, stride 1, no bias."""
    return _qo(jax.lax.conv_general_dilated(
        _q(x, quant), _q(kernel, quant), window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST), quant)


def dense(x, kernel, bias, quant: Quant = None) -> jnp.ndarray:
    return _qo(jnp.dot(_q(x, quant), _q(kernel, quant), precision=HIGHEST),
               quant) + bias


def group_norm(x, scale, bias, eps: float = 1e-6) -> jnp.ndarray:
    """GroupNorm over (H, W, C/G) with G = gcd(8, C), as the templates use
    it (their stated departure from BatchNorm)."""
    n, h, w, c = x.shape
    g = math.gcd(8, c)
    xg = x.reshape(n, h, w, g, c // g)
    mean = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = jnp.square(xg - mean).mean(axis=(1, 2, 4), keepdims=True)
    y = ((xg - mean) * jax.lax.rsqrt(var + eps)).reshape(n, h, w, c)
    return y * scale + bias


def max_pool2(x) -> jnp.ndarray:
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def dropout(x, rate, key) -> jnp.ndarray:
    """Inverted dropout with the mask the program's step draws from the
    same per-step key (first ``make_rng("dropout")`` of the root module)."""
    rate = jnp.asarray(rate, jnp.float32)
    keep = jax.random.bernoulli(fold_path(key, (1,)), 1.0 - rate, x.shape)
    return jnp.where(keep, x / jnp.maximum(1.0 - rate, 1e-6), 0.0)


# -- FLOP counting (2 x multiply-accumulates; norms, pools and activations
# are not counted: the matmul units' work is what a share of the peak means).

def conv_flops(h: int, w: int, kh: int, kw: int, cin: int, cout: int) -> int:
    return 2 * h * w * kh * kw * cin * cout


def dense_flops(cin: int, cout: int) -> int:
    return 2 * cin * cout
