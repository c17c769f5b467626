"""Plain float32 reference of the VGG template (Simonyan & Zisserman 2014,
configuration D at CIFAR size: 13 3x3 convolutions, one 512-wide FC).

Departures from the paper, both the template's own and stated in its file:
GroupNorm(8) after every convolution where later VGGs put BatchNorm, and
one hidden FC layer of 512 instead of two of 4096 (the CIFAR convention).
"""

from __future__ import annotations

from typing import Dict

import jax.numpy as jnp

from . import nnref

_CFGS = {
    11: [64, "M", 128, "M", 256, 256, "M", 512, 512, "M", 512, 512, "M"],
    13: [64, 64, "M", 128, 128, "M", 256, 256, "M", 512, 512, "M", 512, 512, "M"],
    16: [64, 64, "M", 128, 128, "M", 256, 256, 256, "M", 512, 512, 512, "M",
         512, 512, 512, "M"],
}


def _plan(cfg: dict):
    """[(kind, h, w, cin, cout)] in execution order, from the sizes."""
    depth = int(cfg["knobs"]["depth"]["fixed"])
    width = float(cfg["knobs"]["width_mult"]["fixed"])
    h, w, c = cfg["image"]["h"], cfg["image"]["w"], cfg["image"]["c"]
    out = []
    for v in _CFGS[depth]:
        if v == "M":
            if min(h, w) >= 2:
                out.append(("pool", h, w, c, c))
                h, w = h // 2, w // 2
            continue
        ch = max(8, int(v * width))
        out.append(("conv", h, w, c, ch))
        c = ch
    hidden = max(64, int(512 * width))
    out.append(("dense", 1, 1, h * w * c, hidden))
    out.append(("dense", 1, 1, hidden, cfg["image"]["classes"]))
    return out


def init(key, cfg: dict) -> Dict[str, jnp.ndarray]:
    """Initial parameters from the init key, keyed like the stored blob
    (``Conv_3/kernel``)."""
    p: Dict[str, jnp.ndarray] = {}
    n_conv = n_dense = 0
    for kind, _h, _w, cin, cout in _plan(cfg):
        if kind == "conv":
            p[f"Conv_{n_conv}/kernel"] = nnref.kernel_init(
                key, (f"Conv_{n_conv}",), (3, 3, cin, cout))
            p[f"GroupNorm_{n_conv}/scale"] = jnp.ones((cout,), jnp.float32)
            p[f"GroupNorm_{n_conv}/bias"] = jnp.zeros((cout,), jnp.float32)
            n_conv += 1
        elif kind == "dense":
            p[f"Dense_{n_dense}/kernel"] = nnref.kernel_init(
                key, (f"Dense_{n_dense}",), (cin, cout))
            p[f"Dense_{n_dense}/bias"] = jnp.zeros((cout,), jnp.float32)
            n_dense += 1
    return p


def forward(p, x, cfg: dict, train: bool = False, dropout_key=None,
            dropout_rate=None, quant: nnref.Quant = None) -> jnp.ndarray:
    x = x.astype(jnp.float32)
    n_conv = n_dense = 0
    for kind, *_ in _plan(cfg):
        if kind == "pool":
            x = nnref.max_pool2(x)
        elif kind == "conv":
            x = nnref.conv(x, p[f"Conv_{n_conv}/kernel"], quant)
            x = nnref.group_norm(x, p[f"GroupNorm_{n_conv}/scale"],
                                 p[f"GroupNorm_{n_conv}/bias"])
            x = jnp.maximum(x, 0.0)
            n_conv += 1
        else:
            if n_dense == 0:
                x = x.reshape((x.shape[0], -1))
            x = nnref.dense(x, p[f"Dense_{n_dense}/kernel"],
                            p[f"Dense_{n_dense}/bias"], quant)
            if n_dense == 0:
                x = jnp.maximum(x, 0.0)
                if train and dropout_key is not None:
                    x = nnref.dropout(x, dropout_rate, dropout_key)
            n_dense += 1
    return x


def forward_flops(cfg: dict) -> int:
    """Multiply-accumulate FLOPs of one image's forward pass."""
    total = 0
    for kind, h, w, cin, cout in _plan(cfg):
        if kind == "conv":
            total += nnref.conv_flops(h, w, 3, 3, cin, cout)
        elif kind == "dense":
            total += nnref.dense_flops(cin, cout)
    return total
