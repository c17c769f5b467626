"""Fast single-transfer pytree serialization for trial parameters.

Why this exists: persisting a trial's parameters is on the steady-state
throughput path (the async saver overlaps it with the next trial's
training, so trial wall-clock is max(compute, persist) — see
worker/train.py). On the chip a dump is dispatch, not bandwidth: the
host link moved 2.9 GB in 0.59-0.72 s while one VGG16 member's 30 MB,
sliced, cast and copied leaf by leaf, took 0.12-0.26 s (chip runs of
PR 25, PERF.md section 6). So:

  * float32 leaves are optionally cast to bfloat16 ON DEVICE by a
    single jit'd elementwise tree-map (compiles in <1s; a device-side
    concat into one buffer was also tried and fetches slightly faster
    warm, but its 43-way concat took XLA:TPU ~2 minutes to compile —
    not worth it), halving the bytes over the wire;
  * leaf transfers are started with ``copy_to_host_async`` before any
    is consumed, so the host walk overlaps the device DMA;
  * leaves that are already numpy arrays stay on the host: a finished
    pack round casts and copies its STACKED parameters once
    (``PackedTrainLoop.stage_host_params``) and every member is dumped
    from ``host_leaf[i]`` views of that copy — the same bytes, since
    the f32 -> bf16 rounding is elementwise — with no device work;
  * the host side writes raw little-endian buffers — no msgpack.

The bf16 cast is the DEFAULT for serving blobs and loses nothing:
model templates compute in bfloat16 on the MXU anyway (every
conv/dense casts its params down per flax ``dtype=bfloat16``), so a
bf16-stored parameter produces bit-identical serving math. Full-
precision masters for resume live in ``dump_checkpoint``, not here.
Opt out with cast_f32_to_bf16=False (config:
serving_params_dtype="float32").

Format (version RTPK1): magic, u64-le header length, JSON header
listing (key, shape, dtype) per leaf in key order, then the raw
concatenated little-endian buffers. Readable with numpy alone.
"""

from __future__ import annotations

import json
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

MAGIC = b"RTPK1\n"

_EXTRA_DTYPES = {
    "bfloat16": ml_dtypes.bfloat16,
    "float8_e4m3fn": getattr(ml_dtypes, "float8_e4m3fn", None),
}


def _np_dtype(name: str) -> np.dtype:
    if name in _EXTRA_DTYPES and _EXTRA_DTYPES[name] is not None:
        return np.dtype(_EXTRA_DTYPES[name])
    return np.dtype(name)


@jax.jit
def _cast_tree_bf16(tree):
    return jax.tree.map(
        lambda l: l.astype(jnp.bfloat16) if l.dtype == jnp.float32 else l, tree)


def _flat_items(tree: Any):
    """Stable (path-string, leaf) pairs for a params pytree / state dict."""
    from flax import serialization
    from flax.traverse_util import flatten_dict

    state = serialization.to_state_dict(tree)
    flat = flatten_dict(state, sep="/")
    return sorted(flat.items())


def _start_host_copies(leaves) -> None:
    """Kick off every device->host copy before any is consumed."""
    for v in leaves:
        if hasattr(v, "copy_to_host_async"):
            v.copy_to_host_async()


def dump_pytree(tree: Any, cast_f32_to_bf16: bool = True) -> bytes:
    """Serialize a pytree of arrays: raw buffers, pipelined transfers.
    A numpy leaf is written as it is (unless ``jnp.asarray`` would narrow
    its 64-bit dtype): with ``cast_f32_to_bf16`` off a tree that is
    already on the host touches no device."""
    if cast_f32_to_bf16:
        tree = _cast_tree_bf16(tree)
    items = _flat_items(tree)
    spec = []
    leaves = []
    for k, v in items:
        if not (isinstance(v, np.ndarray)
                and v.dtype == jax.dtypes.canonicalize_dtype(v.dtype)):
            v = jnp.asarray(v)
        leaves.append(v)
        spec.append({"k": k, "shape": list(v.shape), "dtype": v.dtype.name})
    header = json.dumps(spec).encode()
    _start_host_copies(leaves)
    parts = [MAGIC, len(header).to_bytes(8, "little"), header]
    parts.extend(np.ascontiguousarray(np.asarray(v)).tobytes() for v in leaves)
    return b"".join(parts)


class StackedHostCopy:
    """One device-to-host copy of a stacked pytree (leading axis: the
    member), for dumping every member from the host.

    Construction is the device's whole part: one jitted cast of the
    stacked leaves to what a dump stores, then ``copy_to_host_async`` on
    each — dispatched, not waited for. ``fetch`` is the only place
    anything waits for the device; after it the device arrays are let go
    and ``member(i)`` hands out ``host_leaf[i]`` views, which
    ``dump_pytree(..., cast_f32_to_bf16=False)`` writes to the bytes a
    dump of the member's own device slices gives. A ``fetch`` that raises
    leaves the copy unfetched, so the next member's raises too.
    """

    def __init__(self, stacked: Any, cast_f32_to_bf16: bool = True):
        if cast_f32_to_bf16:
            stacked = _cast_tree_bf16(stacked)
        _start_host_copies(jax.tree.leaves(stacked))
        self._device = stacked
        self._host = None

    @property
    def fetched(self) -> bool:
        return self._host is not None

    def fetch(self) -> None:
        if self._host is None:
            self._host = jax.tree.map(np.asarray, self._device)
            self._device = None

    def member(self, i: Optional[int]) -> Any:
        """Member ``i`` of a stacked copy; ``None`` for a copy that is one
        trial's own tree (the serial lane's), which is handed out whole."""
        self.fetch()
        if i is None:
            return self._host
        # ``a[i, ...]``: a 0-d array, not a numpy scalar, for a (k,) leaf.
        return jax.tree.map(lambda a: a[i, ...], self._host)


def is_packed(blob: bytes) -> bool:
    return blob[: len(MAGIC)] == MAGIC


def load_pytree(blob: bytes) -> Dict[str, Any]:
    """Inverse of :func:`dump_pytree` → nested state dict of np arrays
    (restore into a template with ``flax.serialization.from_state_dict``)."""
    from flax.traverse_util import unflatten_dict

    if not is_packed(blob):
        raise ValueError("not a RTPK1 packed pytree blob")
    off = len(MAGIC)
    hlen = int.from_bytes(blob[off : off + 8], "little")
    off += 8
    spec = json.loads(blob[off : off + hlen].decode())
    off += hlen
    flat = {}
    for ent in spec:
        dt = _np_dtype(ent["dtype"])
        shape = tuple(ent["shape"])
        n = int(np.prod(shape, dtype=np.int64)) if shape else 1
        arr = np.frombuffer(blob, dtype=dt, count=n, offset=off).reshape(shape)
        flat[ent["k"]] = arr
        off += n * dt.itemsize
    return unflatten_dict(flat, sep="/")
