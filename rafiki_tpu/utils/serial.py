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
  * the host side copies nothing it need not: a blob is handed on as
    its PARTS (``pytree_parts``: magic, header length, header, then
    each leaf's own memory as a contiguous ``memoryview``: no
    ``tobytes``, no msgpack). ``dump_pytree`` is their join, for a
    caller that wants bytes; the worker's dump hands the parts to
    ``ParamsStore.save_parts``, which hashes and writes each as it
    passes, so between the fetched leaves and the page cache no copy
    of the blob is made (ISSUE 34: the joined road cost 6 s a
    gigabyte, four whole-blob copies before the first byte reached
    the disk).

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
import pickle
from typing import Any, Dict, Iterable, List, Optional, Union

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

MAGIC = b"RTPK1\n"

# What a part of a blob may be: anything ``hashlib`` and a file take.
Buffer = Union[bytes, memoryview]

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


def _leaf_bytes(v) -> memoryview:
    """A fetched leaf's own memory, read-only and flat. A host leaf that
    is already contiguous (every ``host_leaf[i]`` view of a stacked copy
    is) is not copied; a custom dtype (bfloat16) has no buffer format of
    its own, hence the view as bytes."""
    a = np.ascontiguousarray(np.asarray(v))
    return memoryview(a.reshape(-1).view(np.uint8)).toreadonly()


def pytree_parts(tree: Any, cast_f32_to_bf16: bool = True) -> List[Buffer]:
    """An RTPK1 blob as the buffers whose join it is: the layout's one
    definition. Pipelined transfers; returns once every leaf is on the
    host. A numpy leaf is handed on as it is (unless ``jnp.asarray``
    would narrow its 64-bit dtype): with ``cast_f32_to_bf16`` off a tree
    that is already on the host touches no device and is not copied.
    The parts alias the leaves: they hold while the caller keeps them."""
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
    parts: List[Buffer] = [MAGIC, len(header).to_bytes(8, "little"), header]
    parts.extend(_leaf_bytes(v) for v in leaves)
    return parts


def parts_nbytes(parts: Iterable[Buffer]) -> int:
    return sum(memoryview(p).nbytes for p in parts)


def dump_pytree(tree: Any, cast_f32_to_bf16: bool = True) -> bytes:
    """Serialize a pytree of arrays to one RTPK1 ``bytes``: the join of
    :func:`pytree_parts` (one copy of the blob)."""
    return b"".join(pytree_parts(tree, cast_f32_to_bf16))


def pickled_dict_parts(small: Dict[str, Any], key: str,
                       value_parts: List[Buffer]) -> List[Buffer]:
    """The parts of a pickle that loads to ``{**small, key: <the join of
    value_parts, one bytes>}``, the value's parts handed on as they are.
    ``pickle`` writes the small entries; the large one is put into the
    finished dict by hand, four opcodes: its key, BINBYTES8 with the
    length, (the bytes,) SETITEM, STOP. Protocol 3 for pickle's share
    because it frames nothing and numbers its memo slots in the stream,
    so what is appended disturbs neither; the header says 4, where
    BINBYTES8 belongs and frames are optional."""
    head = pickle.dumps(small, protocol=3)  # PROTO 3, the dict, STOP
    k = key.encode()
    return [pickle.PROTO + b"\x04" + head[2:-1]
            + pickle.BINUNICODE + len(k).to_bytes(4, "little") + k
            + pickle.BINBYTES8 + parts_nbytes(value_parts).to_bytes(8, "little"),
            *value_parts,
            pickle.SETITEM + pickle.STOP]


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
