"""Params store: trained model parameters on disk, keyed by params id.

Reference parity: the reference persists each trial's
``dump_parameters()`` blob via the meta store / a shared params volume
(SURVEY.md §5 "Checkpoint / resume"). Same trial-granular model here:
one file per params id with sha256 integrity, plus a mid-trial
checkpoint namespace (``<trial>/ckpt_<step>``) the reference lacks —
used by the worker for resumable long trials.

Blobs are whatever the model's ``dump_parameters`` returned. For a
``JaxModel`` that is a pickle of ``{"arch", "dataset_meta", "packed"}``
whose ``"packed"`` is one RTPK1 buffer (utils/serial.py: a JSON header
and the leaves' raw little-endian memory, bfloat16 by default). The
stored file is ``<64 hex of sha256(blob)>\n<blob>``.

How it gets there: a dump is streamed. ``save_parts`` takes the blob as
the buffers whose join it is (``JaxModel.dump_parameter_parts``: the
pickle's few opcodes round the fetched leaves' own memory), hashes and
writes each as it passes, then writes the digest into the line kept
free at the head: between the leaves and the page cache no copy of the
blob is made. ``save(blob)`` is the same road for one part. The file is
``fsync``ed before it is renamed into place, so a params id names
durable bytes or nothing; a write that fails takes its temporary file
with it.

Chaos hook: ``store.params_write`` fires before each write — ``delay``
simulates a slow disk, ``error`` a failing one (raises
:class:`rafiki_tpu.chaos.ChaosError`, an OSError). Keyed by params id
so scenarios can target checkpoint writes (``match=_ckpt_``) apart
from final params. Inert unless ``RAFIKI_CHAOS`` is set.
"""

from __future__ import annotations

import hashlib
import os
import uuid
from pathlib import Path
from typing import Iterable, List, Optional, Union

from rafiki_tpu.chaos import hook as _chaos

# sha256 in hex and the newline ``load`` splits at.
_DIGEST_LINE = 65


class ParamsStore:
    def __init__(self, params_dir: str | os.PathLike):
        self._dir = Path(params_dir)
        self._dir.mkdir(parents=True, exist_ok=True)

    @property
    def directory(self) -> Path:
        """Root directory (subprocess workers reopen it by path)."""
        return self._dir

    def _path(self, params_id: str) -> Path:
        if "/" in params_id or ".." in params_id:
            raise ValueError(f"Bad params id {params_id!r}")
        return self._dir / f"{params_id}.params"

    def save(self, blob: bytes, params_id: Optional[str] = None) -> str:
        return self.save_parts((blob,), params_id)

    def save_parts(self, parts: Iterable[Union[bytes, memoryview]],
                   params_id: Optional[str] = None) -> str:
        """Store the blob that is the join of ``parts`` without joining
        them: one pass, each part hashed and written as it is."""
        params_id = params_id or uuid.uuid4().hex
        _chaos("store.params_write", params_id)  # delay=slow disk, error=failed write
        path = self._path(params_id)
        tmp = path.with_suffix(".tmp")
        sha = hashlib.sha256()
        try:
            with open(tmp, "wb") as f:
                f.seek(_DIGEST_LINE)  # the digest is known last and stands first
                for part in parts:
                    sha.update(part)
                    f.write(part)
                f.seek(0)
                f.write(sha.hexdigest().encode() + b"\n")
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)  # atomic: readers never see a torn file
        except BaseException:
            tmp.unlink(missing_ok=True)
            raise
        return params_id

    def load(self, params_id: str) -> bytes:
        with open(self._path(params_id), "rb") as f:
            digest, blob = f.read().split(b"\n", 1)
        if hashlib.sha256(blob).hexdigest().encode() != digest:
            raise IOError(f"Params {params_id} failed integrity check")
        return blob

    def exists(self, params_id: str) -> bool:
        return self._path(params_id).exists()

    def size(self, params_id: str) -> int:
        """On-disk byte size of the params blob (0 when absent) — the
        HBM residency charge estimate for co-hosted serving."""
        try:
            return self._path(params_id).stat().st_size
        except OSError:
            return 0

    def delete(self, params_id: str) -> None:
        self._path(params_id).unlink(missing_ok=True)

    def list(self) -> List[str]:
        return sorted(p.stem for p in self._dir.glob("*.params"))

    # -- mid-trial checkpoints ----------------------------------------------

    def save_checkpoint(self, trial_id: str, step: int, blob: bytes) -> str:
        return self.save(blob, params_id=f"{trial_id}_ckpt_{step}")

    def latest_checkpoint(self, trial_id: str) -> Optional[tuple]:
        """Return (step, blob) of the newest checkpoint for a trial.

        Only ``<trial>_ckpt_<int>`` ids are checkpoint heads; sharded
        checkpoints park their per-shard chunk blobs in the same
        namespace with a non-integer suffix (``..._ckpt_3_s0of2``,
        shard/checkpoint.py) so one ``delete_checkpoints`` sweep
        reclaims both — those are skipped here, never parsed."""
        best = None
        for p in self._dir.glob(f"{trial_id}_ckpt_*.params"):
            suffix = p.stem.rsplit("_", 1)[1]
            if not suffix.isdigit():
                continue
            step = int(suffix)
            if best is None or step > best:
                best = step
        if best is None:
            return None
        return best, self.load(f"{trial_id}_ckpt_{best}")

    def delete_checkpoints(self, trial_id: str) -> None:
        for p in self._dir.glob(f"{trial_id}_ckpt_*.params"):
            p.unlink(missing_ok=True)
