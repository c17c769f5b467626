"""Dataset utilities: URI-addressed datasets, device-ready batching.

Reference parity: rafiki/model/dataset.py (unverified path):
``dataset_utils.load_dataset_of_image_files(uri)`` (zip of image files +
``images.csv`` with class labels) and ``load_dataset_of_corpus(uri)``
(zip of a TSV corpus for POS tagging). Datasets are addressed by URI.

TPU-native design:
  * a loaded ``Dataset`` is dense numpy arrays (NHWC uint8 images /
    int32 token-tag matrices), so the training loop feeds the device
    fixed-shape batches — XLA traces once per (batch, shape) signature.
  * ``batches()`` drops the train remainder (static shapes for jit) and
    pads + masks the eval remainder, so evaluation is exact without
    dynamic shapes.
  * ``synthetic://`` URIs generate deterministic learnable datasets
    in-process (class-conditional Gaussian images; token-tag sequences
    with a learnable token→tag mapping). This environment has zero
    network egress, and it also gives tests/benches a data source with
    real learnable signal.

URI schemes:
  synthetic://images?classes=10&w=28&h=28&c=1&n=2048&seed=0
  synthetic://corpus?vocab=200&tags=10&n=512&len=24&seed=0
  synthetic://tokens?vocab=20480&n=16&len=8192&seed=0
  /path/to/dataset.zip        (zip of images + images.csv, reference format)
  /path/to/dataset.npz        (npz with arrays x, y)
  file:///path/to/dataset.zip
"""

from __future__ import annotations

import csv
import io
import json
import os
import urllib.parse
import zipfile
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

from rafiki_tpu import telemetry


@dataclass
class Dataset:
    """An in-memory dataset of (x, y) numpy arrays.

    For images: x is (N, H, W, C) float32 in [0, 1], y is (N,) int32.
    For corpora: x is (N, L) int32 token ids, y is (N, L) int32 tag ids
    with -1 padding, plus ``mask`` (N, L) bool.
    """

    x: np.ndarray
    y: np.ndarray
    classes: int
    mask: Optional[np.ndarray] = None
    meta: dict = field(default_factory=dict)

    @property
    def size(self) -> int:
        return int(self.x.shape[0])

    def split(self, frac: float, seed: int = 0) -> Tuple["Dataset", "Dataset"]:
        """Deterministic shuffled split into (first, second) with |first| = frac*N."""
        rng = np.random.default_rng(seed)
        order = rng.permutation(self.size)
        k = int(self.size * frac)
        a, b = order[:k], order[k:]
        mk = lambda idx: Dataset(
            self.x[idx], self.y[idx], self.classes,
            None if self.mask is None else self.mask[idx], dict(self.meta),
        )
        return mk(a), mk(b)

    def batches(
        self,
        batch_size: int,
        shuffle: bool = False,
        seed: int = 0,
        drop_remainder: bool = True,
        start: int = 0,
    ) -> Iterator[dict]:
        """Yield dicts of fixed-shape numpy batches.

        drop_remainder=True  → training mode: every batch is exactly
            batch_size (static shape → single XLA program).
        drop_remainder=False → eval mode: the last batch is zero-padded
            to batch_size and carries ``valid`` (bool mask over rows) so
            metrics can ignore padding.
        start → skip the first ``start`` rows (in iteration order); used
            when a device-side scan already covered a prefix.
        """
        n = self.size
        order = np.random.default_rng(seed).permutation(n) if shuffle else np.arange(n)
        for start in range(start, n, batch_size):
            idx = order[start : start + batch_size]
            if len(idx) < batch_size:
                if drop_remainder:
                    return
                pad = batch_size - len(idx)
                idx = np.concatenate([idx, np.zeros(pad, dtype=idx.dtype)])
                valid = np.zeros(batch_size, dtype=bool)
                valid[: batch_size - pad] = True
            else:
                valid = np.ones(batch_size, dtype=bool)
            batch = {"x": self.x[idx], "y": self.y[idx], "valid": valid}
            if self.mask is not None:
                batch["mask"] = self.mask[idx]
            yield batch


# ---------------------------------------------------------------------------
# Synthetic generators (deterministic, learnable)
# ---------------------------------------------------------------------------

def synthetic_images(classes=10, w=28, h=28, c=1, n=2048, seed=0, noise=0.35,
                     dist=0, flip=0.0) -> Dataset:
    """Class-conditional Gaussian-blob images.

    Each class k gets a fixed random template image; samples are
    template + Gaussian noise, clipped to [0, 1]. Linearly separable
    enough that accuracy tracks model/knob quality (the property the
    advisor needs), hard enough that more training helps.

    ``dist`` seeds the class templates (the underlying distribution);
    ``seed`` seeds the draws. Train/test splits of the same task share
    ``dist`` and differ in ``seed`` — otherwise they would be different
    classification problems and generalization would be impossible.

    ``flip`` relabels that fraction of samples uniformly at random,
    which caps attainable accuracy at a KNOWN ceiling independent of
    model, scale, or epochs: a perfect template classifier scores
    (1-flip) + flip/classes. That makes an accuracy target falsifiable
    — on a saturating task (flip=0) every non-broken config converges
    to ~1.0 and a "top-1 >= X" gate constrains nothing.
    """
    # Low-spatial-frequency templates (drawn coarse, then upsampled):
    # learnable both by flatten-head models (MLP/VGG) and by
    # global-average-pool heads (DenseNet), which can't see per-pixel
    # high-frequency patterns.
    th, tw = max(2, h // 4), max(2, w // 4)
    coarse = (np.random.default_rng(dist)
              .uniform(0.0, 1.0, size=(classes, th, tw, c)).astype(np.float32))
    templates = np.repeat(np.repeat(coarse, h // th + 1, axis=1), w // tw + 1, axis=2)
    templates = templates[:, :h, :w, :]
    rng = np.random.default_rng(seed + 1_000_003)
    y = rng.integers(0, classes, size=n).astype(np.int32)
    x = templates[y] + rng.normal(0.0, noise, size=(n, h, w, c)).astype(np.float32)
    x = np.clip(x, 0.0, 1.0).astype(np.float32)
    if flip > 0:
        flipped = rng.uniform(size=n) < flip
        y = np.where(flipped, rng.integers(0, classes, size=n), y).astype(np.int32)
    return Dataset(x, y, classes, meta={"kind": "images", "synthetic": True})


def synthetic_corpus(vocab=200, tags=10, n=512, length=24, seed=0, noise=0.05,
                     dist=0) -> Dataset:
    """Token sequences with a fixed random token→tag mapping (+ noise).

    A model that learns the per-token mapping (as an HMM/BiLSTM will)
    reaches ~(1-noise) accuracy. ``dist`` seeds the token→tag mapping,
    ``seed`` the draws (see synthetic_images on why they are separate).
    """
    tok2tag = (np.random.default_rng(dist)
               .integers(0, tags, size=vocab).astype(np.int32))
    rng = np.random.default_rng(seed + 1_000_003)
    x = rng.integers(1, vocab, size=(n, length)).astype(np.int32)  # 0 = pad
    y = tok2tag[x]
    flip = rng.uniform(size=y.shape) < noise
    y = np.where(flip, rng.integers(0, tags, size=y.shape), y).astype(np.int32)
    lens = rng.integers(max(2, length // 2), length + 1, size=n)
    mask = np.arange(length)[None, :] < lens[:, None]
    x = np.where(mask, x, 0).astype(np.int32)
    y = np.where(mask, y, -1).astype(np.int32)
    return Dataset(x, y, tags, mask=mask, meta={"kind": "corpus", "synthetic": True, "vocab": vocab})


def synthetic_text(vocab=80, classes=5, n=256, length=16, seed=0, noise=0.1,
                   dist=0) -> Dataset:
    """Fixed-length token sequences with ONE label per sequence — the
    text-classification companion to :func:`synthetic_corpus` (which is
    per-token tagging and therefore carries a mask).

    Token identity encodes the class: token t (1-based) signals class
    ``(t - 1) % classes``; each sequence draws ``1 - noise`` of its
    positions from its own class's tokens and the rest uniformly. A
    mean-pooled embedding separates the classes, accuracy saturates at
    a noise-determined ceiling, and — crucially for the sharded-trial
    lane — sequences are fixed-length so ``mask`` is None and the
    dataset rides the device-resident scan path bit-for-bit.
    """
    rng = np.random.default_rng(seed + 1_000_003)
    y = rng.integers(0, classes, size=n).astype(np.int32)
    m = max(1, (vocab - 1) // classes)  # class tokens per class
    sig_tok = 1 + y[:, None] + classes * rng.integers(0, m, size=(n, length))
    noise_tok = rng.integers(1, vocab, size=(n, length))
    sig = rng.uniform(size=(n, length)) >= noise
    x = np.where(sig, sig_tok, noise_tok).astype(np.int32)
    return Dataset(x, y, classes,
                   meta={"kind": "text", "synthetic": True, "vocab": vocab})


def synthetic_tokens(vocab=256, n=16, length=96, seed=0, follow=0.5,
                     dist=0) -> Dataset:
    """A token stream for next-token language modelling: ``n`` documents
    of ``length`` tokens over ids 0..vocab-1, one a sequence (no packing,
    no mask). x is a document's tokens and y the token that follows each
    (``length + 1`` are drawn), so every position is scored; ``classes``
    is the vocabulary.

    Learnable at two depths. Unigram: a document's free draws follow a
    Zipf law over a seeded permutation of the ids (``dist``), so the most
    frequent id alone is right about 1/H(vocab) of the time, and a few
    optimizer steps find it. Bigram: with probability ``follow`` a token
    is a fixed function of the one before it (a seeded permutation of the
    ids, ``dist`` again), which a model learns only as far as it has seen
    the pairs. ``seed`` seeds the draws alone, as in synthetic_images."""
    law = np.random.default_rng(dist + 7_000_003)
    rank_of = law.permutation(vocab)            # id of each Zipf rank
    successor = law.permutation(vocab)          # the bigram rule
    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64)
    p /= p.sum()
    rng = np.random.default_rng(seed + 1_000_003)
    free = rank_of[rng.choice(vocab, size=(n, length + 1), p=p)]
    bound = rng.uniform(size=(n, length + 1)) < follow
    toks = free.copy()
    for t in range(1, length + 1):
        toks[:, t] = np.where(bound[:, t], successor[toks[:, t - 1]], free[:, t])
    toks = toks.astype(np.int32)
    return Dataset(toks[:, :-1].copy(), toks[:, 1:].copy(), int(vocab),
                   meta={"kind": "tokens", "synthetic": True, "vocab": int(vocab)})


# ---------------------------------------------------------------------------
# Reference on-disk formats
# ---------------------------------------------------------------------------

def load_dataset_of_image_files(uri: str) -> Dataset:
    """Load the reference's image-zip format.

    Format (ref: rafiki/model/dataset.py, unverified): a zip containing
    image files plus ``images.csv`` with header ``path,class``; images
    are loaded, converted to grayscale-or-RGB arrays scaled to [0, 1].
    """
    path = _resolve_path(uri)
    if path.endswith(".npz"):
        return _load_npz(path, kind="images")
    from PIL import Image

    xs: List[np.ndarray] = []
    ys: List[int] = []
    with zipfile.ZipFile(path) as zf:
        with zf.open("images.csv") as f:
            rows = list(csv.DictReader(io.TextIOWrapper(f, "utf-8")))
        for row in rows:
            with zf.open(row["path"]) as imf:
                img = Image.open(imf)
                arr = np.asarray(img, dtype=np.float32) / 255.0
            if arr.ndim == 2:
                arr = arr[:, :, None]
            xs.append(arr)
            ys.append(int(row["class"]))
    x = np.stack(xs)
    y = np.asarray(ys, dtype=np.int32)
    return Dataset(x, y, classes=int(y.max()) + 1, meta={"kind": "images", "uri": uri})


# Canonical corpus encoding: ids must be DETERMINISTIC FUNCTIONS OF THE
# TEXT, not of one zip's iteration order — a train zip and a val zip are
# loaded independently (the model contract passes separate URIs), and
# first-seen-order vocabularies would silently map the same token or
# tag to different ids across the two, corrupting every evaluation.
#   * tokens: feature-hashed into a fixed table (same token → same id
#     in any zip; unseen val tokens get an arbitrary-but-consistent
#     bucket instead of crashing — the standard OOV story);
#   * tags: alphabetical (train/val splits of one corpus share the tag
#     set, and sorted order is content-determined);
#   * length: one fixed bucket (static shapes — one XLA program for
#     every zip; longer sentences truncate, the mask stays exact).
CORPUS_HASH_VOCAB = 8192
CORPUS_MAX_LEN = 64


def corpus_token_id(token: str) -> int:
    """Stable token id in [1, CORPUS_HASH_VOCAB): blake2b feature hash
    (0 is reserved for padding). Use this to build predict() queries
    from raw tokens — it is the same mapping the corpus loader applies."""
    import hashlib

    h = int.from_bytes(
        hashlib.blake2b(token.encode("utf-8"), digest_size=8).digest(), "big")
    return 1 + h % (CORPUS_HASH_VOCAB - 1)


def load_dataset_of_corpus(uri: str, tag_col: str = "tag") -> Dataset:
    """Load the reference's corpus-zip format: a TSV ``corpus.tsv`` of
    token/tag rows with blank lines between sentences. Encoding is
    canonical (see above) so separately-loaded train/val zips agree."""
    path = _resolve_path(uri)
    if path.endswith(".npz"):
        return _load_npz(path, kind="corpus")
    sents: List[List[Tuple[str, str]]] = []
    with zipfile.ZipFile(path) as zf:
        name = next(n for n in zf.namelist() if n.endswith(".tsv"))
        with zf.open(name) as f:
            cur: List[Tuple[str, str]] = []
            for line in io.TextIOWrapper(f, "utf-8"):
                line = line.rstrip("\n")
                if not line:
                    if cur:
                        sents.append(cur)
                        cur = []
                    continue
                tok, tag = line.split("\t")[:2]
                cur.append((tok, tag))
            if cur:
                sents.append(cur)
    tagset = {t: i for i, t in enumerate(sorted(
        {tag for s in sents for _, tag in s}))}
    length = CORPUS_MAX_LEN
    n = len(sents)
    x = np.zeros((n, length), dtype=np.int32)
    y = np.full((n, length), -1, dtype=np.int32)
    mask = np.zeros((n, length), dtype=bool)
    for i, s in enumerate(sents):
        for j, (tok, tag) in enumerate(s[:length]):
            x[i, j] = corpus_token_id(tok)
            y[i, j] = tagset[tag]
            mask[i, j] = True
    return Dataset(x, y, classes=len(tagset), mask=mask,
                   meta={"kind": "corpus", "uri": uri,
                         "vocab": CORPUS_HASH_VOCAB, "tag_map": tagset})


def _load_npz(path: str, kind: str) -> Dataset:
    with np.load(path, allow_pickle=False) as z:
        x = z["x"]
        y = z["y"].astype(np.int32)
        mask = z["mask"] if "mask" in z else None
        saved_meta = (json.loads(str(z["meta_json"]))
                      if "meta_json" in z else {})
    classes = int(y.max()) + 1 if kind == "images" else int(y[y >= 0].max()) + 1
    if saved_meta.get("classes"):
        classes = int(saved_meta.pop("classes"))
    if kind == "images" and x.dtype == np.uint8:
        x = x.astype(np.float32) / 255.0
    meta = {"kind": kind, "uri": path}
    if kind == "corpus":
        # Legacy derivation only when the npz carries no meta: a hashed
        # corpus saved via save_npz MUST keep its fixed table size —
        # max-observed-id+1 would shrink the embedding below ids that
        # corpus_token_id() can legitimately produce for new queries.
        meta["vocab"] = int(x.max()) + 1
    meta.update(saved_meta)
    return Dataset(x, y, classes=classes, mask=mask, meta=meta)


def _resolve_path(uri: str) -> str:
    if uri.startswith("file://"):
        return urllib.parse.urlparse(uri).path
    return os.path.expanduser(uri)


# ---------------------------------------------------------------------------
# Front door
# ---------------------------------------------------------------------------

class DatasetUtils:
    """URI front door, mirroring the reference's ``dataset_utils`` object.

    Loads are cached process-wide (small LRU, keyed by URI + file mtime
    for local paths): a train worker loads the SAME dataset URI once
    per trial, and regenerating a CIFAR-scale synthetic set (~600MB of
    RNG) or re-decoding a zip costs about as much as a warm trial's
    entire compute — a straight trials/hour tax. Datasets are treated
    as immutable by every consumer (templates wrap them in new
    ``Dataset`` views; ``batches()`` shuffles indices, not arrays).
    """

    _CACHE_CAP = 4  # datasets can be ~GBs; keep the working set tight

    def __init__(self):
        import threading

        self._cache: "dict" = {}  # key -> Dataset; insertion order = LRU
        self._lock = threading.Lock()

    def _cache_key(self, uri: str):
        if uri.startswith("synthetic://"):
            return uri  # fully determined by the URI itself
        path = _resolve_path(uri)
        try:
            return (uri, os.path.getmtime(path))  # changed file = new key
        except OSError:
            return None  # missing/odd path: let _load raise, uncached

    def load(self, uri: str) -> Dataset:
        key = self._cache_key(uri)
        if key is not None:
            with self._lock:
                ds = self._cache.get(key)
                if ds is not None:
                    self._cache[key] = self._cache.pop(key)  # refresh LRU
                    return ds
        # A miss is set-up's: generating or decoding the data set (a plain
        # span: the leaf phase that first asks for the data set encloses
        # it); a hit records nothing.
        scheme = urllib.parse.urlparse(uri).scheme or "file"
        with telemetry.span("data.load", uri_scheme=scheme) as sp:
            ds = self._load(uri)
            sp.tags["bytes"] = int(sum(
                a.nbytes for a in (ds.x, ds.y, ds.mask) if a is not None))
        if key is not None:
            with self._lock:
                self._cache[key] = ds
                while len(self._cache) > self._CACHE_CAP:
                    self._cache.pop(next(iter(self._cache)))
        return ds

    def clear_cache(self) -> None:
        with self._lock:
            self._cache.clear()

    def _load(self, uri: str) -> Dataset:
        if uri.startswith("synthetic://"):
            parsed = urllib.parse.urlparse(uri)
            q = {k: int(v[0]) if v[0].lstrip("-").isdigit() else float(v[0])
                 for k, v in urllib.parse.parse_qs(parsed.query).items()}
            if parsed.netloc == "images":
                return synthetic_images(**{k: q[k] for k in q if k in
                                           ("classes", "w", "h", "c", "n", "seed", "noise", "dist", "flip")})
            if parsed.netloc == "corpus":
                kw = dict(q)
                if "len" in kw:
                    kw["length"] = kw.pop("len")
                return synthetic_corpus(**{k: kw[k] for k in kw if k in
                                           ("vocab", "tags", "n", "length", "seed", "noise", "dist")})
            if parsed.netloc == "text":
                kw = dict(q)
                if "len" in kw:
                    kw["length"] = kw.pop("len")
                return synthetic_text(**{k: kw[k] for k in kw if k in
                                         ("vocab", "classes", "n", "length", "seed", "noise", "dist")})
            if parsed.netloc == "tokens":
                kw = dict(q)
                if "len" in kw:
                    kw["length"] = kw.pop("len")
                return synthetic_tokens(**{k: kw[k] for k in kw if k in
                                           ("vocab", "n", "length", "seed", "follow", "dist")})
            raise ValueError(f"Unknown synthetic dataset: {parsed.netloc!r}")
        path = _resolve_path(uri)
        if path.endswith(".npz"):
            with np.load(path, allow_pickle=False) as z:
                kind = "corpus" if ("mask" in z or z["x"].ndim == 2) else "images"
            return _load_npz(path, kind)
        # zip: sniff for corpus vs images
        with zipfile.ZipFile(path) as zf:
            names = zf.namelist()
        if any(n.endswith(".tsv") for n in names):
            return load_dataset_of_corpus(uri)
        return load_dataset_of_image_files(uri)

    load_dataset_of_image_files = staticmethod(load_dataset_of_image_files)
    load_dataset_of_corpus = staticmethod(load_dataset_of_corpus)

    @staticmethod
    def save_npz(dataset: Dataset, path: str) -> str:
        arrays = {"x": dataset.x, "y": dataset.y}
        if dataset.mask is not None:
            arrays["mask"] = dataset.mask
        # Persist the json-able meta (vocab size, tag_map, classes):
        # without it a reloaded hashed corpus would re-derive vocab as
        # max-observed-id+1 and lose the label-space signature.
        portable = {k: v for k, v in dataset.meta.items()
                    if isinstance(v, (str, int, float, bool))}
        if isinstance(dataset.meta.get("tag_map"), dict):
            portable["tag_map"] = dataset.meta["tag_map"]
        portable["classes"] = dataset.classes
        arrays["meta_json"] = np.asarray(json.dumps(portable))
        np.savez_compressed(path, **arrays)
        return path


dataset_utils = DatasetUtils()
