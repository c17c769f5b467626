"""Model zoo: TPU-native model templates mirroring the reference's
examples/models/ (SURVEY.md §2 "Example models", unverified paths):

  FeedForward  ← TfFeedForward.py  (MLP, MNIST-class images)
  Vgg          ← TfVgg16.py        (VGG CNN, CIFAR-10-class images)
  DenseNet     ← PyDenseNet.py     (DenseNet-BC CNN, CIFAR-10)
  SkDt / SkSvm ← SkDt.py, SkSvm.py (sklearn host models)
  PosBiLstm    ← PyBiLstm.py       (BiLSTM POS tagger)
  PosBigramHmm ← BigramHmm.py      (bigram HMM POS tagger)
  Transformer  — no reference analog: text-classifier encoder, the
                 zoo's sharded-lane citizen (docs/sharding.md)
  KimiLinear   — no reference analog: a hybrid linear-attention (gated
                 delta rule) / latent-attention sparse-expert language
                 model, one chip's share of an expert-parallel
                 deployment; the zoo's language-modelling citizen
  Lfm2Moe      — no reference analog: the zoo's second language model, a
                 hybrid of gated short convolutions and grouped-query
                 attention with rotary positions over sparse experts
                 selected by score plus a bias; it shares the router and
                 the expert layer with KimiLinear
  Ouro         — no reference analog: the zoo's third language model, a
                 dense stack of sandwich-normed layers run several times
                 with shared weights, a loss at every pass weighted by a
                 learned exit gate; it shares the attention, the blocked
                 loss and the language-model base with the other two
"""

from rafiki_tpu.models.ff import FeedForward

__all__ = ["FeedForward"]


def _optional():
    # Heavier templates are imported lazily by the registry below.
    pass


MODEL_REGISTRY = {
    "FeedForward": ("rafiki_tpu.models.ff", "FeedForward"),
    "Vgg": ("rafiki_tpu.models.vgg", "Vgg"),
    "DenseNet": ("rafiki_tpu.models.densenet", "DenseNet"),
    "SkDt": ("rafiki_tpu.models.sk", "SkDt"),
    "SkSvm": ("rafiki_tpu.models.sk", "SkSvm"),
    "PosBiLstm": ("rafiki_tpu.models.pos_bilstm", "PosBiLstm"),
    "PosBigramHmm": ("rafiki_tpu.models.pos_hmm", "PosBigramHmm"),
    "Transformer": ("rafiki_tpu.models.transformer", "Transformer"),
    "KimiLinear": ("rafiki_tpu.models.kimi_linear", "KimiLinear"),
    "Lfm2Moe": ("rafiki_tpu.models.lfm2_moe", "Lfm2Moe"),
    "Ouro": ("rafiki_tpu.models.ouro", "Ouro"),
}


def get_model_class(name: str) -> type:
    import importlib

    if name not in MODEL_REGISTRY:
        raise ValueError(f"Unknown model template {name!r}; known: {sorted(MODEL_REGISTRY)}")
    mod_name, cls_name = MODEL_REGISTRY[name]
    try:
        return getattr(importlib.import_module(mod_name), cls_name)
    except ModuleNotFoundError as e:
        raise ValueError(f"Model template {name!r} is not available: {e}") from e
