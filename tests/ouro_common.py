"""What the tests of the third language-model template share
(tests/test_ouro_layers.py, _model.py, _trials.py): the benchmark's reference
and tiny configuration, and tests/kimi_linear_common.py's helpers taken for
this template (the small subclass, the seeded program and its reference
parameters; ``f32`` is the same fixture: all three templates' matrix products
are ``kimi_linear._mm``)."""

import functools

import pytest

import kimi_linear_common as common
from kimi_linear_common import (  # noqa: F401  (re-exported)
    check, close, dataset_utils, f32, FixedKnob, flat, interpreted, K, REPO, telemetry,
    tokens, TRAIN, VAL, value_and_grads)
from ouro_tiny import load_ouro_cfg, template_knobs, tiny_ouro  # noqa: F401
from references import ouro as R  # noqa: F401

from rafiki_tpu.models import ouro as M  # noqa: F401

small_class = functools.partial(common.small_class, template=M.Ouro)
program_of = functools.partial(common.program_of, template=M.Ouro, reference=R)


@pytest.fixture
def cfg():
    return tiny_ouro(load_ouro_cfg())
