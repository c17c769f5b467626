"""What the tests of the second language-model template share
(tests/test_lfm2_moe_layers.py, _model.py, _trials.py): the benchmark's
reference and tiny configuration, and tests/kimi_linear_common.py's helpers
taken for this template (the small subclass, the seeded program and its
reference parameters; ``f32`` is the same fixture: both templates' matrix
products are ``kimi_linear._mm``)."""

import functools

import pytest

import kimi_linear_common as common
from kimi_linear_common import (  # noqa: F401  (re-exported)
    check, close, dataset_utils, f32, FixedKnob, flat, interpreted, K, REPO, telemetry,
    tokens, TRAIN, VAL, value_and_grads)
from lfm2_tiny import load_lfm2_cfg, template_knobs, tiny_lfm2  # noqa: F401
from references import lfm2_moe as R  # noqa: F401

from rafiki_tpu.models import lfm2_moe as M  # noqa: F401

small_class = functools.partial(common.small_class, template=M.Lfm2Moe)
program_of = functools.partial(common.program_of, template=M.Lfm2Moe, reference=R)


@pytest.fixture
def cfg():
    return tiny_lfm2(load_lfm2_cfg())
