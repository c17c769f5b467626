"""The benchmark's copies against the program, at a tiny size on the CPU:
initial parameters bit for bit, forward passes, dropout masks, the task
generator and the stored-blob parser."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import load_cfg, tiny

import check
import datagen

CASES = [("vgg16_cifar", "vgg")]


def program_module(cfg, dtype=jnp.float32):
    from rafiki_tpu.models.vgg import _Vgg

    assert cfg["reference"] == "vgg"
    return _Vgg(depth=cfg["knobs"]["depth"]["fixed"],
                width_mult=cfg["knobs"]["width_mult"]["fixed"],
                num_classes=10, dropout=0.0, dtype=dtype)


def flat(tree):
    from flax.traverse_util import flatten_dict

    return {"/".join(k): v for k, v in flatten_dict(tree).items()}


@pytest.mark.parametrize("name,_ref", CASES)
def test_initial_parameters_equal_the_programs_bit_for_bit(name, _ref):
    cfg = tiny(load_cfg(name))
    _step, init_key = check.trial_keys(1234)
    mine = check.reference_of(cfg).init(init_key, cfg)
    theirs = flat(program_module(cfg).init(
        init_key, np.zeros((1, 8, 8, 3), np.float32), train=False)["params"])
    assert set(mine) == set(theirs)
    for k in mine:
        assert np.array_equal(np.asarray(mine[k]), np.asarray(theirs[k])), k


@pytest.mark.parametrize("name,_ref", CASES)
def test_forward_pass_agrees_with_the_template_in_float32(name, _ref):
    cfg = tiny(load_cfg(name))
    mod = check.reference_of(cfg)
    _step, init_key = check.trial_keys(5)
    p = mod.init(init_key, cfg)
    x = jax.random.uniform(jax.random.PRNGKey(1), (4, 8, 8, 3))
    m = program_module(cfg)
    v = m.init(init_key, np.zeros((1, 8, 8, 3), np.float32), train=False)
    with jax.default_matmul_precision("highest"):
        want = m.apply(v, x, train=False)
    assert np.allclose(np.asarray(mod.forward(p, x, cfg)), np.asarray(want),
                       atol=1e-5)


def test_dropout_mask_is_the_one_the_template_draws():
    cfg = tiny(load_cfg("vgg16_cifar"))
    mod = check.reference_of(cfg)
    _step, init_key = check.trial_keys(5)
    p = mod.init(init_key, cfg)
    x = jax.random.uniform(jax.random.PRNGKey(2), (4, 8, 8, 3))
    key = jax.random.PRNGKey(99)
    m = program_module(cfg)
    v = m.init(init_key, np.zeros((1, 8, 8, 3), np.float32), train=False)
    with jax.default_matmul_precision("highest"):
        want = m.apply(v, x, train=True, dropout_rate=jnp.float32(0.3),
                       rngs={"dropout": key})
    got = mod.forward(p, x, cfg, train=True, dropout_key=key,
                      dropout_rate=jnp.float32(0.3))
    assert np.allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_task_generator_equals_the_programs():
    from rafiki_tpu.model.dataset import dataset_utils

    cfg = tiny(load_cfg("vgg16_cifar"))
    seed = 2 * (2**31 + 11)
    ds = dataset_utils.load(datagen.image_uri(cfg, 64, seed))
    x, y = datagen.images_of(cfg, 64, seed)
    assert np.array_equal(ds.x, x) and np.array_equal(ds.y, y)


def test_stored_blob_parser_equals_the_programs_loader():
    import pickle

    from rafiki_tpu.utils.serial import dump_pytree, load_pytree

    tree = {"Conv_0": {"kernel": jnp.arange(24, dtype=jnp.float32).reshape(2, 3, 4) / 7},
            "Dense_0": {"bias": jnp.ones((5,), jnp.float32) * 0.3}}
    blob = pickle.dumps({"arch": (10, (8, 8, 3)), "packed": dump_pytree(tree)})
    mine = check.parse_params_blob(blob)
    theirs = flat(load_pytree(pickle.loads(blob)["packed"]))
    assert set(mine) == set(theirs)
    for k in mine:
        assert np.array_equal(mine[k], np.asarray(theirs[k], np.float32))


def test_change_gap_by_the_worst_leaf():
    init = {"a": np.zeros(4), "b": np.zeros(4), "c": np.zeros(4)}
    ref = {"a": np.full(4, 1.0), "b": np.full(4, 2.0), "c": np.full(4, 1e-6)}
    same = check.change_gap(init, ref, ref)
    assert same == (0.0, "")
    got = dict(ref, b=np.full(4, 3.0))
    gap, leaf = check.change_gap(init, ref, got)
    assert leaf == "b" and gap == pytest.approx(0.5)
    # a leaf the reference does not move (under a thousandth of the median
    # leaf) is left out, whatever the program did to it
    gap, _ = check.change_gap(init, ref, dict(ref, c=np.full(4, 5.0)))
    assert gap == 0.0
    # an unmoved state reads 1
    gap, _ = check.change_gap(init, ref, init)
    assert gap == pytest.approx(1.0)
