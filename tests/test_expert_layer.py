"""The language-model templates' one expert layer
(rafiki_tpu/models/kimi_linear.py::expert_layer): one sort of all
token-choices and a pass over the live rows, a slab at a time. Held here,
at the tests' tiny sizes and in float32, to the reference's layer (every
held expert on every token, weighted by nought where the token was not
routed to it: benchmark/references/kimi_linear.py::routed_part, with the
choices given), values and all five gradients, at routings that put the
slabs' edges everywhere a fault could hide. The layer inside its module,
the shares of a deployment and the NaN planted past a ragged product's
groups: tests/test_kimi_linear_layers.py, tests/test_lfm2_moe_layers.py.
Shared fixtures: tests/kimi_linear_common.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kimi_linear_common import (  # noqa: F401 (fixtures)
    close, f32, K, load_lm_cfg, program_of, R, tiny_lm, tokens, value_and_grads)

from rafiki_tpu.models import lfm2_moe as M

N, D, F, EXPERTS, TOP_K, HELD = 192, 64, 32, 16, 4, (0, 1, 2, 3)
SLAB = K._slab_rows(N * TOP_K, len(HELD), F)       # 64 rows: a batch's choices are 12 slabs

# (the router's bias, its scaling, its epsilon) as each template calls ``route``
TEMPLATES = {
    "kimi_linear": lambda key: (jnp.zeros((EXPERTS,)), 2.446, 0.0),
    "lfm2_moe": lambda key: (jax.random.uniform(key, (EXPERTS,), jnp.float32,
                                                -M.BIAS_RANGE, M.BIAS_RANGE), 1.0, M.ROUTER_EPS),
}


def operands(seed=0, experts=len(HELD)):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (N, D))
    w_router = 0.5 * jax.random.normal(ks[1], (D, EXPERTS))
    ws = [0.2 * jax.random.normal(k, s) for k, s in
          zip(ks[2:5], ((experts, D, F), (experts, D, F), (experts, F, D)))]
    return x, w_router, ws, jax.random.normal(ks[5], (N, D)), ks[5]


def every_held_expert_on_every_token(x, ids, weights, held, w_gate, w_up, w_down):
    """The reference's layer with the router's choices given."""
    y = jnp.zeros_like(x)
    for e, wg, wu, wd in zip(held, w_gate, w_up, w_down):
        we = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1, keepdims=True)
        y = y + we * R.swiglu(x, wg, wu, wd)
    return y


def agree(x, ids, weights, held, ws, ct):
    """Values and all five gradients; returns (the result, the rows a held
    expert took, the gradients)."""
    got = value_and_grads(lambda x, w, *ws: K.expert_layer(x, ids, w, held, *ws)[0],
                          x, weights, *ws, ct)
    want = value_and_grads(lambda x, w, *ws: every_held_expert_on_every_token(
        x, ids, w, held, *ws), x, weights, *ws, ct)
    for name, a, b in zip(("y", "dx", "dweights", "dw_gate", "dw_up", "dw_down"), got, want):
        assert np.all(np.isfinite(np.asarray(a))), name
        assert close(a, b, 3e-5) or float(jnp.max(jnp.abs(a - b))) < 1e-9, name
    sizes = K.expert_layer(x, ids, weights, held, *ws)[1]
    assert [int(v) for v in sizes] == [int((ids == e).sum()) for e in held]
    return got[0], [int(v) for v in sizes], got[1:]


def boundaries(key):
    """Choices by hand: held expert 0 takes 64 rows (its group ends on a
    slab's edge), 1 takes 40 (inside the second slab), 2 takes 88 (to the
    third slab's edge at 192), 3 takes 10; every other slot names an absent
    expert, a token's four all distinct."""
    t = np.arange(N)
    ids = np.stack([4 + 3 * j + t % 3 for j in range(TOP_K)], axis=1)
    for j, (lo, hi) in enumerate(((0, 64), (0, 40), (50, 138), (180, 190))):
        ids[lo:hi, j] = j
    return jnp.asarray(ids, jnp.int32), jax.random.uniform(key, (N, TOP_K), minval=0.1)


@pytest.mark.parametrize("routing", [
    "as_drawn", "every_expert_held", "no_choice_held", "one_held_expert_on_every_token",
    "a_held_expert_with_no_row", "group_ends_inside_a_slab_and_on_its_edge"])
@pytest.mark.parametrize("template", list(TEMPLATES))
def test_one_sort_and_slabs_equal_every_held_expert_on_every_token(template, routing, f32):
    held = tuple(range(EXPERTS)) if routing == "every_expert_held" else HELD
    x, w_router, ws, ct, key = operands(experts=len(held))
    bias, scaling, eps = TEMPLATES[template](key)
    # a bias of -10 / +10 on the selection: never / always among the top four of sigmoids
    bias = {"no_choice_held": bias.at[jnp.asarray(HELD)].set(-10.0),
            "one_held_expert_on_every_token": bias.at[1].set(10.0),
            "a_held_expert_with_no_row": bias.at[2].set(-10.0)}.get(routing, bias)
    ids, weights = K.route(x, w_router, bias, TOP_K, scaling, eps)
    if routing.startswith("group_ends"):
        ids, weights = boundaries(key)
    y, sizes, grads = agree(x, ids, weights, held, ws, ct)
    live = sum(sizes)
    if routing == "every_expert_held":       # every slab runs, the last one whole
        slab = K._slab_rows(N * TOP_K, EXPERTS, F)
        assert live == N * TOP_K and live % slab == 0 and live // slab > 1
    elif routing == "no_choice_held":        # no slab runs: nought, and nought handed back
        assert live == 0 and not np.any(np.asarray(y))
        assert all(not np.any(np.asarray(g)) for g in grads)
    elif routing == "one_held_expert_on_every_token":   # one group over three slabs and more
        assert sizes[1] == N > 2 * SLAB
    elif routing == "a_held_expert_with_no_row":
        assert sizes[2] == 0 and live > SLAB
    elif routing.startswith("group_ends"):
        assert sizes == [SLAB, 40, 88, 10] and sizes[0] + sizes[1] + sizes[2] == 3 * SLAB
    else:
        assert SLAB < live < N * TOP_K


def _primitives(jaxpr, found):
    for eqn in jaxpr.eqns:
        found.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, found)
    return found


def test_the_layer_is_one_sort_and_a_loop_over_live_slabs_not_over_choices():
    """Staged: one sort (of all N k token-choices: the lowered step's test
    below reads its operand); no scan (the parent scanned over the k
    choices); one ``while`` whose trip count is a device value, in the
    forward pass and one in the backward; three ragged products a slab."""
    x, w_router, ws, ct, key = operands()
    ids, weights = K.route(x, w_router, jnp.zeros((EXPERTS,)), TOP_K, 2.446)

    def layer(x, weights, *ws):
        return K.expert_layer(x, ids, weights, HELD, *ws)[0]

    forward = _primitives(jax.make_jaxpr(layer)(x, weights, *ws).jaxpr, [])
    assert forward.count("sort") == 1 and "scan" not in forward
    assert forward.count("while") == 1 and forward.count("ragged_dot_general") == 3
    both = _primitives(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(layer(*a) * ct),
                                               argnums=(0, 1, 2, 3, 4)))(x, weights, *ws).jaxpr, [])
    assert both.count("sort") == 1 and "scan" not in both and both.count("while") == 2


def _program(template):
    """(the template's model, its closures, seeded parameters, a batch) at
    the tests' tiny size."""
    if template == "kimi_linear":
        cfg = tiny_lm(load_lm_cfg())
        model, fns, params, _ref = program_of(cfg)
    else:
        import lfm2_moe_common as L
        cfg = L.tiny_lfm2(L.load_lfm2_cfg())
        model, fns, params, _ref = L.program_of(cfg)
    x, y = tokens(cfg)
    return model, fns, params, {"x": x, "y": y}


@pytest.mark.parametrize("template", list(TEMPLATES))
def test_a_steps_metrics_carry_the_rows_the_slabs_had_room_for(template):
    """``count.moe.rows_room``: the rows of the slabs the sparse layers ran,
    so at least the rows held, under a slab a layer more than them, and far
    under the slots the parent's passes moved."""
    model, fns, params, batch = _program(template)
    _loss, metrics = jax.jit(fns["loss_fn"])(params, batch, None,
                                             {"label_smoothing": jnp.float32(0.0)})
    held, room, total = (float(metrics[f"count.moe.{k}"])
                         for k in ("slots_held", "rows_room", "slots_total"))
    sparse = sum(1 for _mixer, sp in fns["module"].layer_kinds() if sp)
    slab = K._slab_rows(batch["x"].size * TOP_K, len(HELD), int(model.knobs["moe_intermediate_size"]))
    assert total == batch["x"].size * TOP_K * sparse
    assert 0 < held <= room <= total and room % slab == 0 and room - held < sparse * slab
    assert room < total / 2


@pytest.mark.parametrize("template", list(TEMPLATES))
def test_the_step_lowered_for_a_tpu_sorts_once_a_sparse_layer(template):
    """The templates' whole loss and its gradient, lowered for a TPU: every
    sort in it is of all N k token-choices of a sparse layer, one in the
    forward pass and one where ``nn.remat`` runs the layer again, none in the
    backward pass proper and none of N tokens (the parent's sort of one
    choice, inside its scan over the k)."""
    import re

    model, fns, params, batch = _program(template)
    hyper = {"label_smoothing": jnp.float32(0.0)}
    sparse = sum(1 for _mixer, sp in fns["module"].layer_kinds() if sp)
    keys = batch["x"].size * int(model.knobs[model.TOP_K_KNOB])

    def sorts(fn):
        """The operand of every call of ``argsort`` (lowered as a function of
        its own, which holds the text's only ``stablehlo.sort``s)."""
        text = jax.jit(fn).trace(params, batch, None, hyper).lower(
            lowering_platforms=("tpu",)).as_text()
        assert text.count("stablehlo.sort") == len(re.findall(r"func\.func private @argsort", text))
        return re.findall(r"call @argsort\w*\(%\w+\) : \((tensor<[^>]*>)\)", text)

    forward = sorts(lambda *a: fns["loss_fn"](*a)[0])
    step = sorts(jax.grad(lambda *a: fns["loss_fn"](*a)[0]))
    assert len(forward) == sparse and len(step) == 2 * sparse
    assert set(forward + step) == {f"tensor<{keys}xi32>"}
