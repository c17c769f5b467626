"""The fused chunk kernels of the language-model template's delta rule
(rafiki_tpu/models/kimi_linear.py::kda_chunked) in Pallas' interpreter on
the CPU: against the reference's recurrence token by token
(benchmark/references/kimi_linear.py) and against the ``jax.numpy`` scan
they replace on a TPU; which of the two is staged, by shape and by the
platform a program is lowered for; what a step counts of it. The same for
the branch kernels (``kda_branch``: a branch's convolution, SiLU and
l2norm) against the ``jax.numpy`` tail they replace. Shared fixtures:
tests/kimi_linear_common.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kimi_linear_common import (  # noqa: F401 (fixtures)
    close, f32, interpreted, K, kda_operands, R, step_metrics, value_and_grads)


KDA_NAMES = ("value", "dq", "dk", "dv", "da", "dbeta")


@pytest.mark.parametrize("against", ["the_recurrence", "the_scan"])
def test_the_chunk_kernel_matches(against, f32, interpreted):
    """The fused chunk kernel (Pallas in interpret mode on the CPU), float32
    products: its result against the reference's recurrence token by token
    and against the ``jax.numpy`` scan, at the tolerance
    ``test_chunked_kda_equals_the_recurrence`` holds the scan to."""
    xs, _ct = kda_operands()
    got, _starts = K._kda_kernel_forward(*xs, interpret=True)
    want = (R.delta_rule(*xs) if against == "the_recurrence"
            else K._kda_scan(*xs, K.KDA_KERNEL_CHUNK)[0])
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert close(got, want, 1e-5)


def test_the_chunk_kernel_saves_the_state_each_chunk_starts_from(f32, monkeypatch, interpreted):
    """What the backward kernel starts a chunk from: the scan's carries,
    the groups' boundaries among them, as the kernels keep them
    ([B, H, chunks, dv, dk]: transposed), the first one zero."""
    monkeypatch.setattr(K, "KDA_GROUP", 2)
    xs, _ct = kda_operands()
    _o, starts = K._kda_kernel_forward(*xs, interpret=True)
    _o, want = K._kda_scan(*xs, K.KDA_KERNEL_CHUNK)
    assert want.shape == (2, 2, 1, 2, 128, 128) and starts.shape == (1, 2, 4, 128, 128)
    want = jnp.transpose(want.reshape((4,) + want.shape[2:]), (1, 2, 0, 4, 3))
    assert not np.any(np.asarray(starts[:, :, 0])) and np.any(np.asarray(starts[:, :, 1]))
    assert close(starts, want, 1e-5)


@pytest.mark.parametrize("backward", ["the_backward_kernel", "the_groups_walked_back"])
def test_the_fused_rules_five_gradients_are_the_scans(backward, f32, monkeypatch, interpreted):
    """``jax.grad`` through the ``custom_vjp`` against ``jax.grad`` of the
    ``jax.numpy`` path, float32, two groups of two chunks: with both kernels
    (Pallas' interpreter), and as the rule runs where no kernel does (the
    scan forward, its groups walked back from the saved states)."""
    monkeypatch.setattr(K, "KDA_GROUP", 2)
    xs, ct = kda_operands()
    rule = ((lambda *xs: K._kda_fused(*xs, True)[0]) if backward == "the_backward_kernel"
            else (lambda *xs: K.kda_chunked(*xs, K.KDA_KERNEL_CHUNK)[0]))
    got = value_and_grads(rule, *xs, ct)
    want = value_and_grads(lambda *xs: K._kda_scan(*xs, K.KDA_KERNEL_CHUNK)[0], *xs, ct)
    for name, a, b in zip(KDA_NAMES, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype and close(a, b, 1e-4), name


def test_the_fused_rule_in_bfloat16_is_as_near_the_recurrence_as_the_scan(interpreted):
    """The contract both paths share: bfloat16 q, k, v and operands of the
    products, float32 sums, solve, state and decays. Against the float32
    recurrence the kernels' value and gradients are no further than the
    scan's own (by more than bfloat16's noise), and of the scan's types."""
    xs, ct = kda_operands(dtype=jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        want = value_and_grads(R.delta_rule, *(x.astype(jnp.float32) for x in xs), ct)
    fused = value_and_grads(lambda *xs: K._kda_fused(*xs, True)[0], *xs, ct)
    scan = value_and_grads(lambda *xs: K._kda_scan(*xs, K.KDA_KERNEL_CHUNK)[0], *xs, ct)

    def gap(x, w):
        return float(jnp.max(jnp.abs(x.astype(jnp.float32) - w)) / jnp.max(jnp.abs(w)))

    for name, a, b, w in zip(KDA_NAMES, fused, scan, want):
        assert a.dtype == b.dtype
        assert gap(b, w) < 0.03 and gap(a, w) < max(1.5 * gap(b, w), 0.01), (name, gap(a, w), gap(b, w))


@pytest.mark.parametrize("T, H, d, chunk", [
    (256, 2, 128, 64), (256, 2, 128, 16), (256, 4, 16, 64), (200, 2, 128, 64), (192, 2, 128, 64)],
    ids=["the_kernels_shapes", "chunk_16", "heads_of_16", "a_ragged_length", "three_chunks"])
def test_which_chunk_rule_runs_is_read_from_the_shapes_and_the_lowering(T, H, d, chunk):
    """Chunk 64, heads of 128, a length two chunks divide (the kernels take
    two a step): both paths are staged and the platform the program is
    lowered for takes its own (here the CPU: the scan, flag 0;
    ``tests/test_chip_compile.py`` lowers the same call for a described
    TPU). Any other shape: the scan alone."""
    xs, _ct = kda_operands(T, jnp.bfloat16, H=H, d=d)
    rule = lambda *xs: K.kda_chunked(*xs, chunk)
    staged = str(jax.make_jaxpr(rule)(*xs))
    kernel_shapes = (T, d, chunk) == (256, 128, 64)
    assert ("platform_index" in staged) == ("pallas_call" in staged) == kernel_shapes
    assert ("custom_vjp" in staged) == kernel_shapes
    assert "tpu_custom_call" not in jax.jit(rule).lower(*xs).as_text()
    got, fused = jax.jit(rule)(*xs)
    assert float(fused) == 0.0 and got.dtype == jnp.float32 and got.shape == xs[2].shape
    if T % chunk == 0:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(K._kda_scan(*xs, chunk)[0]))


@pytest.mark.parametrize("seq_len, chunk", [(96, 16), (128, 64)])
def test_a_step_counts_its_kda_layers_and_none_of_them_fused_on_the_cpu(seq_len, chunk):
    """Heads of 16: neither the chunk kernels' shapes nor the branch kernels'."""
    metrics = step_metrics(seq_len, chunk)
    assert metrics["count.kda.layers"] == 4.0    # the leading dense layer mixes by KDA too
    assert metrics["count.kda.fused"] == 0.0
    assert metrics["count.kda.branch_fused"] == 0.0


# -- a branch's tail: convolution, SiLU, l2norm ---------------------------------

BRANCHES = {"q": (True, K.KDA_KERNEL_WIDTH ** -0.5), "k": (True, 1.0), "v": (False, 1.0)}
BLOCK = 32                    # tokens of the branch kernels' block in these tests


def branch_operands(T, dtype, d=K.KDA_KERNEL_WIDTH, taps=4, B=2, H=2):
    """A projection [B, T, H x d] in ``dtype``, taps [taps, H x d] and a
    cotangent [B, T, H, d], with an impulse where a block's history is
    carried: in the projection on the last token of the first block (which
    the second block's first three rows read through the taps), in the
    cotangent on the second block's first token (whose gradient reaches
    back into the first block's last three rows)."""
    ks = jax.random.split(jax.random.PRNGKey(T + d), 3)
    y = jax.random.normal(ks[0], (B, T, H * d)).at[:, BLOCK - 1].set(6.0).astype(dtype)
    w = jax.random.normal(ks[1], (taps, H * d)) / np.sqrt(taps)
    ct = jax.random.normal(ks[2], (B, T, H, d)).at[:, BLOCK].set(6.0)
    return y, w, ct


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("branch", sorted(BRANCHES))
def test_the_branch_kernels_match_the_tail_they_replace(branch, dtype, monkeypatch, interpreted):
    """The forward and backward branch kernels (Pallas' interpreter) over
    four blocks against ``jax.vjp`` of the ``jax.numpy`` tail: the value, the
    projection's gradient (in its own dtype) and the taps' (float32). In
    float32 (the template's bfloat16 made float32, as the ``f32`` fixture
    does) to float32's rounding; in bfloat16 to a bfloat16 step."""
    monkeypatch.setattr(K, "KDA_BRANCH_BLOCK", BLOCK)
    if dtype == "float32":
        monkeypatch.setattr(K, "BF16", jnp.float32)
    normed, scale = BRANCHES[branch]
    y, w, ct = branch_operands(4 * BLOCK, jnp.dtype(dtype))
    got = value_and_grads(lambda y, w: K._branch_fused(y, w, normed, scale, True)[0], y, w, ct)
    want = value_and_grads(lambda y, w: K._branch_tail(y, w, 2, normed, scale), y, w, ct)
    tol = 1e-5 if dtype == "float32" else 1e-2
    for name, a, b in zip(("value", "dy", "dw"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert close(a, b, tol if name != "dw" else 1e-4), (name, branch)
    # the impulses crossed the boundary: without the first block's last token
    # the second block's first rows, and the first block's gradient, differ
    cut = value_and_grads(lambda y, w: K._branch_fused(y, w, normed, scale, True)[0],
                          y.at[:, BLOCK - 1].set(0), w, ct)
    assert not close(cut[0][:, BLOCK: BLOCK + 3], got[0][:, BLOCK: BLOCK + 3], 0.1)


@pytest.mark.parametrize("T, d, taps", [(256, 128, 4), (200, 128, 4), (256, 16, 4), (256, 128, 20)],
                         ids=["the_kernels_shapes", "a_ragged_length", "heads_of_16", "twenty_taps"])
def test_which_branch_tail_runs_is_read_from_the_shapes_and_the_lowering(T, d, taps, monkeypatch):
    """Heads of 128, a length the block divides, at most a halo's taps and
    one: both paths are staged under one ``custom_vjp`` and the platform the
    program is lowered for takes its own (here the CPU: the ``jax.numpy``
    tail, flag 0, its value and gradients, each side compiled, those of the
    tail to the last bit; ``tests/test_chip_compile.py`` lowers the same call
    for a described TPU). Any other shape: the tail alone."""
    monkeypatch.setattr(K, "KDA_BRANCH_BLOCK", 64)
    y, w, ct = branch_operands(T, jnp.bfloat16, d=d, taps=taps)
    normed, scale = BRANCHES["q"]
    rule = lambda y, w: K.kda_branch(y, w, 2, normed, scale)
    staged = str(jax.make_jaxpr(rule)(y, w))
    kernel_shapes = (T, d, taps) == (256, 128, 4)
    assert ("platform_index" in staged) == ("pallas_call" in staged) == kernel_shapes
    assert ("custom_vjp" in staged) == kernel_shapes
    assert "tpu_custom_call" not in jax.jit(rule).lower(y, w).as_text()
    got, fused = jax.jit(rule)(y, w)
    assert float(fused) == 0.0 and got.dtype == jnp.bfloat16 and got.shape == (2, T, 2, d)
    got = jax.jit(lambda *a: value_and_grads(lambda y, w: rule(y, w)[0], *a))(y, w, ct)
    want = jax.jit(lambda *a: value_and_grads(
        lambda y, w: K._branch_tail(y, w, 2, normed, scale), *a))(y, w, ct)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
