"""The dropless softmax top-k router and its sorted, grouped dispatch
(``deepspeed_tpu/moe/dropless.py``), held to a hand computation and to
"every expert for every token, then mask" on the CPU, in float32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe import dropless


def test_router_weights_and_choices_by_hand():
    """Softmax over ALL experts in float32, the k largest kept with their
    raw probabilities (not renormalised), ties to the lower index."""
    logits = jnp.asarray([[0.0, np.log(2.0), np.log(4.0), np.log(1.0)],
                          [3.0, 3.0, -1.0, 3.0]], jnp.float32)
    probs, weights, experts = dropless.softmax_topk(logits, 2)
    np.testing.assert_allclose(np.asarray(probs[0]), [1 / 8, 2 / 8, 4 / 8, 1 / 8],
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(experts), [[2, 1], [0, 1]])
    np.testing.assert_allclose(np.asarray(weights[0]), [0.5, 0.25], rtol=1e-6)
    assert float(weights[0].sum()) == pytest.approx(0.75)      # not renormalised
    assert experts.dtype == jnp.int32 and probs.dtype == jnp.float32
    # bf16 logits are lifted before the softmax
    _, w16, _ = dropless.softmax_topk(logits.astype(jnp.bfloat16), 2)
    assert w16.dtype == jnp.float32


def test_counts_leave_out_rows_without_a_request_and_aux_is_one_when_even():
    experts = jnp.asarray([[0, 1], [1, 2], [3, 0]], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(dropless.expert_counts(experts, 4)), [2, 2, 1, 1])
    live = jnp.asarray([True, False, True])
    np.testing.assert_array_equal(
        np.asarray(dropless.expert_counts(experts, 4, live)), [2, 1, 0, 1])
    # even probabilities and even assignments: E * sum(1/E * 1/E) = 1
    probs = jnp.full((4, 4), 0.25)
    even = jnp.asarray([[0, 1], [2, 3], [0, 1], [2, 3]], jnp.int32)
    assert float(dropless.load_balance_loss(probs, even)) == pytest.approx(1.0)
    # all on one pair of experts: 4 * (0.25 * 0.5 + 0.25 * 0.5) = 1 still for
    # even probs, but probabilities that follow the load raise it
    skew = jnp.asarray([[0.5, 0.5, 0.0, 0.0]] * 4)
    pair = jnp.asarray([[0, 1]] * 4, jnp.int32)
    assert float(dropless.load_balance_loss(skew, pair)) == pytest.approx(2.0)


def _bank(key, E, M, I):
    k1, k2 = jax.random.split(key)
    return (jax.random.normal(k1, (E, M, 2 * I)) * 0.3,
            jax.random.normal(k2, (E, I, M)) * 0.3)


def _swiglu(rows, wi, wo, matmul):
    gate, up = jnp.split(matmul(rows, wi), 2, axis=-1)
    return matmul(jax.nn.silu(gate) * up, wo)


def _grouped(x, weights, experts, wi, wo):
    return dropless.dropless_moe(
        x, weights, experts, wi.shape[0],
        lambda rows, matmul, pick: _swiglu(rows, wi, wo, matmul))


def _every_expert_then_mask(x, weights, experts, wi, wo):
    E = wi.shape[0]
    per_expert = jnp.stack([_swiglu(x, wi[e], wo[e], jnp.matmul)
                            for e in range(E)])                     # [E, T, M]
    w = (jax.nn.one_hot(experts, E) * weights[..., None]).sum(axis=1)   # [T, E]
    return jnp.einsum("etm,te->tm", per_expert, w)


def _routing(case, T, E, k, key):
    if case == "random":
        logits = jax.random.normal(key, (T, E))
    elif case == "all_on_one":       # every row picks expert 2 first
        logits = jax.random.normal(key, (T, E)).at[:, 2].set(10.0)
    else:                            # "one_empty": nobody picks expert 0
        logits = jax.random.normal(key, (T, E)).at[:, 0].set(-30.0)
    _, weights, experts = dropless.softmax_topk(logits, k)
    return weights, experts


@pytest.mark.parametrize("case", ["random", "all_on_one", "one_empty"])
@pytest.mark.parametrize("T,E,k", [(5, 4, 1), (16, 8, 2), (33, 8, 8), (24, 16, 5)])
def test_dispatch_equals_every_expert_then_mask(case, T, E, k):
    """No token dropped, whatever the group sizes: all rows on one expert,
    an expert with no row, k = E."""
    with jax.default_matmul_precision("highest"):
        keys = jax.random.split(jax.random.PRNGKey(T * 131 + E * 7 + k), 3)
        M, I = 16, 8
        x = jax.random.normal(keys[0], (T, M))
        wi, wo = _bank(keys[1], E, M, I)
        weights, experts = _routing(case, T, E, k, keys[2])
        counts = np.asarray(dropless.expert_counts(experts, E))
        assert counts.sum() == T * k
        if case == "all_on_one":
            assert counts[2] == T
        if case == "one_empty" and k < E:
            assert counts[0] == 0
        got = _grouped(x, weights, experts, wi, wo)
        want = _every_expert_then_mask(x, weights, experts, wi, wo)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("case", ["random", "one_empty"])
def test_gradients_equal_every_expert_then_mask(case):
    """The sorted path is differentiable in the rows, the router's weights
    and both matrices of the bank (an expert with no row gets zeros)."""
    with jax.default_matmul_precision("highest"):
        T, E, k, M, I = 12, 6, 3, 16, 8
        keys = jax.random.split(jax.random.PRNGKey(5), 4)
        x = jax.random.normal(keys[0], (T, M))
        wi, wo = _bank(keys[1], E, M, I)
        weights, experts = _routing(case, T, E, k, keys[2])
        target = jax.random.normal(keys[3], (T, M))
        loss = lambda fn: lambda x, w, wi, wo: jnp.sum(
            (fn(x, w, experts, wi, wo) - target) ** 2)
        got = jax.grad(loss(_grouped), argnums=(0, 1, 2, 3))(x, weights, wi, wo)
        want = jax.grad(loss(_every_expert_then_mask), argnums=(0, 1, 2, 3))(
            x, weights, wi, wo)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=1e-4, rtol=1e-4)
        if case == "one_empty":
            assert not np.asarray(got[2][0]).any()


def test_bias_is_each_rows_own_experts():
    """``pick`` hands every sorted row the bias of the expert it went to."""
    T, E, k, M = 7, 4, 2, 8
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    x = jax.random.normal(keys[0], (T, M))
    bias = jax.random.normal(keys[1], (E, M))
    weights, experts = _routing("random", T, E, k, keys[2])
    got = dropless.dropless_moe(x, weights, experts, E,
                                lambda rows, matmul, pick: rows + pick(bias))
    want = (weights[..., None] * (x[:, None] + bias[experts])).sum(axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_renormalised_weights_are_the_softmax_over_the_chosen_logits():
    """``norm_topk_prob`` true: the softmax over the k chosen logits alone,
    which is the softmax over all renormalised over the k."""
    logits = jnp.asarray([[0.0, np.log(2.0), np.log(4.0), np.log(1.0)],
                          [3.0, 3.0, -1.0, 3.0]], jnp.float32)
    probs, weights, experts = dropless.softmax_topk(logits, 2, renormalise=True)
    raw_probs, raw, raw_experts = dropless.softmax_topk(logits, 2)
    np.testing.assert_array_equal(np.asarray(experts), np.asarray(raw_experts))
    np.testing.assert_array_equal(np.asarray(probs), np.asarray(raw_probs))
    np.testing.assert_allclose(np.asarray(weights[0]), [4 / 6, 2 / 6], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(weights),
                               np.asarray(raw / raw.sum(-1, keepdims=True)), rtol=1e-6)


@pytest.mark.parametrize("T,k", [(20, 3), (43, 6), (64, 2)])
def test_rows_are_padded_to_the_kernels_whole_tiles(kernels, monkeypatch, T, k):
    """Where the kernel runs, ``T * k`` assignments that are not whole
    128-row tiles are padded up to them (rows in no group, cut off again)
    and take the kernel, not ``ragged_dot``; the result is that of every
    expert then mask.  Whole tiles (64 x 2) are passed as they are."""
    from deepspeed_tpu.ops.pallas import grouped_matmul as gm
    kernels("grouped_matmul")
    rows_seen = []
    real = gm._grouped
    monkeypatch.setattr(gm, "_grouped",
                        lambda a, w, s: rows_seen.append(a.shape[0]) or real(a, w, s))
    with jax.default_matmul_precision("highest"):
        keys = jax.random.split(jax.random.PRNGKey(T + k), 3)
        E, M, I = 8, 128, 128
        x = jax.random.normal(keys[0], (T, M))
        wi, wo = _bank(keys[1], E, M, I)
        weights, experts = _routing("random", T, E, k, keys[2])
        got = _grouped(x, weights, experts, wi, wo)
        want = _every_expert_then_mask(x, weights, experts, wi, wo)
    assert rows_seen == [-(-T * k // 128) * 128] * 2          # gate|up, down
    assert got.shape == (T, M)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=2e-4)
