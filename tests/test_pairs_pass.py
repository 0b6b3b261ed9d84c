"""The held experts' part over the selected (row, expert) pairs alone
(``ops.grouped_experts.pairs_experts``), what a whole-sequence call of
``SparseExpertsLayer`` past an MXU tile of rows takes: against the
dense pass (every row through every held expert), value and every
gradient (rows, combine weights through the router, all the layer's
weights), under a uniform and under a skewed selection bias; no pair
dropped where every row picks only held experts; the 16 shares of a
group add up to the uncut layer; ``pairs_experts`` alone against a
loop over the pairs.

Float32 on the CPU: the passes differ by the order of float32 sums
(5e-6 of values of order 1 read; 5e-5 of gradients of order 60)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers.moe import SparseExpertsLayer
from deeplearning4j_tpu.ops import grouped_experts as ge

D, ROUTED, TOP = 32, 16, 4
# the bias enters the selection only: +5 sends every row to an expert,
# -5 keeps every row off it
# (numpy: a module-level jax array would stay reachable for the whole
# session, which tests/test_program_scopes.py looks for)
BIASES = {
    "uniform": np.zeros(ROUTED, np.float32),
    # held experts 4..7: none picks 4, all pick 5 and 6
    "skewed": np.array([0, 0, 0, 0, -5, 5, 5] + [0] * 9, np.float32),
    # all four picks of every row are the four held experts
    "all_held": np.array([0] * 4 + [5] * 4 + [0] * 8, np.float32),
}


def _layer(held=(4, 4), shared=1):
    layer = SparseExpertsLayer(
        n_in=D, n_routed_experts=ROUTED, held=held, top_k=TOP,
        expert_width=24, n_shared_experts=shared,
        routed_scaling_factor=2.8, router_bias=True)
    params, _ = layer.initialize(jax.random.PRNGKey(0),
                                 InputType.recurrent(D))
    return layer, params


def _loss(layer, params, x, pairs, monkeypatch):
    monkeypatch.setattr(ge, "pairs_pass", lambda n: pairs)

    def f(params, x):
        out, tally = layer.apply_tallied(params, x)
        return jnp.sum(out * jnp.cos(out)), (out, tally["held"])
    return jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(params, x)


@pytest.mark.parametrize("bias", sorted(BIASES))
def test_pairs_pass_is_the_dense_pass_in_value_and_gradient(
        bias, monkeypatch):
    layer, params = _layer()
    params["br"] = jnp.asarray(BIASES[bias])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 100, D))
    (l1, (o1, c1)), g1 = _loss(layer, params, x, True, monkeypatch)
    (l0, (o0, c0)), g0 = _loss(layer, params, x, False, monkeypatch)
    np.testing.assert_array_equal(c1, c0)
    np.testing.assert_allclose(o1, o0, atol=5e-6)
    for mine, want in zip(jax.tree_util.tree_leaves(g1),
                          jax.tree_util.tree_leaves(g0)):
        np.testing.assert_allclose(mine, want, atol=1e-4, rtol=1e-5)
    if bias == "skewed":
        assert c1.tolist()[:3] == [0, 200, 200]
    if bias == "all_held":        # 4 chunks of 200 rows, none dropped
        assert c1.tolist() == [200] * 4


def test_whole_chunks_follow_a_skew_and_drop_no_pair(monkeypatch):
    """512 rows, every pick on a held expert: the 2,048 pairs take
    four chunks of 512 and the result is still the dense pass's."""
    layer, params = _layer()
    params["br"] = jnp.asarray(BIASES["all_held"])
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 512, D))
    (_, (o1, c1)), g1 = _loss(layer, params, x, True, monkeypatch)
    (_, (o0, _)), g0 = _loss(layer, params, x, False, monkeypatch)
    assert c1.tolist() == [512] * 4
    np.testing.assert_allclose(o1, o0, atol=5e-6)
    for mine, want in zip(jax.tree_util.tree_leaves(g1),
                          jax.tree_util.tree_leaves(g0)):
        np.testing.assert_allclose(mine, want, atol=2e-4, rtol=1e-5)


def test_a_call_past_an_mxu_tile_takes_the_pairs_pass(monkeypatch):
    """By the rows, off a serving step only: ``pairs_experts`` is
    reached at 200 rows and not at 100, nor by a serving step."""
    layer, params = _layer()
    calls = []
    real = ge.pairs_experts
    monkeypatch.setattr(ge, "pairs_experts",
                        lambda *a: calls.append(a[0].shape) or real(*a))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 100, D))
    layer.apply_tallied(params, x)
    assert calls == [(200, D)]
    layer.apply_tallied(params, x[:1])
    layer.apply_tallied(params, x, stream=True)
    assert calls == [(200, D)]
    assert ge.pairs_pass(129) and not ge.pairs_pass(128)


def test_the_shares_of_a_group_add_up_to_the_uncut_layer(monkeypatch):
    """16 chips hold one expert each: their parts through the pairs
    pass, the shared expert counted once, give the uncut layer's dense
    pass; their counts give every pick."""
    monkeypatch.setattr(ge, "pairs_pass", lambda n: True)
    whole, params = _layer(held=None)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 60, D))
    total, picks = 0.0, 0
    shared = SparseExpertsLayer(n_in=D, n_routed_experts=ROUTED,
                                held=(0, 1), top_k=TOP, expert_width=24,
                                n_shared_experts=0,
                                routed_scaling_factor=2.8,
                                router_bias=True)
    for e in range(ROUTED):
        shared.held = (e, 1)
        part = {k: (v[e:e + 1] if k in ("Wg", "Wu", "Wd") else v)
                for k, v in params.items() if not k.startswith("Ws")}
        out, tally = shared.apply_tallied(part, x)
        total, picks = total + out, picks + int(tally["held"][0])
    from deeplearning4j_tpu.nn.conf.layers.moe import swiglu
    total = total + swiglu(x, params["Wsg"], params["Wsu"], params["Wsd"])
    monkeypatch.setattr(ge, "pairs_pass", lambda n: False)
    np.testing.assert_allclose(total, whole.apply_tallied(params, x)[0],
                               atol=1e-5)
    assert picks == 60 * TOP


def test_pairs_experts_alone_is_a_loop_over_the_pairs():
    """``pairs_experts`` on its own arguments against one SwiGLU a
    kept pair: value and every gradient, with an empty group, picks
    left out (``local == held``) and two chunks' worth of pairs."""
    n, d, w, held, k = 130, 16, 24, 4, 2
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[0], (n, d))
    wg, wu = (0.3 * jax.random.normal(ks[i], (held, d, w))
              for i in (1, 2))
    wd = 0.3 * jax.random.normal(ks[3], (held, w, d))
    # none on expert 0; ``held`` marks a pick to leave out
    local = jax.random.randint(ks[4], (n, k), 1, held + 1)
    local = local.at[:100].set(jnp.clip(local[:100], 1, held - 1))
    cw = jax.random.uniform(ks[5], (n, k))
    assert n < int(jnp.sum(local < held)) <= 2 * n

    def looped(x, cw, wg, wu, wd):
        keep = (local < held)[..., None]
        e = jnp.minimum(local, held - 1)
        g = jnp.einsum("nd,nkdw->nkw", x, wg[e])
        u = jnp.einsum("nd,nkdw->nkw", x, wu[e])
        y = jnp.einsum("nkw,nkwd->nkd", jax.nn.silu(g) * u, wd[e])
        return jnp.sum(jnp.where(keep, cw[..., None] * y, 0.0), axis=1)

    def run(f):
        return jax.value_and_grad(
            lambda *a: jnp.sum(jnp.sin(f(*a))), argnums=(0, 1, 2, 3, 4))(
                x, cw, wg, wu, wd)

    (l1, g1), (l0, g0) = run(lambda x, cw, *ws: ge.pairs_experts(
        x, local, cw, *ws)), run(looped)
    np.testing.assert_allclose(l1, l0, rtol=1e-5)
    for mine, want in zip(g1, g0):
        np.testing.assert_allclose(mine, want, atol=1e-4, rtol=1e-4)
    assert not np.asarray(g1[2][0]).any()      # expert 0 saw no row


def test_rows_past_the_groups_end_are_never_read(monkeypatch):
    """On a TPU ``ragged_dot`` leaves the rows past its groups' end
    as the buffer was, in the product and in its transpose (read on
    the chip, PR 48: the first gradient came out 5e6 times the
    reference's). Here a stand-in fills those rows with 1e6 in both
    passes: value and every gradient are what they are without it."""
    real = jax.lax.ragged_dot

    def spoil(out, sizes):
        past = jnp.arange(out.shape[0]) >= jnp.sum(sizes)
        return jnp.where(past[:, None], 1e6, out)

    @jax.custom_vjp
    def dirty(lhs, rhs, sizes):
        return spoil(real(lhs, rhs, sizes), sizes)

    def fwd(lhs, rhs, sizes):
        return dirty(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        d_lhs, d_rhs = jax.vjp(lambda a, b: real(a, b, sizes),
                               lhs, rhs)[1](g)
        return spoil(d_lhs, sizes), d_rhs, None

    dirty.defvjp(fwd, bwd)
    layer, params = _layer()
    params["br"] = jnp.asarray(BIASES["skewed"])
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 200, D))
    (l0, (o0, _)), g0 = _loss(layer, params, x, True, monkeypatch)
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        lambda a, b, sizes, **kw: dirty(a, b, sizes))
    (l1, (o1, _)), g1 = _loss(layer, params, x, True, monkeypatch)
    np.testing.assert_allclose(o1, o0, atol=1e-6)
    for mine, want in zip(jax.tree_util.tree_leaves(g1),
                          jax.tree_util.tree_leaves(g0)):
        np.testing.assert_allclose(mine, want, atol=1e-5, rtol=1e-5)
