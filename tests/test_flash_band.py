"""The flash kernels over a band: a sliding window and grouped heads
(``ops/attention.py``, ``_Band``), interpreted on the CPU, against the
exact einsum (``_exact_band``: the mathematics of
``GroupedQueryAttentionLayer._attend``), forward and VJP.

Windows smaller than a tile, a tile exactly, larger than one and no
multiple of it, and the whole sequence; 8 query heads on one key head
(the ``trinity_mini_ep16`` ratio) and equal head counts; square and
oblong tiles. Float32 at ``highest`` precision: the two differ by the
order of float32 sums alone, so 2e-5 of values of order 1 holds (the
largest read 3e-6).

The ``columns_*`` cases have a head of 128, one lane tile: the kernels
read and write such query-side operands where the projections leave
them (o and do as column blocks of ``(B, T, H * D)``, q and dq as
blocks of the same array with T last, turned in the kernel); the key
side, and every operand at a head of 8, goes through the head-major
``(B * N, T, D)`` form."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import GroupedQueryAttentionLayer
from deeplearning4j_tpu.ops import attention as A

T, D = 64, 8
TOL = dict(atol=2e-5, rtol=2e-5)
# (window, query heads, key heads, block_q, block_k[, head, batch])
CASES = {
    "under_a_tile": (5, 8, 1, 16, 16),
    "one_tile": (16, 8, 1, 16, 16),
    "over_a_tile_ragged": (40, 8, 1, 16, 16),
    "tile_and_a_half": (24, 8, 1, 16, 16),
    "whole_sequence": (64, 8, 1, 16, 16),
    "no_window_grouped": (None, 8, 1, 16, 16),
    "two_key_heads": (20, 4, 2, 16, 16),
    "equal_heads_window": (20, 2, 2, 16, 16),
    "wide_key_tiles": (20, 8, 1, 16, 32),
    "wide_query_tiles": (20, 8, 1, 32, 16),
    "columns_window": (20, 8, 1, 16, 16, 128, 1),
    "columns_full_grouped": (None, 8, 1, 16, 16, 128, 1),
    "columns_two_key_heads": (20, 4, 2, 16, 16, 128, 1),
    "columns_equal_heads": (20, 2, 2, 16, 16, 128, 1),
    "columns_two_sequences": (20, 4, 2, 16, 16, 128, 2),
}


def _operands(H, K, D=D, B=2):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    shape = lambda n: (B, T, n, D)
    return (jax.random.normal(ks[0], shape(H)),
            jax.random.normal(ks[1], shape(K)),
            jax.random.normal(ks[2], shape(K)),
            jax.random.normal(ks[3], shape(H)))


@pytest.mark.parametrize("case", sorted(CASES))
def test_forward_is_the_einsum(case):
    window, H, K, bq, bk, *shape = CASES[case]
    q, k, v, _ = _operands(H, K, *shape)
    got = A.pallas_flash_attention(
        q, k, v, causal=True, block_q=bq, block_k=bk, interpret=True,
        precision="highest", window=window)
    np.testing.assert_allclose(got, A._exact_band(q, k, v, window), **TOL)


@pytest.mark.parametrize("case", sorted(CASES))
def test_backward_is_the_einsums_vjp(case):
    window, H, K, bq, bk, *shape = CASES[case]
    q, k, v, g = _operands(H, K, *shape)
    kw = dict(causal=True, block_q=bq, block_k=bk, interpret=True,
              precision="highest", window=window)
    o, lse = A.pallas_flash_attention(q, k, v, return_lse=True, **kw)
    got = A.pallas_flash_attention_bwd(q, k, v, o, lse, g, **kw)
    _, vjp = jax.vjp(lambda a, b, c: A._exact_band(a, b, c, window),
                     q, k, v)
    for mine, want in zip(got, vjp(g)):
        assert mine.shape == want.shape      # dk, dv a KEY head
        np.testing.assert_allclose(mine, want, **TOL)


def _both_ways(q, k, v, g, **kw):
    """One call forward and backward: (o, lse, dq, dk, dv) and the
    text of the two traced programs."""
    fwd = lambda q, k, v: A.pallas_flash_attention(
        q, k, v, return_lse=True, **kw)
    bwd = lambda q, k, v, o, lse, g: A.pallas_flash_attention_bwd(
        q, k, v, o, lse, g, **kw)
    o, lse = fwd(q, k, v)
    text = "\n".join(str(jax.make_jaxpr(f)(*a)) for f, a in (
        (fwd, (q, k, v)), (bwd, (q, k, v, o, lse, g))))
    return (o, lse, *bwd(q, k, v, o, lse, g)), text


def test_the_operand_form_follows_the_head_size(monkeypatch):
    """At a head of 128 the traced programs hold no head-major copy of
    a query-side operand: o and do are the projections' ``(B, T, H *
    D)`` arrays, q and dq the same with T last (the kernels turn the
    tile), and the key side alone is transposed to ``(B * K, T, D)``;
    at a head of 8 every operand is. The forms give a tile the same
    numbers: at a head of 128 the call equals the same call through
    the head-major operands (the test's steering of the shape's
    predicate: the program has no switch) to the order of the
    interpreter's float32 sums: the CPU contracts a turned tile in
    another order, the largest difference read 3.1e-6."""
    kw = dict(causal=True, block_q=16, block_k=16, interpret=True,
              precision="highest", window=20)
    _, narrow = _both_ways(*_operands(4, 2), **kw)
    assert "f32[8,64,8]" in narrow and "f32[4,64,8]" in narrow
    assert "f32[2,64,32]" not in narrow and "f32[2,32,64]" not in narrow
    wide = _operands(4, 2, 128, 2)
    assert A._heads_are_columns(wide[0].shape)
    columns, text = _both_ways(*wide, **kw)
    assert "f32[2,64,512]" in text and "f32[2,512,64]" in text
    assert "f32[4,64,128]" in text and "f32[8,64,128]" not in text
    monkeypatch.setattr(A, "_heads_are_columns", lambda shape: False)
    jax.clear_caches()          # the jitted kernels traced the other form
    heads, text = _both_ways(*wide, **kw)
    jax.clear_caches()
    assert "f32[8,64,128]" in text and "f32[2,512,64]" not in text
    for mine, old in zip(columns, heads):
        np.testing.assert_allclose(mine, old, **TOL)


def test_a_band_spans_the_windows_tiles_and_no_more():
    """At the cell's sizes: 16 tiles of 512 a side; a row of tiles
    sees 5 key tiles under a window of 2,048 and a column 5 query
    tiles, all 16 without a window; tiles outside are not named."""
    band = A._Band(2048, 512, 512, 8192)
    assert (band.k_steps, band.q_steps) == (5, 5)
    assert [band.first_k(i) for i in (0, 3, 4, 15)] == [0, 0, 0, 11]
    assert [band.last_q(j) for j in (0, 11, 15)] == [4, 15, 15]
    whole = A._Band(None, 512, 512, 8192)
    assert (whole.k_steps, whole.q_steps) == (16, 16)
    # no window and equal heads: the kernels as they were
    assert A._band_of(True, None, 512, 512, 8192, 1) is None
    with pytest.raises(ValueError, match="causal"):
        A._band_of(False, 2048, 512, 512, 8192, 1)


def test_dispatch_off_a_tpu_takes_the_exact_band_and_differentiates():
    q, k, v, g = _operands(8, 1)
    f = lambda q, k, v: A.flash_attention(q, k, v, causal=True, window=20)
    out, vjp = jax.vjp(f, q, k, v)
    want, want_vjp = jax.vjp(lambda a, b, c: A._exact_band(a, b, c, 20),
                             q, k, v)
    np.testing.assert_allclose(out, want, **TOL)
    for mine, ref in zip(vjp(g), want_vjp(g)):
        np.testing.assert_allclose(mine, ref, **TOL)
    with pytest.raises(ValueError, match="kv_mask"):
        A.flash_attention(q, k, v, causal=True, window=20,
                          kv_mask=jnp.ones((2, T)))


@pytest.mark.parametrize("window", [None, 24])
def test_the_layer_takes_the_kernels_where_its_shapes_admit(
        window, monkeypatch):
    """``apply`` through the interpreted kernels (the test's steering:
    the dispatch asks the backend) equals ``apply`` through
    ``_attend``, output gate and score scale included; a sink or two
    head sizes keep ``_attend``."""
    import functools
    layer = GroupedQueryAttentionLayer(
        n_heads=8, n_kv_heads=2, qk_head_dim=16, v_head_dim=16,
        rotary_dim=16 if window else 0, window=window, qk_norm=True,
        out_gate=True, softmax_scale=0.3)
    params, _ = layer.initialize(jax.random.PRNGKey(0),
                                 InputType.recurrent(32))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 256, 32))
    want = layer.apply(params, {}, x)[0]
    assert not layer._takes_flash(256)               # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(A, "pallas_flash_attention", functools.partial(
        A.pallas_flash_attention, interpret=True, precision="highest"))
    assert layer._takes_flash(256) and not layer._takes_flash(200)
    np.testing.assert_allclose(layer.apply(params, {}, x)[0], want,
                               atol=2e-5, rtol=2e-4)
    assert not GroupedQueryAttentionLayer(sink=True)._takes_flash(256)
    assert not GroupedQueryAttentionLayer(
        qk_head_dim=24, v_head_dim=16)._takes_flash(256)
