"""The grouped pass of ``SparseExpertsLayer.apply_tallied``
(``ops/grouped_experts.py``) held to the dense pass, its oracle: the
kernel in Pallas' interpret mode on the CPU, the layer's dispatch
steered by the test (``grouped``), since the predicate is False off a
TPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import dtypes
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import SparseExpertsLayer
from deeplearning4j_tpu.ops import grouped_experts

D, W = 32, 48


@pytest.fixture
def grouped(monkeypatch):
    """Every serving-step call of the layer takes the grouped pass,
    interpreted. Returns the list of row counts the kernel was given."""
    seen = []
    kernel = grouped_experts.pallas_grouped_experts

    def interpreted(x, *args, **kw):
        seen.append(x.shape[0])
        return kernel(x, *args, interpret=True, **kw)

    monkeypatch.setattr(grouped_experts, "grouped_pass", lambda *a: True)
    monkeypatch.setattr(grouped_experts, "pallas_grouped_experts",
                        interpreted)
    return seen


def _layer(dtype="float32", **kw):
    layer = SparseExpertsLayer(n_in=D, expert_width=W, **kw)
    policy = dtypes.Policy(*(jnp.dtype(dtype),) * 3)
    with dtypes.policy_scope(policy):
        params, _ = layer.initialize(jax.random.PRNGKey(1),
                                     InputType.recurrent(D))
    # weights wide enough that a missing or doubled pair shows
    params = {k: (v * 4 if k[:2] in ("Wg", "Wu", "Wd") else v)
              for k, v in params.items()}
    return layer, params


def _favour(layer, params, experts):
    """A correction bias that makes every row select ``experts``."""
    br = np.zeros((layer.router_width,), np.float32)
    br[list(experts)] = 100.0
    return dict(params, br=jnp.asarray(br, params["br"].dtype))


def _x(b, t, dtype="float32", seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (b, t, D),
                             jnp.dtype(dtype))


def _both(layer, params, x, active):
    dense = layer.apply_tallied(params, x, active)
    return dense, layer.apply_tallied(params, x, active, stream=True)


CASES = {
    # every expert held; the chunk program's ragged rows
    "all_held_top4": (dict(n_routed_experts=16, top_k=4), "rows"),
    "all_held_top8": (dict(n_routed_experts=16, top_k=8), None),
    # a share of a wider router: most pairs are nobody's here
    "held_range_top8": (dict(n_routed_experts=64, held=(16, 8), top_k=8,
                             n_shared_experts=0, router_bias=True),
                        "rows"),
    "held_range_top4_slots": (dict(n_routed_experts=32, held=(4, 8),
                                   top_k=4), "slots"),
    # LongCat's router: softmax, zero-compute experts, the bias
    "zero_experts_bias": (dict(n_routed_experts=32, held=(8, 8), top_k=6,
                               n_zero_experts=16, router_bias=True,
                               scoring_func="softmax", n_shared_experts=0,
                               norm_topk_prob=False,
                               routed_scaling_factor=6.0), "rows"),
}


def _active(kind, b, t):
    """None, a (B,) mask with whole slots inactive, or a (B, T) mask
    with ragged rows and whole slots inactive."""
    if kind is None:
        return None
    if kind == "slots":
        return jnp.arange(b) % 3 != 1
    n_valid = jnp.asarray([(3 * i) % (t + 1) for i in range(b)])
    return jnp.arange(t)[None, :] < n_valid[:, None]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_grouped_pass_is_the_dense_pass(grouped, case, dtype):
    """The output within the reordering of a float32 sum (rounded
    once to the layer's dtype), the tally exactly."""
    kw, mask = CASES[case]
    layer, params = _layer(dtype, **kw)
    b, t = 16, 4
    x = _x(b, t, dtype)
    (yd, td), (yg, tg) = _both(layer, params, x, _active(mask, b, t))
    assert grouped == [b * t]
    for k in td:
        np.testing.assert_array_equal(np.asarray(td[k]), np.asarray(tg[k]))
    assert int(td["held"].sum()) > 0
    yd, yg = np.asarray(yd, np.float32), np.asarray(yg, np.float32)
    scale = np.abs(yd).max()
    assert scale > 0.05
    # float32: the sum's order; bfloat16: at most the last bit of the
    # one rounding both outputs get
    tol = 2e-6 if dtype == "float32" else 2 ** -7
    np.testing.assert_allclose(yg, yd, atol=tol * scale, rtol=tol)


@pytest.mark.parametrize("favoured, hit", [
    # an expert of the share that no row picked, beside picked ones
    ((4, 5, 7, 30), [1, 1, 0, 1]),
    # every held pair on ONE expert: 256 rows, two row tiles of it;
    # the experts before and after it in the share unread
    ((6, 20, 21, 22), [0, 0, 1, 0]),
    # no pair at all for this share
    ((20, 21, 22, 23), [0, 0, 0, 0]),
])
def test_unpicked_experts_and_one_crowded_expert(grouped, favoured, hit):
    layer, params = _layer(n_routed_experts=32, held=(4, 4), top_k=4,
                           router_bias=True, n_shared_experts=0)
    params = _favour(layer, params, favoured)
    x = _x(64, 4)
    active = _active("rows", 64, 4)
    (yd, td), (yg, tg) = _both(layer, params, x, active)
    rows = int(active.sum())
    np.testing.assert_array_equal(np.asarray(tg["held"]),
                                  np.asarray(hit) * rows)
    np.testing.assert_array_equal(np.asarray(td["held"]),
                                  np.asarray(tg["held"]))
    scale = max(float(np.abs(yd).max()), 1.0)
    np.testing.assert_allclose(np.asarray(yg), np.asarray(yd),
                               atol=2e-6 * scale, rtol=2e-6)
    if not any(hit):
        assert not np.asarray(yg).any()
    # the blocks an unpicked expert's grid steps name are the ones the
    # step before left in the buffers
    expert, tile = grouped_experts._blocks(tg["held"], 2)
    last = -1
    for e, h in enumerate(hit):
        last = e if h else last
        want = (e, -1) if h else (
            (last, 2) if last >= 0
            else (hit.index(1) if any(hit) else 0, 0))
        assert (int(expert[e]), int(tile[e])) == want


def test_row_and_width_tiles_of_the_kernel():
    """The kernel alone at tiles smaller than its shapes: four row
    tiles of a group, three width tiles of the experts."""
    n, e, d, w = 32, 3, 16, 24
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(ks[0], (n, d))
    wg, wu = (jax.random.normal(k, (e, d, w)) * 0.3 for k in ks[1:3])
    wd = jax.random.normal(ks[3], (e, w, d)) * 0.3
    sel = jnp.stack([jnp.ones((n,), bool), jnp.zeros((n,), bool),
                     jnp.arange(n) % 5 == 0], axis=1)
    comb = jnp.where(sel, jax.random.uniform(ks[4], (n, e)), 0.0)
    got = grouped_experts.pallas_grouped_experts(
        x, sel, comb, wg, wu, wd, row_tile=8, width_tile=8,
        interpret=True)
    h = jax.nn.silu(jnp.einsum("nd,edw->enw", x, wg)) \
        * jnp.einsum("nd,edw->enw", x, wu)
    want = jnp.einsum("enw,ewd,ne->nd", h, wd, comb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


# does the call take the grouped pass? (top_k, the router's width):
# lfm2_24b_a2b (4, 64), mimo_v25_ep16 (8, 256), axk1_ep16 (8, 192),
# longcat_ep32 (12, 768)
@pytest.mark.parametrize(
    "backend, rows, dtype, d, w, top_k, router, want", [
        ("cpu", 256, "bfloat16", 2048, 1536, 4, 64, False),
        ("tpu", 64, "bfloat16", 2048, 1536, 4, 64, False),
        ("tpu", 128, "bfloat16", 2048, 1536, 4, 64, False),
        ("tpu", 256, "bfloat16", 2048, 1536, 4, 64, True),
        ("tpu", 128, "bfloat16", 4096, 2048, 8, 256, False),
        ("tpu", 512, "bfloat16", 4096, 2048, 8, 256, True),
        ("tpu", 128, "bfloat16", 7168, 2048, 8, 192, False),
        # under an MXU tile of rows, where a tenth of the held experts
        # is expected unpicked: exp(-128 * 12 / 768) = 0.135
        ("tpu", 128, "bfloat16", 6144, 2048, 12, 768, True),
        ("tpu", 32, "bfloat16", 6144, 2048, 12, 768, True),
        ("tpu", 72, "bfloat16", 6144, 2048, 12, 768, False),  # no tiles
        ("tpu", 256, "float32", 2048, 1536, 4, 64, False),
        ("tpu", 256, "bfloat16", 2048, 1000, 4, 64, False),   # no lanes
        ("tpu", 192, "bfloat16", 2048, 1536, 4, 64, False),   # no tiles
        ("tpu", 4096, "bfloat16", 7168, 2048, 8, 192, False),  # memory
    ])
def test_the_predicate_is_of_the_shapes(monkeypatch, backend, rows, dtype,
                                        d, w, top_k, router, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    layer = SparseExpertsLayer(n_in=d, expert_width=w,
                               n_routed_experts=router, held=(0, 8),
                               top_k=top_k)
    assert layer.takes_grouped_pass(rows, jnp.dtype(dtype)) is want
    assert grouped_experts.grouped_pass(rows, top_k, router, d, w,
                                        jnp.dtype(dtype)) is want


@pytest.mark.parametrize("rows, d, w, want", [
    (512, 2048, 1536, True),        # lfm2_24b_a2b: 1,024 passes
    (768, 2048, 1536, True),        # 1,728, the last read flat there
    (896, 2048, 1536, False),       # 2,128, the first read turned
    (512, 4096, 2048, True),        # mimo_v25_ep16: 2,048, read flat
    (640, 4096, 2048, False),       # 2,720, read turned
    (256, 6144, 2048, True),        # longcat_ep32: 1,344
    (384, 6144, 2048, False),       # 2,160, read turned
    (512, 6144, 2048, False),       # 3,072
    (256, 7168, 2048, True),        # axk1_ep16: 1,568
    (512, 7168, 2048, False),       # 3,584 (and past the fast memory)
    (256, 8192, 2048, True),        # 1,792, read flat at 85 MiB asked
    (1024, 1024, 2048, True),       # 1,280
    (1536, 1024, 2048, False),      # 2,304, read turned
])
def test_the_kernel_says_where_its_time_turns(monkeypatch, rows, d, w,
                                              want):
    """``weight_bound`` is of the unrolled body's size, every shape
    here as it was read on the chip (PERF.md section 6, PR 46), and the
    layer's ``carries_rows`` is it where the pass is grouped at all:
    nowhere off a TPU or in float32."""
    bf16 = jnp.dtype("bfloat16")
    assert grouped_experts.weight_bound(rows, d, w) is want
    layer = SparseExpertsLayer(n_in=d, expert_width=w,
                               n_routed_experts=64, held=(0, 8), top_k=4)
    assert not layer.carries_rows(rows, bf16)            # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert layer.carries_rows(rows, bf16) is (
        want and layer.takes_grouped_pass(rows, bf16))
    assert not layer.carries_rows(rows, jnp.dtype("float32"))


def test_apply_keeps_the_dense_pass_under_grad(monkeypatch):
    """``apply``, which ``fit`` differentiates, never reaches the
    kernel (a Mosaic call without a VJP), whatever the predicate
    says; a serving step's call does."""
    def refuse(*a, **kw):
        raise AssertionError("the grouped pass was reached")

    monkeypatch.setattr(grouped_experts, "grouped_pass", lambda *a: True)
    monkeypatch.setattr(grouped_experts, "pallas_grouped_experts", refuse)
    layer, params = _layer(n_routed_experts=8, top_k=2)
    x = _x(4, 64)                   # 256 rows
    loss = lambda p: jnp.sum(layer.apply(p, {}, x)[0] ** 2)
    grads = jax.grad(loss)(params)
    assert float(jnp.abs(grads["Wg"]).max()) > 0
    assert layer.apply_counted(params, x)[0].shape == x.shape
    with pytest.raises(AssertionError, match="grouped pass was reached"):
        layer.apply_tallied(params, x, None, stream=True)
