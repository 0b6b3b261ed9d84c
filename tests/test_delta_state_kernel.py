"""The kernel of ``GatedDeltaMixerLayer.apply_stream_paged``
(``ops/delta_state.py``) held to the ``jax.numpy`` form of the same
method, its oracle: the kernel in Pallas' interpret mode on the CPU,
the layer's dispatch steered by the test (``by_kernel``), since the
predicate is False off a TPU."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers.delta_rule import \
    GatedDeltaMixerLayer
from deeplearning4j_tpu.ops import delta_state

D, DK, SLOTS = 32, 16, 4
# the float32 sum over a head's key values in another order (the
# kernel folds eight partial sums, XLA's reduction its own tree), then
# a chain of at most four rows' substitutions with |beta| < 2 and
# |k . k| <= 1: a few units in the last place of the largest value.
# Read here: 2e-7 of it at worst
TOL = 2e-6


@pytest.fixture
def by_kernel(monkeypatch):
    """``by_kernel()``: from then on every paged step of the layer
    takes the kernel, interpreted. Returns the list of (state shape,
    rows) the kernel is given."""
    def steer():
        seen = []
        kernel = delta_state.pallas_delta_state

        def interpreted(state, k, *args, **kw):
            seen.append((state.shape, k.shape[1]))
            return kernel(state, k, *args, interpret=True, **kw)

        monkeypatch.setattr(delta_state, "delta_state_pass",
                            lambda *a: True)
        monkeypatch.setattr(delta_state, "pallas_delta_state", interpreted)
        return seen
    return steer


def _mixer(heads, dv, neg):
    layer = GatedDeltaMixerLayer(n_in=D, n_heads=heads, key_head_dim=DK,
                                 value_head_dim=dv, allow_neg_eigval=neg)
    params, _ = layer.initialize(jax.random.PRNGKey(1),
                                 InputType.recurrent(D))
    return layer, params


# (heads, dv): one head a lane tile; two of 64 side by side on one
# tile; two of 192 on three, as published
PACKS = {"p1_dv128": (2, 128, 1), "p2_dv64": (4, 64, 2),
         "p2_dv192": (4, 192, 2)}
# what slots 0 and 1 do; slots 2 and 3 feed all their rows mid-stream
MASKS = {
    "a_fresh_slot_over_nan": lambda t: dict(n_valid=(t, t), pos=(0, 3),
                                            nan=0),
    "a_slot_feeds_nothing": lambda t: dict(n_valid=(0, t), pos=(0, 3)),
    "fewer_rows_than_t": lambda t: dict(n_valid=(max(t - 1, 1), 1),
                                        pos=(7, 0)),
}


@pytest.mark.parametrize("mask", sorted(MASKS))
@pytest.mark.parametrize("neg", [False, True], ids=["beta", "two_beta"])
@pytest.mark.parametrize("pack", sorted(PACKS))
@pytest.mark.parametrize("t", [1, 2, 4])
def test_kernel_step_is_the_numpy_step(by_kernel, t, pack, neg, mask):
    """One step of ``apply_stream_paged`` over a pool an earlier
    tenant left non-zero, junk in the rows past ``n_valid``: output,
    state and window by the kernel against the ``jax.numpy`` form,
    within the order of a float32 sum; a fresh slot's NaN dropped, a
    slot that feeds nothing left bit for bit."""
    heads, dv, p = PACKS[pack]
    layer, params = _mixer(heads, dv, neg)
    assert layer._pack == p
    what = MASKS[mask](t)
    rng = np.random.default_rng(t)
    pool = {"state": rng.normal(0, 1, (SLOTS, heads // p, DK, p * dv)
                                ).astype(np.float32),
            "conv": rng.normal(0, 1, (SLOTS, 3, layer.conv_dim)
                               ).astype(np.float32)}
    if "nan" in what:
        pool["state"][what["nan"], :, ::3, ::5] = np.nan
    n_valid = np.array(what["n_valid"] + (t, t), np.int32)
    pos = np.array(what["pos"] + (11, 40), np.int32)
    x = rng.normal(0, 1, (SLOTS, t, D)).astype(np.float32)
    for s in range(SLOTS):
        x[s, n_valid[s]:] = 99.0
    table = np.where(n_valid[:, None] > 0, 1, 0).astype(np.int32)
    args = (params, jax.tree_util.tree_map(jnp.asarray, pool),
            jnp.asarray(table), jnp.asarray(pos), jnp.asarray(x),
            *((jnp.asarray(n_valid),) if t > 1 else ()))
    want, want_pool = jax.jit(layer.apply_stream_paged)(*args)
    seen = by_kernel()
    got, got_pool = jax.jit(layer.apply_stream_paged)(*args)
    assert seen == [(pool["state"].shape, t)]
    want, got = np.asarray(want), np.asarray(got)
    assert np.isfinite(want).all() and np.abs(want).max() > 0.05
    np.testing.assert_allclose(got, want, rtol=TOL,
                               atol=TOL * np.abs(want).max())
    ws, gs = np.asarray(want_pool["state"]), np.asarray(got_pool["state"])
    kept = n_valid == 0
    # a slot that fed nothing keeps its row whatever it holds
    np.testing.assert_array_equal(gs[kept], pool["state"][kept])
    assert np.isfinite(gs[~kept]).all()
    np.testing.assert_allclose(gs[~kept], ws[~kept], rtol=TOL,
                               atol=TOL * np.abs(ws[~kept]).max())
    np.testing.assert_array_equal(np.asarray(got_pool["conv"]),
                                  np.asarray(want_pool["conv"]))


def _rows(t, heads, packs, w, seed=0):
    """Made-up operands of the kernel alone over ``SLOTS`` slots."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda key: (lambda y: y / jnp.linalg.norm(
        y, axis=-1, keepdims=True))(jax.random.normal(
            key, (SLOTS, t, heads, DK)))
    k, q = unit(ks[0]), unit(ks[1])
    return (jax.random.normal(ks[2], (SLOTS, packs, DK, w)), k, q,
            jax.random.normal(ks[3], (SLOTS, t, packs, w)),
            jax.random.uniform(ks[4], (SLOTS, t, heads), minval=0.2),
            jax.random.uniform(ks[5], (SLOTS, t, heads), maxval=2.0),
            jnp.einsum("sjhd,sihd->sjih", k, k),
            jnp.einsum("sjhd,sihd->sjih", k, q),
            jnp.arange(SLOTS) == 1, jnp.arange(SLOTS) != 2)


@pytest.mark.parametrize("block", [1, 2, 3, 6])
def test_blocks_of_head_packs_of_the_kernel(block):
    """The kernel alone over six head-packs at every block of them
    that divides six: the grid's second axis and the loop inside a
    step carve the same pool."""
    args = _rows(2, 12, 6, 128)
    o, new = delta_state.pallas_delta_state(*args, interpret=True)
    assert delta_state._heads_block(6) == 6
    ob, newb = delta_state.pallas_delta_state(
        *args, heads_block=block, interpret=True)
    for got, want in ((ob, o), (newb, new)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=TOL, atol=TOL * 4)
    # slot 2 fed nothing
    np.testing.assert_array_equal(np.asarray(new[2]),
                                  np.asarray(args[0][2]))


def test_the_pool_is_aliased_through_the_kernel():
    """The state is the kernel's operand AND its second result in one
    buffer (the sixth operand, the two scalar-prefetched masks
    counted): donated by the paged step, nothing of the pool's size is
    allocated."""
    args = _rows(2, 4, 2, 128)
    jaxpr = jax.make_jaxpr(functools.partial(
        delta_state.pallas_delta_state.__wrapped__, interpret=True))(*args)
    calls = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    assert tuple(calls[0].params["input_output_aliases"]) == ((5, 1),)
    assert calls[0].invars[5].aval.shape == args[0].shape
    assert calls[0].outvars[1].aval.shape == args[0].shape
    assert calls[0].params["name"] == "pallas_delta_state"


# does the step take the kernel? olmo_hybrid_7b's pool is (64, 15, 96,
# 384) float32
@pytest.mark.parametrize("backend, packs, dk, w, t, dtype, want", [
    ("cpu", 15, 96, 384, 2, "float32", False),
    ("tpu", 15, 96, 384, 2, "float32", True),
    ("tpu", 15, 96, 384, 1, "float32", True),
    ("tpu", 15, 96, 384, 4, "float32", True),
    ("tpu", 15, 96, 384, 5, "float32", False),      # past the bound
    ("tpu", 15, 96, 384, 2, "bfloat16", False),
    ("tpu", 30, 96, 192, 2, "float32", False),      # no whole lane tile
    ("tpu", 15, 100, 384, 2, "float32", False),     # no whole sublanes
    ("tpu", 4, 8, 128, 2, "float32", True),
    # 15 tiles of (512, 1024) twice over, in and out: past the fast
    # memory the kernel may ask for
    ("tpu", 15, 512, 1024, 2, "float32", False),
    ("tpu", 15, 512, 512, 2, "float32", True),
])
def test_the_predicate_is_of_the_shapes(monkeypatch, backend, packs, dk, w,
                                        t, dtype, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert delta_state.delta_state_pass(64, packs, dk, w, t,
                                        jnp.dtype(dtype)) is want


def test_off_a_tpu_the_layer_takes_the_numpy_form(by_kernel, monkeypatch):
    """Without the test's steering the predicate says no here and the
    kernel is never traced."""
    layer, params = _mixer(4, 64, True)
    pool = layer.zero_pool(SLOTS, 4, jnp.float32)
    step = lambda: layer.apply_stream_paged(
        params, pool, jnp.ones((SLOTS, 1), jnp.int32),
        jnp.zeros((SLOTS,), jnp.int32), jnp.ones((SLOTS, 2, D)),
        jnp.full((SLOTS,), 2))
    traced = []
    monkeypatch.setattr(
        delta_state, "pallas_delta_state",
        lambda *a, **kw: traced.append(a) or 1 / 0)
    assert not delta_state.delta_state_pass(SLOTS, 2, DK, 128, 2,
                                            jnp.float32)
    step()
    assert not traced
    monkeypatch.undo()
    seen = by_kernel()
    step()
    assert seen == [((SLOTS, 2, DK, 128), 2)]


def test_the_roofline_reader_over_a_made_up_trace():
    """benchmark/layer_metrics/delta_state_roofline_pct.serve.py: the
    bytes the kernel's calls need (every slot's state read once and
    written once a call) at the chip's bandwidth over their device
    time; nothing where no such kernel is in the trace (the parent of
    PR 47, a cell without the layer) or no trace was taken; a count
    past the peak raises."""
    import json
    import os
    import types
    from benchmark.harness import counts, spec
    reader = spec.load_module("layer_metrics",
                              "delta_state_roofline_pct.serve")
    cell = spec.load("olmo_hybrid_serve_reason")
    need = 2 * 64 * 4 * 30 * 96 * 192          # bytes a call
    assert need == 2 * cell.traffic["server"]["slots"] * (
        2_280_960 - 2 * 3 * 11520)
    at_peak_ns = need / 819e9 * 1e9            # 345.7 us
    ops = lambda ns: [["%fusion.1", 0, 1000]] + [
        [f"%pallas_delta_state.{i}", 2000 * (i + 1) + int(i * ns),
         int(ns)] for i in range(12)]
    obs = lambda ns, name="pallas_delta_state": {
        "cell": cell, "device": types.SimpleNamespace(
            device_kind="TPU v5 lite"),
        "trace": {"devices": [{"ops": [
            [n.replace("pallas_delta_state", name), s, d]
            for n, s, d in ops(ns)]}]}}
    assert reader.read(obs(at_peak_ns / 0.8)) == pytest.approx(80.0, 1e-4)
    assert reader.read(obs(at_peak_ns / 0.8, "multiply_fusion")) is None
    assert reader.read({"cell": cell}) is None
    with pytest.raises(counts.CountError):
        reader.read(obs(at_peak_ns / 1.2))
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert next(m for m in bench["per_layer"]
                if m["name"] == "delta_state_roofline_pct.serve") == {
        "name": "delta_state_roofline_pct.serve", "unit": "%",
        "better": "higher", "source": "device_trace", "layer": "Kernels",
        "moves": "serve_tokens_per_s",
        "workloads": ["olmo_hybrid_serve_reason"]}


def test_the_measurement_tool_runs_on_a_chip_alone():
    """tools/measure_delta_state.py prints times under a chip's names
    and holds the kernel's float32 reads to the ``jax.numpy`` form's
    there: off a TPU it exits 2 before it builds anything, with no
    line on its output."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    done = subprocess.run(
        [sys.executable, os.path.join(root, "tools",
                                      "measure_delta_state.py")],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 2, done.stderr[-2000:]
    assert done.stdout == "" and "chiprun" in done.stderr
