"""What a recomputed layer keeps (``recompute_layers``,
``MultiLayerNetwork._apply_in_train_step``): its input, and its flash
call's output and row statistics (``ops.attention.FLASH_KEPT``), so
the forward kernel runs once a step and the backward pass computes
everything else of the layer again.

The dispatch asks the backend, so the tests steer it as
``tests/test_flash_band.py`` does: ``_use_pallas`` answers for the
shapes alone and the two kernel entry points are interpreted, float32
at ``highest`` precision. The differentiated step is read as a jaxpr
(``pallas_call`` equations by their kernel's name, in every nested
jaxpr): nothing compiles for the counts."""

import functools
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import spec                    # noqa: E402
from deeplearning4j_tpu import (MultiLayerNetwork,    # noqa: E402
                                NeuralNetConfiguration)
from deeplearning4j_tpu.data.dataset import DataSet   # noqa: E402
from deeplearning4j_tpu.nn.conf import updaters       # noqa: E402
from deeplearning4j_tpu.nn.conf.inputs import InputType   # noqa: E402
from deeplearning4j_tpu.nn.conf.layers import (       # noqa: E402
    DenseLayer, GroupedQueryDecoderBlock, OutputLayer, RnnOutputLayer)
from deeplearning4j_tpu.ops import attention as A     # noqa: E402

T, C, CLASSES = 128, 24, 5
KERNELS = ("_fwd_kernel", "_dq_kernel", "_dkv_kernel")
REMAT = "remat2"        # jax.checkpoint's equation (printed as "checkpoint")


@pytest.fixture
def interpreted(monkeypatch):
    """The flash kernels wherever the shapes admit them, interpreted."""
    monkeypatch.setattr(
        A, "_use_pallas",
        lambda T, bq, bk: bq > 0 and T % bq == 0 and T % bk == 0)
    for name in ("pallas_flash_attention", "pallas_flash_attention_bwd"):
        monkeypatch.setattr(A, name, functools.partial(
            getattr(A, name), interpret=True, precision="highest"))


def _drop_the_policy(monkeypatch):
    """``jax.checkpoint`` without its policy: the layer recomputed
    whole, as before there was a rule about what it keeps."""
    checkpoint = jax.checkpoint
    monkeypatch.setattr(jax, "checkpoint",
                        lambda fn, policy=None: checkpoint(fn))


@pytest.fixture
def bare_checkpoint(monkeypatch):
    _drop_the_policy(monkeypatch)


def _stack(recompute):
    """A full layer and a window layer with rotary positions, each
    with per-head norms and the output gate, as ``trinity_mini_ep16``
    stacks them."""
    b = (NeuralNetConfiguration.builder().set_seed(0)
         .updater(updaters.sgd(0.1)).recompute_layers(recompute).list())
    for window in (None, 64):
        b = b.layer(GroupedQueryDecoderBlock(
            n_heads=4, n_kv_heads=2, qk_head_dim=16, v_head_dim=16,
            rotary_dim=16 if window else 0, window=window, qk_norm=True,
            out_gate=True, intermediate_size=32))
    conf = (b.layer(RnnOutputLayer(n_out=CLASSES, loss="mcxent"))
            .set_input_type(InputType.recurrent(C, T)).build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2, T, C)).astype(np.float32)
    y = np.eye(CLASSES, dtype=np.float32)[rng.integers(0, CLASSES, (2, T))]
    return net, net._batch_tuple(DataSet(x, y))


def _step_jaxpr(net, batch):
    return jax.make_jaxpr(net._train_core)(
        net.params, net.state, net.opt_state, batch, jax.random.PRNGKey(0))


def _equations(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs in its
    equations' parameters (jit, checkpoint, custom_vjp, scan)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.tree_util.tree_leaves(
                list(eqn.params.values()),
                is_leaf=lambda v: hasattr(v, "eqns") or hasattr(v, "jaxpr")):
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns") and eqn.primitive.name != "pallas_call":
                yield from _equations(sub)


def _flash_calls(closed):
    """{kernel: its ``pallas_call`` equations in the step}."""
    found = dict.fromkeys(KERNELS, 0)
    for eqn in _equations(closed.jaxpr):
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["jaxpr"].debug_info.func_name] += 1
    return found


def _loss_and_grads(net, batch):
    def loss(params):
        counts = [] if net.conf.conf.recompute else None
        return net._loss(params, net.state, batch, jax.random.PRNGKey(0),
                         training=True, train_step=counts)[0]
    return jax.value_and_grad(loss)(net.params)


def _recomputing_changes_no_number(make):
    """Loss and every gradient leaf of ``make(True)``, the recomputing
    network, equal those of ``make(False)``."""
    (kept, kept_grads), (plain, plain_grads) = (
        _loss_and_grads(*make(recompute)) for recompute in (True, False))
    assert float(kept) == pytest.approx(float(plain), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(kept_grads),
                    jax.tree_util.tree_leaves(plain_grads), strict=True):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-5)


def test_the_forward_kernel_runs_once_a_layer(interpreted):
    net, batch = _stack(True)
    step = _step_jaxpr(net, batch)
    assert _flash_calls(step) == {
        "_fwd_kernel": 2, "_dq_kernel": 2, "_dkv_kernel": 2}
    # what is kept: the heads' output side by side, as the projection
    # behind it reads it, and a row statistic a head
    named = {(e.params["name"], e.outvars[0].aval.shape)
             for e in _equations(step.jaxpr) if e.primitive.name == "name"}
    assert named == {(A.FLASH_OUT, (2, T, 4 * 16)), (A.FLASH_LSE, (2, 4, T))}


def test_a_layer_recomputed_whole_runs_it_twice(interpreted,
                                                bare_checkpoint):
    """The same helper on the step as it was: the count above is the
    policy's doing."""
    net, batch = _stack(True)
    assert _flash_calls(_step_jaxpr(net, batch)) == {
        "_fwd_kernel": 4, "_dq_kernel": 2, "_dkv_kernel": 2}


def test_without_recomputation_the_step_is_what_it_was(interpreted):
    net, batch = _stack(False)
    step = _step_jaxpr(net, batch)
    assert _flash_calls(step) == {
        "_fwd_kernel": 2, "_dq_kernel": 2, "_dkv_kernel": 2}
    assert not any(e.primitive.name == REMAT
                   for e in _equations(step.jaxpr))


def test_keeping_o_and_lse_changes_no_number(interpreted):
    """Loss and every gradient leaf of the recomputing step equal
    those of the step that recomputes nothing: the kept values ARE the
    first run's (the same float32 arithmetic in an order of XLA's
    choosing: 1e-6 of gradients whose largest entries are of order
    0.1)."""
    _recomputing_changes_no_number(_stack)


@pytest.mark.parametrize("masked", [False, True], ids=["plain", "kv_masked"])
def test_both_dispatches_name_what_their_kernel_wrote(interpreted, masked):
    """``_flash`` and ``_flash_masked`` alike: under a policy over
    ``FLASH_KEPT`` the differentiated call holds one forward kernel,
    under a bare checkpoint two, and the gradients are the same."""
    import jax.numpy as jnp
    q, k, v = (jax.random.normal(key, (2, T, 2, 16))
               for key in jax.random.split(jax.random.PRNGKey(0), 3))
    mask = jnp.ones((2, T)).at[1, 100:].set(0.0) if masked else None

    def loss(q, k, v):
        return jnp.sum(A.flash_attention(q, k, v, causal=True,
                                         kv_mask=mask) ** 2)

    keeps = jax.checkpoint_policies.save_only_these_names(*A.FLASH_KEPT)
    grads = {}
    for policy, forward in ((keeps, 1), (None, 2)):
        grad = jax.grad(jax.checkpoint(loss, policy=policy), (0, 1, 2))
        calls = _flash_calls(jax.make_jaxpr(grad)(q, k, v))
        assert calls == {"_fwd_kernel": forward, "_dq_kernel": 1,
                         "_dkv_kernel": 1}
        grads[forward] = grad(q, k, v)
    for a, b in zip(grads[1], grads[2]):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6)


def test_off_a_tpu_nothing_is_named():
    """The CPU's path has no kernel results to keep: no ``name``
    equation in the step, and the layer is recomputed whole."""
    net, batch = _stack(True)
    step = _step_jaxpr(net, batch)
    names = [e.primitive.name for e in _equations(step.jaxpr)]
    assert "name" not in names and "pallas_call" not in names
    assert names.count(REMAT) == 2


def _dense(recompute):
    conf = (NeuralNetConfiguration.builder().set_seed(0)
            .recompute_layers(recompute).list()
            .layer(DenseLayer(n_out=8)).layer(DenseLayer(n_out=8))
            .layer(OutputLayer(n_out=3))
            .set_input_type(InputType.feed_forward(4)).build())
    net = MultiLayerNetwork(conf).init()
    x = np.ones((2, 4), np.float32)
    y = np.eye(3, dtype=np.float32)[[0, 1]]
    return net, net._batch_tuple(DataSet(x, y))


def _signature(closed):
    """Every equation's primitive with the shapes it reads and
    writes: of a checkpoint equation in the backward pass, what
    was kept for it."""
    return [(e.primitive.name,
             [str(v.aval) for v in e.invars],
             [str(v.aval) for v in e.outvars])
            for e in _equations(closed.jaxpr)]


def test_a_recomputing_network_without_a_flash_call_keeps_what_it_kept(
        interpreted, monkeypatch):
    """Nothing is named in a dense stack, so the policy has nothing to
    keep: the step is, equation for equation, the one a bare
    ``jax.checkpoint`` gives, and its numbers are the plain step's."""
    _recomputing_changes_no_number(_dense)
    with_policy = _step_jaxpr(*_dense(True))
    _drop_the_policy(monkeypatch)
    bare = _step_jaxpr(*_dense(True))
    assert _signature(with_policy) == _signature(bare)
    names = [name for name, _, _ in _signature(with_policy)]
    assert names.count(REMAT) == 2 and "name" not in names


def test_the_counter_of_second_runs_reads_a_step_like_these():
    """``flash_fwd_reruns_pct.train`` (``benchmark/layer_metrics``) on
    the op names such steps leave in a device trace: 50 where every
    layer's forward kernel ran twice, 0 where once, nothing to read in
    a trace without a backward call."""
    reader = spec.load_module("layer_metrics", "flash_fwd_reruns_pct.train")
    fwd, bwd = "%pallas_flash_attention.{}", "%pallas_flash_attention_bwd.{}"

    def step(rerun):
        names = [fwd.format(i) for i in (0, 1)]
        for i in (1, 0):
            names += [fwd.format(10 + i)] * rerun
            names += [bwd.format(2 * i), bwd.format(2 * i + 1)]
        return names

    trace = lambda names: {"trace": {"devices": [{"ops": [
        [n, 10 * i, 5] for i, n in enumerate(names)]}]}}
    # a trace that begins and ends inside a step
    assert reader.read(trace((step(True) * 4)[3:-2])) == pytest.approx(50.0)
    assert reader.read(trace((step(False) * 4)[3:-2])) == 0.0
    assert reader.read(trace([fwd.format(0)] * 3)) is None
    assert reader.read(trace(["%fusion.1"])) is None
    assert reader.read({"trace": None}) is None
