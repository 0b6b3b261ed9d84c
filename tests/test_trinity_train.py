"""``trinity_mini_ep16`` at a small size on the CPU: the network that
``benchmark/builders/trinity_dsl.py`` builds from the DSL (through
JSON) against ``benchmark/reference/trinity.py`` on seeded weights,
logits, loss and every leaf's gradient; training through
``MultiLayerNetwork.fit(iterator)`` with the held experts' counts in
the program's counters; recomputation on and off; a step with the
output gate, a post-branch norm or the shared expert left out is not
``correct``; the counts file recounts the parameters leaf by leaf.

Tolerances: both sides are float32 with exact float32 matmuls on the
CPU and differ by the order of sums: 2e-6 of softmax outputs, 1e-6 of
a loss of 4.6, and of each gradient leaf 1e-5 of its largest entry
(5e-7 read)."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run          # noqa: E402
from benchmark.harness import spec, weights     # noqa: E402
from deeplearning4j_tpu.data.dataset import DataSet   # noqa: E402
from deeplearning4j_tpu.data.iterators import (       # noqa: E402
    ListDataSetIterator)
from deeplearning4j_tpu.nn.conf.multi_layer import (  # noqa: E402
    MultiLayerConfiguration)
from deeplearning4j_tpu.observability.registry import REGISTRY  # noqa: E402

CELL, T = "trinity_train_8k", 32


def _tiny(config):
    with open(os.path.join(ROOT, "benchmark", "tests", "tiny",
                           CELL + ".json")) as f:
        config.update(json.load(f)["config"])
    return config


@pytest.fixture(scope="module")
def parts():
    cell = spec.load(CELL)
    config = _tiny(cell.config)
    builder = spec.load_module("builders", config["builder"])
    ref = spec.load_module("reference", config["reference"])
    net = builder.build(config, T).init()
    params = weights.maker(net.params, config["init"])(123)
    ids = np.random.default_rng(0).integers(0, config["vocab_size"],
                                            (2, T + 1))
    x = ids[:, :-1].astype(np.float32)
    y = np.eye(config["vocab_size"], dtype=np.float32)[ids[:, 1:]]
    return config, builder, ref, net, params, x, y


def test_the_network_is_the_dsls_and_round_trips(parts):
    config, _, _, net, params, _, _ = parts
    again = MultiLayerConfiguration.from_json(net.conf.to_json())
    assert again.to_json() == net.conf.to_json()
    assert again.conf.recompute == "layers"
    kinds = [(b.window, b.rotary_dim, b.n_routed_experts)
             for b in net.layers[1:-2]]
    # published layers 1-5: S | S F S S, one dense and four expert
    assert kinds == [(8, 16, 0), (8, 16, 16), (None, 0, 16), (8, 16, 16),
                     (8, 16, 16)]
    block = net.layers[2]
    assert (block.norm_placement, block.out_gate, block.qk_norm,
            block.n_shared_experts, block.held) == ("both", True, True,
                                                    1, (0, 4))
    assert net.layers[0].multiplier == config["hidden_size"] ** 0.5
    assert net.counts_experts


def test_output_is_the_references(parts):
    config, _, ref, net, params, x, _ = parts
    net.params = params
    got = np.asarray(net.output(x))
    want = np.stack([np.asarray(jax.nn.softmax(ref.logits(
        params, x[r].astype(np.int32), config))) for r in range(2)])
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_loss_and_every_leafs_gradient_are_the_references(parts):
    config, _, ref, net, params, x, y = parts
    batch = net._batch_tuple(DataSet(x, y))
    loss, grads = jax.value_and_grad(lambda p: net._loss(
        p, net.state, batch, None, training=True)[0])(params)
    want_loss, want = ref.loss_and_grads(params, ref.batch_of(x, y),
                                         config)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    assert len(flat) == 93     # 1 + 14 + 4 x 19 + 2
    for (path, mine), ref_leaf in zip(flat,
                                      jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(ref_leaf)))
        if jax.tree_util.keystr(path).endswith("['br']"):
            assert scale == 0.0          # the bias selects, no more
        np.testing.assert_allclose(
            mine, ref_leaf, atol=1e-5 * scale,
            err_msg=jax.tree_util.keystr(path))


def _copy(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


def _count(name):
    return REGISTRY.counter(name).value


def test_fit_trains_and_counts_the_held_experts_pairs(parts):
    config, builder, _, _, params, x, y = parts
    net = builder.build(config, T).init()
    net.params = _copy(params)          # fit donates what it is given
    before = {k: _count(k) for k in (
        "train_moe_pairs_total", "train_moe_pairs_busiest_expert_total",
        "train_moe_steps_total")}
    first = net.score(DataSet(x, y))
    for _ in range(2):
        net.fit(ListDataSetIterator([DataSet(x, y)] * 3))
    assert float(net.score_value) < first
    pairs, busiest, steps = (_count(k) - v for k, v in before.items())
    assert steps == 6 and not net._pending_counts
    # 4 expert layers x 64 rows x 4 picks over 16 experts, 4 held
    assert 0.5 < pairs / steps / (4 * 64 * 4 * 4 / 16) < 2.0
    assert 0.25 <= busiest / pairs <= 1.0


def test_the_wrapper_trains_a_network_that_counts_its_experts(parts):
    """``_jit_train_step`` is one program for the executor and for
    ``ParallelWrapper``, and returns the counts behind the loss where
    the network has expert layers: the wrapper's ``fit_batch`` over
    two CPU devices takes the carry and the loss and ends where the
    network's own ``fit`` ends (a step of Adam)."""
    from deeplearning4j_tpu.parallel.mesh import MeshSpec, build_mesh
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
    config, builder, _, _, params, x, y = parts
    ends = []
    for wrapped in (True, False):
        net = builder.build(config, T).init()
        net.params = _copy(params)
        if wrapped:
            mesh = build_mesh(MeshSpec(data=2), jax.devices()[:2])
            ParallelWrapper(net, mesh, prefetch_buffer=0).fit_batch(
                DataSet(x, y))
            assert net.iteration_count == 1
        else:
            net.fit(ListDataSetIterator([DataSet(x, y)]))
        ends.append(net.params)
    # one step of Adam moves every entry by the rate, whichever way
    # its gradient points: an entry whose gradient is nothing but the
    # order of a sum (1 in 8,192 read) may differ by part of a step
    rate = config["assumed"]["learning_rate"]
    for a, b in zip(*(jax.tree_util.tree_leaves(e) for e in ends)):
        gap = np.abs(np.asarray(a) - np.asarray(b))
        assert gap.max() <= rate and gap.mean() <= 1e-3 * rate


def test_recomputation_changes_no_number(parts):
    """Three steps with every layer recomputed in the backward pass
    and three with nothing recomputed end in the same parameters (the
    same arithmetic in another order of XLA's choosing: 1e-6)."""
    config, builder, _, _, params, x, y = parts
    ends = []
    for recompute in ("layers", None):
        net = builder.build(dict(config, recompute=recompute), T).init()
        assert net.conf.conf.recompute == recompute
        net.params = _copy(params)
        net.fit(ListDataSetIterator([DataSet(x, y)] * 3))
        ends.append((float(net.score_value), net.params))
    assert ends[0][0] == pytest.approx(ends[1][0], rel=1e-6)
    for a, b in zip(*(jax.tree_util.tree_leaves(e[1]) for e in ends)):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_recompute_off_leaves_a_dense_networks_step_alone():
    """A network without expert layers and without ``recompute`` goes
    through ``layer.apply`` as before: no counts, four outputs."""
    from deeplearning4j_tpu import (MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (DenseLayer,
                                                   OutputLayer)
    conf = (NeuralNetConfiguration.builder().list()
            .layer(DenseLayer(n_out=8)).layer(OutputLayer(n_out=3))
            .set_input_type(InputType.feed_forward(4)).build())
    assert "recompute" not in conf.to_dict()["global"]
    net = MultiLayerNetwork(conf).init()
    assert not net.counts_experts
    x, y = np.ones((2, 4), np.float32), np.eye(3, dtype=np.float32)[[0, 1]]
    out = net._train_core(net.params, net.state, net.opt_state,
                          net._batch_tuple(DataSet(x, y)),
                          jax.random.PRNGKey(0))
    assert len(out) == 4


def _leave_out(what):
    """A ``break_step`` hook of the training driver: the network's
    blocks lose one mechanism before the first step is traced."""
    def break_step(net):
        for block in net.layers[1:-2]:
            attn, moe = block._ensure_parts()
            if what == "output_gate":
                attn.out_gate = False
            elif what == "post_branch_norm":
                block.norm_placement = "pre"
            elif moe is not None:
                moe.n_shared_experts = 0
    return break_step


@pytest.mark.parametrize("what", ["output_gate", "post_branch_norm",
                                  "shared_expert"])
def test_a_step_without_a_mechanism_is_not_correct(what, monkeypatch,
                                                   capsys):
    real = spec.load

    def load(workload):
        cell = real(workload)
        with open(os.path.join(ROOT, "benchmark", "tests", "tiny",
                               workload + ".json")) as f:
            over = json.load(f)
        cell.config.update(over["config"])
        cell.traffic["inputs"].update(over["traffic"]["inputs"])
        return cell

    monkeypatch.setattr(spec, "load", load)
    args = ["--workload", CELL, "--seed", str(2 ** 31 + 48),
            "--seconds", "1", "--trace", "0"]
    r = bench_run.main(args, find_devices=lambda n: jax.devices()[:n],
                       break_step=_leave_out(what))
    assert r["correct"] is False, capsys.readouterr().out


def test_counts_recount_the_parameters_leaf_by_leaf():
    """At the PUBLISHED widths, as shapes: every leaf of the builder's
    tree against ``counts/trinity.py``, 504,147,712 in all (8.07 GB at
    16 bytes); the needed FLOPs a token against the hand count of
    ISSUE 48 (0.71 G forward)."""
    cell = spec.load(CELL)
    config = cell.config
    builder = spec.load_module("builders", config["builder"])
    count = spec.load_module("counts", config["train_flops"])
    shapes = jax.eval_shape(
        lambda: builder.build(config, 8192).init().params)
    got = {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path): int(np.prod(leaf.shape))
           for path, leaf in
           jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert got == count.param_leaves(config)
    assert sum(got.values()) == 504_147_712
    flops = count.train_flops(config, cell.traffic)
    assert flops / 3 / 8192 == pytest.approx(0.7128e9, rel=1e-3)
    # the band, not the triangle: 1,792 keys a query in the mean
    assert count.visible_pairs(8192, 2048) / 8192 == pytest.approx(
        1792.1, abs=0.1)
    assert count.visible_pairs(8192) == 8192 * 8193 // 2
