"""The contract of a served block, over every registered one: what a
new decoder block is tested by without a new file (add a row to
``CASES`` and its literals to ``WANT``).

A block is a DSL layer (its JSON gives an equal layer back), a
parameter tree with pinned keys (checkpoints and the benchmark's
builders name them), and a ``PagedLayer``: it declares the kind of
cache it keeps, its paged step at a chunk's width is its ``apply``
over the same rows, ``apply_stream_paged`` is ``apply_stream_paged_aux``
without the counts, and ``PagedSlotSession`` builds from the
declaration the pools and the limits it built by probing for methods
before PR 44 (the literals below are that tree's answers, case by
case). Tiny sizes, eager, no batcher and no compile for a chip."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.models.paged_kv import PagedSlotSession
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    DeltaRuleDecoderBlock, EmbeddingSequenceLayer,
    GroupedQueryDecoderBlock, LatentDecoderBlock, RnnOutputLayer, ShortConvDecoderBlock, ShortcutExpertBlock,
    StateSpaceDecoderBlock, TransformerEncoderLayer, layer_from_dict)
from deeplearning4j_tpu.nn.conf.layers.paged import (PAGES, RING, STATE,
                                                     PagedCache, PagedLayer)

V, D, PAGE, SLOTS, CAP = 17, 32, 8, 3, 32
EXPERTS = dict(n_routed_experts=4, top_k=2, expert_width=16)

CASES = {
    "latent": lambda: LatentDecoderBlock(),
    "latent_experts": lambda: LatentDecoderBlock(held=(0, 4), **EXPERTS),
    "shortcut": lambda: ShortcutExpertBlock(n_zero_experts=2, **EXPERTS),
    "gqa_global": lambda: GroupedQueryDecoderBlock(rotary_dim=4,
                                                   qk_norm=True),
    "gqa_window_experts": lambda: GroupedQueryDecoderBlock(
        window=12, sink=True, residual_multiplier=0.5, **EXPERTS),
    "state_space": lambda: StateSpaceDecoderBlock(
        residual_multiplier=0.22),
    "short_conv": lambda: ShortConvDecoderBlock(),
    "short_conv_experts": lambda: ShortConvDecoderBlock(**EXPERTS),
    "delta_rule": lambda: DeltaRuleDecoderBlock(allow_neg_eigval=True),
    # the Olmo family's: each branch's output normed, q and k normed
    # over the whole projected width
    "delta_rule_post": lambda: DeltaRuleDecoderBlock(
        n_heads=3, key_head_dim=6, value_head_dim=10,
        norm_placement="post"),
    "gqa_post_width": lambda: GroupedQueryDecoderBlock(
        qk_norm="width", norm_placement="post"),
    # Trinity's (afmoe): a norm on both sides of both branches, the
    # attention's output gated; a window layer with a shared expert
    "gqa_both_gated": lambda: GroupedQueryDecoderBlock(
        qk_norm=True, out_gate=True, norm_placement="both"),
    "gqa_window_shared": lambda: GroupedQueryDecoderBlock(
        window=12, rotary_dim=8, qk_norm=True, out_gate=True,
        norm_placement="both", n_shared_experts=1, **EXPERTS),
    "encoder": lambda: TransformerEncoderLayer(n_heads=2, causal=True),
}

_LATENT = "attn/Wkva attn/Wkvb attn/Wo attn/Wqa attn/Wqb attn/kv_gain " \
    "attn/q_gain"
_NORMS = "norm1_gain norm2_gain"
_NORMS4 = _NORMS + " norm1_post_gain norm2_post_gain"
_GATED = "attn/Wgate attn/Wk attn/Wo attn/Wq attn/Wv attn/k_norm_gain " \
    "attn/q_norm_gain"
_ROUTED = "moe/Wd moe/Wg moe/Wr moe/Wu moe/br"
_DELTA = "delta/A_log delta/Wa delta/Wb delta/Wg delta/Wk delta/Wo " \
    "delta/Wq delta/Wv delta/conv_w delta/dt_bias delta/g"
_F32 = "float32"
_LATENT_POOL = {"ckv": ((13, PAGE, 16), _F32), "kr": ((13, PAGE, 128), _F32)}

# case: (parameter keys, declaration, returns counts, chunk_rows_max,
# pool leaves as (shape, dtype)): 12 allocator pages and the scratch
# page; a ring of 3 pages a slot and the scratch page; a row a slot
WANT = {
    "latent": (f"Wd Wg Wu {_LATENT} {_NORMS}",
               PagedCache(PAGES), False, CAP, _LATENT_POOL),
    "latent_experts": (
        f"{_LATENT} moe/Wd moe/Wg moe/Wr moe/Wsd moe/Wsg moe/Wsu moe/Wu "
        f"{_NORMS}", PagedCache(PAGES), True, CAP, _LATENT_POOL),
    "shortcut": (
        " ".join([_LATENT.replace("attn/", "attn0/"),
                  _LATENT.replace("attn/", "attn1/"),
                  "mlp0/Wd mlp0/Wg mlp0/Wu mlp1/Wd mlp1/Wg mlp1/Wu",
                  _ROUTED, "norm_a0_gain norm_a1_gain norm_f0_gain "
                  "norm_f1_gain"]),
        PagedCache(PAGES), True, CAP,
        {f"a{i}/{leaf}": spec for i in (0, 1)
         for leaf, spec in _LATENT_POOL.items()}),
    "gqa_global": (
        "Wd Wg Wu attn/Wk attn/Wo attn/Wq attn/Wv attn/k_norm_gain "
        f"attn/q_norm_gain {_NORMS}", PagedCache(PAGES), False, CAP,
        {"k": ((13, PAGE, 16), _F32), "v": ((13, PAGE, 16), _F32)}),
    "gqa_window_experts": (
        f"attn/Wk attn/Wo attn/Wq attn/Wv attn/sink {_ROUTED} {_NORMS}",
        PagedCache(RING, ring_pages=3), True, PAGE,
        {"k": ((10, PAGE, 16), _F32), "v": ((10, PAGE, 16), _F32)}),
    "state_space": (
        f"Wd Wg Wu {_NORMS} ssm/A_log ssm/D ssm/W_in ssm/W_out "
        "ssm/conv_b ssm/conv_w ssm/dt_bias ssm/g",
        PagedCache(STATE, unrolls_chunk_rows=True), False, CAP,
        {"conv": ((SLOTS, 3, 64), _F32),
         "ssm": ((SLOTS, 4, 8, 16), _F32)}),
    "short_conv": (
        f"Wd Wg Wu conv/W_in conv/W_out conv/conv_w {_NORMS}",
        PagedCache(STATE), False, CAP, {"conv": ((SLOTS, 2, D), _F32)}),
    "short_conv_experts": (
        f"conv/W_in conv/W_out conv/conv_w {_ROUTED} {_NORMS}",
        PagedCache(STATE), True, CAP, {"conv": ((SLOTS, 2, D), _F32)}),
    "delta_rule": (
        f"Wd Wg Wu {_DELTA} {_NORMS}",
        PagedCache(STATE, unrolls_chunk_rows=True), False, CAP,
        {"conv": ((SLOTS, 3, 128), _F32),
         "state": ((SLOTS, 4, 8, 16), _F32)}),
    "delta_rule_post": (
        f"Wd Wg Wu {_DELTA} {_NORMS}",
        PagedCache(STATE, unrolls_chunk_rows=True), False, CAP,
        {"conv": ((SLOTS, 3, 66), _F32),
         "state": ((SLOTS, 3, 6, 10), _F32)}),
    "gqa_post_width": (
        "Wd Wg Wu attn/Wk attn/Wo attn/Wq attn/Wv attn/k_norm_gain "
        f"attn/q_norm_gain {_NORMS}", PagedCache(PAGES), False, CAP,
        {"k": ((13, PAGE, 16), _F32), "v": ((13, PAGE, 16), _F32)}),
    "gqa_both_gated": (
        f"Wd Wg Wu {_GATED} {_NORMS4}", PagedCache(PAGES), False, CAP,
        {"k": ((13, PAGE, 16), _F32), "v": ((13, PAGE, 16), _F32)}),
    "gqa_window_shared": (
        f"{_GATED} {_ROUTED} moe/Wsd moe/Wsg moe/Wsu {_NORMS4}",
        PagedCache(RING, ring_pages=3), True, PAGE,
        {"k": ((10, PAGE, 16), _F32), "v": ((10, PAGE, 16), _F32)}),
    "encoder": (
        "W1 W2 attn/Wk attn/Wo attn/Wq attn/Wv attn/bo b1 b2 ln1_b ln1_g "
        "ln2_b ln2_g", PagedCache(PAGES), False, CAP,
        {"k": ((13, PAGE, D), _F32), "v": ((13, PAGE, D), _F32)}),
}


def _paths(tree):
    return {"/".join(str(k.key) for k in path): leaf for path, leaf
            in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.fixture(scope="module", params=sorted(CASES))
def built(request):
    """(case, block, its parameters, a session over embedding, block,
    head), built once a case."""
    conf = (NeuralNetConfiguration.builder().set_seed(0).list()
            .layer(EmbeddingSequenceLayer(n_in=V, n_out=D))
            .layer(CASES[request.param]())
            .layer(RnnOutputLayer(n_out=V, loss="mcxent"))
            .set_input_type(InputType.recurrent(V, CAP)).build())
    net = MultiLayerNetwork(conf).init()
    return (request.param, net.layers[1], net.params[1],
            PagedSlotSession(net, SLOTS, CAP, PAGE))


def test_json_gives_an_equal_layer(built):
    _, block, _, _ = built
    again = layer_from_dict(json.loads(json.dumps(block.to_dict())))
    assert type(again) is type(block) and again == block
    assert again.to_dict() == block.to_dict()


def test_parameter_keys(built):
    case, _, params, _ = built
    assert sorted(_paths(params)) == sorted(WANT[case][0].split())


def test_cache_declaration(built):
    case, block, _, _ = built
    _, cache, counts, _, _ = WANT[case]
    assert isinstance(block, PagedLayer)
    assert block.paged_cache(PAGE) == cache
    assert bool(block.stream_aux) == counts
    # the CPU gathers; a ring or a state row is not read by table
    assert not block.paged_reads_by_table(PAGE, 1, jnp.float32)


def test_session_builds_the_pools_from_the_declaration(built):
    case, _, _, sess = built
    _, cache, counts, rows_max, pool = WANT[case]
    got = {k: (leaf.shape, str(leaf.dtype))
           for k, leaf in _paths(sess._pools[1]).items()}
    assert got == pool
    assert sess._pools[0] is None and sess._pools[2] is None
    assert sess._ring == [0, cache.ring_pages, 0]
    assert sess._state == [False, cache.kind == STATE, False]
    assert sess._slot_owned == (cache.kind != PAGES)
    assert sess._aux_layers == ([1] if counts else [])
    assert sess.chunk_rows_max == rows_max and sess.chunkable
    assert sess.unrolls_chunk_rows == cache.unrolls_chunk_rows
    assert sess.state_pool_bytes == (
        sum(leaf.nbytes for leaf in _paths(sess._pools[1]).values())
        if cache.kind == STATE else 0)
    assert not sess.runs_grouped_experts(1)          # the CPU


def test_paged_step_is_apply_and_drops_the_counts(built):
    """A chunk of 3 rows from position 0: slot 0 feeds all three,
    slot 1 two, slot 2 none. The rows fed are ``apply``'s over the
    same rows; without the counts the step is the same step."""
    _, block, params, sess = built
    x = jax.random.normal(jax.random.PRNGKey(1), (SLOTS, 3, D))
    n_valid = jnp.array([3, 2, 0], jnp.int32)
    per = sess.pages_per_slot
    args = (params, sess._pools[1],
            jnp.arange(1, SLOTS * per + 1, dtype=jnp.int32).reshape(
                SLOTS, per), jnp.zeros((SLOTS,), jnp.int32), x)
    out, pool = block.apply_stream_paged(*args, n_valid=n_valid)
    want = block.apply(params, {}, x)[0]
    np.testing.assert_allclose(out[0], want[0], atol=1e-5)
    np.testing.assert_allclose(out[1, :2], want[1, :2], atol=1e-5)
    if isinstance(block, TransformerEncoderLayer):   # returns no counts
        return
    same, same_pool, counts = block.apply_stream_paged_aux(
        *args, n_valid=n_valid)
    np.testing.assert_array_equal(out, same)
    jax.tree_util.tree_map(np.testing.assert_array_equal, pool, same_pool)
    assert (counts is not None) == bool(block.stream_aux)
    if isinstance(counts, dict):                     # a zero expert's picks
        assert int(counts["selected"]) == 9 * 2      # every row x top_k
        counts = jnp.append(counts["held"], counts["zero"])
    if block.stream_aux:
        assert int(counts.sum()) == 9 * 2            # no ``active``: all
