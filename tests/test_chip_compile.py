"""Compile-only checks against a DESCRIBED TPU v5e (no chip attached).

The TPU compiler is installed in the CPU sandbox and compiles for a
topology that is described, not attached — so what Mosaic or the SPMD
partitioner would refuse on the chip is refused here, at no chip time
(the on-chip-measurement guide, section 2, rehearsal 3). Nothing runs:
these cases say nothing about results or times.

Covered: the flash kernels of the main path at real widths, forward
and backward, plain and key-masked (a head of 64 through transposed
operands, a head of 128 read where the projections leave it, with no
relayout around the calls: ``_relayouts_in``); the same kernels under a
four-device ``data`` mesh both ways a user reaches them —
``fit(mesh_spec=)``'s GSPMD step (a Mosaic call has no partitioning
rule: ``flash_attention`` must open its own shard_map island) and
``ParallelWrapper``'s manual shard_map step (a checked shard_map
needs the kernel outputs' varying axes declared) — and the ring island
of a dp x tp x sp mesh. Dispatch asks ``jax.default_backend()``, which
is 'cpu' here, so the cases that go through ``flash_attention`` steer
it in the test.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import (Mesh, NamedSharding,  # noqa: E402
                          PartitionSpec as P, SingleDeviceSharding)

from deeplearning4j_tpu.ops import attention as A  # noqa: E402
from deeplearning4j_tpu.parallel.mesh import AXES  # noqa: E402
from deeplearning4j_tpu.parallel.seq_context import (  # noqa: E402
    gspmd_mesh, sequence_parallel_gspmd)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no libtpu / unknown topology here
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without the chip (the next one
    warns and recompiles) — keep the cache out of these compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def as_tpu(monkeypatch):
    """Kernel dispatch decides on jax.default_backend(); the described
    device does not change it, so the test does."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _mesh(topo, **sizes):
    shape = tuple(sizes.get(a, 1) for a in AXES)
    return Mesh(np.array(topo.devices).reshape(shape), AXES)


def _kernels_in(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


_ITEM = {"f32": 4, "bf16": 2}


def _relayouts_in(compiled, at_least=1 << 20):
    """The ``copy`` / ``transpose`` / ``reshape`` instructions of the
    compiled module (fused ones too; a reshape that costs nothing is a
    ``bitcast`` by now) over float arrays of ``at_least`` bytes, as
    ``(op, bytes)``: what moves operands between the layout a producer
    leaves and the one a kernel asks for."""
    found = []
    for dtype, dims, op in re.findall(
            r" = (f32|bf16)\[([\d,]+)\]\S* (copy|transpose|reshape)\(",
            compiled.as_text()):
        size = _ITEM[dtype] * int(np.prod([int(d) for d in dims.split(",")]))
        if size >= at_least:
            found.append((op, size))
    return found


# ---- single chip: the kernels themselves ---------------------------------

@pytest.mark.parametrize("shape", [(8, 1024, 16, 64), (2, 1024, 8, 128)],
                         ids=["lm_b8_h16_d64", "b2_h8_d128"])
@pytest.mark.parametrize("masked", [False, True],
                         ids=["plain", "kv_masked"])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_kernel_compiles_for_v5e(topo, shape, masked, direction):
    B, T, H, D = shape
    one = SingleDeviceSharding(topo.devices[0])
    blk = A._auto_block(T, D)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)
    mask = (jax.ShapeDtypeStruct((B, T), jnp.float32, sharding=one)
            if masked else None)
    kw = dict(causal=True, block_q=blk, block_k=blk)
    if direction == "fwd":
        def fn(q, k, v, m):
            return A.pallas_flash_attention(q, k, v, m,
                                            return_lse=True, **kw)
        args = (x, x, x, mask)
        want = 1
    else:
        lse = jax.ShapeDtypeStruct((B, H, T), jnp.float32, sharding=one)

        def fn(q, k, v, o, l, do, m):
            return A.pallas_flash_attention_bwd(q, k, v, o, l, do, m,
                                                **kw)
        args = (x, x, x, x, lse, x, mask)
        want = 2                  # dq, fused dk/dv
    compiled = jax.jit(fn).lower(*args).compile()
    assert _kernels_in(compiled) == want
    if D == 64:     # half a lane tile: the transposed operands, as before
        assert _relayouts_in(compiled)


def test_small_auto_block_compiles_for_v5e(topo):
    """T = 40 takes the 8-wide auto tile: the smallest block the
    dispatcher can pick must still satisfy Mosaic's tiling."""
    one = SingleDeviceSharding(topo.devices[0])
    blk = A._auto_block(40, 64)
    assert blk == 8
    x = jax.ShapeDtypeStruct((2, 40, 4, 64), jnp.float32, sharding=one)

    def loss(q, k, v):
        o, lse = A.pallas_flash_attention(q, k, v, causal=True,
                                          block_q=blk, block_k=blk,
                                          return_lse=True)
        return A.pallas_flash_attention_bwd(q, k, v, o, lse, o,
                                            causal=True, block_q=blk,
                                            block_k=blk)
    assert _kernels_in(jax.jit(loss).lower(x, x, x).compile()) == 3


@pytest.mark.parametrize("window", [2048, None], ids=["window", "full"])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_band_compiles_for_v5e(topo, window, direction):
    """The band kernels at ``trinity_train_8k``'s own shapes: one
    sequence of 8,192, 32 query heads on 4 key heads of 128, float32,
    tiles of 512; a window layer's grid walks 5 key tiles a row of
    tiles, a full layer's all 16 (``tests/test_flash_band.py`` holds
    the mathematics, interpreted). The operands come and go as the
    layer's XLA side holds them: o and do ``(1, T, H * 128)``, what a
    matmul reads and writes; q and dq the same array with T last, the
    layout the per-head norm and the rotation run in; all split into
    heads by a reshape. A head of 128 is a block of those arrays, so
    nothing of 134 MB (a query-sized operand) is copied, transposed
    or re-laid around the calls; the key side (17 MB an operand) is,
    into the head-major slabs the kernels read from fast memory."""
    one = SingleDeviceSharding(topo.devices[0])
    T, H, K, D = 8192, 32, 4, 128
    blk = A._auto_block(T, D)
    assert blk == 512
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                              sharding=one)
    q, o, k = sds(1, H * D, T), sds(1, T, H * D), sds(1, T, K * D)
    kw = dict(causal=True, block_q=blk, block_k=blk, window=window)
    heads = lambda x: x.reshape(1, T, -1, D)
    wide = lambda x: x.reshape(1, T, -1)
    turn = lambda x: x.transpose(0, 2, 1)
    if direction == "fwd":
        def fn(q, k, v):
            o, lse = A.pallas_flash_attention(
                heads(turn(q)), heads(k), heads(v), return_lse=True, **kw)
            return wide(o), lse
        args, want = (q, k, k), 1
    else:
        lse = sds(1, H, T)

        def fn(q, k, v, o, l, do):
            dq, dk, dv = A.pallas_flash_attention_bwd(
                heads(turn(q)), heads(k), heads(v), heads(o), l, heads(do),
                **kw)
            return turn(wide(dq)), wide(dk), wide(dv)
        args, want = (q, k, k, o, lse, o), 2
    compiled = jax.jit(fn).lower(*args).compile()
    assert _kernels_in(compiled) == want
    assert not _relayouts_in(compiled, at_least=T * H * D * 4)
    out = compiled.output_shardings        # dk, dv a KEY head
    assert len(jax.tree_util.tree_leaves(out)) == (2 if want == 1 else 3)


def test_a_recomputed_layer_keeps_its_flash_calls_results(topo, as_tpu):
    """A recomputing stack's compiled train step at
    ``trinity_train_8k``'s attention shapes (a window layer and a full
    one, 32 query heads on 4 key heads of 128 over 8,192 positions,
    float32, per-head norms, rotation, output gate) holds ONE forward
    flash call a layer and two backward ones: ``o`` and ``lse`` are
    kept (``ops.attention.FLASH_KEPT``), everything else of the layer
    is computed again. Recomputed whole, the step held two forward
    calls a layer."""
    from deeplearning4j_tpu import (MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (
        GroupedQueryDecoderBlock, RnnOutputLayer)
    T, C, layers = 8192, 2048, 2
    b = NeuralNetConfiguration.builder().recompute_layers().list()
    for window in (2048, None):
        b = b.layer(GroupedQueryDecoderBlock(
            n_heads=32, n_kv_heads=4, qk_head_dim=128, v_head_dim=128,
            rotary_dim=128 if window else 0, window=window, qk_norm=True,
            out_gate=True, norm_placement="both", intermediate_size=256))
    conf = (b.layer(RnnOutputLayer(n_out=128, loss="mcxent"))
            .set_input_type(InputType.recurrent(C, T)).build())
    held = {}

    def shapes():
        held["net"] = net = MultiLayerNetwork(conf).init()
        return net.params, net.state, net.opt_state

    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one)
    carry = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype),
                                   jax.eval_shape(shapes))
    batch = (sds((1, T, C)), sds((1, T, 128)), None, None)
    text = jax.jit(held["net"]._train_step_fn(),
                   donate_argnums=(0, 1, 2)).lower(
        *carry, batch, sds((2,), jnp.uint32), sds((), jnp.int32)
    ).compile().as_text()
    calls = re.findall(r"%(pallas_flash_attention[\w.]*) = ", text)
    forward = [name for name in calls if "_bwd" not in name]
    assert len(forward) == layers
    assert len(calls) - len(forward) == 2 * layers


def test_pairs_pass_compiles_for_v5e(topo, as_tpu):
    """The held experts' pairs pass at ``trinity_mini_ep16``'s widths
    (8,192 rows of 2048 through 8 held experts of 1024, float32
    parameters), value and gradient, compiles for the chip as
    ragged products, without a dense ``(held, rows, width)`` one."""
    from deeplearning4j_tpu.ops import grouped_experts as ge
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one)
    n, d, w, held, k = 8192, 2048, 1024, 8, 8
    assert ge.pairs_pass(n)

    def loss(x, local, cw, wg, wu, wd):
        return jnp.sum(ge.pairs_experts(x, local, cw, wg, wu, wd) ** 2)

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 2, 3, 4, 5))).lower(
        sds((n, d)), sds((n, k), jnp.int32), sds((n, k)),
        sds((held, d, w)), sds((held, d, w)), sds((held, w, d))
    ).compile().as_text()
    assert "ragged-dot" in text        # the chip's own grouped product
    assert f"f32[{held},{n},{w}]" not in text


@pytest.mark.parametrize("heads, head_dim, slots, t, dtype, by_table", [
    (16, 64, 8, 1, jnp.float32, True), (16, 64, 8, 16, jnp.float32, True),
    (16, 64, 64, 2, jnp.float32, True), (16, 64, 8, 16, jnp.bfloat16, True),
    (32, 128, 8, 1, jnp.float32, True), (32, 128, 8, 4, jnp.float32, False),
    (64, 128, 8, 1, jnp.float32, False), (64, 128, 8, 1, jnp.bfloat16, False)],
    ids=["decode_8x1", "chunk_8x16", "chunk_64x2", "chunk_8x16_bf16",
         "wide_32x128_decode", "wide_32x128_chunk_gathers",
         "wide_64x128_gathers", "wide_64x128_bf16_gathers"])
def test_paged_attention_step_compiles_for_v5e(topo, as_tpu, heads,
                                               head_dim, slots, t, dtype,
                                               by_table):
    """``SelfAttentionLayer.apply_stream_paged`` over the serving
    cell's pool (64 pages of 16 a slot). At gpt2-medium's widths, both
    step programs: the dispatch takes the by-table kernel, and Mosaic
    takes its page DMAs, its block-diagonal operand, its tiling and
    the float32 passes of its single-row dots. At wider rows the
    kernel's buffers outgrow the fast memory Mosaic gives it (64 x 128
    in float32 is refused at compile time): whatever the predicate
    admits compiles, and what it does not keeps the gather."""
    from deeplearning4j_tpu.nn.conf.layers import SelfAttentionLayer
    width = heads * head_dim
    layer = SelfAttentionLayer(n_in=width, n_out=width, n_heads=heads,
                               causal=True)
    assert layer.paged_reads_by_table(16, t, dtype) == by_table
    assert not layer.paged_reads_by_table(4, t, dtype)   # no whole tile
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    w = sds((width, width), dtype)
    params = {"Wq": w, "Wk": w, "Wv": w, "Wo": w,
              "bo": sds((width,), dtype)}
    pool = {name: sds((slots * 64 + 1, 16, width), dtype)
            for name in ("k", "v")}
    ints = lambda *shape: sds(shape, jnp.int32)
    compiled = jax.jit(layer.apply_stream_paged, donate_argnums=(1,)).lower(
        params, pool, ints(slots, 64), ints(slots),
        sds((slots, t, width), dtype), ints(slots)).compile()
    assert _kernels_in(compiled) == int(by_table)


@pytest.mark.parametrize("kind, t, kernels", [
    ("global", 2, 1), ("global", 1, 1), ("window", 2, 0), ("window", 1, 0)],
    ids=["global_chunk_64x2", "global_decode_64x1", "window_chunk_64x2",
         "window_decode_64x1"])
def test_grouped_query_paged_step_compiles_for_v5e(topo, as_tpu, kind, t,
                                                   kernels):
    """``GroupedQueryAttentionLayer.apply_stream_paged`` at the shapes
    of the benchmark's ``mimo_serve_mixedlen`` cell in bfloat16, both
    step programs. A global layer (64 heads of 192 over 4, values of
    128; 64 slots x 128 pages of 16): the grouped by-table kernel is
    in the compiled step, so Mosaic takes a key row of 6 lane tiles
    beside a value row of 4, the block-diagonal operand's columns at
    multiples of 192, the 32 KB table in scalar memory and the
    (slots, t * 64, 128) output. A window layer (8 key heads, window
    128, a sink; 64 rings of 9 pages): no kernel, the ring slice and
    ``_attend`` as before."""
    from deeplearning4j_tpu.nn.conf.layers import GroupedQueryAttentionLayer
    bf16, slots, d = jnp.bfloat16, 64, 4096
    layer = GroupedQueryAttentionLayer(
        n_in=d, n_heads=64, qk_head_dim=192, v_head_dim=128, rotary_dim=64,
        value_scale=0.707, **(
            dict(n_kv_heads=4, rope_theta=1e7) if kind == "global"
            else dict(n_kv_heads=8, window=128, sink=True)))
    assert layer.paged_reads_by_table(16, t, bf16) == bool(kernels)
    assert not layer.paged_reads_by_table(8, t, bf16)    # no whole tile
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    K = layer.n_kv_heads
    params = {"Wq": sds((d, 64 * 192), bf16), "Wk": sds((d, K * 192), bf16),
              "Wv": sds((d, K * 128), bf16), "Wo": sds((64 * 128, d), bf16)}
    if layer.sink:
        params["sink"] = sds((64,), bf16)
    n_pages = slots * (layer.paged_cache(16).ring_pages or 128) + 1
    pool = {"k": sds((n_pages, 16, K * 192), bf16),
            "v": sds((n_pages, 16, K * 128), bf16)}
    ints = lambda *shape: sds(shape, jnp.int32)
    compiled = jax.jit(layer.apply_stream_paged, donate_argnums=(1,)).lower(
        params, pool, ints(slots, 128), ints(slots),
        sds((slots, t, d), bf16), ints(slots)).compile()
    assert _kernels_in(compiled) == kernels
    # the gathers of a global layer's whole table were 2 x 335 MB (what
    # is left at t = 1 is XLA's transposed copy of Wq, 101 MB)
    if kernels:
        assert compiled.memory_analysis().temp_size_in_bytes < 150e6


@pytest.mark.parametrize("t", [2, 1], ids=["chunk_64x2", "decode_64x1"])
def test_narrow_head_paged_step_compiles_for_v5e(topo, as_tpu, t):
    """``GroupedQueryAttentionLayer.apply_stream_paged`` at the shapes
    of the benchmark's ``granite_serve_chat`` cell (32 query heads over
    8 key/value heads of 64, no rotary, scores times 1/64; 64 slots x
    64 pages of 16, bfloat16): a value head of 64 is no whole lane
    tile, so the pool keeps it 128 wide and the grouped by-table
    kernel is in the compiled step, with a key row of 4 lane tiles
    beside a value row of 8."""
    from deeplearning4j_tpu.nn.conf.layers import GroupedQueryAttentionLayer
    bf16, slots, d = jnp.bfloat16, 64, 2048
    layer = GroupedQueryAttentionLayer(
        n_in=d, n_heads=32, n_kv_heads=8, qk_head_dim=64, v_head_dim=64,
        softmax_scale=0.015625)
    assert layer.paged_reads_by_table(16, t, bf16)
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    place = lambda tree: jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), tree)
    params = {"Wq": sds((d, d), bf16), "Wk": sds((d, 512), bf16),
              "Wv": sds((d, 512), bf16), "Wo": sds((d, d), bf16)}
    pool = place(jax.eval_shape(
        lambda: layer.zero_pool(slots * 64 + 1, 16, bf16)))
    assert pool["k"].shape[-1] == 8 * 64 and pool["v"].shape[-1] == 8 * 128
    ints = lambda *shape: sds(shape, jnp.int32)
    compiled = jax.jit(layer.apply_stream_paged, donate_argnums=(1,)).lower(
        params, pool, ints(slots, 64), ints(slots),
        sds((slots, t, d), bf16), ints(slots)).compile()
    assert _kernels_in(compiled) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 50e6


def _block_step(topo, layer, hidden, slots, t):
    """``layer.apply_stream_paged_aux`` compiled for one described
    chip in bfloat16 over ``slots`` x 64 pages of 16, as the paged
    step calls it at ``t`` rows a slot."""
    from deeplearning4j_tpu import dtypes
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    bf16 = jnp.bfloat16
    one = SingleDeviceSharding(topo.devices[0])
    place = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        tree)
    with dtypes.policy_scope(dtypes.Policy(
            param_dtype=bf16, compute_dtype=bf16, output_dtype=bf16)):
        params = place(jax.eval_shape(lambda: layer.initialize(
            jax.random.PRNGKey(0), InputType.recurrent(hidden))[0]))
    assert {a.dtype for a in jax.tree_util.tree_leaves(params)} == {
        jnp.dtype(bf16)}
    pool = place(jax.eval_shape(
        lambda: layer.zero_pool(slots * 64 + 1, 16, bf16)))
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    return jax.jit(layer.apply_stream_paged_aux,
                   donate_argnums=(1,)).lower(
        params, pool, sds((slots, 64), jnp.int32),
        sds((slots,), jnp.int32), sds((slots, t, hidden), bf16),
        sds((slots, t), bool) if t > 1 else sds((slots,), bool),
        sds((slots,), jnp.int32) if t > 1 else None).compile()


@pytest.mark.parametrize("t", [4, 2, 1],
                         ids=["wide_64x4", "chunk_64x2", "decode_64x1"])
def test_latent_decoder_block_step_compiles_for_v5e(topo, as_tpu, t):
    """``LatentDecoderBlock.apply_stream_paged_aux`` at the widths and
    the pool of the benchmark's ``axk1_serve_decode`` cell (hidden
    7168, 64 heads over a latent of 512 + 64, YaRN, 12 held of 192
    experts, top-8; 64 slots of 64 pages of 16) in bfloat16, both step
    programs: the latent by-table kernel is in the compiled step, so
    Mosaic takes a page of 512-wide latent rows beside one of rotary
    keys widened to a lane tile, ``t * 64`` rows of one shared key
    head and the (slots, t * 64, 512) output; and the gathers' copies
    and float32 scores are gone from the step's temporaries."""
    from deeplearning4j_tpu.nn.conf.layers import LatentDecoderBlock
    layer = LatentDecoderBlock(
        n_in=7168, n_heads=64, q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        rope_scaling={"type": "yarn", "factor": 32, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 4096},
        n_routed_experts=192, held=(0, 12), top_k=8, expert_width=2048,
        n_shared_experts=1, routed_scaling_factor=2.5)
    assert layer.paged_reads_by_table(16, t, jnp.bfloat16)
    assert not layer.paged_reads_by_table(8, t, jnp.bfloat16)   # no tile
    compiled = _block_step(topo, layer, 7168, 64, t)
    # at the wide width's 256 rows the experts run grouped
    assert layer.experts_grouped(64 * t, jnp.bfloat16) == (t == 4)
    assert _kernels_in(compiled) == 1 + (t == 4)
    mem = compiled.memory_analysis()
    # a layer's weights are 1.35 GB and its pool 84 MB; the step's
    # temporaries are 37 / 41 MB where the gathered copies of 64 x
    # 1,024 cached rows and the float32 scores made them 152 / 124 MB
    assert 1.3e9 < mem.argument_size_in_bytes < 1.6e9
    assert mem.temp_size_in_bytes < 60e6


@pytest.mark.parametrize("heads, t, dtype, admitted", [
    (64, 16, jnp.bfloat16, True), (64, 8, jnp.float32, True),
    (128, 1, jnp.float32, True), (64, 32, jnp.bfloat16, False)],
    ids=["chunk_8x16_bf16", "chunk_8x8_f32", "decode_128_heads_f32_passes",
         "chunk_8x32_refused"])
def test_latent_kernel_compiles_where_the_predicate_admits(
        topo, as_tpu, heads, t, dtype, admitted):
    """The latent kernel alone at the widest row counts
    ``latent_reads_by_table`` admits (1,024 rows a slot in bfloat16,
    512 in float32, the float32 passes of a single-row step): Mosaic
    takes them; at 2,048 rows, which it would refuse for fast memory,
    the predicate says no and the layer keeps the gather."""
    from deeplearning4j_tpu.ops import paged_attention as PA
    assert PA.latent_reads_by_table(heads, 512, 64, 16, t, dtype) == admitted
    if not admitted:
        return
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    ints = lambda *shape: sds(shape, jnp.int32)
    fn = lambda *a: PA.pallas_paged_attention_latent(*a, scale=0.07)
    compiled = jax.jit(fn).lower(
        sds((8, t, heads, 512), dtype), sds((8, t, heads, 64), dtype),
        sds((513, 16, 512), dtype), sds((513, 16, 128), dtype),
        ints(8, 64), ints(8), ints(8)).compile()
    assert _kernels_in(compiled) == 1


@pytest.mark.parametrize("t", [8, 4, 1],
                         ids=["wide_32x8", "chunk_32x4", "decode_32x1"])
def test_shortcut_expert_block_step_compiles_for_v5e(topo, as_tpu, t):
    """``ShortcutExpertBlock.apply_stream_paged_aux`` at the widths
    and the pool of the benchmark's ``longcat_serve_tooluse`` cell
    (hidden 6144, 64 heads, 16 held of 512 + 256 experts, top-12; 32
    slots of 64 pages of 16) in bfloat16, both step programs: the
    768-wide top-k, two latent pools written and read by table (one
    latent kernel an attention, 256 rows a slot in the chunk
    program), and the tally beside the output, in a chip's memory."""
    from deeplearning4j_tpu.nn.conf.layers import ShortcutExpertBlock
    layer = ShortcutExpertBlock(
        n_in=6144, eps=1e-5, n_heads=64, q_lora_rank=1536,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, rope_theta=1e7, intermediate_size=12288,
        n_routed_experts=512, n_zero_experts=256, held=(0, 16),
        top_k=12, expert_width=2048, routed_scaling_factor=6.0)
    assert layer.paged_reads_by_table(16, t, jnp.bfloat16)
    compiled = _block_step(topo, layer, 6144, 32, t)
    # the experts run grouped at every width: past an MXU tile of rows
    # at the wide one, and under it because 12 picks over a router of
    # 768 leave a tenth and more of the held experts unpicked
    assert layer.experts_grouped(32 * t, jnp.bfloat16)
    assert _kernels_in(compiled) == 3
    out, new_pool, tally = compiled.output_shardings
    assert set(new_pool) == {"a0", "a1"}
    assert set(tally) == {"held", "zero", "selected"}
    mem = compiled.memory_analysis()
    # a layer's weights are 2.49 GB; the step's temporaries beside
    # them are 29 / 8 MB where the two gathers of 32 x 1,024 cached
    # rows and their float32 scores made them 118 / 74 MB
    assert 2.4e9 < mem.argument_size_in_bytes < 2.7e9
    # (55 MB at the wide width's 256 rows)
    assert mem.temp_size_in_bytes < (60e6 if t == 8 else 50e6)


@pytest.mark.parametrize("t", [8, 4, 2, 1], ids=[
    "wide_64x8_as_tpu", "wide_64x4", "chunk_64x2", "decode_64x1"])
def test_grouped_query_window_cell_step_fits_v5e(topo, request, t):
    """The WHOLE id-returning step of the benchmark's
    ``mimo_serve_mixedlen`` cell (``PagedSlotSession._step_ids`` over
    the configuration's own network: 7 layers at the published widths
    in bfloat16, 64 slots of capacity 2,048, page 16), both step
    programs: global layers over the allocator's 8,193 pages, window
    layers over 64 rings of 9 pages, in a chip's 16 GB. At t = 8, the
    wide width on a TPU, the dispatch is the TPU's: two global layers
    by table and every expert layer grouped at the 512 rows the
    session says they carry."""
    from benchmark.harness import spec
    from deeplearning4j_tpu.models.paged_kv import PagedSlotSession
    if t == 8:
        request.getfixturevalue("as_tpu")
    cell = spec.load("mimo_serve_mixedlen")
    config, sv = cell.config, cell.traffic["server"]
    builder = spec.load_module("builders", config["builder"])
    with builder.policy(config):
        net = builder.build(config).init()       # parameters as shapes
    sess = PagedSlotSession(net, sv["slots"], sv["capacity"],
                            sv["page_size"])
    assert sess._ring == [0, 0, 9, 9, 9, 9, 0, 9, 0, 0]
    assert sess.chunk_rows_max == 16 >= t
    sess._make_step()
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    place = lambda tree: jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), tree)
    slots = sv["slots"]
    compiled = sess._step_ids.lower(
        place(net.params), net.state, place(sess._pools),
        sds((slots, sess.pages_per_slot), jnp.int32),
        sds((slots,), jnp.int32), sds((slots, t, 1), jnp.float32),
        sds((slots,), jnp.int32), sds((slots,), jnp.int32),
        sds((slots,), bool)).compile()
    assert sess.experts_carry_rows(t) == (t == 8)
    assert _kernels_in(compiled) == (2 + len(sess._aux_layers)) * (t == 8)
    mem = compiled.memory_analysis()
    # 6.86 GB of weights, 0.67 GB of global pages, 0.24 GB of rings;
    # the pools are donated; the gathers of two global layers' whole
    # tables and the experts' dense pass stay under 1 GB
    assert 7.7e9 < mem.argument_size_in_bytes < 7.9e9
    assert mem.alias_size_in_bytes > 0.9e9
    assert mem.temp_size_in_bytes < 1e9
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) < 12e9


@pytest.mark.parametrize("t", [2, 1], ids=["chunk_64x2", "decode_64x1"])
def test_state_space_block_step_compiles_for_v5e(topo, t):
    """``StateSpaceDecoderBlock.apply_stream_paged`` at the widths of
    the benchmark's ``granite_serve_chat`` cell (hidden 2048, 64 heads
    of 64 over a state of 128, convolution of 4, MLP of 8192) in
    bfloat16 over 64 slots, both step programs: the (64, 64, 64, 128)
    float32 state pool is donated and updated in place, and ONE
    instruction of the compiled step reads it (the row reductions and
    the update in one pass over the pool): a masked or rescaled copy
    for a second reader would be another 134 MB of temporaries."""
    from deeplearning4j_tpu import dtypes
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import StateSpaceDecoderBlock
    bf16, slots, d = jnp.bfloat16, 64, 2048
    layer = StateSpaceDecoderBlock(
        n_in=d, n_heads=64, head_dim=64, state_size=128, n_groups=1,
        conv_width=4, intermediate_size=8192, residual_multiplier=0.22)
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    place = lambda tree: jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), tree)
    with dtypes.policy_scope(dtypes.Policy(
            param_dtype=bf16, compute_dtype=bf16, output_dtype=bf16)):
        params = place(jax.eval_shape(lambda: layer.initialize(
            jax.random.PRNGKey(0), InputType.recurrent(d))[0]))
    pool = place(jax.eval_shape(
        lambda: layer.zero_pool(slots, 16, bf16)))
    assert pool["ssm"].shape == (slots, 64, 64, 128)
    assert pool["ssm"].dtype == jnp.float32
    assert pool["conv"].shape == (slots, 3, 4352)
    compiled = jax.jit(layer.apply_stream_paged, donate_argnums=(1,)).lower(
        params, pool, sds((slots, 64), jnp.int32), sds((slots,), jnp.int32),
        sds((slots, t, d), bf16),
        sds((slots,), jnp.int32) if t > 1 else None).compile()
    mem = compiled.memory_analysis()
    state = slots * 64 * 64 * 128 * 4
    assert mem.alias_size_in_bytes >= state
    assert mem.temp_size_in_bytes < state // 2
    entry = compiled.as_text().split("ENTRY", 1)[1]
    name = next(m for m in re.findall(r"%([\w.]+) = f32\[64,64,64,128\]"
                                      r"\S* parameter", entry))
    readers = [line for line in entry.splitlines()
               if f"%{name}" in line and " parameter(" not in line]
    assert len(readers) == 1, readers
    # and the small numbers of a chunk's rows beside it are whole-array
    # sums and products that fuse: no contraction, windowed reduction
    # (a cumsum) or gather, each a few programs of its own a layer
    small = re.findall(r" (dot|convolution|reduce-window|gather)\(.*"
                       r'op_name="[^"]*/state/', compiled.as_text())
    assert not small, small


@pytest.mark.parametrize("t", [2, 1], ids=["chunk_64x2", "decode_64x1"])
def test_short_conv_block_step_compiles_for_v5e(topo, t):
    """``ShortConvDecoderBlock.apply_stream_paged`` at the widths of
    the benchmark's ``lfm2_serve_agent`` cell (hidden 2048, a window
    of 2 rows, the dense MLP of 11776) in bfloat16 over 64 slots, both
    step programs: the (64, 2, 2048) window pool is donated and
    updated in place, and what lies between the two projections is
    whole-array products, sums and selects that fuse: no contraction,
    windowed reduction (a cumsum) or gather by row under
    ``conv/window``, and no program of its own for each tap (four
    executed instructions at most: the gate's product, the taps'
    slices, the select's predicates and the select; a window of 5
    taps has as many)."""
    from deeplearning4j_tpu import dtypes
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import ShortConvDecoderBlock
    bf16, slots, d = jnp.bfloat16, 64, 2048
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    place = lambda tree: jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), tree)

    def between_the_projections(width):
        """The executed instructions of the compiled step under
        ``conv/window``."""
        layer = ShortConvDecoderBlock(n_in=d, conv_width=width,
                                      intermediate_size=11776)
        with dtypes.policy_scope(dtypes.Policy(
                param_dtype=bf16, compute_dtype=bf16, output_dtype=bf16)):
            params = place(jax.eval_shape(lambda: layer.initialize(
                jax.random.PRNGKey(0), InputType.recurrent(d))[0]))
        assert params["conv"]["conv_w"].shape == (width, d)
        pool = place(jax.eval_shape(
            lambda: layer.zero_pool(slots, 16, bf16)))
        assert pool["conv"].shape == (slots, width - 1, d)
        compiled = jax.jit(layer.apply_stream_paged,
                           donate_argnums=(1,)).lower(
            params, pool, sds((slots, 64), jnp.int32),
            sds((slots,), jnp.int32), sds((slots, t, d), bf16),
            sds((slots,), jnp.int32) if t > 1 else None).compile()
        assert compiled.memory_analysis().alias_size_in_bytes >= \
            slots * (width - 1) * d * 2
        text = compiled.as_text()
        small = re.findall(r" (dot|convolution|reduce-window|gather)\(.*"
                           r'op_name="[^"]*/window/', text)
        assert not small, small
        return [line for line in text.split("ENTRY", 1)[1].splitlines()
                if "/window/" in line and re.search(
                    r" (fusion|custom-call|copy|select|multiply|add)\(",
                    line)]

    run = between_the_projections(3)
    assert len(run) <= 4, run
    assert len(between_the_projections(5)) == len(run)


@pytest.mark.parametrize("t", [8, 2, 1],
                         ids=["wide_64x8", "chunk_64x2", "decode_64x1"])
def test_short_conv_expert_cell_step_fits_v5e(topo, as_tpu, t):
    """The WHOLE id-returning step of the benchmark's
    ``lfm2_serve_agent`` cell (``PagedSlotSession._step_ids`` over the
    configuration's own network: 9 layers at the published widths in
    bfloat16 with ALL 64 experts of 8 layers, 64 slots of capacity
    2,048, page 16), both step programs: 7 window pools of one leaf
    beside 2 attention layers in the allocator's 8,193 pages (a value
    head of 64 in a lane tile of 128, both read by the grouped
    kernel), state layers that are ``aux`` layers too, in a chip's
    16 GB."""
    from benchmark.harness import spec
    from deeplearning4j_tpu.models.paged_kv import PagedSlotSession
    cell = spec.load("lfm2_serve_agent")
    config, sv = cell.config, cell.traffic["server"]
    builder = spec.load_module("builders", config["builder"])
    with builder.policy(config):
        net = builder.build(config).init()       # parameters as shapes
    sess = PagedSlotSession(net, sv["slots"], sv["capacity"],
                            sv["page_size"])
    assert sess._state == [False, True, False, True, True, True, False,
                           True, True, True, False, False]
    assert sess._aux_layers == list(range(2, 10))
    assert sess.state_pool_bytes == 7 * 64 * 2 * 2048 * 2
    assert sess._pools[2]["v"].shape == (8193, 16, 8 * 128)
    sess._make_step()
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    place = lambda tree: jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), tree)
    slots = sv["slots"]
    compiled = sess._step_ids.lower(
        place(net.params), net.state, place(sess._pools),
        sds((slots, sess.pages_per_slot), jnp.int32),
        sds((slots,), jnp.int32), sds((slots, t, 1), jnp.float32),
        sds((slots,), jnp.int32), sds((slots,), jnp.int32),
        sds((slots,), bool)).compile()
    # two attention kernels, and at the wide width's 512 rows, which
    # the session says every expert layer carries, the grouped kernel
    # of each of the eight
    assert sess.runs_grouped_experts(t) == (t == 8)
    assert sess.experts_carry_rows(t) == (t == 8)
    assert _kernels_in(compiled) == 2 + 8 * (t == 8)
    mem = compiled.memory_analysis()
    # 10.62 GB of weights and 0.81 GB of pages; the pools are donated;
    # the dense pass of 128 rows through 64 experts stays under 1 GB
    assert 11.3e9 < mem.argument_size_in_bytes < 11.6e9
    assert mem.alias_size_in_bytes > 0.8e9
    assert mem.temp_size_in_bytes < 1e9
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) < 13e9


@pytest.mark.parametrize("t", [2, 1], ids=["chunk_64x2", "decode_64x1"])
def test_delta_rule_block_step_compiles_for_v5e(topo, as_tpu, t):
    """``DeltaRuleDecoderBlock.apply_stream_paged`` at the widths of
    the benchmark's ``olmo_hybrid_serve_reason`` cell (hidden 3840, 30
    heads of 96 x 192, convolutions of 4, MLP of 11008, the norms
    behind the branches) in bfloat16 over 64 slots, both step
    programs. The float32 state pool is (64, 15, 96, 384), two heads
    side by side: whole lane tiles, so the device holds the row's
    2,211,840 B as counted ((64, 30, 96, 192) would be held 96 x 256 a
    head, a third more). It is donated and updated in place, in ONE
    pass whatever t: ONE instruction of the compiled step reads it,
    the kernel's custom call (``ops/delta_state.py``: the 2 t
    reductions ``S^T k`` / ``S^T q``, the forward substitution and the
    write over a tile held in fast memory), and the pool is aliased
    through it, the call's second result in its sixth operand's
    buffer. No copy of it is made (temporaries under 8 MB), and what
    else lies between the projections is whole-array products, sums
    and selects: no contraction and no gather by row. The one
    reduction that is not over an array's own axis is the gated norm's
    mean over a head's OWN 192 of the pack's 384 lanes
    (``_head_mean``: a masked sum a head of the pack, two, spread back
    over the lanes; the op_name is its ``jit(_where)``), and how XLA
    emits the two depends on the rows it finds ``o`` in. At t = 2
    they are two windowed reductions over (64, 2, 15, 384) (a window
    of 767 over the 384 lanes: the sum at every lane), as at both
    widths while ``o`` came out of XLA's own fusion; at t = 1 the
    kernel hands ``o`` over as (64, 1, 15, 384) and they are two plain
    reductions to (64, 15), spread back by the fusion that reads
    them. Two either way: they do not grow with t. (Until PR 47 this
    docstring took them for the L2 norms' sums over a head's 96
    values; those are plain reductions over the last axis, then and
    now.)"""
    from deeplearning4j_tpu import dtypes
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import DeltaRuleDecoderBlock
    bf16, slots, d = jnp.bfloat16, 64, 3840
    layer = DeltaRuleDecoderBlock(
        n_in=d, n_heads=30, key_head_dim=96, value_head_dim=192,
        conv_width=4, allow_neg_eigval=True, intermediate_size=11008,
        norm_placement="post")
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    place = lambda tree: jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), tree)
    with dtypes.policy_scope(dtypes.Policy(
            param_dtype=bf16, compute_dtype=bf16, output_dtype=bf16)):
        params = place(jax.eval_shape(lambda: layer.initialize(
            jax.random.PRNGKey(0), InputType.recurrent(d))[0]))
    pool = place(jax.eval_shape(
        lambda: layer.zero_pool(slots, 16, bf16)))
    assert pool["state"].shape == (slots, 15, 96, 384)
    assert pool["state"].dtype == jnp.float32
    assert pool["conv"].shape == (slots, 3, 11520)
    compiled = jax.jit(layer.apply_stream_paged, donate_argnums=(1,)).lower(
        params, pool, sds((slots, 48), jnp.int32), sds((slots,), jnp.int32),
        sds((slots, t, d), bf16),
        sds((slots,), jnp.int32) if t > 1 else None).compile()
    mem = compiled.memory_analysis()
    state = slots * 30 * 96 * 192 * 4
    assert mem.alias_size_in_bytes >= state
    assert mem.temp_size_in_bytes < 8e6
    text = compiled.as_text()
    entry = text.split("ENTRY", 1)[1]
    # held as counted: the pool's layout has no padded tile
    name, tiles = re.findall(
        r"%([\w.]+) = f32\[64,15,96,384\](\S*) parameter", entry)[0]
    assert "T(8,128)" in tiles and 96 % 8 == 0 and 384 % 128 == 0
    readers = [line for line in entry.splitlines()
               if f"%{name}" in line and " parameter(" not in line]
    assert len(readers) == 1, readers
    assert 'custom_call_target="tpu_custom_call"' in readers[0]
    assert "/delta/state/" in readers[0] and \
        "pallas_delta_state" in readers[0]
    assert readers[0].split("custom-call(")[1].split(")")[0].split(
        ", ")[5].endswith(f"%{name}")
    assert "output_to_operand_aliasing={{1}: (5, {})}" in readers[0]
    assert _kernels_in(compiled) == 1
    small = re.findall(r" (dot|convolution|reduce-window|gather)\(.*"
                       r'op_name="[^"]*/state/', text)
    # the gated norm's two head means: windowed at t = 2, plain at 1
    assert small == ["reduce-window"] * {2: 2, 1: 0}[t], small
    plain = re.findall(r"= f32\[64,15\]\S* reduce\(.*"
                       r'op_name="[^"]*/state/', text)
    assert len(plain) == {2: 0, 1: 2}[t], plain


@pytest.mark.parametrize("t", [2, 1], ids=["chunk_64x2", "decode_64x1"])
def test_delta_rule_hybrid_cell_step_fits_v5e(topo, as_tpu, t):
    """The WHOLE id-returning step of the benchmark's
    ``olmo_hybrid_serve_reason`` cell (``PagedSlotSession._step_ids``
    over the configuration's own network: 16 layers at the published
    widths in bfloat16, 64 slots of capacity 768, page 16), both step
    programs: 12 state pools of 2.28 MB a slot beside 4 attention
    layers of 30 heads in the allocator's 3,073 pages, every one read
    by table through the grouped kernel (a slot's 30 or 60 rows
    rounded up to 32 or 64: no gather of a whole table), every state
    pool read and written in one pass by ``pallas_delta_state``: 16
    kernels a step, in a chip's 16 GB."""
    from benchmark.harness import spec
    from deeplearning4j_tpu.models.paged_kv import PagedSlotSession
    cell = spec.load("olmo_hybrid_serve_reason")
    config, sv = cell.config, cell.traffic["server"]
    builder = spec.load_module("builders", config["builder"])
    with builder.policy(config):
        net = builder.build(config).init()       # parameters as shapes
    sess = PagedSlotSession(net, sv["slots"], sv["capacity"],
                            sv["page_size"])
    assert sess._state == [False] + [True, True, True, False] * 4 + [
        False, False]
    assert sess.unrolls_chunk_rows and not any(sess._ring)
    assert sess.state_pool_bytes == 12 * 64 * 2_280_960
    assert sess._pools[4]["k"].shape == (3073, 16, 30 * 128)
    assert all(net.layers[i].paged_reads_by_table(16, t, jnp.bfloat16)
               for i in (4, 8, 12, 16))
    sess._make_step()
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    place = lambda tree: jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), tree)
    slots = sv["slots"]
    compiled = sess._step_ids.lower(
        place(net.params), net.state, place(sess._pools),
        sds((slots, sess.pages_per_slot), jnp.int32),
        sds((slots,), jnp.int32), sds((slots, t, 1), jnp.float32),
        sds((slots,), jnp.int32), sds((slots,), jnp.int32),
        sds((slots,), bool)).compile()
    assert _kernels_in(compiled) == 16          # 4 attention + 12 state
    assert compiled.as_text().count(
        'pallas_delta_state/pallas_call') >= 12
    mem = compiled.memory_analysis()
    # 8.20 GB of weights, 1.75 GB of state rows, 3.02 GB of pages; the
    # pools are donated; the logits of 128 rows over 100,352 ids and
    # the MLPs' activations stay under 1 GB
    assert 12.9e9 < mem.argument_size_in_bytes < 13.1e9
    assert mem.alias_size_in_bytes > 4.7e9
    assert mem.temp_size_in_bytes < 1e9
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) < 14.5e9


@pytest.mark.parametrize("d, w, routed, held, top_k", [
    (2048, 1536, 64, None, 4), (4096, 2048, 256, (0, 16), 8)],
    ids=["lfm2_24b_a2b", "mimo_v25_ep16"])
def test_grouped_expert_pass_compiles_for_v5e(topo, as_tpu, d, w, routed,
                                              held, top_k):
    """The expert layer of the wide step (64 slots x 8 = 512 rows,
    which the layer says it carries on weights it reads anyway; 256
    rows too, the wide step of a pool that keeps that budget) at
    ``lfm2_24b_a2b``'s and ``mimo_v25_ep16``'s own widths in bfloat16,
    as a serving step calls it: the predicate admits it, the grouped
    kernel is in the compiled text under its own name, and nothing
    under ``moe/experts`` gathers, scatters or sorts rows (the
    ordering is whole-array arithmetic and one 0/1 matmul). At the
    narrow step's 128 rows the same call compiles to the dense pass."""
    from deeplearning4j_tpu import dtypes
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import SparseExpertsLayer
    bf16 = jnp.bfloat16
    layer = SparseExpertsLayer(
        n_in=d, n_routed_experts=routed, held=held, top_k=top_k,
        expert_width=w, n_shared_experts=0, router_bias=True)
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    with dtypes.policy_scope(dtypes.Policy(
            param_dtype=bf16, compute_dtype=bf16, output_dtype=bf16)):
        params = jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype),
            jax.eval_shape(lambda: layer.initialize(
                jax.random.PRNGKey(0), InputType.recurrent(d))[0]))
    step = jax.jit(lambda p, x, a: layer.apply_tallied(p, x, a, True))
    texts = {}
    for t in (8, 4, 2):
        assert layer.takes_grouped_pass(64 * t, bf16) == (t >= 4)
        assert layer.carries_rows(64 * t, bf16) == (t >= 4)
        texts[t] = step.lower(params, sds((64, t, d), bf16),
                              sds((64, t), bool)).compile().as_text()
    assert "tpu_custom_call" not in texts[2]
    for t in (8, 4):
        assert texts[t].count("tpu_custom_call") == 1
        assert "pallas_grouped_experts" in texts[t]
        moved = re.findall(r" (gather|scatter|sort|dynamic-slice|"
                           r'dynamic-update-slice)\(.*op_name="[^"]*'
                           r'moe/experts', texts[t])
        assert not moved, (t, moved)


@pytest.mark.parametrize("config, d, w, routed, top_k, slots, want", [
    ("lfm2_24b_a2b", 2048, 1536, 64, 4, 64, 512),
    ("mimo_v25_ep16", 4096, 2048, 256, 8, 64, 512),
    ("longcat_ep32", 6144, 2048, 768, 12, 32, 256),
    ("axk1_ep16", 7168, 2048, 192, 8, 64, 256)])
def test_the_wide_budget_of_the_published_shapes(as_tpu, config, d, w,
                                                 routed, top_k, slots,
                                                 want):
    """What an expert layer of each serving configuration's widths
    says of the 512 rows a wide step would carry: ``lfm2_24b_a2b`` and
    ``mimo_v25_ep16`` carry them; ``longcat_ep32`` runs them grouped
    past the kernel's turn, and ``axk1_ep16`` dense (the kernel would
    pass its fast memory), so both answer 256, where all four are
    grouped and under the turn."""
    from deeplearning4j_tpu.nn.conf.layers import SparseExpertsLayer
    from deeplearning4j_tpu.serving.continuous import (
        GROUPED_CHUNK_ROWS, WIDE_CHUNK_ROWS, chunk_width)
    bf16 = jnp.bfloat16
    layer = SparseExpertsLayer(n_in=d, n_routed_experts=routed,
                               held=(0, 8), top_k=top_k, expert_width=w)
    rows = slots * chunk_width(slots, 1024, GROUPED_CHUNK_ROWS)
    assert rows == GROUPED_CHUNK_ROWS == 512
    assert layer.carries_rows(rows, bf16) == (want == 512)
    assert layer.takes_grouped_pass(rows, bf16) == (
        config != "axk1_ep16")
    assert layer.carries_rows(WIDE_CHUNK_ROWS, bf16)
    assert not layer.carries_rows(rows, jnp.float32)


# ---- four chips: the kernels on a mesh -----------------------------------

def _attention_loss(q, k, v, mask=None):
    o = A.flash_attention(q, k, v, causal=True, kv_mask=mask)
    return jnp.sum(o.astype(jnp.float32) ** 2)


@pytest.mark.parametrize("masked", [False, True],
                         ids=["plain", "kv_masked"])
def test_flash_under_gspmd_data_mesh(topo, as_tpu, masked):
    """The fit(mesh_spec="dp=4") path: a plain jit over batch-sharded
    operands. Without the island the partitioner answers 'Mosaic
    kernels cannot be automatically partitioned'."""
    mesh = _mesh(topo, data=4)
    x = jax.ShapeDtypeStruct((8, 1024, 16, 64), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("data")))
    args = (x, x, x)
    if masked:
        args += (jax.ShapeDtypeStruct(
            (8, 1024), jnp.float32,
            sharding=NamedSharding(mesh, P("data"))),)

    def step(*a):
        with gspmd_mesh(mesh):
            return jax.value_and_grad(_attention_loss,
                                      argnums=(0, 1, 2))(*a)
    compiled = jax.jit(step).lower(*args).compile()
    assert _kernels_in(compiled) == 3          # fwd, dq, dk/dv
    # the island really splits the batch: 8 examples -> 2 per device
    assert "bf16[2,1024,16,64]" in compiled.as_text()
    assert _relayouts_in(compiled)      # a head of 64: transposed operands


def test_flash_under_gspmd_dp_tp_mesh(topo, as_tpu):
    """dp=2 x tp=2 (serving/tp_backend's forward): batch over 'data',
    heads over 'model'."""
    mesh = _mesh(topo, data=2, model=2)
    x = jax.ShapeDtypeStruct(
        (8, 1024, 16, 64), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("data", None, "model", None)))

    def fwd(q, k, v):
        with gspmd_mesh(mesh):
            return A.flash_attention(q, k, v, causal=True)
    compiled = jax.jit(fwd).lower(x, x, x).compile()
    assert _kernels_in(compiled) == 1
    assert "bf16[4,1024,8,64]" in compiled.as_text()


@pytest.mark.parametrize("masked", [False, True],
                         ids=["plain", "kv_masked"])
def test_flash_inside_manual_shard_map(topo, as_tpu, masked):
    """The ParallelWrapper manual-step path: the whole loss traced
    inside one checked shard_map over 'data'. Without vma on the
    kernel outputs shard_map answers 'vma ... must not be None'."""
    mesh = _mesh(topo, data=4)
    x = jax.ShapeDtypeStruct((8, 1024, 16, 64), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("data")))
    args, specs = (x, x, x), (P("data"),) * 3
    if masked:
        args += (jax.ShapeDtypeStruct(
            (8, 1024), jnp.float32,
            sharding=NamedSharding(mesh, P("data"))),)
        specs += (P("data"),)

    def per_device(*a):
        loss, grads = jax.value_and_grad(_attention_loss,
                                         argnums=(0, 1, 2))(*a)
        return jax.lax.pmean(loss, "data"), grads
    step = jax.shard_map(per_device, mesh=mesh, in_specs=specs,
                         out_specs=(P(), (P("data"),) * 3),
                         check_vma=True)
    compiled = jax.jit(step).lower(*args).compile()
    assert _kernels_in(compiled) == 3
    assert _relayouts_in(compiled)      # a head of 64: transposed operands


def test_ring_island_on_dp_tp_sp_mesh(topo, as_tpu):
    """GSPMD-mode sequence parallelism: the attention layer's ring
    island must be manual over EVERY mesh axis — a Mosaic call under
    an axis left automatic is refused like the plain GSPMD case."""
    from deeplearning4j_tpu.nn.conf.layers import SelfAttentionLayer
    mesh = _mesh(topo, data=2, seq=2)
    layer = SelfAttentionLayer(n_in=256, n_out=256, n_heads=4,
                               causal=True)
    repl = NamedSharding(mesh, P())
    params = {k: jax.ShapeDtypeStruct((256, 256), jnp.float32,
                                      sharding=repl)
              for k in ("Wq", "Wk", "Wv", "Wo")}
    params["bo"] = jax.ShapeDtypeStruct((256,), jnp.float32,
                                        sharding=repl)
    x = jax.ShapeDtypeStruct(
        (4, 2048, 256), jnp.float32,
        sharding=NamedSharding(mesh, P("data", "seq")))

    def loss(p, x):
        with sequence_parallel_gspmd(mesh, "seq"):
            y, _ = layer.apply(p, {}, x)
        return jnp.sum(y ** 2)
    compiled = jax.jit(jax.grad(loss)).lower(params, x).compile()
    assert _kernels_in(compiled) >= 3
    assert "collective-permute" in compiled.as_text()
    assert _relayouts_in(compiled)      # a head of 64: transposed operands
