"""Attention over a paged KV pool, read in place by page table.

The paged decode step (``SelfAttentionLayer.apply_stream_paged``,
models/paged_kv.py) used to build every slot's virtual cache with
``k_pool[table]``: a copy of ``slots x capacity`` positions a layer a
step whatever the slots hold, then read again by two einsums under a
mask. This kernel walks a slot's pages BY TABLE and stops at the
slot's last live page, so traffic follows the tokens held and not
the capacity:

- ``table`` and the per-slot ``lengths`` / ``pos`` are scalar
  prefetch; the pool leaves stay in HBM (``pl.ANY``) and whole pages
  are fetched by hand, ``pages_per_block`` of them a block, double
  buffered: while block i is computed block i + 1 is in flight, and a
  slot's last block prefetches the next slot's first;
- a pool leaf is ``(n_pages, page_size, H * Dh)``: a page is
  ``page_size`` lane-dense rows of all heads, one contiguous DMA;
- all heads of a slot go through the MXU at once. Query row ``j`` of
  head ``h`` is the flat ``(H * Dh,)`` query row ``j`` with every
  other head's columns zeroed (a block-diagonal ``(t * H, H * Dh)``
  operand), so ``scores = Qx @ K_block^T`` is ``(t * H, block)`` with
  keys on lanes, and ``Qx``'s zeros do the head split the gather path
  did with a reshape. The value product is ``(t * H, H * Dh)``, of
  which row ``(j, h)`` keeps head ``h``'s columns. The MXU is idle in
  a decode step, so the H-fold surplus of multiplies is free next to
  per-head matmuls that would each wait out the unit's latency;
- running maximum and sum in float32 (online softmax); the two dots
  with float32 accumulation at the precision the gather's einsums
  come to on the chip (``_dot_precision``).

Query row ``j`` of slot ``s`` sits at ``pos[s] + j`` and sees keys at
positions ``<= pos[s] + j`` and ``< lengths[s]``: rows of one chunk are
causal among themselves, and nothing past a slot's length reaches any
row, whatever the table's stale entries point at. A slot of length 0
fetches nothing and yields zero rows.

``paged_attention`` dispatches: this kernel on a TPU for the shapes it
tiles, the gather elsewhere (the CPU path and the tests' oracle).

``pallas_paged_attention_grouped`` (grouped-query heads of two widths,
``GroupedQueryAttentionLayer``) and ``pallas_paged_attention_latent``
(the absorbed latent attention, ``LatentAttentionLayer``: one shared
key head, whose values are the keys' own latent part) are further
bodies that share the page walk (``_page_walk``) and the online-softmax
block and none of the head algebra. Each layer dispatches on its own
predicate; its ``_attend`` over the gathered table is the oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.attention import _NEG_INF

__all__ = ["grouped_reads_by_table", "lane_tiled", "latent_reads_by_table",
           "paged_attention", "paged_attention_gather",
           "pallas_paged_attention", "pallas_paged_attention_grouped",
           "pallas_paged_attention_latent", "pages_read", "reads_by_table"]

# keys a block: one lane tile of scores. A live slot of the serving
# cells holds about a hundred tokens, so most slots are one block
_BLOCK_KEYS = 128
# bytes of fast memory the kernel may ask for (``_vmem_bytes``): three
# quarters of the 16 MiB Mosaic gives a kernel on a v5e unasked, the
# rest left to the temporaries Mosaic makes of its own
_VMEM_BUDGET = 12 << 20


def pages_read(lengths, page_size: int):
    """Pages the kernel fetches of a slot that holds ``lengths``
    positions: up to the one its last position is in, none at length
    0. The kernel's ``live_pages``; the session's accounting of KV
    positions read uses it on host arrays."""
    return (lengths + page_size - 1) // page_size


def paged_attention_gather(q, k_pool, v_pool, table, pos, n_heads):
    """The gather path: each slot's virtual cache of ``P * page_size``
    positions assembled from its page table, dense attention under the
    ``k_pos <= q_pos`` mask. Stale or unassigned table entries gather
    garbage pages, but their positions exceed every query's and the
    mask zeroes them exactly (``exp(_NEG_INF - max) == 0.0``).
    ``q`` (S, t, H * Dh), pool leaves (n_pages, page_size, H * Dh),
    ``table`` (S, P), ``pos`` (S,) → (S, t, H * Dh)."""
    S, t, HD = q.shape
    P = table.shape[1]
    ps = k_pool.shape[1]
    H = n_heads
    Dh = HD // H
    qh = q.reshape(S, t, H, Dh)
    k_cache = k_pool[table].reshape(S, P * ps, H, Dh)
    v_cache = v_pool[table].reshape(S, P * ps, H, Dh)
    logits = jnp.einsum("bqhd,bkhd->bhqk", qh,
                        k_cache.astype(q.dtype)) * (Dh ** -0.5)
    k_pos = jnp.arange(P * ps)[None, None, :]                  # (1,1,K)
    q_pos = (pos[:, None] + jnp.arange(t)[None, :])[:, :, None]
    logits = jnp.where((k_pos <= q_pos)[:, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v_cache.astype(q.dtype))
    return out.reshape(S, t, HD)


def _page_walk(lengths_ref, table_ref, k_hbm, v_hbm, k_buf, v_buf, sems,
               state, *, page_size, pages_per_block, pages_per_slot):
    """The page walk the kernels share, for the slot ``s`` of this
    grid step: its live pages fetched by table into the double buffer,
    ``pages_per_block`` a block. Returns ``(s, start, each_block)``:
    ``start()`` sets the first block going unless the slot before
    already did (operands are built behind it), and
    ``each_block(compute)`` calls ``compute(i, buf)`` once block ``i``
    has landed in ``k_buf[buf]`` / ``v_buf[buf]``. While a block is
    computed the next is in flight, and a slot's last block prefetches
    the next slot's first."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    ps, ppb, P = page_size, pages_per_block, pages_per_slot
    s = pl.program_id(0)
    n_slots = pl.num_programs(0)

    def live_pages(slot):
        return pages_read(lengths_ref[slot], ps)

    def each_live_page(slot, blk, buf, fn):
        """``fn`` on the K and the V copy of every live page of block
        ``blk`` of ``slot`` into buffer ``buf``: the same descriptors
        start a block and wait for it."""
        n_live = live_pages(slot)
        for p in range(ppb):
            idx = blk * ppb + p

            @pl.when(idx < n_live)
            def _():
                page = table_ref[slot * P + jnp.minimum(idx, P - 1)]
                rows = pl.ds(p * ps, ps)
                fn(pltpu.make_async_copy(
                    k_hbm.at[page], k_buf.at[buf, rows], sems.at[0, buf]))
                fn(pltpu.make_async_copy(
                    v_hbm.at[page], v_buf.at[buf, rows], sems.at[1, buf]))

    def start():
        nonlocal n_blocks
        n_blocks = (live_pages(s) + ppb - 1) // ppb

        # state[0]: the buffer the next block to compute lands in;
        # state[1]: the slot whose first block is already in flight
        @pl.when(s == 0)
        def _():
            state[0] = 0
            state[1] = -1
            # a block's tail past the slot's live pages is never
            # fetched: what the buffer holds there must be finite,
            # since the value product multiplies it by an exact zero
            v_buf[...] = jnp.zeros_like(v_buf)

        @pl.when(state[1] != s)
        def _():
            each_live_page(s, 0, state[0], lambda c: c.start())

    def each_block(compute):
        def block(i, carry):
            buf = state[0]
            last = i + 1 >= n_blocks

            @pl.when(jnp.logical_not(last))
            def _():
                each_live_page(s, i + 1, 1 - buf, lambda c: c.start())

            @pl.when(last & (s + 1 < n_slots))
            def _():
                each_live_page(s + 1, 0, 1 - buf, lambda c: c.start())
                state[1] = s + 1

            each_live_page(s, i, buf, lambda c: c.wait())
            compute(i, buf)
            state[0] = 1 - buf
            return carry

        jax.lax.fori_loop(0, n_blocks, block, 0)

    n_blocks = None     # the slot's blocks, counted by ``start``
    return s, start, each_block


def _softmax_block(sc, v, i, qlim_scr, m_scr, l_scr, acc_scr, precision):
    """One block of the online softmax: the scores ``sc`` (rows, keys)
    of block ``i`` masked to the last key each row sees, the running
    maximum and sum in float32, the probabilities rounded to the
    values' dtype and multiplied into the accumulator."""
    k_pos = i * sc.shape[1] + jax.lax.broadcasted_iota(
        jnp.int32, sc.shape, 1)
    sc = jnp.where(k_pos <= qlim_scr[:, 0:1], sc, _NEG_INF)
    m_prev = m_scr[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
    # block 0 holds key 0, which every row of a slot with a token
    # sees: m_new is finite from there on, and a masked score's
    # exp(_NEG_INF - m_new) is an exact zero
    p = jnp.exp(sc - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_scr[:, 0:1] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=precision)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)


def _kernel(lengths_ref, pos_ref, table_ref, q_ref, k_hbm, v_hbm, o_ref,
            qx_scr, qlim_scr, m_scr, l_scr, acc_scr, k_buf, v_buf, sems,
            state, *, n_heads, head_dim, page_size, pages_per_block,
            pages_per_slot, precision):
    from jax.experimental import pallas as pl

    H, Dh = n_heads, head_dim
    t = q_ref.shape[1]
    HD = H * Dh
    s, start, each_block = _page_walk(
        lengths_ref, table_ref, k_hbm, v_hbm, k_buf, v_buf, sems, state,
        page_size=page_size, pages_per_block=pages_per_block,
        pages_per_slot=pages_per_slot)
    length = lengths_ref[s]
    pos = pos_ref[s]
    start()

    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    col = jax.lax.broadcasted_iota(jnp.int32, (H, HD), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (H, HD), 0)
    own = (col >= head * Dh) & (col < (head + 1) * Dh)
    for j in range(t):
        rows = slice(j * H, (j + 1) * H)
        # (selected in float32: Mosaic has no relayout of the 32-bit
        # mask onto a 16-bit operand's tiling)
        qx_scr[rows, :] = jnp.where(
            own, jnp.broadcast_to(
                q_ref[0, j:j + 1, :].astype(jnp.float32), (H, HD)),
            0.0).astype(qx_scr.dtype)
        qlim_scr[rows, :] = jnp.full(
            (H, qlim_scr.shape[1]), jnp.minimum(pos + j, length - 1),
            jnp.int32)

    def block(i, buf):
        qx = qx_scr[...]
        k = k_buf[buf].astype(qx.dtype)
        v = v_buf[buf].astype(qx.dtype)
        sc = jax.lax.dot_general(
            qx, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision) * (Dh ** -0.5)
        _softmax_block(sc, v, i, qlim_scr, m_scr, l_scr, acc_scr,
                       precision)

    each_block(block)

    # a slot of length 0 ran no block: acc and l are 0, its rows come
    # out 0 and not NaN
    inv = 1.0 / jnp.maximum(l_scr[:, 0:1], 1e-30)
    for j in range(t):
        rows = slice(j * H, (j + 1) * H)
        kept = jnp.where(own, acc_scr[rows, :] * inv[rows], 0.0)
        o_ref[0, j:j + 1, :] = jnp.sum(
            kept, axis=0, keepdims=True).astype(o_ref.dtype)


def _grouped_kernel(lengths_ref, pos_ref, table_ref, q_ref, k_hbm, v_hbm,
                    o_ref, qx_scr, qlim_scr, m_scr, l_scr, acc_scr, k_buf,
                    v_buf, sems, state, *, n_heads, n_kv_heads, page_size,
                    pages_per_block, pages_per_slot, precision):
    """``_kernel`` for grouped-query heads of two widths: ``n_heads``
    query heads of ``dq`` over ``n_kv_heads`` key heads of ``dq`` and
    value heads of ``dv``. ``q_ref`` (1, t * H, dq) holds row ``(j,
    h)`` at ``j * H + h``; the block-diagonal operand puts it at the
    columns of key head ``h // (H / K)`` of a flat ``(K * dq,)`` key
    row, so ``scores = Qx @ K_block^T`` is ``(t * H, block)`` in one
    pass over the block. The value product is ``(t * H, K * dv)``, of
    which row ``(j, h)`` keeps its own key head's ``dv`` columns:
    ``o_ref`` (1, t * H, dv)."""
    from jax.experimental import pallas as pl

    H, K = n_heads, n_kv_heads
    R, dq = q_ref.shape[1:]
    dv = o_ref.shape[2]
    s, start, each_block = _page_walk(
        lengths_ref, table_ref, k_hbm, v_hbm, k_buf, v_buf, sems, state,
        page_size=page_size, pages_per_block=pages_per_block,
        pages_per_slot=pages_per_slot)
    start()

    def key_head(width):
        """(R, width): the key head that row ``j * H + h`` reads."""
        row = jax.lax.broadcasted_iota(jnp.int32, (R, width), 0)
        return jax.lax.div(jax.lax.rem(row, H), H // K)

    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    # (selected in float32, as in ``_kernel``)
    q = q_ref[0].astype(jnp.float32)
    head = key_head(dq)
    for k in range(K):
        qx_scr[:, k * dq:(k + 1) * dq] = jnp.where(
            head == k, q, 0.0).astype(qx_scr.dtype)
    row = jax.lax.broadcasted_iota(jnp.int32, qlim_scr.shape, 0)
    qlim_scr[...] = jnp.minimum(pos_ref[s] + jax.lax.div(row, H),
                                lengths_ref[s] - 1)

    def block(i, buf):
        qx = qx_scr[...]
        k = k_buf[buf].astype(qx.dtype)
        v = v_buf[buf].astype(qx.dtype)
        sc = jax.lax.dot_general(
            qx, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision) * (dq ** -0.5)
        _softmax_block(sc, v, i, qlim_scr, m_scr, l_scr, acc_scr,
                       precision)

    each_block(block)

    # (a slot of length 0: zeros, as in ``_kernel``)
    head = key_head(dv)
    out = acc_scr[:, 0:dv]
    for k in range(1, K):
        out = jnp.where(head == k, acc_scr[:, k * dv:(k + 1) * dv], out)
    inv = 1.0 / jnp.maximum(l_scr[:, 0:1], 1e-30)
    o_ref[0] = (out * inv).astype(o_ref.dtype)


def _dot_precision(t: int, dtype):
    """The precision of the kernel's two dots: what the gather's
    einsums come to on the chip. One query row a slot is a
    matrix-vector product, which XLA keeps off the MXU and in float32
    (multiply-reduce fusions): float32 passes here, over an MXU that a
    decode step leaves idle. A chunk of rows is a matmul at the
    default precision, operands rounded to bfloat16, there as here."""
    if t == 1 and jnp.dtype(dtype) == jnp.float32:
        return jax.lax.Precision.HIGHEST
    return jax.lax.Precision.DEFAULT


def _sublanes(dtype) -> int:
    """Rows of ``dtype`` in one sublane tile."""
    return 32 // jnp.dtype(dtype).itemsize


def _whole_tiles(rows: int, dtype) -> int:
    """``rows`` rounded up to whole sublane tiles of ``dtype``."""
    n = _sublanes(dtype)
    return -(-rows // n) * n


def _vmem_bytes(rows: int, key_row: int, value_row: int, q_and_o: int,
                page_size: int, t: int, dtype) -> int:
    """The fast memory a kernel asks for, over ``rows`` = ``t * H``
    query rows, pool rows ``key_row`` and ``value_row`` wide and
    ``q_and_o`` elements in a slot's query and output blocks: the
    scratch as declared there, the double-buffered query and output
    blocks, the float32 value product beside the accumulator and,
    where the dots run float32 passes, the pieces Mosaic splits a
    block of K or V into for them."""
    item = jnp.dtype(dtype).itemsize
    block = max(page_size, _BLOCK_KEYS // page_size * page_size)
    passes = _dot_precision(t, dtype) == jax.lax.Precision.HIGHEST
    return (2 * block * (key_row + value_row) * item  # K, V double buffered
            + passes * block * max(key_row, value_row) * 4  # operand pieces
            + rows * (key_row * item          # block-diag q
                      + value_row * (4 + 4))  # acc, product
            + 3 * rows * 128 * 4              # row limits, max, sum
            + 2 * q_and_o * item)             # q and o blocks


@functools.partial(jax.jit, static_argnames=("n_heads", "interpret"))
def pallas_paged_attention(q, k_pool, v_pool, table, lengths, pos, *,
                           n_heads: int, interpret: bool = False):
    """``q`` (S, t, H * Dh); pool leaves (n_pages, page_size, H * Dh);
    ``table`` (S, P) int32; ``lengths`` (S,) the positions a slot holds
    once the step's rows are written; ``pos`` (S,) the position of a
    slot's query row 0 → (S, t, H * Dh) in ``q``'s dtype."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, t, HD = q.shape
    P = table.shape[1]
    ps = k_pool.shape[1]
    ppb = max(1, min(P, _BLOCK_KEYS // ps))
    R = t * n_heads
    kernel = functools.partial(
        _kernel, n_heads=n_heads, head_dim=HD // n_heads, page_size=ps,
        pages_per_block=ppb, pages_per_slot=P,
        precision=_dot_precision(t, q.dtype))
    row = pl.BlockSpec((1, t, HD), lambda s, *_: (s, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S,),
            in_specs=[row, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row,
            scratch_shapes=[
                pltpu.VMEM((R, HD), q.dtype),           # block-diag q
                pltpu.VMEM((R, 128), jnp.int32),        # last key a row sees
                pltpu.VMEM((R, 128), jnp.float32),      # running max
                pltpu.VMEM((R, 128), jnp.float32),      # running sum
                pltpu.VMEM((R, HD), jnp.float32),       # accumulator
                pltpu.VMEM((2, ppb * ps, HD), k_pool.dtype),
                pltpu.VMEM((2, ppb * ps, HD), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((2,), jnp.int32),
            ]),
        out_shape=jax.ShapeDtypeStruct((S, t, HD), q.dtype),
        # slots in turn on one core: the buffers, their semaphores and
        # the prefetch of the next slot's first block are carried over
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="pallas_paged_attention",
    )(lengths.astype(jnp.int32), pos.astype(jnp.int32),
      table.reshape(-1).astype(jnp.int32), q, k_pool, v_pool)


def reads_by_table(n_heads: int, head_dim: int, page_size: int, t: int,
                   dtype) -> bool:
    """Does :func:`paged_attention` run the kernel for these shapes?
    Only on a TPU, and only where Mosaic tiles them: a page is whole
    sublane tiles of its dtype, a row whole lane tiles, a slot's heads
    whole sublane tiles of the float32 accumulator, and everything
    the kernel holds in fast memory (``_vmem_bytes``: the K and V
    buffers grow with the row, the accumulator and its likes with
    ``t * H`` rows of it) fits the budget."""
    sublanes = _sublanes(dtype)
    return (jax.default_backend() == "tpu"
            and page_size % sublanes == 0
            and (n_heads * head_dim) % 128 == 0
            and n_heads % 8 == 0
            and _vmem_bytes(t * n_heads, n_heads * head_dim,
                            n_heads * head_dim, 2 * t * n_heads * head_dim,
                            page_size, t, dtype) <= _VMEM_BUDGET)


def paged_attention(q, k_pool, v_pool, table, pos, n_valid=None, *,
                    n_heads: int):
    """Each slot's ``t`` query rows over the positions it holds in the
    paged pool (the step's own rows already written there): by table
    where :func:`reads_by_table` says so, by the gather elsewhere.
    ``n_valid`` (S,): rows at or past it carry no token; their output
    is finite and means nothing."""
    t, HD = q.shape[1:]
    if reads_by_table(n_heads, HD // n_heads, k_pool.shape[1], t,
                      k_pool.dtype):
        lengths = pos + (t if n_valid is None else n_valid)
        with jax.named_scope("paged_attention/pallas"):
            return pallas_paged_attention(
                q, k_pool, v_pool, table, lengths, pos, n_heads=n_heads)
    with jax.named_scope("paged_attention/gather"):
        return paged_attention_gather(q, k_pool, v_pool, table, pos,
                                      n_heads)


@functools.partial(jax.jit,
                   static_argnames=("n_heads", "n_kv_heads", "interpret"))
def pallas_paged_attention_grouped(q, k_pool, v_pool, table, lengths, pos,
                                   *, n_heads: int, n_kv_heads: int,
                                   interpret: bool = False):
    """``q`` (S, t, H, dq); ``k_pool`` (n_pages, page_size, K * dq) and
    ``v_pool`` (n_pages, page_size, K * dv); ``table``, ``lengths``,
    ``pos`` as in :func:`pallas_paged_attention` → (S, t, H * dv) in
    ``q``'s dtype. Query head ``h`` reads key/value head
    ``h // (H / K)``. A slot's ``t * H`` rows that are no whole
    sublane tiles (30 heads) are rounded up to them here: zero query
    rows behind the slot's own, whose output is dropped."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, t, H, dq = q.shape
    P = table.shape[1]
    ps, krow = k_pool.shape[1:]
    vrow = v_pool.shape[2]
    dv = vrow // n_kv_heads
    ppb = max(1, min(P, _BLOCK_KEYS // ps))
    R = _whole_tiles(t * H, q.dtype)
    q = q.reshape(S, t * H, dq)
    if R != t * H:
        q = jnp.pad(q, ((0, 0), (0, R - t * H), (0, 0)))
    kernel = functools.partial(
        _grouped_kernel, n_heads=H, n_kv_heads=n_kv_heads, page_size=ps,
        pages_per_block=ppb, pages_per_slot=P,
        precision=_dot_precision(t, q.dtype))
    rows = lambda width: pl.BlockSpec((1, R, width), lambda s, *_: (s, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S,),
            in_specs=[rows(dq), pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=rows(dv),
            scratch_shapes=[
                pltpu.VMEM((R, krow), q.dtype),         # block-diag q
                pltpu.VMEM((R, 128), jnp.int32),        # last key a row sees
                pltpu.VMEM((R, 128), jnp.float32),      # running max
                pltpu.VMEM((R, 128), jnp.float32),      # running sum
                pltpu.VMEM((R, vrow), jnp.float32),     # accumulator
                pltpu.VMEM((2, ppb * ps, krow), k_pool.dtype),
                pltpu.VMEM((2, ppb * ps, vrow), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((2,), jnp.int32),
            ]),
        out_shape=jax.ShapeDtypeStruct((S, R, dv), q.dtype),
        # (slots in turn on one core, as in ``pallas_paged_attention``)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="pallas_paged_attention_grouped",
    )(lengths.astype(jnp.int32), pos.astype(jnp.int32),
      table.reshape(-1).astype(jnp.int32), q, k_pool, v_pool)
    if R != t * H:
        out = out[:, :t * H]
    return out.reshape(S, t, H * dv)


def grouped_reads_by_table(n_heads: int, n_kv_heads: int, qk_head_dim: int,
                           v_head_dim: int, page_size: int, t: int,
                           dtype) -> bool:
    """:func:`reads_by_table` for :func:`pallas_paged_attention_grouped`:
    on a TPU, a page whole sublane tiles of its dtype, a key row and a
    value head whole lane tiles (a row of the output is one value
    head), and the fast memory within the budget, reckoned on a
    slot's ``t * H`` rows rounded up to whole sublane tiles as the
    wrapper rounds them."""
    rows = _whole_tiles(t * n_heads, dtype)
    return (jax.default_backend() == "tpu"
            and page_size % _sublanes(dtype) == 0
            and (n_kv_heads * qk_head_dim) % 128 == 0
            and v_head_dim % 128 == 0
            and _vmem_bytes(rows, n_kv_heads * qk_head_dim,
                            n_kv_heads * v_head_dim,
                            rows * (qk_head_dim + v_head_dim),
                            page_size, t, dtype) <= _VMEM_BUDGET)


def _latent_kernel(lengths_ref, pos_ref, table_ref, ql_ref, qr_ref, kr_hbm,
                   ckv_hbm, o_ref, qlim_scr, m_scr, l_scr, acc_scr, kr_buf,
                   ckv_buf, sems, state, *, n_heads, scale, page_size,
                   pages_per_block, pages_per_slot, precision):
    """``_kernel`` for the absorbed latent attention: true multi-query
    attention, every row of ``ql_ref`` (1, t * H, rkv) and ``qr_ref``
    (1, t * H, dr) (row ``(j, h)`` at ``j * H + h``) over the ONE key
    head a token has, its latent beside its rotary key. The walk's two
    leaves are the rotary key (its "key") and the latent (its
    "value"): ``scores = (q_lat @ ckv^T + q_rope @ kr^T) * scale``,
    and the values are the latent block itself, so no operand is
    block-diagonal and the accumulator ``o_ref`` (1, t * H, rkv) is
    what ``W_kvb``'s value half is applied to outside."""
    H = n_heads
    s, start, each_block = _page_walk(
        lengths_ref, table_ref, kr_hbm, ckv_hbm, kr_buf, ckv_buf, sems,
        state, page_size=page_size, pages_per_block=pages_per_block,
        pages_per_slot=pages_per_slot)
    start()

    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    row = jax.lax.broadcasted_iota(jnp.int32, qlim_scr.shape, 0)
    qlim_scr[...] = jnp.minimum(pos_ref[s] + jax.lax.div(row, H),
                                lengths_ref[s] - 1)

    def block(i, buf):
        ql, qr = ql_ref[0], qr_ref[0]
        ckv = ckv_buf[buf].astype(ql.dtype)
        kr = kr_buf[buf].astype(qr.dtype)
        dot = lambda q, k: jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        sc = (dot(ql, ckv) + dot(qr, kr)) * scale
        _softmax_block(sc, ckv, i, qlim_scr, m_scr, l_scr, acc_scr,
                       precision)

    each_block(block)

    # (a slot of length 0: zeros, as in ``_kernel``)
    inv = 1.0 / jnp.maximum(l_scr[:, 0:1], 1e-30)
    o_ref[0] = (acc_scr[...] * inv).astype(o_ref.dtype)


def lane_tiled(width: int) -> int:
    """``width`` up to whole lane tiles: the row the latent pool keeps
    a rotary key in (Mosaic will not copy a page of narrower rows)."""
    return -(-width // 128) * 128


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def pallas_paged_attention_latent(q_lat, q_rope, ckv_pool, kr_pool, table,
                                  lengths, pos, *, scale: float,
                                  interpret: bool = False):
    """``q_lat`` (S, t, H, rkv) and ``q_rope`` (S, t, H, dr), the
    absorbed query's two halves; ``ckv_pool`` (n_pages, page_size,
    rkv) and ``kr_pool`` (n_pages, page_size, >= dr: a rotary key and
    zeros past it, ``LatentAttentionLayer.zero_pool``, which the
    query's zeros there meet); ``scale`` the softmax scale (the
    layer's, which carries YaRN's); ``table``, ``lengths``, ``pos`` as
    in :func:`pallas_paged_attention` → (S, t, H, rkv) in ``q_lat``'s
    dtype: each head's weighted sum of the cached latent."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, t, H, rkv = q_lat.shape
    dr = kr_pool.shape[2]
    q_rope = jnp.pad(q_rope, ((0, 0),) * 3 + ((0, dr - q_rope.shape[3]),))
    P = table.shape[1]
    ps = ckv_pool.shape[1]
    ppb = max(1, min(P, _BLOCK_KEYS // ps))
    R = t * H
    kernel = functools.partial(
        _latent_kernel, n_heads=H, scale=scale, page_size=ps,
        pages_per_block=ppb, pages_per_slot=P,
        precision=_dot_precision(t, q_lat.dtype))
    rows = lambda width: pl.BlockSpec((1, R, width), lambda s, *_: (s, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S,),
            in_specs=[rows(rkv), rows(dr),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=rows(rkv),
            scratch_shapes=[
                pltpu.VMEM((R, 128), jnp.int32),        # last key a row sees
                pltpu.VMEM((R, 128), jnp.float32),      # running max
                pltpu.VMEM((R, 128), jnp.float32),      # running sum
                pltpu.VMEM((R, rkv), jnp.float32),      # accumulator
                pltpu.VMEM((2, ppb * ps, dr), kr_pool.dtype),
                pltpu.VMEM((2, ppb * ps, rkv), ckv_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((2,), jnp.int32),
            ]),
        out_shape=jax.ShapeDtypeStruct((S, R, rkv), q_lat.dtype),
        # (slots in turn on one core, as in ``pallas_paged_attention``)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="pallas_paged_attention_latent",
    )(lengths.astype(jnp.int32), pos.astype(jnp.int32),
      table.reshape(-1).astype(jnp.int32), q_lat.reshape(S, R, rkv),
      q_rope.reshape(S, R, dr), kr_pool, ckv_pool)
    return out.reshape(S, t, H, rkv)


def latent_reads_by_table(n_heads: int, kv_lora_rank: int,
                          qk_rope_head_dim: int, page_size: int, t: int,
                          dtype) -> bool:
    """:func:`reads_by_table` for :func:`pallas_paged_attention_latent`:
    on a TPU, a page whole sublane tiles of its dtype, the latent row
    whole lane tiles (the rotary key's row is widened to them in the
    pool), a slot's ``t * H`` rows whole sublane tiles, and the fast
    memory within the budget."""
    sublanes = _sublanes(dtype)
    kr_row = lane_tiled(qk_rope_head_dim)
    return (jax.default_backend() == "tpu"
            and page_size % sublanes == 0
            and kv_lora_rank % 128 == 0
            and (t * n_heads) % sublanes == 0
            and _vmem_bytes(t * n_heads, kr_row, kv_lora_rank,
                            t * n_heads * (2 * kv_lora_rank + kr_row),
                            page_size, t, dtype) <= _VMEM_BUDGET)
