"""The held experts' pass over the selected (row, expert) pairs alone.

``SparseExpertsLayer``'s dense pass sends every row through every held
expert and lets a combine weight of 0 discard what was not selected:
``N x held`` row-expert products for ``N x top_k`` selected pairs, and
every held expert's weights read whether a row picked it or not. Up to
128 rows the arithmetic is free, the MXU waits for the experts'
weights either way; past it the pass is arithmetic (``N`` FLOP a
weight byte against the chip's 240). This kernel computes the selected
pairs alone, one held expert after another:

- the grid is ``(held, width tiles)``; ``Wg``, ``Wu`` ``(held, d, w)``
  and ``Wd`` ``(held, w, d)`` are read AS STORED, a ``(d, tw)`` /
  ``(tw, d)`` block a step, each hit expert's weights once. An expert
  no row selected maps its blocks onto the block the step before it
  fetched (scalar prefetch, ``_blocks``): the pipeline fetches nothing
  new and the body is skipped, so its weights are not read at all;
- the step's rows ``x`` ``(N, d)`` sit whole in fast memory. An
  expert's rows are picked out of them by a 0/1 matrix on the MXU
  (``sel @ x``, exact: one 1 a row), a tile of at most ``row_tile``
  rows at a time, so the MXU's time follows the weights and not ``N``;
- ``g``, ``u`` and the down projection accumulate in float32, the
  activation is float32, ``silu(g) * u`` is rounded once to the
  operands' dtype before the down projection: ``einsum_f32``'s
  arithmetic, tile by tile;
- the combine goes back to rows one pair at a time on the vector unit:
  the group's row ids and the rows' combine weights are scalars
  (``rowid``, ``comb`` in scalar memory), and row ``p`` of the tile
  times its weight is added in float32 to the result's row
  ``rowid[p]``, so the work follows the pairs the share holds, not
  ``N x top_k`` (a 0/1 matmul back to ``N`` rows costs ``N x d``
  products an expert whatever it holds, and three of them to carry
  float32: measured 0.68 ms a layer at ``d`` 6144, ``N`` 256).

Everything the kernel needs of the routing comes as dense ``(held, N)``
arrays that XLA makes with whole-array arithmetic (``_groups``: no
sort, no gather, no scatter): a row's position inside its expert's
group is a strictly-lower-triangular 0/1 matmul, and the group's row
ids are that position compared against an iota and summed.

``grouped_pass`` says for which calls the layer takes this kernel; the
dense pass stays the oracle, the path off a TPU and the path of every
other small call.

That kernel keeps all rows in fast memory and has no backward pass: a
serving step's. Off a serving step, past an MXU tile of rows
(``pairs_pass``), the held experts' part is ``pairs_experts``: the
(row, pick) pairs sorted by expert, the picked rows gathered, three
grouped matrix products over the groups (``jax.lax.ragged_dot``: read
on the chip beside the grouped-matmul kernels JAX ships, it took 7.86
ms a layer against their 7.15 and needs no second route off a TPU),
the result weighted and scatter-added in float32. It differentiates
(rows, combine weights, all three weights), keeps nothing between its
forward and backward pass but its inputs, and has no capacity: the
sorted pairs are walked in chunks of ``N`` pairs, as many as the
routing filled: one under even routing, up to ``min(top_k, held)``
where every row picks only held experts, so no pair is dropped at any
skew.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

__all__ = ["grouped_pass", "pallas_grouped_experts", "weight_bound",
           "pairs_pass", "pairs_experts"]

_F32 = jnp.float32
# rows of an expert's group a tile: the MXU holds a 128 x 128 weight
# tile for as long as it takes to load the next, so up to 128 rows
# pass through it in the weights' own time
_ROW_TILE = 128
# columns of ``d`` a matmul of the body produces at a time: bounds the
# float32 temporaries Mosaic keeps beside the buffers
_COLS = 512
# columns of the experts' width a grid step reads (``_width_tile``):
# four lane tiles. Read, one layer at 256 rows (my chip runs, PR 43):
# ``lfm2_24b_a2b`` 1.671 / 1.683 / 1.734 ms at 512 / 768 / 1536
# columns (the widest is one step an expert, and leaves the first
# block's fetch exposed), ``longcat_ep32`` 1.59 / 1.91 ms at 512 / 256
_WIDTH_TILE = 512
# bytes of fast memory the kernel may ask for (``_vmem_bytes``): a v5e
# core has 128 MiB
_VMEM_BUDGET = 96 << 20
# the most 128 x 128 weight-tile passes the kernel's unrolled body may
# spell out and still run in its weights' streaming time
# (``weight_bound``): the largest count read flat
_WEIGHT_BOUND_PASSES = 2048
# the share of the held experts no row is expected to pick from which
# the kernel pays under an MXU tile of rows (``grouped_pass``): three
# times what it costs over the dense pass when every expert is hit
_UNHIT_SHARE = 0.1


def _width_tile(w: int) -> int:
    """The widest tile of the experts' width of whole lane tiles that
    divides ``w``, up to ``_WIDTH_TILE``; ``w`` itself where there is
    none (a width the predicate refuses: the interpreted kernel's
    small shapes)."""
    return max((tw for tw in range(128, _WIDTH_TILE + 1, 128)
                if w % tw == 0), default=w)


def _vmem_bytes(n: int, d: int, w: int, itemsize: int) -> int:
    """Fast memory the kernel asks for at ``n`` rows: the rows and the
    float32 result (two buffers each, as the pipeline allocates
    them), the gathered rows, the down projection's accumulator, the
    three weight blocks (two buffers each), and the body's
    temporaries (``g``, ``u``, their product, a matmul's ``_COLS``
    columns)."""
    tw = _width_tile(w)
    rows = n * d * (2 * itemsize + 2 * 4 + itemsize + 4)
    weights = 6 * d * tw * itemsize
    temps = _ROW_TILE * (3 * tw + 2 * _COLS) * 4
    return rows + weights + temps


def grouped_pass(n: int, top_k: int, router_width: int, d: int, w: int,
                 dtype) -> bool:
    """Does a call of ``n`` rows, ``top_k`` picks a row over a router
    of ``router_width``, through held experts of ``(d, w)`` take the
    grouped kernel? On a TPU, for bfloat16 operands whose
    widths are whole lane tiles, rows whole row tiles and buffers
    within the fast memory (``_vmem_bytes``), in two cases:

    - the rows pass one MXU tile (``n > 128``). Up to 128 rows the
      dense pass costs its weights' streaming time; past it, ``n``
      FLOP a weight byte against the chip's 240. One layer, dense /
      grouped, ms (``tools/measure_expert_pass.py``, my chip runs,
      PR 43; PERF.md section 6 has the table): ``lfm2_24b_a2b`` 1.641 /
      1.655 at 64 rows, 1.649 / 1.654 at 128, 2.033 / 1.666 at 256,
      3.663 / 1.684 at 512; ``mimo_v25_ep16`` 1.143 / 1.136 at 128,
      1.393 / 1.160 at 256; ``axk1_ep16`` 1.596 / 1.610 at 128, 1.844 /
      1.661 at 256;
    - fewer rows, but a tenth or more of the held experts expected
      unpicked (``exp(-n top_k / router_width)`` under uniform
      routing: every pick lands on a given expert with ``1 /
      router_width``, whatever share is held): the kernel reads hit
      experts alone and the dense pass all of them. With every expert
      hit the kernel is level or up to 3 % behind (``axk1_ep16`` at 64
      rows 1.540 / 1.584, expected unpicked 7 %: dense);
      ``longcat_ep32`` at 128 rows (13.5 %) reads 1.705 / 1.616 with
      15 of 16 hit, at 32 rows 1.655 / 0.986 with 9 of 16.

    How many experts are held does not enter: both passes are linear
    in it."""
    rows_tile = n % _ROW_TILE == 0 if n > _ROW_TILE else n % 16 == 0
    return (jax.default_backend() == "tpu"
            and jnp.dtype(dtype) == jnp.bfloat16
            and rows_tile and d % 128 == 0 and w % 128 == 0
            and _vmem_bytes(n, d, w, 2) <= _VMEM_BUDGET
            and (n > _ROW_TILE
                 or math.exp(-n * top_k / router_width) >= _UNHIT_SHARE))


def _mxu_passes(n: int, d: int, w: int) -> int:
    """The 128 x 128 weight-tile passes ``_kernel``'s body spells out
    for a grid step at ``n`` rows: the body is unrolled over the row
    tiles, and each tile's branch holds the gather (``sel @ x``:
    ``n / 128`` by ``d / 128`` passes), the gate and up projections and
    the down projection (``d / 128`` by a width tile's lanes each)."""
    return (n // _ROW_TILE) * (d // 128) * (
        n // 128 + 3 * (_width_tile(w) // 128))


def weight_bound(n: int, d: int, w: int) -> bool:
    """Is the kernel's time at ``n`` rows through experts of ``(d,
    w)`` still the time its hit experts' weights take to stream, so
    that rows up to ``n`` ride on weights the pass reads anyway? Past a
    point the time TURNS: 55-90 us more an expert at once, whatever
    the expert count and the width. Read, ms a call over the weights'
    time at 819 GB/s (``tools/measure_expert_pass.py`` and the kernel
    alone on made-up shapes, my chip runs, PR 46; PERF.md section 6
    has the tables), the last rows flat / the first turned, by ``d``:
    1024: 1,024 rows (1.35) / 1,536 (6.6); 2048: 768 (1.14) / 896
    (3.6); 3072: 640 (1.21) / 768 (3.2); 4096: 512 (1.20) / 640 (2.3);
    6144 and 7168: 256 (1.20, 1.29) / 384 (1.54, 1.83); 8192: 256
    (1.18) / 384 (1.68). The kernel's ask of fast memory does NOT
    order them (``_vmem_bytes`` 85 MiB flat at ``d`` 8192, 45 MiB
    turned at 2048), nor do the rows' bytes (4 MiB flat at 4096 and
    8192, 3.5 MiB turned at 2048). What does is the size of the
    unrolled body, ``_mxu_passes``: every reading up to 2,048 passes
    is flat (``mimo_v25_ep16`` at 512 rows is 2,048; ``lfm2_24b_a2b``
    there 1,024) and every one from 2,128 turned (``longcat_ep32`` at
    512 rows is 3,072), which reads like the body outgrowing the
    core's instruction memory; that was not looked into, and a body
    that loops over its row tiles would move the line (ROADMAP S15
    (b))."""
    return _mxu_passes(n, d, w) <= _WEIGHT_BOUND_PASSES


def _groups(sel, comb):
    """``sel`` (N, E) bool, which rows selected which held expert,
    and ``comb`` (N, E) float32, their combine weights -> ``pos``
    (E, N) int32, a row's position inside its expert's group (-1: not
    in it); ``rowid`` (E, N) int32, the group's rows in order (0 past
    its end); ``comb`` (E, N); and ``counts`` (E,) int32."""
    n = sel.shape[0]
    member = sel.T
    # rows before it in the group: a 0/1 matmul, exact in float32
    before = jnp.einsum("en,mn->em", member.astype(jnp.bfloat16),
                        jnp.tri(n, n, -1, dtype=jnp.bfloat16),
                        preferred_element_type=_F32)
    pos = jnp.where(member, before.astype(jnp.int32), -1)
    row = jnp.arange(n, dtype=jnp.int32)
    rowid = jnp.sum(jnp.where(pos[:, None, :] == row[None, :, None],
                              row[None, None, :], 0), axis=2)
    return (pos, rowid, comb.T,
            jnp.sum(member, axis=1, dtype=jnp.int32))


def _blocks(counts, last_tile: int):
    """For every held expert the weight block its grid steps name:
    ``(expert, tile)`` with tile -1 for "the step's own". A hit expert
    names itself; one with no row names the block the step before it
    left in the buffers (the last hit expert's last tile; ahead of the
    first hit expert, that one's first tile), so nothing is fetched
    for it."""
    e = counts.shape[0]
    hit = counts > 0
    idx = jnp.arange(e, dtype=jnp.int32)
    # the last hit expert at or before each (-1: none yet)
    prev = jnp.max(jnp.where(hit[None, :] & (idx[None, :] <= idx[:, None]),
                             idx[None, :], -1), axis=1)
    first = jnp.argmax(hit).astype(jnp.int32)
    expert = jnp.where(prev >= 0, prev, first)
    tile = jnp.where(hit, -1, jnp.where(prev >= 0, last_tile, 0))
    return expert, tile.astype(jnp.int32)


def _kernel(expert_ref, tile_ref, counts_ref, pos_ref, rowid_ref, comb_ref,
            x_ref, wg_ref, wu_ref, wd_ref, o_ref, xs_scr, y_scr, *,
            row_tile: int):
    from jax.experimental import pallas as pl

    del expert_ref, tile_ref        # the index maps' operands
    e, j = pl.program_id(0), pl.program_id(1)
    n, d = x_ref.shape
    cols = [slice(c, min(c + _COLS, d)) for c in range(0, d, _COLS)]

    @pl.when((e == 0) & (j == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    count = counts_ref[e]
    for t in range(n // row_tile):
        first = t * row_tile
        rows = slice(first, first + row_tile)

        @pl.when(count > first)
        def _(first=first, rows=rows):
            @pl.when(j == 0)
            def _():
                # row r of the tile is the group's row first + r
                want = first + jax.lax.broadcasted_iota(
                    jnp.int32, (row_tile, n), 0)
                sel = jnp.where(pos_ref[0] == want, 1.0, 0.0).astype(
                    x_ref.dtype)
                for c in cols:
                    xs_scr[rows, c] = jnp.dot(
                        sel, x_ref[:, c],
                        preferred_element_type=_F32).astype(xs_scr.dtype)
                y_scr[rows, :] = jnp.zeros((row_tile, d), _F32)

            xt = xs_scr[rows, :]
            g = jnp.dot(xt, wg_ref[0], preferred_element_type=_F32)
            u = jnp.dot(xt, wu_ref[0], preferred_element_type=_F32)
            h = (jax.nn.silu(g) * u).astype(xt.dtype)
            for c in cols:
                y_scr[rows, c] += jnp.dot(
                    h, wd_ref[0, :, c], preferred_element_type=_F32)

            @pl.when(j == pl.num_programs(1) - 1)
            def _():
                def pair(p, carry):
                    row = rowid_ref[0, 0, first + p]
                    o_ref[pl.ds(row, 1), :] += (
                        y_scr[pl.ds(first + p, 1), :]
                        * comb_ref[0, 0, row])
                    return carry

                jax.lax.fori_loop(
                    0, jnp.minimum(count - first, row_tile), pair, 0)


@functools.partial(jax.jit,
                   static_argnames=("row_tile", "width_tile", "interpret"))
def pallas_grouped_experts(x, sel, comb, w_gate, w_up, w_down, *,
                           row_tile: int = _ROW_TILE, width_tile=None,
                           interpret: bool = False):
    """``x`` (N, d); ``sel`` (N, E) bool, the rows that selected each
    held expert; ``comb`` (N, E) float32, their combine weights;
    ``w_gate``, ``w_up`` (E, d, w) and ``w_down`` (E, w, d) in ``x``'s
    dtype -> (N, d) float32: ``sum_e comb[n, e] * swiglu_e(x[n])`` over
    the selected pairs. ``N`` is whole ``row_tile``s (at most 128 a
    tile), ``w`` whole ``width_tile``s."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, d = x.shape
    e, _, w = w_gate.shape
    row_tile = min(row_tile, n)
    tw = width_tile or _width_tile(w)
    tiles = w // tw
    pos, rowid, comb, counts = _groups(sel, comb)
    expert, tile = _blocks(counts, tiles - 1)

    def block(i, j, expert_ref, tile_ref, _):
        own = tile_ref[i] < 0
        return expert_ref[i], jnp.where(own, j, tile_ref[i])

    def up(i, j, *refs):
        ex, tl = block(i, j, *refs)
        return ex, 0, tl

    def down(i, j, *refs):
        ex, tl = block(i, j, *refs)
        return ex, tl, 0

    group = lambda i, j, *_: (i, 0, 0)
    scalars = pl.BlockSpec((1, 1, n), group, memory_space=pltpu.SMEM)
    whole = lambda i, j, *_: (0, 0)
    return pl.pallas_call(
        functools.partial(_kernel, row_tile=row_tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(e, tiles),
            in_specs=[pl.BlockSpec((1, 1, n), group),       # pos
                      scalars, scalars,                     # rowid, comb
                      pl.BlockSpec((n, d), whole),          # x
                      pl.BlockSpec((1, d, tw), up),
                      pl.BlockSpec((1, d, tw), up),
                      pl.BlockSpec((1, tw, d), down)],
            out_specs=pl.BlockSpec((n, d), whole),
            scratch_shapes=[pltpu.VMEM((n, d), x.dtype),    # gathered rows
                            pltpu.VMEM((n, d), _F32)]),     # down's sum
        out_shape=jax.ShapeDtypeStruct((n, d), _F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_BUDGET + (16 << 20)),
        interpret=interpret,
        name="pallas_grouped_experts",
    )(expert, tile, counts, pos[:, None, :], rowid[:, None, :],
      comb[:, None, :], x, w_gate, w_up, w_down)


# ---------------------------------------------------------------------
# the pairs pass: whole sequences, forward and backward
# ---------------------------------------------------------------------

def pairs_pass(n: int) -> bool:
    """Does a call of ``n`` rows that is no serving step run the held
    experts over the selected pairs alone? Past one MXU tile of rows:
    up to there the dense pass costs its weights' streaming time,
    past it ``held / (top_k held / router_width)`` times the
    arithmetic (16x at 8 of 128 held and 8 picks a row) and as many
    ``(held, n, w)`` float32 temporaries."""
    return n > _ROW_TILE


def _plan(local, held: int, size: int):
    """``local`` (N, k) int32, a pick's held expert or ``held`` for a
    pick this share does not compute -> the pairs in expert order
    (N k, whole chunks of ``size`` = N); the groups' bounds in that
    order (held + 1,); how many chunks the share's pairs fill (one at
    least)."""
    flat = local.reshape(-1)
    order = jnp.argsort(flat).astype(jnp.int32)
    counts = jnp.sum(flat[:, None] == jnp.arange(held, dtype=flat.dtype),
                     axis=0, dtype=jnp.int32)
    bounds = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)])
    return order, bounds, jnp.maximum(-(-bounds[-1] // size), 1)


def _chunk(i, order, bounds, size: int, k: int):
    """Chunk ``i`` of the sorted pairs: their rows, their places in
    the (N k,) combine weights, which of them are pairs at all, and
    the groups' sizes inside the chunk."""
    lo = i * size
    pair = jax.lax.dynamic_slice(order, (lo,), (size,))
    valid = lo + jnp.arange(size, dtype=jnp.int32) < bounds[-1]
    cut = jnp.clip(bounds, lo, lo + size)
    return pair // k, pair, valid, cut[1:] - cut[:-1]


def _chunk_rows(x, w, w_gate, w_up, w_down, rows, pair, valid, sizes):
    """One chunk of pairs -> (chunk, d) float32, each pair's expert
    over its row times its combine weight: what is added to the
    result's rows ``rows``."""
    keep = valid[:, None]

    def dot(lhs, rhs):
        # rows past the groups' end are no group's: on a TPU the
        # product leaves them as the buffer was, and so does its
        # transpose in the backward pass (read on the chip: a first
        # gradient 5e6 times the reference's). Masked on both sides,
        # so neither pass reads them.
        return jnp.where(keep, jax.lax.ragged_dot(
            jnp.where(keep, lhs, 0), rhs, sizes,
            preferred_element_type=_F32), 0)

    xs = x[rows]
    h = (jax.nn.silu(dot(xs, w_gate)) * dot(xs, w_up)).astype(x.dtype)
    return dot(h, w_down) * jnp.where(
        valid, w.reshape(-1)[pair], 0.0)[:, None]


def _over_chunks(x, local, held: int, body, init):
    """``body(chunk's places, carry)`` over the chunks the routing
    filled (:func:`_plan`, :func:`_chunk`). The first chunk, the only
    one unless the routing is skewed past ``N`` pairs, is in the
    caller's own computation and not in a loop's body (a profiler
    trace names its ops with the step's); the loop over the others
    then runs no turn."""
    size, k = x.shape[0], local.shape[1]
    order, bounds, n_chunks = _plan(local, held, size)
    turn = lambda i, carry: body(_chunk(i, order, bounds, size, k),
                                 carry)
    return jax.lax.fori_loop(1, n_chunks, turn, turn(0, init))


@jax.custom_vjp
def pairs_experts(x, local, w, w_gate, w_up, w_down):
    """``x`` (N, d); ``local`` (N, k) int32, each pick's held expert
    (0 .. E-1) or E for a pick to leave out (an absent expert's, an
    inactive row's); ``w`` (N, k) float32 combine weights; ``w_gate``,
    ``w_up`` (E, d, w) and ``w_down`` (E, w, d) -> (N, d) float32:
    ``sum_j w[n, j] swiglu_{local[n, j]}(x[n])`` over the picks kept.
    ``einsum_f32``'s arithmetic: float32 sums and activation,
    ``silu(g) * u`` rounded once to ``x``'s dtype."""
    def body(where, out):
        return out.at[where[0]].add(_chunk_rows(
            x, w, w_gate, w_up, w_down, *where))

    return _over_chunks(x, local, w_gate.shape[0], body,
                        jnp.zeros(x.shape, _F32))


def _pairs_fwd(x, local, w, w_gate, w_up, w_down):
    return (pairs_experts(x, local, w, w_gate, w_up, w_down),
            (x, local, w, w_gate, w_up, w_down))


def _pairs_bwd(res, g):
    x, local, w, w_gate, w_up, w_down = res
    diff = (x, w, w_gate, w_up, w_down)

    def body(where, acc):
        _, vjp = jax.vjp(lambda *a: _chunk_rows(*a, *where), *diff)
        return jax.tree_util.tree_map(jnp.add, acc, vjp(g[where[0]]))

    dx, dw, dg, du, dd = _over_chunks(
        x, local, w_gate.shape[0], body,
        jax.tree_util.tree_map(jnp.zeros_like, diff))
    return dx, None, dw, dg, du, dd


pairs_experts.defvjp(_pairs_fwd, _pairs_bwd)
