"""The gated delta rule's step over a pool of matrix states, each
slot's tile read once and written once.

``GatedDeltaMixerLayer.apply_stream_paged`` solves a chunk's ``t`` rows
a slot against the state ``S_0`` the slot holds: ``2 t`` reductions
``S_0^T k_i`` / ``S_0^T q_i`` over the key axis, a forward substitution
over the rows, and the write ``S_t = G_t S_0 + sum_j (G_t / G_j) k_j
u_j^T``. The write needs the reductions' result, so XLA makes two
fusions of it and the pool crosses the chip's memory three times a
step (read, read again, written). This kernel holds a tile between the
reads and the write: the pool crosses twice, what the mathematics
needs.

- the grid is ``(slots, blocks of head-packs)``; the state ``(S, H / p,
  dk, W)`` float32 is read and written AS STORED, ``heads_block`` tiles
  of ``(dk, W)`` a step, and is aliased to its output (donated: nothing
  of its size is allocated). A loop over the block's tiles keeps the
  body one tile long whatever the block;
- the reductions are one product a tile on the MXU, the pack's ``2 t
  p`` key and query rows ``(.., 2 t p, dk)`` as XLA has them against
  the tile, at ``Precision.HIGHEST`` (float32 products, six bfloat16
  passes: Mosaic knows that and the default, one pass, and
  ``tools/measure_delta_state.py`` fails on the chip where the state
  shows the second); row ``n`` of the result is right on its own
  head's lanes and a select picks it. On the vector unit the same reductions need
  every key and query value broadcast over lanes (12 permutes a row a
  head, the permute units' time): read, that body took 0.580 ms a
  call at ``t = 2`` where this one takes 0.486-0.489, and 0.928 at
  ``t = 4`` where this one takes 0.594;
- the write runs on the vector unit in float32, eight key rows (one
  sublane tile) at a time: the keys are turned once a tile (``.T``,
  the transpose unit) so that the key axis lies on sublanes, and a
  column of them is spread over its head's lanes by a broadcast and a
  select;
- what is one number a head and a row (``alpha``, ``beta``, the rows'
  products ``k_j . k_i``, ``k_j . q_i``) comes as scalars in scalar
  memory and is spread over its head's lanes in the body; ``v`` comes
  and ``o`` goes ``(S, t, H / p, W)``, a slot's rows whole in fast
  memory across the slot's steps;
- a fresh slot's tile is dropped by a select where it is USED (the
  reductions' result and the decayed state), so whatever it holds, a
  non-finite value too, is dropped; a slot that fed nothing gets its
  tile back as it was.

Read on the chip (``tools/measure_delta_state.py``, my chip runs,
PR 47; PERF.md section 6 has the table), ``olmo_hybrid_7b``'s pool
``(64, 15, 96, 384)``, 283 MB to read and write a call, 0.346 ms at
the chip's 819 GB/s: 0.479 ms a call at ``t = 1``, 0.489 at ``t = 2``
and 0.594 at ``t = 4`` (591, 579 and 476 GB/s), a mixer's whole step
0.758 / 0.866 / 1.057 ms where the ``jax.numpy`` form's is 0.985 /
1.078 / 1.456. By block of head-packs, ms a call at ``t`` 1 / 2 / 4:
15 (a slot's whole row, 2.2 MB a step) 0.479 / 0.489 / 0.594; 5:
0.483 / 0.515 / 0.602; 1: 0.681 / 0.727 / 0.813, a grid step costing
about 0.25 us: 15 is kept (``_HEADS_BLOCK``).

``delta_state_pass`` says for which calls the layer takes this kernel;
the ``jax.numpy`` form in the layer stays the oracle, the path off a
TPU and the path of every other shape.
"""

from __future__ import annotations

import functools
import operator

import jax
import jax.numpy as jnp

__all__ = ["delta_state_pass", "pallas_delta_state"]

_F32 = jnp.float32
_LANES, _SUBLANES = 128, 8
# head-packs a grid step holds (``_heads_block``): the largest divisor
# of the pool's count up to this, a slot's whole row in
# ``olmo_hybrid_7b`` (the module docstring has the readings)
_HEADS_BLOCK = 15
# the most rows a slot the kernel takes (``delta_state_pass``)
_ROWS_BOUND = 4
# bytes of fast memory the kernel may ask for: a v5e core has 128 MiB.
# ``olmo_hybrid_7b`` asks 8.7 MiB; what this refuses is a block of
# more than 6 M values (15 tiles of (512, 1024), say), which no
# configuration here has: such a pool takes the ``jax.numpy`` form
# where Mosaic would refuse the call
_VMEM_BUDGET = 96 << 20


def _heads_block(packs: int) -> int:
    return max(b for b in range(1, _HEADS_BLOCK + 1) if packs % b == 0)


def _scalars(t: int) -> int:
    """Numbers a head a slot in scalar memory: ``alpha`` and ``beta``
    a row, ``k_j . k_i`` for ``j < i`` and ``k_j . q_i`` for ``j <=
    i``."""
    return 2 * t + t * t


def _vmem_bytes(packs: int, block: int, dk: int, w: int, t: int) -> int:
    """Fast memory the kernel asks for: the state's block in and out
    and a slot's ``v`` and ``o``, two buffers each as the pipeline
    allocates them (the block's keys and queries, 4 KB a tile, are not
    counted)."""
    return 4 * 2 * (2 * block * dk * w + 2 * t * (packs + _SUBLANES) * w)


def delta_state_pass(slots: int, packs: int, dk: int, w: int, t: int,
                     dtype) -> bool:
    """Does a step over a pool ``(slots, packs, dk, w)`` of ``dtype``
    at ``t`` rows a slot take the kernel? On a TPU, for a float32
    state whose minor axis is whole lane tiles and whose key axis is
    whole sublane tiles, and a ``t`` up to ``_ROWS_BOUND``: the body is
    unrolled over the rows (``t`` terms of the write a sublane tile,
    ``t^2`` scalars a head), and 4 is the widest chunk read on the
    chip: there the kernel's time has left the memory's (0.594 ms a
    call against 0.479 at one row) and the step still gains (1.057 ms
    against 1.456); past it nothing was read. The benchmark's one
    cell sends 1 and 2; at 3 the kernel read 0.528 ms a call and the
    step 0.920 against 1.303 (PERF.md section 6). ``slots`` is the
    grid's first axis and bounds nothing: it is here because the
    layer asks with the pool's shape."""
    del slots
    return (jax.default_backend() == "tpu"
            and jnp.dtype(dtype) == _F32
            and w % _LANES == 0 and dk % _SUBLANES == 0
            and 1 <= t <= _ROWS_BOUND
            and _vmem_bytes(packs, _heads_block(packs), dk, w, t)
            <= _VMEM_BUDGET)


def _kernel(restart_ref, fed_ref, sc_ref, kq_ref, v_ref, state_ref,
            o_ref, new_ref, *, t: int, p: int, block: int):
    from jax.experimental import pallas as pl

    s, b = pl.program_id(0), pl.program_id(1)
    dk, w = state_ref.shape[2:]
    dv = w // p
    restart, fed = restart_ref[s] != 0, fed_ref[s] != 0
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1) // dv
    n_sc = _scalars(t)
    total = functools.partial(functools.reduce, operator.add)

    def spread(part):
        """``part(j)``, a value of head ``j`` of the pack that
        broadcasts against ``(.., w)``, over that head's lanes."""
        out = part(0)
        for j in range(1, p):
            out = jnp.where(lane == j, part(j), out)
        return out

    def tile(h, carry):
        g = b * block + h                       # the pack among all
        number = lambda i: spread(
            lambda j: sc_ref[0, 0, (g * p + j) * n_sc + i])
        # rows (which * t + i) * p + j of ``kq``: keys then queries;
        # turned, the key axis lies on sublanes
        cols = kq_ref[0, h].T                   # (dk, rows)
        column = lambda a, i: spread(
            lambda j: cols[a:a + _SUBLANES, i * p + j:i * p + j + 1])
        chunks = range(0, dk, _SUBLANES)
        # the 2 t reductions, every head's rows against the pack's
        # whole tile: row n is right on its own head's lanes
        sums = jax.lax.dot_general(
            kq_ref[0, h], state_ref[0, h], (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=_F32)            # (rows, w)
        read = [[jnp.where(restart, 0.0, spread(
            lambda j: sums[(which * t + i) * p + j:
                           (which * t + i) * p + j + 1, :]))
                 for i in range(t)] for which in range(2)]
        # the forward substitution, (1, w) a value
        pair = iter(range(2 * t, n_sc))
        kk = {(j, i): number(next(pair))
              for i in range(t) for j in range(i)}
        kq = {(j, i): number(next(pair))
              for i in range(t) for j in range(i + 1)}
        G, since, us = 1.0, [], []
        for i in range(t):
            al = number(i)
            G = G * al
            since = [x * al for x in since] + [jnp.ones((1, w), _F32)]
            u = v_ref[0, i, pl.ds(g, 1), :] - G * read[0][i]
            if i:
                u = u - total(since[j] * kk[j, i] * us[j]
                              for j in range(i))
            us.append(number(t + i) * u)
            o_ref[0, i, pl.ds(g, 1), :] = G * read[1][i] + total(
                since[j] * kq[j, i] * us[j] for j in range(i + 1))
        wide = lambda x: jnp.broadcast_to(x, (_SUBLANES, w))
        G = wide(G)
        wu = [wide(since[j] * us[j]) for j in range(t)]
        for a in chunks:
            part = state_ref[0, h, a:a + _SUBLANES, :]
            new = jnp.where(restart, 0.0, G * part)
            for j in range(t):
                new = new + wu[j] * column(a, j)
            new_ref[0, h, a:a + _SUBLANES, :] = jnp.where(fed, new, part)
        return carry

    jax.lax.fori_loop(0, block, tile, 0)


@functools.partial(jax.jit, static_argnames=("heads_block", "interpret"))
def pallas_delta_state(state, k, q, v, alpha, beta, kk, kq, restart, fed,
                       *, heads_block=None, interpret: bool = False):
    """All float32: ``state`` (S, H / p, dk, W), ``p`` heads of ``dv = W
    / p`` values side by side; ``k``, ``q`` (S, t, H, dk); ``v`` (S, t,
    H / p, W); ``alpha``, ``beta`` (S, t, H), already 1 and 0 on a row
    past a slot's last; ``kk``, ``kq`` (S, t, t, H), ``k_j . k_i`` and
    ``k_j . q_i`` at ``[:, j, i]``; ``restart``, ``fed`` (S,) bool ->
    ``(o (S, t, H / p, W), the state after the step)``, the state's
    buffer reused."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, packs, dk, w = state.shape
    t, H = k.shape[1], k.shape[2]
    p = H // packs
    block = heads_block or _heads_block(packs)
    n_sc = _scalars(t)
    # keys then queries, a pack's rows together: (S, packs, 2 t p, dk)
    by_pack = lambda x: x.reshape(S, t, packs, p, dk).transpose(
        0, 2, 1, 3, 4).reshape(S, packs, t * p, dk)
    rows = jnp.concatenate([by_pack(k), by_pack(q)], axis=2)
    rows = jnp.pad(rows, ((0, 0), (0, 0),
                          (0, -rows.shape[2] % _SUBLANES), (0, 0)))
    heads_last = lambda x: jnp.moveaxis(x, -1, 1)        # (S, H, ..)
    upper = [(j, i) for i in range(t) for j in range(i)]
    upto = [(j, i) for i in range(t) for j in range(i + 1)]
    pick = lambda x, pairs: jnp.stack(
        [x[:, j, i] for j, i in pairs], axis=-1).reshape(S, H, len(pairs))
    sc = jnp.concatenate(
        [heads_last(alpha), heads_last(beta)]
        + ([pick(kk, upper)] if upper else []) + [pick(kq, upto)],
        axis=-1).reshape(S, 1, H * n_sc)
    whole = lambda s, b, *_: (s, 0, 0, 0)
    tiles = lambda s, b, *_: (s, b, 0, 0)
    o, new = pl.pallas_call(
        functools.partial(_kernel, t=t, p=p, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S, packs // block),
            in_specs=[pl.BlockSpec((1, 1, H * n_sc),
                                   lambda s, b, *_: (s, 0, 0),
                                   memory_space=pltpu.SMEM),
                      pl.BlockSpec((1, block, rows.shape[2], dk), tiles),
                      pl.BlockSpec((1, t, packs, w), whole),
                      pl.BlockSpec((1, block, dk, w), tiles)],
            out_specs=[pl.BlockSpec((1, t, packs, w), whole),
                       pl.BlockSpec((1, block, dk, w), tiles)]),
        out_shape=[jax.ShapeDtypeStruct((S, t, packs, w), _F32),
                   jax.ShapeDtypeStruct(state.shape, _F32)],
        # operands count the scalar prefetch: the state is the sixth
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_BUDGET + (16 << 20)),
        interpret=interpret,
        name="pallas_delta_state",
    )(restart.astype(jnp.int32), fed.astype(jnp.int32), sc, rows, v, state)
    return o, new
