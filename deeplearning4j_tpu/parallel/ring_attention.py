"""Ring attention: sequence/context parallelism over the device mesh.

The reference (2017-era) handles long sequences only via truncated BPTT
+ masking (SURVEY §5 'long-context'); scaling *attention* across
devices is a required capability extension for the TPU rebuild
(SURVEY §2.3, §7 Stage 5). This module implements blockwise ring
attention (Liu et al. 2023 style): Q/K/V sharded over the ``seq`` mesh
axis; each device computes attention of its Q block against the K/V
block it currently holds while K/V blocks rotate around the ICI ring
via ``ppermute``, with flash-style running-max/denominator accumulation
so the result is EXACT attention at O(T/n) memory per device.

Two interchangeable local-chunk engines drive the ring:

- pure-jnp blockwise accumulation (any backend — the dryrun/CPU path);
- the Pallas flash kernels (``ops/attention.py``) per chunk, FORWARD
  AND BACKWARD (``make_ring_attention_fn(use_kernels='auto')``, the
  TPU default): each chunk returns (o, lse), chunks merge exactly via
  logsumexp weights, and the backward ring feeds the same global lse
  to the dq / fused dk-dv kernels while the dk/dv accumulators rotate
  home with their K/V blocks. Validated against the oracle on real
  TPU (fwd and all three grads).

Also exports ``blockwise_attention`` (single-device chunked attention,
the memory-efficient fallback). The layer-config entry points are
``SelfAttentionLayer`` / ``TransformerEncoderLayer``
(nn/conf/layers/attention.py), which route through
``ring_self_attention`` here whenever the wrapper activates a seq
axis (parallel/seq_context).
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ring_attention", "blockwise_attention", "attention_reference",
           "make_ring_attention_fn", "ring_self_attention"]

logger = logging.getLogger("deeplearning4j_tpu")


def attention_reference(q, k, v, *, causal: bool = False, scale=None):
    """Plain softmax attention (B, T, H, D) — correctness oracle."""
    scale = scale or (1.0 / math.sqrt(q.shape[-1]))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        tq, tk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _block_accum(q, k, v, m_prev, num_prev, den_prev, scale, mask_bias):
    """One flash-attention accumulation step.

    q: (B,Tq,H,D); k,v: (B,Tk,H,D); running (m, num, den) — carried in
    FLOAT32 regardless of the input dtype (bf16 softmax state would
    accumulate unbounded error over long sequences).
    mask_bias: (Tq,Tk) additive bias (0 or -inf) or None.
    """
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    logits = logits.astype(jnp.promote_types(logits.dtype,
                                             jnp.float32))
    if mask_bias is not None:
        logits = logits + mask_bias
    m_cur = jnp.max(logits, axis=-1)                       # (B,H,Tq)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows (m == -inf): exp(-inf - -inf) -> nan
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(logits - m_safe[..., None])
    p = jnp.where(jnp.isneginf(logits), 0.0, p)
    corr = jnp.exp(jnp.where(jnp.isneginf(m_prev), -jnp.inf,
                             m_prev - m_safe))
    corr = jnp.where(jnp.isneginf(m_prev), 0.0, corr)
    num_new = num_prev * corr[..., None] \
        + jnp.einsum("bhqk,bkhd->bhqd", p, v)
    den_new = den_prev * corr + jnp.sum(p, axis=-1)
    return m_new, num_new, den_new


def blockwise_attention(q, k, v, *, block_size: int = 512,
                        causal: bool = False, scale=None):
    """Single-device chunked attention — exact, O(block) memory."""
    scale = scale or (1.0 / math.sqrt(q.shape[-1]))
    B, T, H, D = q.shape
    nblocks = -(-T // block_size)
    pad = nblocks * block_size - T
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # >=f32 accumulators derived from q (+0·x): exact softmax state
    # for bf16 inputs (f64 stays f64 for gradient checking), and the
    # carry inherits q's varying mesh axes when this runs inside a
    # shard_map (e.g. a pipeline stage)
    acc_dt = jnp.promote_types(q.dtype, jnp.float32)
    zero_bht = _varying_zero_bht(q, acc_dt)
    m = jnp.full((B, H, T), -jnp.inf, acc_dt) + zero_bht
    num = jnp.zeros((B, H, T, D), acc_dt) + zero_bht[..., None]
    den = jnp.zeros((B, H, T), acc_dt) + zero_bht
    q_idx = jnp.arange(T)

    def body(i, carry):
        m, num, den = carry
        k_blk = lax.dynamic_slice_in_dim(k, i * block_size, block_size, 1)
        v_blk = lax.dynamic_slice_in_dim(v, i * block_size, block_size, 1)
        k_idx = i * block_size + jnp.arange(block_size)
        bias = jnp.where(k_idx[None, :] < T, 0.0, -jnp.inf)
        if causal:
            bias = bias + jnp.where(k_idx[None, :] <= q_idx[:, None],
                                    0.0, -jnp.inf)
        m, num, den = _block_accum(q, k_blk, v_blk, m, num, den, scale,
                                   bias)
        return m, num, den

    m, num, den = lax.fori_loop(0, nblocks, body, (m, num, den))
    out = num / jnp.maximum(den, 1e-30)[..., None]          # (B,H,T,D)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def _ring_attention_sharded(q, k, v, *, axis_name: str, causal: bool,
                            scale):
    """Runs inside shard_map: q,k,v are the LOCAL (B, T/n, H, D) blocks."""
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    B, Tl, H, D = q.shape
    acc_dt = jnp.promote_types(q.dtype, jnp.float32)
    zero_bht = _varying_zero_bht(q, acc_dt)   # >=f32 softmax state
    m = jnp.full((B, H, Tl), -jnp.inf, acc_dt) + zero_bht
    num = jnp.zeros((B, H, Tl, D), acc_dt) + zero_bht[..., None]
    den = jnp.zeros((B, H, Tl), acc_dt) + zero_bht
    perm = [(i, (i + 1) % n) for i in range(n)]
    q_global = idx * Tl + jnp.arange(Tl)

    def body(step, carry):
        m, num, den, k_cur, v_cur = carry
        src_dev = (idx - step) % n            # whose K/V we now hold
        k_global = src_dev * Tl + jnp.arange(Tl)
        if causal:
            bias = jnp.where(k_global[None, :] <= q_global[:, None],
                             0.0, -jnp.inf)
        else:
            bias = None
        m, num, den = _block_accum(q, k_cur, v_cur, m, num, den, scale,
                                   bias)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return m, num, den, k_nxt, v_nxt

    m, num, den, _, _ = lax.fori_loop(
        0, n, body, (m, num, den, k, v))
    out = num / jnp.maximum(den, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


# ---------------------------------------------------------------------------
# ring FLASH attention: the Pallas kernels drive each local chunk, in
# BOTH directions. Per ring step a device computes its q block against
# the K/V chunk it currently holds with the hand kernel; per-chunk
# (o, lse) pairs merge with logsumexp weights (associative, so a
# running merge is exact). The backward ring reuses the dq / fused
# dk-dv kernels with the GLOBAL lse — p = exp(s - lse) is already the
# correct global softmax weight per tile — and the dk/dv accumulators
# ROTATE with the K/V chunks, arriving home after the full cycle.
# ---------------------------------------------------------------------------

def _merge_chunks(o_a, lse_a, o_b, lse_b):
    """Merge two partial attention results (o: (B,T,H,D),
    lse: (B,H,T)). Exact: o = Σ o_i · exp(lse_i − lse_total)."""
    lse = jnp.logaddexp(lse_a, lse_b)
    # fully-empty chunks carry lse = -inf: weight 0, never nan
    wa = jnp.where(jnp.isneginf(lse_a), 0.0, jnp.exp(lse_a - lse))
    wb = jnp.where(jnp.isneginf(lse_b), 0.0, jnp.exp(lse_b - lse))
    to_btH = lambda w: jnp.moveaxis(w, 1, 2)[..., None]   # (B,T,H,1)
    # accumulate in f32, return in the carry dtype — bf16 inputs must
    # not promote the fori_loop carry (trace-time dtype mismatch)
    o = (o_a.astype(jnp.float32) * to_btH(wa)
         + o_b.astype(jnp.float32) * to_btH(wb))
    return o.astype(o_a.dtype), lse


def _jnp_chunk(q, k, v, causal, kmask=None):
    """Pure-jnp (o, lse) for one chunk — the kernel's test double and
    the CPU-path equivalent; same math, same outputs. ``kmask``:
    optional (B, Tk) 0/1 key-padding chunk (masked keys leave the
    softmax)."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        T = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None],
                      s, -jnp.inf)
    if kmask is not None:
        s = jnp.where(kmask[:, None, None, :] > 0, s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)                     # (B,H,Tq)
    p = jnp.exp(s - jnp.where(jnp.isneginf(lse), 0.0, lse)[..., None])
    p = jnp.where(jnp.isneginf(s), 0.0, p)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o.astype(q.dtype), lse


def _jnp_chunk_bwd(q, k, v, o, lse, do, causal, kmask=None):
    """Pure-jnp per-chunk backward with the GLOBAL lse — mirrors the
    Pallas dq/dk/dv kernel math exactly (masked keys recompute to
    p = 0, so no gradient leaks through them)."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    f32 = lambda a: a.astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", f32(q), f32(k)) * scale
    if causal:
        T = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None],
                      s, -jnp.inf)
    if kmask is not None:
        s = jnp.where(kmask[:, None, None, :] > 0, s, -jnp.inf)
    p = jnp.exp(s - jnp.where(jnp.isneginf(lse), 0.0, lse)[..., None])
    p = jnp.where(jnp.isneginf(s), 0.0, p)
    delta = jnp.einsum("bqhd,bqhd->bhq", f32(do), f32(o))
    dp = jnp.einsum("bqhd,bkhd->bhqk", f32(do), f32(v))
    ds = p * (dp - delta[..., None]) * scale
    dq = jnp.einsum("bhqk,bkhd->bqhd", ds, f32(k)).astype(q.dtype)
    dk = jnp.einsum("bhqk,bqhd->bkhd", ds, f32(q)).astype(k.dtype)
    dv = jnp.einsum("bhqk,bqhd->bkhd", p, f32(do)).astype(v.dtype)
    return dq, dk, dv


def _varying_zero_bht(q, dtype=jnp.float32):
    """A (B, H, Tl) zero derived from q (+0·x), so it carries q's FULL
    varying-axes set — under a dp×sp mesh the batch varies over
    ('data','seq'), not just the ring axis, and fori_loop carry /
    lax.switch branch types must line up (jax>=0.9 VMA typing)."""
    return (0.0 * jnp.moveaxis(q[..., 0], 1, 2)).astype(dtype)


def _chunk_branches(causal, impl, masked=False):
    """(full, diagonal, skip) forward branches for one ring chunk.
    The kernel's causal flag is static, so the runtime three-way
    (src before / at / after my block) is a lax.switch over
    statically-compiled variants. impl: 'pallas' (TPU kernels) or
    'jnp' (test double / CPU). ``masked``: branches additionally take
    the (B, Tk) key-padding chunk that rotates with its K/V block."""
    from deeplearning4j_tpu.ops.attention import pallas_flash_attention

    def _run(q, k, v, km, c):
        if impl == "jnp":
            return _jnp_chunk(q, k, v, c, km)
        return pallas_flash_attention(q, k, v, km, causal=c,
                                      block_q=_blk(q), block_k=_blk(q),
                                      return_lse=True)

    def skip(q, k, v, *_):        # one body serves both arities
        B, T, H, D = q.shape
        return (jnp.zeros_like(q),
                jnp.full((B, H, T), -jnp.inf, jnp.float32)
                + _varying_zero_bht(q))

    if masked:
        def full(q, k, v, km):
            return _run(q, k, v, km, False)

        def diag(q, k, v, km):
            return _run(q, k, v, km, causal)
    else:
        def full(q, k, v):
            return _run(q, k, v, None, False)

        def diag(q, k, v):
            return _run(q, k, v, None, causal)

    return full, diag, skip


def _blk(q):
    from deeplearning4j_tpu.ops.attention import _auto_block
    return _auto_block(q.shape[1], q.shape[3])


def _ring_flash_sharded(q, k, v, kmask=None, *, axis_name: str,
                        causal: bool, impl: str = "pallas"):
    """Forward ring with Pallas local chunks; returns (o, lse).
    ``kmask``: optional LOCAL (B, T/n) key-padding chunk — it rotates
    around the ring WITH its K/V block."""
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    B, Tl, H, D = q.shape
    masked = kmask is not None
    full, diag, skip = _chunk_branches(causal, impl, masked=masked)
    perm = [(i, (i + 1) % n) for i in range(n)]
    o = jnp.zeros_like(q)            # zeros_like(q): already varying
    lse = (jnp.full((B, H, Tl), -jnp.inf, jnp.float32)
           + _varying_zero_bht(q))

    def body(step, carry):
        o, lse, k_cur, v_cur, km_cur = carry
        src = (idx - step) % n
        ops = (q, k_cur, v_cur) + ((km_cur,) if masked else ())
        if causal:
            branch = jnp.where(src < idx, 0, jnp.where(src == idx,
                                                       1, 2))
            o_c, lse_c = lax.switch(branch, (full, diag, skip), *ops)
        else:   # every chunk is a full chunk: no switch, one kernel
            o_c, lse_c = full(*ops)
        o, lse = _merge_chunks(o, lse, o_c, lse_c)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        km_nxt = (lax.ppermute(km_cur, axis_name, perm) if masked
                  else km_cur)
        return o, lse, k_nxt, v_nxt, km_nxt

    km0 = kmask if masked else jnp.zeros((), q.dtype)
    o, lse, _, _, _ = lax.fori_loop(0, n, body, (o, lse, k, v, km0))
    return o, lse


def _ring_flash_bwd_sharded(q, k, v, o, lse, do, kmask=None, *,
                            axis_name: str, causal: bool,
                            impl: str = "pallas"):
    """Backward ring: the dq / fused dk-dv Pallas kernels per chunk
    with the GLOBAL lse; dk/dv accumulators (and the mask chunk, when
    present) rotate with k/v."""
    from deeplearning4j_tpu.ops.attention import (
        pallas_flash_attention_bwd)
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    blk = _blk(q)
    masked = kmask is not None

    def _run_bwd(q, k, v, o, lse, do, km, c):
        if impl == "jnp":
            return _jnp_chunk_bwd(q, k, v, o, lse, do, c, km)
        return pallas_flash_attention_bwd(q, k, v, o, lse, do, km,
                                          causal=c, block_q=blk,
                                          block_k=blk)

    def bwd_skip(q, k, v, *_):    # one body serves both arities
        return (jnp.zeros_like(q), jnp.zeros_like(k),
                jnp.zeros_like(v))

    if masked:
        def bwd_full(q, k, v, o, lse, do, km):
            return _run_bwd(q, k, v, o, lse, do, km, False)

        def bwd_diag(q, k, v, o, lse, do, km):
            return _run_bwd(q, k, v, o, lse, do, km, causal)
    else:
        def bwd_full(q, k, v, o, lse, do):
            return _run_bwd(q, k, v, o, lse, do, None, False)

        def bwd_diag(q, k, v, o, lse, do):
            return _run_bwd(q, k, v, o, lse, do, None, causal)

    # zeros_like of the (varying) inputs: accumulators start varying
    dq = jnp.zeros_like(q)
    dkr = jnp.zeros_like(k)
    dvr = jnp.zeros_like(v)

    def body(step, carry):
        dq, dkr, dvr, k_cur, v_cur, km_cur = carry
        src = (idx - step) % n
        ops = (q, k_cur, v_cur, o, lse, do) + (
            (km_cur,) if masked else ())
        if causal:
            branch = jnp.where(src < idx, 0, jnp.where(src == idx,
                                                       1, 2))
            dq_c, dk_c, dv_c = lax.switch(
                branch, (bwd_full, bwd_diag, bwd_skip), *ops)
        else:
            dq_c, dk_c, dv_c = bwd_full(*ops)
        dq = dq + dq_c
        dkr = dkr + dk_c
        dvr = dvr + dv_c
        # rotate K/V and their gradient accumulators together — after
        # the full cycle (n rotations) each dk/dv is back at its owner
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        dk_nxt = lax.ppermute(dkr, axis_name, perm)
        dv_nxt = lax.ppermute(dvr, axis_name, perm)
        km_nxt = (lax.ppermute(km_cur, axis_name, perm) if masked
                  else km_cur)
        return dq, dk_nxt, dv_nxt, k_nxt, v_nxt, km_nxt

    km0 = kmask if masked else jnp.zeros((), q.dtype)
    dq, dkr, dvr, _, _, _ = lax.fori_loop(
        0, n, body, (dq, dkr, dvr, k, v, km0))
    return dq, dkr, dvr


def _make_ring_flash_inner(axis_name: str, causal: bool,
                           impl: str = "pallas"):
    @functools.partial(jax.custom_vjp)
    def ring_flash(q, k, v):
        o, _ = _ring_flash_sharded(q, k, v, axis_name=axis_name,
                                   causal=causal, impl=impl)
        return o

    def fwd(q, k, v):
        o, lse = _ring_flash_sharded(q, k, v, axis_name=axis_name,
                                     causal=causal, impl=impl)
        return o, (q, k, v, o, lse)

    def bwd(res, g):
        q, k, v, o, lse = res
        return _ring_flash_bwd_sharded(q, k, v, o, lse, g,
                                       axis_name=axis_name,
                                       causal=causal, impl=impl)

    ring_flash.defvjp(fwd, bwd)
    return ring_flash


def _make_ring_flash_masked(axis_name: str, causal: bool,
                            impl: str = "pallas"):
    """Masked variant: the key-padding chunk is a 4th operand (data,
    zero cotangent) whose block rotates with its K/V."""
    @functools.partial(jax.custom_vjp)
    def ring_flash(q, k, v, km):
        o, _ = _ring_flash_sharded(q, k, v, km, axis_name=axis_name,
                                   causal=causal, impl=impl)
        return o

    def fwd(q, k, v, km):
        o, lse = _ring_flash_sharded(q, k, v, km, axis_name=axis_name,
                                     causal=causal, impl=impl)
        return o, (q, k, v, km, o, lse)

    def bwd(res, g):
        q, k, v, km, o, lse = res
        dq, dk, dv = _ring_flash_bwd_sharded(
            q, k, v, o, lse, g, km, axis_name=axis_name,
            causal=causal, impl=impl)
        return dq, dk, dv, jnp.zeros_like(km)

    ring_flash.defvjp(fwd, bwd)
    return ring_flash


def ring_self_attention(q, k, v, *, axis_name: str,
                        causal: bool = False, kv_mask=None):
    """Ring flash attention for use INSIDE an existing ``shard_map``
    whose mesh carries ``axis_name``: q, k, v are the LOCAL
    (B, T/n, H, D) blocks of a sequence sharded over that axis; the
    return value is the local block of EXACT global attention, with a
    custom VJP whose backward ring rotates dk/dv home — so it is safe
    to differentiate through inside an SPMD train step.

    This is the entry point ``SelfAttentionLayer`` routes through when
    ``parallel.seq_context`` marks a seq axis active (the wrapper's
    sequence-parallel train step). Kernel selection matches
    ``make_ring_attention_fn(use_kernels='auto')``: Pallas chunks on
    TPU with tile-divisible local lengths, pure-jnp chunks elsewhere.
    ``kv_mask``: optional LOCAL (B, T/n) key-padding chunk — it
    rotates around the ring with its K/V block, so variable-length
    batches train sequence-parallel too (padded QUERY rows stay the
    caller's to zero).
    """
    blk = _blk(q)
    impl = ("pallas" if jax.default_backend() == "tpu" and blk > 0
            else "jnp")
    # the mask kernel tile puts block_k on lanes: Mosaic needs it
    # 128-divisible or equal to the (local) array dim
    if (kv_mask is not None and impl == "pallas"
            and not (blk % 128 == 0 or blk == q.shape[1])):
        impl = "jnp"
    # the choice is recorded, not silent (as ops.attention does)
    logger.debug("ring_self_attention: %s q=%s %s axis=%s causal=%s "
                 "masked=%s", impl, q.shape, q.dtype, axis_name, causal,
                 kv_mask is not None)
    with jax.named_scope(f"ring_self_attention/{impl}"):
        if kv_mask is not None:
            from deeplearning4j_tpu.ops.attention import float_kv_mask
            return _make_ring_flash_masked(axis_name, causal, impl)(
                q, k, v, float_kv_mask(kv_mask))
        return _make_ring_flash_inner(axis_name, causal, impl)(q, k, v)


def make_ring_attention_fn(mesh: Mesh, *, axis: str = "seq",
                           causal: bool = False, scale=None,
                           use_kernels: str = "auto"):
    """Build a jitted ring-attention fn over ``mesh``: inputs
    (B, T, H, D) sharded on T over ``axis``; output sharded the same.

    ``use_kernels``: 'auto' drives each local chunk through the Pallas
    flash kernels (forward AND backward) when running on TPU with
    tile-divisible local lengths and the default 1/sqrt(D) scale;
    'never' keeps the pure-jnp blockwise accumulation (any backend)."""
    spec = P(None, axis, None, None)

    def inner(q, k, v):
        s = scale or (1.0 / math.sqrt(q.shape[-1]))
        use = (use_kernels == "auto"
               and jax.default_backend() == "tpu"
               and scale is None
               and _blk(q) > 0)    # _auto_block returns 0 unless it
                                   # divides the local length
        if use:
            return _make_ring_flash_inner(axis, causal)(q, k, v)
        return _ring_attention_sharded(q, k, v, axis_name=axis,
                                       causal=causal, scale=s)

    sharded = jax.shard_map(inner, mesh=mesh,
                            in_specs=(spec, spec, spec), out_specs=spec)

    @jax.jit
    def fn(q, k, v):
        return sharded(q, k, v)

    return fn


def ring_attention(q, k, v, mesh: Mesh, *, axis: str = "seq",
                   causal: bool = False, scale=None):
    """One-shot convenience wrapper around make_ring_attention_fn."""
    fn = make_ring_attention_fn(mesh, axis=axis, causal=causal,
                                scale=scale)
    spec = NamedSharding(mesh, P(None, axis, None, None))
    q = jax.device_put(q, spec)
    k = jax.device_put(k, spec)
    v = jax.device_put(v, spec)
    return fn(q, k, v)
