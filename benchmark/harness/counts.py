"""Operations and bytes that an algorithm NEEDS, from shapes alone.

2 FLOPs per multiply-add. Recomputed work is not counted. A share of
a roofline or of a peak computed from these may not pass 105 %:
``share_pct`` raises, because then a count here is too high or the
time left out part of the work. Nothing is clipped.
"""


class CountError(ValueError):
    pass


def share_pct(needed_seconds, measured_seconds, what):
    if measured_seconds <= 0:
        raise CountError(f"{what}: no measured time")
    pct = 100.0 * needed_seconds / measured_seconds
    if pct > 105.0:
        raise CountError(
            f"{what}: {pct:.1f} % of the peak -- the operations or "
            "bytes are counted too high, or the time leaves out work")
    return pct


def roofline_seconds(flops, bytes_, peaks):
    """Least time the chip could take, and which bound sets it."""
    tf, tb = flops / peaks["flops_per_s"], bytes_ / peaks["bytes_per_s"]
    return max(tf, tb), ("compute" if tf >= tb else "memory")


# ---- attention kernels, (BH, T, Dh) operands, causal

def flash_fwd(bh, t, dh, itemsize, causal=True):
    """QK^T and PV: 2 matmuls of 2*T*T*Dh each, half under a causal
    mask. Reads Q, K, V; writes O."""
    flops = 2 * (2 * bh * t * t * dh) * (0.5 if causal else 1.0)
    return flops, 4 * bh * t * dh * itemsize


def flash_bwd(bh, t, dh, itemsize, causal=True):
    """The backward pass as FlashAttention-2 counts it: S again, dP,
    dV, dQ, dK = 5 matmuls. Reads Q, K, V, O, dO; writes dQ, dK, dV.
    A split into a dq and a dkv kernel that each rebuild S and dP
    still NEEDS only this."""
    flops = 5 * (2 * bh * t * t * dh) * (0.5 if causal else 1.0)
    return flops, 8 * bh * t * dh * itemsize


# ---- shape primitives of whole-model counts (benchmark/counts/)

def transformer_layer_fwd_flops(d, ff, t):
    """Forward FLOPs of one block on one sequence (the hand-worked
    test case): QKV+O 4 d*d, MLP 2 d*ff, causal attention."""
    return 2 * t * (4 * d * d + 2 * d * ff) + 2 * (2 * t * t * d) * 0.5


def conv_flops(h_out, w_out, kh, kw, c_in, c_out):
    return 2 * h_out * w_out * kh * kw * c_in * c_out


def bottleneck_fwd_flops(h, w, c_in, mid, c_out, stride, project):
    """One bottleneck residual block (1x1 -> 3x3 -> 1x1, stride on the
    first 1x1 as the program's zoo model places it), forward."""
    ho, wo = h // stride, w // stride
    f = (conv_flops(ho, wo, 1, 1, c_in, mid)
         + conv_flops(ho, wo, 3, 3, mid, mid)
         + conv_flops(ho, wo, 1, 1, mid, c_out))
    if project:
        f += conv_flops(ho, wo, 1, 1, c_in, c_out)
    return f
