"""What ``trinity_mini_ep16`` NEEDS, from shapes alone: the forward +
backward FLOPs of one training sample (a configuration names this
file in its ``train_flops`` key), the operations and bytes of its
band-aware flash kernels' calls, and its parameters leaf by leaf.

Needed work only: the band a window lets a query see and not the
causal triangle, the selected (row, held expert) pairs and not held x
rows, nothing that the step computes a second time. The pairs are
those of EVEN routing (``expected_pairs``: the model's nominal work,
as a dense model's 6 FLOPs a parameter a token): what the routers
selected in a run is ``moe_pairs_per_step.train``'s to say, and a
step that computes more pairs than these does more than is counted
here."""

from benchmark.harness import counts


def _layers(config):
    """(is window, is dense) of each kept layer, by published index."""
    first = config["first_layer"]
    return [(config["layer_types"][l] == "sliding_attention",
             l < config["published"]["num_dense_layers"])
            for l in range(first, first + config["num_hidden_layers"])]


def visible_pairs(t, window=None):
    """(query, key) pairs a causal attention over ``t`` positions
    computes: query ``i`` sees ``min(i + 1, window)`` keys."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def flash_band(t, window, dh, n_heads, n_kv_heads, itemsize):
    """One attention call over one sequence, ``((fwd flops, fwd
    bytes), (bwd flops, bwd bytes))``: 2 matmuls forward and 5
    backward (``harness/counts.flash_bwd``) of 2 FLOPs a visible pair
    a head value; Q, O and their gradients once a query head, K and V
    (and dK, dV) once a KEY head."""
    pair_flops = 2 * visible_pairs(t, window) * dh * n_heads
    row = t * dh * itemsize
    fwd = (2 * pair_flops, (2 * n_heads + 2 * n_kv_heads) * row)
    bwd = (5 * pair_flops, (4 * n_heads + 4 * n_kv_heads) * row)
    return fwd, bwd


def expected_pairs(config, rows):
    """Selected pairs on held experts of one layer under uniform
    routing: ``rows x top_k x held / router width``."""
    return (rows * config["num_experts_per_tok"] * config["num_experts"]
            / config["router_experts"])


def _attention_params(c):
    d, hd = c["hidden_size"], c["head_dim"]
    q = c["num_attention_heads"] * hd
    kv = c["num_key_value_heads"] * hd
    return {"Wq": d * q, "Wgate": d * q, "Wo": q * d, "Wk": d * kv,
            "Wv": d * kv, "q_norm_gain": hd, "k_norm_gain": hd}


def param_leaves(config):
    """``{path: elements}`` of every parameter leaf of the kept stack,
    as the builder's tree names them."""
    c = config
    d, v, w = c["hidden_size"], c["vocab_size"], c["moe_intermediate_size"]
    out = {"0/W": v * d}
    for i, (_, dense) in enumerate(_layers(c), 1):
        for g in ("norm1_gain", "norm1_post_gain", "norm2_gain",
                  "norm2_post_gain"):
            out[f"{i}/{g}"] = d
        for k, n in _attention_params(c).items():
            out[f"{i}/attn/{k}"] = n
        if dense:
            ff = c["intermediate_size"]
            out.update({f"{i}/Wg": d * ff, f"{i}/Wu": d * ff,
                        f"{i}/Wd": ff * d})
        else:
            held, ws = c["num_experts"], w * c["num_shared_experts"]
            out.update({f"{i}/moe/Wr": d * c["router_experts"],
                        f"{i}/moe/br": c["router_experts"],
                        f"{i}/moe/Wg": held * d * w,
                        f"{i}/moe/Wu": held * d * w,
                        f"{i}/moe/Wd": held * w * d,
                        f"{i}/moe/Wsg": d * ws, f"{i}/moe/Wsu": d * ws,
                        f"{i}/moe/Wsd": ws * d})
    n = len(_layers(c))
    out[f"{n + 1}/gain"] = d
    out[f"{n + 2}/W"] = d * v
    return out


def train_flops(config, traffic):
    """One sequence of ``seq_len`` tokens, forward + backward (3x the
    forward's matmul FLOPs; attention 2 matmuls forward and 4
    backward over the visible pairs)."""
    c, t = config, traffic["inputs"]["seq_len"]
    d, w = c["hidden_size"], c["moe_intermediate_size"]
    a = _attention_params(c)
    proj = sum(a[k] for k in ("Wq", "Wgate", "Wo", "Wk", "Wv"))
    heads_dh = c["num_attention_heads"] * c["head_dim"]
    total = 0.0
    for window, dense in _layers(c):
        matmul = proj
        if dense:
            matmul += 3 * d * c["intermediate_size"]
        else:
            matmul += (d * c["router_experts"]
                       + 3 * d * w * c["num_shared_experts"]
                       + 3 * d * w * expected_pairs(c, 1))
        total += 6 * matmul * t
        total += 6 * 2 * visible_pairs(
            t, c["sliding_window"] if window else None) * heads_dh
    return total + 6 * d * c["vocab_size"] * t


def flash_needed_seconds(config, t, itemsize, peaks):
    """The least time the chip could take over one step's attention
    calls (every kept layer, forward and backward once), and which
    bound sets most of them."""
    c, sec, bound = config, 0.0, {}
    for window, _ in _layers(c):
        for flops, bytes_ in flash_band(
                t, c["sliding_window"] if window else None,
                c["head_dim"], c["num_attention_heads"],
                c["num_key_value_heads"], itemsize):
            s, which = counts.roofline_seconds(flops, bytes_, peaks)
            sec += s
            bound[which] = bound.get(which, 0) + 1
    return sec, max(bound, key=bound.get)
