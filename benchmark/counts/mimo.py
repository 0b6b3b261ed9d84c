"""Bytes that one step of a MiMo-V2 shaped share reads (a
configuration names this file in its ``serve_step_bytes`` key): every
weight but the embedding table once, whoever is in the batch (the
expert layer passes every row through EVERY held expert, so all their
weights are read, hit or not), the embedding rows, and the key/value
rows a step NEEDS of the slots that stepped: in a global layer the
positions a slot holds, in a window layer no more than the window of
them. Stored bytes are bfloat16.

What the program moves beyond that is not needed and not counted: its
global layers gather every slot's whole table and its window layers a
slot's whole ring. The cache rows are counted a step a token, as
``counts/axk1.py`` counts them: a chunk step reads a slot's rows once
for its t tokens, which overstates the cache's part (about 3 % of a
step's bytes at this configuration's sizes)."""

from benchmark.harness import inputs

ITEM = 2


def layer_kinds(config):
    """Per layer of the cut, (window layer?, expert layer?)."""
    n = config["num_hidden_layers"]
    return list(zip(map(bool, config["hybrid_layer_pattern"][:n]),
                    map(bool, config["moe_layer_freq"][:n])))


def attention_params(config, window):
    """One attention layer with its input norm: Wq, Wk, Wv, Wo, the
    gain, and a sink logit a head where the kind has one."""
    swa = "swa_" if window else ""
    d, h = config["hidden_size"], config[swa + "num_attention_heads"]
    kv = config["swa_num_key_value_heads" if window
                else "num_key_value_heads"]
    dq, dv = config[swa + "head_dim"], config[swa + "v_head_dim"]
    sink = config["add_swa_attention_sink_bias" if window
                  else "add_full_attention_sink_bias"]
    return (d * h * dq + d * kv * dq + d * kv * dv + h * dv * d + d
            + (h if sink else 0))


def ffn_params(config, expert):
    """One feed-forward half with its input norm: the dense MLP, or
    the router with its correction bias and the held experts."""
    d = config["hidden_size"]
    if not expert:
        return 3 * d * config["intermediate_size"] + d
    width = config["router_experts"]
    return (d * width + width + d
            + config["n_routed_experts"] * 3 * d
            * config["moe_intermediate_size"])


def parameters(config):
    """All parameters this chip holds."""
    d, v = config["hidden_size"], config["vocab_size"]
    return sum(attention_params(config, w) + ffn_params(config, e)
               for w, e in layer_kinds(config)) + 2 * d * v + d


def cache_values(config, window):
    """Values one cached position of one layer holds (keys and values
    of every key/value head)."""
    swa = "swa_" if window else ""
    kv = config["swa_num_key_value_heads" if window
                else "num_key_value_heads"]
    return kv * (config[swa + "head_dim"] + config[swa + "v_head_dim"])


def mean_cached_rows(traffic, window=None):
    """Cache rows a slot-step needs, averaged over the steps of the
    mix's fixed set of requests: a request of L = prompt + output
    tokens takes L - 1 steps that need 1, 2, ... L - 1 rows, each no
    more than ``window`` where one is given."""
    pairs = inputs.serve_lengths(traffic, 0, 1)[0]
    steps = rows = 0
    for p, o in pairs:
        n = p + o - 1
        w = n if window is None else min(window, n)
        steps += n
        # 1 + 2 + ... + w, then w for the n - w steps that follow
        rows += w * (w + 1) / 2 + (n - w) * w
    return rows / steps


def serve_step_bytes(config, traffic, active_slots):
    """``active_slots``: mean live slots a step."""
    d = config["hidden_size"]
    weights = parameters(config) - d * config["vocab_size"]
    cache = sum(
        active_slots * cache_values(config, w) * mean_cached_rows(
            traffic, config["sliding_window"] if w else None)
        for w, _ in layer_kinds(config))
    return ITEM * (weights + active_slots * d + cache)
