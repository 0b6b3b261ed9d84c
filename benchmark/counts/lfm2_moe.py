"""Parameters of an LFM2-MoE shaped model and the bytes one step of it
reads and writes (a configuration names this file in its
``serve_step_bytes`` key): every weight but the embedding table once,
whoever is in the batch (the expert layer passes every row through
EVERY expert, so all their weights are read, hit or not), the
embedding rows, of each slot that stepped each ``conv`` layer's window
READ AND WRITTEN once, and the key/value rows the ``full_attention``
layers need of the positions a slot holds. Stored bytes are bfloat16.

What the program moves beyond that is not needed and not counted: it
passes the window of EVERY slot's row through a step, live or not, and
a value head takes a lane tile of 128 in the pool where it holds 64.
The cache rows are counted a step a token, as ``counts/mimo.py``
counts them."""

from benchmark.harness import spec

mean_cached_rows = spec.load_module("counts", "mimo").mean_cached_rows

ITEM = 2            # bfloat16


def layer_kinds(config):
    """Per layer of the cut (the published layers from ``first_layer``,
    0 where the file has no such key), (``conv`` layer?, expert
    layer?)."""
    first = config.get("first_layer", 0)
    return [(config["layer_types"][l] == "conv",
             l >= config["num_dense_layers"])
            for l in range(first, first + config["num_hidden_layers"])]


def conv_params(config):
    """One short-convolution mixer: W_in, the taps, W_out."""
    d = config["hidden_size"]
    return d * 3 * d + config["conv_L_cache"] * d + d * d


def attention_params(config):
    """One attention: Wq, Wk, Wv, Wo and the two per-head gains."""
    d = config["hidden_size"]
    head = d // config["num_attention_heads"]
    kv = config["num_key_value_heads"] * head
    return 2 * d * d + 2 * d * kv + 2 * head


def ffn_params(config, expert):
    """One feed-forward half: the dense MLP, or the router with its
    selection bias and every expert."""
    d = config["hidden_size"]
    if not expert:
        return 3 * d * config["intermediate_size"]
    e = config["num_experts"]
    return d * e + e + e * 3 * d * config["moe_intermediate_size"]


def layer_params(config, conv, expert):
    """One block with its two norm gains."""
    return ((conv_params(config) if conv else attention_params(config))
            + ffn_params(config, expert) + 2 * config["hidden_size"])


def parameters(config, tied=False):
    """All parameters held: the program's (an embedding and a head of
    their own) or, ``tied``, the source's count."""
    d, v = config["hidden_size"], config["vocab_size"]
    return (sum(layer_params(config, c, e)
                for c, e in layer_kinds(config))
            + (1 if tied else 2) * d * v + d)


def window_bytes(config):
    """What one ``conv`` layer keeps of one stream."""
    return ITEM * (config["conv_L_cache"] - 1) * config["hidden_size"]


def cache_values(config):
    """Values one cached position of one attention layer holds."""
    head = config["hidden_size"] // config["num_attention_heads"]
    return 2 * config["num_key_value_heads"] * head


def serve_step_bytes(config, traffic, active_slots):
    """``active_slots``: mean live slots a step."""
    d = config["hidden_size"]
    kinds = [conv for conv, _ in layer_kinds(config)]
    n_conv, n_attn = kinds.count(True), kinds.count(False)
    weights = parameters(config) - d * config["vocab_size"]
    cache = (n_attn * active_slots * cache_values(config)
             * mean_cached_rows(traffic))
    return (ITEM * (weights + active_slots * d + cache)
            + n_conv * active_slots * 2 * window_bytes(config))
