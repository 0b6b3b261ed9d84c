"""Bytes that one step of a Granite-4.0-H shaped model reads and
writes (a configuration names this file in its ``serve_step_bytes``
key): every weight but the embedding table once, whoever is in the
batch, the embedding rows, of each slot that stepped each state-space
layer's state and convolution window READ AND WRITTEN once, and the
key/value rows the attention layers need of the positions a slot
holds. Weights, windows and key/value rows are bfloat16, the state
float32.

What the program moves beyond that is not needed and not counted: it
passes the state of EVERY slot's row through a step, live or not, and
reads it more than once. The cache rows are counted a step a token, as
``counts/mimo.py`` counts them."""

from benchmark.harness import spec

_MIMO = spec.load_module("counts", "mimo")
mean_cached_rows = _MIMO.mean_cached_rows

ITEM = 2            # bfloat16
STATE_ITEM = 4      # float32


def layer_counts(config):
    """(state-space layers, attention layers)."""
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    return kinds.count("mamba"), kinds.count("attention")


def conv_dim(config):
    return (config["mamba_n_heads"] * config["mamba_d_head"]
            + 2 * config["mamba_n_groups"] * config["mamba_d_state"])


def mixer_params(config):
    """One Mamba-2 mixer: W_in, the convolution and its bias, A_log,
    D and dt_bias a head, the gated norm's gain, W_out."""
    d, h = config["hidden_size"], config["mamba_n_heads"]
    d_in, cd = h * config["mamba_d_head"], conv_dim(config)
    return (d * (d_in + cd + h) + cd * config["mamba_d_conv"] + cd
            + 3 * h + d_in + d_in * d)


def attention_params(config):
    """One attention: Wq, Wk, Wv, Wo."""
    d = config["hidden_size"]
    head = d // config["num_attention_heads"]
    kv = config["num_key_value_heads"] * head
    return 2 * d * d + 2 * d * kv


def mlp_params(config):
    return 3 * config["hidden_size"] * config["shared_intermediate_size"]


def parameters(config, tied=False):
    """All parameters held: the program's (an embedding and a head of
    their own) or, ``tied``, the source's count."""
    d, v = config["hidden_size"], config["vocab_size"]
    n_ssm, n_attn = layer_counts(config)
    per_layer = mlp_params(config) + 2 * d           # two norm gains
    return (n_ssm * (mixer_params(config) + per_layer)
            + n_attn * (attention_params(config) + per_layer)
            + (1 if tied else 2) * d * v + d)


def state_bytes(config):
    """What one state-space layer keeps of one stream: the float32
    state and the convolution's window."""
    return (STATE_ITEM * config["mamba_n_heads"] * config["mamba_d_head"]
            * config["mamba_d_state"]
            + ITEM * (config["mamba_d_conv"] - 1) * conv_dim(config))


def cache_values(config):
    """Values one cached position of one attention layer holds."""
    head = config["hidden_size"] // config["num_attention_heads"]
    return 2 * config["num_key_value_heads"] * head


def serve_step_bytes(config, traffic, active_slots):
    """``active_slots``: mean live slots a step."""
    d = config["hidden_size"]
    n_ssm, n_attn = layer_counts(config)
    weights = parameters(config) - d * config["vocab_size"]
    cache = (n_attn * active_slots * cache_values(config)
             * mean_cached_rows(traffic))
    return (ITEM * (weights + active_slots * d + cache)
            + n_ssm * active_slots * 2 * state_bytes(config))
