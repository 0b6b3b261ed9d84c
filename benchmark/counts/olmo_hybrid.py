"""Bytes that one step of an Olmo-Hybrid shaped model reads and writes
(a configuration names this file in its ``serve_step_bytes`` key):
every weight but the embedding table once, whoever is in the batch,
the embedding rows, of each slot that stepped each linear layer's
state and convolution window READ once AND WRITTEN once, and the
key/value rows the full-attention layers need of the positions a slot
holds. Weights, windows and key/value rows are bfloat16, the state
float32.

The state is counted as the mathematics has it, 30 x 96 x 192 values a
layer a stream, whatever layout holds it: what any implementation must
move. What the program moves beyond that is not needed and not
counted: it passes EVERY slot's row through a step, live or not, and
reads it twice (once for ``S^T k`` and ``S^T q``, once for the write:
the delta rule's update needs the first pass's result). The cache rows
are counted a step a token, as ``counts/mimo.py`` counts them."""

from benchmark.harness import spec

_MIMO = spec.load_module("counts", "mimo")
mean_cached_rows = _MIMO.mean_cached_rows

ITEM = 2            # bfloat16
STATE_ITEM = 4      # float32


def layer_counts(config):
    """(linear layers, full-attention layers) among those kept."""
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    return kinds.count("linear_attention"), kinds.count("full_attention")


def conv_dim(config):
    """Channels of a linear layer's three convolutions: q | k | v."""
    return (2 * config["linear_num_key_heads"]
            * config["linear_key_head_dim"]
            + config["linear_num_value_heads"]
            * config["linear_value_head_dim"])


def mixer_params(config):
    """One gated delta-rule mixer: Wq, Wk; Wv, Wg, Wo; Wa, Wb; the
    convolutions; A_log and dt_bias a head; the gated norm's gain."""
    d, h = config["hidden_size"], config["linear_num_value_heads"]
    kd = config["linear_num_key_heads"] * config["linear_key_head_dim"]
    vd = h * config["linear_value_head_dim"]
    return (2 * d * kd + 3 * d * vd + 2 * d * h
            + config["linear_conv_kernel_dim"] * conv_dim(config)
            + 2 * h + config["linear_value_head_dim"])


def attention_params(config):
    """One attention: Wq, Wk, Wv, Wo and the two whole-width gains."""
    d = config["hidden_size"]
    head = d // config["num_attention_heads"]
    kv = config["num_key_value_heads"] * head
    return 2 * d * d + 2 * d * kv + d + kv


def mlp_params(config):
    return 3 * config["hidden_size"] * config["intermediate_size"]


def parameters(config, layers=None):
    """All parameters held over the first ``layers`` layers
    (``num_hidden_layers`` where None), with the embedding, the last
    gain and a head of its own (``tie_word_embeddings`` false)."""
    d, v = config["hidden_size"], config["vocab_size"]
    kinds = config["layer_types"][:layers or config["num_hidden_layers"]]
    per_layer = mlp_params(config) + 2 * d           # two branch norms
    return (kinds.count("linear_attention")
            * (mixer_params(config) + per_layer)
            + kinds.count("full_attention")
            * (attention_params(config) + per_layer)
            + 2 * d * v + d)


def state_bytes(config):
    """What one linear layer keeps of one stream: the float32 state
    and the convolutions' window."""
    return (STATE_ITEM * config["linear_num_value_heads"]
            * config["linear_key_head_dim"]
            * config["linear_value_head_dim"]
            + ITEM * (config["linear_conv_kernel_dim"] - 1)
            * conv_dim(config))


def cache_values(config):
    """Values one cached position of one attention layer holds."""
    head = config["hidden_size"] // config["num_attention_heads"]
    return 2 * config["num_key_value_heads"] * head


def serve_step_bytes(config, traffic, active_slots):
    """``active_slots``: mean live slots a step."""
    d = config["hidden_size"]
    n_linear, n_full = layer_counts(config)
    weights = parameters(config) - d * config["vocab_size"]
    cache = (n_full * active_slots * cache_values(config)
             * mean_cached_rows(traffic))
    return (ITEM * (weights + active_slots * d + cache)
            + n_linear * active_slots * 2 * state_bytes(config))
