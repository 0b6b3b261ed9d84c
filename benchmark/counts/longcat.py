"""Bytes that one step of a LongCat-Flash shaped share reads (a
configuration names this file in its ``serve_step_bytes`` key): every
weight but the embedding table once, whoever is in the batch (the
expert layer passes every row through EVERY held expert, so all their
weights are read, hit or not; a zero-compute expert has none), the
embedding rows and the cache rows, in BOTH latent pools of every
layer, of the slots that stepped. Stored bytes are bfloat16.

The cache rows are counted as ``counts/axk1.py`` counts them, a step
a token: a chunk step reads a slot's rows once for its t tokens, so
this overstates the cache's part, which is under 1 % of a step's
bytes at this configuration's sizes."""

from benchmark.harness import spec

ITEM = 2

mean_cached_rows = spec.load_module("counts", "axk1").mean_cached_rows


def layer_params(config):
    """Parameters of one layer by kind: (one latent attention with
    its input norm, one dense MLP with its input norm, the router with
    its bias, one routed expert)."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    rq, rkv = config["q_lora_rank"], config["kv_lora_rank"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    attn = (d * rq + rq * h * (dn + dr) + d * (rkv + dr)
            + rkv * h * (dn + dv) + h * dv * d + rq + rkv + d)
    dense = 3 * d * config["ffn_hidden_size"] + d
    width = config["router_experts"] + config["zero_expert_num"]
    router = d * width + width
    expert = 3 * d * config["expert_ffn_hidden_size"]
    return attn, dense, router, expert


def parameters(config):
    """All parameters this chip holds."""
    attn, dense, router, expert = layer_params(config)
    layer = (2 * attn + 2 * dense + router
             + config["n_routed_experts"] * expert)
    d, v = config["hidden_size"], config["vocab_size"]
    return config["num_layers"] * layer + 2 * d * v + d


def serve_step_bytes(config, traffic, active_slots):
    """``active_slots``: mean live slots a step."""
    d = config["hidden_size"]
    weights = parameters(config) - d * config["vocab_size"]
    cache = (active_slots * mean_cached_rows(traffic)
             * config["num_layers"] * 2
             * (config["kv_lora_rank"] + config["qk_rope_head_dim"]))
    return ITEM * (weights + active_slots * d + cache)
