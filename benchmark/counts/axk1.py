"""Bytes that one decode step of an A.X-K1 shaped share reads (a
configuration names this file in its ``serve_step_bytes`` key): every
weight but the embedding table once, whoever is in the batch (the
expert layer passes every row through EVERY held expert, so all their
weights are read, hit or not), the embedding rows and the cache rows
of the slots that stepped. Stored bytes are bfloat16."""

ITEM = 2


def layer_params(config):
    """Parameters of one block outside its routed experts, by kind:
    (attention, dense MLP, router + shared expert), and one routed
    expert's."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    rq, rkv = config["q_lora_rank"], config["kv_lora_rank"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    attn = (d * rq + rq * h * (dn + dr) + d * (rkv + dr)
            + rkv * h * (dn + dv) + h * dv * d + rq + rkv + 2 * d)
    dense = 3 * d * config["intermediate_size"]
    expert = 3 * d * config["moe_intermediate_size"]
    outside = (d * config["router_experts"]
               + config["n_shared_experts"] * expert)
    return attn, dense, outside, expert


def mean_cached_rows(traffic):
    """Cache rows a slot-step reads, averaged over the steps of the
    mix's fixed set of requests: a request of L = prompt + output
    tokens takes L - 1 steps that read 1, 2, ... L - 1 rows."""
    from benchmark.harness import inputs
    pairs = inputs.serve_lengths(traffic, 0, 1)[0]
    steps = sum(p + o - 1 for p, o in pairs)
    rows = sum((p + o - 1) * (p + o) / 2 for p, o in pairs)
    return rows / steps


def serve_step_bytes(config, traffic, active_slots):
    """``active_slots``: mean live slots a step."""
    attn, dense, outside, expert = layer_params(config)
    n_dense = config["first_k_dense_replace"]
    n_expert = config["num_hidden_layers"] - n_dense
    d = config["hidden_size"]
    weights = (n_dense * (attn + dense)
               + n_expert * (attn + outside
                             + config["n_routed_experts"] * expert)
               + d + d * config["vocab_size"])   # final norm, head
    cache = (active_slots * mean_cached_rows(traffic)
             * config["num_hidden_layers"]
             * (config["kv_lora_rank"] + config["qk_rope_head_dim"]))
    return ITEM * (weights + active_slots * d + cache)
