"""Configuration file -> a MultiLayerNetwork, through the public DSL:
EmbeddingSequenceLayer + ``num_hidden_layers``
GroupedQueryDecoderBlocks + RMSNormalization + a bias-free
RnnOutputLayer, all in bfloat16 (``policy``).

Layer ``l`` is a sliding-window layer where ``hybrid_layer_pattern[l]``
is 1 (its own key/value head count and rotary base, a sink logit a
head) and an expert layer where ``moe_layer_freq[l]`` is 1. The file's
``n_routed_experts`` is how many experts this chip HOLDS, from
``held_first_expert``; ``router_experts`` is the router's width (the
published ``n_routed_experts``)."""

from benchmark.harness import spec

_AXK1 = spec.load_module("builders", "axk1_dsl")
policy = _AXK1.policy           # bfloat16 throughout, as stated there


def block(config, layer):
    from deeplearning4j_tpu.nn.conf.layers import GroupedQueryDecoderBlock
    if (config["scoring_func"], config["topk_method"], config["n_group"],
            config["norm_topk_prob"], config["n_shared_experts"]) != (
            "sigmoid", "noaux_tc", 1, True, None):
        raise ValueError(
            "the block's expert layer is a sigmoid router with a "
            "selection-only correction bias over one group, its "
            "selected weights normalised, and no shared expert")
    if config["attention_bias"] or config["hidden_act"] != "silu":
        raise ValueError("the block has no attention bias and a SiLU "
                         "gate")
    window = bool(config["hybrid_layer_pattern"][layer])
    swa = "swa_" if window else ""
    head_dim = config[swa + "head_dim"]
    expert = bool(config["moe_layer_freq"][layer])
    return GroupedQueryDecoderBlock(
        eps=config["layernorm_epsilon"],
        n_heads=config[swa + "num_attention_heads"],
        n_kv_heads=config["swa_num_key_value_heads" if window
                          else "num_key_value_heads"],
        qk_head_dim=head_dim, v_head_dim=config[swa + "v_head_dim"],
        rotary_dim=int(config["partial_rotary_factor"] * head_dim),
        rope_theta=float(config["swa_rope_theta" if window
                                else "rope_theta"]),
        window=config["sliding_window"] if window else None,
        sink=config["add_swa_attention_sink_bias" if window
                    else "add_full_attention_sink_bias"],
        value_scale=config["attention_value_scale"],
        intermediate_size=config["intermediate_size"],
        n_routed_experts=config["router_experts"] if expert else 0,
        held=(config["held_first_expert"], config["n_routed_experts"]),
        top_k=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        routed_scaling_factor=config["routed_scaling_factor"] or 1.0)


def build(config, seq_len=None):
    from deeplearning4j_tpu import (MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf import updaters
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (
        EmbeddingSequenceLayer, RMSNormalization, RnnOutputLayer)
    d, v = config["hidden_size"], config["vocab_size"]
    b = (NeuralNetConfiguration.builder().set_seed(0)
         .updater(updaters.sgd(0.0)).list()
         .layer(EmbeddingSequenceLayer(n_in=v, n_out=d)))
    for layer in range(config["num_hidden_layers"]):
        b = b.layer(block(config, layer))
    conf = (b.layer(RMSNormalization(eps=config["layernorm_epsilon"]))
            .layer(RnnOutputLayer(n_out=v, loss="mcxent",
                                  has_bias=False))
            .set_input_type(InputType.recurrent(
                v, seq_len or config["max_position_embeddings"]))
            .build())
    # parameters as shapes first: set-up never holds a second set
    return _AXK1._ShapesFirst(MultiLayerNetwork(conf))
