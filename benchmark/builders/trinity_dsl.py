"""Configuration file -> a MultiLayerNetwork, through the public DSL:
EmbeddingSequenceLayer (rows scaled by sqrt(hidden_size),
``mup_enabled``) + ``num_hidden_layers`` GroupedQueryDecoderBlocks +
RMSNormalization + a bias-free RnnOutputLayer, in float32, each layer
recomputed in the backward pass (``recompute``).

Block ``i`` is the published layer ``first_layer + i``: a
sliding-window layer with rotary positions where ``layer_types`` says
``sliding_attention``, a full layer without positions where it says
``full_attention``; the dense MLP where the published layer is one of
the source's leading dense layers (``published.num_dense_layers``),
the experts elsewhere. Every block norms both sides of both branches,
norms each query and key head, and gates the attention's output. The
file's ``num_experts`` is how many experts this chip HOLDS, from
``held_first_expert``; ``router_experts`` is the router's width (the
published ``num_experts``)."""


def block(config, i):
    from deeplearning4j_tpu.nn.conf.layers import GroupedQueryDecoderBlock
    if (config["score_func"], config["n_group"], config["topk_group"],
            config["route_norm"], config["hidden_act"]) != (
            "sigmoid", 1, 1, True, "silu"):
        raise ValueError(
            "the block's expert layer is a sigmoid router with a "
            "selection-only bias over one group, its selected weights "
            "normalised, and SiLU-gated experts")
    layer = config["first_layer"] + i
    window = config["layer_types"][layer] == "sliding_attention"
    dense = layer < config["published"]["num_dense_layers"]
    head = config["head_dim"]
    return GroupedQueryDecoderBlock(
        eps=config["rms_norm_eps"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        qk_head_dim=head, v_head_dim=head,
        rotary_dim=head if window else 0,
        rope_theta=float(config["rope_theta"]),
        window=config["sliding_window"] if window else None,
        qk_norm=True, out_gate=True, norm_placement="both",
        intermediate_size=config["intermediate_size"],
        n_routed_experts=0 if dense else config["router_experts"],
        held=(config["held_first_expert"], config["num_experts"]),
        top_k=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        n_shared_experts=config["num_shared_experts"],
        routed_scaling_factor=config["route_scale"])


def build(config, seq_len=None):
    from deeplearning4j_tpu import (MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf import updaters
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (
        EmbeddingSequenceLayer, RMSNormalization, RnnOutputLayer)
    from deeplearning4j_tpu.nn.conf.multi_layer import (
        MultiLayerConfiguration)
    a = config["assumed"]
    d, v = config["hidden_size"], config["vocab_size"]
    if not config["mup_enabled"] or config["tie_word_embeddings"]:
        raise ValueError("the embedding is scaled by sqrt(hidden_size) "
                         "and the head is its own matrix")
    b = (NeuralNetConfiguration.builder().set_seed(0)
         .updater(getattr(updaters, a["updater"])(a["learning_rate"]))
         .recompute_layers(config["recompute"] == "layers").list()
         .layer(EmbeddingSequenceLayer(n_in=v, n_out=d,
                                       multiplier=d ** 0.5)))
    for i in range(config["num_hidden_layers"]):
        b = b.layer(block(config, i))
    conf = (b.layer(RMSNormalization(eps=config["rms_norm_eps"]))
            .layer(RnnOutputLayer(n_out=v, loss="mcxent",
                                  has_bias=False))
            .set_input_type(InputType.recurrent(
                v, seq_len or config["max_position_embeddings"]))
            .build())
    # through JSON, as a configuration that is data travels
    return MultiLayerNetwork(MultiLayerConfiguration.from_json(
        conf.to_json()))


def policy(config):
    """float32 throughout, as the configuration's ``precision``
    states: the program's default."""
    import contextlib
    return contextlib.nullcontext()
