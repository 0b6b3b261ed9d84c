"""Configuration file -> a MultiLayerNetwork, through the public DSL:
EmbeddingSequenceLayer (``embedding_multiplier``) +
``num_hidden_layers`` decoder blocks + RMSNormalization + a bias-free
RnnOutputLayer (``logits_scaling``), all in bfloat16 (``policy``).

Layer ``l`` is a StateSpaceDecoderBlock (a Mamba-2 mixer) where
``layer_types[l]`` is "mamba" and a GroupedQueryDecoderBlock without
position encoding, its scores scaled by ``attention_multiplier``,
where it is "attention"; both put ``residual_multiplier`` on their
branches and the dense MLP of ``shared_intermediate_size`` behind the
mixer (``num_local_experts`` is 0: there is no routed part)."""

from benchmark.harness import spec

_AXK1 = spec.load_module("builders", "axk1_dsl")
policy = _AXK1.policy           # bfloat16 throughout, as stated there


def block(config, layer):
    from deeplearning4j_tpu.nn.conf.layers import (
        GroupedQueryDecoderBlock, StateSpaceDecoderBlock)
    c = config
    if (c["num_local_experts"], c["hidden_act"], c["attention_bias"],
            c["position_embedding_type"], c["normalization_function"],
            c["mamba_proj_bias"], c["mamba_conv_bias"]) != (
            0, "silu", False, "nope", "rmsnorm", False, True):
        raise ValueError(
            "the blocks are dense SiLU-gated MLPs behind RMSNorm, "
            "attention without bias or position encoding, and a "
            "mixer whose only bias is the convolution's")
    common = dict(eps=c["rms_norm_eps"],
                  intermediate_size=c["shared_intermediate_size"],
                  residual_multiplier=c["residual_multiplier"])
    kind = c["layer_types"][layer]
    if kind == "attention":
        head = c["hidden_size"] // c["num_attention_heads"]
        return GroupedQueryDecoderBlock(
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], qk_head_dim=head,
            v_head_dim=head, rotary_dim=0,
            softmax_scale=c["attention_multiplier"], **common)
    if kind != "mamba":
        raise ValueError(f"layer_types[{layer}] = {kind!r}: 'mamba' "
                         "or 'attention'")
    if c["mamba_expand"] * c["hidden_size"] != \
            c["mamba_n_heads"] * c["mamba_d_head"]:
        raise ValueError("mamba_expand * hidden_size is not "
                         "mamba_n_heads * mamba_d_head")
    return StateSpaceDecoderBlock(
        n_heads=c["mamba_n_heads"], head_dim=c["mamba_d_head"],
        state_size=c["mamba_d_state"], n_groups=c["mamba_n_groups"],
        conv_width=c["mamba_d_conv"], **common)


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
         .layer(EmbeddingSequenceLayer(
             n_in=v, n_out=d,
             multiplier=config["embedding_multiplier"])))
    for layer in range(config["num_hidden_layers"]):
        b = b.layer(block(config, layer))
    conf = (b.layer(RMSNormalization(eps=config["rms_norm_eps"]))
            .layer(RnnOutputLayer(
                n_out=v, loss="mcxent", has_bias=False,
                logits_divisor=config["logits_scaling"]))
            .set_input_type(InputType.recurrent(
                v, seq_len or config["max_position_embeddings"]))
            .build())
    # parameters as shapes first: set-up never holds a second set
    return _AXK1._ShapesFirst(MultiLayerNetwork(conf))
