"""Configuration file -> a MultiLayerNetwork, through the public DSL:
EmbeddingSequenceLayer + ``num_hidden_layers`` decoder blocks +
RMSNormalization + a bias-free RnnOutputLayer, all in bfloat16
(``policy``).

The layers are the published layers ``first_layer`` (0 where the file
has no such key) and the ``num_hidden_layers - 1`` that follow.
Published layer ``l`` is a ShortConvDecoderBlock (a gated short
convolution of ``conv_L_cache``) where ``layer_types[l]`` is "conv"
and a GroupedQueryDecoderBlock with per-head q/k norms and rotary over
the whole head where it is "full_attention"; those before
``num_dense_layers`` carry the dense MLP of ``intermediate_size``,
the others ALL ``num_experts`` routed experts (``held`` is None: the
chip holds the whole layer)."""

from benchmark.harness import spec

_AXK1 = spec.load_module("builders", "axk1_dsl")
policy = _AXK1.policy           # bfloat16 throughout, as stated there


def block(config, layer):
    """The block of PUBLISHED layer ``layer``."""
    from deeplearning4j_tpu.nn.conf.layers import (
        GroupedQueryDecoderBlock, ShortConvDecoderBlock)
    c = config
    if (c["conv_bias"], c["use_expert_bias"], c["norm_topk_prob"],
            c["rope_parameters"]["rope_type"]) != (
            False, True, True, "default"):
        raise ValueError(
            "the blocks are a convolution without bias, a router with "
            "a selection-only bias and normalised weights, and rotary "
            "positions without scaling")
    expert = layer >= c["num_dense_layers"]
    common = dict(
        eps=c["norm_eps"], intermediate_size=c["intermediate_size"],
        n_routed_experts=c["num_experts"] if expert else 0,
        top_k=c["num_experts_per_tok"],
        expert_width=c["moe_intermediate_size"],
        routed_scaling_factor=float(c["routed_scaling_factor"]))
    kind = c["layer_types"][layer]
    if kind == "full_attention":
        head = c["hidden_size"] // c["num_attention_heads"]
        return GroupedQueryDecoderBlock(
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], qk_head_dim=head,
            v_head_dim=head, rotary_dim=head,
            rope_theta=float(c["rope_parameters"]["rope_theta"]),
            qk_norm=True, **common)
    if kind != "conv":
        raise ValueError(f"layer_types[{layer}] = {kind!r}: 'conv' or "
                         "'full_attention'")
    return ShortConvDecoderBlock(conv_width=c["conv_L_cache"], **common)


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
    first = config.get("first_layer", 0)
    for layer in range(first, first + config["num_hidden_layers"]):
        b = b.layer(block(config, layer))
    conf = (b.layer(RMSNormalization(eps=config["norm_eps"]))
            .layer(RnnOutputLayer(n_out=v, loss="mcxent",
                                  has_bias=False))
            .set_input_type(InputType.recurrent(
                v, seq_len or config["max_position_embeddings"]))
            .build())
    # parameters as shapes first: set-up never holds a second set
    return _AXK1._ShapesFirst(MultiLayerNetwork(conf))
