"""Configuration file -> a MultiLayerNetwork, through the public DSL:
EmbeddingSequenceLayer + ``num_hidden_layers`` decoder blocks +
RMSNormalization + a bias-free RnnOutputLayer, all in bfloat16
(``policy``).

The layers are the published layers 0 .. ``num_hidden_layers`` - 1.
Layer ``l`` is a DeltaRuleDecoderBlock (a gated delta-rule mixer)
where ``layer_types[l]`` is "linear_attention" and a
GroupedQueryDecoderBlock without position encoding, its queries and
keys normed over the whole projected width, where it is
"full_attention"; both norm each branch's OUTPUT (``norm_placement``
"post") and carry the dense MLP of ``intermediate_size``."""

from benchmark.harness import spec

_AXK1 = spec.load_module("builders", "axk1_dsl")
policy = _AXK1.policy           # bfloat16 throughout, as stated there


def block(config, layer):
    from deeplearning4j_tpu.nn.conf.layers import (
        DeltaRuleDecoderBlock, GroupedQueryDecoderBlock)
    c = config
    if (c["hidden_act"], c["attention_bias"],
            c["rope_parameters"]["rope_theta"],
            c["linear_num_key_heads"]) != (
            "silu", False, None, c["linear_num_value_heads"]):
        raise ValueError(
            "the blocks are dense SiLU-gated MLPs, attention without "
            "bias or position encoding, and a delta rule with a key "
            "head a value head")
    common = dict(eps=c["rms_norm_eps"],
                  intermediate_size=c["intermediate_size"],
                  norm_placement="post")
    kind = c["layer_types"][layer]
    if kind == "full_attention":
        head = c["hidden_size"] // c["num_attention_heads"]
        return GroupedQueryDecoderBlock(
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], qk_head_dim=head,
            v_head_dim=head, rotary_dim=0, qk_norm="width", **common)
    if kind != "linear_attention":
        raise ValueError(f"layer_types[{layer}] = {kind!r}: "
                         "'linear_attention' or 'full_attention'")
    return DeltaRuleDecoderBlock(
        n_heads=c["linear_num_value_heads"],
        key_head_dim=c["linear_key_head_dim"],
        value_head_dim=c["linear_value_head_dim"],
        conv_width=c["linear_conv_kernel_dim"],
        allow_neg_eigval=c["linear_allow_neg_eigval"], **common)


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
    conf = (b.layer(RMSNormalization(eps=config["rms_norm_eps"]))
            .layer(RnnOutputLayer(n_out=v, loss="mcxent",
                                  has_bias=False))
            .set_input_type(InputType.recurrent(
                v, seq_len or config["max_position_embeddings"]))
            .build())
    # parameters as shapes first: set-up never holds a second set
    return _AXK1._ShapesFirst(MultiLayerNetwork(conf))
