"""Configuration file -> a MultiLayerNetwork, through the public DSL:
EmbeddingSequenceLayer + ``num_layers`` ShortcutExpertBlocks +
RMSNormalization + a bias-free RnnOutputLayer, all in bfloat16
(``policy``).

The file's ``n_routed_experts`` is how many routed experts this chip
HOLDS, from ``held_first_expert``; ``router_experts`` is how many the
router knows (the published ``n_routed_experts``), and its width is
that plus ``zero_expert_num``."""

from benchmark.harness import spec

_AXK1 = spec.load_module("builders", "axk1_dsl")
policy = _AXK1.policy           # bfloat16 throughout, as stated there


def block(config):
    from deeplearning4j_tpu.nn.conf.layers import ShortcutExpertBlock
    if config["zero_expert_type"] != "identity":
        raise ValueError(
            f"zero_expert_type {config['zero_expert_type']!r}: the "
            "expert layer's zero-compute experts are identities")
    if config["attention_method"] != "MLA" or config["attention_bias"]:
        raise ValueError("the block's attention is bias-free latent "
                         "attention (attention_method \"MLA\")")
    return ShortcutExpertBlock(
        eps=config["rms_norm_eps"],
        n_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        rope_theta=float(config["rope_theta"]),
        scale_q_lora=config["mla_scale_q_lora"],
        scale_kv_lora=config["mla_scale_kv_lora"],
        intermediate_size=config["ffn_hidden_size"],
        n_routed_experts=config["router_experts"],
        n_zero_experts=config["zero_expert_num"],
        held=(config["held_first_expert"], config["n_routed_experts"]),
        top_k=config["moe_topk"],
        expert_width=config["expert_ffn_hidden_size"],
        routed_scaling_factor=config["routed_scaling_factor"])


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
    for _ in range(config["num_layers"]):
        b = b.layer(block(config))
    conf = (b.layer(RMSNormalization(eps=config["rms_norm_eps"]))
            .layer(RnnOutputLayer(n_out=v, loss="mcxent",
                                  has_bias=False))
            .set_input_type(InputType.recurrent(
                v, seq_len or config["max_position_embeddings"]))
            .build())
    # parameters as shapes first: set-up never holds a second set
    return _AXK1._ShapesFirst(MultiLayerNetwork(conf))
