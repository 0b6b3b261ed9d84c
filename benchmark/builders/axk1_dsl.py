"""Configuration file -> a MultiLayerNetwork, through the public DSL:
EmbeddingSequenceLayer + ``first_k_dense_replace`` dense and then
expert LatentDecoderBlocks + RMSNormalization + a bias-free
RnnOutputLayer, all in bfloat16 (``policy``).

The file's ``n_routed_experts`` is how many experts this chip HOLDS,
from ``held_first_expert``; ``router_experts`` is the router's width
(the published ``n_routed_experts``)."""


def block(config, layer):
    from deeplearning4j_tpu.nn.conf.layers import LatentDecoderBlock
    if config["topk_method"] != "none":
        raise ValueError(
            f"topk_method {config['topk_method']!r}: the expert layer "
            "selects by plain top-k over all scores (\"none\") only")
    expert = layer >= config["first_k_dense_replace"]
    return LatentDecoderBlock(
        eps=config["rms_norm_eps"],
        n_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        rope_theta=float(config["rope_theta"]),
        rope_scaling=config.get("rope_scaling"),
        intermediate_size=config["intermediate_size"],
        n_routed_experts=config["router_experts"] if expert else 0,
        held=(config["held_first_expert"], config["n_routed_experts"]),
        top_k=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        n_shared_experts=config["n_shared_experts"],
        routed_scaling_factor=config["routed_scaling_factor"],
        norm_topk_prob=config["norm_topk_prob"])


class _ShapesFirst:
    """``init()`` gives the network with its parameters as SHAPES
    (``jax.eval_shape`` of the program's own ``init``): the driver
    reads shapes and dtypes from them and puts the benchmark's
    weights in their place, so set-up never holds a second set."""

    def __init__(self, net):
        self.net = net

    def init(self):
        import jax
        net = self.net
        shapes = jax.eval_shape(lambda: net.init().params)
        net.params, net.opt_state, net._rng_key = shapes, None, None
        net.state = [{} for _ in net.layers]
        return net


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
    return _ShapesFirst(MultiLayerNetwork(conf))


def policy(config):
    """bfloat16 parameters, activations and cache, as the
    configuration's ``precision`` states (accumulation, softmax,
    router scores, norm statistics and logits are float32 inside the
    layers)."""
    import jax.numpy as jnp

    from deeplearning4j_tpu import dtypes
    return dtypes.policy_scope(dtypes.Policy(
        param_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16,
        output_dtype=jnp.bfloat16))
