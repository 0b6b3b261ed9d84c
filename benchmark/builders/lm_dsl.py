"""Configuration file -> a MultiLayerNetwork, through the public DSL:
EmbeddingSequenceLayer + n_layer causal TransformerEncoderLayer +
RnnOutputLayer, exactly as ``chip_smoke.lm_conf`` builds its LM."""


def build(config, seq_len=None):
    from deeplearning4j_tpu import (MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf import updaters
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (
        EmbeddingSequenceLayer, RnnOutputLayer, TransformerEncoderLayer)
    a = config["assumed"]
    opt = getattr(updaters, a["updater"])(a["learning_rate"])
    b = (NeuralNetConfiguration.builder().set_seed(0).updater(opt).list()
         .layer(EmbeddingSequenceLayer(n_in=config["vocab_size"],
                                       n_out=config["n_embd"])))
    for _ in range(config["n_layer"]):
        b = b.layer(TransformerEncoderLayer(n_heads=config["n_head"],
                                            causal=True))
    conf = (b.layer(RnnOutputLayer(n_out=config["vocab_size"],
                                   loss="mcxent"))
            .set_input_type(InputType.recurrent(
                config["vocab_size"], seq_len or config["n_positions"]))
            .build())
    return MultiLayerNetwork(conf)


def policy(config):
    """The dtype policy scope the configuration is built and run in."""
    import contextlib
    return contextlib.nullcontext()
