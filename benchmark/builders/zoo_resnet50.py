"""Configuration file -> the zoo's ResNet50 ComputationGraph, under
the dtype policy the configuration states (``dtypes.tpu_bf16()``: the
policy ConvolutionLayer and DenseLayer do read)."""


def build(config, seq_len=None):
    from deeplearning4j_tpu.nn.conf import updaters
    from deeplearning4j_tpu.zoo import ResNet50
    a = config["assumed"]
    s = config["image_size"]
    return ResNet50(n_classes=config["num_classes"],
                    input_shape=(s, s, 3),
                    updater=getattr(updaters, a["updater"])(
                        a["learning_rate"], a["momentum"]))


def policy(config):
    from deeplearning4j_tpu import dtypes
    return dtypes.policy_scope(getattr(dtypes, config["dtype_policy"])())
