"""Required forward + backward FLOPs of one training image through
ResNet50 (a configuration names this file in its ``train_flops``
key)."""

from benchmark.harness.counts import bottleneck_fwd_flops, conv_flops


def fwd_flops(image=224, classes=1000):
    """He et al. 2015 table 1, 50-layer: stem 7x7/2, 3x3/2 max-pool,
    stages of 3, 4, 6, 3 bottlenecks, average pool, fc. Convolutions
    and the fc only (batch norm and ReLU are not matmul work)."""
    h = image // 2
    f = conv_flops(h, h, 7, 7, 3, 64)
    h //= 2
    c_in = 64
    for blocks, mid, c_out, stride in ((3, 64, 256, 1), (4, 128, 512, 2),
                                       (6, 256, 1024, 2),
                                       (3, 512, 2048, 2)):
        for b in range(blocks):
            f += bottleneck_fwd_flops(h, h, c_in, mid, c_out,
                                      stride if b == 0 else 1, b == 0)
            if b == 0:
                h //= stride
            c_in = c_out
    return f + 2 * 2048 * classes


def train_flops(config, traffic):
    """Forward + backward (twice the forward: input and weight
    gradients) per image."""
    return 3 * fwd_flops(config["image_size"], config["num_classes"])
