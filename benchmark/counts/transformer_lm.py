"""Required forward + backward FLOPs of one training sample of a
GPT-2 shaped LM with an untied head (a configuration names this file
in its ``train_flops`` key)."""


def train_flops(config, traffic):
    """One sequence of ``seq_len`` tokens: 6 FLOPs per matmul
    parameter per token (forward 2, backward 4) plus causal attention
    (scores and values: forward 2 matmuls, backward 4, each 2*T*T*D,
    halved)."""
    seq_len = traffic["inputs"]["seq_len"]
    d, n, v = config["n_embd"], config["n_layer"], config["vocab_size"]
    ff = config.get("n_inner") or 4 * d
    matmul_params = n * (4 * d * d + 2 * d * ff) + d * v
    attn = n * 6 * (2 * seq_len * seq_len * d) * 0.5
    return 6 * matmul_params * seq_len + attn
