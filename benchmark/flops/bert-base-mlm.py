"""Operations the ``bert-base-mlm`` job needs, from its shapes.

Copied from ``bench.py:mlm_model_flops_per_example`` (the original is
listed under Open questions in ``PERF.md`` for a later PR to delete).
Matmul FLOPs only, forward x 3 for forward + backward; recomputed
operations do not count.
"""


def train_flops_per_sequence(cfg: dict, seq_len: int, num_masked: int) -> float:
    """Per layer and token: qkv 6H^2 + out-projection 2H^2 + MLP 4*H*M,
    the score and value products 4*L*H; the head per masked position:
    transform 2H^2 + tied decode 2*H*V."""
    H, M = cfg["hidden_size"], cfg["intermediate_size"]
    V, layers = cfg["vocab_size"], cfg["num_hidden_layers"]
    per_token_layer = 8.0 * H * H + 4.0 * H * M + 4.0 * seq_len * H
    encoder_fwd = seq_len * layers * per_token_layer
    head_fwd = num_masked * (2.0 * H * H + 2.0 * H * V)
    return 3.0 * (encoder_fwd + head_fwd)


def train_flops_per_step(cfg: dict, traffic: dict, chips: int) -> float:
    """Of one optimizer step over the global batch of ``chips`` chips."""
    return (traffic["sequences_per_chip"] * chips
            * train_flops_per_sequence(cfg, traffic["seq_len"],
                                       traffic["num_masked"]))
