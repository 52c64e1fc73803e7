"""train_mfu (%): the whole training step's share of the chips' bf16
peak, from the traced window's tokens per second.

    model FLOPs per token x tokens/s / (chips x peak bf16 FLOP/s)

Model FLOPs per token (``flops_per_token``) count the matrix products of
forward and backward, 6 x (non-embedding parameters + the output head),
plus causal attention at 6 * L * seq * (heads * head_dim): the score and
context products cost 2 * 2 * seq * (heads * head_dim) per token and
layer over the full square, half of that under the causal mask, times
three for forward and backward.  Recomputation (remat), norms and the
softmax are not counted.  The peak comes from ``bench/peaks.json`` by
``device_kind``; an unknown kind is an error.
"""

import harness


def flops_per_token(cfg: dict, seq: int) -> float:
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    kv = int(cfg["num_key_value_heads"])
    hd = int(cfg.get("head_dim") or d // h)
    f = int(cfg["intermediate_size"])
    n_layers = int(cfg["num_hidden_layers"])
    vocab = int(cfg["vocab_size"])
    per_layer = 2 * d * h * hd + 2 * d * kv * hd + 3 * d * f + 2 * d
    params = n_layers * per_layer + d + d * vocab   # + final norm, head
    return 6.0 * params + 6.0 * n_layers * seq * h * hd


def read(facts: dict, trace):
    if "tokens_per_s" not in facts:
        return None
    peak = harness.load_peaks(facts["device_kind"])["bf16_flops_per_s"]
    flops = flops_per_token(facts["config"], facts["seq"])
    return 100.0 * flops * facts["tokens_per_s"] / (facts["chips"] * peak)
