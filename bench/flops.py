"""Model FLOPs of a training step, by the PaLM convention (Chowdhery et al.
2022, appendix B): per token 6 x the parameters of every matrix product
(the unembedding included, the input embedding's lookup not) for the
forward and backward passes, plus 12 x layers x sequence x attention width
for the attention scores and their values. Recomputed (rematerialized)
operations do not count."""
from __future__ import annotations


def per_token(conf: dict, seq_len: int) -> float:
    """FLOPs per trained token of a Llama-style decoder configuration."""
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    hd, kv = d // h, conf["num_key_value_heads"]
    ff, vocab, layers = conf["intermediate_size"], conf["vocab_size"], conf["num_hidden_layers"]
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff
    matmul = layers * per_layer + d * vocab
    return 6.0 * matmul + 12.0 * layers * seq_len * h * hd
