"""Model FLOPs of one training step of a dense GQA language model.

Counted, for T = batch * seq_len tokens, L layers and S = seq_len:

- 6 * N * T for the parameters, forward (2 N T) and backward (4 N T), where
  N counts every weight from the configuration's shapes: per layer the q
  and out projections (2 * d * H * hd), the k and v projections
  (2 * d * KV * hd), the gated MLP's up, gate and down (3 * d * F) and two
  norms (2 * d); once the tied embedding, which is the head's weight
  (V * d), and the final norm (d);
- causal attention's scores and weighted values: forward 2 * S * H * hd
  per token and layer for each, half of the full S x S square, and twice
  that backward: 6 * L * H * hd * S * T in all.

Not counted: full rematerialization's second forward, the softmax, norms,
rotary embeddings and other elementwise work, the embedding lookup.
"""


def params(conf: dict) -> int:
    d, V = conf["hidden_size"], conf["vocab_size"]
    H, KV = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd, F = conf["head_dim"], conf["intermediate_size"]
    layer = 2 * d * H * hd + 2 * d * KV * hd + 3 * d * F + 2 * d
    return conf["num_hidden_layers"] * layer + V * d + d


def model_flops_per_step(conf: dict, traffic: dict) -> float:
    B, S = traffic["batch"], traffic["seq_len"]
    attn = 6 * conf["num_hidden_layers"] * conf["num_attention_heads"] \
        * conf["head_dim"] * S
    return (6.0 * params(conf) + attn) * B * S
