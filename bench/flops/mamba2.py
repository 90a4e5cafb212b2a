"""Model FLOPs of one training step of a Mamba2 language model.

Counted, for T = batch * seq_len tokens and L layers:

- 6 * N * T for the parameters, forward (2 N T) and backward (4 N T), where
  N counts every weight from the configuration's shapes: per layer the
  in-projections of x, z, B, C and dt (d * (2*di + 2*n + H)), the depthwise
  convolution and its bias ((W + 1) * (di + 2*n)), A_log, D and dt_bias
  (3*H), the out-projection (di*d) and the norm (d); once the tied
  embedding, which is the head's weight (V*d, V its ``embedding_rows``),
  and the final norm (d);
- the SSD block's chunked products with chunk Q (``chunk_size``), per
  sequence and layer forward: C.B within chunks 2*S*Q*n, scores times x
  2*S*Q*H*P, chunk states 2*S*H*n*P, and the states' contribution to the
  outputs 2*S*H*n*P; times 3 for the backward pass.

Not counted: full rematerialization's second forward, the decay
exponentials and other elementwise work, the embedding lookup.
"""


def params(conf: dict) -> int:
    d, V = conf["d_model"], conf["embedding_rows"]
    ssm = conf["ssm_cfg"]
    n, P, W = ssm["d_state"], ssm["headdim"], ssm["d_conv"]
    di = ssm["expand"] * d
    H = di // P
    layer = (d * (2 * di + 2 * n + H) + (W + 1) * (di + 2 * n) + 3 * H
             + di * d + d)
    return conf["n_layer"] * layer + V * d + d


def model_flops_per_step(conf: dict, traffic: dict) -> float:
    B, S = traffic["batch"], traffic["seq_len"]
    ssm = conf["ssm_cfg"]
    n, P, Q = ssm["d_state"], ssm["headdim"], ssm["chunk_size"]
    H = ssm["expand"] * conf["d_model"] // P
    ssd = 2 * S * Q * n + 2 * S * Q * H * P + 4 * S * H * n * P
    return 6.0 * params(conf) * B * S + 3 * B * conf["n_layer"] * ssd
