"""Model FLOPs that a Fed-PLT round's local training requires.

Counted from shapes, as the forward and backward passes need them:

* every weight matmul, 2 FLOPs per multiply-add, the tied output head
  included (the embedding lookup is a gather, not a matmul);
* causal attention: the query-key scores and the probability-value
  products over the S(S+1)/2 pairs a causal mask leaves;
* backward = 2 x forward.

Recomputation (remat's second forward pass) is not counted, nor are
norms, activations, the softmax, the loss or the optimizer's update.
"""

from __future__ import annotations


def matmul_params(d_model: int, n_heads: int, n_kv_heads: int,
                  head_dim: int, d_ff: int, vocab: int, n_layers: int) -> int:
    """Weights that take part in a matmul, per token, tied head included
    (a gated MLP: gate and up projections, then down)."""
    attn = d_model * (n_heads + 2 * n_kv_heads) * head_dim \
        + n_heads * head_dim * d_model
    mlp = 3 * d_model * d_ff
    return n_layers * (attn + mlp) + vocab * d_model


def forward_flops_per_sequence(d_model: int, n_heads: int, n_kv_heads: int,
                               head_dim: int, d_ff: int, vocab: int,
                               n_layers: int, seq_len: int) -> float:
    dense = 2.0 * seq_len * matmul_params(d_model, n_heads, n_kv_heads,
                                          head_dim, d_ff, vocab, n_layers)
    pairs = seq_len * (seq_len + 1) / 2.0
    attn = n_layers * 2 * (2.0 * pairs * n_heads * head_dim)
    return dense + attn


def train_flops_per_round(model: dict, fed: dict) -> float:
    """Forward + backward FLOPs of one round: every agent, every local
    epoch, every sequence of its batch.  ``model`` holds the widths under
    the names of ``bench/configs`` files, ``fed`` the traffic's sizes."""
    d = model["hidden_size"]
    heads = model["num_attention_heads"]
    fwd = forward_flops_per_sequence(
        d_model=d, n_heads=heads,
        n_kv_heads=model["num_key_value_heads"],
        head_dim=model.get("head_dim", d // heads),
        d_ff=model["intermediate_size"], vocab=model["vocab_size"],
        n_layers=model["num_hidden_layers"], seq_len=fed["seq_len"])
    passes = fed["n_agents"] * fed["n_epochs"] * fed["seqs_per_agent"]
    return 3.0 * fwd * passes
