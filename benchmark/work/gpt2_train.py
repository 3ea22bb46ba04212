"""Operations a GPT-2 training step needs, from its shapes.

Forward-and-backward matmul FLOPs per token, recomputation not counted
(the MFU convention): each matmul weight is used once forward (2 FLOPs a
parameter a token) and twice backward; causal attention does half of the
S x S score and value products.
"""


def matmul_params(sizes):
    """Parameters that take part in a matmul per token: the four
    attention projections and the two MLP matrices of each layer, and the
    tied output head.  Embedding look-ups are gathers, not matmuls."""
    E, I, L, V = sizes["E"], sizes["I"], sizes["L"], sizes["V"]
    return L * (4 * E * E + 2 * E * I) + V * E


def attention_flops_per_token(sizes, seq_len):
    """Forward FLOPs per token of the score and value products, causal:
    a token at position t attends t+1 keys; averaged over the sequence
    that is (S+1)/2 keys, 2 products, 2 FLOPs a multiply-add, E wide."""
    return sizes["L"] * 2 * 2 * sizes["E"] * (seq_len + 1) / 2


def train_flops_per_token(sizes, seq_len):
    """Forward + backward = 3 x forward."""
    return 3 * (2 * matmul_params(sizes)
                + attention_flops_per_token(sizes, seq_len))
