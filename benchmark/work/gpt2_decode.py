"""Bytes and operations of the serving steps, from their shapes.

A decode step is bound by memory: every weight is read once, and the
keys and values of every live position once.  A prefill chunk row is a
small matmul problem over the same weights.
"""


def weight_bytes(sizes, bytes_per_el=2):
    """All weights a decode step reads: the blocks' matrices and biases,
    the LayerNorms, and the tied head (the whole embedding table as the
    output projection; the input look-up reads rows only)."""
    E, I, L, V = sizes["E"], sizes["I"], sizes["L"], sizes["V"]
    per_layer = 4 * E * E + 4 * E + 2 * E * I + I + E + 4 * E
    return (L * per_layer + V * E + 2 * E) * bytes_per_el


def kv_bytes(sizes, live_positions, bytes_per_el=2):
    """Keys and values of ``live_positions`` cached positions (summed
    over the live lanes), every layer, read once."""
    return 2 * sizes["L"] * sizes["E"] * live_positions * bytes_per_el


def decode_step_bytes(sizes, live_positions, bytes_per_el=2):
    return (weight_bytes(sizes, bytes_per_el)
            + kv_bytes(sizes, live_positions, bytes_per_el))


def decode_step_flops(sizes, lanes, live_positions):
    """2 FLOPs a matmul parameter a lane, plus the score and value
    products over the live positions."""
    E, I, L, V = sizes["E"], sizes["I"], sizes["L"], sizes["V"]
    mm = L * (4 * E * E + 2 * E * I) + V * E
    return 2 * mm * lanes + 2 * 2 * L * E * live_positions


def chunk_row_flops(sizes, chunk, offset):
    """One prefill chunk of ``chunk`` tokens at ``offset``: the blocks'
    matmuls over the chunk, and attention of each chunk token over the
    offset + its own causal part.  No head: only the last chunk's last
    token is projected to the vocabulary, and that is counted apart."""
    E, I, L = sizes["E"], sizes["I"], sizes["L"]
    mm = L * (4 * E * E + 2 * E * I)
    keys = offset + (chunk + 1) / 2
    return chunk * (2 * mm + 2 * 2 * L * E * keys)
