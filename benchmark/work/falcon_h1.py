"""Bytes and operations of Falcon-H1's serving steps, from their shapes
(``sizes`` as ``references/falcon_h1.sizes_of`` gives them).

The least a step must do: a decode step reads every weight once (the
embedding by rows), the keys and values of every live position once, and
reads and writes every live lane's recurrent state once.  A prefill chunk
row reads the layers' weights for a small matmul problem; inside it the
chunked scan (``ssm_scan``) is the four products of the state-space-dual
form over one state read and one state write.
"""


def layer_matmul_params(s):
    """Matrix parameters of one layer: attention, the mixer's two
    projections, the feed-forward's three."""
    e, qd, kd = s["E"], s["H"] * s["D"], s["KV"] * s["D"]
    in_w = 2 * s["DS"] + 2 * s["G"] * s["N"] + s["MH"]
    return (e * qd + 2 * e * kd + qd * e          # q, k, v, o
            + e * in_w + s["DS"] * e              # mixer in, out
            + 3 * e * s["I"])                     # gate, up, down


def layer_vector_params(s):
    conv = s["DS"] + 2 * s["G"] * s["N"]
    return 2 * s["E"] + s["DS"] + (s["K"] + 1) * conv + 3 * s["MH"]


def weight_bytes(s, lanes, bytes_per_el=2):
    """All weights a decode step reads: the layers' matrices (bf16) and
    vectors (float32), the whole head, the final norm, and one row of
    the embedding a lane."""
    return (s["L"] * (layer_matmul_params(s) * bytes_per_el
                      + layer_vector_params(s) * 4)
            + s["E"] * s["V"] * bytes_per_el + s["E"] * 4
            + lanes * s["E"] * bytes_per_el)


def kv_bytes_per_position(s, bytes_per_el=2):
    """Keys and values of one cached position, every layer."""
    return 2 * s["L"] * s["KV"] * s["D"] * bytes_per_el


def state_bytes_per_slot(s):
    """One sequence's recurrent state, every layer, float32: the SSM
    state and the conv's tail."""
    conv = s["DS"] + 2 * s["G"] * s["N"]
    return s["L"] * 4 * (s["MH"] * s["MP"] * s["N"] + (s["K"] - 1) * conv)


def decode_step_bytes(s, lanes, live_positions, bytes_per_el=2):
    """Weights once, live K/V once, live state read and written once."""
    return (weight_bytes(s, lanes, bytes_per_el)
            + kv_bytes_per_position(s, bytes_per_el) * live_positions
            + 2 * state_bytes_per_slot(s) * lanes)


def ssm_scan_flops(s, chunk):
    """One layer's chunked scan over ``chunk`` tokens: C.B^T a group,
    the masked product with x a head, the read of the incoming state and
    the state's update."""
    t, h, p, n, g = chunk, s["MH"], s["MP"], s["N"], s["G"]
    return 2 * (g * t * t * n + h * t * t * p + 2 * h * t * p * n)


def ssm_scan_bytes(s, chunk):
    """One layer: the state read and written (float32), the chunk's x, B,
    C and dt read and its y written (float32, as the scan is computed)."""
    t, gn = chunk, s["G"] * s["N"]
    return 4 * (2 * s["MH"] * s["MP"] * s["N"]
                + t * (2 * s["DS"] + 2 * gn + s["MH"]))


def ssm_scan_bound_seconds(s, chunk, peaks):
    """The least time all layers' scans of one chunk row can take, and
    which peak bounds it."""
    flops = s["L"] * ssm_scan_flops(s, chunk) / peaks["bf16_flops_per_s"]
    byts = s["L"] * ssm_scan_bytes(s, chunk) / peaks["hbm_bytes_per_s"]
    return max(flops, byts), ("flops" if flops > byts else "bytes")


def chunk_row_flops(s, chunk, offset):
    """One prefill chunk row at ``offset``: the layers' matmuls over the
    chunk, attention over the offset and the chunk's causal part, the
    scans.  No head: only a prompt's last token is projected."""
    keys = offset + (chunk + 1) / 2
    attn = 2 * 2 * s["H"] * s["D"] * keys * chunk
    return s["L"] * (2 * layer_matmul_params(s) * chunk + attn
                     + ssm_scan_flops(s, chunk))
