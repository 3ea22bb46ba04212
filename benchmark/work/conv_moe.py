"""Bytes and operations of the ``conv_moe`` family's serving steps, from
their shapes (``sizes`` as ``references/conv_moe.sizes_of`` gives them).

The least a decode step must do: read every weight outside the routed
experts once (the embedding is the head, read whole as the head; a lane's
own row of it besides), read the matrices of each held expert that some
token chose (the count of experts hit is the program's to report:
``work/mla_moe.py``), read every cached position of the ATTENTION layers
once -- the conv layers have none -- and read and write each live lane's
convolution tails (``conv_L_cache - 1`` rows of ``hidden_size`` a conv
layer, float32).  A lane's query heads meet each cached row twice: once
for the score, once for the value.
"""


def n_conv(s):
    return sum(k == "conv" for k in s["kinds"])


def n_full(s):
    return s["L"] - n_conv(s)


def conv_params(s):
    """Matrix parameters of one conv operator: the in-projection to
    three streams and the out-projection."""
    return 3 * s["E"] * s["E"] + s["E"] * s["E"]


def attn_params(s):
    """Matrix parameters of one attention operator: q, o; k, v."""
    return 2 * s["E"] * s["E"] + 2 * s["E"] * s["KV"] * s["D"]


def dense_ffn_params(s):
    return 3 * s["E"] * s["I"]


def expert_params(s):
    """One routed expert: gate, up, down."""
    return 3 * s["E"] * s["IM"]


def router_params(s):
    return s["E"] * s["R"] + s["R"]          # and its bias; float32


def vector_params(s):
    """Every layer's float32 vectors: two norms over the stream a layer;
    the taps of a conv layer; two norms over a head of an attention
    layer."""
    return (2 * s["L"] * s["E"] + n_conv(s) * s["KC"] * s["E"]
            + n_full(s) * 2 * s["D"])


def param_count(s):
    """Every parameter the chip holds (the head is the embedding)."""
    n_held = s["held"][1] - s["held"][0]
    km = s["L"] - s["KD"]
    return (n_conv(s) * conv_params(s) + n_full(s) * attn_params(s)
            + s["KD"] * dense_ffn_params(s)
            + km * (n_held * expert_params(s) + router_params(s))
            + vector_params(s) + s["E"] + s["V"] * s["E"])


def fixed_weight_bytes(s, lanes, bytes_per_el=2):
    """What a decode step reads whoever the router chooses: the layers'
    matrices outside the routed experts (bf16), the routers and vectors
    (float32), the whole embedding as the head, the final norm, one row
    of the embedding a lane."""
    mats = (n_conv(s) * conv_params(s) + n_full(s) * attn_params(s)
            + s["KD"] * dense_ffn_params(s))
    f32 = (s["L"] - s["KD"]) * router_params(s) + vector_params(s) + s["E"]
    return (mats * bytes_per_el + f32 * 4
            + s["E"] * s["V"] * bytes_per_el
            + lanes * s["E"] * bytes_per_el)


def expert_bytes(s, bytes_per_el=2):
    return expert_params(s) * bytes_per_el


def row_bytes(s, bytes_per_el=2):
    """One cached position in one attention layer: its keys and its
    values."""
    return 2 * s["KV"] * s["D"] * bytes_per_el


def kv_bytes_per_position(s, bytes_per_el=2):
    return n_full(s) * row_bytes(s, bytes_per_el)


def tail_bytes(s, lanes):
    """The live lanes' tails of every conv layer, read and written."""
    return 2 * lanes * n_conv(s) * (s["KC"] - 1) * s["E"] * 4


def decode_step_bytes(s, lanes, live_positions, experts_hit,
                      bytes_per_el=2):
    """Fixed weights once, the experts that were hit once (over all
    expert layers), the attention layers' live rows once, the tails read
    and written."""
    return (fixed_weight_bytes(s, lanes, bytes_per_el)
            + experts_hit * expert_bytes(s, bytes_per_el)
            + kv_bytes_per_position(s, bytes_per_el) * live_positions
            + tail_bytes(s, lanes))


def attn_flops_per_row(s):
    """One lane's query heads against one cached row of one layer: the
    score and the value."""
    return 4 * s["H"] * s["D"]


def _bound(flops, byts, peaks):
    flops = flops / peaks["bf16_flops_per_s"]
    byts = byts / peaks["hbm_bytes_per_s"]
    return max(flops, byts), ("flops" if flops > byts else "bytes")


def attn_bound_seconds(s, live_positions, peaks, bytes_per_el=2):
    """The least time the attention layers' attention of one decode step
    can take, and which peak bounds it."""
    return _bound(n_full(s) * attn_flops_per_row(s) * live_positions,
                  kv_bytes_per_position(s, bytes_per_el) * live_positions,
                  peaks)


def short_conv_bound_seconds(s, lanes, peaks, bytes_per_el=2):
    """The least time the conv operators of one decode step can take:
    their matrices and taps read once and the live lanes' tails read and
    written, or ``lanes`` tokens through the two projections."""
    byts = (n_conv(s) * (conv_params(s) * bytes_per_el
                         + s["KC"] * s["E"] * 4) + tail_bytes(s, lanes))
    return _bound(lanes * n_conv(s) * 2 * conv_params(s), byts, peaks)


def experts_bound_seconds(s, experts_hit, assignments, peaks,
                          bytes_per_el=2):
    """The least time the held experts of one step can take: the hit
    experts' matrices read once, or ``assignments`` tokens through an
    expert's three matmuls."""
    return _bound(assignments * 2 * expert_params(s),
                  experts_hit * expert_bytes(s, bytes_per_el), peaks)


def held_weight_bytes(s, bytes_per_el=2):
    """Everything the chip holds: for sizing, not for a roofline."""
    return fixed_weight_bytes(s, 0, bytes_per_el) \
        + (s["L"] - s["KD"]) * (s["held"][1] - s["held"][0]) \
        * expert_bytes(s, bytes_per_el)
