"""Bytes and operations of the ``swa_moe`` family's serving steps, from
their shapes (``sizes`` as ``references/swa_moe.sizes_of`` gives them).

The least a decode step must do: read every weight outside the routed
experts once (the embedding by rows), read the matrices of each held
expert that some token chose (the count of experts hit is the program's
to report: ``work/mla_moe.py``), read every cached position of the FULL
layers once, and of the WINDOW layers the positions that lie inside the
window -- ``min(positions, sliding_window)`` a lane, whatever the ring's
capacity and however the program stores them.  A lane's query heads meet
each of those rows twice: once for the score, once for the value.
"""


def n_full(s):
    return s["L"] // s["PER"]


def n_window(s):
    return s["L"] - n_full(s)


def attn_params(s):
    """Matrix parameters of one layer's attention: q, gate, o; k, v."""
    qd, kd = s["H"] * s["D"], s["KV"] * s["D"]
    return 3 * s["E"] * qd + 2 * s["E"] * kd


def dense_ffn_params(s):
    return 3 * s["E"] * s["I"]


def expert_params(s):
    """One routed expert: gate, up, down."""
    return 3 * s["E"] * s["IM"]


def router_params(s):
    return s["E"] * s["R"] + s["R"]          # and its bias; float32


def vector_params(s):
    """A layer's norm weights: four over the stream, two over a head;
    float32."""
    return 4 * s["E"] + 2 * s["D"]


def fixed_weight_bytes(s, lanes, bytes_per_el=2):
    """What a decode step reads whoever the router chooses: the layers'
    matrices outside the routed experts (bf16), the routers and vectors
    (float32), the whole head, the final norm, one row of the embedding
    a lane."""
    kd, km = s["KD"], s["L"] - s["KD"]
    mats = (s["L"] * attn_params(s) + kd * dense_ffn_params(s)
            + km * s["NS"] * expert_params(s))
    f32 = km * router_params(s) + s["L"] * vector_params(s) + s["E"]
    return (mats * bytes_per_el + f32 * 4
            + s["E"] * s["V"] * bytes_per_el
            + lanes * s["E"] * bytes_per_el)


def expert_bytes(s, bytes_per_el=2):
    return expert_params(s) * bytes_per_el


def row_bytes(s, bytes_per_el=2):
    """One cached position in one layer: its keys and its values."""
    return 2 * s["KV"] * s["D"] * bytes_per_el


def full_rows_bytes(s, full_rows, bytes_per_el=2):
    """``full_rows``: cached positions summed over the lanes."""
    return n_full(s) * row_bytes(s, bytes_per_el) * full_rows


def window_rows_bytes(s, window_rows, bytes_per_el=2):
    """``window_rows``: positions inside the window, summed over the
    lanes."""
    return n_window(s) * row_bytes(s, bytes_per_el) * window_rows


def decode_step_bytes(s, lanes, full_rows, window_rows, experts_hit,
                      bytes_per_el=2):
    """Fixed weights once, the experts that were hit once (over all
    expert layers), live rows of both kinds once."""
    return (fixed_weight_bytes(s, lanes, bytes_per_el)
            + experts_hit * expert_bytes(s, bytes_per_el)
            + full_rows_bytes(s, full_rows, bytes_per_el)
            + window_rows_bytes(s, window_rows, bytes_per_el))


def attn_flops_per_row(s):
    """One lane's query heads against one cached row of one layer: the
    score and the value."""
    return 4 * s["H"] * s["D"]


def _attn_bound(s, layers, rows, byts, peaks):
    flops = layers * attn_flops_per_row(s) * rows \
        / peaks["bf16_flops_per_s"]
    byts = byts / peaks["hbm_bytes_per_s"]
    return max(flops, byts), ("flops" if flops > byts else "bytes")


def window_attn_bound_seconds(s, window_rows, peaks, bytes_per_el=2):
    """The least time the window layers' attention of one decode step can
    take, and which peak bounds it."""
    return _attn_bound(s, n_window(s), window_rows,
                       window_rows_bytes(s, window_rows, bytes_per_el),
                       peaks)


def full_attn_bound_seconds(s, full_rows, peaks, bytes_per_el=2):
    return _attn_bound(s, n_full(s), full_rows,
                       full_rows_bytes(s, full_rows, bytes_per_el), peaks)


def experts_bound_seconds(s, experts_hit, assignments, peaks,
                          bytes_per_el=2):
    """The least time the held experts of one step can take: the hit
    experts' matrices read once, or ``assignments`` tokens through an
    expert's three matmuls."""
    byts = experts_hit * expert_bytes(s, bytes_per_el) \
        / peaks["hbm_bytes_per_s"]
    flops = assignments * 2 * expert_params(s) / peaks["bf16_flops_per_s"]
    return max(flops, byts), ("flops" if flops > byts else "bytes")


def held_weight_bytes(s, bytes_per_el=2):
    """Everything the chip holds: for sizing, not for a roofline."""
    km = s["L"] - s["KD"]
    n_held = s["held"][1] - s["held"][0]
    return (fixed_weight_bytes(s, 0, bytes_per_el)
            + s["V"] * s["E"] * bytes_per_el
            + km * n_held * expert_bytes(s, bytes_per_el))
