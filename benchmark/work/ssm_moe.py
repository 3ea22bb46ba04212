"""Bytes and operations of the ``ssm_moe`` family's serving steps, from
their shapes (``sizes`` as ``references/ssm_moe.sizes_of`` gives them).

The least a decode step must do: read every weight outside the routed
experts once (mixers, attention, routers, latent projections and shared
experts, the whole head; a lane's own row of the embedding besides),
read the two matrices of each held expert that some token chose (the
count of experts hit is the program's to report: ``work/mla_moe.py``),
read every cached position of the ATTENTION layers once -- the other
layers have none -- and read and write each live lane's recurrent state:
a Mamba layer's ``heads x head size x state size`` float32 and its
convolution's ``conv_kernel - 1`` last inputs.
"""


def count(s, letter):
    return s["pattern"].count(letter)


def d_ssm(s):
    return s["MH"] * s["MP"]


def conv_dim(s):
    return d_ssm(s) + 2 * s["G"] * s["N"]


def mixer_params(s):
    """Matrix parameters of one Mamba mixer: the in-projection to z, xBC
    and dt, and the out-projection."""
    return (s["E"] * (d_ssm(s) + conv_dim(s) + s["MH"])
            + d_ssm(s) * s["E"])


def attn_params(s):
    """Matrix parameters of one attention mixer: q, o; k, v."""
    return 2 * s["E"] * s["H"] * s["D"] + 2 * s["E"] * s["KV"] * s["D"]


def latent_params(s):
    """Matrix parameters of an expert layer outside its routed experts:
    the two latent projections and the shared expert's two matrices."""
    return 2 * s["E"] * s["LAT"] + 2 * s["E"] * s["IS"]


def expert_params(s):
    """One routed expert: up and down, in the latent."""
    return 2 * s["LAT"] * s["IM"]


def router_params(s):
    return s["E"] * s["R"] + s["R"]          # and its bias; float32


def vector_params(s):
    """Every layer's float32 vectors: one norm over the stream a layer;
    a Mamba layer's taps and conv bias, its gated norm, dt_bias, A_log
    and D."""
    return (s["L"] * s["E"]
            + count(s, "M") * ((s["KC"] + 1) * conv_dim(s) + d_ssm(s)
                               + 3 * s["MH"]))


def param_count(s):
    """Every parameter the chip holds (embedding and untied head)."""
    n_held = s["held"][1] - s["held"][0]
    return (count(s, "M") * mixer_params(s) + count(s, "*") * attn_params(s)
            + count(s, "E") * (latent_params(s) + router_params(s)
                               + n_held * expert_params(s))
            + vector_params(s) + s["E"] + 2 * s["V"] * s["E"])


def fixed_weight_bytes(s, lanes, bytes_per_el=2):
    """What a decode step reads whoever the router chooses: the layers'
    matrices outside the routed experts (bf16), the routers and vectors
    (float32), the whole head, the final norm, one row of the embedding
    a lane."""
    mats = (count(s, "M") * mixer_params(s) + count(s, "*") * attn_params(s)
            + count(s, "E") * latent_params(s))
    f32 = count(s, "E") * router_params(s) + vector_params(s) + s["E"]
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
    return count(s, "*") * row_bytes(s, bytes_per_el)


def ssm_state_bytes(s):
    """One lane's SSM state in one Mamba layer, float32."""
    return 4 * s["MH"] * s["MP"] * s["N"]


def state_bytes_per_slot(s):
    """One sequence's recurrent state, every Mamba layer, float32: the
    SSM state and the conv's tail."""
    return count(s, "M") * (ssm_state_bytes(s)
                            + 4 * (s["KC"] - 1) * conv_dim(s))


def decode_step_bytes(s, lanes, live_positions, experts_hit,
                      bytes_per_el=2):
    """Fixed weights once, the experts that were hit once (over all
    expert layers), the attention layers' live rows once, the live lanes'
    state read and written."""
    return (fixed_weight_bytes(s, lanes, bytes_per_el)
            + experts_hit * expert_bytes(s, bytes_per_el)
            + kv_bytes_per_position(s, bytes_per_el) * live_positions
            + 2 * state_bytes_per_slot(s) * lanes)


def _bound(flops, byts, peaks):
    flops = flops / peaks["bf16_flops_per_s"]
    byts = byts / peaks["hbm_bytes_per_s"]
    return max(flops, byts), ("flops" if flops > byts else "bytes")


def experts_bound_seconds(s, experts_hit, assignments, peaks,
                          bytes_per_el=2):
    """The least time the held experts of one step can take: the hit
    experts' two matrices read once, or ``assignments`` tokens through an
    expert's two matmuls."""
    return _bound(assignments * 2 * expert_params(s),
                  experts_hit * expert_bytes(s, bytes_per_el), peaks)


def ssm_step_bound_seconds(s, lanes, peaks):
    """The least time the one-step recurrences of one decode step can
    take: every Mamba layer's state of every live lane read and written
    (float32), or its update and read-out -- a multiply-add an element
    each."""
    els = lanes * count(s, "M") * s["MH"] * s["MP"] * s["N"]
    return _bound(4 * els, 2 * 4 * els, peaks)


def attn_flops_per_row(s):
    """One lane's query heads against one cached row of one layer: the
    score and the value."""
    return 4 * s["H"] * s["D"]


def attn_bound_seconds(s, live_positions, peaks, bytes_per_el=2):
    """The least time the attention layers' attention of one decode step
    can take, and which peak bounds it."""
    return _bound(count(s, "*") * attn_flops_per_row(s) * live_positions,
                  kv_bytes_per_position(s, bytes_per_el) * live_positions,
                  peaks)


def held_weight_bytes(s, bytes_per_el=2):
    """Everything the chip holds: for sizing, not for a roofline."""
    return fixed_weight_bytes(s, 0, bytes_per_el) \
        + s["E"] * s["V"] * bytes_per_el \
        + count(s, "E") * (s["held"][1] - s["held"][0]) \
        * expert_bytes(s, bytes_per_el)
