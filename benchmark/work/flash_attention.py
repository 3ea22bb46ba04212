"""The least a causal flash-attention training step can cost, from its
shapes: the forward kernel and the two backward kernels (dq; dk/dv) of
one layer, batch B, H heads, sequence S, head size D.

Forward: QK^T and PV, 2 matmuls of 2*S*S*D FLOPs a head, halved by the
causal mask.  Backward: dq recomputes QK^T and forms dP and dQ (3
matmuls); dk/dv recomputes QK^T and forms dP, dV, dK (4 matmuls).
Recomputation inside the kernels is part of the algorithm the kernels
implement, so it counts here (this is a kernel's roofline, not MFU).
Bytes: q, k, v, o (and their gradients) read or written once.
"""


def flops(B, H, S, D):
    one = 2 * S * S * D * B * H / 2            # one causal S x S matmul
    return {"fwd": 2 * one, "dq": 3 * one, "dkv": 4 * one}


def bytes_moved(B, H, S, D, bytes_per_el=2):
    t = B * H * S * D * bytes_per_el           # one (B, H, S, D) tensor
    return {"fwd": 4 * t,                      # q k v -> o
            "dq": 5 * t,                       # q k v do -> dq
            "dkv": 6 * t}                      # q k v do -> dk dv


def bound_seconds(B, H, S, D, peaks, bytes_per_el=2):
    """{kernel: (seconds, which bound)} on a chip with ``peaks``."""
    out = {}
    f, b = flops(B, H, S, D), bytes_moved(B, H, S, D, bytes_per_el)
    for k in f:
        tc = f[k] / peaks["bf16_flops_per_s"]
        tm = b[k] / peaks["hbm_bytes_per_s"]
        out[k] = (max(tc, tm), "compute" if tc >= tm else "memory")
    return out
