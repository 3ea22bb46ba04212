"""Recurrent ops (reference: src/model/operation/rnn.{h,cc}, unverified —
``CudnnRNNHandle``: packed single-buffer weight layout, LSTM/GRU/
vanilla-tanh/relu modes, multi-layer, bidirectional, inter-layer dropout).

TPU-native: each layer-direction is one ``lax.scan`` over time whose cell
is a fused GEMM (both input and recurrent projections hit the MXU);
``jax.vjp`` through the scan replaces cuDNN's rnn-backward.  The
cuDNN-style *packed weight* API is kept: all weights live in ONE flat
parameter (``RNNHandle.weights_size``), as the reference exposes, so
checkpoints and DistOpt treat an RNN as a single tensor.

Layout of the packed buffer (documented here since cuDNN's is opaque):
for each layer, for each direction: W_ih (G*H, I), W_hh (G*H, H),
b_ih (G*H,), b_hh (G*H,), flattened row-major and concatenated.
Gate order: LSTM i,f,g,o; GRU r,z,n (cuDNN convention).
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .. import autograd
from .. import amp
from ..autograd import _Func
from ..layer import Layer
from ..tensor import Tensor

_GATES = {"lstm": 4, "gru": 3, "vanilla_tanh": 1, "vanilla_relu": 1}


class RNNHandle:
    """Parity stand-in for CudnnRNNHandle: computes the packed weight size
    and the per-(layer, direction) slice offsets.

    The round-1..3 Pallas fused-cell LSTM kernel was DELETED in round 4
    after the decisive sweep (real v5e, on-device loop differencing):
    at the char-RNN bench shape it could not fit VMEM at all (T·B·4H
    floats must be resident) and silently fell back to a hoisted-GEMM
    scan that tied the plain scan (5108 vs 4816 samples/s, overlapping
    spreads); at every VMEM-fitting shape (T≤20) both paths run in
    tens of microseconds and the kernel LOSES or ties (0.32x–1.23x,
    all within run-to-run noise).  lax.scan + XLA is the one
    measurement-backed path.  ``use_pallas`` is still accepted (and
    ignored) for checkpoint/API compatibility."""

    def __init__(self, input_size, hidden_size, num_layers=1, mode="lstm",
                 bidirectional=False, dropout=0.0, use_pallas=False):
        assert mode in _GATES, f"unknown rnn mode {mode}"
        self.input_size = int(input_size)
        self.hidden_size = int(hidden_size)
        self.num_layers = int(num_layers)
        self.mode = mode
        self.bidirectional = bool(bidirectional)
        self.num_directions = 2 if bidirectional else 1
        self.dropout = float(dropout)
        del use_pallas  # accepted for API compat; kernel deleted (round 4)
        self.slices = self._layout()
        self.weights_size = self._total

    def _layout(self):
        G, H = _GATES[self.mode], self.hidden_size
        off = 0
        slices = {}
        for l in range(self.num_layers):
            I = self.input_size if l == 0 else H * self.num_directions
            for d in range(self.num_directions):
                for name, shape in (("w_ih", (G * H, I)), ("w_hh", (G * H, H)),
                                    ("b_ih", (G * H,)), ("b_hh", (G * H,))):
                    n = int(np.prod(shape))
                    slices[(l, d, name)] = (off, off + n, shape)
                    off += n
        self._total = off
        return slices

    def unpack(self, w_flat, l, d):
        out = {}
        for name in ("w_ih", "w_hh", "b_ih", "b_hh"):
            a, b, shape = self.slices[(l, d, name)]
            out[name] = w_flat[a:b].reshape(shape)
        return out

    def init_weights(self, device, dtype=jnp.float32) -> Tensor:
        """One flat weight tensor, uniform(-1/sqrt(H), 1/sqrt(H)) like
        cuDNN-era SINGA init."""
        w = Tensor((self.weights_size,), device=device, dtype=dtype,
                   requires_grad=True, stores_grad=True)
        k = 1.0 / np.sqrt(self.hidden_size)
        w.uniform(-k, k)
        return w


def _cell_fn(mode):
    if mode == "lstm":
        def cell(carry, xt, w_ih, w_hh, b):
            h, c = carry
            g = xt @ w_ih.T + h @ w_hh.T + b
            i, f, gg, o = jnp.split(g, 4, axis=-1)
            i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
            gg = jnp.tanh(gg)
            c = f * c + i * gg
            h = o * jnp.tanh(c)
            return (h, c), h
        return cell
    if mode == "gru":
        def cell(carry, xt, w_ih, w_hh, b_ih, b_hh):
            h, = carry
            gi = xt @ w_ih.T + b_ih
            gh = h @ w_hh.T + b_hh
            ir, iz, in_ = jnp.split(gi, 3, axis=-1)
            hr, hz, hn = jnp.split(gh, 3, axis=-1)
            r = jax.nn.sigmoid(ir + hr)
            z = jax.nn.sigmoid(iz + hz)
            n = jnp.tanh(in_ + r * hn)
            h = (1 - z) * n + z * h
            return (h,), h
        return cell
    act = jnp.tanh if mode == "vanilla_tanh" else jax.nn.relu

    def cell(carry, xt, w_ih, w_hh, b):
        h, = carry
        h = act(xt @ w_ih.T + h @ w_hh.T + b)
        return (h,), h
    return cell


def _scan_direction(x, h0, c0, params, mode, reverse):
    """x: (T, B, I) -> y: (T, B, H); returns (y, h_T, c_T)."""
    cell = _cell_fn(mode)
    if mode == "gru":
        def f(carry, xt):
            return cell(carry, xt, params["w_ih"], params["w_hh"],
                        params["b_ih"], params["b_hh"])
        carry0 = (h0,)
    else:
        b = params["b_ih"] + params["b_hh"]
        def f(carry, xt):
            return cell(carry, xt, params["w_ih"], params["w_hh"], b)
        carry0 = (h0, c0) if mode == "lstm" else (h0,)
    carry, ys = lax.scan(f, carry0, x, reverse=reverse)
    h_T = carry[0]
    c_T = carry[1] if mode == "lstm" else jnp.zeros_like(h_T)
    return ys, h_T, c_T


def rnn_forward(x, hx, cx, W, handle, batch_first=False):
    """Full multi-layer (bi)directional RNN as autograd ops.

    x: Tensor (T,B,I) or (B,T,I) if batch_first; hx/cx: Tensors
    (L*D, B, H); W: packed flat weight Tensor.
    Returns (y, hy, cy) Tensors; for non-LSTM modes cy is zeros.
    """
    mode = handle.mode
    L, D, H = handle.num_layers, handle.num_directions, handle.hidden_size

    if batch_first:
        x = autograd.transpose(x, (1, 0, 2))

    inp = x
    h_finals, c_finals = [], []
    for l in range(L):
        outs = []
        for d in range(D):
            idx = l * D + d

            def f(xv, hv, cv, wv, l=l, d=d, idx=idx, **_meta):
                params = handle.unpack(wv, l, d)
                y, hT, cT = _scan_direction(
                    xv, hv[idx], cv[idx], params, mode, reverse=(d == 1))
                return y, hT, cT

            # slice metadata rides op.params so sonnx export can unpack
            # the flat weight into ONNX W/R/B initializers (_dec_rnn)
            y, hT, cT = _Func(
                fn=f, name=f"RNN[l{l}d{d}]",
                mode=mode, layer=l, direction=d, idx=idx, hidden=H,
                slices={name: handle.slices[(l, d, name)]
                        for name in ("w_ih", "w_hh", "b_ih", "b_hh")},
            )(inp, hx, cx, W)
            outs.append(y)
            h_finals.append(hT)
            c_finals.append(cT)
        inp = outs[0] if D == 1 else autograd.cat(outs, axis=2)
        if handle.dropout > 0 and l < L - 1:
            inp = autograd.dropout(inp, handle.dropout)

    y = inp
    if batch_first:
        y = autograd.transpose(y, (1, 0, 2))
    hy = autograd.cat([autograd.unsqueeze(t, 0) for t in h_finals], axis=0) \
        if len(h_finals) > 1 else autograd.unsqueeze(h_finals[0], 0)
    cy = autograd.cat([autograd.unsqueeze(t, 0) for t in c_finals], axis=0) \
        if len(c_finals) > 1 else autograd.unsqueeze(c_finals[0], 0)
    return y, hy, cy


class _BaseRNN(Layer):
    """Shared layer wrapper over rnn_forward with the packed-weight
    handle (reference: layer.CudnnRNN / autograd RNN classes)."""

    mode = "vanilla_tanh"

    def __init__(self, hidden_size, num_layers=1, bidirectional=False,
                 dropout=0.0, batch_first=False, return_sequences=True,
                 use_pallas=False):  # accepted+ignored (round 4)
        super().__init__()
        self.hidden_size = int(hidden_size)
        self.num_layers = int(num_layers)
        self.bidirectional = bool(bidirectional)
        self.dropout = float(dropout)
        self.batch_first = bool(batch_first)
        self.return_sequences = return_sequences
        del use_pallas
        self.handle = None

    def initialize(self, x, hx=None, cx=None):
        input_size = x.shape[-1]
        self.handle = RNNHandle(
            input_size, self.hidden_size, self.num_layers, self.mode,
            self.bidirectional, self.dropout)
        self.W = self.handle.init_weights(x.device, amp.param_dtype(x.data.dtype))

    def _zero_state(self, x):
        B = x.shape[0] if self.batch_first else x.shape[1]
        L, D, H = self.num_layers, self.handle.num_directions, self.hidden_size
        z = Tensor((L * D, B, H), device=x.device, dtype=x.data.dtype,
                   requires_grad=False)
        return z

    def forward(self, x, hx=None, cx=None):
        if hx is None:
            hx = self._zero_state(x)
        if cx is None:
            cx = self._zero_state(x)
        y, hy, cy = rnn_forward(x, hx, cx, self.W, self.handle,
                                self.batch_first)
        if self.mode == "lstm":
            return (y, (hy, cy)) if self.return_sequences else (hy, (hy, cy))
        return (y, hy) if self.return_sequences else (hy, hy)


class LSTM(_BaseRNN):
    mode = "lstm"


class GRU(_BaseRNN):
    mode = "gru"


class RNN(_BaseRNN):
    """Vanilla RNN; nonlinearity in {'tanh','relu'} (reference arg)."""

    def __init__(self, hidden_size, nonlinearity="tanh", **kw):
        super().__init__(hidden_size, **kw)
        self.mode = f"vanilla_{nonlinearity}"


class CudnnRNN(LSTM):
    """Source-compat alias: the reference exposes the cuDNN-backed RNN
    under this name; here it is the same scan-based LSTM."""
