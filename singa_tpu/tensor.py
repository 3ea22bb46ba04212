"""SINGA-shaped ``Tensor`` over ``jax.Array``.

Reference parity (apache/singa, paths unverified — SURVEY.md §2):
  - ``python/singa/tensor.py`` (~1.7k LoC): Python ``Tensor`` wrapping the
    SWIG ``CTensor``; numpy interop, operators, ``to_device``, module-level
    functional ops (``add``, ``mult``, ``softmax``, reductions, random
    fills, row/column ops...).
  - ``src/core/tensor/tensor.cc`` + ``tensor_math_{cpp,cuda}.h``: the C++
    tensor and its per-backend math dispatch (cuBLAS GEMM, CUDA kernels).

TPU-native design: the SWIG boundary and the C++ tensor disappear; one
Python class holds a ``jax.Array`` and every math op is a ``jnp``/``lax``
call, so the same code path serves eager mode and ``jax.jit`` tracing
(graph mode).  "In-place" SINGA ops (``+=``, ``SetValue``, ``copy_data``)
become functional *rebinds* of the underlying array — semantically
equivalent for SINGA programs, which never alias one buffer through two
tensors across a mutation (the scheduler would serialize them anyway).

Autograd bookkeeping (``creator``/``requires_grad``/``stores_grad``)
matches ``python/singa/tensor.py``; the tape lives in ``autograd.py``.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from . import device as device_module
from .device import get_default_device

# ---------------------------------------------------------------------------
# dtypes — SINGA's proto enum (core.proto kFloat32...) becomes plain numpy
# dtypes; names kept importable as tensor.float32 etc.
# ---------------------------------------------------------------------------
float16 = jnp.float16
bfloat16 = jnp.bfloat16
float32 = jnp.float32
float64 = jnp.float64  # note: jax x64 is off by default; maps to float32
int8 = jnp.int8
uint8 = jnp.uint8
int16 = jnp.int16
int32 = jnp.int32
int64 = jnp.int64
bool_ = jnp.bool_

# SINGA proto-enum-style names for source compat
kFloat16 = float16
kFloat32 = float32
kInt = int32
kInt32 = int32
kInt64 = int64
kChar = int8
kUChar = uint8
kDouble = float64

_SINGA2DTYPE = {
    "float16": float16,
    "bfloat16": bfloat16,
    "float32": float32,
    "int32": int32,
    "int64": int64,
}


def _asdtype(dt):
    if dt is None:
        return jnp.float32
    if isinstance(dt, str):
        return _SINGA2DTYPE.get(dt, np.dtype(dt).type)
    return dt


def _raw(x):
    """Unwrap Tensor → jax array; pass scalars/arrays through."""
    if isinstance(x, Tensor):
        return x.data
    return x


class Tensor:
    """A tensor on a singa device, wrapping a ``jax.Array`` (or a tracer
    while a graph-mode step is being traced).

    Mirrors python/singa/tensor.py's constructor signature (unverified).
    """

    __array_priority__ = 100  # make numpy defer to our reflected operators

    def __init__(
        self,
        shape=(),
        device=None,
        dtype=None,
        data=None,
        requires_grad=True,
        stores_grad=False,
        creator=None,
        name=None,
    ):
        """``dtype=None`` means float32 for fresh (zero-filled) tensors and
        "keep the data's dtype" when ``data`` is given; an explicit dtype
        always wins."""
        self.device = device if device is not None else get_default_device()
        want = _asdtype(dtype) if dtype is not None else None
        if data is None:
            arr = jnp.zeros(tuple(shape), dtype=want or jnp.float32)
            arr = jax.device_put(arr, self.device.jax_device)
        else:
            if isinstance(data, Tensor):
                arr = data.data
            elif isinstance(data, np.ndarray):
                arr = jax.device_put(jnp.asarray(data), self.device.jax_device)
            else:
                # jax array / tracer / python scalar
                arr = jnp.asarray(data)
            if want is not None and arr.dtype != np.dtype(want):
                arr = arr.astype(want)
        self.data = arr
        self.requires_grad = requires_grad
        self.stores_grad = stores_grad
        self.creator = creator
        self.name = name

    # -- basic properties --------------------------------------------------
    @property
    def shape(self):
        return tuple(self.data.shape)

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def T(self):
        return _wrap(jnp.transpose(self.data), self.device)

    def ndim(self):
        return self.data.ndim

    def is_empty(self):
        return self.size() == 0

    def size(self):
        return int(np.prod(self.shape)) if self.shape else 1

    def memsize(self):
        return self.size() * self.data.dtype.itemsize

    def __len__(self):
        return self.shape[0] if self.shape else 0

    def __repr__(self):
        return (
            f"Tensor(shape={self.shape}, dtype={np.dtype(self.data.dtype).name}, "
            f"device={type(self.device).__name__})"
        )

    # -- shape ops ---------------------------------------------------------
    def reshape(self, shape):
        """Returns a reshaped tensor (SINGA >=3 returns new tensor)."""
        return _wrap(jnp.reshape(self.data, tuple(shape)), self.device)

    def transpose(self, axes=None):
        return _wrap(jnp.transpose(self.data, axes), self.device)

    def squeeze(self, axis=None):
        return _wrap(jnp.squeeze(self.data, axis), self.device)

    def reset_like(self, t: "Tensor"):
        return self._settle(jnp.zeros(t.shape, dtype=t.data.dtype))

    def as_type(self, dtype):
        return _wrap(self.data.astype(_asdtype(dtype)), self.device)

    def astype(self, dtype):
        return self.as_type(dtype)

    # -- device movement ---------------------------------------------------
    def to_device(self, dev):
        """Move in place (SINGA Tensor::ToDevice mutates); returns self."""
        if not _is_tracing(self.data):
            self.data = jax.device_put(self.data, dev.jax_device)
        self.device = dev
        return self

    def to_host(self):
        """Move to host CPU (reference Tensor::ToHost) — explicitly a
        CppCPU, not the mutable default device, which may itself be an
        accelerator after set_default_device(tpu)."""
        return self.to_device(device_module.CppCPU())

    # -- fills / random ----------------------------------------------------
    def _settle(self, arr):
        """Rebind ``self.data`` to ``arr`` ON this tensor's device.  A
        fill computes wherever its operands are (an unplaced PRNG key
        or constant lands on the process default device); without this
        a ``gaussian()`` quietly moved a parameter off the device it
        was created on, and everything that follows the weights (the
        serve engine) followed it there."""
        if not _is_tracing(arr):
            arr = jax.device_put(arr, self.device.jax_device)
        self.data = arr
        return self

    def set_value(self, x, inplace=True):
        return self._settle(jnp.full(self.shape, x, dtype=self.data.dtype))

    def SetValue(self, x):  # C++-style alias used by reference scripts
        return self.set_value(x)

    def gaussian(self, mean=0.0, std=1.0):
        key = self.device.rng_key()
        return self._settle(
            mean + std * jax.random.normal(key, self.shape, dtype=jnp.float32))

    def uniform(self, low=0.0, high=1.0):
        key = self.device.rng_key()
        return self._settle(jax.random.uniform(
            key, self.shape, dtype=jnp.float32, minval=low, maxval=high
        ).astype(_asdtype(self.dtype)))

    def bernoulli(self, p):
        key = self.device.rng_key()
        return self._settle(jax.random.bernoulli(key, p, self.shape).astype(
            _asdtype(self.dtype)
        ))

    # -- copies ------------------------------------------------------------
    def copy_from_numpy(self, np_array, offset=0):
        assert np_array.size == self.size(), "array size mismatch"
        return self._settle(jnp.asarray(
            np.ascontiguousarray(np_array, dtype=np.dtype(self.data.dtype)).reshape(
                self.shape
            )
        ))

    def copy_data(self, t: "Tensor"):
        """Copy t's buffer into self (shape must match)."""
        assert t.shape == self.shape, f"shape mismatch {t.shape} vs {self.shape}"
        self.data = t.data.astype(self.data.dtype)
        return self

    def copy_from(self, t: "Tensor"):
        return self.copy_data(t)

    def clone(self):
        """Deep copy (reference Tensor::Clone copies the buffer).  The
        copy matters: graph-mode steps donate their state buffers to XLA,
        so an aliased buffer would be invalidated by the donor's next
        step."""
        data = self.data
        if not _is_tracing(data):
            data = jnp.array(data, copy=True)
        t = Tensor(
            device=self.device,
            data=data,
            requires_grad=self.requires_grad,
            stores_grad=self.stores_grad,
        )
        return t

    def copy(self):
        return self.clone()

    def deepcopy(self):
        return self.clone()

    # -- reductions / norms ------------------------------------------------
    def l1(self):
        return float(jnp.mean(jnp.abs(self.data)))

    def l2(self):
        # SINGA Tensor::L2 returns ||x||_2 / sqrt(n) (nrm2 / num elems? —
        # upstream divides by size; we match mean-style normalization).
        return float(jnp.linalg.norm(self.data.ravel()) / np.sqrt(self.size()))

    def sum(self, axis=None):
        return _wrap(jnp.sum(self.data, axis=axis), self.device)

    def mean(self, axis=None):
        return _wrap(jnp.mean(self.data, axis=axis), self.device)

    def max(self, axis=None):
        return _wrap(jnp.max(self.data, axis=axis), self.device)

    def min(self, axis=None):
        return _wrap(jnp.min(self.data, axis=axis), self.device)

    # -- arithmetic operators (eager, non-autograd — matches reference
    #    tensor.py, where operators go through tensor math not the tape) ---
    def __add__(self, x):
        return _wrap(self.data + _raw(x), self.device)

    __radd__ = __add__

    def __sub__(self, x):
        return _wrap(self.data - _raw(x), self.device)

    def __rsub__(self, x):
        return _wrap(_raw(x) - self.data, self.device)

    def __mul__(self, x):
        return _wrap(self.data * _raw(x), self.device)

    __rmul__ = __mul__

    def __truediv__(self, x):
        return _wrap(self.data / _raw(x), self.device)

    def __rtruediv__(self, x):
        return _wrap(_raw(x) / self.data, self.device)

    def __floordiv__(self, x):
        return _wrap(self.data // _raw(x), self.device)

    def __pow__(self, x):
        return _wrap(self.data ** _raw(x), self.device)

    def __neg__(self):
        return _wrap(-self.data, self.device)

    def __abs__(self):
        return _wrap(jnp.abs(self.data), self.device)

    def __matmul__(self, x):
        return _wrap(jnp.matmul(self.data, _raw(x)), self.device)

    # in-place ops rebind the array; under SINGA semantics the scheduler
    # serializes writers, so rebinding is observationally equivalent.
    def __iadd__(self, x):
        self.data = self.data + _raw(x)
        return self

    def __isub__(self, x):
        self.data = self.data - _raw(x)
        return self

    def __imul__(self, x):
        self.data = self.data * _raw(x)
        return self

    def __itruediv__(self, x):
        self.data = self.data / _raw(x)
        return self

    # comparisons return 0/1 float tensors like SINGA's LT/GT kernels
    def __lt__(self, x):
        return _wrap((self.data < _raw(x)).astype(jnp.float32), self.device)

    def __le__(self, x):
        return _wrap((self.data <= _raw(x)).astype(jnp.float32), self.device)

    def __gt__(self, x):
        return _wrap((self.data > _raw(x)).astype(jnp.float32), self.device)

    def __ge__(self, x):
        return _wrap((self.data >= _raw(x)).astype(jnp.float32), self.device)

    def __getitem__(self, idx):
        return _wrap(self.data[idx], self.device)

    def __float__(self):
        return float(self.data)

    def __int__(self):
        return int(self.data)


def _wrap(arr, dev=None) -> Tensor:
    t = Tensor.__new__(Tensor)
    t.data = arr
    t.device = dev if dev is not None else get_default_device()
    t.requires_grad = False
    t.stores_grad = False
    t.creator = None
    t.name = None
    return t


def _is_tracing(x) -> bool:
    return isinstance(x, jax.core.Tracer)


# ---------------------------------------------------------------------------
# module-level functional API (reference: python/singa/tensor.py module
# functions, unverified list — implemented generously)
# ---------------------------------------------------------------------------

def from_numpy(np_array, device=None, requires_grad=False) -> Tensor:
    np_array = np.asarray(np_array)
    if np_array.dtype == np.float64:
        np_array = np_array.astype(np.float32)
    elif np_array.dtype == np.int64:
        # jax runs x32: jnp would truncate to int32 anyway, but via the
        # Tensor(dtype=int64) path that emits a per-call UserWarning;
        # downcast explicitly like float64 -> float32 above
        np_array = np_array.astype(np.int32)
    t = Tensor(
        shape=np_array.shape,
        device=device,
        dtype=np_array.dtype.type,
        data=np_array,
        requires_grad=requires_grad,
    )
    return t


def to_host(t):
    """Host COPY of t (reference: module-level tensor.to_host clones
    then moves — the input keeps its device; only the method form
    migrates in place)."""
    return t.clone().to_host()


def to_numpy(t) -> np.ndarray:
    arr = _raw(t)
    if isinstance(arr, jax.Array) and not arr.is_fully_addressable:
        # multi-host: a cross-process sharded array (e.g. DistOpt
        # residuals after a step) needs a collective fetch.  SPMD
        # lockstep: every process calls to_numpy at the same point, so
        # the allgather is safe.
        from jax.experimental import multihost_utils as mh

        return np.asarray(mh.process_allgather(arr, tiled=True))
    return np.asarray(jax.device_get(arr))


def from_raw_tensor(arr, device=None) -> Tensor:
    return _wrap(jnp.asarray(arr), device)


def sizeof(dtype) -> int:
    return np.dtype(_asdtype(dtype)).itemsize


def _unary(fn):
    def op(t):
        return _wrap(fn(_raw(t)), getattr(t, "device", None))

    return op


abs = _unary(jnp.abs)  # noqa: A001 - mirrors reference module name
exp = _unary(jnp.exp)
log = _unary(jnp.log)
sigmoid = _unary(jax.nn.sigmoid)
sign = _unary(jnp.sign)
sqrt = _unary(jnp.sqrt)
square = _unary(jnp.square)
tanh = _unary(jnp.tanh)
ceil = _unary(jnp.ceil)
floor = _unary(jnp.floor)
round = _unary(jnp.round)  # noqa: A001
relu = _unary(jax.nn.relu)


def pow(t, x, out=None):  # noqa: A001
    r = _wrap(_raw(t) ** _raw(x), getattr(t, "device", None))
    if out is not None:
        out.data = r.data
        return out
    return r


def sum(t, axis=None, out=None):  # noqa: A001
    r = _wrap(jnp.sum(_raw(t), axis=axis), getattr(t, "device", None))
    if out is not None:
        out.data = r.data
        return out
    return r


def mean(t, axis=None):
    return _wrap(jnp.mean(_raw(t), axis=axis), getattr(t, "device", None))


def average(t, axis=None):
    return mean(t, axis)


def reshape(t, shape):
    return t.reshape(shape)


def transpose(t, axes=None):
    return t.transpose(axes)


def squeeze(t, axis=None):
    return t.squeeze(axis)


def concatenate(tensors, axis=0):
    dev = tensors[0].device if tensors else None
    return _wrap(jnp.concatenate([_raw(t) for t in tensors], axis=axis), dev)


def stack(tensors, axis=0):
    dev = tensors[0].device if tensors else None
    return _wrap(jnp.stack([_raw(t) for t in tensors], axis=axis), dev)


def repeat(t, repeats, axis=None):
    return _wrap(jnp.repeat(_raw(t), repeats, axis=axis), getattr(t, "device", None))


def tile(t, reps):
    return _wrap(jnp.tile(_raw(t), reps), getattr(t, "device", None))


def add(lhs, rhs, ret=None):
    r = _wrap(_raw(lhs) + _raw(rhs), getattr(lhs, "device", None))
    if ret is not None:
        ret.data = r.data
        return ret
    return r


def sub(lhs, rhs, ret=None):
    r = _wrap(_raw(lhs) - _raw(rhs), getattr(lhs, "device", None))
    if ret is not None:
        ret.data = r.data
        return ret
    return r


def eltwise_mult(lhs, rhs, ret=None):
    r = _wrap(_raw(lhs) * _raw(rhs), getattr(lhs, "device", None))
    if ret is not None:
        ret.data = r.data
        return ret
    return r


def div(lhs, rhs, ret=None):
    r = _wrap(_raw(lhs) / _raw(rhs), getattr(lhs, "device", None))
    if ret is not None:
        ret.data = r.data
        return ret
    return r


def mult(A, B, C=None, alpha=1.0, beta=0.0):
    """GEMM: C = alpha*A@B + beta*C (reference: tensor.cc Mult → cuBLAS
    GEMM in tensor_math_cuda.h; here lax dot_general hits the MXU)."""
    out = alpha * jnp.matmul(_raw(A), _raw(B))
    if C is not None and beta != 0.0:
        out = out + beta * _raw(C)
    r = _wrap(out, getattr(A, "device", None))
    if C is not None:
        C.data = r.data
        return C
    return r


def matmul(A, B):
    return _wrap(jnp.matmul(_raw(A), _raw(B)), getattr(A, "device", None))


def einsum(spec, *tensors):
    dev = getattr(tensors[0], "device", None) if tensors else None
    return _wrap(jnp.einsum(spec, *[_raw(t) for t in tensors]), dev)


def tensordot(A, B, axes=2):
    return _wrap(jnp.tensordot(_raw(A), _raw(B), axes=axes), getattr(A, "device", None))


def axpy(alpha, x, y):
    """y += alpha * x (BLAS axpy; reference tensor_math_cuda.h Axpy)."""
    y.data = y.data + alpha * _raw(x)
    return y


def softmax(t, axis=-1, out=None):
    r = _wrap(jax.nn.softmax(_raw(t), axis=axis), getattr(t, "device", None))
    if out is not None:
        out.data = r.data
        return out
    return r


def lt(t, x):
    return t < x


def le(t, x):
    return t <= x


def gt(t, x):
    return t > x


def ge(t, x):
    return t >= x


def maximum(a, b):
    return _wrap(jnp.maximum(_raw(a), _raw(b)), getattr(a, "device", None))


def minimum(a, b):
    return _wrap(jnp.minimum(_raw(a), _raw(b)), getattr(a, "device", None))


def clip(t, lo, hi):
    return _wrap(jnp.clip(_raw(t), lo, hi), getattr(t, "device", None))


def argmax(t, axis=-1):
    return _wrap(jnp.argmax(_raw(t), axis=axis), getattr(t, "device", None))


def argmin(t, axis=-1):
    return _wrap(jnp.argmin(_raw(t), axis=axis), getattr(t, "device", None))


def where(cond, a, b):
    return _wrap(jnp.where(_raw(cond) != 0, _raw(a), _raw(b)), getattr(a, "device", None))


# -- row/column ops (reference tensor.py add_row/add_column etc. operate on
#    2-D matrices; broadcasting does the work on XLA) ----------------------

def add_column(v, M):
    """M[:, j] += v for all j (v is length-nrows)."""
    M.data = M.data + _raw(v)[:, None]
    return M


def add_row(v, M):
    M.data = M.data + _raw(v)[None, :]
    return M


def mult_column(v, M):
    M.data = M.data * _raw(v)[:, None]
    return M


def mult_row(v, M):
    M.data = M.data * _raw(v)[None, :]
    return M


def div_column(v, M):
    M.data = M.data / _raw(v)[:, None]
    return M


def div_row(v, M):
    M.data = M.data / _raw(v)[None, :]
    return M


def sum_columns(M):
    return _wrap(jnp.sum(_raw(M), axis=1), getattr(M, "device", None))


def sum_rows(M):
    return _wrap(jnp.sum(_raw(M), axis=0), getattr(M, "device", None))


# -- random fills ----------------------------------------------------------

def gaussian(mean, std, t: Tensor):
    return t.gaussian(mean, std)


def uniform(low, high, t: Tensor):
    return t.uniform(low, high)


def bernoulli(p, t: Tensor):
    return t.bernoulli(p)


def zeros_like(t):
    return _wrap(jnp.zeros_like(_raw(t)), getattr(t, "device", None))


def ones_like(t):
    return _wrap(jnp.ones_like(_raw(t)), getattr(t, "device", None))


def zeros(shape, dtype=float32, device=None):
    return Tensor(shape=shape, device=device, dtype=dtype)


def ones(shape, dtype=float32, device=None):
    t = Tensor(shape=shape, device=device, dtype=dtype)
    return t.set_value(1.0)


def eye(n, dtype=float32, device=None):
    return _wrap(jnp.eye(n, dtype=_asdtype(dtype)), device)


def arange(*args, dtype=float32, device=None):
    return _wrap(jnp.arange(*args, dtype=_asdtype(dtype)), device)


def copy_data_to_from(dst: Tensor, src: Tensor, size=None):
    dst.copy_data(src)
    return dst
