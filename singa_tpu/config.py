"""Build/capability flags.

Reference parity: apache/singa surfaces compile-time CMake options
(``USE_CUDA``, ``USE_DNNL``, ``ENABLE_DIST``, ... baked into
``singa_config.h.in`` — see SURVEY.md §5.6, unverified paths) to Python.
Here the stack is a single-language JAX/XLA build, so the flags are computed
at import time from the live environment instead of at compile time.
"""

import jax

# The TPU-native stack replaces SINGA's CUDA/cuDNN/OpenCL backends entirely.
USE_CUDA = False
USE_CUDNN = False
USE_OPENCL = False
USE_DNNL = False

# JAX is always present; an accelerator backend may or may not be.
# USE_TPU is resolved lazily (module __getattr__ below): calling
# jax.devices() at import time would initialize the XLA backend as a side
# effect of `import singa_tpu`, which breaks jax.distributed.initialize
# (it must run before any backend init) for multi-host users.
USE_PYTHON = True


def _use_tpu() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())


def __getattr__(name):
    if name == "USE_TPU":
        return _use_tpu()
    raise AttributeError(name)

# Distributed training (DistOpt over ICI/DCN collectives) is always compiled
# in: jax collectives need no extra build flag, unlike NCCL/MPI.
ENABLE_DIST = True

CPP_VERSION = None  # no native C++ tensor core; see native/ for IO helpers
VERSION = "0.2.0"

# ---------------------------------------------------------------------------
# Debug mode (SURVEY.md §5.2): the reference has no sanitizers — scheduler
# read/write edges are its only race protection.  The TPU analogue: jit
# purity makes races structurally impossible, and JAX already raises on
# any host access to a donated buffer; debug mode adds the check that
# still matters on this stack — NaN detection inside compiled steps
# (jax_debug_nans re-runs the offending op eagerly and raises at the op,
# not three steps later).
# ---------------------------------------------------------------------------

_debug = False


def debug(enable: bool = True) -> None:
    """Toggle NaN-checking debug mode (jax_debug_nans).  Costs a re-run
    per detected NaN only; keep off for benchmarking."""
    global _debug
    _debug = bool(enable)
    jax.config.update("jax_debug_nans", _debug)


def debug_enabled() -> bool:
    return _debug
