"""singa_tpu — a TPU-native deep-learning framework with the capabilities
of Apache SINGA (reference: yaochang/singa), built from scratch on
JAX/XLA/Pallas.  See SURVEY.md for the reference layer map this package
rebuilds and README.md for the design stance.
"""

import os as _os

import jax as _jax

# Persistent compile cache — the ONE place in the tree that sets it
# (tests/test_bringup.py greps for a second).  When
# JAX_COMPILATION_CACHE_DIR is set, JAX reads it and the package does
# nothing.  Otherwise the cache lives at a FIXED path inside the
# checkout: the directory is part of the cache key, so a tempfile name,
# a pid or a timestamp would never hit.
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__))), ".jax_cache"))

from . import amp  # noqa: F401,E402
from . import config  # noqa: F401,E402
from .config import VERSION as __version__  # noqa: F401,E402

# Submodules are imported lazily by user code (`from singa_tpu import
# tensor, device, autograd, layer, model, opt, sonnx`), mirroring how
# reference scripts import `from singa import ...`.
