"""Test configuration: force an 8-device virtual CPU mesh.

The reference (apache/singa) could only test its NCCL Communicator with >=2
physical GPUs (SURVEY.md §4); here every distributed code path runs in CI on
a virtual 8-device CPU topology.

The tier-1 command already sets ``JAX_PLATFORMS=cpu``; the config update
below makes a bare ``pytest`` behave the same on a machine that has a chip,
where the suite would otherwise take it (interpret-mode Pallas and the
8-device mesh exist only on the CPU backend).  It must run before any
backend initializes, i.e. before singa_tpu or test modules touch
jax.devices().
"""

import os
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running tests (subprocess multihost, CNN-zoo "
        "training, >15s parity sweeps); `-m 'not slow'` is the fast "
        "inner loop for builders")


@pytest.fixture(autouse=True, scope="module")
def _bound_jax_executable_maps():
    """Release compiled executables between test MODULES.

    One pytest process compiles thousands of XLA:CPU executables (each
    eager `_op` primitive application of a new shape caches one);
    their code mappings accumulate against the kernel's
    ``vm.max_map_count`` (65530 default) until, near the end of the
    full suite, an mmap fails inside ``backend_compile_and_load`` and
    XLA SEGFAULTS (observed twice at the same 88% mark, in whichever
    test compiled next — reproduced and measured: the map count grows
    ~4k/min through the ONNX-conformance module).  Clearing jax's
    caches per module returns the maps to baseline; within-module
    compilation reuse — where nearly all the cache hits are — is
    unaffected.  The serve stack's own cache of compiled programs
    (``serve/paged.py`` ``_aot_cache``: decode steps and chunk rows,
    every launch width of every budgeted engine) is emptied with
    them."""
    yield
    jax.clear_caches()
    paged = sys.modules.get("singa_tpu.serve.paged")
    if paged is not None:
        paged._aot_cache.clear()


# tests/benchmark/test_bench_loader.py::test_cell_loads_with_all_its_files
# asserts, for every cell of BENCHMARK.json, that its configuration's
# family is "gpt2".  PR 27 added the first cell of another family
# (falconh1-serve-docqa), PR 34 the second (dotsvlm1-serve-reason), and a
# model_config PR may edit no file the benchmark already has;
# tests/benchmark/test_bench_falcon_h1.py and test_bench_mla_moe.py make
# the test's other assertions for those cells.  Take this hook out with
# that line, in the next benchmark PR (PERF.md, Open questions).
_OTHER_FAMILY = {
    "falconh1-serve-docqa": "falcon_h1",
    "dotsvlm1-serve-reason": "mla_moe",
}


def pytest_collection_modifyitems(items):
    case = "test_bench_loader.py::test_cell_loads_with_all_its_files[{}]"
    for item in items:
        for cell, family in _OTHER_FAMILY.items():
            if item.nodeid.endswith(case.format(cell)):
                item.add_marker(pytest.mark.xfail(
                    strict=True, reason="asserts family == 'gpt2' of "
                                        f"every cell; this one is {family}"))
