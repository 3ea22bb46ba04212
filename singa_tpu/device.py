"""Devices and platform discovery for the TPU-native SINGA rebuild.

Reference parity (apache/singa, paths unverified — see SURVEY.md §2.1):
  - ``include/singa/core/device.h`` / ``src/core/device/device.cc``:
    ``Device`` base with ``Exec(fn, read_blocks, write_blocks)``, block
    allocation, ``CopyDataToFrom``.
  - ``src/core/device/cpp_cpu.cc`` (``CppCPU``),
    ``src/core/device/cuda_gpu.cc`` (``CudaGPU``: stream + cuBLAS/cuDNN
    handles + cnmem pool), ``src/core/device/platform.cc`` (``Platform``).
  - ``python/singa/device.py``: ``create_cuda_gpu(_on)``,
    ``get_default_device``.

TPU-native design: a singa ``Device`` wraps a ``jax.Device``. There is no
``Exec``/``Block``/stream machinery to rebuild — XLA owns HBM and the
dispatch queue, and SINGA's buffering graph scheduler
(``src/core/scheduler/scheduler.cc``) collapses into ``jax.jit`` tracing of
the whole train step (see ``model.py``).  What remains device state here:

  * placement: which ``jax.Device`` new tensors land on,
  * the graph flag (``EnableGraph`` — whether ``Model`` runs jitted),
  * a functional PRNG key (SINGA's per-device curand generator becomes a
    threaded ``jax.random`` key; graph mode treats it as traced state),
  * profiling verbosity (SINGA v3.1 per-op time profiling → ``jax.profiler``).
"""

from __future__ import annotations

import os
import threading

import jax
import numpy as np

__all__ = [
    "Device",
    "CppCPU",
    "TpuDevice",
    "create_tpu_device",
    "create_tpu_devices",
    "create_tpu_device_on",
    "create_cuda_gpu",
    "create_cuda_gpu_on",
    "create_cuda_gpus",
    "create_cuda_gpus_on",
    "get_default_device",
    "set_default_device",
    "enable_tensor_graph",
    "get_num_tpus",
    "device_query",
]

_lock = threading.Lock()


def _accelerator_devices():
    """Non-CPU jax devices THIS process can address (multi-host runtimes
    list every host's devices in jax.devices(); eager placement must
    stay on local chips), falling back to local CPU when none exist."""
    local = jax.local_devices()
    accel = [d for d in local if d.platform != "cpu"]
    return accel if accel else local


class Device:
    """Base device: placement + graph flag + PRNG + profiling verbosity.

    Mirrors ``singa::Device`` (include/singa/core/device.h, unverified) in
    API shape; the execution model is jax's async dispatch instead of
    ``Exec`` lambdas over ``Block`` dependencies.
    """

    def __init__(self, dev_id: int, jax_device, lang: str):
        self._id = int(dev_id)
        self.jax_device = jax_device
        self._lang = lang
        self.graph_enabled_ = False
        self.verbosity_ = 0
        self.skip_iteration_ = 5
        # Functional RNG: one key per device, split on demand.  In graph mode
        # Model treats this as part of the persistent traced state so random
        # ops (dropout, init) stay reproducible and jit-safe.
        seed = int.from_bytes(os.urandom(4), "little")
        self._rng_key = jax.random.PRNGKey(seed)

    # -- identity ----------------------------------------------------------
    def id(self) -> int:
        return self._id

    def lang(self) -> str:
        return self._lang

    @property
    def platform(self) -> str:
        return self.jax_device.platform

    def __repr__(self):
        return f"<{type(self).__name__} id={self._id} jax={self.jax_device}>"

    # -- RNG ---------------------------------------------------------------
    def SetRandSeed(self, seed: int):
        self._rng_key = jax.random.PRNGKey(int(seed))

    def rng_key(self):
        """Split and return a fresh subkey (mutates device key state)."""
        self._rng_key, sub = jax.random.split(self._rng_key)
        return sub

    # -- graph mode --------------------------------------------------------
    # SINGA: Device::EnableGraph buffers Exec lambdas into the scheduler
    # graph; here the flag tells Model.compile to jit the train step.
    def EnableGraph(self, enable: bool):
        self.graph_enabled_ = bool(enable)

    def graph_enabled(self) -> bool:
        return self.graph_enabled_

    def ResetGraph(self):
        """Drop compiled step caches (SINGA: Graph::Reset)."""
        from . import model as _model

        _model._clear_compiled_caches(self)

    # -- sync / profiling --------------------------------------------------
    def Sync(self):
        """Block until all queued work on this device is done."""
        (jax.device_put(0, self.jax_device) + 0).block_until_ready()

    def SetVerbosity(self, v: int):
        self.verbosity_ = int(v)

    def SetSkipIteration(self, n: int):
        self.skip_iteration_ = int(n)

    def PrintTimeProfiling(self):
        """Per-op profiling: the static XLA cost-analysis table of every
        compiled step, and — when a ``jax.profiler`` trace was captured
        via ``enable_profiling``/``disable_profiling`` — the MEASURED
        per-op/per-fusion durations parsed out of that trace, printed
        next to it.

        SINGA v3.1 prints CUDA-event timings per scheduler node; the
        cost table is the static analogue and the parsed trace is the
        measured one (true parity with the reference's v3.1 measured
        profiling).  Returns the measured-durations
        dict (``{op name: {"count", "total_us"}}``; empty when no trace
        was captured) so tests and tooling can assert on it.
        """
        from . import model as _model

        for fn, cost in _model._compiled_cost_tables(self):
            print(f"== time profiling for compiled step {fn} ==")
            for k, v in sorted(cost.items()):
                print(f"  {k}: {v}")
        measured = self.profiled_durations()
        if measured:
            print("== measured durations (jax.profiler trace, "
                  f"{len(measured)} distinct ops) ==")
            top = sorted(measured.items(),
                         key=lambda kv: -kv[1]["total_us"])[:32]
            for name, rec in top:
                print(f"  {name}: {rec['total_us']:.1f} us over "
                      f"{rec['count']} event(s)")
        return measured

    def profiled_durations(self) -> dict:
        """Measured per-op durations from the last profiler capture:
        parse the newest trace-event JSON under the ``enable_profiling``
        logdir and aggregate every complete ("ph" == "X") event's
        duration by op name — XLA thunk/fusion events ("dot.3",
        "multiply_multiply_fusion", executable dispatch) survive, host
        Python frame events (``$file.py:line`` names) are dropped.
        ``{}`` when no capture exists; never raises (profiling is a
        diagnostic, not a dependency)."""
        logdir = getattr(self, "_profile_dir", None)
        if not logdir:
            return {}
        import glob
        import gzip
        import json

        try:
            paths = sorted(
                glob.glob(os.path.join(logdir, "**",
                                       "*.trace.json.gz"),
                          recursive=True),
                key=os.path.getmtime)
            if not paths:
                return {}
            with gzip.open(paths[-1], "rt") as fh:
                trace = json.load(fh)
        except Exception:
            return {}
        out = {}
        for e in trace.get("traceEvents", []):
            if e.get("ph") != "X" or not e.get("dur"):
                continue
            name = e.get("name", "")
            # host-side Python frame annotations ("$profiler.py:91
            # start_trace", "file.py:123 fn") are tracing overhead,
            # not device work
            if name.startswith("$") or ".py:" in name:
                continue
            rec = out.setdefault(name, {"count": 0, "total_us": 0.0})
            rec["count"] += 1
            rec["total_us"] += float(e["dur"])
        return out

    def enable_profiling(self, logdir: str = "/tmp/singa_tpu_trace"):
        jax.profiler.start_trace(logdir)
        self._profile_dir = logdir

    def disable_profiling(self):
        jax.profiler.stop_trace()


class CppCPU(Device):
    """Host CPU device (reference: src/core/device/cpp_cpu.cc, unverified)."""

    def __init__(self, dev_id: int = -1):
        cpus = ([d for d in jax.local_devices(backend="cpu")]
                if _has_cpu_backend() else jax.local_devices())
        idx = 0 if dev_id < 0 else dev_id % len(cpus)
        super().__init__(dev_id, cpus[idx], "kCpp")


class TpuDevice(Device):
    """Accelerator device — the rebuild of ``CudaGPU``
    (src/core/device/cuda_gpu.cc, unverified).  No stream/handle/cnmem
    state survives the port: XLA's client owns HBM and execution order.
    """

    def __init__(self, dev_id: int = 0, jax_device=None):
        if jax_device is None:
            accel = _accelerator_devices()
            jax_device = accel[dev_id % len(accel)]
        super().__init__(dev_id, jax_device, "kTpu")


def _has_cpu_backend() -> bool:
    try:
        jax.devices("cpu")
        return True
    except RuntimeError:
        return False


# ---------------------------------------------------------------------------
# Platform (reference: src/core/device/platform.cc, unverified)
# ---------------------------------------------------------------------------

_default_device: Device | None = None
_device_cache: dict = {}


def _cached(kind, dev_id, ctor):
    with _lock:
        key = (kind, dev_id)
        if key not in _device_cache:
            _device_cache[key] = ctor()
        return _device_cache[key]


def get_num_tpus() -> int:
    return len(_accelerator_devices())


def create_tpu_device(dev_id: int = 0) -> TpuDevice:
    return _cached("tpu", dev_id, lambda: TpuDevice(dev_id))


def create_tpu_device_on(dev_id: int) -> TpuDevice:
    return create_tpu_device(dev_id)


def create_tpu_devices(num: int) -> list:
    return [create_tpu_device(i) for i in range(num)]


# SINGA-compatible creators (python/singa/device.py, unverified).  Per the
# north star, reference train scripts switch to TPU by changing only the
# device-creation line; aliasing the CUDA creators to the accelerator device
# means even that change is optional.
def create_cuda_gpu(set_default: bool = True):
    return create_tpu_device(0)


def create_cuda_gpu_on(dev_id: int, set_default: bool = True):
    return create_tpu_device(dev_id)


def create_cuda_gpus(num: int):
    return create_tpu_devices(num)


def create_cuda_gpus_on(dev_ids):
    return [create_tpu_device(i) for i in dev_ids]


def get_default_device() -> Device:
    global _default_device
    with _lock:
        if _default_device is None:
            _default_device = CppCPU(-1)
        return _default_device


def set_default_device(dev: Device):
    global _default_device
    _default_device = dev


def enable_tensor_graph(enable: bool = True):
    """Convenience: toggle graph mode on the default device."""
    get_default_device().EnableGraph(enable)


# ---------------------------------------------------------------------------
# Memory-pool API shims (reference: src/core/memory/memory.cc — CnMemPool /
# CudaMemPool device allocators, SURVEY.md §2.1 Memory-pool row: "no-op
# shim (XLA owns HBM); keep API for source compat").  Scripts that
# construct a pool and pass it to device creation keep working; the pool
# only tracks what it was asked for, since allocation itself belongs to
# the XLA client.
# ---------------------------------------------------------------------------


class DeviceMemPool:
    """API-compat allocator shim; XLA's client owns real HBM."""

    def __init__(self, init_size_mb: int = 256, max_size_mb: int = 0):
        self.init_size_mb = int(init_size_mb)
        self.max_size_mb = int(max_size_mb)
        self._outstanding = 0  # bytes "allocated" through the shim API

    def Malloc(self, size: int) -> int:
        self._outstanding += int(size)
        return 0  # opaque handle; nothing real to hand out

    def Free(self, ptr: int, size: int = 0) -> None:
        self._outstanding = max(0, self._outstanding - int(size))

    def GetMemUsage(self):
        """(free, total) in bytes, from the live backend when it reports
        memory stats, else (0, 0) like a CPU pool."""
        try:
            stats = jax.devices()[0].memory_stats() or {}
            total = stats.get("bytes_limit", 0)
            used = stats.get("bytes_in_use", 0)
            return (total - used, total)
        except Exception:
            return (0, 0)


class CnMemPool(DeviceMemPool):
    """Reference cnmem-backed pool name, kept for source compat."""


class CudaMemPool(DeviceMemPool):
    """Reference CUDA pool name, kept for source compat."""


def device_query(dev_id: int = 0, verbose: bool = False):
    devs = jax.devices()
    info = {
        "num_devices": len(devs),
        "platforms": sorted({d.platform for d in devs}),
        "devices": [str(d) for d in devs] if verbose else None,
    }
    return info
