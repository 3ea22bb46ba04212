"""``Model`` API, shaped after the reference's ``python/singa/model.py``
(~400 LoC, unverified — SURVEY.md §2.2): ``compile(inputs, is_train,
use_graph, sequential)``, user-overridden ``train_one_batch``,
``set_optimizer``, ``save_states``/``load_states``, train/eval switches.

Graph mode, TPU-native: the reference's buffering graph scheduler
(``src/core/scheduler/scheduler.cc`` — record Exec lambdas on iteration 1,
topo-sort by block deps, replay thereafter) collapses into ``jax.jit``:

  * before iteration 1, an **abstract warm-up** (``jax.eval_shape`` of one
    step) materializes lazily-created optimizer state at zero cost — the
    reference instead executes its first graph iteration eagerly while
    recording, which on this backend would compile every op separately;
  * iteration 1 traces the user's ``train_one_batch`` into one pure
    function over (persistent state, batch) and compiles it with donated
    state buffers — XLA's scheduler then owns op ordering, fusion, memory
    reuse and latency hiding (the jobs of scheduler.cc + cnmem);
  * later iterations replay the cached executable, keyed by input
    shape/dtype like the reference keys its graph on buffered shapes.

"Persistent state" = model params + layer states (BN running stats) +
optimizer state (momentum, step counter) + the device PRNG key (so dropout
advances deterministically inside the compiled step).
"""

from __future__ import annotations

import os
import tempfile
import time as _time
import zipfile
import io as _io

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import autograd, layer, tensor
from .observe import monitor as _monitor
from .observe import trace as _trace
from .observe.registry import registry as _obs_registry
from .resilience import faults as _faults
from .tensor import Tensor

# Default checkpoint file mode (0o666 & ~umask), probed WITHOUT calling
# os.umask(): mutating the process-global umask — even briefly at
# import — would race any other thread creating files (advisor r04).
# Instead, the kernel applies the umask for us to a throwaway O_CREAT
# file, whose stat we read.  Lazy + cached: the probe touches the
# filesystem once per process, at first save.
_CKPT_MODES = {}


def _ckpt_mode(ckpt_dir):
    """Probe in the CHECKPOINT directory itself: it is known writable
    (the save is about to mkstemp there) and carries the ACL defaults
    the checkpoint will actually get — a tempdir probe would fail on
    read-only /tmp sandboxes and could mismatch.  Cached PER
    DIRECTORY, matching that rationale (a second save into a
    directory with different default ACLs re-probes; a benign
    double-probe between concurrent async saves just writes the same
    value twice)."""
    ckpt_dir = os.path.abspath(ckpt_dir)
    mode = _CKPT_MODES.get(ckpt_dir)
    if mode is None:
        import stat as _stat
        import uuid as _uuid

        p = os.path.join(ckpt_dir, f".singa-tpu-mode-{_uuid.uuid4().hex}")
        fd = os.open(p, os.O_CREAT | os.O_EXCL | os.O_WRONLY, 0o666)
        try:
            mode = _stat.S_IMODE(os.fstat(fd).st_mode)
        finally:
            os.close(fd)
            try:
                os.unlink(p)
            except OSError:
                pass
        _CKPT_MODES[ckpt_dir] = mode
    return mode

# registry of graph runners (for Device.ResetGraph / PrintTimeProfiling)
_graph_runners = []


def _key_digest(key, width=96) -> str:
    """Compact, human-scannable form of a graph-cache key for trace
    args (the full nested tuple can run to kilobytes)."""
    s = str(key)
    return s if len(s) <= width else s[:width - 3] + "..."


def _cost_args(cost) -> dict:
    """Scalar entries of an XLA cost-analysis table (a dict on the
    installed jax, CPU and TPU alike), keyed safely for trace span
    args (spaces -> underscores)."""
    out = {}
    for k in ("flops", "bytes accessed", "transcendentals",
              "optimal_seconds"):
        v = cost.get(k)
        if isinstance(v, (int, float)):
            out[k.replace(" ", "_")] = float(v)
    return out


def _clear_compiled_caches(device=None):
    for r in _graph_runners:
        r.clear()


def _compiled_cost_tables(device=None):
    out = []
    for r in _graph_runners:
        out.extend(r.cost_tables())
    return out


class Model(layer.Layer):
    """Subclass and override ``forward`` and ``train_one_batch`` (reference
    contract; see examples/)."""

    def __init__(self):
        super().__init__()
        self._optimizer = None
        self.graph_mode = False
        self.sequential = False
        self._graph_runner = None
        self.dist = False
        # GSPMD model-parallel plan (parallel/sharding.ShardingPlan):
        # when set, graph mode jits the step over globally-shaped arrays
        # laid out per the plan (tp/sp/pp/ep + dp), letting XLA's SPMD
        # partitioner insert the collectives.  Orthogonal to `dist`
        # (the reference-parity shard_map DistOpt path).
        self.sharding_plan = None
        # distributed output reassembly: "auto" (scalars -> cross-replica
        # mean, others -> merge per-rank batch), "stack" (raw (W, ...)),
        # or a list/tuple of per-output leaf specs from
        # {"mean", "concat", "stack"} matching the flattened structure of
        # train_one_batch's return value — the explicit form for outputs
        # that are neither scalars nor batch-leading (e.g. RNN hidden
        # states shaped (L, B/W, H), which "auto" would merge wrongly)
        self.dist_outputs = "auto"

    # -- reference API -----------------------------------------------------
    def compile(self, inputs, is_train=True, use_graph=False, sequential=False):
        """Initialize params with a dummy forward over ``inputs`` and fix
        the execution mode (reference: model.Model.compile)."""
        assert isinstance(inputs, (list, tuple)), "inputs must be a list"
        self.train(is_train)
        # name the layer tree before the dummy forward so params are
        # created with unique hierarchical names
        self.set_name(self.name)
        # dummy forward creates params eagerly (reference does the same)
        prev = autograd.training
        autograd.set_training(False)
        try:
            self.forward(*inputs)
        finally:
            autograd.set_training(prev)
        self._initialized = True
        # params created during the dummy forward get their final names now
        self.set_name(self.name)
        names = list(self.get_states().keys())
        assert len(names) == len(set(names)), (
            f"duplicate param/state names after compile: {names}")
        self.graph_mode = bool(use_graph)
        self.sequential = bool(sequential)
        if inputs:
            self.device = inputs[0].device
            self.device.EnableGraph(use_graph)
        if self.graph_mode:
            self._graph_runner = _GraphRunner(self)
            _graph_runners.append(self._graph_runner)
        if self._optimizer is not None and self.dist:
            self._optimizer.attach_model(self)

    def forward(self, *input):
        raise NotImplementedError

    def train_one_batch(self, *input, **kwargs):
        raise NotImplementedError

    def __call__(self, *input, **kwargs):
        if not self._initialized:
            # allow un-compiled eager use, like a plain Layer
            self.initialize(*input)
            self._initialized = True
        if autograd.training:
            return self._call_train_one_batch(*input, **kwargs)
        return self.forward(*input, **kwargs)

    def _call_train_one_batch(self, *args, **kwargs):
        if self.graph_mode and self._graph_runner is not None:
            return self._graph_runner.run(args, kwargs)
        return self.train_one_batch(*args, **kwargs)

    def train_n_batches(self, *args, n_steps=None, **kwargs):
        """Run K training steps in ONE host dispatch (round-5 addition;
        the reference dispatches per iteration — SURVEY.md §3.1 hot
        loop).  Two modes:

        * **stacked** (default): every ``Tensor`` argument carries a
          leading steps axis ``K`` (e.g. ``x: (K, B, D)``,
          ``y: (K, B)``) — K different prefetched batches;
        * **repeat** (``n_steps=K``): Tensor arguments are per-step
          shaped and the SAME device-resident batch feeds all K steps
          (useful for benchmarking and tight fitting loops without
          K-stacked input memory).

        Non-Tensor arguments are trace-time constants shared by every
        step.  The compiled program is ``lax.scan`` over the SAME step
        function graph mode traces for ``train_one_batch``, with
        donated state — so one host dispatch buys K optimizer
        updates, which makes small latency-bound models (MLP,
        char-RNN) compute-bound instead of paying one dispatch per
        step.

        Returns ``train_one_batch``'s outputs with a leading K axis on
        every leaf (a scalar loss becomes a ``(K,)`` loss history;
        mind the memory if the model returns logits and K is large).
        Identical math to K single steps: the PRNG key, optimizer step
        counter and schedules advance inside the scan exactly as they
        would across K separate dispatches (tests/test_model.py asserts
        parity)."""
        if not (self.graph_mode and self._graph_runner is not None):
            raise ValueError(
                "train_n_batches requires compile(..., use_graph=True) "
                "— the multi-step scan only exists inside the compiled "
                "graph step")
        if not autograd.training:
            # mirror __call__'s gate: in eval mode the step would trace
            # without taping and still mutate params K times
            raise ValueError(
                "train_n_batches requires training mode (call "
                "model.train() first); the model is in eval mode")
        ts = [a for a in args if isinstance(a, Tensor)] + \
            [v for v in kwargs.values() if isinstance(v, Tensor)]
        if not ts:
            raise ValueError("train_n_batches needs at least one Tensor "
                             "input (the leading dim is the step count)")
        if n_steps is not None:
            if int(n_steps) < 1:
                raise ValueError(f"n_steps must be >= 1, got {n_steps}")
            return self._graph_runner.run(args, kwargs,
                                          n_steps=int(n_steps),
                                          repeat=True)
        for t in ts:
            if len(t.shape) == 0:
                raise ValueError(
                    "a 0-d Tensor argument cannot carry a steps axis; "
                    "pass it as a plain Python scalar (trace-time "
                    "constant) or use repeat mode (n_steps=K)")
        k = ts[0].shape[0]
        for t in ts:
            if t.shape[0] != k:
                raise ValueError(
                    f"all Tensor inputs must share the leading steps "
                    f"dim: got {t.shape[0]} vs {k}")
        if k < 1:
            raise ValueError(f"steps dim must be >= 1, got {k}")
        return self._graph_runner.run(args, kwargs, n_steps=int(k))

    def train(self, mode=True):
        self.training = bool(mode)
        autograd.set_training(mode)

    def eval(self):
        self.train(False)

    def set_sharding_plan(self, plan):
        """Attach a parallel.sharding.ShardingPlan; requires graph mode
        (GSPMD layouts only exist inside the compiled step).  Mutually
        exclusive with DistOpt's shard_map path."""
        if plan is not None and self.dist:
            raise ValueError(
                "sharding_plan and DistOpt are mutually exclusive: DistOpt "
                "runs the reference-parity shard_map data-parallel path; "
                "with a plan, use a plain optimizer — data parallelism "
                "comes from the mesh's 'data' axis")
        self.sharding_plan = plan
        if self._graph_runner is not None:
            # executables traced without the plan (or with another plan)
            # have the wrong layouts baked in
            self._graph_runner.clear()

    def set_optimizer(self, optimizer):
        dist = getattr(optimizer, "is_distributed", False)
        if dist and self.sharding_plan is not None:
            raise ValueError(
                "sharding_plan and DistOpt are mutually exclusive (see "
                "set_sharding_plan); use a plain optimizer with a plan")
        self._optimizer = optimizer
        self.dist = dist
        if self._graph_runner is not None:
            # executables bake the old optimizer's hyperparameters (read
            # at trace time) and its state materialization; a swapped
            # optimizer must recompile — and clearing here (like
            # set_sharding_plan) also defuses CPython id-reuse matching
            # a stale cache entry
            self._graph_runner.clear()

    @property
    def optimizer(self):
        return self._optimizer

    @optimizer.setter
    def optimizer(self, opt):
        self.set_optimizer(opt)

    def set_states(self, states: dict):
        """Layer.set_states plus decode-cache invalidation: the KV-decode
        session cache (models/gpt2_decode.extract_params) holds strong
        refs to the weight buffers it was built from, so after a weight
        swap the SUPERSEDED copy would stay pinned in device memory
        until the next generate call rebuilt the entry (ADVICE round
        5).  Dropping the entry here releases the old buffers
        immediately; the id-keyed signature already guaranteed the
        stale entry could never be *served*, only *retained*."""
        super().set_states(states)
        self.__dict__.pop("_decode_param_cache", None)

    # -- state (params + layer states + optimizer states) ------------------
    def persistent_tensors(self) -> dict:
        """Ordered name->Tensor map of everything that survives across
        steps; the traced state of graph mode."""
        d = dict(sorted(self.get_states().items()))
        if self._optimizer is not None:
            for k, v in sorted(self._optimizer.state_tensors().items()):
                d[f"__opt__{k}"] = v
        return d

    # -- checkpointing (reference: save_states/load_states zip format,
    #    SURVEY.md §3.5/§5.4) ---------------------------------------------
    def save_states(self, fpath, aux_states=None, async_save=False,
                    retry=None):
        """Zip of one .npy per state tensor + optimizer state + aux.

        ``async_save=True`` (beyond reference parity — the TPU-native
        upgrade orbax calls async checkpointing): the state is CAPTURED
        at call time as fresh DEVICE-SIDE copies (``jnp.copy`` — an
        async on-device op, so this returns without waiting), while the
        device→host transfer and zip write run in a background thread.
        The copies are essential, not just an optimization: graph mode
        compiles the step with donated state buffers, so the *original*
        arrays are deleted by the very next training step.  Returns an
        ``AsyncSaveHandle``; call ``.wait()`` before relying on the
        file (exceptions re-raise there; a fire-and-forget failure is
        logged at thread exit and counted in
        ``checkpoint.async_failures``).

        ``retry``: an optional
        :class:`~singa_tpu.resilience.retry.RetryPolicy` — transient
        write I/O retries with backoff (sync and async paths alike),
        counted under ``resilience.retries{site=checkpoint.write}``."""
        def snap(a):
            if not async_save:
                return a
            if isinstance(a, jax.Array) and not a.is_fully_addressable:
                # multi-host sharded state: the collective fetch must
                # happen on THIS thread (SPMD lockstep — a background
                # thread would deadlock the other processes)
                return _host_array(a)
            return jnp.copy(a)  # shield from graph-mode buffer donation

        with _trace.span("snapshot/capture", cat="snapshot",
                         path=str(fpath), async_save=bool(async_save)):
            captured = {k: snap(v.data)
                        for k, v in self.get_states().items()}
            if self._optimizer is not None:
                # state_tensors (not get_states): keep the transfer off
                # this thread; snap() shields the buffers from donation
                for k, v in self._optimizer.state_tensors().items():
                    captured[f"__opt__{k}"] = snap(v.data)
            if aux_states:
                for k, v in aux_states.items():
                    captured[f"__aux__{k}"] = np.asarray(v)

        def _write():
            with _trace.span("snapshot/write", cat="snapshot",
                             path=str(fpath), tensors=len(captured),
                             async_save=bool(async_save)):
                if retry is None:
                    _write_inner()
                else:
                    from .resilience.retry import retry_call

                    retry_call(_write_inner, "checkpoint.write",
                               policy=retry)

        def _write_inner():
            _faults.check("checkpoint.write")
            states = {k: _host_array(v) for k, v in captured.items()}
            # unique temp per call: two overlapping async saves to the
            # same fpath must not interleave writes into one temp file
            fd, tmp = tempfile.mkstemp(
                prefix=os.path.basename(fpath) + ".",
                suffix=".tmp",
                dir=os.path.dirname(os.path.abspath(fpath)) or ".",
            )
            try:
                # mkstemp creates 0600; restore the umask-derived mode so
                # the checkpoint stays as readable as a plain open()
                os.fchmod(fd, _ckpt_mode(
                    os.path.dirname(os.path.abspath(fpath)) or "."))
                with os.fdopen(fd, "wb") as fh:
                    with zipfile.ZipFile(fh, "w", zipfile.ZIP_DEFLATED) as zf:
                        for k, v in states.items():
                            buf = _io.BytesIO()
                            np.save(buf, v)
                            zf.writestr(k + ".npy", buf.getvalue())
                os.replace(tmp, fpath)
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise

        if not async_save:
            _write()
            return None
        return AsyncSaveHandle(_write)

    def load_states(self, fpath):
        _faults.check("checkpoint.read")
        aux = {}
        opt_states = {}
        states = {}
        with zipfile.ZipFile(fpath, "r") as zf:
            for info in zf.namelist():
                k = info[:-len(".npy")]
                arr = np.load(_io.BytesIO(zf.read(info)), allow_pickle=False)
                if k.startswith("__aux__"):
                    aux[k[len("__aux__"):]] = arr
                elif k.startswith("__opt__"):
                    opt_states[k[len("__opt__"):]] = arr
                else:
                    states[k] = arr
        self.set_states(states)
        if self._optimizer is not None and opt_states:
            self._optimizer.set_states(opt_states)
        return aux

    # -- manager-aware checkpointing (single-file save_states/load_states
    #    parity above stays untouched) ------------------------------------
    def checkpoint_manager(self, root, keep=3, retry_policy=None):
        """A :class:`~singa_tpu.resilience.checkpoint.CheckpointManager`
        rooted at ``root``: step-numbered directories, strict-JSON
        manifests with whole-file digests, last-``keep`` retention, and
        corruption fallback on restore (docs/RESILIENCE.md)."""
        from .resilience.checkpoint import CheckpointManager

        return CheckpointManager(root, keep=keep,
                                 retry_policy=retry_policy)

    def save_checkpoint(self, root, step, aux_states=None, keep=3,
                        manager=None):
        """Manager-aware save: one validated, manifested checkpoint
        directory for ``step`` under ``root`` (retention applied).
        Returns the committed directory path."""
        mgr = manager or self.checkpoint_manager(root, keep=keep)
        return mgr.save(self, step, aux_states=aux_states)

    def restore_latest_checkpoint(self, root, manager=None):
        """Manager-aware restore: loads the newest VALID checkpoint
        under ``root``, falling back past corrupt/truncated steps
        (``resilience.checkpoint_fallbacks``).  Returns
        ``(step, aux_states)``."""
        mgr = manager or self.checkpoint_manager(root)
        return mgr.restore_latest(self)


def _host_array(a) -> np.ndarray:
    """Device->host fetch mirroring tensor.to_numpy's multi-host path
    (process_allgather for cross-process sharded arrays)."""
    if isinstance(a, jax.Array) and not a.is_fully_addressable:
        from jax.experimental import multihost_utils as mh

        return np.asarray(mh.process_allgather(a, tiled=True))
    return np.asarray(a)


class AsyncSaveHandle:
    """Background checkpoint write started by
    ``Model.save_states(async_save=True)``.

    A fire-and-forget save that fails must not be SILENT: the thread
    logs the exception at exit and bumps ``checkpoint.async_failures``
    whether or not anyone ever calls ``wait()`` — ``wait()`` still
    re-raises (test-pinned), the telemetry is additive."""

    def __init__(self, fn):
        import threading

        self._exc = None

        def run():
            try:
                fn()
            except BaseException as e:  # re-raised on wait()
                self._exc = e
                _obs_registry().counter(
                    "checkpoint.async_failures",
                    help="async checkpoint writes that failed in the "
                         "background thread").inc()
                from .utils.logging import get_channel

                get_channel("checkpoint").error(
                    "async checkpoint save failed (call wait() to "
                    "re-raise): %r", e)

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()

    def wait(self, timeout=None):
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise TimeoutError("checkpoint write still in progress")
        if self._exc is not None:
            raise self._exc

    def done(self):
        return not self._thread.is_alive()


class _GraphRunner:
    """Compiles/replays ``train_one_batch`` (see module docstring)."""

    def __init__(self, model: Model):
        self.model = model
        self._compiled = {}  # key -> (jit_fn, state_names)
        self._plan_layouts = {}  # key -> (names, state/in/rng shardings)
        self._warm_keys = set()  # step signatures already state-probed
        # observe: compile-cache hit/miss + step counters (process-wide
        # registry; cached here so the hot replay path pays one integer
        # add, not a registry lookup)
        reg = _obs_registry()
        self._m_hit = reg.counter(
            "graph.cache_hit", help="graph-step executable replays")
        self._m_miss = reg.counter(
            "graph.cache_miss", help="graph-step compiles (new signature)")
        self._m_steps = reg.counter(
            "train.steps", help="optimizer steps dispatched via graph mode")

    def clear(self):
        self._compiled.clear()
        self._plan_layouts.clear()
        self._warm_keys.clear()

    def executables(self):
        """The compiled step executables (``jax.stages.Compiled``), in
        compile order — HLO text (``as_text()``) and input shardings
        are what chip_smoke.py and bench_dist.py assert on."""
        return [fn for fn, _names, _cost in self._compiled.values()]

    def cost_tables(self):
        """XLA cost analysis per compiled step (feeds
        Device.PrintTimeProfiling, the rebuild of the reference's per-op
        CUDA-event profiling)."""
        return [(str(key), cost)
                for key, (_fn, _names, cost) in self._compiled.items()
                if cost]

    def _abstract_key(self, args, kwargs):
        def sig(v):
            if isinstance(v, Tensor):
                return ("T", tuple(v.shape), str(np.dtype(v.data.dtype)))
            return ("V", v)

        # Trace-time globals are baked into the executable, so they must
        # be part of the cache key or toggling them after compile would
        # silently replay a stale program (round-2 verdict: amp.enable()
        # after compile kept running the fp32 step).  Covered here: the
        # amp compute dtype, the training flag, and the DistOpt flag.
        # Optimizer and sharding-plan REPLACEMENT is handled by their
        # setters clearing this cache (an id() in the key would be
        # vulnerable to CPython id reuse matching a stale entry);
        # optimizer hyperparameter SCHEDULES flow through the traced
        # step counter, so they do not need to be keyed.
        from . import amp
        m = self.model
        globals_sig = (
            str(amp.compute_dtype()),
            autograd.training,
            m.dist,
        )
        return (
            tuple(sig(a) for a in args),
            tuple(sorted((k, sig(v)) for k, v in kwargs.items())),
            globals_sig,
        )

    def _slice_step0(self, args, kwargs):
        """Per-step view of multi-step (K-leading) inputs: Tensor args
        sliced at step 0 (shape/dtype carriers for the abstract key,
        state probe, and step-builder structure)."""
        dev = self.model.device

        def sl(v):
            if isinstance(v, Tensor):
                return tensor._wrap(v.data[0], dev)
            return v

        return (tuple(sl(a) for a in args),
                {k: sl(v) for k, v in kwargs.items()})

    def run(self, args, kwargs, n_steps=None, repeat=False):
        # train.step: everything the host does for one call — the state
        # probe, placing state and inputs, the compile on a new
        # signature, the dispatch, writing the new state back and the
        # dist_outputs reduction.  The device runs on after it closes
        # (train.dispatch, inside, says when XLA took the work).
        with _trace.phase("train.step", cat="train",
                          steps=n_steps or 1):
            return self._run(args, kwargs, n_steps, repeat)

    def _run(self, args, kwargs, n_steps, repeat):
        model = self.model
        # multi-step: key/probe/build on the per-step slice; the leading
        # K axis lives only in the scan's xs.  repeat mode feeds the
        # same per-step-shaped batch to every scan iteration, so inputs
        # have NO leading steps axis (lead stays 0).
        if n_steps is None or repeat:
            key_args, key_kwargs = args, kwargs
        else:
            key_args, key_kwargs = self._slice_step0(args, kwargs)
        lead = 0 if (n_steps is None or repeat) else 1   # inputs
        out_lead = 0 if n_steps is None else 1           # scan-stacked ys
        key = self._abstract_key(key_args, key_kwargs)
        if n_steps is not None:
            key = key + (("__steps__", n_steps, repeat),)
        if key not in self._warm_keys:
            # Materialize lazily-created optimizer state (momentum buffers,
            # sparse residuals) by abstractly evaluating one step — no
            # compile, no execution; new state starts at zero, which is
            # exactly the optimizers' init.  The reference instead executes
            # its first graph iteration eagerly while recording; on this
            # backend eager dispatch compiles every op separately, so the
            # abstract probe saves minutes on large models.  Keyed per
            # step signature: a later call with a DIFFERENT dist-option
            # kwarg creates NEW optimizer state (e.g. sparse residuals)
            # that must be materialized too, or it would be left holding
            # dead tracers from its first trace.
            self._materialize_state(key_args, key_kwargs)
            self._warm_keys.add(key)
        state = model.persistent_tensors()
        names = list(state.keys())
        tensors = [state[n] for n in names]
        dev = model.device

        in_arrays = [a.data for a in args if isinstance(a, Tensor)]
        in_arrays += [v.data for k, v in sorted(kwargs.items())
                      if isinstance(v, Tensor)]
        if model.sharding_plan is not None and not model.dist:
            # GSPMD path: lay out state + inputs per the plan; XLA's SPMD
            # partitioner inserts every collective (dp grad psum, tp
            # all-reduce pairs, ep all-to-all); only ring attention and
            # the pipeline use explicit shard_map collectives.
            plan = model.sharding_plan
            if plan.input_specs is None:
                # "auto" input layout shards (per-step) dim 0 over data;
                # reject non-divisible batches instead of silently
                # replicating (explicit input_specs is the override for
                # genuinely non-batch-leading inputs)
                dp = plan.axis_size("data")
                for a in in_arrays:
                    if a.ndim - lead >= 1 and a.shape[lead] % dp != 0:
                        raise ValueError(
                            f"input dim {lead} ({a.shape[lead]}) not "
                            f"divisible by data-axis size {dp}; pass "
                            f"ShardingPlan(input_specs=...) for non-batch "
                            f"inputs")

            def in_spec(a, i):
                # per-step spec, prefixed with the (unsharded) steps axis
                # for multi-step stacked inputs
                if lead:
                    per = jax.ShapeDtypeStruct(a.shape[1:], a.dtype)
                    return P(None, *plan.spec_for_input(per, i))
                return plan.spec_for_input(a, i)

            layout = self._plan_layouts.get(key)
            if layout is None or layout[0] != names:
                param_specs = {
                    n: s for n, t in model.get_params().items()
                    if (s := getattr(t, "partition_spec", None)) is not None
                }
                layout = (names, [
                    plan.sharding(plan.spec_for_state(n, t, param_specs))
                    for n, t in zip(names, tensors)
                ], [
                    plan.sharding(in_spec(a, i))
                    for i, a in enumerate(in_arrays)
                ], plan.sharding(P()))
                self._plan_layouts[key] = layout
            _, state_sh, in_sh, rep = layout
            state_arrays = [jax.device_put(t.data, s)
                            for t, s in zip(tensors, state_sh)]
            state_arrays.append(jax.device_put(dev._rng_key, rep))
            in_arrays = [jax.device_put(a, s)
                         for a, s in zip(in_arrays, in_sh)]
        elif model.dist:
            # replicate state over the mesh, shard batch inputs on dim 0
            from jax.sharding import NamedSharding

            comm = model._optimizer.communicator
            mesh, axis = comm.mesh, comm.axis_name
            nproc = jax.process_count()
            if nproc == 1:
                for a in in_arrays:
                    if a.ndim - lead >= 1 \
                            and a.shape[lead] % comm.world_size != 0:
                        raise ValueError(
                            f"global batch dim {a.shape[lead]} not "
                            f"divisible by world size {comm.world_size}")
                rep = NamedSharding(mesh, P())
                ranked = NamedSharding(mesh, P(axis))
                state_arrays = [
                    jax.device_put(t.data,
                                   ranked if "__residual__" in n else rep)
                    for n, t in zip(names, tensors)
                ]
                state_arrays.append(jax.device_put(dev._rng_key, rep))

                def dist_spec(a):
                    # batch axis on the mesh; the steps axis (multi-step)
                    # stays unsharded so the scan slices per step
                    if a.ndim - lead >= 1:
                        return P(*([None] * lead), axis)
                    return P(*([None] * lead)) if lead else P()

                in_arrays = [
                    jax.device_put(a, NamedSharding(mesh, dist_spec(a)))
                    for a in in_arrays
                ]
            else:
                # MULTI-HOST (reference: each MPI rank feeds its own
                # shard): inputs are this process's LOCAL batch; state
                # is broadcast from process 0 (the reference's MPI
                # bcast) into one global replicated array.  After step 1
                # the state is already global (outputs of the global
                # step) and passes through untouched.
                state_arrays, in_arrays = self._globalize_multihost(
                    mesh, axis, names, tensors, in_arrays, dev,
                    check=key not in self._compiled, lead=lead)
        else:
            state_arrays = [jax.device_put(t.data, dev.jax_device)
                            for t in tensors]
            state_arrays.append(jax.device_put(dev._rng_key, dev.jax_device))

        if model.sharding_plan is not None and not model.dist:
            # activate the plan while tracing so constrain() ops pin
            # GSPMD layouts (they are identity outside planned traces)
            from .parallel.sharding import _PlanActive
            trace_ctx = _PlanActive()
        else:
            import contextlib
            trace_ctx = contextlib.nullcontext()
        with trace_ctx:
            fresh_compile = (key not in self._compiled
                             or self._compiled[key][1] != names)
            if fresh_compile:
                self._m_miss.inc()
                _trace.event("graph/cache_miss", cat="train",
                             key=_key_digest(key))
                with _trace.phase("graph.compile", cat="train",
                                  key=_key_digest(key),
                                  steps=n_steps or 1) as sp:
                    fn = self._build(key_args, key_kwargs, names,
                                     n_steps=n_steps, repeat=repeat)
                    # AOT: a Mosaic or HBM failure surfaces here, once,
                    # as itself — never a silent second compile at
                    # dispatch with the cost table (hence MFU) gone
                    fn = fn.lower(state_arrays, in_arrays).compile()
                    cost = fn.cost_analysis()
                    self._compiled[key] = (fn, names, cost)
                    sp.set(**_cost_args(cost))
            else:
                self._m_hit.inc()
            self._m_steps.inc(n_steps or 1)
            if _faults._armed:
                # chaos hook for the train dispatch path; disarmed the
                # replay loop pays this one module-flag read
                _faults.check("train.step")
            fn = self._compiled[key][0]
            # watchdog heartbeat around the dispatch (two clock calls,
            # only while monitoring is on): liveness always; step time
            # only for replays — a compile dispatch is minutes against
            # milliseconds and would poison the EWMA anomaly estimator
            # and the per-process straggler histogram
            _mon = _monitor.active()
            _hb_t0 = _time.perf_counter() if _mon else 0.0
            with _trace.phase("train.dispatch", cat="train"):
                # host-side dispatch time: device execution is async, so
                # this closes when XLA accepts the work, not when the
                # step finishes — the caller's readback sync (loss fetch)
                # carries the device tail
                new_state, out_tree = fn(state_arrays, in_arrays)
            if _mon:
                _monitor.heartbeat(
                    "train", step_time=_time.perf_counter() - _hb_t0,
                    steps=n_steps or 1, fresh_compile=fresh_compile)
        for t, a in zip(tensors, new_state[:-1]):
            t.data = a
            t.creator = None
        dev._rng_key = new_state[-1]
        if model.dist or model.sharding_plan is not None:
            # the step returns the PRNG key replicated over the mesh;
            # re-commit it to the device's own chip so later EAGER rng
            # use (e.g. initializing another model) doesn't propagate
            # multi-device placement.  Multi-host: the global replicated
            # array isn't device_puttable directly — its value is any
            # local shard.
            k = dev._rng_key
            if isinstance(k, jax.Array) and not k.is_fully_addressable:
                k = np.asarray(k.addressable_shards[0].data)
            dev._rng_key = jax.device_put(k, dev.jax_device)
        if model.dist and model.dist_outputs != "stack":
            # Outputs come back stacked per-rank (see _build).  The "auto"
            # reassembly contract handles only UNAMBIGUOUS leaves: a
            # per-rank scalar, now (W,), becomes the cross-replica mean
            # (the global loss); a leaf whose dim 1 equals the per-rank
            # batch merges its first two dims, (W, B/W, ...) -> (B, ...).
            # Anything else (e.g. RNN hidden states shaped (L, B/W, H))
            # RAISES with the fix — silently guessing a merge corrupted
            # such outputs before (round-2 verdict).  Explicit per-leaf
            # specs via model.dist_outputs = ["mean"/"concat"/"stack",
            # ...] (flattened output order), or "stack" for raw (W, ...)
            # per-rank stacks.  Known contract boundary: a NON-batch
            # per-rank vector that coincidentally has per-rank-batch
            # length still merges — only explicit specs can express
            # that; the dist input path itself requires batch-leading
            # dim-0 inputs (divisibility check above), so per_rank
            # derived from input dim 0 is consistent with the sharding.
            W = model._optimizer.communicator.world_size
            global_b = next(
                (a.shape[lead] for a in in_arrays
                 if getattr(a, "ndim", 0) - lead >= 1), None)
            per_rank = global_b // W if global_b else None

            def merge(a):
                # fold the per-rank axis into the batch axis (both sit
                # after the optional leading steps axis of multi-step)
                ol = out_lead
                return a.reshape(a.shape[:ol]
                                 + (a.shape[ol] * a.shape[ol + 1],)
                                 + a.shape[ol + 2:])

            def unstack_auto(a):
                if a.ndim == 1 + out_lead:
                    return (jnp.mean(a, axis=out_lead) if out_lead
                            else jnp.mean(a))
                if per_rank is not None and a.ndim >= 2 + out_lead \
                        and a.shape[out_lead + 1] == per_rank:
                    return merge(a)
                per_leaf = tuple(a.shape[out_lead + 1:])
                raise ValueError(
                    f"cannot auto-reassemble distributed output of "
                    f"per-rank shape {per_leaf}: its leading dim is "
                    f"neither a scalar nor the per-rank batch "
                    f"({per_rank}); set model.dist_outputs to a list of "
                    f"per-leaf specs from {{'mean', 'concat', 'stack'}} "
                    f"(flattened train_one_batch output order), or "
                    f"'stack' for raw (W, ...) stacks")

            if isinstance(model.dist_outputs, (list, tuple)):
                leaves, treedef = jax.tree.flatten(out_tree)
                specs = list(model.dist_outputs)
                if len(specs) != len(leaves):
                    raise ValueError(
                        f"dist_outputs has {len(specs)} specs but "
                        f"train_one_batch returned {len(leaves)} outputs")
                applied = []
                for spec, a in zip(specs, leaves):
                    if spec == "mean":
                        applied.append(jnp.mean(a, axis=out_lead))
                    elif spec == "concat":
                        applied.append(merge(a))
                    elif spec == "stack":
                        applied.append(a)
                    else:
                        raise ValueError(f"unknown dist_outputs spec "
                                         f"{spec!r}")
                out_tree = jax.tree.unflatten(treedef, applied)
            else:
                out_tree = jax.tree.map(unstack_auto, out_tree)
        return jax.tree.map(
            lambda a: tensor._wrap(a, dev),
            out_tree,
        )

    @staticmethod
    def _globalize_multihost(mesh, axis, names, tensors, in_arrays, dev,
                             check, lead=0):
        """Lift process-local arrays to global arrays over the
        multi-host mesh (jax.distributed runtime).

        Replicated state is BROADCAST from process 0 (the reference's
        MPI bcast of initial params / NCCL id): hosts whose local init
        diverged — a checkpoint loaded on one host, host-dependent
        seeds — start consistent instead of silently training on
        per-shard-different 'replicated' values.  Per-rank sharded
        state (DistOpt residuals, global shape (W, ...)): each host
        contributes the row blocks of ITS devices per the mesh's
        device order.  Batch inputs: the local batch becomes this
        host's slice of the global batch dim.

        ``check``: on a new step signature, first verify every host
        shows the same input shapes — a ragged final batch would
        otherwise compile per-host-different programs and deadlock in
        the collectives with no diagnostic."""
        from jax.experimental import multihost_utils as mh

        pid = jax.process_index()

        if check:
            digest = np.zeros(64, np.int64)
            flat = [d for a in in_arrays
                    for d in (a.ndim, *a.shape)][:63]
            digest[0] = len(flat)
            digest[1:1 + len(flat)] = flat
            gathered = mh.process_allgather(digest)  # (nproc, 64)
            if not (gathered == gathered[0]).all():
                raise ValueError(
                    "multi-host input shapes disagree across processes "
                    f"(shape digests: {gathered.tolist()}); every host "
                    "must feed the same LOCAL batch shape each step — "
                    "drop or pad the ragged final batch")

        def is_global(a):
            return (isinstance(a, jax.Array)
                    and len(a.sharding.device_set) == mesh.devices.size)

        # rows of a (W, ...) per-rank array owned by this host, in the
        # mesh's device order (host_local_array_to_global_array stitches
        # shards in that order)
        my_dev_idx = [i for i, d in enumerate(mesh.devices.flat)
                      if d.process_index == pid]
        if my_dev_idx != list(range(my_dev_idx[0], my_dev_idx[-1] + 1)):
            # must hold under `python -O` too: a non-contiguous order
            # would silently stitch residual row blocks wrongly
            raise ValueError(
                "this process's devices are not contiguous in the mesh; "
                "build the data axis in process order")

        state_arrays = []
        for n, t in zip(names, tensors):
            a = t.data
            if is_global(a):
                state_arrays.append(a)
                continue
            host = np.asarray(a)
            if "__residual__" in n:
                per_dev = host.shape[0] // mesh.devices.size
                host = host[my_dev_idx[0] * per_dev:
                            (my_dev_idx[-1] + 1) * per_dev]
                spec = P(axis)
            else:
                host = mh.broadcast_one_to_all(host)
                spec = P()
            state_arrays.append(
                mh.host_local_array_to_global_array(host, mesh, spec))
        key = dev._rng_key
        state_arrays.append(
            key if is_global(key) else
            mh.host_local_array_to_global_array(
                np.asarray(mh.broadcast_one_to_all(np.asarray(key))),
                mesh, P()))
        n_local = jax.local_device_count()
        global_in = []
        for a in in_arrays:
            if is_global(a):
                global_in.append(a)
                continue
            if a.ndim - lead >= 1:
                if a.shape[lead] % n_local != 0:
                    raise ValueError(
                        f"local batch dim {a.shape[lead]} not divisible "
                        f"by local device count {n_local}")
                # lead=1: multi-step stacked input — the steps axis stays
                # replicated; the per-step batch axis shards over ranks
                spec = P(*([None] * lead), axis)
            else:
                spec = P(*([None] * lead)) if lead else P()
            global_in.append(
                mh.host_local_array_to_global_array(np.asarray(a), mesh,
                                                    spec))
        return state_arrays, global_in

    def _materialize_state(self, args, kwargs):
        model = self.model
        dev = model.device
        before = dict(model.persistent_tensors())
        saved = [(t, t.data) for t in before.values()]
        saved_key = dev._rng_key
        tensor_idx = [i for i, a in enumerate(args) if isinstance(a, Tensor)]
        tensor_kw = sorted(k for k, v in kwargs.items()
                           if isinstance(v, Tensor))
        in_arrays = [args[i].data for i in tensor_idx] + \
            [kwargs[k].data for k in tensor_kw]

        def probe(in_arrays):
            call_args = list(args)
            for i, arr in zip(tensor_idx, in_arrays[:len(tensor_idx)]):
                call_args[i] = tensor._wrap(arr, dev)
            call_kwargs = dict(kwargs)
            for k, arr in zip(tensor_kw, in_arrays[len(tensor_idx):]):
                call_kwargs[k] = tensor._wrap(arr, dev)
            model.train_one_batch(*call_args, **call_kwargs)
            return jnp.zeros(())

        try:
            jax.eval_shape(probe, in_arrays)
        finally:
            for t, a in saved:
                t.data = a
                t.creator = None
            dev._rng_key = saved_key
        # tensors created during the probe hold dead abstract tracers;
        # zero-fill them (momenta/residuals/step counters all start at 0)
        for name, t in model.persistent_tensors().items():
            if name not in before:
                aval = getattr(t.data, "aval", t.data)
                t.data = jax.device_put(
                    jnp.zeros(aval.shape, aval.dtype), dev.jax_device)
                t.creator = None

    def _build(self, args, kwargs, names, n_steps=None, repeat=False):
        """Build the jitted step.  ``n_steps``: wrap the step in a
        ``lax.scan`` over K stacked batches (train_n_batches) — one
        executable, one dispatch, K optimizer updates; with ``repeat``
        the same per-step batch feeds every iteration instead of
        scanning stacked xs.  ``args``/``kwargs`` are always PER-STEP
        shaped (the caller slices multi-step inputs), so the step
        closure and the shard_map specs below are identical in all
        modes; only the scan differs."""
        model = self.model
        dev = model.device
        tensor_idx = [i for i, a in enumerate(args) if isinstance(a, Tensor)]
        tensor_kw = sorted(k for k, v in kwargs.items() if isinstance(v, Tensor))

        def step(state_arrays, in_arrays):
            state = model.persistent_tensors()
            tensors = [state[n] for n in names]
            saved = [(t, t.data) for t in tensors]
            saved_key = dev._rng_key
            try:
                for t, a in zip(tensors, state_arrays[:-1]):
                    t.data = a
                    t.creator = None
                dev._rng_key = state_arrays[-1]
                call_args = list(args)
                for i, arr in zip(tensor_idx, in_arrays[:len(tensor_idx)]):
                    call_args[i] = tensor._wrap(arr, dev)
                    call_args[i].requires_grad = False
                call_kwargs = dict(kwargs)
                for k, arr in zip(tensor_kw, in_arrays[len(tensor_idx):]):
                    call_kwargs[k] = tensor._wrap(arr, dev)
                    call_kwargs[k].requires_grad = False
                out = model.train_one_batch(*call_args, **call_kwargs)
                new_state = [t.data for t in tensors] + [dev._rng_key]
                out_tree = jax.tree.map(
                    lambda v: v.data if isinstance(v, Tensor) else v, out,
                    is_leaf=lambda v: isinstance(v, Tensor),
                )
                return new_state, out_tree
            finally:
                for t, a in saved:
                    t.data = a
                    t.creator = None
                dev._rng_key = saved_key

        def finish(step_fn):
            if n_steps is None:
                return jax.jit(step_fn, donate_argnums=(0,))

            if repeat:
                def multi(state_arrays, in_arrays):
                    # same device-resident batch every iteration
                    return jax.lax.scan(
                        lambda st, _: step_fn(st, in_arrays),
                        state_arrays, None, length=n_steps)
            else:
                def multi(state_arrays, stacked_in):
                    # scan slices each stacked input's leading steps
                    # axis; the step's (new_state, out_tree) contract is
                    # exactly scan's (carry, y), so outputs stack to
                    # (K, ...) leaves
                    return jax.lax.scan(step_fn, state_arrays, stacked_in)

            return jax.jit(multi, donate_argnums=(0,))

        if not model.dist:
            return finish(step)

        # DistOpt: run the step per-rank under shard_map — SINGA's SPMD
        # programming model recovered inside a single-controller runtime.
        # Replicated state (params, optimizer moments) uses P(); per-rank
        # accumulators (DistOpt residuals, global shape (W, ...)) are
        # sharded P(axis) so each rank keeps a private slice; layer state
        # that legitimately diverges per rank (BN running stats computed
        # on the local shard) is pmean'd — tiny arrays, and strictly
        # better-defined than the reference's "rank 0's copy wins".
        comm = model._optimizer.communicator
        mesh, axis = comm.mesh, comm.axis_name
        state_specs = [
            P(axis) if "__residual__" in n else P() for n in names
        ] + [P()]  # trailing entry: PRNG base key
        layer_state_names = set(model.get_states()) - set(model.get_params())
        pmean_idx = [i for i, n in enumerate(names)
                     if n in layer_state_names]

        def rank_step(state_arrays, in_arrays):
            # advance the PRNG base once (replicated), give each rank an
            # independent subkey so dropout masks differ across ranks
            base = state_arrays[-1]
            new_base, sub = jax.random.split(base)
            rank_key = jax.random.fold_in(sub, jax.lax.axis_index(axis))
            new_state, out_tree = step(
                list(state_arrays[:-1]) + [rank_key], in_arrays)
            new_state = list(new_state[:-1]) + [new_base]
            for i in pmean_idx:
                new_state[i] = jax.lax.pmean(new_state[i], axis)
            # stack every output with a leading per-rank axis so one
            # out_spec covers arbitrary train_one_batch return trees
            out_stacked = jax.tree.map(lambda a: jnp.expand_dims(a, 0),
                                       out_tree)
            return new_state, out_stacked

        in_tensors = [x for x in args if isinstance(x, Tensor)] \
            + [kwargs[k] for k in tensor_kw]
        in_tensor_specs = [
            P(axis) if t.data.ndim >= 1 else P() for t in in_tensors
        ]
        sharded = jax.shard_map(
            rank_step,
            mesh=mesh,
            in_specs=(state_specs, in_tensor_specs),
            out_specs=(state_specs, P(axis)),
            check_vma=False,
        )
        return finish(sharded)
