"""A training job: one compiled step with its state, driven from the seed
through its first steps (which the reference follows) and then through
the window -- the same object, the same call, the same feed.

From the program it takes ``Model.compile(use_graph=True)``, the call
``model(ids, labels)``, ``opt.SGD``/``DistOpt`` and the ``graph.cache_miss``
counter.
"""

import time

import numpy as np

from benchmark.harness import loader, profile
from benchmark.harness.output import say

CLOCK = time.perf_counter
CHECK_STEPS = 3


class Session:
    def __init__(self, cell, devices):
        from singa_tpu import amp, device, opt

        self.cell, self.devices = cell, devices
        cfg, job = cell["config"], cell["traffic"]
        self.ref = loader.load_module("references", cfg["family"])
        self.adapter = loader.load_module("adapters", cfg["family"])
        self.sizes = self.ref.sizes_of(cfg)
        self.job = job
        self.batch = job["rows_per_chip"] * len(devices)
        self.seq = job["seq_len"]
        t = CLOCK()
        self.dev = device.create_tpu_device(0)
        o = opt.SGD(lr=job["lr"], momentum=job["momentum"])
        if len(devices) > 1:
            from singa_tpu.parallel.communicator import (Communicator,
                                                         get_mesh)
            from singa_tpu.parallel.dist_opt import DistOpt

            o = DistOpt(o, communicator=Communicator(
                mesh=get_mesh(devices=list(devices))))
        amp.enable(cfg["job"]["amp"] == "bfloat16")
        self.model = self.adapter.build_model(
            cfg, self.dev, train=True, batch_shape=(self.batch, self.seq),
            optimizer=o, attn_impl=cfg["job"]["attn_impl"])
        say(f"setup: model build {CLOCK() - t:.1f} s")

    def _feed(self, seed):
        """A pool of different batches on the device, from the seed:
        (N, B, S+1) token ids; step k takes batch k mod N, inputs
        [:, :-1] and next-token labels [:, 1:].  All rows differ."""
        from singa_tpu import tensor

        n = self.job["batches_in_pool"]
        toks = feed_tokens(self.job, self.batch, self.sizes["V"], seed)
        raw = [(toks[i, :, :-1], toks[i, :, 1:]) for i in range(n)]
        wrapped = [tuple(tensor.from_raw_tensor(a, self.dev) for a in p)
                   for p in raw]
        return raw, wrapped

    def _norms(self, arrays):
        """{state name: norm} of a {state name: array} dict, one jitted
        call."""
        import jax
        import jax.numpy as jnp

        f = jax.jit(lambda d: {k: jnp.sqrt(jnp.sum(
            a.astype(jnp.float32) ** 2)) for k, a in d.items()})
        return f(arrays)

    def run(self, seed, seconds, trace, overrides=None, tamper=None):
        import jax
        from singa_tpu.observe.registry import registry

        m, cell = self.model, self.cell
        t = CLOCK()
        w0 = self.ref.init_weights(self.sizes, seed)
        self.adapter.put_weights(m, w0)
        raw, feed = self._feed(seed)
        say(f"setup: weights + feed {CLOCK() - t:.1f} s")
        step = m if tamper is None else tamper(m)

        # ---- the first steps, through the window's own call and feed
        t = CLOCK()
        names = self.adapter.state_names(m.cfg.n_layer)
        losses, first_grad = [], None
        p0 = {k: jax.numpy.copy(t_.data) for k, t_ in m.get_states().items()}
        for k in range(CHECK_STEPS):
            _, loss = step(*feed[k % len(feed)])
            losses.append(loss.data)
            if k == 0:
                # SGD with momentum from a zero buffer: after one step
                # the buffer IS the gradient the optimizer got
                st = m.persistent_tensors()
                first_grad = self._norms(
                    {n: st[f"__opt__{n}:momentum"].data for n in names})
        delta = self._norms({k: t_.data - p0[k]
                             for k, t_ in m.get_states().items()})
        del p0, w0
        for k in range(CHECK_STEPS, self.job["warm_steps"]):
            _, loss = step(*feed[k % len(feed)])
        jax.block_until_ready(loss.data)
        program = dict(
            losses=[float(x) for x in losses],
            first_grad={k: float(v) for k, v in first_grad.items()},
            delta={k: float(v) for k, v in delta.items()})
        say(f"setup: first {self.job['warm_steps']} steps (compile "
            f"included) {CLOCK() - t:.1f} s; losses {program['losses']}")

        # ---- the window
        miss = registry().counter("graph.cache_miss")
        miss0 = miss.value
        tracer = None
        if trace:
            tracer = profile.Tracer(
                loader.ROOT, cell["cell"]["trace_window"]["length_s"],
                seconds)
        pending, steps, k = [], 0, self.job["warm_steps"]
        with profile.quiet_gc():
            t0 = CLOCK()
            while True:
                now = CLOCK()
                if now >= t0 + seconds:
                    break
                if tracer:
                    tracer.poll(now - t0)
                with profile.span("train.step"):
                    _, loss = step(*feed[k % len(feed)])
                pending.append(loss.data)
                k += 1
                steps += 1
                if len(pending) > 2:      # stay two steps ahead, no more
                    with profile.span("train.wait"):
                        jax.block_until_ready(pending.pop(0))
            jax.block_until_ready(pending)
            t1 = CLOCK()
            if tracer:
                tracer.stop()
        last = float(loss.data)
        say(f"window: {steps} steps in {t1 - t0:.3f} s; last loss {last}")
        out = dict(
            attempted=steps, failed=0 if np.isfinite(last) else steps,
            window_s=t1 - t0, setup_end=t0, samples={},
            counters=dict(
                tokens=steps * self.batch * self.seq, steps=steps,
                compiles_in_window=miss.value - miss0,
                batch=self.batch, seq_len=self.seq, chips=len(self.devices)),
            tracer=tracer, program=program,
            batches=raw[:CHECK_STEPS])
        return out

    def release(self):
        """Free the program's state before the reference runs."""
        from singa_tpu import amp

        amp.enable(False)
        self.model = None

    def check(self, seed, run, precision="f32"):
        """The reference follows the first three steps.  Returns
        {number: value}: the program's numbers against the float32
        reference's, or, with a lower ``precision``, the control's -- the
        reference in that precision put in the program's place."""
        prog = run["program"]
        n_layer = self.sizes["L"]
        got = (prog["losses"],
               self.adapter.leaf_values(prog["first_grad"], n_layer),
               self.adapter.leaf_values(prog["delta"], n_layer))
        if precision != "f32":
            got = _follow(self.ref, self.sizes, self.job, seed,
                          run["batches"], precision)
        want = _follow(self.ref, self.sizes, self.job, seed, run["batches"],
                       "f32")
        say(f"check: reference losses {want[0]}, "
            f"{'program' if precision == 'f32' else precision} {got[0]}")
        return gaps(got, want)


def _follow(ref, sizes, job, seed, batches, precision):
    """(losses, first-gradient norms, parameter-change norms) of the
    reference's first steps, the norms as {tensor: numpy array}."""
    import jax

    losses, g1, delta = ref.sgd_momentum_steps(
        ref.init_weights(sizes, seed), sizes, batches, job["lr"],
        job["momentum"], precision)
    host = lambda t: {k: np.asarray(jax.device_get(v)) for k, v in t.items()}
    return losses, host(g1), host(delta)


def gaps(got, want):
    """The numbers compared.  Norms go by the worst leaf: the gap between
    the two norms of a leaf against the reference's norm of that leaf or
    of the median leaf, whichever is larger (some gradients are all but
    zero)."""
    out = {"loss_gap": max(abs(a - b) for a, b in zip(got[0], want[0]))}
    for key, g, w in (("first_grad_norm_gap", got[1], want[1]),
                      ("param_change_norm_gap", got[2], want[2])):
        floor = float(np.median(np.concatenate(
            [np.atleast_1d(v).ravel() for v in w.values()])))
        out[key] = max(
            float(np.max(np.abs(np.atleast_1d(np.asarray(g[k], np.float64))
                                - np.atleast_1d(w[k]))
                         / np.maximum(np.atleast_1d(w[k]), floor)))
            for k in w)
    return out


def feed_tokens(job, batch, vocab, seed):
    """(N, B, S+1) seeded token ids on the device, in one jitted call."""
    import jax
    import jax.numpy as jnp

    shape = (job["batches_in_pool"], batch, job["seq_len"] + 1)
    return jax.jit(lambda k: jax.random.randint(k, shape, 0, vocab,
                                                jnp.int32))(
        jax.random.PRNGKey(int(seed) % (2 ** 32) ^ 0x5EED))


def control(cell, seed, precision):
    """The control's numbers at the cell's own size without the program:
    the reference in ``precision`` against the float32 reference over the
    batches the seed gives (for ``tools/many.py --control-only``; needs
    one chip whatever the cell's chips)."""
    cfg, job = cell["config"], cell["traffic"]
    ref = loader.load_module("references", cfg["family"])
    sizes = ref.sizes_of(cfg)
    toks = feed_tokens(job, job["rows_per_chip"] * cell["chips"],
                       sizes["V"], seed)
    batches = [(toks[i, :, :-1], toks[i, :, 1:]) for i in range(CHECK_STEPS)]
    return gaps(_follow(ref, sizes, job, seed, batches, precision),
                _follow(ref, sizes, job, seed, batches, "f32"))
