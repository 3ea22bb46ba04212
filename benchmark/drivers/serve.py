"""Serving: one engine, stepped by the caller on one thread; requests
released when they are due.  Open loop (the mix's ``arrivals`` has a
``rate_per_s``): independent users, sent on the arrival schedule whether
or not earlier requests have finished, each timed from when it was due.
Closed loop (``arrivals`` has ``clients``) and session turns: sent after
the request they wait for has finished.  Nothing follows a caller's last
request, so a closed-loop window is the cell's only while every caller
still has requests to send: ``chains_at`` counts how far they had got at
the window's end and raises ``RanDry`` where one had ended its chain.

From the program it takes ``model.serve()``, ``submit``, ``step`` and the
``on_token`` callback (where the benchmark stamps its own clock), the
always-on counters ``serve.tokens_out`` (``engine.stats``) and
``serve.prefill.budget_chunks`` (the metrics registry), and
``jit_cache_size()``.
"""

import heapq
import time

import numpy as np

from benchmark.harness import loader, profile, stats, traffic
from benchmark.harness.output import say

CLOCK = time.perf_counter
DRAIN_CAP_S = 20.0


class RanDry(RuntimeError):
    """A caller of a closed loop had no request left before the window
    ended: the window measured fewer callers than the cell states."""


def _mix_file(cell):
    mix = next((w["traffic"] for w in loader.manifest()["workloads"]
                if w["name"] == cell["name"]), None)
    return (f"benchmark/traffic/{mix}.json" if mix
            else "the cell's traffic mix")


def chains_at(cell, reqs, logs, t1):
    """Closed loop: how many of the mix's requests had been sent when the
    window ended at ``t1``, and by the caller that was furthest along.  A
    rate over fewer callers than the cell states is not that cell's rate,
    so a caller whose last request had finished before ``t1`` is an error
    that says which key of which file to lengthen, and the run gives no
    result."""
    chains = {}
    for i, r in enumerate(reqs):
        chains.setdefault(r.client, []).append(i)

    sent = {c: sum(1 for i in ch if logs[i].submitted is not None
                   and logs[i].submitted < t1)
            for c, ch in chains.items()}
    total = sum(sent.values())
    dry = {c: t1 - logs[ch[-1]].token_times[-1]
           for c, ch in chains.items()
           if logs[ch[-1]].finished and logs[ch[-1]].token_times[-1] < t1}
    if dry:
        first = max(dry, key=dry.get)
        raise RanDry(
            f"closed loop ran dry: {len(dry)} of {len(chains)} callers had "
            f"ended their chains before the window did (caller {first} "
            f"finished the last of its {len(chains[first])} requests "
            f"{dry[first]:.1f} s before the window's end; {total} of "
            f"{len(reqs)} requests sent), so the window measured fewer "
            f"callers than the cell states; lengthen `rounds` in "
            f"{_mix_file(cell)}")
    far = max(sent, key=sent.get)
    return (f"closed loop, {total} of {len(reqs)} requests sent; the "
            f"caller furthest along had sent {sent[far]} of its "
            f"{len(chains[far])}")


class Session:
    """Holds the chip: the model is built once; ``run`` makes the seed's
    weights, an engine, the traffic, and measures one window."""

    def __init__(self, cell, devices):
        from singa_tpu import device

        self.cell = cell
        self.devices = devices
        cfg = cell["config"]
        self.ref = loader.load_module("references", cfg["family"])
        self.adapter = loader.load_module("adapters", cfg["family"])
        self.sizes = self.ref.sizes_of(cfg)
        t = CLOCK()
        self.dev = device.create_tpu_device(0)
        self.model = self.adapter.build_model(
            cfg, self.dev, train=False, batch_shape=(1, 16))
        say(f"setup: model build {CLOCK() - t:.1f} s")

    # ------------------------------------------------------------ engine

    def _engine(self, seed):
        import jax.numpy as jnp
        from singa_tpu.serve import PagedConfig

        e = self.cell["config"]["engine"]
        t = CLOCK()
        w = self.ref.init_weights(self.sizes, seed)
        self.adapter.put_weights(self.model, w)
        del w
        eng = self.model.serve(
            paged=PagedConfig(
                block_size=e["block_size"], num_blocks=e["num_blocks"],
                prefill_token_budget=e["prefill_token_budget"]),
            dtype=getattr(jnp, e["dtype"]), max_slots=e["max_slots"])
        say(f"setup: weights + engine {CLOCK() - t:.1f} s")
        return eng

    def _warm(self, eng, max_live):
        """Every shape the traffic can reach, and no other: the chunk
        path (one shape under a token budget) and each halving bucket of
        the decode step up to the one that covers ``max_live`` lanes."""
        from singa_tpu.serve import GenerationRequest

        t = CLOCK()
        e = self.cell["config"]["engine"]
        rng = np.random.default_rng(0)
        # the engine's decode buckets halve from max_slots down to 1; the
        # widest one needed is the smallest that covers max_live lanes
        ladder, b = [e["max_slots"]], e["max_slots"]
        while b > 1:
            b = max(1, b // 2)
            ladder.append(b)
        top = min(w for w in ladder if w >= min(max_live, e["max_slots"]))
        widths = [w for w in ladder if w <= top]
        # a wave of n short requests admits over a few steps and retires
        # together, so the step runs at n's bucket at least once
        per_step = max(1, e["prefill_token_budget"] // e["block_size"])
        for n in widths:
            hs = [eng.submit(GenerationRequest(
                rng.integers(0, self.sizes["V"], e["block_size"] - 1),
                max_new_tokens=n // per_step + 3, temperature=0.0))
                for _ in range(n)]
            while eng.pending:
                eng.step()
            for h in hs:
                h.result()
        say(f"setup: warm-up of decode buckets {widths} "
            f"{CLOCK() - t:.1f} s")

    # --------------------------------------------------------------- run

    def run(self, seed, seconds, trace, overrides=None, tamper=None):
        """One measured window.  ``overrides`` replaces keys of the mix's
        ``arrivals`` (the rate sweep).  ``tamper`` is for the tests: a
        function the engine is handed to before traffic starts."""
        from singa_tpu.observe.registry import registry
        from singa_tpu.serve import GenerationRequest
        from singa_tpu.serve.jitpin import jit_cache_size

        cell, mix = self.cell, dict(self.cell["traffic"])
        if overrides:
            mix["arrivals"] = dict(mix["arrivals"], **overrides)
        arr = mix["arrivals"]
        closed = "clients" in arr
        preroll = float(mix["preroll_s"])
        reqs = traffic.make_requests(
            mix, seed, (preroll, seconds, DRAIN_CAP_S), self.sizes["V"],
            self.sizes["P"])
        traffic.check_fits(reqs, self.sizes["P"])
        eng = self._engine(seed)
        if tamper is not None:
            tamper(eng)
        self._warm(eng, arr["clients"] if closed else 10 ** 9)

        logs = [stats.RequestLog(None, len(r.prompt), r.max_new)
                for r in reqs]
        successors = {}
        for i, r in enumerate(reqs):
            if r.after >= 0:
                successors.setdefault(r.after, []).append(i)
        due = []                                  # heap of (time, index)

        def stamp(i):
            def on_token(_req, _tok):
                now = CLOCK()
                log = logs[i]
                log.token_times.append(now)
                if log.finished:
                    for j in successors.get(i, ()):
                        heapq.heappush(due, (now + reqs[j].think_s, j))
            return on_token

        tracer = None
        if trace:
            tracer = profile.Tracer(
                loader.ROOT, cell["cell"]["trace_window"]["length_s"],
                seconds)
        step_s, live, blocks = [], [], []
        edges = {}

        # get-or-create by name and label: the engine's own counter
        chunks = registry().counter("serve.prefill.budget_chunks",
                                    engine=eng.stats.engine_label)

        def counters():
            return dict(
                chunks=chunks.value,
                tokens_out=eng.stats.tokens_out,
                decode_steps=eng.stats.decode_steps,
                jit=jit_cache_size())

        t_traffic = CLOCK()
        for i, r in enumerate(reqs):
            if r.after < 0:
                heapq.heappush(due, (t_traffic + r.due_s, i))
        t0 = t1 = None
        with profile.quiet_gc():
            while True:
                now = CLOCK()
                if t0 is None and now >= t_traffic + preroll:
                    t0, edges["c0"] = now, counters()
                if t0 is not None and t1 is None and now >= t0 + seconds:
                    t1, edges["c1"] = now, counters()
                    if tracer:
                        tracer.stop()        # stalls the host for seconds
                    t_drain = now = CLOCK()
                if t1 is not None:
                    # every request due in the window is accounted for:
                    # keep serving until each has its first token
                    waiting = any(
                        t0 <= lg.due < t1 and not lg.token_times
                        and not lg.failed for lg in logs
                        if lg.due is not None)
                    if not waiting or now > t_drain + DRAIN_CAP_S:
                        break
                if tracer and t0 is not None and t1 is None:
                    tracer.poll(now - t0)
                while due and due[0][0] <= now:
                    d, i = heapq.heappop(due)
                    logs[i].due, logs[i].submitted = d, now
                    try:
                        logs[i].handle = eng.submit(GenerationRequest(
                            reqs[i].prompt, max_new_tokens=reqs[i].max_new,
                            temperature=0.0, on_token=stamp(i)))
                    except Exception as e:      # refused: counts as failed
                        say(f"request {i} refused: {e!r}")
                        logs[i].failed = True
                if eng.pending:
                    with profile.span("engine.step"):
                        eng.step()
                    if t0 is not None and t1 is None:
                        step_s.append(CLOCK() - now)
                        live.append(eng.live_slots)
                        blocks.append(eng.paged_arena.blocks_used)
                else:
                    with profile.span("idle.no_request"):
                        wait = (due[0][0] - now) if due else 0.001
                        time.sleep(max(0.0, min(wait, 0.001)))
        say(f"setup: pre-roll {preroll:.1f} s; window {t1 - t0:.3f} s; "
            f"drain {CLOCK() - t1:.2f} s")
        if closed:
            try:
                say(f"window: {chains_at(cell, reqs, logs, t1)}")
            except RanDry:
                eng.close(force=True)
                raise

        # ---- the window's numbers (host clock and program counters)
        for lg in logs:
            if lg.handle is not None and lg.handle.done() and not lg.failed:
                try:
                    lg.handle.result()
                except Exception as ex:
                    say(f"a request failed: {ex!r}")
                    lg.failed = True
        s = stats.window_samples([lg for lg in logs if lg.due is not None],
                                 t0, t1)
        c0, c1 = edges["c0"], edges["c1"]
        e = cell["config"]["engine"]
        processed = ((c1["chunks"] - c0["chunks"]) * e["block_size"]
                     + c1["tokens_out"] - c0["tokens_out"])
        failed = s["failed"] + s["unanswered"]
        missing = [float("inf")] * (s["failed"] + s["unanswered"])
        out = dict(
            attempted=s["attempted"], failed=failed,
            window_s=t1 - t0, setup_end=t0,
            samples=dict(
                ttft_ms=[x * 1e3 for x in s["ttft"]] + missing,
                token_gap_ms=[x * 1e3 for x in s["gaps"]],
                generator_late_ms=[x * 1e3 for x in s["late"]],
                engine_step_ms=[x * 1e3 for x in step_s],
                queue_wait_ms=self._queue_waits(logs, t0, t1)),
            counters=dict(
                processed_tokens=processed,
                prompt_tokens=(c1["chunks"] - c0["chunks"])
                * e["block_size"],
                generated_tokens=c1["tokens_out"] - c0["tokens_out"],
                decode_steps=c1["decode_steps"] - c0["decode_steps"],
                compiles_in_window=c1["jit"] - c0["jit"],
                live_slots_mean=float(np.mean(live)) if live else 0.0,
                max_slots=e["max_slots"],
                blocks_used_peak=max(blocks) if blocks else 0,
                num_blocks=e["num_blocks"],
                live_positions_mean=self._live_positions(logs, t0, t1)),
            pool_shape=tuple(eng.paged_arena.pool_k.shape),
            tracer=tracer)
        say(f"window: {s['attempted']} requests due, {len(s['ttft'])} "
            f"answered, {failed} failed; {len(s['gaps'])} token gaps; "
            f"{processed} tokens processed "
            f"({out['counters']['prompt_tokens']} prompt + "
            f"{out['counters']['generated_tokens']} generated)")
        if s["ttft"] and s["gaps"]:
            tt, gp = out["samples"]["ttft_ms"], out["samples"]["token_gap_ms"]
            say(f"window: ttft ms mean/p50/p90/max {np.mean(tt):.1f}/"
                f"{stats.percentile(tt, 50):.1f}/"
                f"{stats.percentile(tt, 90):.1f}/{max(tt):.1f}; token gap "
                f"ms p50/p95/p99 {stats.percentile(gp, 50):.1f}/"
                f"{stats.percentile(gp, 95):.1f}/"
                f"{stats.percentile(gp, 99):.1f}; live lanes mean/max "
                f"{np.mean(live):.1f}/{max(live)}")
        lens = sorted(lg.prompt_len for lg in logs if lg.due is not None)
        say(f"lengths sent: prompts min/median/max {lens[0]}/"
            f"{lens[len(lens) // 2]}/{lens[-1]} over {len(lens)} requests")

        # ---- what the check needs, then free the program's state
        sample = self._sample(logs, reqs, t0, t1, seed)
        eng.close(force=True)
        del eng
        out["finished_sample"] = sample
        return out

    @staticmethod
    def _queue_waits(logs, t0, t1):
        """Due -> admitted, over requests due in the window that finished:
        the generator's lateness plus the engine's own queue time."""
        out = []
        for lg in logs:
            if (lg.due is not None and t0 <= lg.due < t1 and lg.finished
                    and lg.handle is not None and lg.handle.done()
                    and not lg.failed):
                q = lg.handle.result().queue_time
                out.append((lg.submitted - lg.due + q) * 1e3)
        return out

    @staticmethod
    def _live_positions(logs, t0, t1):
        """Mean, over the window's token emissions, of the cached
        positions the emitting request held: what a decode step reads per
        live lane, for the roofline's bytes."""
        tot = n = 0
        for lg in logs:
            for k, t in enumerate(lg.token_times):
                if t0 <= t < t1:
                    tot += lg.prompt_len + k
                    n += 1
        return tot / n if n else 0.0

    def _sample(self, logs, reqs, t0, t1, seed):
        """A seeded sample of the requests the window finished, with the
        longest in it: [(prompt, served tokens, streamed count)]."""
        k = int(self.cell["cell"]["check"]["requests"])
        done = [i for i, lg in enumerate(logs)
                if lg.handle is not None and lg.finished and not lg.failed
                and lg.handle.done() and t0 <= lg.token_times[-1] < t1]
        if not done:
            return []
        longest = max(done, key=lambda i: logs[i].prompt_len
                      + logs[i].max_new)
        rest = [i for i in done if i != longest]
        rng = np.random.default_rng(int(seed) + 1)
        pick = [longest] + list(rng.permutation(rest)[:k - 1])
        out = []
        for i in pick:
            toks = np.asarray(logs[i].handle.result().tokens)
            out.append((reqs[i].prompt, toks, len(logs[i].token_times)))
        return out

    # ------------------------------------------------------------- check

    def check(self, seed, run, precision="f32"):
        """Reference over each sampled prompt with its served tokens.
        Returns {number: value}; the limits are the cell's."""
        w = self.ref.init_weights(self.sizes, seed)
        worst, total, scale, bad, served = 0.0, 0.0, 0.0, 0, 0
        for prompt, toks, streamed in run["finished_sample"]:
            n = len(prompt)
            if (not np.array_equal(toks[:n], prompt)
                    or streamed != len(toks) - n):
                bad += 1
                continue
            g, t, sc = self.ref.served_token_gap(w, self.sizes, toks, n,
                                                 precision)
            worst, total, scale = max(worst, g), total + t, max(scale, sc)
            served += len(toks) - n
        say(f"check: {len(run['finished_sample'])} requests, {served} "
            f"served tokens, logits' scale {scale:.3f}")
        return {"served_logit_gap_mean": total / max(served, 1),
                "served_logit_gap_max": worst, "malformed_results": bad,
                "unchecked": 0 if run["finished_sample"] else 1}
