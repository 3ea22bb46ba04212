"""Serving telemetry: per-request latency, queue/slot gauges, token
throughput.

Since the ``singa_tpu.observe`` round, every number here lives in the
process-wide metrics registry (``observe.registry``) instead of a
private attribute soup: counters/gauges/histograms are registered
under ``serve.*`` with an ``engine=<n>`` label (one label value per
engine instance, so two engines in one process never collide), which
makes the serving surface exportable over Prometheus text alongside
the train-side metrics without any extra glue.  The TTFT/TPOT
histograms still ride :class:`~singa_tpu.utils.metrics.LatencySeries`:
the registry's Histogram owns the series and ``self.ttft``/``self.tpot``
are the same object (one copy of the data, two views).  Engines are
process-lifetime in the registry; call :meth:`unregister` when
retiring one in a long-lived process.

The ``snapshot()`` schema is STABLE — tests/test_serve.py asserts the
exact key set, and bench_serve.py writes it into BENCH_SERVE.json so
future PRs have a comparable perf trajectory — extend it by adding
keys, never by renaming.

Metric definitions (the serving-standard ones):

* **TTFT** (time to first token): submit → the prefill token, queue
  wait included — the user-visible "how long until it starts".
* **TPOT** (time per output token): mean inter-token gap AFTER the
  first token; requests emitting one token have no TPOT sample.
  TOKENS-PER-STEP AWARE since the speculative round: the measure is
  (last token − first token) / (n − 1), which counts every token a
  step emitted, however many that was — for a speculative engine this
  IS step time / accepted tokens, so a replica whose draft stops
  agreeing (acceptance collapses toward 0, steps emit ~1 token) shows
  a proportionally worse TPOT and ``tpot_ewma``, and the fleet Router
  prices it out honestly without any speculation-specific wiring.
* **serve.request.queue_wait_s / serve.request.admission_s{kind=}**:
  the TTFT split — submit→admission (queue wait) and admission→first
  token (prefill, ``kind=cold|warm``), the same per-request numbers
  the request ledger (``observe.requests``) attributes, exported as
  bucketed Prometheus histograms so the split aggregates across a
  fleet.
* **serve.spec.{accepted,drafted}** (speculative engines only):
  draft proposals the target verify kept / offered — the realized
  acceptance rate on live traffic, the number the speculation-vs-
  unroll crossover (gpt2_decode.generate_speculative docstring) turns
  on.
* **slot occupancy**: live slots / max_slots, sampled once per decode
  step — how full the fixed-shape batch actually runs.
* **queue depth**: sampled after each step's scheduling pass.
"""

from __future__ import annotations

import itertools

from ..observe import trace as _trace
from ..observe.registry import registry
from ..utils.logging import get_channel

_engine_ids = itertools.count()


class EngineStats:
    """Accumulated over an engine's lifetime; ``snapshot()`` at any
    point.  All wall-clock numbers come from the engine's clock so a
    fake clock makes the whole schema deterministic in tests.

    ``slo``: an optional :class:`~singa_tpu.observe.health.SLO`.  When
    set, every retire is checked against its targets (per REQUEST —
    exact under any traffic shape, and strictly stronger than the
    percentile line each target guards) and every scheduling pass
    against ``queue_depth_max``; breaches increment
    ``serve.slo_violations{engine=,kind=ttft|tpot|queue}`` and emit
    trace instants (which the monitor's flight recorder captures even
    with tracing off)."""

    def __init__(self, max_slots: int, clock, reg=None, slo=None,
                 spec=False):
        self.max_slots = int(max_slots)
        self._clock = clock
        self._t0 = clock()
        reg = reg if reg is not None else registry()
        self.registry = reg
        self.engine_label = str(next(_engine_ids))
        lbl = dict(engine=self.engine_label)
        self._submitted = reg.counter(
            "serve.submitted",
            help="submit() calls (queue-full rejections included)", **lbl)
        self._completed = reg.counter(
            "serve.completed", help="requests retired normally", **lbl)
        self._rej_deadline = reg.counter(
            "serve.rejected_deadline",
            help="requests dropped past their deadline", **lbl)
        self._rej_queue = reg.counter(
            "serve.rejected_queue_full",
            help="requests rejected by back-pressure", **lbl)
        self._prefills = reg.counter(
            "serve.prefills", help="admission prefills run", **lbl)
        self._prefill_tokens = reg.counter(
            "serve.prefill.tokens",
            help="prompt positions prefilled (real positions: neither "
                 "padding nor positions a prefix cache supplied)", **lbl)
        self._decode_steps = reg.counter(
            "serve.decode_steps", help="pool decode steps run", **lbl)
        self._attn_kernel_steps = reg.counter(
            "serve.decode.attn_kernel_steps",
            help="pool decode steps whose attention over the pool ran "
                 "the Pallas kernel, not the block loop "
                 "(ops/paged_attention.decode_attn_impl)", **lbl)
        self._state_kernel_steps = reg.counter(
            "serve.decode.state_kernel_steps",
            help="pool decode steps whose recurrent state was advanced "
                 "by the Pallas kernel, every lane in one call a layer, "
                 "not the loop a lane at a time "
                 "(ops/mamba2.step_impl)", **lbl)
        self._tokens_out = reg.counter(
            "serve.tokens_out", help="tokens emitted", **lbl)
        self._h_ttft = reg.histogram(
            "serve.ttft", help="submit->first-token seconds", **lbl)
        self._h_tpot = reg.histogram(
            "serve.tpot", help="mean inter-token seconds", **lbl)
        self.ttft = self._h_ttft.series
        self.tpot = self._h_tpot.series
        # request-lifecycle phase histograms (the ledger's queue/
        # prefill decomposition, as aggregable Prometheus series): the
        # fed values are the SAME numbers the request ledger records
        # per timeline, so the histogram percentiles and the ledger's
        # why_slow attribution can never disagree about the population
        self._h_queue_wait = reg.histogram(
            "serve.request.queue_wait_s",
            help="submit->admission seconds (queue-wait phase of "
                 "TTFT)", **lbl)
        self._h_admission = {
            kind: reg.histogram(
                "serve.request.admission_s",
                help="admission->first-token seconds (prefill phase "
                     "of TTFT, cold vs prefix-warm)", kind=kind, **lbl)
            for kind in ("cold", "warm")}
        self._queue_depth = reg.gauge(
            "serve.queue_depth", help="scheduler queue depth", **lbl)
        self._occupancy = reg.gauge(
            "serve.occupancy",
            help="live slots / max_slots, last decode step", **lbl)
        # mean/max accumulators (a gauge only keeps the last sample)
        self._queue_depth_sum = 0
        self._queue_depth_max = 0
        self._queue_samples = 0
        self._occupancy_sum = 0.0
        self._log = get_channel("serve")
        self._registered = [
            self._submitted, self._completed, self._rej_deadline,
            self._rej_queue, self._prefills, self._prefill_tokens,
            self._decode_steps, self._attn_kernel_steps,
            self._state_kernel_steps,
            self._tokens_out, self._queue_depth, self._occupancy,
            self._h_ttft, self._h_tpot, self._h_queue_wait,
            self._h_admission["cold"], self._h_admission["warm"],
        ]
        # set by the engine when a prefix cache is attached: a
        # zero-arg callable returning the cache's snapshot dict
        self.prefix_source = None
        # set by the engine in paged mode: the PagedKVArena's snapshot
        # (blocks free/used, preemption and swap counters)
        self.paged_source = None
        # set by the engine in tensor-parallel mode: the TPExecutor's
        # snapshot (shard count, per-shard KV bytes, dispatch counts)
        self.tp_source = None
        # set by the engine in expert-parallel mode (serve/ep.py): the
        # EPExecutor's snapshot (expert shard count, per-expert routed
        # token load, dropped-token count, load imbalance)
        self.ep_source = None
        # set by the engine in pipeline-parallel mode (serve/pp.py):
        # the PPExecutor's snapshot (stage count, microbatches,
        # per-stage KV bytes, dispatch counts)
        self.pp_source = None
        # speculative engines only: acceptance accounting (``spec`` is
        # set by the engine when a draft model is attached; a plain
        # engine registers nothing and snapshots spec: None)
        self.spec = bool(spec)
        self._spec_accepted = self._spec_drafted = None
        self._spec_chunks = None
        if spec:
            self._spec_accepted = reg.counter(
                "serve.spec.accepted",
                help="draft proposals the target verify kept", **lbl)
            self._spec_drafted = reg.counter(
                "serve.spec.drafted",
                help="draft proposals offered to the target verify",
                **lbl)
            self._spec_chunks = reg.counter(
                "serve.spec.chunks",
                help="per-slot verify chunks run (one per live slot "
                     "per spec step)", **lbl)
            self._registered += [self._spec_accepted,
                                 self._spec_drafted,
                                 self._spec_chunks]
        # recency-weighted TPOT (None until the first multi-token
        # retire): the fleet router's SLO-headroom signal — a replica
        # whose decode is degrading shows it here long before the
        # lifetime-mean tpot histogram moves
        self.tpot_ewma = None
        self._tpot_alpha = 0.25
        self.slo = slo
        self._slo_viol = {}
        if slo is not None:
            for kind in ("ttft", "tpot", "queue"):
                c = reg.counter(
                    "serve.slo_violations",
                    help="requests/steps beyond the declared SLO "
                         "target", kind=kind, **lbl)
                self._slo_viol[kind] = c
                self._registered.append(c)

    def unregister(self):
        """Remove this engine's metrics from the registry.  Call when
        retiring an engine in a long-lived process (per-tenant engines,
        reload loops): the registry is process-lifetime, so without
        this each discarded engine pins its serve.* set — including
        the unbounded TTFT/TPOT value lists — forever.  The stats
        object itself keeps working (snapshot() reads the same
        objects); they just stop being exported."""
        self.registry.remove(*self._registered)

    # registry-backed counts, readable as plain attributes
    @property
    def submitted(self):
        return self._submitted.value

    @property
    def completed(self):
        return self._completed.value

    @property
    def rejected_deadline(self):
        return self._rej_deadline.value

    @property
    def rejected_queue_full(self):
        return self._rej_queue.value

    @property
    def prefills(self):
        return self._prefills.value

    @property
    def prefill_tokens(self):
        return self._prefill_tokens.value

    @property
    def decode_steps(self):
        return self._decode_steps.value

    @property
    def attn_kernel_steps(self):
        return self._attn_kernel_steps.value

    @property
    def state_kernel_steps(self):
        return self._state_kernel_steps.value

    @property
    def tokens_out(self):
        return self._tokens_out.value

    # -- recording hooks (called by the engine) -------------------------
    def on_submit(self):
        self._submitted.inc()

    def on_queue_full(self, request_id):
        self._rej_queue.inc()
        self._log.warning("queue full: rejected %s", request_id)

    def on_deadline_expired(self, request_id):
        self._rej_deadline.inc()
        self._log.warning("deadline expired: rejected %s", request_id)

    def on_prefill(self):
        self._prefills.inc()

    def on_prefill_tokens(self, n: int):
        """``n`` prompt positions went through a prefill dispatch: a
        whole admission's, or one chunk's under a token budget."""
        self._prefill_tokens.inc(n)

    def on_admission(self, queue_wait_s, admission_s, warm=False):
        """One admission's latency split: ``queue_wait_s`` (submit ->
        the scheduling pass that admitted it) and ``admission_s``
        (admission -> first token, the prefill cost — labeled
        ``kind=warm`` when a prefix-cache hit skipped most of it)."""
        self._h_queue_wait.observe(queue_wait_s)
        self._h_admission["warm" if warm else "cold"].observe(
            admission_s)

    def on_token(self):
        self._tokens_out.inc()

    def on_spec(self, accepted: int, drafted: int):
        """One live slot's verify outcome: ``accepted`` of ``drafted``
        proposals kept (the +1 bonus/correction token is counted by
        ``on_token``, not here — acceptance measures the DRAFT)."""
        self._spec_accepted.inc(int(accepted))
        self._spec_drafted.inc(int(drafted))
        self._spec_chunks.inc()

    def on_decode_step(self, live_slots: int, attn_kernel=False,
                       state_kernel=False):
        self._decode_steps.inc()
        if attn_kernel:
            self._attn_kernel_steps.inc()
        if state_kernel:
            self._state_kernel_steps.inc()
        occ = live_slots / self.max_slots
        self._occupancy_sum += occ
        self._occupancy.set(occ)

    def on_schedule(self, queue_depth: int):
        self._queue_samples += 1
        self._queue_depth_sum += queue_depth
        self._queue_depth_max = max(self._queue_depth_max, queue_depth)
        self._queue_depth.set(queue_depth)
        slo = self.slo
        if (slo is not None and slo.queue_depth_max is not None
                and queue_depth > slo.queue_depth_max):
            self._slo_viol["queue"].inc()
            _trace.event("serve/queue_pressure", cat="serve",
                         depth=queue_depth,
                         limit=slo.queue_depth_max)

    def on_complete(self, result):
        self._completed.inc()
        self.ttft.record(result.ttft)
        if result.tpot is not None:
            self.tpot.record(result.tpot)
            a = self._tpot_alpha
            self.tpot_ewma = (result.tpot if self.tpot_ewma is None
                              else (1 - a) * self.tpot_ewma
                              + a * result.tpot)
        slo = self.slo
        if slo is None:
            return
        if slo.ttft_p99_s is not None and result.ttft > slo.ttft_p99_s:
            self._slo_viol["ttft"].inc()
            _trace.event("serve/slo_violation", cat="serve",
                         kind="ttft", request=result.request_id,
                         value=result.ttft, target=slo.ttft_p99_s)
        if (slo.tpot_p50_s is not None and result.tpot is not None
                and result.tpot > slo.tpot_p50_s):
            self._slo_viol["tpot"].inc()
            _trace.event("serve/slo_violation", cat="serve",
                         kind="tpot", request=result.request_id,
                         value=result.tpot, target=slo.tpot_p50_s)

    @property
    def uptime_s(self) -> float:
        """Engine-clock seconds since construction (the submit clock —
        serve health reports never recompute wall from trace events)."""
        return max(self._clock() - self._t0, 1e-9)

    @property
    def goodput_tokens_per_s(self) -> float:
        """Useful emitted tokens per wall second over the engine's
        lifetime.  ``tokens_out`` counts only tokens requests asked
        for (the engine never generates straggler padding), so this IS
        goodput, not raw device throughput."""
        return self.tokens_out / self.uptime_s

    # -- reporting ------------------------------------------------------
    def snapshot(self) -> dict:
        wall = self.uptime_s
        return {
            "requests": {
                "submitted": self.submitted,
                "completed": self.completed,
                "rejected_deadline": self.rejected_deadline,
                "rejected_queue_full": self.rejected_queue_full,
            },
            "throughput": {
                "tokens_out": self.tokens_out,
                "wall_s": wall,
                "uptime_s": wall,
                "tokens_per_s": self.tokens_out / wall,
                # same wall read as tokens_per_s — re-reading the
                # clock via the property would make the identical-by-
                # definition pair disagree by clock jitter
                "goodput_tokens_per_s": self.tokens_out / wall,
                "prefills": self.prefills,
                "prefill_tokens": self.prefill_tokens,
                "decode_steps": self.decode_steps,
            },
            "latency": {
                "ttft": self.ttft.summary(),
                "tpot": self.tpot.summary(),
                # schema extension (add-only): the router's headroom
                # signal, exposed so fleet snapshots explain routing
                "tpot_ewma_s": self.tpot_ewma,
            },
            "queue": {
                "mean_depth": (self._queue_depth_sum
                               / self._queue_samples
                               if self._queue_samples else 0.0),
                "max_depth": self._queue_depth_max,
            },
            "slots": {
                "max_slots": self.max_slots,
                "occupancy_mean": (self._occupancy_sum
                                   / self.decode_steps
                                   if self.decode_steps else 0.0),
            },
            "slo": (None if self.slo is None else {
                "targets": self.slo.asdict(),
                "violations": {k: c.value
                               for k, c in self._slo_viol.items()},
            }),
            "prefix": (self.prefix_source()
                       if self.prefix_source is not None else None),
            # add-only schema extension (paged round): None for
            # slot-arena engines; block accounting + preemption/swap
            # counters for paged ones
            "paged": (self.paged_source()
                      if self.paged_source is not None else None),
            # add-only schema extension (TP-serve round): None for
            # single-device engines; shard/mesh/dispatch accounting
            # for tensor-parallel ones (serve/tp.py)
            "tp": (self.tp_source()
                   if self.tp_source is not None else None),
            # add-only schema extensions (EP/PP-serve round): None
            # unless the engine runs the expert-parallel or
            # pipeline-parallel executor (serve/ep.py, serve/pp.py)
            "ep": (self.ep_source()
                   if self.ep_source is not None else None),
            "pp": (self.pp_source()
                   if self.pp_source is not None else None),
            # add-only schema extension (speculative round): None for
            # plain engines.  tokens_per_chunk = accepted proposals +
            # the chunk's bonus/correction token, per verify chunk —
            # the accepted-tokens/step number (slight overcount for
            # chunks the budget truncated mid-emit; acceptance itself
            # is exact)
            "spec": (None if not self.spec else {
                "drafted": self._spec_drafted.value,
                "accepted": self._spec_accepted.value,
                "chunks": self._spec_chunks.value,
                "acceptance_rate": (
                    self._spec_accepted.value / self._spec_drafted.value
                    if self._spec_drafted.value else None),
                "tokens_per_chunk": (
                    (self._spec_accepted.value + self._spec_chunks.value)
                    / self._spec_chunks.value
                    if self._spec_chunks.value else None),
            }),
        }
