"""GPT-2 — decoder-only causal LM.

Reference parity note: upstream SINGA ships GPT-2 only as an
ONNX-imported example (examples/onnx/gpt2.py, unverified — SURVEY.md
§2.4 lists the ONNX model zoo); like models/bert.py, this is the
TPU-native first-class implementation, and examples/onnx/gpt2.py
round-trips it through sonnx.

TPU-first design:
  * the whole decoder is one jitted graph-mode step (fused causal
    attention on the MXU);
  * fully parallel-aware: pass a ``ShardingPlan`` and the blocks become
    Megatron tensor-parallel (+ ring-attention sequence-parallel) via
    parallel/tensor_parallel.py; ``moe_every`` turns every Nth MLP into
    an expert-parallel GShard MoE (parallel/moe.py) — a GPT-MoE;
  * ``tie_weights=True`` (GPT-2 convention) reuses the token embedding
    as the LM head through a taped transpose-matmul.
"""

import numpy as np
import jax.numpy as jnp

from .. import autograd, layer, model, tensor
from ..tensor import Tensor


class GPT2Config:
    def __init__(self, vocab_size=50257, n_positions=1024, n_embd=768,
                 n_layer=12, n_head=12, n_inner=None, dropout=0.1,
                 layer_norm_eps=1e-5, tie_weights=True, moe_every=None,
                 moe_experts=8, moe_top_k=2, moe_aux_weight=0.01,
                 moe_capacity_factor=1.25, moe_groups=None, remat=False,
                 attn_impl="auto", n_kv_head=None, attn_window=None):
        self.vocab_size = vocab_size
        self.n_positions = n_positions
        self.n_embd = n_embd
        self.n_layer = n_layer
        self.n_head = n_head
        # grouped-query attention: n_kv_head < n_head shares each K/V
        # head across a group of n_head // n_kv_head query heads
        # (n_head/n_kv_head× smaller KV cache at decode)
        self.n_kv_head = int(n_kv_head or n_head)
        if n_head % self.n_kv_head != 0:
            raise ValueError(f"n_head {n_head} not divisible by "
                             f"n_kv_head {self.n_kv_head}")
        # sliding-window (Mistral-style) causal attention: each query
        # sees the previous attn_window positions only; the KV-cached
        # decoder keeps an O(attn_window) rolling cache
        self.attn_window = None if attn_window is None else int(attn_window)
        if self.attn_window is not None and self.attn_window < 1:
            raise ValueError(f"attn_window must be >= 1, "
                             f"got {attn_window}")
        self.n_inner = n_inner or 4 * n_embd
        self.dropout = dropout
        self.layer_norm_eps = layer_norm_eps
        self.tie_weights = tie_weights
        # MoE: every Nth block's MLP becomes a MoEFFN (None = dense)
        self.moe_every = moe_every
        self.moe_experts = moe_experts
        self.moe_top_k = moe_top_k
        self.moe_aux_weight = moe_aux_weight
        self.moe_capacity_factor = moe_capacity_factor
        # routing-group override (default: plan's data-axis size); lets
        # a serial model reproduce a sharded run's grouped routing
        self.moe_groups = moe_groups
        # remat: recompute attention internals in backward
        # (jax.checkpoint) — memory for FLOPs on long sequences
        self.remat = remat
        # attn_impl: "fused" (S x S scores in HBM) or "flash" (Pallas
        # online-softmax fwd+bwd kernels, O(S·D) HBM).  "auto" picks by
        # the measured crossover, re-swept in round 4 (real v5e, GPT-2
        # small, 8192 tokens/step): flash TIES fused at S in {256, 512}
        # (104.4 vs 103.4 / 108.0 vs 108.0 k tok/s) and WINS 31% at
        # S=1024 (100.2 vs 76.5) — the threshold moved down from
        # round 3's 2048.  Flash stays the only impl surviving
        # S >= 16384 on one chip (LONGCTX.json); fused keeps short S.
        if attn_impl == "auto":
            attn_impl = "flash" if n_positions >= 1024 else "fused"
        self.attn_impl = attn_impl

    @classmethod
    def small(cls, **kw):
        """GPT-2 small (124M)."""
        return cls(**kw)

    @classmethod
    def medium(cls, **kw):
        kw.setdefault("n_embd", 1024)
        kw.setdefault("n_layer", 24)
        kw.setdefault("n_head", 16)
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw):
        """For tests: 2 layers, 64 hidden."""
        kw.setdefault("vocab_size", 256)
        kw.setdefault("n_positions", 128)
        kw.setdefault("n_embd", 64)
        kw.setdefault("n_layer", 2)
        kw.setdefault("n_head", 4)
        kw.setdefault("n_inner", 128)
        return cls(**kw)


class GPT2Model(model.Model):
    """Decoder trunk: wte + wpe -> pre-LN causal blocks -> final LN."""

    def __init__(self, cfg=None, plan=None):
        super().__init__()
        from ..parallel.tensor_parallel import (
            ParallelTransformerBlock, VocabParallelEmbedding)

        self.cfg = cfg or GPT2Config.small()
        self.plan = plan
        c = self.cfg
        self.wte = VocabParallelEmbedding(c.vocab_size, c.n_embd, plan)
        self.wpe = layer.Embedding(c.n_positions, c.n_embd, std=0.01)
        self.blocks = []
        for i in range(c.n_layer):
            moe = (c.moe_every is not None
                   and (i + 1) % c.moe_every == 0)
            self.blocks.append(ParallelTransformerBlock(
                c.n_head, c.n_inner, plan, dropout=c.dropout, causal=True,
                eps=c.layer_norm_eps, num_kv_heads=c.n_kv_head,
                window=c.attn_window,
                moe_experts=c.moe_experts if moe else None,
                moe_top_k=c.moe_top_k,
                moe_capacity_factor=c.moe_capacity_factor,
                moe_groups=c.moe_groups,
                remat=c.remat, use_flash=c.attn_impl == "flash"))
        self.ln_f = layer.LayerNorm(c.layer_norm_eps)

    def forward(self, input_ids):
        b, s = input_ids.shape
        pos = tensor.from_numpy(
            np.broadcast_to(np.arange(s, dtype=np.int32), (b, s)).copy(),
            input_ids.device)
        x = autograd.add(self.wte(input_ids), self.wpe(pos))
        if self.cfg.dropout > 0:
            x = autograd.dropout(x, self.cfg.dropout)
        for blk in self.blocks:
            x = blk(x)
        return self.ln_f(x)

    def aux_losses(self):
        """Taped MoE load-balance losses from the last forward."""
        return [blk.aux_loss for blk in self.blocks
                if blk.aux_loss is not None]


class GPT2LMHead(model.Model):
    """Causal-LM head; the training workload (next-token prediction)."""

    def __init__(self, cfg=None, plan=None):
        super().__init__()
        self.cfg = cfg or GPT2Config.small()
        self.plan = plan
        self.transformer = GPT2Model(self.cfg, plan)
        if not self.cfg.tie_weights:
            from ..parallel.tensor_parallel import ColumnParallelLinear

            self.lm_head = ColumnParallelLinear(
                self.cfg.vocab_size, plan, bias=False, gather_output=True)
        self.loss_fn = layer.SoftMaxCrossEntropy()

    def forward(self, input_ids):
        h = self.transformer.forward(input_ids)
        if self.cfg.tie_weights:
            # logits = h @ wte^T (GPT-2 weight tying); with a plan the
            # vocab-sharded table makes this a column-parallel matmul
            wt = autograd.transpose(self.transformer.wte.W, (1, 0))
            logits = autograd.matmul(h, wt)
        else:
            logits = self.lm_head(h)
        return logits

    def train_one_batch(self, input_ids, labels):
        """labels: next-token ids, same shape as input_ids (callers pass
        ids shifted by one; positions to ignore use label -1 — their
        loss AND gradient are zero, and the mean is taken over valid
        (label >= 0) positions only, standard ignore_index semantics)."""
        logits = self.forward(input_ids)
        b, s, v = logits.shape
        loss = self.loss_fn(
            autograd.reshape(logits, (b * s, v)),
            autograd.reshape(labels, (b * s,)))
        # _SoftMaxCrossEntropy zeroes ignored rows (one_hot(-1) is all
        # zeros) but divides by ALL rows; rescale so the mean is over
        # valid positions, else reported loss (and effective lr) shrinks
        # with the ignore fraction
        scale = autograd._op(
            lambda lab: (b * s) / jnp.maximum(jnp.sum(
                (lab.reshape(-1) >= 0).astype(jnp.float32)), 1.0),
            labels, _name="IgnoreIndexScale")
        loss = autograd.mul(loss, scale)
        for aux in self.transformer.aux_losses():
            loss = autograd.add(
                loss, autograd.mul_scalar(aux, self.cfg.moe_aux_weight))
        self.optimizer(loss)
        return logits, loss

    # -- sampling (fixed-shape, jit-friendly: full-context forward per
    #    emitted token, like examples/rnn's fixed-shape sampling) --------
    def generate(self, prompt_ids, max_new_tokens=20, temperature=1.0,
                 rng=None, use_cache=None, top_k=0, top_p=None,
                 min_p=None, repetition_penalty=None):
        """Greedy/temperature sampling with optional top-k / top-p
        (nucleus) filtering. prompt_ids: np.ndarray (S0,).

        ``prompt_ids``: one 1-D prompt (returns a 1-D array), or —
        round 5, KV-cached path only — a list/2-D batch of prompts,
        possibly ragged (returns a list of 1-D arrays; rows decode
        lockstep in one executable via models/gpt2_decode.generate).

        ``use_cache`` (default auto): dense single-device models whose
        generation fits n_positions decode through the KV-cached
        incremental path (models/gpt2_decode.py — one compiled
        prefill + lax.scan, O(S·D) per token) instead of one
        full-context forward per token; plan-sharded models decode
        there too (SPMD over the mesh, round 4), and MoE models since
        round 5 (capacity-free expert routing — token-equal to the
        windowed path when its capacity drops nothing); over-length
        generations use the windowed path below."""
        from . import gpt2_decode as _gd

        # shared classification with gpt2_decode (KV-cached path only)
        if _gd._is_batch(prompt_ids):
            if use_cache is False:
                raise ValueError(
                    "batched generate requires the KV-cached path "
                    "(use_cache=False is single-prompt only); loop "
                    "over rows for the windowed sampler")
            rows = [np.asarray(r, np.int32).reshape(-1)
                    for r in list(prompt_ids)]
            over = any(len(r) + max_new_tokens > self.cfg.n_positions
                       for r in rows)
            if over and use_cache is not True:
                # a batch that exceeds n_positions cannot ride the KV
                # cache; loop EVERY row through the windowed fallback
                # (all rows on one path — mixing cached and windowed
                # rows would sample from different RNG streams), the
                # exact loop the old error message told the caller to
                # write (round-6 fix; use_cache=True keeps the
                # explicit-request ValueError below)
                return [self.generate(
                    r, max_new_tokens=max_new_tokens,
                    temperature=temperature, rng=rng, use_cache=False,
                    top_k=top_k, top_p=top_p, min_p=min_p,
                    repetition_penalty=repetition_penalty)
                    for r in rows]
            was_training = getattr(self, "training", False)
            self.eval()
            try:
                return _gd.generate(
                    self, prompt_ids, max_new_tokens=max_new_tokens,
                    temperature=temperature, rng=rng, top_k=top_k,
                    top_p=top_p, min_p=min_p,
                    repetition_penalty=repetition_penalty)
            finally:
                if was_training:
                    self.train(True)
        n0 = len(np.asarray(prompt_ids).reshape(-1))
        blocks = self.transformer.blocks
        initialized = bool(blocks) and blocks[0].mlp is not None
        if use_cache is None:
            # plan-sharded dense models decode through the KV cache too
            # since round 4 (extract_params lays weights out per the
            # plan; the pure-jnp generation jits SPMD over the mesh)
            use_cache = (initialized  # deferred init needs a forward
                         and n0 + max_new_tokens <= self.cfg.n_positions)
        # .training only exists after train()/eval(); an un-compiled
        # model can still generate (the windowed path lazily inits)
        # validate sampling params up front so BOTH paths (KV-cached and
        # windowed) fail the same way — the windowed math would otherwise
        # NaN on top_p=0 instead of raising
        if top_k and top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {top_k}")
        # clamp like HF: top_k > vocab means no filter (the windowed
        # np.sort path would IndexError otherwise — advisor r04)
        top_k = min(int(top_k or 0), self.cfg.vocab_size)
        if top_p is not None and not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if min_p is not None and not 0.0 < min_p <= 1.0:
            raise ValueError(f"min_p must be in (0, 1], got {min_p}")
        if repetition_penalty is not None and repetition_penalty <= 0.0:
            raise ValueError(f"repetition_penalty must be > 0, "
                             f"got {repetition_penalty}")
        was_training = getattr(self, "training", False)
        self.eval()
        try:
            if use_cache:
                from . import gpt2_decode

                return gpt2_decode.generate(
                    self, prompt_ids, max_new_tokens=max_new_tokens,
                    temperature=temperature, rng=rng, top_k=top_k,
                    top_p=top_p, min_p=min_p,
                    repetition_penalty=repetition_penalty)
            ids = list(np.asarray(prompt_ids).tolist())
            ctx = self.cfg.n_positions
            wte = self.transformer.wte
            if hasattr(wte, "W"):
                dev = wte.W.device  # follow the params
            else:  # un-compiled model: first forward will deferred-init
                from .. import device as device_module

                dev = device_module.get_default_device()
            for _ in range(max_new_tokens):
                live = ids[-ctx:]
                # causal attention ignores positions to the RIGHT, so a
                # fixed-size right-padded window keeps the forward shape
                # static (one compile for the whole generation) and the
                # logits at index len(live)-1 are exact
                window = np.zeros((1, ctx), np.int32)
                window[0, :len(live)] = live
                x = tensor.from_numpy(window, dev)
                logits = self.forward(x)
                last = tensor.to_numpy(logits)[0, len(live) - 1]
                last = last.astype(np.float64)
                if repetition_penalty is not None \
                        and repetition_penalty != 1.0:
                    # CTRL/HF semantics: seen tokens (the WHOLE
                    # sequence so far, prompt included) are divided
                    # when positive, multiplied when negative —
                    # applied before greedy argmax too
                    seen = np.unique(np.asarray(ids, np.int64))
                    pen = np.where(last[seen] > 0,
                                   last[seen] / repetition_penalty,
                                   last[seen] * repetition_penalty)
                    last[seen] = pen
                if temperature <= 0:
                    nxt = int(np.argmax(last))
                else:
                    logit = last / temperature
                    if top_k:
                        kth = np.sort(logit)[-int(top_k)]
                        logit = np.where(logit < kth, -np.inf, logit)
                    if top_p is not None:
                        order = np.argsort(-logit)
                        sp = np.exp(logit[order] - logit[order][0])
                        sp /= sp.sum()
                        cum = np.cumsum(sp)
                        keep = np.zeros(len(logit), bool)
                        keep[order] = (cum - sp) < top_p
                        logit = np.where(keep, logit, -np.inf)
                    if min_p is not None:
                        # keep p >= min_p·p_max
                        logit = np.where(
                            logit < logit.max() + np.log(min_p),
                            -np.inf, logit)
                    p = np.exp(logit - logit.max())
                    p /= p.sum()
                    r = rng or np.random
                    nxt = int(r.choice(len(p), p=p))
                ids.append(nxt)
            return np.asarray(ids, np.int32)
        finally:
            if was_training:
                self.train(True)


    # -- serving (round 6): iteration-level continuous batching --------
    def served_family(self):
        """What the serve engine calls this model's math through
        (models/served.py)."""
        from .gpt2_decode import FAMILY

        return FAMILY

    def serve(self, **kw):
        """An in-process continuous-batching inference engine over this
        model's KV-cached decoder (singa_tpu.serve.InferenceEngine):
        asynchronous request admission, a fixed-shape slot pool (no
        recompiles), per-step retirement and backfill.  Keyword args
        pass through to the engine (``max_slots``, ``max_len``,
        ``dtype``, ``top_k``, ``top_p``, ``scheduler``, ``clock``,
        ``slo`` — declarative latency targets, see
        ``singa_tpu.observe.SLO`` — ``prefix_cache`` — a
        ``serve.PrefixCacheConfig`` enabling block-granular radix
        prefix caching + pinned multi-turn sessions — and the
        fast-decode knobs: ``draft_model=`` + ``spec_k=`` for
        speculative decoding (up to spec_k tokens per step; greedy
        streams byte-identical to the plain engine, sampled traffic
        served via rejection sampling) and ``cache_dtype="int8"`` for
        a quantized KV arena.  ``paged=`` — a ``serve.PagedConfig``
        replacing the worst-case slot arena with ONE block-paged KV
        pool shared with the prefix cache: admission by blocks-free,
        block-by-block growth, priority preemption with byte-exact
        swap/resume; pair with ``scheduler="priority"`` for strict-
        priority admission).  ``tp=k`` — tensor-parallel serving
        (serve/tp.py): ONE engine's weights and KV arenas shard
        across a k-device mesh (Megatron column/row layout under
        shard_map, attention heads + MLP columns partitioned, one
        psum per attention output and per MLP fc2, each shard owning
        the (…, H_kv/k, …) slice of every cache pool) — the
        larger-than-one-device serving story, with token streams
        pinned identical to the single-device engine and every other
        knob composing unchanged.  Long-context serving (the
        long-context round): ``PagedConfig(prefill_token_budget=)``
        splits a long admission's prefill across steps in
        block-width chunks so decode lanes never stall behind it;
        sliding-window models (``GPT2Config(attn_window=)``) serve
        in paged mode holding O(window) blocks per slot; and
        ``TPConfig(ring_prefill=True)`` prefills cold long prompts
        sequence-sharded over the tp mesh.  ``ep=EPConfig(ep=, tp=)``
        — expert-parallel MoE serving (serve/ep.py): experts shard
        over an ``ep`` mesh axis with capacity-bounded GShard
        dispatch inside the jitted pool steps, dense layers keep the
        Megatron layout on an orthogonal ``tp`` axis, and streams
        stay token-identical to the single-device MoE engine.
        ``pp=PPConfig(stages=, microbatches=)`` — pipeline-parallel
        serving (serve/pp.py): the layer stack partitions into
        stages, each owning its layer slice of the paged KV pool,
        with microbatched decode so pipeline bubbles amortize across
        the continuous batch (requires ``paged=``).  See
        docs/SERVING.md "Fast decode", "Paged KV and preemption",
        "Tensor-parallel serving", "Long-context serving", and
        "Expert-parallel and pipeline serving"."""
        from ..serve import InferenceEngine

        return InferenceEngine(self, **kw)

    def serve_fleet(self, replicas=2, **kw):
        """N supervised engine replicas behind a health-checked router
        (singa_tpu.serve.ServeFleet): least-loaded / SLO-headroom
        scoring, sticky ``pin_session`` routing, cross-replica
        failover with never-started requeue parity, optional hedged
        re-dispatch.  Replicas share this model's weights and jitted
        executables but own their KV arena and prefix cache.  Keyword
        args: ``router``, ``restart_budget``, ``budget_reset_after_s``,
        ``shed_on_slo_pressure``, ``hedge_after_steps``, plus
        everything :meth:`serve` accepts (forwarded to every replica's
        engine).  ``tp=k`` builds a fleet of TENSOR-PARALLEL replicas:
        the device mesh partitions into ``replicas`` disjoint k-wide
        groups (tp inside each replica, data parallelism across them;
        ``tp x replicas`` must fit the mesh).  ``ep=``/``pp=`` do the
        same for expert-parallel MoE and pipeline-parallel replicas —
        (ep x tp)-wide or stage-wide disjoint groups respectively.
        See docs/SERVING.md "Fleet serving", "Tensor-parallel
        serving", and "Expert-parallel and pipeline serving"."""
        from ..serve import ServeFleet

        return ServeFleet(self, replicas=replicas, **kw)


def create_model(size="small", plan=None, **kw):
    cfg = getattr(GPT2Config, size)(**kw)
    return GPT2LMHead(cfg, plan)
