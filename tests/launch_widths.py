"""What the served families' tests share about the budgeted prefill's
launches (engine ``_launch_chunks``: a pass's pieces go out in the
fewest launches the step's budget allows -- any whole number of one
request's blocks, and two requests' pieces in one launch where both fit
the pair program's slots): the prompts of each case, a run that keeps
what every admission left behind, and the comparison with the engine
whose budget is one block -- which launches a block at a time, as every
engine did before widths.

Blocks are 8 positions and rows 128 in every family's tiny preset.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from singa_tpu.serve import GenerationRequest
from singa_tpu.serve import engine as E

BLOCK, ROW = 8, 128
RATIOS = (1, 2, 3, 4)         # prefill_token_budget / block_size

#: prompt lengths of each case (all submitted before the first step)
CASES = {
    # 27 of a window's 32 (ratio 4), of its second 16 (ratio 2)
    "ends-mid-window": (27,),
    # the first takes one block of the step's budget, so the second is
    # admitted with the rest: a block short at ratio 2 (its next window
    # starts at block 1), 16 + 8 at ratio 4 (the next starts at block 3)
    "starts-at-an-odd-block": (7, 60),
    # the last block of the row: a window may not pass its end
    "reaches-the-rows-end": (125,),
    # (at ratio 4 each fills a slot of the pair program: one launch)
    "two-share-a-steps-budget": (12, 13),
    # two blocks and one, admitted in one pass: at ratio 4 one launch,
    # the second request's slot half unused
    "a-short-one-pads-its-slot": (12, 7),
    # 1 + 1 + 3 blocks: at ratio 4 a pair (both slots half unused) and
    # a launch of two of the third's blocks in one pass
    "three-in-a-pass": (5, 6, 22),
}
N_NEW = 3

#: launches that carried two requests, by (case, ratio): a pair program
#: exists where the budget is four blocks or more
MERGED = {("two-share-a-steps-budget", 4): 1,
          ("a-short-one-pads-its-slot", 4): 1,
          ("three-in-a-pass", 4): 1}

#: sha256 of the text ``engine._chunk_row`` lowered to at commit 4bfc691
#: (the parent of the PR that brought widths), per family at its tiny
#: preset with budget = block (:func:`chunk_row_lowering`, run there).
#: A change to a family's chunk-row math moves its line; widths may not,
#: nor launches of several segments.  (``swa_moe`` and ``conv_moe`` came
#: after widths: theirs is the text at cd88bfa, the parent of the PR
#: that made a launch a list of segments.)
PARENT_LOWERING = {
    "gpt2":
        "a334465d171e754a6406e92455c08371859f74594f7a7b6f02b028aedfe094b4",
    "falcon_h1":
        "90ace8aa4726cef34d827231cc7152459cb2df3dee38968cdeded8a3f87242be",
    "mla_moe":
        "c817c277b2920d8bbaa77007af52b25666cf2436a4e9c2ae85c232f28e1d24d5",
    "swa_moe":
        "687189e6aca738e3e3f8daab2447776780b0509dd52e7ec1a5775143bcafe654",
    "conv_moe":
        "46c801cfdaa61e0900a44bda1792313d552d5789fbdb0354702f5a2b3927dbe0",
}


def prompts_of(case, vocab):
    return [np.random.default_rng(100 + n).integers(0, vocab, n)
            .astype(np.int32) for n in CASES[case]]


def serve_case(eng, case, vocab):
    """Serve ``case`` on ``eng`` (greedy).  Returns the token streams,
    each admission's private rows (the prompt's positions: what a
    launch leaves above them is nobody's) and carried state as they
    stood when its last block landed, and what the counters gained."""
    kept = {}
    finish = eng._finish_prefilling

    def spy(idx, pf):
        plen = len(pf.request.prompt_ids)
        kept[pf.request.request_id] = (
            jax.tree.map(lambda r: np.asarray(r)[..., :plen, :],
                         (pf.kc_row, pf.vc_row)),
            jax.tree.map(np.asarray, pf.state))
        return finish(idx, pf)

    eng._finish_prefilling = spy
    c0 = (eng._c_budget_chunks.value, eng._c_launches.value,
          eng._c_merged_launches.value)
    try:
        hs = [eng.submit(GenerationRequest(p, max_new_tokens=N_NEW,
                                           temperature=0.0))
              for p in prompts_of(case, vocab)]
        while eng.pending:
            eng.step()
    finally:
        eng._finish_prefilling = finish
    return dict(
        tokens=[np.asarray(h.result().tokens) for h in hs],
        left=[kept[h.request.request_id] for h in hs],
        chunks=eng._c_budget_chunks.value - c0[0],
        launches=eng._c_launches.value - c0[1],
        merged=eng._c_merged_launches.value - c0[2])


class Runs:
    """``run(ratio, case)`` on one engine a ratio (``make(budget)``),
    each pair served once."""

    def __init__(self, make, vocab):
        self._make, self._vocab = make, vocab
        self.engines, self._runs = {}, {}

    def engine(self, ratio):
        if ratio not in self.engines:
            self.engines[ratio] = self._make(BLOCK * ratio)
        return self.engines[ratio]

    def run(self, ratio, case):
        if (ratio, case) not in self._runs:
            self._runs[ratio, case] = serve_case(
                self.engine(ratio), case, self._vocab)
        return self._runs[ratio, case]

    def close(self):
        for eng in self.engines.values():
            eng.close()


def blocks_of(case):
    """Blocks the case's prompts take: ``(last_off - 0) / B + 1`` each,
    the padding of a prompt's last block counted."""
    return sum((n - 1) // BLOCK + 1 for n in CASES[case])


def assert_same_as_one_block(got, want, case, ratio, atol):
    """``got`` (budget = ratio blocks) against ``want`` (budget = one
    block): the same tokens, rows and state; the same count of blocks;
    fewer launches exactly when the budget is wider than a block; two
    requests in a launch exactly where :data:`MERGED` says."""
    for a, b in zip(got["tokens"], want["tokens"]):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(jax.tree.leaves(got["left"]),
                    jax.tree.leaves(want["left"])):
        np.testing.assert_allclose(a, b, atol=atol)
    assert got["chunks"] == want["chunks"] == blocks_of(case)
    assert want["launches"] == want["chunks"] and want["merged"] == 0
    assert got["merged"] == MERGED.get((case, ratio), 0)
    if ratio == 1:
        assert got["launches"] == got["chunks"]
    else:
        assert got["launches"] < got["chunks"]


def chunk_row_lowering(eng):
    """sha256 of the text ``_chunk_row`` lowers to for ``eng``'s
    one-block call, from the arguments a prefilling request has."""
    arena = eng.paged_arena
    kc_row, vc_row = arena.gather_row([], n_used=0)
    kw = {}
    if eng._state_spec:
        kw["state"] = {k: jnp.zeros((eng._state[k].shape[0],) + tuple(s),
                                    dt)
                       for k, (s, dt) in eng._state_spec.items()}
    if eng._state_spec or eng._fam.pad_aware:
        kw["n_valid"] = jnp.int32(3)
    text = E._chunk_row.lower(
        eng._params, jnp.zeros((1, eng.max_len), jnp.int32), kc_row,
        vc_row, jnp.int32(8), fam=eng._fam, **kw,
        **eng._chunk_statics).as_text()
    return hashlib.sha256(text.encode()).hexdigest()
