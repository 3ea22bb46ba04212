"""Choosing the next token from a row of logits: the filter chain and
the sampler that the offline generators and the serve engine share,
whatever model produced the logits.
"""

import jax
import jax.numpy as jnp

from .paged_attention import NEG_INF


def filter_logits(logit, temperature, top_p, top_k, use_top_p):
    """Temperature + top-k + top-p (nucleus) filtered f32 logits —
    exactly the tensor ``sample(greedy=False)`` hands to
    ``jax.random.categorical``, factored out so the speculative
    rejection-sampling verify (``gpt2_decode.spec_verify``) scores the SAME
    post-filter distribution the direct sampler draws from (any drift
    here is a silent distribution bug, so the code exists once)."""
    logit = logit.astype(jnp.float32) / temperature
    if top_k:
        kth = jax.lax.top_k(logit, top_k)[0][-1]
        logit = jnp.where(logit < kth, NEG_INF, logit)
    if use_top_p:
        order = jnp.argsort(-logit)
        sp = jax.nn.softmax(logit[order])
        cum = jnp.cumsum(sp)
        # smallest prefix with mass >= top_p: drop tokens whose
        # *preceding* cumulative mass already reached it (the top-1
        # token is always kept)
        keep_sorted = (cum - sp) < top_p
        keep = jnp.zeros_like(keep_sorted).at[order].set(keep_sorted)
        logit = jnp.where(keep, logit, NEG_INF)
    return logit


def sample(logit, key, temperature, top_p, greedy, top_k, use_top_p,
            min_p=1.0, use_min_p=False, rep_mask=None, rep_penalty=1.0,
            mask=None):
    """One token from a (V,) logit row.  ``greedy``/``top_k``/
    ``use_top_p``/``use_min_p`` are static; ``temperature``/``top_p``/
    ``min_p``/``rep_penalty`` are traced.  Filter order follows the
    de-facto standard (HF generate): repetition penalty (a processor —
    applies before greedy argmax too) → temperature → top-k → top-p
    (nucleus) → min-p → categorical.

    ``rep_mask`` (V,) bool marks tokens already in the sequence
    (prompt + emitted); their logits are divided by ``rep_penalty``
    when positive and multiplied when negative (CTRL semantics, as in
    HF).

    ``mask`` (V,) bool is the CONSTRAINED-decoding vocab mask (the
    serve engine's grammar automaton, serve/structured.py): False
    lanes drop to NEG_INF before greedy argmax AND before the filter
    chain, so both modes sample only grammar-legal tokens.  None (the
    default) and an all-True mask are bitwise no-ops — unconstrained
    streams cannot drift."""
    logit = logit.astype(jnp.float32)
    if mask is not None:
        logit = jnp.where(mask, logit, NEG_INF)
    if rep_mask is not None:
        pen = jnp.where(logit > 0, logit / rep_penalty,
                        logit * rep_penalty)
        logit = jnp.where(rep_mask, pen, logit)
    if greedy:
        return jnp.argmax(logit).astype(jnp.int32)
    logit = filter_logits(logit, temperature, top_p, top_k, use_top_p)
    if use_min_p:
        # keep p >= min_p·p_max  ⇔  logit >= max + ln(min_p)
        logit = jnp.where(logit < jnp.max(logit) + jnp.log(min_p),
                          NEG_INF, logit)
    return jax.random.categorical(key, logit).astype(jnp.int32)


def select_sample(logit, key, temp, top_k, top_p, use_top_p, mask=None):
    """Per-row sampling with a TRACED greedy flag.  The offline paths
    bake ``greedy`` in as a static (one compile per mode); a slot pool
    mixes greedy and sampled requests in one executable, so compute
    both branches of the SAME ``sample`` the offline path uses and
    select — the greedy branch is argmax over the identical f32 logit,
    the sampled branch divides by max(temp, 1e-6) exactly as
    ``generate`` does, so either way the chosen token matches the
    offline token bit for bit.  ``mask`` (V,) bool or None is the
    constrained-decoding vocab mask, forwarded to the shared
    ``sample`` (None / all-True are bitwise no-ops)."""
    g = sample(logit, key, temp, top_p, True, top_k, use_top_p,
               mask=mask)
    s = sample(logit, key, jnp.maximum(temp, 1e-6), top_p, False,
               top_k, use_top_p, mask=mask)
    return jnp.where(temp <= 0.0, g, s).astype(jnp.int32)
