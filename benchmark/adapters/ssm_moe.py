"""The system under test for the ``ssm_moe`` family: builds the program's
``SsmMoeLMHead`` from a configuration file through the entry points a
user calls, and lays the benchmark's seeded weights into it piece by
piece.

The model is built in the engine's dtype (bfloat16 at size): the
reference's float32 pieces exist one at a time -- a mixer's matrix, one
expert's matrix, a block of the embedding's rows or of the head's columns
-- and are cast as they are laid into the program's stacked weights, in
place.  The program keeps its layers in stacks by kind
(``SsmMoeConfig.place`` says where a layer of the model lies); every
tensor is the published one, stored (in, out).
"""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def program_config(config, **kw):
    from singa_tpu.models.ssm_moe import SsmMoeConfig

    names = {f.name for f in dataclasses.fields(SsmMoeConfig)}
    e, share = config["engine"], config.get("share", {})
    given = {k: v for k, v in config.items() if k in names}
    # where a file states a share, its n_routed_experts counts the
    # experts held here; the program's is the router's width
    given["n_routed_experts"] = share.get("num_experts_published",
                                          config["n_routed_experts"])
    given["experts_held"] = tuple(share.get(
        "experts_held", (0, given["n_routed_experts"])))
    return SsmMoeConfig(**given, max_len=e["max_len"], dtype=e["dtype"],
                        **kw)


def build_model(config, dev, *, train, batch_shape, **_):
    """The program's model, compiled through ``Model.compile``."""
    from singa_tpu import tensor
    from singa_tpu.models.ssm_moe import SsmMoeLMHead

    if train:
        raise NotImplementedError(
            "the ssm_moe family has no training path yet")
    m = SsmMoeLMHead(program_config(config))
    ids = tensor.from_numpy(np.zeros(batch_shape, np.int32), dev)
    m.compile([ids], is_train=False, use_graph=False, sequential=False)
    return m


@partial(jax.jit, donate_argnums=(0,))
def _lay_in(buf, piece, at):
    return jax.lax.dynamic_update_slice(buf, piece.astype(buf.dtype),
                                        tuple(at))


def _lay(buf, piece, *at):
    """``piece`` into ``buf`` at the leading indices ``at`` (zeros for
    the rest), in place, and wait: launches run ahead of the device, and
    every float32 piece launched is memory taken (PR 27)."""
    at = list(at) + [0] * (buf.ndim - len(at))
    piece = piece.reshape((1,) * (buf.ndim - piece.ndim) + piece.shape)
    return jax.block_until_ready(
        _lay_in(buf, piece, jnp.asarray(at, jnp.int32)))


def put_weights(m, w):
    """Hand the benchmark's weights ``w`` (the reference's handle: a
    function of tensor, layer and block) to the program's state
    tensors, which keep their dtype and their buffers' size."""
    from benchmark.references.ssm_moe import layer_keys, vocab_blocks

    s, c = w.sizes, m.cfg
    st = {k.rsplit(".", 1)[-1]: t for k, t in m.get_states().items()}
    buf = {k: t.data for k, t in st.items()}
    for b, (first, _) in enumerate(vocab_blocks(s)):
        buf["wte"] = _lay(buf["wte"], w.tensor("embed", block=b), first)
        buf["head"] = _lay(buf["head"], w.tensor("head", block=b), 0,
                           first)
    buf["lnf"] = _lay(buf["lnf"], w.tensor("lnf"))
    for layer in range(s["L"]):
        stack, i = c.place(layer)
        for name in layer_keys(s, layer):
            k = f"{stack}_{name}"
            if name in ("e_up", "e_down"):
                for e in range(*s["held"]):
                    buf[k] = _lay(buf[k], w.tensor(name, layer, e), i,
                                  e - s["held"][0])
            else:
                buf[k] = _lay(buf[k], w.tensor(name, layer), i)
    for k, t in st.items():
        if tuple(buf[k].shape) != tuple(t.shape):
            raise ValueError(f"{k}: program {t.shape}, laid "
                             f"{buf[k].shape}")
        t.data = buf[k]
    assert c.experts_held == tuple(s["held"]), (c.experts_held, s["held"])
