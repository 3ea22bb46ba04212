"""The system under test for the ``conv_moe`` family: builds the program's
``ConvMoeLMHead`` from a configuration file through the entry points a
user calls, and lays the benchmark's seeded weights into it piece by
piece.

The model is built in the engine's dtype (bfloat16 at size): the
reference's float32 pieces exist one at a time -- an operator's matrix,
one expert's matrix, a block of the dense feed-forward's columns, of the
embedding's rows -- and are cast as they are laid into the program's
stacked weights, in place.  The program keeps its layers in stacks by
kind (``ConvMoeConfig.place`` says where a layer of the model lies), gate
and up side by side in one matrix, and W_q and W_k as (out, in); every
other tensor is the published one, and the head is the embedding.
"""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def program_config(config, **kw):
    from singa_tpu.models.conv_moe import ConvMoeConfig

    names = {f.name for f in dataclasses.fields(ConvMoeConfig)}
    e, share = config["engine"], config.get("share", {})
    given = {k: v for k, v in config.items() if k in names}
    # where a file states a share, its num_experts counts the experts
    # held here; the program's is the router's width
    given["num_experts"] = share.get("num_experts_published",
                                     config["num_experts"])
    given["experts_held"] = tuple(share.get(
        "experts_held", (0, given["num_experts"])))
    given["layer_types"] = tuple(config["layer_types"])
    given["rope_theta"] = config["rope_parameters"]["rope_theta"]
    return ConvMoeConfig(**given, max_len=e["max_len"], dtype=e["dtype"],
                         **kw)


def build_model(config, dev, *, train, batch_shape, **_):
    """The program's model, compiled through ``Model.compile``."""
    from singa_tpu import tensor
    from singa_tpu.models.conv_moe import ConvMoeLMHead

    if train:
        raise NotImplementedError(
            "the conv_moe family has no training path yet")
    m = ConvMoeLMHead(program_config(config))
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
    from benchmark.references.conv_moe import (dense_blocks, layer_keys,
                                               vocab_blocks)

    s, c = w.sizes, m.cfg
    st = {k.rsplit(".", 1)[-1]: t for k, t in m.get_states().items()}
    buf = {k: t.data for k, t in st.items()}
    for b, (first, _) in enumerate(vocab_blocks(s)):
        buf["wte"] = _lay(buf["wte"], w.tensor("embed", block=b), first)
    buf["lnf"] = _lay(buf["lnf"], w.tensor("lnf"))
    im = s["IM"]
    for layer in range(s["L"]):
        stack, i = c.place(layer)

        def lay(name, piece, *at):
            k = f"{stack}_{name}"
            buf[k] = _lay(buf[k], piece, i, *at)

        for name in layer_keys(s, layer):
            if name in ("w_gate", "w_up", "w_down"):
                for b, (first, _) in enumerate(dense_blocks(s)):
                    piece = w.tensor(name, layer, b)
                    if name == "w_down":
                        lay("w_down", piece, first)
                    else:
                        lay("w_gu", piece, 0,
                            first + (s["I"] if name == "w_up" else 0))
            elif name in ("e_gate", "e_up", "e_down"):
                for e in range(*s["held"]):
                    piece, at = w.tensor(name, layer, e), e - s["held"][0]
                    if name == "e_down":
                        lay("e_down", piece, at)
                    else:
                        lay("e_gu", piece, at, 0,
                            im if name == "e_up" else 0)
            elif name in ("wq", "wk"):
                lay(name, w.tensor(name, layer).T)    # stored (out, in)
            else:
                lay(name, w.tensor(name, layer))
    for k, t in st.items():
        if tuple(buf[k].shape) != tuple(t.shape):
            raise ValueError(f"{k}: program {t.shape}, laid "
                             f"{buf[k].shape}")
        t.data = buf[k]
    assert c.experts_held == tuple(s["held"]), (c.experts_held, s["held"])
