"""The system under test for the ``falcon_h1`` family: builds the
program's ``FalconH1LMHead`` from a configuration file through the entry
points a user calls, and lays the benchmark's seeded weights into it
tensor by tensor.

The model is built in the engine's dtype (bfloat16 at size): the
reference's float32 tensors exist one at a time -- a layer's tensor, a
block of the embedding's rows, a block of the head's columns -- and are
cast as they are laid into the program's stacked weights, in place.
"""

import dataclasses
from functools import partial

import jax
import numpy as np


def program_config(config, **kw):
    from singa_tpu.models.falcon_h1 import FalconH1Config

    names = {f.name for f in dataclasses.fields(FalconH1Config)}
    e = config["engine"]
    return FalconH1Config(
        **{k: (tuple(v) if isinstance(v, list) else v)
           for k, v in config.items() if k in names},
        max_len=e["max_len"], dtype=e["dtype"], **kw)


def build_model(config, dev, *, train, batch_shape, **_):
    """The program's model, compiled through ``Model.compile``."""
    from singa_tpu import tensor
    from singa_tpu.models.falcon_h1 import FalconH1LMHead

    if train:
        raise NotImplementedError(
            "the falcon_h1 family has no training path yet")
    m = FalconH1LMHead(program_config(config))
    ids = tensor.from_numpy(np.zeros(batch_shape, np.int32), dev)
    m.compile([ids], is_train=False, use_graph=False, sequential=False)
    return m


@partial(jax.jit, donate_argnums=(0,), static_argnames=("axis",))
def _lay_in(buf, piece, at, *, axis):
    return jax.lax.dynamic_update_slice_in_dim(
        buf, piece.astype(buf.dtype), at, axis)


def _lay(buf, piece, at, *, axis):
    """``piece`` into ``buf`` in place, and wait: launches run ahead of
    the device, and every float32 piece launched is memory taken (ten of
    them were 4.4 GB, the peak of the whole run; PR 27)."""
    return jax.block_until_ready(_lay_in(buf, piece, at, axis=axis))


def put_weights(m, w):
    """Hand the benchmark's weights ``w`` (the reference's handle: a
    function of tensor, layer and block) to the program's state
    tensors, which keep their dtype and their buffers' size."""
    from benchmark.references.falcon_h1 import LAYER_KEYS, vocab_blocks

    states = {k.rsplit(".", 1)[-1]: t for k, t in m.get_states().items()}
    missing = set(states) ^ (set(LAYER_KEYS) | {"wte", "head", "lnf"})
    if missing:
        raise KeyError(f"program states and reference tensors differ: "
                       f"{sorted(missing)}")
    blocks = vocab_blocks(w.sizes)
    for name, t in states.items():
        buf = t.data
        if name == "wte":
            for b, (first, _) in enumerate(blocks):
                buf = _lay(buf, w.tensor("embed", block=b), first, axis=0)
        elif name == "head":
            for b, (first, _) in enumerate(blocks):
                buf = _lay(buf, w.tensor("head", block=b), first, axis=1)
        elif name == "lnf":
            buf = _lay(buf, w.tensor("lnf"), 0, axis=0)
        else:
            for layer in range(m.cfg.n_layer):
                buf = _lay(buf, w.tensor(name, layer)[None], layer, axis=0)
        if tuple(buf.shape) != tuple(t.shape):
            raise ValueError(f"{name}: program {t.shape}, laid "
                             f"{buf.shape}")
        t.data = buf
