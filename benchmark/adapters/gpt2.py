"""The system under test for the ``gpt2`` family: builds the program's
``GPT2LMHead`` from a configuration file through the entry points a user
calls, and hands it the benchmark's seeded weights.

From the program this takes the model, its optimizer wrapper and its
engine -- nothing that decides a number.
"""

import numpy as np

STATE_PREFIX = "GPT2LMHead.transformer."
# reference tensor -> the program's state name inside a block
_BLOCK = {"ln1_s": "ln1.scale", "ln1_b": "ln1.bias",
          "wq": "attn.q_proj.W", "bq": "attn.q_proj.b",
          "wk": "attn.k_proj.W", "bk": "attn.k_proj.b",
          "wv": "attn.v_proj.W", "bv": "attn.v_proj.b",
          "wo": "attn.out_proj.W", "bo": "attn.out_proj.b",
          "ln2_s": "ln2.scale", "ln2_b": "ln2.bias",
          "w1": "mlp.fc1.W", "b1": "mlp.fc1.b",
          "w2": "mlp.fc2.W", "b2": "mlp.fc2.b"}
_TOP = {"wte": "wte.W", "wpe": "wpe.W", "lnf_s": "ln_f.scale",
        "lnf_b": "ln_f.bias"}


def state_names(n_layer):
    """{program state name: (reference key, layer or None)}."""
    out = {STATE_PREFIX + v: (k, None) for k, v in _TOP.items()}
    for i in range(n_layer):
        for k, v in _BLOCK.items():
            out[f"{STATE_PREFIX}blocks{i}.{v}"] = (k, i)
    return out


def program_config(config, **kw):
    from singa_tpu.models.gpt2 import GPT2Config

    return GPT2Config(
        vocab_size=config["vocab_size"], n_positions=config["n_positions"],
        n_embd=config["n_embd"], n_layer=config["n_layer"],
        n_head=config["n_head"], n_inner=config.get("n_inner"),
        layer_norm_eps=config["layer_norm_epsilon"], dropout=0.0, **kw)


def build_model(config, dev, *, train, batch_shape, optimizer=None,
                attn_impl="auto"):
    """The program's model, compiled through ``Model.compile``."""
    from singa_tpu import tensor
    from singa_tpu.models.gpt2 import GPT2LMHead

    m = GPT2LMHead(program_config(config, attn_impl=attn_impl))
    if optimizer is not None:
        m.set_optimizer(optimizer)
    ids = tensor.from_numpy(np.zeros(batch_shape, np.int32), dev)
    m.compile([ids], is_train=train, use_graph=train, sequential=False)
    return m


def put_weights(m, w, place=lambda a: a):
    """Hand the benchmark's weights ``w`` (reference layout, stacked
    layers) to the program's state tensors.  ``place`` lays an array out
    as the program keeps that state (identity on one chip)."""
    names = state_names(m.cfg.n_layer)
    states = m.get_states()
    missing = set(states) ^ set(names)
    if missing:
        raise KeyError(f"program states and reference tensors differ: "
                       f"{sorted(missing)[:4]}")
    for name, t in states.items():
        k, i = names[name]
        a = w[k] if i is None else w[k][i]
        if tuple(a.shape) != tuple(t.shape):
            raise ValueError(f"{name}: program {t.shape}, reference "
                             f"{a.shape}")
        t.data = place(a)
    m.__dict__.pop("_decode_param_cache", None)


def leaf_values(tree_by_state, n_layer):
    """{program state name: value} -> {reference key: list per layer or
    value}: the program's per-state numbers in the reference's layout."""
    out = {}
    for name, (k, i) in state_names(n_layer).items():
        if i is None:
            out[k] = tree_by_state[name]
        else:
            out.setdefault(k, [None] * n_layer)[i] = tree_by_state[name]
    return out
