"""The configuration as it is run, and its weights drawn from the seed.

The weights are the benchmark's own: one jitted call draws every leaf
of the program's parameter tree on the device, in the type it is
served in, so the program and the reference read the same numbers and
the reference takes nothing the program made.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

# config-file key -> ModelConfig field
_FIELDS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}


def model_config(conf: Dict[str, Any]):
    """The program's ``ModelConfig`` for a config file: the registered
    architecture with every size taken from the file."""
    from repro.configs import get_config
    base = get_config(conf["registered"])
    kw = {field: conf[key] for key, field in _FIELDS.items()}
    flux = dataclasses.replace(base.flux, **conf.get("flux", {}))
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        conf["torch_dtype"]]
    return dataclasses.replace(base, name=conf["name"], flux=flux,
                               dtype=dtype, param_dtype=dtype, **kw)


def routing_pattern(conf: Dict[str, Any], n_layers: int) -> Tuple[str, ...]:
    """The fixed per-layer FA/SA pattern every request of the cell is
    served with: "alternate" is FA on even layers, SA on odd ones."""
    r = conf["routing"]
    if r == "alternate":
        return tuple("fa" if i % 2 == 0 else "sa" for i in range(n_layers))
    if len(r) != n_layers or set(r) - {"fa", "sa"}:
        raise ValueError(f"routing {r!r}: expected 'alternate' or one of "
                         f"fa/sa per layer ({n_layers})")
    return tuple(r)


def _leaf_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def _draw(key, name: str, shape, dtype):
    last = name.rsplit("/", 1)[-1]
    if last == "scale":                       # norm gains
        return jnp.ones(shape, dtype)
    if re.fullmatch(r".*_b\d*", last):        # router biases
        return jnp.zeros(shape, dtype)
    std = 0.02 if last == "embed" else float(shape[-2]) ** -0.5
    return jax.random.normal(key, shape, dtype) * jnp.asarray(std, dtype)


def make_weights(cfg, seed: int) -> Dict[str, Any]:
    """Every parameter of ``cfg`` drawn from ``seed`` in one compiled
    call on the default device: norm gains 1, biases 0, embeddings
    N(0, 0.02), matrices N(0, 1/fan_in)."""
    from functools import partial
    from repro.models import model as MD
    spec = jax.eval_shape(partial(MD.init_params, cfg=cfg),
                          jax.random.key(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(spec)

    def build(key):
        return treedef.unflatten([
            _draw(jax.random.fold_in(key, i), _leaf_name(p), x.shape,
                  x.dtype) for i, (p, x) in enumerate(leaves)])

    return jax.jit(build)(jax.random.key(seed))


def layer_weights(params, cfg, layer: int) -> Dict[str, Any]:
    """Layer ``layer``'s block, unstacked from the period-stacked trunk."""
    period = len(cfg.layer_pattern)
    per, pos = divmod(layer, period)
    return jax.tree.map(lambda a: a[per], params["trunk"][pos])
