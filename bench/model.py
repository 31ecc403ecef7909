"""The configuration as it is run, and its weights drawn from the seed.

The weights are the benchmark's own: one jitted call draws every leaf
of the program's parameter tree on the device, in the type it is
served in, so the program and the reference read the same numbers and
the reference takes nothing the program made.
"""
from __future__ import annotations

import dataclasses
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
    "layer_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}
_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def model_config(conf: Dict[str, Any]):
    """The program's ``ModelConfig`` for a config file: the registered
    architecture, with each size the file gives under a ``_FIELDS`` key
    (a key it leaves out keeps the registered value), its ``flux``
    geometry and ``torch_dtype``, and last its ``"program"`` block:
    ``ModelConfig`` fields set by name, for what the published keys do
    not say.  A field ``ModelConfig`` lacks is an error."""
    from repro.configs import get_config
    base = get_config(conf["registered"])
    kw = {field: conf[key] for key, field in _FIELDS.items() if key in conf}
    kw["flux"] = dataclasses.replace(base.flux, **conf.get("flux", {}))
    if "torch_dtype" in conf:
        kw["dtype"] = kw["param_dtype"] = _DTYPES[conf["torch_dtype"]]
    cfg = dataclasses.replace(base, name=conf["name"], **kw)
    program = conf.get("program", {})
    known = {f.name for f in dataclasses.fields(cfg)}
    unknown = sorted(set(program) - known)
    if unknown:
        raise KeyError(f"configuration {conf['name']!r}: ModelConfig has no "
                       f"field {', '.join(map(repr, unknown))} (its "
                       f"\"program\" block)")
    return dataclasses.replace(cfg, **{
        k: tuple(v) if isinstance(v, list) else v
        for k, v in program.items()})


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


def _stacked(name: str) -> int:
    """Leading axes that stack a leaf per period of the trunk."""
    return int(bool({"trunk", "layers"} & set(name.split("/"))))


def _draw(key, name: str, shape, dtype):
    """A leaf of one vector per layer is a norm gain (ones) if named
    ``scale``, else a bias (zeros); a matrix is N(0, 1/fan_in), an
    embedding N(0, 0.02)."""
    last = name.rsplit("/", 1)[-1]
    if len(shape) - _stacked(name) <= 1:
        return (jnp.ones if last == "scale" else jnp.zeros)(shape, dtype)
    std = 0.02 if last == "embed" else float(shape[-2]) ** -0.5
    return jax.random.normal(key, shape, dtype) * jnp.asarray(std, dtype)


def make_weights(cfg, seed: int, mesh=None) -> Dict[str, Any]:
    """Every parameter of ``cfg`` drawn from ``seed`` in one compiled
    call: norm gains 1, biases 0, embeddings N(0, 0.02), matrices
    N(0, 1/fan_in).  Without ``mesh`` they land on the default device;
    with it each leaf is born in the program's tensor-parallel serving
    sharding (``param_shardings_decode_tp``), each device drawing only
    its own shard, and the values are those of the one-device draw."""
    from functools import partial
    from repro.models import model as MD
    spec = jax.eval_shape(partial(MD.init_params, cfg=cfg),
                          jax.random.key(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(spec)

    def build(key):
        return treedef.unflatten([
            _draw(jax.random.fold_in(key, i), _leaf_name(p), x.shape,
                  x.dtype) for i, (p, x) in enumerate(leaves)])

    shardings = None
    if mesh is not None:
        from repro.launch.shardings import param_shardings_decode_tp
        shardings = param_shardings_decode_tp(spec, mesh)
    return jax.jit(build, out_shardings=shardings)(jax.random.key(seed))


def layer_weights(params, cfg, layer: int) -> Dict[str, Any]:
    """Layer ``layer``'s block, unstacked from the period-stacked trunk."""
    period = len(cfg.layer_pattern)
    per, pos = divmod(layer, period)
    return jax.tree.map(lambda a: a[per], params["trunk"][pos])
