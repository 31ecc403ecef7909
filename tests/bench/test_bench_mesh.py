"""A cell on four chips through the harness, and what lets a new
configuration bring its own reference and program fields.

The four-chip run needs four devices, and the suite keeps one: a child
process (this file run as a script) gets four virtual CPU devices and
drives ``run_cell`` on a ``(1, 4)`` mesh, weights born sharded, the
check against the reference, and one fault planted under the mesh,
the exchange between chips left out; the same seed then runs here on
one device."""
import json
import os
import subprocess
import sys
import time
from types import SimpleNamespace

import numpy as np
import pytest

import bench_tiny
from bench import loop, model, readers, spec, work
from bench import run as R
from bench import trace as TR

CHIPS = 4
HEADS = (8, 4)              # both split over four chips
CHILD_TIMEOUT = 240         # seconds; about 40 alone on 8 CPU cores


def _run_tiny(cell, hook=None):
    run, numbers, limits = loop.run_cell(
        cell, seed=bench_tiny.SEED, seconds=1.5, trace=False,
        t_start=time.perf_counter(), log=lambda s: None, engine_hook=hook)
    return run, R.result_line(run, numbers, limits, False,
                              log=lambda s: None)


def _exchange_left_out(engine):
    """Decode with each row-parallel product (``wo``, ``down``) summed
    over the first chip's rows alone: what a chip computes when the
    all-reduce after it is left out."""
    import jax
    from functools import partial
    from repro.models import model as MD

    def first_shard(path, w):
        if getattr(path[-1], "key", None) not in ("wo", "down"):
            return w
        keep = w.shape[1] // CHIPS
        return w.at[:, keep:].set(0)

    params = jax.tree_util.tree_map_with_path(first_shard, engine.params)
    plain = jax.jit(partial(MD.decode_many, cfg=engine.cfg),
                    static_argnames=("n_steps", "greedy", "duo_layers",
                                     "unroll"))

    def faulty(**kw):
        return plain(**dict(kw, params=params))
    faulty._cache_size = plain._cache_size
    engine._decode_many = faulty


def _child() -> dict:
    import jax
    jax.config.update("jax_default_matmul_precision", "highest")
    assert len(jax.devices()) == CHIPS, jax.devices()
    out = {}
    held = {}

    def look(engine):
        leaves = jax.tree_util.tree_leaves(engine.params)
        total = sum(x.nbytes for x in leaves)
        per = [sum(s.data.nbytes for x in leaves
                   for s in x.addressable_shards if s.device == d)
               for d in engine.mesh.devices.flat]
        held.update(mesh=list(engine.mesh.shape.values()),
                    axes=list(engine.mesh.axis_names), total=total,
                    per_device=per)

    run, line = _run_tiny(bench_tiny.tiny_cell(chips=CHIPS, heads=HEADS),
                          look)
    out["mesh"] = dict(line, held=held,
                       window_compiles=run.compiles.get("window", 0),
                       geometry=[run.geometry.n_heads,
                                 run.geometry.n_kv_heads])
    out["fault"] = _run_tiny(bench_tiny.tiny_cell(chips=CHIPS, heads=HEADS),
                             _exchange_left_out)[1]

    # the draw born sharded against the draw on one device
    from repro.launch.mesh import make_debug_mesh
    cfg = model.model_config(bench_tiny.tiny_cell(heads=HEADS).config)
    one = model.make_weights(cfg, bench_tiny.SEED)
    tp = model.make_weights(cfg, bench_tiny.SEED, make_debug_mesh(1, CHIPS))
    pairs = list(zip(jax.tree_util.tree_leaves(one),
                     jax.tree_util.tree_leaves(tp)))
    out["draw"] = {
        "leaves": len(pairs),
        "sharded": sum(len(b.sharding.device_set) == CHIPS
                       and not b.sharding.is_fully_replicated
                       for _, b in pairs),
        "bitwise_equal": all(np.array_equal(np.asarray(a), np.asarray(b))
                             for a, b in pairs)}
    return out


@pytest.fixture(scope="module")
def four_chips():
    flags = os.environ.get("XLA_FLAGS", "")
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        f"{flags} --xla_force_host_platform_device_count={CHIPS}").strip())
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                          env=env, capture_output=True, text=True,
                          timeout=CHILD_TIMEOUT)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_four_chip_cell_is_correct_under_a_mesh(four_chips):
    line = four_chips["mesh"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["checks"]["logit_gap"]["limit"] == 1e-3   # the tiny limit
    assert line["device"]["chips"] == CHIPS
    assert line["device"]["count"] == CHIPS
    assert line["window_compiles"] == 0
    held = line["held"]
    assert held["axes"] == ["data", "model"] and held["mesh"] == [1, CHIPS]
    assert max(held["per_device"]) < held["total"]     # weights split
    assert line["geometry"] == [HEADS[0] // CHIPS, HEADS[1] // CHIPS]


def test_same_seed_on_one_chip_reads_as_on_four(four_chips):
    """The four-chip cell's seed and heads on this process's one device:
    the path of every one-chip cell, no mesh."""
    seen = {}
    run, line = _run_tiny(bench_tiny.tiny_cell(chips=1, heads=HEADS),
                          lambda engine: seen.update(mesh=engine.mesh))
    assert seen["mesh"] is None
    assert line["correct"] is True and line["device"]["chips"] == 1
    assert line["attempted"] == four_chips["mesh"]["attempted"]
    assert run.geometry.n_heads == HEADS[0]


def test_mesh_born_weights_equal_the_one_device_draw(four_chips):
    draw = four_chips["draw"]
    assert draw["bitwise_equal"] is True
    assert draw["sharded"] > draw["leaves"] // 2


def test_exchange_between_chips_left_out_fails_the_check(four_chips):
    fault = four_chips["fault"]
    assert fault["correct"] is False
    assert fault["checks"]["logit_gap"]["value"] > \
        fault["checks"]["logit_gap"]["limit"]


def _tiny_cfg(heads=HEADS):
    cell = bench_tiny.tiny_cell(heads=heads)
    cfg = model.model_config(cell.config)
    return cfg, model.routing_pattern(cell.config, cfg.num_layers)


def test_work_counts_one_chips_share():
    cfg, pattern = _tiny_cfg()
    one, four = (work.geometry(cfg, pattern, c) for c in (1, CHIPS))
    assert (four.n_heads, four.n_kv_heads, four.chips) == (2, 1, CHIPS)
    lives = [5, 40, 63]
    for count in (lambda g: work.decode_attn(g, lives),
                  lambda g: work.prefill_attn(g, 16, 16)):
        f1, b1 = count(one)
        f4, b4 = count(four)
        assert (f4, b4) == (f1 / CHIPS, b1 / CHIPS)
    # the whole model's FLOPs, whatever the chips
    assert (work.model_flops(four, [3, 50], 2)
            == work.model_flops(one, [3, 50], 2))
    # KV heads that do not split stay whole on each chip
    cfg2, _ = _tiny_cfg(heads=(8, 2))
    assert work.geometry(cfg2, pattern, CHIPS).n_kv_heads == 2
    cfg3, _ = _tiny_cfg(heads=(6, 2))
    with pytest.raises(ValueError, match="6 query heads"):
        work.geometry(cfg3, pattern, CHIPS)


def test_step_mfu_divides_by_the_cells_chips():
    cfg, pattern = _tiny_cfg()
    from bench.loop import Tick
    ticks = [Tick(0.0, 0.1, (0, 16, 16), [20, 33]),
             Tick(0.1, 0.2, None, [21, 34])]

    def mfu(chips):
        run = SimpleNamespace(
            trace=SimpleNamespace(window_s=2.0), traced_ticks=ticks,
            peaks={"bf16_flops_per_s": 1e9},
            geometry=work.geometry(cfg, pattern, chips))
        return readers.step_mfu(run)

    flops = work.model_flops(work.geometry(cfg, pattern),
                             list(range(16, 32)) + [19, 32, 20, 33], 5)
    assert mfu(1) == pytest.approx(100.0 * flops / (2.0 * 1e9))
    assert mfu(CHIPS) == pytest.approx(mfu(1) / CHIPS)


def test_reference_defaults_to_the_dense_block():
    from bench import reference
    assert spec.reference(bench_tiny.tiny_cell().config) is reference


PLANTED = '''
from bench import reference as dense

calls = []


def logits(params, conf, pattern, tokens, first, max_len, *, fp8=False):
    calls.append(len(tokens))
    return dense.logits(params, conf, pattern, tokens, first, max_len,
                        fp8=fp8)


def matmul_params(conf):
    return 123457
'''


def test_planted_reference_and_program_fields_drive_a_run(tmp_path,
                                                          monkeypatch):
    """A configuration that names its reference and program fields in
    its own files: the check runs that reference, the work counts take
    its matmul count, the program runs the fields."""
    (tmp_path / "planted.py").write_text(PLANTED)
    monkeypatch.setattr(spec, "REFERENCES", str(tmp_path))
    cell = bench_tiny.tiny_cell()
    conf_file = tmp_path / "planted-config.json"
    conf_file.write_text(json.dumps(dict(
        cell.config, reference="planted",
        program={"moe_capacity_factor": 2.0})))
    cell.config = json.loads(conf_file.read_text())
    ref = spec.reference(cell.config)
    assert ref.__file__ == str(tmp_path / "planted.py")
    run, line = _run_tiny(cell)
    assert line["correct"] is True
    assert ref.calls and run.geometry.matmul_params == 123457
    assert model.model_config(cell.config).moe_capacity_factor == 2.0


def test_unknown_reference_is_refused_naming_its_file(tmp_path,
                                                      monkeypatch):
    monkeypatch.setattr(spec, "REFERENCES", str(tmp_path))
    conf = dict(bench_tiny.tiny_cell().config, reference="no-such-block")
    with pytest.raises(FileNotFoundError,
                       match=r"no-such-block.*no file .*no-such-block\.py"):
        spec.reference(conf)
    with pytest.raises(ValueError, match="not a name"):
        spec.reference(dict(conf, reference="../run"))


def test_model_config_keeps_registered_values_for_absent_keys():
    from repro.configs import get_config
    conf = bench_tiny.tiny_cell().config
    base = get_config(conf["registered"])
    for key in ("rope_theta", "rms_norm_eps", "tie_word_embeddings"):
        del conf[key]
    cfg = model.model_config(conf)
    assert cfg.rope_theta == base.rope_theta
    assert cfg.norm_eps == base.norm_eps
    assert cfg.tie_embeddings == base.tie_embeddings
    assert cfg.d_model == 64 and cfg.num_layers == 2   # given keys apply
    # a LayerNorm model's epsilon maps to the same field
    assert model.model_config(dict(conf, layer_norm_eps=3e-6)).norm_eps \
        == 3e-6


def test_program_block_sets_fields_last():
    conf = dict(bench_tiny.tiny_cell().config, program={
        "num_heads": 8, "layer_pattern": ["attn"], "causal_split_depth": 2})
    cfg = model.model_config(conf)
    assert cfg.num_heads == 8                 # over the file's 4
    assert cfg.layer_pattern == ("attn",) and cfg.causal_split_depth == 2


def test_program_block_refuses_an_unknown_field():
    conf = dict(bench_tiny.tiny_cell().config,
                program={"use_parallel_residual": True})
    with pytest.raises(KeyError, match="use_parallel_residual"):
        model.model_config(conf)


def test_vector_leaves_draw_gains_and_biases():
    import jax
    import jax.numpy as jnp
    key = jax.random.key(0)
    for name, shape in (("final_norm/bias", (64,)),
                        ("trunk/0/norm1/bias", (2, 64)),
                        ("trunk/0/attn/q_norm_b", (2, 16))):
        x = model._draw(key, name, shape, jnp.float32)
        assert x.shape == shape and not np.asarray(x).any()
    assert np.all(np.asarray(model._draw(key, "trunk/0/norm2/scale",
                                         (2, 64), jnp.float32)) == 1)
    # the dense tree: ones at its gains, zeros at its router biases,
    # every matrix drawn
    cfg, _ = _tiny_cfg()
    for path, x in jax.tree_util.tree_flatten_with_path(
            model.make_weights(cfg, 5))[0]:
        name, a = model._leaf_name(path), np.asarray(x)
        last = name.rsplit("/", 1)[-1]
        if last == "scale":
            assert np.all(a == 1), name
        elif last.endswith(("_b", "_b1", "_b2")):
            assert not a.any(), name
        else:
            assert a.std() > 0, name


def test_trace_reads_the_plane_of_the_cells_first_device():
    planes = [SimpleNamespace(name=n) for n in (
        "/host:CPU", "/device:TPU:1", "/device:TPU:0", "/device:TPU:10")]
    assert TR.device_plane(planes, 0).name == "/device:TPU:0"
    assert TR.device_plane(planes, 1).name == "/device:TPU:1"
    assert TR.device_plane(planes[:1], 0) is None      # the CPU backend
    assert TR.device_plane(planes[:2], 0).name == "/device:TPU:1"  # one chip
    with pytest.raises(ValueError, match="TPU 3"):
        TR.device_plane(planes, 3)


if __name__ == "__main__":
    print(json.dumps(_child()), flush=True)
