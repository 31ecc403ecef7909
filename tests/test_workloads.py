"""Launch-layer workload builders: lower+compile on a tiny debug mesh
with smoke configs (the real thing is launch/dryrun.py on 512 devices —
this guards the plumbing in the normal test environment)."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, smoke_variant
from repro.configs.base import InputShape
from repro.distributed import logical_rules
from repro.launch import workloads as WL
from repro.launch import hlo_analysis as HA
from repro.launch.mesh import make_debug_mesh

SMALL = {
    "train": InputShape("t", 64, 2, "train"),
    "prefill": InputShape("p", 64, 2, "prefill"),
    "decode": InputShape("d", 64, 2, "decode"),
}


def _lower(cfg, shape, **kw):
    mesh = make_debug_mesh(1, 1)
    wl = WL.build_workload(cfg, shape, mesh, **kw)
    with jax.set_mesh(mesh), logical_rules(wl.rules):
        compiled = jax.jit(wl.fn, in_shardings=wl.in_shardings).lower(
            *wl.args).compile()
        hlo = compiled.as_text()
    return compiled, hlo


@pytest.mark.parametrize("arch", ["phi3-mini-3.8b", "gemma3-12b",
                                  "granite-moe-3b-a800m",
                                  "jamba-1.5-large-398b", "mamba2-780m",
                                  "whisper-tiny", "phi-3-vision-4.2b",
                                  "deepseek-v2-236b"])
@pytest.mark.parametrize("kind", ["train", "prefill", "decode"])
def test_workload_lowers(arch, kind):
    cfg = smoke_variant(get_config(arch))
    compiled, hlo = _lower(cfg, SMALL[kind])
    terms = HA.roofline_terms(compiled, hlo, 1)
    assert terms["hlo_flops_per_chip"] > 0
    assert terms["t_compute_s"] >= 0


@pytest.mark.parametrize("arch", ["phi3-mini-3.8b",
                                  "jamba-1.5-large-398b",
                                  "deepseek-v2-236b"])
def test_prefill_chunked_workload_lowers(arch):
    cfg = smoke_variant(get_config(arch))
    shape = InputShape("pc", 64, 2, "prefill_chunked")
    compiled, hlo = _lower(cfg, shape, chunk=16)
    terms = HA.roofline_terms(compiled, hlo, 1)
    assert terms["hlo_flops_per_chip"] > 0


def test_decode_variants_lower():
    cfg = smoke_variant(get_config("phi3-mini-3.8b"))
    _lower(cfg, SMALL["decode"], decode_tp=True)
    _lower(cfg, SMALL["decode"], msr=1.0)


def test_train_no_seq_shard_lowers():
    cfg = smoke_variant(get_config("phi3-mini-3.8b"))
    _lower(cfg, SMALL["train"], seq_shard=False)


def test_causal_split_workload():
    cfg = smoke_variant(get_config("phi3-mini-3.8b")).replace(
        causal_split_depth=2)
    _lower(cfg, SMALL["prefill"])
