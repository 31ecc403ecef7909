"""Necessary-work counts against hand counts, and the peaks table."""
import pytest

import bench_tiny  # noqa: F401
from bench import peaks, work


def _geom(hq, hkv, d, pattern, sink=4, local=8):
    return work.Geometry(hq, hkv, d, tuple(pattern), sink, local,
                         matmul_params=1000, head_params=50)


def test_decode_attn_counts_kv_once_per_kv_head():
    # G = 12: 96 query heads share 8 KV heads (command-r-plus widths)
    g = _geom(96, 8, 128, ["fa"])
    flops, nbytes = work.decode_attn(g, [100, 300])
    live = 100 + 300
    assert flops == 2 * 96 * live * (128 + 128)
    kv = 8 * live * 256 * 2
    qo = 2 * 2 * 96 * 128 * 2           # two slots, q in + o out
    assert nbytes == kv + qo
    # counting K/V per query head would read 12x the bytes
    assert 96 * live * 256 * 2 == 12 * kv


def test_decode_attn_sa_layers_see_the_ring():
    g = _geom(4, 2, 16, ["fa", "sa"], sink=4, local=8)
    flops, _ = work.decode_attn(g, [5, 40])
    # fa layer: 5 + 40 keys; sa layer: 5 + min(40, 12) keys
    assert flops == 2 * 4 * (5 + 40 + 5 + 12) * 32


def test_prefill_attn_hand_count():
    g = _geom(4, 2, 16, ["fa", "sa", "fa"])
    flops, nbytes = work.prefill_attn(g, start=8, size=4)
    keys = 9 + 10 + 11 + 12                 # rows 8..11 see row+1 keys
    assert flops == 2 * 4 * keys * 32 * 2   # two FA layers
    assert nbytes == (2 * 4 * 4 * 16 + 2 * 12 * 32) * 2 * 2


def test_model_flops_hand_count():
    g = _geom(4, 2, 16, ["fa", "sa"], sink=2, local=2)
    # positions 0 and 9: fa sees 1 and 10 keys, sa 1 and 4
    got = work.model_flops(g, [0, 9], logits_rows=1)
    want = 2 * 2 * 1000 + 2 * 4 * (1 + 1 + 10 + 4) * 32 + 2 * 50
    assert got == want


def test_roofline_share_names_its_bound():
    pk = peaks.peaks("TPU v5 lite")
    share, bound = work.roofline_share(197e12, 1.0, 2.0, pk)
    assert bound == "flops" and share == pytest.approx(50.0)
    share, bound = work.roofline_share(1.0, 819e9, 4.0, pk)
    assert bound == "bytes" and share == pytest.approx(25.0)


def test_geometry_from_config():
    from bench import model
    cell = bench_tiny.tiny_cell()
    cfg = model.model_config(cell.config)
    g = work.geometry(cfg, model.routing_pattern(cell.config, 2))
    assert g.pattern == ("fa", "sa") and g.ring == 32
    per_layer = 64 * 64 * 2 + 64 * 32 * 2 + 3 * 64 * 128
    assert g.matmul_params == 2 * per_layer
    assert g.head_params == 64 * 128


def test_peaks_refuse_unknown_kind():
    assert peaks.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="TPU v4"):
        peaks.peaks("TPU v4")


def test_traffic_offers_every_seed_the_same_work():
    import json
    import os
    from bench import traffic
    with open(os.path.join(bench_tiny.ROOT, "bench", "traffic",
                           "rag-longctx.json")) as f:
        mix = json.load(f)

    def sizes(seed):
        arr = traffic.generate(mix, rate=0.5, seconds=40, vocab=1000,
                               seed=seed)
        return ([len(a.tokens) for a in arr], [a.n_out for a in arr],
                [a.due for a in arr], [a.tokens[300] for a in arr])

    a, b = sizes(1), sizes(2 ** 33 + 1)
    assert a[:3] == b[:3] and a[3] != b[3]    # one schedule, new ids
    assert len(a[0]) == 20
    # arrivals span the window: the first at once, the last one mean
    # gap before the close
    assert a[2][0] == 0.0 and a[2][-1] == pytest.approx(40 - 1 / 0.5)
    assert a[2] == sorted(a[2])
