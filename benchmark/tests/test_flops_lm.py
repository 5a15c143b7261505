"""``benchmark/flops_lm.py`` against a brute-force count, and the three
readers this configuration brought against hand-made records."""

import importlib.util
import json
import os

import pytest

from benchmark import flops_lm

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


@pytest.mark.parametrize("seq,window", [(32, 8), (32, None), (8, 8), (8, 9),
                                        (33, 1), (200, 64)])
def test_causal_pairs_against_brute_force(seq, window):
    brute = sum(1 for i in range(seq) for j in range(seq)
                if j <= i and (not window or i - j < window))
    assert flops_lm.causal_pairs(seq, window) == brute


def test_step_flops_of_the_published_cut():
    with open(os.path.join(BENCH, "configs",
                           "mellum2_12b_ep4_train.json")) as f:
        cfg = json.load(f)
    # uniform routing: 8192 tokens x 8 x 16 / 64 x 4 layers
    parts = flops_lm.lm_flops_per_step(
        cfg, cfg["layer_types"], cfg["deployment"]["router_experts"], 1,
        8192, 4 * 8192 * 8 * 16 / 64)
    fwd = {k: v / 3 for k, v in parts.items()}
    # ISSUE 29's forward count: projections 1 392, attention 936 (full
    # layer 550, window layer 129), experts 812, head 928 GFLOP
    assert fwd["attention"] == pytest.approx(936e9, rel=0.01)
    assert fwd["experts"] == pytest.approx(812e9, rel=0.01)
    assert fwd["dense"] == pytest.approx((1392 + 928 + 9.7) * 1e9, rel=0.01)
    assert parts["step"] == pytest.approx(12.2e12, rel=0.01)
    full = 4 * 32 * 128 * flops_lm.causal_pairs(8192)
    window = 4 * 32 * 128 * flops_lm.causal_pairs(8192, 1024)
    assert full / 1e9 == pytest.approx(550, rel=0.01)
    assert window / 1e9 == pytest.approx(129, rel=0.01)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


RUN = {"kind": "train", "traced_steps": 10, "peaks": {"bf16_flops": 100.0},
       "lm_flops": {"experts": 30.0, "attention": 8.0},
       "prepared_stats": {"moe_expert_load_max": 300,
                          "moe_expert_load_mean": 200.0},
       "trace": {"device_ops": [["fusion", 9.0], ["moe_gmm", 4.0],
                                ["moe_gmm_wgrad", 2.0],
                                ["flash_gqa_fwd", 1.0],
                                ["flash_gqa_bwd_dkv", 2.0],
                                ["flash_gqa_bwd_dq", 1.0],
                                ["flash_fwd", 50.0]]}}


def test_readers_on_a_record():
    # 30 x 10 FLOPs in 6 s of a 100 FLOP/s peak; 8 x 10 in 4 s
    assert _reader("moe_gmm_roofline_pct")(RUN) == pytest.approx(50.0)
    assert _reader("attn_window_roofline_pct")(RUN) == pytest.approx(20.0)
    assert _reader("expert_load_max_over_mean")(RUN) == pytest.approx(1.5)


@pytest.mark.parametrize("name", ["moe_gmm_roofline_pct",
                                  "attn_window_roofline_pct",
                                  "expert_load_max_over_mean"])
def test_readers_find_nothing_where_the_program_has_nothing(name):
    """A BERT record (no such kernel, no such counter), an untraced run
    and a serving record: None, never an exception."""
    bert = {"kind": "train", "traced_steps": 10, "peaks": RUN["peaks"],
            "prepared_stats": {"steps": 10},
            "trace": {"device_ops": [["fusion", 9.0]]}}
    for run in (bert, dict(bert, trace=None), {"kind": "serve"},
                dict(RUN, trace=dict(device_ops=[["fusion", 1.0]]),
                     prepared_stats={})):
        assert _reader(name)(run) is None
