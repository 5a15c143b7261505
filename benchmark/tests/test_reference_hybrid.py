"""The plain jnp reference of the hybrid decoder
(benchmark/reference/olmo_hybrid_jnp.py) against the program at the
``olmo_hybrid_serve.doc_closed`` cell's rehearsal size, on the CPU in
float32, through the cell's own builder functions; each way the reference
can be computed WRONG (its ``CONTROLS``) refused by at least one of the
cell's limits; ``benchmark/flops_gdn.py`` against the issue's arithmetic;
and the two readers this configuration brought against hand-made
records."""

import importlib.util
import json
import os

import numpy as np
import pytest

from benchmark import flops_gdn

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "olmo_hybrid_serve.doc_closed"


def _config(rehearse=True):
    from benchmark import run as bench_run
    resolved = bench_run.resolve_cell(
        bench_run.load_manifest(os.path.dirname(BENCH)), CELL)
    if rehearse:
        bench_run.apply_rehearsal(resolved["config"], resolved["traffic"])
    return resolved["config"]


@pytest.fixture(scope="module")
def hybrid_served():
    """The cell's rehearsal engine, three requests (two chunked across
    several launches, one packed) served through both caches with their
    logits, and the weights."""
    from benchmark.builders import serve_hybrid
    config = _config()
    engine = serve_hybrid.build_engine(config, seed=11)
    engine.start()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, config["model"]["vocab_size"], n)
               for n in (7, 40, 57)]
    results = [f.result(timeout=600) for f in [
        engine.generate({"src_ids": p}, max_new_tokens=40,
                        return_logits=True) for p in prompts]]
    weights = serve_hybrid.close_and_take_weights(engine)
    return config, weights, prompts, results


def _verdict(hybrid_served, wrong=()):
    from benchmark.builders import serve_hybrid
    config, weights, prompts, results = hybrid_served
    readings = [serve_hybrid.compare(
        config["reference"], serve_hybrid.reference_model(config), weights,
        p, r.tokens, r.logits, wrong=wrong)
        for p, r in zip(prompts, results)]
    return serve_hybrid.judge(config["reference"], readings)


def test_hybrid_decoder_served_through_both_caches_matches_the_reference(
        hybrid_served):
    verdict = _verdict(hybrid_served)
    assert verdict["ok"], verdict


def _controls():
    from benchmark.reference import olmo_hybrid_jnp
    return olmo_hybrid_jnp.CONTROLS


@pytest.mark.parametrize("control", _controls())
def test_hybrid_reference_computed_wrong_is_refused(hybrid_served, control):
    """Each way the forward pass can be wrong (a gate, a norm, the
    convolution, a carried tail or state, the norm's place, a block of
    the context, the state's precision) is refused by at least one of
    the cell's limits at 1e-4."""
    verdict = _verdict(hybrid_served, wrong=(control,))
    assert not verdict["ok"], (control, verdict)
    assert [k for k, v in verdict["worst"].items()
            if v > verdict["limits"][k]], verdict


@pytest.mark.parametrize("rounding", ["residual_bfloat16",
                                      "activations_bfloat16"])
def test_a_rounding_of_the_reference_is_a_reading_and_no_control(
        hybrid_served, rounding):
    """``ROUNDINGS`` keep quantities of the f32 pass in bfloat16 to read
    the noise floor (chip_smoke leg H): they move the logits, by less
    than a gross fault does (this tiny model amplifies a rounding)."""
    from benchmark.reference import olmo_hybrid_jnp
    assert rounding in olmo_hybrid_jnp.ROUNDINGS
    assert not set(olmo_hybrid_jnp.ROUNDINGS) & set(olmo_hybrid_jnp.CONTROLS)
    worst = _verdict(hybrid_served, wrong=(rounding,))["worst"]
    assert 1e-4 < worst["logit_rel_l2"] < 0.3, worst
    with pytest.raises(ValueError, match="unknown"):
        _verdict(hybrid_served, wrong=("residual_float16",))


@pytest.mark.parametrize("seed", [0, 7, 3600000201])
def test_the_sample_always_holds_the_longest_first_request(seed):
    from benchmark import run as bench_run, traffic as traffic_mod
    from benchmark.builders import serve_hybrid
    resolved = bench_run.resolve_cell(
        bench_run.load_manifest(os.path.dirname(BENCH)), CELL)
    tr = resolved["traffic"]
    requests = traffic_mod.closed_loop_requests(
        tr, resolved["config"]["model"], tr["order_seed"], tr["clients"])
    sample = serve_hybrid.sample_of(requests, tr["clients"], 3, seed)
    assert len(set(sample.tolist())) == 3 and sample.max() < tr["clients"]
    assert tr["prompt"]["max"] in [requests[r].prompt.size for r in sample]


def test_needed_work_of_the_published_cut():
    cfg = _config(rehearse=False)
    m = dict(cfg["model"], layer_types=cfg["layer_types"])
    assert flops_gdn.layers_of(m, "linear_attention") == 6
    assert flops_gdn.layers_of(m, "full_attention") == 2
    # ISSUE 36's table: 215.6 M and 185.8 M a layer, 4.87 GB in bfloat16
    assert flops_gdn.layer_params(m, "linear_attention") == pytest.approx(
        215.6e6, rel=2e-3)
    assert flops_gdn.layer_params(m, "full_attention") == pytest.approx(
        185.8e6, rel=2e-3)
    assert flops_gdn.step_weight_bytes(m) == pytest.approx(
        2 * (1665.0e6 + 385.4e6), rel=2e-3)
    assert flops_gdn.kv_bytes_per_token(m) == 30720
    assert flops_gdn.matrix_state_bytes_per_row(m) == 6 * 30 * 192 * 96 * 4
    assert flops_gdn.state_bytes_per_row(m) == 6 * (30 * 192 * 96 * 4
                                                    + 11520 * 3 * 2)
    # brute force, one head and token of the recurrence
    dk, dv = 96, 192
    per_head = dk * dv + 3 * 2 * dk * dv
    assert flops_gdn.recurrent_flops_per_token(m) \
        == 6 * (30 * per_head + 2 * 4 * 11520)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    need = flops_gdn.chunked_needed_seconds(m, 1024, peaks)
    assert need == max(
        1024 * flops_gdn.chunked_flops_per_token(m) / 197e12,
        1024 * flops_gdn.chunked_bytes_per_token(m) / 819e9)
    assert 1e-5 < need < 1e-3


def _reader(name):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _record():
    cfg = _config(rehearse=False)
    cfg["model"] = {k: v for k, v in cfg.items()
                    if not isinstance(v, (dict, list))}
    # two requests of prompt 100; 1 prefill token and 4 decode tokens each
    req = np.array([0, 1, 0, 1, 0, 1, 0, 1, 0, 1])
    stamps = np.repeat(np.arange(5.0), 2) + np.tile([0.0, 1e-5], 5)
    return {
        "kind": "serve", "config": cfg,
        "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        "events": {"req": req, "stamps": stamps, "plen": np.array([100, 100]),
                   "t_start": 0.0, "t_end": 5.0},
        "trace": {"device_ops": [["gdn_decode", 0.004], ["fusion", 0.5]]},
        "tail": {"k0": 0, "k1": 10, "stats0": {}, "stats1": {}},
        "engine_stats": {"state_rows_launched": 640, "state_rows_live": 400},
    }


def test_the_two_readers_on_a_hand_made_record():
    run = _record()
    m = dict(run["config"]["model"], layer_types=run["config"]["layer_types"])
    state = flops_gdn.matrix_state_bytes_per_row(m)
    assert _reader("gdn_decode_roofline_pct")(run) == pytest.approx(
        100 * 2 * 8 * state / 819e9 / 0.004)
    assert _reader("state_read_amp")(run) == pytest.approx(1.6)


def test_the_readers_find_nothing_on_a_program_without_the_layer():
    """On the parent (no state counters, no ``gdn_*`` rows, another
    model's keys) every reader returns None and does not raise."""
    run = _record()
    run["trace"]["device_ops"] = [["fusion", 0.5]]
    run["engine_stats"] = {}
    for name in ("gdn_decode_roofline_pct", "state_read_amp"):
        assert _reader(name)(run) is None
    run["config"]["model"] = {"kv_lora_rank": 512}
    assert _reader("gdn_decode_roofline_pct")(run) is None
