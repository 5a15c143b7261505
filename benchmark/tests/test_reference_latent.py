"""The plain jnp reference of the latent-attention decoder
(benchmark/reference/deepseek_v3_jnp.py) against the program at the
``deepseek_v3_decode.reason_closed`` cell's rehearsal size, on the CPU in
float32, through the cell's own builder functions; and each way the
reference can be computed WRONG (its ``CONTROLS``) refused by at least one
of the cell's limits."""

import numpy as np
import pytest


# ---------------------------------------------------------------------------
# the latent-attention decoder (deepseek_v3_jnp) and its controls
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def latent_served():
    """The serve_lm cell's rehearsal engine, three requests served
    through the latent cache with their logits, and the weights."""
    import os
    from benchmark import run as bench_run
    from benchmark.builders import serve_lm
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    resolved = bench_run.resolve_cell(bench_run.load_manifest(root),
                                      "deepseek_v3_decode.reason_closed")
    bench_run.apply_rehearsal(resolved["config"], resolved["traffic"])
    config = resolved["config"]
    engine = serve_lm.build_engine(config, seed=11)
    engine.start()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, config["model"]["vocab_size"], n)
               for n in (21, 40, 57)]
    results = [f.result(timeout=300) for f in [
        engine.generate({"src_ids": p}, max_new_tokens=24,
                        return_logits=True) for p in prompts]]
    weights = serve_lm.close_and_take_weights(engine)
    return config, weights, prompts, results


def _latent_verdict(latent_served, wrong=()):
    from benchmark.builders import serve_lm
    config, weights, prompts, results = latent_served
    readings = [serve_lm.compare(
        config["reference"], serve_lm.reference_model(config),
        tuple(config["deployment"]["held_experts"]), weights, p, r.tokens,
        r.logits, wrong=wrong) for p, r in zip(prompts, results)]
    return serve_lm.judge(config["reference"], readings)


def test_latent_decoder_served_through_the_cache_matches_the_reference(
        latent_served):
    verdict = _latent_verdict(latent_served)
    assert verdict["ok"], verdict


def _controls():
    from benchmark.reference import deepseek_v3_jnp
    return deepseek_v3_jnp.CONTROLS


@pytest.mark.parametrize("control", _controls())
def test_latent_reference_computed_wrong_is_refused(latent_served, control):
    """Each way the forward pass can be wrong (a term of the score, a
    factor of the router, an expert, the cache's precision, a block of
    the context) is refused by at least one of the cell's limits."""
    verdict = _latent_verdict(latent_served, wrong=(control,))
    assert not verdict["ok"], (control, verdict)
    over = [k for k, v in verdict["worst"].items()
            if v > verdict["limits"][k]]
    assert over, verdict


# ---------------------------------------------------------------------------
# the cell's schedule: the file's order, the seed's ids, a window opened on
# a token count
# ---------------------------------------------------------------------------

def _cell_traffic():
    import os
    from benchmark import run as bench_run
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    resolved = bench_run.resolve_cell(bench_run.load_manifest(root),
                                      "deepseek_v3_decode.reason_closed")
    return resolved["traffic"], resolved["config"]["model"]


def test_every_seed_offers_the_files_order_with_its_own_ids():
    from benchmark import traffic as T
    from benchmark.builders import serve_lm
    tr, m = _cell_traffic()
    tr = dict(tr, max_requests=300)
    a, b = (serve_lm.requests_for(tr, m, s) for s in (7, 2 ** 31 + 5))
    lengths = [(r.prompt.size, r.max_new) for r in a]
    assert lengths == [(r.prompt.size, r.max_new) for r in b]
    assert lengths == [(r.prompt.size, r.max_new) for r in
                       T.closed_loop_requests(tr, m, tr["order_seed"], 300)]
    assert sorted(lengths[:256]) == T.closed_loop_multiset(tr)
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, b))
    assert all(r.prompt.max() < m["vocab_size"] for r in a)


def test_the_window_opens_after_the_sync_that_brought_the_count():
    import threading
    import time
    from benchmark.builders import serve_lm

    class Load:
        k = 0

    load = Load()

    def worker():       # three syncs of 40 tokens, 30 ms apart
        for _ in range(3):
            time.sleep(0.03)
            for _ in range(40):
                load.k += 1
                time.sleep(0.0001)

    t = threading.Thread(target=worker)
    t.start()
    serve_lm.wait_for_tokens(load, 50, time.monotonic() + 5.0)
    seen = load.k
    t.join()
    assert seen == 80          # the second sync whole, none of the third
    with pytest.raises(SystemExit):
        serve_lm.wait_for_tokens(load, 10 ** 9, time.monotonic() + 0.05)
