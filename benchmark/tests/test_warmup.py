"""The serving builder's warm-up groups, derived from the engine's buckets
and the traffic's length ranges."""

import json
import os

from benchmark.builders import serve
from benchmark.run import apply_rehearsal, model_keys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def stages(rehearse):
    from paddle_tpu.serving import DecodeConfig
    with open(os.path.join(BENCH, "configs", "bert_base_decoder.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", "chat_closed_c128.json")) as f:
        traffic = json.load(f)
    if rehearse:
        apply_rehearsal(config, traffic)
    kw = {k: tuple(v) if isinstance(v, list) else v
          for k, v in config["engine"].items()}
    out = serve._warmup_stages(DecodeConfig(**kw), traffic,
                               model_keys(config), seed=3000000019)
    return [[(r.prompt.size, r.max_new) for r in st] for st in out]


def test_full_size_groups_are_the_ones_measured_on_the_chip():
    """Round one of PR 24 listed these 19 groups by hand in the traffic
    file and measured with them (12 runs, no compilation in a window);
    the derivation has to give the same requests."""
    got = stages(rehearse=False)
    want = [[(100, 12)] * 127 + [(257, 1)]]
    want += [[(sb, 1)] * bb for sb in (32, 64, 128)
             for bb in (1, 2, 4, 8, 16, 32)]
    assert got == want


def test_a_small_loop_warms_every_decode_bucket_it_can_fall_to():
    # 8 clients, chains of 4, outputs from 4 tokens: the whole batch can
    # leave inside one chain, so every batch bucket is reachable
    got = stages(rehearse=True)
    want = [[(25, 8)] * n + [(65, 1)] for n in (7, 4, 2, 1)]
    want += [[(sb, 1)] * bb for sb in (16, 32) for bb in (1, 2, 4, 8)]
    assert got == want
