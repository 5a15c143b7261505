"""Decode scheduler: mean live rows of a decode chain over the window,
from ``stats()["decode_batch_hist"]`` ({live rows: chains})."""


def read(run):
    hist = (run.get("engine_stats") or {}).get("decode_batch_hist")
    if not hist:
        return None
    chains = sum(hist.values())
    if chains <= 0:
        return None
    return sum(int(k) * v for k, v in hist.items()) / chains
