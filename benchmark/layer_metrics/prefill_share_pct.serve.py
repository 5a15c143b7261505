"""Decode scheduler: the share of the window's launch time (dispatch +
sync, ``stats()["launch_ns"]``) that went to prompts — packed prefill
batches and prefill chunks — rather than decode chains."""


def read(run):
    launch = (run.get("engine_stats") or {}).get("launch_ns")
    if not launch:
        return None
    total = sum(launch.values())
    if total <= 0:
        return None
    return 100.0 * (launch.get("prefill", 0) + launch.get("chunk", 0)) / total
