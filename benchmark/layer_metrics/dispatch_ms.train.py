"""Executor host path: ``PreparedStep.stats["dispatch_ns"]`` per step,
ms — host time inside the compiled step's call."""


def read(run):
    if run["kind"] != "train" or not run["steps"]:
        return None
    return run["prepared_stats"]["dispatch_ns"] / 1e6 / run["steps"]
