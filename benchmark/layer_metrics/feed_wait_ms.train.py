"""Input feed: host time a step waited for its batch, ms per step.
``PreparedStep.stats["feed_wait_ns"]`` (the program's counter, which
covers reader-bound programs) plus the benchmark's own span around
``next(loader)`` (which covers ``DataLoader.from_generator``)."""


def read(run):
    if run["kind"] != "train" or not run["steps"]:
        return None
    ns = run["prepared_stats"].get("feed_wait_ns", 0) + run["feed_wait_ns"]
    return ns / 1e6 / run["steps"]
