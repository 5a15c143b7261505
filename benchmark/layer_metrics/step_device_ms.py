"""Compiled step: device busy time in the traced tail per step, device
0, ms."""


def read(run):
    tr = run.get("trace")
    if run["kind"] != "train" or not tr or not run.get("traced_steps"):
        return None
    return 1e3 * tr["device0_busy_s"] / run["traced_steps"]
