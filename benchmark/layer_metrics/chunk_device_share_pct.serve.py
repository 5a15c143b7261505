"""Decode scheduler: the share of the window's device time (the
engine's in-flight ledger, ``stats()["device_ns"]`` by launch kind) that
went to prompts — packed prefill batches and prefill chunks — rather
than decode chains.  ``prefill_share_pct.serve`` reads the launch clock
instead, which books a non-final chunk's device time under ``chain``.
The ledger's completions are stamps of the HOST (a blocking call's
return), so each kind's seconds are an upper bound on the device's own
record and hold the worker's wake-up after the launch: 0.7-1.9 % over
for chunks and for ``reason_closed``'s chains, 7 % over for
``chat_closed``'s short chains (PERF.md section 6, PR 38)."""


def read(run):
    device = (run.get("engine_stats") or {}).get("device_ns")
    if not device:
        return None
    total = sum(device.values())
    if total <= 0:
        return None
    return 100.0 * (device.get("prefill", 0) + device.get("chunk", 0)) / total
