"""The decode worker's in-flight ledger (ISSUE 38), on stubbed fetch
handles and a stubbed clock: device time by launch kind where the
launch clock is biased, which phases count as starved, and what a slow
phase leaves on record.  The worker thread never starts: the tests walk
``_launch()`` and the phase clock from their own thread, as the worker
would.  The tiling invariant on a live engine with chunks in its traffic
and the span attributes in the xplane are in tests/test_decode.py."""

import pytest

from paddle_tpu.models.bert import BertConfig
from paddle_tpu.models.decoder import BertDecoder
from paddle_tpu.observability import flight
from paddle_tpu.serving import DecodeConfig, DecodeEngine
from paddle_tpu.serving import decode as decode_mod
from paddle_tpu.serving.decode import (LAUNCH_KINDS, PHASES,
                                       SLOW_PHASE_FACTOR, STARVABLE)

MS = 1_000_000


class _Clock:
    """The engine's one clock, moved by the stubs alone."""

    def __init__(self):
        self.ns = 5_000 * MS

    def __call__(self):
        return self.ns


class _Handle:
    """A fetch handle whose execution completes at ``ready_at``."""

    def __init__(self, clock, ready_at):
        self._clock, self._ready_at = clock, ready_at
        self.blocked = 0

    def is_ready(self):
        return self._clock.ns >= self._ready_at

    def block_until_ready(self):
        self.blocked += 1
        self._clock.ns = max(self._clock.ns, self._ready_at)
        return self

    def numpy(self):
        self.block_until_ready()
        return [0]


class _Prepared:
    """A prepared step of one executable (``_cur``) on a device that
    runs its launches in order: the ``feed`` IS the launch's device
    milliseconds; ``run`` returns after ``dispatch_ms`` of host time."""

    def __init__(self, clock, dispatch_ms=0, device=None):
        self._clock, self._dispatch = clock, dispatch_ms * MS
        self._device = device if device is not None else self
        self._device_free = 0
        self._cur = self
        self.handles = []

    def run(self, feed):
        device = self._device
        start = max(self._clock.ns, device._device_free)
        self._device_free = device._device_free = start + feed * MS
        self._clock.ns += self._dispatch
        pair = [_Handle(self._clock, self._device_free) for _ in range(2)]
        self.handles.append(pair)
        return pair

    def sync_scope(self):
        pass


@pytest.fixture
def rig(monkeypatch):
    """An engine whose worker never runs, on a stubbed clock, its phase
    clock started as ``_worker_loop`` starts it."""
    cfg = BertConfig(vocab_size=512, hidden_size=64, num_hidden_layers=1,
                     num_attention_heads=2, intermediate_size=128,
                     max_position_embeddings=64, type_vocab_size=2)
    eng = DecodeEngine(BertDecoder(cfg, seed=3), DecodeConfig(
        block_size=4, max_seq_len=32, max_batch_size=4,
        prefill_seq_buckets=(8, 16), chunk_tokens=4), auto_start=False)
    clock = _Clock()
    monkeypatch.setattr(decode_mod, "_now_ns", clock)
    eng._switch("retire")
    yield eng, clock
    eng._switch(None)
    eng.shutdown()


def test_ledger_books_device_time_where_the_launch_clock_cannot(rig):
    """A non-final chunk finishes 30 ms after its dispatch and the chain
    behind it 10 ms later.  The launch clock books 0 / 40 ms (the bias,
    pinned: the chunk's time is waited out in the chain's sync); the
    ledger books 30 / 10 ms, in k + 1 = 2 blocking calls."""
    eng, clock = rig
    prepared = _Prepared(clock)
    out, launch_chunk, _ = eng._launch("chunk", prepared, 30, None)
    assert out is None and launch_chunk == 0
    assert [e[0] for e in eng._inflight] == ["chunk"]
    out, launch_chain, end_ns = eng._launch("chain", prepared, 10, 1)
    assert out == [0] and launch_chain == 40 * MS
    st = eng.stats()
    assert st["device_ns"] == {"prefill": 0, "chunk": 30 * MS,
                               "chain": 10 * MS}
    assert not eng._inflight and eng._device_free_ns == end_ns == clock.ns
    # the chunk was waited on once, on the handle the ledger kept; the
    # chain's own fetch is the only other blocking call
    (chunk_kept, chunk_other), (chain_kept, chain_fetched) = \
        prepared.handles
    assert (chunk_kept.blocked, chunk_other.blocked) == (1, 0)
    assert (chain_kept.blocked, chain_fetched.blocked) == (0, 1)
    assert st["phase_ns"]["sync"] == 40 * MS


def test_an_exposed_dispatch_is_starved_once_and_the_ledger_tiles(rig):
    """Dispatches of 2 ms: the chunk's finds the device free, so it is a
    starved phase and the chunk's device interval starts at its END; the
    chain's opens with the chunk in flight, so it is not starved and the
    chain's interval starts at the chunk's completion.  A ``stats()``
    between the two books nothing (the next sync does), and a round that
    leaves two chunks in flight and runs no chain is booked by the chain
    of the round after, in order."""
    eng, clock = rig
    prepared = _Prepared(clock, dispatch_ms=2)
    clock.ns += 3 * MS                  # 3 ms of retire, nothing in flight
    eng._launch("chunk", prepared, 30, None)
    mid = eng.stats()
    assert mid["device_ns"]["chunk"] == 0 and len(eng._inflight) == 1
    assert mid["starved_ns"]["dispatch"] == 2 * MS
    assert mid["starved_ns"]["retire"] == 3 * MS
    with eng._phase("feed"):            # the chain's feed: chunk in flight
        clock.ns += 4 * MS
    eng._launch("chain", prepared, 10, 0)
    st = eng.stats()
    # chunk: dispatched at 3..5 ms, ready at 3 + 30; from 5 to 33 = 28
    # chain: queued behind it, ready at 43; from 33 to 43 = 10
    assert st["device_ns"]["chunk"] == 28 * MS
    assert st["device_ns"]["chain"] == 10 * MS
    assert st["starved_ns"] == dict.fromkeys(STARVABLE, 0) | {
        "retire": 3 * MS, "dispatch": 2 * MS}
    total = sum(st["phase_ns"].values())
    assert total == 43 * MS
    assert sum(st["device_ns"].values()) + sum(st["starved_ns"].values()) \
        + st["phase_ns"]["idle"] == total
    # the ramp: two rounds of chunks alone, then a chain
    eng._launch("chunk", prepared, 20, None)
    eng._launch("chunk", prepared, 20, None)
    assert [e[0] for e in eng._inflight] == ["chunk", "chunk"]
    eng._launch("chain", prepared, 5, 0)
    st2 = eng.stats()
    assert not eng._inflight
    # first chunk: free device, from its dispatch's end: 20 - 2; second
    # from the first's completion: 20; the chain 5
    assert st2["device_ns"]["chunk"] - st["device_ns"]["chunk"] == 38 * MS
    assert st2["device_ns"]["chain"] - st["device_ns"]["chain"] == 5 * MS
    assert st2["starved_ns"]["dispatch"] == 4 * MS
    assert sum(st2["device_ns"].values()) \
        + sum(st2["starved_ns"].values()) + st2["phase_ns"]["idle"] \
        == sum(st2["phase_ns"].values())


def test_a_phase_opened_with_a_chunk_in_flight_is_not_starved(rig):
    eng, clock = rig
    prepared = _Prepared(clock)
    for name in ("admit", "feed", "emit"):
        with eng._phase(name):
            clock.ns += 7 * MS
    free = eng.stats()["starved_ns"]
    assert free == {"admit": 7 * MS, "feed": 7 * MS, "dispatch": 0,
                    "emit": 7 * MS, "retire": 0}
    eng._launch("chunk", prepared, 500, None)
    for name in ("admit", "feed", "emit", "retire"):
        with eng._phase(name):
            clock.ns += 7 * MS
    with eng._phase("idle"):            # the traffic's, never the host's
        clock.ns += 7 * MS
    assert eng.stats()["starved_ns"] == free
    eng._launch("chain", prepared, 1, 0)
    with eng._phase("emit"):
        clock.ns += 2 * MS
    st = eng.stats()
    assert st["starved_ns"]["emit"] == 9 * MS
    assert st["phase_ns"]["idle"] == 7 * MS
    # the open phase (retire, starved) counts as far as it has come
    clock.ns += 11 * MS
    assert eng.stats()["starved_ns"]["retire"] == 11 * MS


def test_a_slow_sync_is_on_record_with_its_context(rig):
    """A phase is slow against the launch itself.  Chains of one
    executable usually hold the device 50 ms: a sync of 150 ms leaves
    nothing, one of 600 ms lands in ``slow_phase_ns``, the ring and the
    flight recorder with what the worker was launching.  The launch
    whose dispatch bound the executable (the compile) is neither judged
    nor learnt from, and a long ``idle`` is never slow."""
    eng, clock = rig
    prepared = _Prepared(clock, dispatch_ms=0)
    flight.reset()
    eng._launching = ("chain", 3, 8, 41)
    clock.ns += 20_000 * MS             # "compiling" inside the dispatch
    eng._launch("chain", prepared, 5_000, 0)
    assert eng._usual_ns[prepared] == 0 and eng._yardstick_ns == 0
    eng._launch("chain", prepared, 50, 0)
    assert eng._usual_ns[prepared] == 50 * MS
    eng._launch("chain", prepared, 50, 0)
    eng._launch("chain", prepared, 150, 0)
    with eng._phase("idle"):
        clock.ns += 100_000 * MS
    st = eng.stats()
    assert not any(st["slow_phase_ns"].values()) and st["slow_phases"] == []
    # a running mean: 50 + (150 - 50) / 8
    assert eng._usual_ns[prepared] == 62_500_000
    t0 = clock.ns
    eng._launch("chain", prepared, 600, 0)
    eng._launching = None
    st = eng.stats()
    assert st["slow_phase_ns"] == dict.fromkeys(PHASES[1:], 0) | {
        "sync": 600 * MS}
    assert st["slow_phases"] == [
        ["sync", "chain", t0, 600 * MS, 3, 8, eng._blocks_in_use(), 41]]
    events = [e for e in flight.steps_snapshot()
              if e[1] == "decode_slow_phase"]
    assert len(events) == 1
    assert events[0][2] == {"phase": "sync", "launch": "chain",
                            "start_ns": t0, "dur_ns": 600 * MS, "rows": 3,
                            "size": 8, "blocks": eng._blocks_in_use(),
                            "rid": 41}
    # a host phase is measured against the launch last waited on; between
    # launches the row names no launch and counts what is live; the ring
    # keeps the last 32
    assert eng._yardstick_ns == 62_500_000
    with eng._phase("emit"):
        clock.ns += SLOW_PHASE_FACTOR * 62_500_000 - 1
    assert eng.stats()["slow_phase_ns"]["emit"] == 0
    for _ in range(40):
        with eng._phase("emit"):
            clock.ns += SLOW_PHASE_FACTOR * 62_500_000
    st = eng.stats()
    assert st["slow_phase_ns"]["emit"] == 40 * SLOW_PHASE_FACTOR * 62_500_000
    assert len(st["slow_phases"]) == 32
    assert st["slow_phases"][-1][:2] == ["emit", None]
    assert st["slow_phases"][-1][4:] == [0, None, eng._blocks_in_use(), None]


def test_a_sync_is_measured_against_every_launch_it_waits_on(rig):
    """Two chunks of 30 ms in flight before a chain of 10 ms: the
    chain's sync waits out 70 ms, seven times the chain's own usual time
    and once the usual time of the three launches together — not slow.
    The same sync at four times that is; a sync that waits on a launch
    with no usual time yet is not judged at all."""
    eng, clock = rig
    chunk = _Prepared(clock)
    chain = _Prepared(clock, device=chunk)
    for _ in range(3):                  # bind, then learn 30 and 10 ms
        eng._launch("chunk", chunk, 30, 0)
        eng._launch("chain", chain, 10, 0)
    assert (eng._usual_ns[chunk], eng._usual_ns[chain]) == (30 * MS, 10 * MS)
    eng._launch("chunk", chunk, 30, None)
    eng._launch("chunk", chunk, 30, None)
    eng._launch("chain", chain, 10, 0)
    assert eng._yardstick_ns == 70 * MS
    assert eng.stats()["slow_phases"] == []
    eng._launch("chunk", chunk, 30, None)
    eng._launch("chunk", chunk, 30, None)
    eng._launch("chain", chain, 220, 0)    # the sync: 280 ms
    (row,) = eng.stats()["slow_phases"]
    assert row[0] == "sync" and row[3] == 280 * MS
    fresh = _Prepared(clock, device=chunk)
    eng._launch("chunk", fresh, 5_000, None)
    eng._launch("chain", chain, 10, 0)
    assert eng._yardstick_ns == 0 and len(eng.stats()["slow_phases"]) == 1


def test_new_stats_values_are_what_the_builders_delta_differences(rig):
    """``benchmark/builders/serve.py::_delta`` differences a flat dict of
    numbers key by key and keeps the later value of anything that is not
    a number or a dict: every new ``stats()`` value is one or the other,
    and the whole of ``stats()`` still goes through ``json.dumps``."""
    import json
    eng, clock = rig
    prepared = _Prepared(clock)
    for ms in (30, 30, 30):
        eng._launch("chain", prepared, ms, 0)
    eng._launch("chunk", prepared, 30, None)
    eng._launch("chain", prepared, 600, 0)
    st = eng.stats()
    dicts = {"device_ns": LAUNCH_KINDS, "starved_ns": STARVABLE,
             "slow_phase_ns": PHASES[1:]}
    for key, keys in dicts.items():
        assert tuple(st[key]) == keys, key
        assert all(type(v) is int for v in st[key].values()), key
    assert isinstance(st["slow_phases"], list) and len(st["slow_phases"]) == 1
    assert all(isinstance(r, list) and len(r) == 8
               for r in st["slow_phases"])
    json.dumps(st)
    # a snapshot is a copy: the worker's next booking does not reach it
    chain_ns = st["device_ns"]["chain"]
    eng._launch("chain", prepared, 6_000, 0)
    assert st["device_ns"]["chain"] == chain_ns
    assert len(st["slow_phases"]) == 1
    assert len(eng.stats()["slow_phases"]) == 2


def test_the_probe_reads_device_time_by_kind_without_the_ledger():
    """``tools/ledger_probe.py::device_against_spans`` on a made-up
    xplane: each of the device's executables takes the next dispatch
    that opened before it started, the launch span around that dispatch
    names its kind, and the device's idle between executables falls to
    the worker's phases by overlap, split by their ``starved``."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    from ledger_probe import device_against_spans
    spans = [(name, a * MS, b * MS, {"starved": starved})
             for name, a, b, starved in (
                 ("feed", 0, 1, "1"), ("dispatch", 1, 3, "1"),
                 ("feed", 10, 11, "0"), ("dispatch", 11, 12, "0"),
                 ("sync", 12, 95, "0"), ("emit", 95, 100, "1"),
                 ("feed", 100, 101, "1"), ("dispatch", 101, 103, "1"),
                 ("sync", 103, 160, "0"))]
    spans += [("chunk", 0, 10 * MS, {}), ("chain", 10 * MS, 100 * MS, {}),
              ("chain", 100 * MS, 160 * MS, {})]
    modules = [(-5 * MS, 0, "jit_step(0)"),     # dispatched before the trace
               (2 * MS, 40 * MS, "jit_step(1)"),
               (40 * MS, 90 * MS, "jit_step(2)"),
               (102 * MS, 150 * MS, "jit_step(2)")]
    out = device_against_spans(modules, spans)
    assert out["modules_unmatched"] == 1
    by_kind = {k: (v["launches"], round(v["seconds"] * 1e3))
               for k, v in out["device_s_by_kind"].items()}
    assert by_kind == {"chunk": (1, 38), "chain": (2, 98)}
    assert {k: v["launches"] for k, v in out["device_s_by_module"].items()} \
        == {"jit_step(0)": 1, "jit_step(1)": 1, "jit_step(2)": 2}
    idle = {k: round(v * 1e3) for k, v in
            out["device_idle_s_by_phase"].items()}
    # 0..2 ms: the first feed and half its dispatch; 90..102 ms: the end
    # of the sync (the worker's wake-up), the emit, the next feed and
    # dispatch up to the device's start
    assert idle == {"feed.starved=1": 2, "dispatch.starved=1": 2,
                    "sync.starved=0": 5, "emit.starved=1": 5}
    assert round(out["device_idle_between_modules_s"] * 1e3) == 14


def test_a_launch_found_complete_teaches_nothing_and_a_blocked_dispatch_is_not_slow(rig):
    """The ramp: chunks pile up with no chain between them, and the
    prepared step's own window makes each further dispatch wait for the
    oldest (here: two in flight).  Such a dispatch lasts one chunk — not
    slow — and at the sync the chunks but the last are complete already:
    they are booked to ``device_ns`` but their lengths (the first gets
    all the time, the others none) do not reach the running mean."""
    eng, clock = rig

    class _Windowed(_Prepared):
        def run(self, feed):
            if len(self.handles) >= 2:      # wait for the oldest but one
                self.handles[-2][0].block_until_ready()
            return super().run(feed)

    chunk = _Windowed(clock)
    chain = _Prepared(clock, device=chunk)
    for _ in range(3):
        eng._launch("chunk", chunk, 80, 0)
        eng._launch("chain", chain, 10, 0)
    assert eng._usual_ns[chunk] == 80 * MS
    booked = eng.stats()["device_ns"]["chunk"]
    for _ in range(8):                      # dispatches of 0, 0, 80, 80...
        eng._launch("chunk", chunk, 80, None)
    eng._launch("chain", chain, 10, 0)
    st = eng.stats()
    assert st["phase_ns"]["dispatch"] == 6 * 80 * MS
    assert st["slow_phases"] == []
    assert st["device_ns"]["chunk"] - booked == 8 * 80 * MS
    # the seventh chunk was complete when the sync looked, the eighth
    # and the chain were seen to complete
    assert eng._usual_ns[chunk] == 80 * MS
    assert eng._usual_ns[chain] == 10 * MS
