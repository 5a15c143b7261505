"""Autoregressive decode engine: paged KV-cache + continuous token-level
batching + prefill/decode split executables.

The reference's generation story is ops inside one scoring program
(`beam_search`, `sampling_id`, the `sequence_*` family) served by
re-running the WHOLE prefix through AnalysisPredictor per emitted token
— O(prefix) recompute per token, one request at a time.  TPU-natively,
generation throughput is won on cache residency and batch occupancy,
so the decode runtime composes every serving substrate piece built so
far:

* **paged/block KV-cache** — one preallocated pool of fixed-size blocks
  per layer per K/V (``[num_blocks, block_size, hidden]`` persistables);
  sequences own i32 block tables, attention reads THROUGH the table
  (``fused_attention``'s cache variant: on TPU a decode step's
  one-token query reads the pools in place, page by page as far as each
  row's live context — the ``paged_decode_attention`` Pallas routes, a
  body for heads that share a lane tile and one for heads of whole
  tiles — and a chunk's longer query gathers the table for the
  ``cached_flash_attention`` route; gather-based on CPU), and
  ``cache_write`` appends via host-computed flat slot ids.  The pool is
  sized ONCE at engine start by the PR 5 static analyzer
  (``memory_analysis.plan_cache_pool``) and admission prices
  :func:`blocks_needed` per request BEFORE any compile — the
  ``ServingFleet`` HBM-admission idea generalized from "one more bucket
  executable" to "one more cache block";
* **continuous batching at token granularity** — the worker runs a
  scheduling round per decode step: finished sequences retire and free
  their blocks IMMEDIATELY, waiting prefills slot in the same round,
  and the decode step batches every live sequence into the next batch
  bucket.  Prefill rides the PR 7 ragged segment-packing recipe
  (several prompts share a row, one-hot mask channels make the
  attention bias block-diagonal; causal masking composes per segment);
* **prefill/decode split executables** — one bucketed prefill grid
  (batch x seq buckets: writes cache blocks, emits each segment's first
  token) and one fixed-shape decode-step executable per batch bucket
  (reads the cache, appends one token), all resolved through the
  persistent AOT cache (``flag("aot_cache_dir")``): a warm restart
  deserializes the whole grid with 0 fresh compiles;
* **bit-parity contract** — generated TOKENS are the output, and every
  sequence must match its unbatched greedy reference token-for-token
  (:meth:`DecodeEngine.greedy_reference` — the reference-shaped
  full-prefix loop on an isolated weight snapshot) no matter how it was
  co-batched, delayed behind a full pool, or placed into reused blocks.
  Masked cache reads contribute EXACT zeros (cache_ops.ctx_len_bias),
  so neither co-residents nor block leftovers can perturb a row.

**Decode fast path v2** layers three throughput levers on top:

* **device-chained decode** — the decode step lowers into a
  ``chain_length``-step ``lax.scan`` (the ``decode_chain`` marker op,
  ``executor.lower_decode_chain``): next-token feedback, cache writes,
  block-table walking and per-row EOS/length masks all stay on device,
  and the host fetches ONE packed ``[chain, B]`` token matrix per chain
  instead of one token per step.  The scheduler picks the chain length
  per round: a short chain when admittable work is waiting (so new
  requests don't sit behind a long chain), the smallest chain covering
  the longest remaining budget otherwise.  Greedy rows ride the body's
  own argmax, so chained output is bit-identical to single-stepping;
  sampling rows (``DecodeConfig(sampling=True)``) draw on device with
  per-request folded keys (ops/sampling_ops.py) and are deterministic
  under a fixed seed;
* **cross-request prefix caching** — completed prefills PROMOTE their
  full prompt blocks into a content-hash index over the same pool
  (key = model/layout identity + the exact token prefix the block
  closes).  A new request charges admission only for its non-shared
  suffix, reuses the hit blocks by reference, and prefills only the
  suffix tokens; refcount-0 index blocks are evictable LRU-first, and
  eviction can never free a block a live sequence references;
* **chunked prefill** — suffix (and, with ``chunk_tokens`` set, long)
  prompts prefill in fixed-width chunks through a cache-READING
  prefill program (absolute positions feed the per-query causal bound,
  ``QPos``), one chunk per scheduling round, so a long prompt
  interleaves with live decode chains instead of head-of-line blocking
  them.  Only the final chunk syncs to the host.

**The worker's timeline** is legible from inside the program.  Every
instant of the worker thread falls in exactly one of :data:`PHASES`:
``idle`` (the condition wait with nothing to do), ``admit``, ``feed``
(host arrays for the next launch), ``dispatch`` (owner handoff +
``prepared.run`` until it returns), ``sync`` (the blocking
``.numpy()``), ``emit`` (token loops, ``on_token`` callbacks, prefix
promotion) and ``retire`` (``_retire()`` and whatever bookkeeping is
left between the others).  Each boundary is one clock read added into
``stats()["phase_ns"]`` (always on), and a ``decode::<phase>`` span
(on whenever the profiler or any ``jax.profiler`` session is) whose
parent is the launch's ``decode::prefill|chunk|chain`` span.
``stats()["launch_ns"]``/``["launches"]`` split the worker's dispatch +
sync time by the kind of the launch that paid it.  That is NOT device
time by kind: a non-final chunk is dispatched and never synced, so its
device time is waited out in the next chain's ``sync`` and lands under
``chain``.  Device time by kind is the **in-flight ledger**'s
(``stats()["device_ns"]``): every launch
dispatched and not yet known complete is on it; a ``sync`` waits on the
launches before its own in order and stamps each completion, and launch
*i* held the device from the later of launch *i-1*'s completion and its
own dispatch to its own completion.  A host phase (``admit``, ``feed``,
``dispatch``, ``emit``, ``retire``) that OPENS with the ledger empty has
the device waiting on the host, and its length is also in
``stats()["starved_ns"][phase]``, so that ``sum(device_ns) +
sum(starved_ns) + phase_ns["idle"] == sum(phase_ns)`` whenever the
ledger is empty: the ledger tiles the worker's wall time as the phases
do (a launch whose dispatch found the device free starts at that
dispatch's END, so an exposed dispatch counts once, as starved).  A
launch is booked when some ``sync`` learns of its completion, never
earlier: a ``stats()`` read of the device counters, or a round that
leaves chunks in flight and runs no chain, may find the device finished
without the worker knowing, and the next ``sync`` books those launches up
to the instant it sees them complete.  Completions are stamps of the
HOST, taken when a blocking call returns, so a launch's device time
includes the worker's wake-up after it: an upper bound on what the
device's own record gives.  The ledger also keeps a running mean of
each executable's device time, and one compare at every boundary
catches a **slow phase**: a non-idle interval of
:data:`SLOW_PHASE_FACTOR` times what the device usually takes over the
launches the phase waited on (``sync``) or the worker last dispatched
(a host phase) — a stall measured against the launch itself, whatever
the model's size (``stats()["slow_phase_ns"]`` by phase,
``["slow_phases"]`` the last 32 with what the worker was running, and a
``decode_slow_phase`` flight event).  A dispatch that bound a new
executable (a compile or a cache load) is not judged, and its launch
is left out of the mean, as is a launch that was complete before the
worker waited on it.  Every ``decode::<phase>`` span carries
``starved=0|1``.  ``["kv_pages_read"]`` over
``["kv_pages_spanned"]`` is
the share of the block tables the decode steps' cache reads touch (per
step and row ``ceil(ctx / block_size)`` pages of ``max_blocks_per_seq``,
counted on the host from the rows' positions); counters a model's
programs keep on the device (``program._device_counters``, e.g. a
sparse decoder's ``moe_assignments_local`` / ``moe_experts_hit``) appear
as ``stats()[key][kind]`` per launch kind; and every request
carries ``rid`` and its submit/admit/first-token/done stamps
(``GenerationResult.timing``,
``stats()["queue_wait_ns"]``/``["first_token_ns"]``).

**Recurrent state** is the second kind of per-sequence cache.  A model
whose layers carry a state from token to token (a linear-attention
layer's ``[heads, d_k, d_v]`` matrix and the tail of its short
convolution, ops/linear_attn_ops.py) declares ``state_bytes_per_slot()``
and gets, beside the block pools and owned by the same manager, a
**state pool**: one slot a batch row (``max_batch_size``) and one
scratch slot more.  A sequence takes ONE slot at admission together with
its blocks, keeps it through its chunks and chains, and gives it back
with its blocks at retire.  A slot is free whenever a batch row is, so
admission waits on blocks alone (``admission_waits``) until snapshots or
preemption let a slot outlive its row.  Every launch feeds a per-row
``state_slot``; rows of a bucket that hold no sequence name the scratch
slot, so they never write a live one.  A sequence's first launch carries
``state_fresh`` (the packed prefill, whose rows then hold one segment
each, starts every row fresh): the state starts from zero on the device,
no host-side clear.  The chain's scan carries the state pools as it
carries the KV pools.  The prefix index matches KV blocks by content; a
hit without the recurrent state at that boundary would be wrong output,
so ``prefix_cache=True`` is REFUSED for such a model until state
snapshots exist.

Static safety: ``analysis.verify_decode`` checks every program at
engine start — no collectives, no persistable writes outside the
declared cache pool, and the ``decode_chain`` marker (when present)
unique and last.  Failure containment: the ``serving_decode``
faultline seam drills the fatal path (all in-flight generation futures
fail with the error, blocks free, the engine goes unhealthy, ``drain``
cannot hang).
"""

from __future__ import annotations

import threading
import time
import weakref
from collections import deque
from concurrent.futures import Future
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..framework.errors import InvalidArgumentError, UnavailableError
from ..observability import flight as _flight
from ..observability import watchdog as _watchdog
from ..observability.tracing import next_step_id, step_scope
from ..profiler import RecordEvent, register_serving_engine
from ..testing import faultline as _faultline
from ..testing.faultline import _ARMED as _FL_ARMED
from .engine import _plan_bins


#: the worker's phases: every instant of its wall time is in exactly one
PHASES = ("idle", "admit", "feed", "dispatch", "sync", "emit", "retire")
#: the executables a launch (dispatch + sync) can be of
LAUNCH_KINDS = ("prefill", "chunk", "chain")
#: the phases in which the device waits on the HOST when nothing is in
#: flight (``sync`` waits on the device, ``idle`` on the traffic)
STARVABLE = ("admit", "feed", "dispatch", "emit", "retire")
#: a non-idle phase interval is a stall at this many times the usual
#: device time of the launches it waited on or was last dispatching (a
#: healthy ``sync`` is about once that, a host phase a small part of it)
SLOW_PHASE_FACTOR = 4

#: the engine's one clock: request stamps, phase boundaries and the
#: load generator's own ``time.monotonic()`` stamps are comparable
_now_ns = time.monotonic_ns


def blocks_needed(prompt_len: int, max_new_tokens: int,
                  block_size: int) -> int:
    """Cache blocks one sequence needs END-TO-END (prompt + every token
    it may generate) — the admission unit.  Reserved in full at admit
    time, so a mid-generation sequence can never stall on an empty
    pool."""
    total = int(prompt_len) + int(max_new_tokens)
    return -(-total // int(block_size))


def _pow2_buckets(n: int) -> Tuple[int, ...]:
    out, b = [], 1
    while b < n:
        out.append(b)
        b *= 2
    out.append(int(n))
    return tuple(out)


class DecodeConfig:
    """Decode-engine knobs.

    ``pool_blocks=None`` sizes the pool from ``hbm_budget_gb`` (config
    value, else the flag) through the static analyzer; with no budget
    either, the pool defaults to full occupancy
    (``max_batch_size * max_blocks_per_seq``)."""

    def __init__(self, block_size: int = 8,
                 max_seq_len: int = 64,
                 max_batch_size: int = 8,
                 batch_buckets: Optional[Sequence[int]] = None,
                 prefill_seq_buckets: Sequence[int] = (16, 32, 64),
                 prefill_batch_buckets: Optional[Sequence[int]] = None,
                 pack_max_segments: int = 4,
                 pool_blocks: Optional[int] = None,
                 max_new_tokens: int = 16,
                 eos_token_id: Optional[int] = None,
                 hbm_budget_gb: Optional[float] = None,
                 chain_lengths: Sequence[int] = (1, 4),
                 prefix_cache: bool = True,
                 chunk_tokens: Optional[int] = None,
                 sampling: bool = False,
                 prefix_reserve_blocks: int = 0):
        if block_size < 1:
            raise InvalidArgumentError("block_size must be >= 1")
        if max_batch_size < 1:
            raise InvalidArgumentError("max_batch_size must be >= 1")
        self.block_size = int(block_size)
        self.max_seq_len = int(max_seq_len)
        self.max_batch_size = int(max_batch_size)
        self.batch_buckets = tuple(sorted(
            int(b) for b in (batch_buckets or
                             _pow2_buckets(self.max_batch_size))))
        if self.batch_buckets[-1] < self.max_batch_size:
            raise InvalidArgumentError(
                f"batch_buckets {list(self.batch_buckets)} must cover "
                f"max_batch_size={self.max_batch_size}")
        self.prefill_seq_buckets = tuple(sorted(
            int(s) for s in prefill_seq_buckets))
        if not self.prefill_seq_buckets:
            raise InvalidArgumentError(
                "prefill_seq_buckets must name at least one bucket")
        self.prefill_batch_buckets = tuple(sorted(
            int(b) for b in (prefill_batch_buckets or
                             _pow2_buckets(self.max_batch_size))))
        self.pack_max_segments = int(pack_max_segments)
        if self.pack_max_segments < 1:
            raise InvalidArgumentError("pack_max_segments must be >= 1")
        self.pool_blocks = pool_blocks
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token_id = eos_token_id
        self.hbm_budget_gb = hbm_budget_gb
        self.chain_lengths = tuple(sorted(
            {int(v) for v in chain_lengths}))
        if not self.chain_lengths or self.chain_lengths[0] < 1:
            raise InvalidArgumentError(
                f"chain_lengths {list(chain_lengths)} must name at "
                f"least one length >= 1")
        self.prefix_cache = bool(prefix_cache)
        self.chunk_tokens = int(chunk_tokens) if chunk_tokens else None
        if self.chunk_tokens is not None and self.chunk_tokens < 1:
            raise InvalidArgumentError("chunk_tokens must be >= 1")
        self.sampling = bool(sampling)
        self.prefix_reserve_blocks = int(prefix_reserve_blocks)
        if self.prefix_reserve_blocks < 0:
            raise InvalidArgumentError(
                "prefix_reserve_blocks must be >= 0")

    @property
    def max_blocks_per_seq(self) -> int:
        return -(-self.max_seq_len // self.block_size)

    @property
    def chunk_width(self) -> int:
        """Token width of one prefill chunk (the chunked-prefill
        executable's fixed [1, C] shape)."""
        return int(self.chunk_tokens or self.prefill_seq_buckets[-1])

    @property
    def executable_grid(self) -> int:
        """Executable count a fully-warm engine holds: the prefill
        (batch x seq) grid, one chained decode step per (chain length x
        batch bucket), and the chunked-prefill program when the prefix
        cache or chunking is on."""
        n = (len(self.prefill_batch_buckets) *
             len(self.prefill_seq_buckets) +
             len(self.chain_lengths) * len(self.batch_buckets))
        if self.prefix_cache or self.chunk_tokens:
            n += 1
        return n


_ROW_OF = None


def _logits_row(logits, i: int):
    """Row ``i`` of ``[..., B, vocab]`` logits as a device array: ONE
    compiled slice whatever ``i`` is, dispatched and not waited for."""
    global _ROW_OF
    if _ROW_OF is None:
        import jax
        _ROW_OF = jax.jit(lambda x, i: jax.lax.dynamic_index_in_dim(
            x, i, x.ndim - 2, keepdims=False))
    return _ROW_OF(logits, np.int32(i))


class GenerationResult:
    """What a generation future resolves to."""

    __slots__ = ("tokens", "prompt_len", "finish_reason", "steps",
                 "timing", "_logit_parts", "_logits")

    def __init__(self, tokens, prompt_len, finish_reason, steps,
                 timing=None, logit_parts=None):
        self.tokens = np.asarray(tokens, dtype=np.int64)
        self.prompt_len = int(prompt_len)
        self.finish_reason = finish_reason      # "length" | "eos"
        self.steps = int(steps)                 # decode steps it rode
        #: {"rid", "submit", "admit", "first_token", "done"}: the
        #: request's id and its stamps in ``time.monotonic_ns()`` —
        #: TTFT = (admit - submit) queue wait + (first_token - admit)
        #: prefill.  None from the reference loop.
        self.timing = timing
        # (device rows [n, vocab] or [chain, vocab], tokens they cover)
        # a launch; brought to the host when ``logits`` is first read
        self._logit_parts = logit_parts
        self._logits = None

    @property
    def logits(self):
        """``[len(tokens), vocab]`` float32, row ``t`` the logits
        ``tokens[t]`` was chosen from — only for a request that asked
        (``generate(return_logits=True)``), else None.  The rows stay on
        the device until the first read, which copies them in the
        reader's thread: the worker only ever slices."""
        if self._logits is None and self._logit_parts:
            self._logits = np.concatenate([
                np.asarray(rows, np.float32).reshape(-1, rows.shape[-1])[:n]
                for rows, n in self._logit_parts])
            self._logit_parts = None
        return self._logits

    def __repr__(self):
        return (f"GenerationResult(tokens={self.tokens.tolist()}, "
                f"prompt_len={self.prompt_len}, "
                f"finish_reason={self.finish_reason!r})")


class _Seq:
    __slots__ = ("prompt", "max_new", "eos", "future", "on_token",
                 "block_ids", "pos", "out_tokens", "done", "reason",
                 "t_submit", "steps", "_gather_idx", "waited_rounds",
                 "temperature", "top_k", "top_p", "seed", "hit_blocks",
                 "_chunk_off", "rid", "t_submit_ns", "t_admit_ns",
                 "t_first_token_ns", "logits", "state_slot")

    def __init__(self, prompt, max_new, eos, on_token,
                 temperature=0.0, top_k=0, top_p=0.0, seed=0,
                 return_logits=False):
        self.prompt = prompt
        self.max_new = max_new
        self.eos = eos
        self.future: Future = Future()
        self.on_token = on_token
        self.block_ids: List[int] = []
        self.state_slot: Optional[int] = None   # recurrent-state slot held
        self.pos = 0                   # tokens currently in cache
        self.out_tokens: List[int] = []
        self.done = False
        self.reason = "length"
        self.rid = -1                  # set under the queue lock
        self.t_submit_ns = _now_ns()
        self.t_submit = self.t_submit_ns * 1e-9
        self.t_admit_ns = None
        self.t_first_token_ns = None
        self.steps = 0
        self._gather_idx = 0
        self.waited_rounds = 0
        self.temperature = float(temperature)   # <= 0 means greedy
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.seed = int(seed)
        self.hit_blocks = 0            # leading blocks shared by ref
        self._chunk_off = 0            # prompt tokens already in cache
        # (device rows, tokens they cover) a launch (None: not asked for)
        self.logits: Optional[List[tuple]] = [] if return_logits else None


class _PrefixIndex:
    """Cross-request KV prefix cache: a content-hash index over FULL
    blocks of the engine's one pool.

    A key is ``sha256(layout_key + prompt[:(j+1)*block_size])`` — the
    model/layout identity plus the EXACT token prefix the block closes,
    so two requests share block ``j`` iff every token up to and
    including that block matches and the bytes in the pool mean the
    same thing (same parameters, same block geometry).  Entries are
    refcounted: a probe hit or a promotion holds one reference per
    user, retirement releases it, and only refcount-0 entries are
    evictable (LRU-first — a hit refreshes recency).  An indexed block
    at refcount 0 is *effectively free*: admission counts it as
    available and :meth:`evict_one` hands it out, which is what lets
    suffix-priced admission admit where full-span pricing would wait
    forever."""

    def __init__(self, layout_key: str, block_size: int,
                 block_bytes: int):
        from collections import OrderedDict
        self._layout = layout_key.encode("utf-8")
        self._bs = int(block_size)
        self.block_bytes = int(block_bytes)
        self._entries: "OrderedDict[bytes, list]" = OrderedDict()
        self._by_block: Dict[int, bytes] = {}
        self.hits = 0
        self.misses = 0
        self.bytes_saved = 0
        self.evictions = 0

    def _key(self, prompt: np.ndarray, j: int) -> bytes:
        import hashlib
        data = self._layout + \
            np.ascontiguousarray(prompt[:(j + 1) * self._bs],
                                 dtype=np.int64).tobytes()
        return hashlib.sha256(data).digest()

    def shareable_blocks(self, prompt_len: int) -> int:
        """FULL blocks of the prompt a hit may cover — the last prompt
        token is always recomputed (prefill must emit the first
        generated token), so the shareable span stops one token short."""
        return (int(prompt_len) - 1) // self._bs

    def probe(self, prompt: np.ndarray, prompt_len: int) -> List[int]:
        """Consecutive hit blocks from block 0, each ACQUIRED (one ref
        held by the caller until release/retire)."""
        out: List[int] = []
        for j in range(self.shareable_blocks(prompt_len)):
            key = self._key(prompt, j)
            ent = self._entries.get(key)
            if ent is None:
                break
            ent[1] += 1
            self._entries.move_to_end(key)
            out.append(ent[0])
        return out

    def promote(self, prompt: np.ndarray, j: int, block_id: int) -> bool:
        """Index one freshly-prefilled full block (the promoting
        sequence holds the initial reference).  A racing identical
        prompt already holds the key — its twin's block stays private."""
        key = self._key(prompt, j)
        if key in self._entries:
            return False
        self._entries[key] = [int(block_id), 1]
        self._by_block[int(block_id)] = key
        return True

    def contains_block(self, block_id: int) -> bool:
        return int(block_id) in self._by_block

    def release_block(self, block_id: int):
        self._entries[self._by_block[int(block_id)]][1] -= 1

    def release(self, block_ids: Sequence[int]):
        for bid in block_ids:
            self.release_block(bid)

    def evictable(self) -> int:
        return sum(1 for ent in self._entries.values() if ent[1] == 0)

    def evict_one(self) -> Optional[int]:
        """Pop the least-recently-used refcount-0 entry and hand its
        block back; an entry anybody still references is untouchable."""
        victim = None
        for key, ent in self._entries.items():
            if ent[1] == 0:
                victim = key
                break
        if victim is None:
            return None
        bid = self._entries.pop(victim)[0]
        del self._by_block[bid]
        self.evictions += 1
        return bid

    def __len__(self):
        return len(self._entries)


class _PhaseSpan:
    """One worker phase as a context manager: the phase clock switches
    to ``name`` on entry and back to ``retire`` on exit, and a
    ``decode::<name>`` span brackets the same interval."""

    __slots__ = ("_engine", "_name", "_span")

    def __init__(self, engine: "DecodeEngine", name: str):
        self._engine = engine
        self._name = name
        self._span = None

    def __enter__(self):
        starved = self._engine._switch(self._name)
        # the attribute goes in with the span's name: one encoding, where
        # a set() on an open span costs a call into the profiler
        self._span = RecordEvent("decode::" + self._name,
                                 {"starved": int(starved)})
        return self._span.__enter__()

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        self._engine._switch("retire")
        return False


class DecodeEngine:
    """Continuous-batching generation over a paged KV-cache.

    ::

        model = BertDecoder(cfg)
        engine = DecodeEngine(model, DecodeConfig(
            block_size=8, max_seq_len=64, max_batch_size=8,
            prefill_seq_buckets=(16, 32)))
        engine.warmup()                       # AOT-compile the grid
        fut = engine.generate({"src_ids": prompt}, max_new_tokens=16)
        result = fut.result()                 # GenerationResult
        engine.shutdown()

    One worker thread owns the device: each scheduling round retires
    finished sequences (freeing their blocks), admits waiting prefills
    that fit the pool, and runs one decode step over every live
    sequence."""

    def __init__(self, model, config: Optional[DecodeConfig] = None,
                 place=None, auto_start: bool = True):
        from ..flags import flag
        from ..framework.core import CPUPlace, TPUPlace
        from ..framework.executor import Executor, Scope

        self.config = cfg = config or DecodeConfig()
        self.model = model
        mcfg = model.cfg
        if cfg.max_seq_len > mcfg.max_position_embeddings:
            raise InvalidArgumentError(
                f"max_seq_len={cfg.max_seq_len} exceeds the model's "
                f"max_position_embeddings={mcfg.max_position_embeddings}")
        self._mbps = cfg.max_blocks_per_seq
        # recurrent state: one slot a batch row and a scratch slot (the
        # last) for the rows of a bucket that hold no sequence
        state_bytes = getattr(model, "state_bytes_per_slot", None)
        self._state_bytes = int(state_bytes()) if state_bytes else 0
        self._state_slots = cfg.max_batch_size if self._state_bytes else 0
        if self._state_bytes and cfg.prefix_cache:
            raise InvalidArgumentError(
                "prefix_cache=True is refused for a model with recurrent "
                "state: the prefix index matches KV blocks by content, "
                "and a hit without the recurrent state at that block "
                "boundary is wrong output (state snapshots do not exist "
                "yet) — set DecodeConfig(prefix_cache=False)")
        # a packed prefill row carries ONE recurrent state: one segment
        self._pack = 1 if self._state_bytes else cfg.pack_max_segments

        # -- pool sizing (the memory analyzer IS the admission model) --
        budget = cfg.hbm_budget_gb
        if budget is None:
            budget = float(flag("hbm_budget_gb") or 0.0)
        self.pool_plan: Dict[str, Any] = {}
        pool_blocks = cfg.pool_blocks
        if pool_blocks is None:
            if budget:
                pool_blocks = self._plan_pool(budget)
            else:
                pool_blocks = cfg.max_batch_size * self._mbps
        if pool_blocks < 1:
            raise InvalidArgumentError(
                f"pool_blocks={pool_blocks} — the paged cache needs at "
                f"least one block")
        # a pool smaller than one max-length sequence is legal (requests
        # that cannot fit are rejected per-request at generate()); a
        # budget-SIZED pool keeps the min_blocks=max_blocks_per_seq
        # floor so admission failures surface at engine start
        self.pool_blocks = int(pool_blocks)

        # -- programs + state ------------------------------------------
        need_chunk = cfg.prefix_cache or cfg.chunk_tokens
        self._programs = model.build(
            self.pool_blocks, cfg.block_size, self._mbps,
            self._pack, chain_lengths=cfg.chain_lengths,
            with_sampling=cfg.sampling,
            chunk_tokens=cfg.chunk_width if need_chunk else None,
            **self._state_kw())
        if place is None:
            import jax
            place = CPUPlace() if jax.default_backend() == "cpu" \
                else TPUPlace(0)
        self._scope = Scope()
        self._exe = Executor(place)
        self._exe.run(self._programs.startup, scope=self._scope)
        import jax.numpy as jnp
        progs = self._programs
        declaring = [progs.decode, progs.prefill, progs.chunk,
                     *progs.chains.values()]
        for name in progs.cache_vars:
            # a pool is in every program; a device counter only in the
            # programs of its launch kind
            v = next(p.global_block().var(name) for p in declaring
                     if p is not None and p.global_block().has_var(name))
            self._scope.set_var(name, jnp.zeros(
                tuple(v.shape), dtype=np.dtype(v.dtype)))
        # device counters by launch kind: {kind: {persistable: pairs}}
        # (``program._device_counters``; chains of every length share
        # theirs), read by stats()
        self._counters = {
            kind: dict(getattr(prog, "_device_counters", {}))
            for kind, prog in (("prefill", progs.prefill),
                               ("chunk", progs.chunk),
                               ("chain", next(iter(progs.chains.values()),
                                              None)))
            if prog is not None and getattr(prog, "_device_counters", None)}
        if flag("verify_programs"):
            from ..framework.analysis import verify_decode
            to_verify = [(self._programs.prefill,
                          self._programs.prefill_feeds,
                          self._programs.fetch_names),
                         (self._programs.decode,
                          self._programs.decode_feeds,
                          self._programs.fetch_names)]
            for prog in self._programs.chains.values():
                to_verify.append((prog, self._programs.chain_feeds,
                                  self._programs.chain_fetch_names))
            if self._programs.chunk is not None:
                to_verify.append((self._programs.chunk,
                                  self._programs.chunk_feeds,
                                  self._programs.fetch_names))
            for prog, feeds, fetches_v in to_verify:
                verify_decode(
                    prog, feed_names=feeds,
                    fetch_names=fetches_v,
                    scope_names=self._scope.var_names(),
                    # the pools are in every program; a device counter
                    # only in the programs of its launch kind (each was
                    # found in some program above, where it was zeroed)
                    cache_vars=[n for n in self._programs.cache_vars
                                if prog.global_block().has_var(n)]
                ).raise_on_error()

        # the reference loop's isolated weight snapshot is taken at its
        # first use (_reference_scope): an engine that never scores a
        # prefix holds its weights once
        self._ref_scope: Optional[Scope] = None

        fetches = list(self._programs.fetch_names)
        self._prefill = self._exe.prepare(
            self._programs.prefill,
            feed_names=self._programs.prefill_feeds,
            fetch_list=fetches, scope=self._scope, donate_state=True)
        # all decode stepping runs through the chained executables (a
        # chain of length 1 IS the single step); progs.decode stays for
        # the pool-sizing probe and verification only
        self._chains = {
            length: self._exe.prepare(
                prog, feed_names=self._programs.chain_feeds,
                fetch_list=list(self._programs.chain_fetch_names),
                scope=self._scope, donate_state=True)
            for length, prog in self._programs.chains.items()}
        self._chain_lengths = tuple(sorted(self._chains))
        self._chunk = None
        if self._programs.chunk is not None:
            self._chunk = self._exe.prepare(
                self._programs.chunk,
                feed_names=self._programs.chunk_feeds,
                fetch_list=fetches, scope=self._scope,
                donate_state=True)
        self._score = None              # reference path, built lazily
        self._owner = None              # which prepared step holds state

        # -- cross-request prefix cache --------------------------------
        self._prefix_index: Optional[_PrefixIndex] = None
        if cfg.prefix_cache:
            layout = getattr(model, "cache_layout_key", None)
            layout_key = layout(cfg.block_size) if layout is not None \
                else f"{getattr(model, 'name', 'model')}" \
                     f"/bs={cfg.block_size}"
            self._prefix_index = _PrefixIndex(
                layout_key, cfg.block_size,
                model.cache_block_bytes(cfg.block_size))

        # -- scheduling state ------------------------------------------
        self._free: List[int] = list(range(self.pool_blocks - 1, -1, -1))
        self._free_state: List[int] = list(
            range(self._state_slots - 1, -1, -1))
        self._pending: List[_Seq] = []
        self._active: List[_Seq] = []
        self._chunking: List[_Seq] = []
        self._cond = threading.Condition()
        self._run_lock = threading.Lock()   # device rounds vs warmup
        self._lock_wanted = 0               # stats() readers waiting for it
        self._ref_lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._stop = False
        self._abort = False                 # close(): stop at a round's end
        self._closed = False
        self._accepting = True
        self._unhealthy: Optional[BaseException] = None

        self._stats_lock = threading.Lock()
        self._submitted = 0
        self._completed = 0
        self._failed = 0
        self._rejected = 0
        self._tokens_out = 0
        self._decode_steps = 0
        self._kv_pages_read = 0
        self._kv_pages_spanned = 0
        self._prefill_batches = 0
        self._decode_batch_hist: Dict[int, int] = {}
        self._peak_blocks = 0
        self._block_reuses = 0          # a freed block handed out again
        self._retired_blocks: set = set()
        self._admission_waits = 0
        self._state_peak = 0
        self._state_reuses = 0          # a freed slot handed out again
        self._retired_state: set = set()
        self._state_rows_launched = 0   # bucket rows x decode steps
        self._state_rows_live = 0       # of them, rows a sequence owned
        self._host_syncs = 0            # one per device->host token fetch
        self._chains_run = 0
        self._chain_tokens = 0
        self._chain_hist: Dict[int, int] = {}
        self._chunk_steps = 0
        self._chunk_tokens = 0          # prompt tokens the chunks computed
        self._interleaved_rounds = 0    # rounds mixing chunks + chains
        self._prefill_tokens = 0        # prompt tokens actually computed
        self._t_first = None
        self._t_last = None
        self._next_rid = 0              # under _cond
        # the worker's phase clock (all under _stats_lock): closed time
        # per phase, and the phase open now with its start (None while
        # no worker runs), so stats() is exact at any instant
        self._phase_ns = dict.fromkeys(PHASES, 0)
        self._phase_open: Optional[Tuple[str, int, bool]] = None
        self._launch_ns = dict.fromkeys(LAUNCH_KINDS, 0)
        self._launches = dict.fromkeys(LAUNCH_KINDS, 0)
        # the in-flight ledger, oldest first (the worker is its one
        # writer): a launch dispatched and not yet known complete is
        # [kind, a fetch handle of it, the stamp its device interval
        # cannot start before (its dispatch's start, or the dispatch's
        # end where the ledger was empty at that start), its executable
        # (None where this dispatch bound it), that executable's usual
        # device ns at the dispatch (0: none yet)]
        self._inflight: deque = deque()
        self._device_free_ns = 0        # the last completion a sync saw
        self._device_ns = dict.fromkeys(LAUNCH_KINDS, 0)
        self._starved_ns = dict.fromkeys(STARVABLE, 0)
        # executable -> the running mean of its launches' device ns (0:
        # bound, not yet launched again), and what the open phase is
        # measured against (0: nothing to measure it against yet)
        self._usual_ns = weakref.WeakKeyDictionary()
        self._yardstick_ns = 0
        self._slow_phase_ns = dict.fromkeys(PHASES[1:], 0)
        self._slow_phases: deque = deque(maxlen=32)
        # what the worker is launching, for a slow phase's row: (kind,
        # rows, bucket or chain length, first rid), set inside the
        # launch's feed phase; None between launches
        self._launching: Optional[tuple] = None
        self._admitted = 0
        self._queue_wait_ns = 0         # sum of admit - submit
        self._first_token_ns = 0        # sum of first token - admit
        self._first_tokens = 0
        _watchdog.ensure_started()
        register_serving_engine(self)   # /metrics pulls stats() at scrape
        if auto_start:
            self.start()

    # -- pool sizing ------------------------------------------------------
    def _plan_pool(self, budget_gb: float) -> int:
        """Static pool sizing: build a PROBE decode program (minimum
        viable pool) and let the analyzer price blocks under the budget
        — 0 compiles, the decode analog of ServingFleet admission."""
        from ..framework.memory_analysis import plan_cache_pool
        cfg = self.config
        probe = self.model.build(self._mbps, cfg.block_size, self._mbps,
                                 self._pack, **self._state_kw())
        bb = cfg.batch_buckets[-1]
        feed = self._decode_feed_arrays(
            bb, [], pad_only=True)
        plan = plan_cache_pool(
            probe.decode, feed_shapes=feed,
            fetch_names=probe.fetch_names,
            cache_vars=probe.cache_vars,
            block_bytes=self.model.cache_block_bytes(cfg.block_size),
            budget_gb=budget_gb, min_blocks=self._mbps,
            reserve_blocks=cfg.prefix_reserve_blocks)
        self.pool_plan = {
            "blocks": plan["blocks"],
            "block_bytes": plan["block_bytes"],
            "fixed_bytes": plan["fixed_bytes"],
            "budget_bytes": plan["budget_bytes"],
            "reserve_blocks": plan.get("reserve_blocks", 0),
        }
        return plan["blocks"]

    def _state_kw(self) -> Dict[str, int]:
        """``model.build``'s extra argument for a model with recurrent
        state: the state pools' slots, the scratch slot included."""
        return {"state_slots": self._state_slots + 1} \
            if self._state_bytes else {}

    # -- lifecycle --------------------------------------------------------
    def start(self):
        if self._thread is None:
            self._thread = threading.Thread(target=self._worker_loop,
                                            name="decode-engine-worker",
                                            daemon=True)
            self._thread.start()
        return self

    def drain(self, timeout: float = 60.0) -> bool:
        """Block until every submitted generation resolved (or failed).
        Never hangs on an unhealthy engine — the fatal path resolves
        every future before marking unhealthy."""
        deadline = time.monotonic() + timeout
        with self._cond:
            self._cond.notify_all()
            while self._pending or self._active or self._chunking:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._cond.wait(remaining)
            return True

    def shutdown(self, drain: bool = True, timeout: float = 60.0) -> bool:
        with self._cond:
            self._accepting = False
            if not drain:
                for seq in self._pending:
                    seq.future.set_exception(UnavailableError(
                        "decode engine shut down before the request ran"))
                self._pending.clear()
            self._stop = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout)
            return not self._thread.is_alive()
        return True

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown()
        return False

    # -- submission -------------------------------------------------------
    @staticmethod
    def _normalize_prompt(feed) -> np.ndarray:
        if isinstance(feed, dict):
            if "src_ids" not in feed:
                raise InvalidArgumentError(
                    "generate() feed must carry 'src_ids' (the prompt "
                    "token ids)")
            arr = np.asarray(feed["src_ids"])
        else:
            arr = np.asarray(feed)
        if arr.ndim == 2:
            if arr.shape[0] != 1:
                raise InvalidArgumentError(
                    f"generate() takes ONE sequence per call; got a "
                    f"batch of {arr.shape[0]} — submit them separately, "
                    f"the engine co-batches at token granularity")
            arr = arr[0]
        if arr.ndim != 1 or arr.size == 0:
            raise InvalidArgumentError(
                f"prompt must be a non-empty 1-D (or [1, S]) int array, "
                f"got shape {list(arr.shape)}")
        return arr.astype(np.int64)

    def generate(self, feed, max_new_tokens: Optional[int] = None,
                 eos_token_id: Optional[int] = None,
                 on_token=None, temperature: Optional[float] = None,
                 top_k: Optional[int] = None,
                 top_p: Optional[float] = None,
                 seed: Optional[int] = None,
                 return_logits: bool = False) -> Future:
        """Submit one prompt; returns a Future of
        :class:`GenerationResult`.  ``on_token(token_id)`` (optional)
        streams tokens from the worker thread as they decode.

        ``temperature``/``top_k``/``top_p``/``seed`` select the
        on-device sampling policy (requires
        ``DecodeConfig(sampling=True)``); default/``temperature<=0``
        rows stay greedy and keep the bit-parity contract.  A fixed
        seed draws the same tokens no matter how the request is
        co-batched or chain-scheduled.  ``return_logits`` also hands back
        the float32 logits every token was chosen from
        (``GenerationResult.logits``), sliced on the device for this
        request alone; it needs a model whose chain programs return
        them (``DecoderPrograms.chain_fetch_names``).

        Admission prices :func:`blocks_needed` HERE — a request that can
        never fit the pool (or the model's length budget) is rejected
        immediately, before any compile or queue time."""
        cfg = self.config
        if not cfg.sampling and any(
                v is not None for v in (temperature, top_k, top_p, seed)):
            raise InvalidArgumentError(
                "sampling parameters need DecodeConfig(sampling=True) — "
                "this engine's chain executables were built greedy-only")
        if return_logits and len(self._programs.chain_fetch_names) < 2:
            raise InvalidArgumentError(
                "return_logits needs chain programs that return every "
                "step's logits; this model's return "
                f"{self._programs.chain_fetch_names}")
        prompt = self._normalize_prompt(feed)
        plen = int(prompt.size)
        max_new = cfg.max_new_tokens if max_new_tokens is None \
            else int(max_new_tokens)
        if max_new < 1:
            raise InvalidArgumentError("max_new_tokens must be >= 1")
        eos = cfg.eos_token_id if eos_token_id is None else eos_token_id
        if plen + max_new > cfg.max_seq_len:
            with self._stats_lock:
                self._rejected += 1
            raise InvalidArgumentError(
                f"prompt ({plen} tokens) + max_new_tokens ({max_new}) "
                f"exceeds max_seq_len={cfg.max_seq_len}")
        if plen > cfg.prefill_seq_buckets[-1] and not cfg.chunk_tokens:
            with self._stats_lock:
                self._rejected += 1
            raise InvalidArgumentError(
                f"prompt length {plen} exceeds the largest prefill "
                f"bucket {cfg.prefill_seq_buckets[-1]} — set "
                f"DecodeConfig(chunk_tokens=...) to prefill long "
                f"prompts in chunks")
        need = blocks_needed(plen, max_new, cfg.block_size)
        if need > self.pool_blocks:
            with self._stats_lock:
                self._rejected += 1
            raise InvalidArgumentError(
                f"admission rejected: the request needs {need} cache "
                f"blocks (prompt {plen} + up to {max_new} new tokens at "
                f"block_size={cfg.block_size}) but the pool holds "
                f"{self.pool_blocks} — 0 compiles spent; shrink the "
                f"request or grow the pool")
        seq = _Seq(prompt, max_new, eos, on_token,
                   temperature=temperature or 0.0, top_k=top_k or 0,
                   top_p=top_p or 0.0, seed=seed or 0,
                   return_logits=return_logits)
        with self._cond:
            if self._unhealthy is not None:
                raise UnavailableError(
                    f"decode engine is unhealthy — its worker died with "
                    f"{self._unhealthy!r}; restart the engine")
            if not self._accepting:
                raise UnavailableError("decode engine is shut down")
            seq.rid = self._next_rid
            self._next_rid += 1
            self._pending.append(seq)
            self._cond.notify_all()
        with self._stats_lock:
            self._submitted += 1
            if self._t_first is None:
                self._t_first = seq.t_submit
        return seq.future

    # -- worker -----------------------------------------------------------
    def _worker_loop(self):
        self._switch("retire")          # the phase clock starts here
        try:
            self._loop_inner()
        except BaseException as e:    # noqa: BLE001 — worker last line
            self._worker_fatal(e)
        finally:
            self._switch(None)

    def _switch(self, phase: Optional[str]) -> bool:
        """A phase boundary: close the open phase into ``phase_ns`` and
        open ``phase`` at the same clock read, so the phases tile the
        worker's wall time with nothing between them.  A host phase that
        opens with nothing in flight is **starved** (returned; its length
        also goes to ``starved_ns``); a non-idle interval of
        :data:`SLOW_PHASE_FACTOR` times ``_yardstick_ns`` is put on
        record."""
        now = _now_ns()
        starved = phase in STARVABLE and not self._inflight
        slow = None
        with self._stats_lock:
            if self._phase_open is not None:
                name, t0, was_starved = self._phase_open
                dur = now - t0
                self._phase_ns[name] += dur
                if was_starved:
                    self._starved_ns[name] += dur
                if 0 < self._yardstick_ns * SLOW_PHASE_FACTOR <= dur \
                        and name != "idle":
                    self._slow_phase_ns[name] += dur
                    slow = (name, t0, dur)
            self._phase_open = None if phase is None \
                else (phase, now, starved)
        if slow is not None:
            self._note_slow_phase(*slow)
        return starved

    def _note_slow_phase(self, name: str, t0: int, dur: int) -> None:
        """One row of ``stats()["slow_phases"]`` and a flight event:
        ``[phase, launch kind or None, start ns, length ns, rows of the
        launch (live sequences between launches), its size (a packed
        prefill's sequence bucket, a chunk's tokens, a chain's length),
        blocks in use, first rid]`` — whether the stall sat in ``sync``
        (the device or its allocator), ``dispatch`` (the runtime) or
        ``emit`` / ``retire`` (Python), and under what load."""
        held = self._active + self._chunking
        kind, rows, size, rid = self._launching or (
            None, len(held), None, held[0].rid if held else None)
        row = [name, kind, t0, dur, rows, size, self._blocks_in_use(), rid]
        with self._stats_lock:
            self._slow_phases.append(row)
        _flight.note_event("decode_slow_phase", phase=name, launch=kind,
                           start_ns=t0, dur_ns=dur, rows=rows, size=size,
                           blocks=row[6], rid=rid)

    def _phase(self, name: str) -> "_PhaseSpan":
        """``with self._phase("feed"):`` — the worker is in ``name``
        until the block ends, then back in ``retire`` (bookkeeping)."""
        return _PhaseSpan(self, name)

    def _loop_inner(self):
        while True:
            with self._cond:
                while not self._stop and not self._pending \
                        and not self._active and not self._chunking:
                    with self._phase("idle"):
                        self._cond.wait()
                if self._stop and not self._pending \
                        and not self._active and not self._chunking:
                    return
                if self._abort:
                    failed = self._drop_all(UnavailableError(
                        "decode engine shut down with the request in "
                        "flight"))
                    with self._stats_lock:
                        self._failed += failed
                    return
            if _FL_ARMED:
                # drill seam: an uncaught decode-worker exception,
                # outside any per-step recovery
                _faultline.crossing("serving_decode")
            while self._lock_wanted:
                # a stats() reader waits for the gap between two rounds:
                # do not take the lock back before it has had it
                time.sleep(2e-4)
            with self._run_lock:
                n_chunking = len(self._chunking)
                with self._phase("admit") as span:
                    admitted = self._admit()
                    if span.recording:
                        taken = admitted + self._chunking[n_chunking:]
                        span.set(rids=",".join(str(s.rid) for s in taken))
                if admitted:
                    self._run_prefill(admitted)
                    self._retire()
                if self._chunking:
                    if self._active:
                        with self._stats_lock:
                            self._interleaved_rounds += 1
                    self._chunk_round()
                    self._retire()
                if self._active:
                    self._chain_step()
                    self._retire()

    def _worker_fatal(self, exc: BaseException):
        """Terminal worker failure: every generation future fails, every
        cache block frees, the engine goes unhealthy."""
        _flight.dump("decode_worker_fatal", exc=exc,
                     extra={"pending": len(self._pending),
                            "active": len(self._active),
                            "chunking": len(self._chunking)})
        with self._cond:
            self._unhealthy = exc
            self._accepting = False
            self._stop = True
            failed = self._drop_all(UnavailableError(
                f"decode engine worker died: {exc!r} — "
                f"generation failed (flight bundle dumped)"))
        with self._stats_lock:
            self._failed += failed

    def _drop_all(self, error: BaseException) -> int:
        """Under ``_cond``: fail every queued and in-flight generation
        with ``error`` and free their blocks; returns how many failed."""
        victims = self._active + self._chunking + self._pending
        for seq in self._active + self._chunking:
            self._release_blocks(seq)
        self._active, self._chunking, self._pending = [], [], []
        failed = 0
        for seq in victims:
            if not seq.future.done():
                seq.future.set_exception(error)
                failed += 1
        self._cond.notify_all()
        return failed

    # -- scheduling -------------------------------------------------------
    def _availability(self) -> int:
        """Blocks admission may hand out NOW: the free list plus every
        refcount-0 indexed block (evictable = effectively free)."""
        n = len(self._free)
        if self._prefix_index is not None:
            n += self._prefix_index.evictable()
        return n

    def _take_blocks(self, n: int) -> List[int]:
        """Allocate ``n`` blocks: free list first, then LRU eviction of
        refcount-0 index entries (availability was checked by the
        caller, so eviction cannot come up short)."""
        out: List[int] = []
        for _ in range(n):
            if self._free:
                bid = self._free.pop()
            else:
                bid = self._prefix_index.evict_one()
                if bid is None:
                    raise UnavailableError(
                        "cache pool accounting violated: admission "
                        "priced blocks that are not available")
            if bid in self._retired_blocks:
                with self._stats_lock:
                    self._block_reuses += 1
            out.append(bid)
        return out

    def _release_blocks(self, seq: _Seq):
        """Return a sequence's blocks: indexed blocks drop one reference
        (staying cached, evictable once nobody references them), the
        rest go back to the free list."""
        idx = self._prefix_index
        for bid in reversed(seq.block_ids):
            if idx is not None and idx.contains_block(bid):
                idx.release_block(bid)
            else:
                self._free.append(bid)
        seq.block_ids = []
        if seq.state_slot is not None:
            self._free_state.append(seq.state_slot)
            seq.state_slot = None

    def _admit(self) -> List[_Seq]:
        """Pull pending prefills that fit THIS round: decode-slot
        capacity, prefill row/segment capacity, and — the paged-cache
        admission — enough blocks for the sequence's NON-SHARED span
        (prefix-cache hits ride existing blocks by reference and charge
        nothing; full-span pricing would keep a hit-heavy request
        waiting on blocks it never needs).  Continue-scan (head-of-line
        fix): a large request waiting on blocks does not starve smaller
        later ones.  Requests with a prefix hit or an over-bucket
        prompt go to the chunked-prefill queue; the rest return for the
        packed prefill batch."""
        cfg = self.config
        idx = self._prefix_index
        admitted: List[_Seq] = []
        row_lens: List[int] = []
        bucket_s = None
        taken = 0
        now = _now_ns()
        with self._cond:
            slots_left = (cfg.max_batch_size - len(self._active)
                          - len(self._chunking))
            for seq in list(self._pending):
                if taken >= slots_left:
                    break
                plen = int(seq.prompt.size)
                need_total = blocks_needed(plen, seq.max_new,
                                           cfg.block_size)
                # probe acquires refs on the hit blocks so a concurrent
                # eviction (for an earlier admit this round) can't free
                # them out from under the pricing below
                hits = idx.probe(seq.prompt, plen) \
                    if idx is not None else []
                need = need_total - len(hits)
                if need > self._availability():
                    if hits:
                        idx.release(hits)
                    seq.waited_rounds += 1
                    with self._stats_lock:
                        self._admission_waits += 1
                    continue
                chunked = bool(hits) or \
                    plen > cfg.prefill_seq_buckets[-1]
                if not chunked:
                    need_s = bucket_s
                    if need_s is None or plen > need_s:
                        need_s = next(s for s in cfg.prefill_seq_buckets
                                      if s >= plen)
                    trial = row_lens + [plen]
                    if _plan_bins(trial, need_s, self._pack,
                                  cfg.prefill_batch_buckets[-1]) is None:
                        continue
                    row_lens = trial
                    bucket_s = need_s
                self._pending.remove(seq)
                # hit blocks by reference + the suffix span allocated
                # fresh; handing a previously-used block to a new
                # sequence is the reuse case the parity contract covers
                seq.block_ids = list(hits) + self._take_blocks(need)
                if self._state_bytes:
                    # one slot a batch row: a free row has a free slot
                    seq.state_slot = self._free_state.pop()
                    if seq.state_slot in self._retired_state:
                        with self._stats_lock:
                            self._state_reuses += 1
                seq.hit_blocks = len(hits)
                seq._chunk_off = len(hits) * cfg.block_size
                taken += 1
                if idx is not None:
                    probed = idx.shareable_blocks(plen)
                    idx.hits += len(hits)
                    idx.misses += probed - len(hits)
                    idx.bytes_saved += len(hits) * idx.block_bytes
                seq.t_admit_ns = now
                with self._stats_lock:
                    self._prefill_tokens += plen - seq._chunk_off
                    self._admitted += 1
                    self._queue_wait_ns += now - seq.t_submit_ns
                if chunked:
                    self._chunking.append(seq)
                else:
                    admitted.append(seq)
        return admitted

    def _slot(self, seq: _Seq, p: int) -> int:
        bs = self.config.block_size
        return seq.block_ids[p // bs] * bs + p % bs

    # -- prefill ----------------------------------------------------------
    def _prefill_feed(self, admitted: List[_Seq]):
        cfg = self.config
        K = self._pack
        plens = [int(s.prompt.size) for s in admitted]
        bucket_s = next(s for s in cfg.prefill_seq_buckets
                        if s >= max(plens))
        plan = _plan_bins(plens, bucket_s, K,
                          cfg.prefill_batch_buckets[-1])
        placements, n_rows = plan
        bucket_b = next(b for b in cfg.prefill_batch_buckets
                        if b >= n_rows)
        src = np.zeros((bucket_b, bucket_s), np.int64)
        pos = np.zeros((bucket_b, bucket_s), np.int64)
        mask = np.zeros((bucket_b, bucket_s, K), np.float32)
        slots = np.full((bucket_b, bucket_s), -1, np.int32)
        last_pos = np.zeros((bucket_b, K), np.int64)
        chan = [0] * bucket_b
        for seq, (row, off) in zip(admitted, placements):
            plen = int(seq.prompt.size)
            ch = chan[row]
            chan[row] += 1
            src[row, off:off + plen] = seq.prompt
            pos[row, off:off + plen] = np.arange(plen)
            mask[row, off:off + plen, ch] = 1.0
            slots[row, off:off + plen] = [self._slot(seq, p)
                                          for p in range(plen)]
            last_pos[row, ch] = off + plen - 1
            seq._gather_idx = row * K + ch
        feed = {"src_ids": src, "pos_ids": pos, "input_mask": mask,
                "slot_ids": slots, "last_pos": last_pos}
        if self._state_bytes:       # one segment a row: the row's slot
            feed["state_slot"] = self._state_rows(
                bucket_b, [(row, seq) for seq, (row, _)
                           in zip(admitted, placements)])
            feed["state_fresh"] = np.ones((bucket_b,), np.int32)
        return feed, (bucket_b, bucket_s)

    def _state_rows(self, bucket_b: int, rows=()) -> np.ndarray:
        """The per-row ``state_slot`` feed of a launch: each sequence's
        slot at its row, the scratch slot everywhere else."""
        out = np.full((bucket_b,), self._state_slots, np.int32)
        for row, seq in rows:
            out[row] = seq.state_slot
        return out

    def _acquire(self, prepared):
        """Owner handoff between the prefill and decode prepared steps:
        both donate the shared scope state (weights pass through
        aliased; the cache pools update in place), so the outgoing
        owner's device-resident state must flow back through the scope
        before the other side pulls it — dict writes of device arrays,
        no host transfer."""
        if self._owner is not None and self._owner is not prepared:
            self._owner.sync_scope()
        self._owner = prepared

    def _launch(self, kind: str, prepared, feed, fetch: Optional[int]):
        """One executable's ``dispatch`` and, when ``fetch`` names the
        handle the host needs, its ``sync``, under the watchdog.
        Returns (the fetched host array or None, the two phases' ns, the
        stamp at which the later of them closed).  The two phases' ns is
        what the WORKER spent on this launch (``launch_ns``), not the
        executable's device time: a launch without ``fetch`` returns as
        soon as it is dispatched, and whoever syncs next waits it out.
        So the launch goes on the in-flight ledger, and a ``sync`` first
        waits on every earlier launch there in order, stamping each
        completion (all outputs of one execution become ready together),
        then fetches its own: k + 1 blocking calls across the interval
        one call would have blocked across, k the launches in flight
        before it.  Launch *i* held the device from the later of launch
        *i-1*'s completion and its own dispatch (the dispatch's END where
        the ledger was empty at its start, which made that dispatch a
        starved phase) to its own completion, the last one's being the
        stamp that closes the ``sync``: that goes to ``device_ns[kind]``
        and into the running mean of the launch's executable, against
        which the phases of later launches of it are measured
        (:data:`SLOW_PHASE_FACTOR`).  The launch's fetch handles stay in
        ``self._handles`` for a request that asked for its logits."""
        # the worker is the phase clock's one writer, so its reads
        # outside the lock see its own last write
        ph = self._phase_ns
        launch0 = ph["dispatch"] + ph["sync"]
        out = None
        _watchdog.begin("decode")
        try:
            with self._phase("dispatch"):
                _, since, exposed = self._phase_open
                self._acquire(prepared)
                handles = self._handles = prepared.run(feed)
                step = prepared._cur    # the executable the feed bound
                usual = self._usual_ns.get(step)
                if usual is None:
                    # bound by this dispatch (a compile or a cache load):
                    # not judged, and not a launch to learn its usual
                    # time from
                    self._usual_ns[step] = 0
                    step = None
                self._yardstick_ns = usual = usual or 0
                # on the ledger before the boundary, so that the phase
                # that opens there is not starved
                entry = [kind, handles[0], since, step, usual]
                self._inflight.append(entry)
            if exposed:     # a starved dispatch is no device time
                entry[2] = self._phase_open[1]
            if fetch is not None:
                with self._phase("sync"):
                    waited = list(self._inflight)
                    usual = [e[4] for e in waited]
                    self._yardstick_ns = sum(usual) if all(usual) else 0
                    # a launch found complete before the worker waits
                    # on it (chunks pile up in a ramp and a dispatch
                    # then blocks in the prepared step's own window)
                    # was not seen to complete: booked, but not learnt
                    # from
                    done, seen = [], []
                    for earlier in waited[:-1]:
                        seen.append(not earlier[1].is_ready())
                        earlier[1].block_until_ready()
                        done.append(_now_ns())
                    seen.append(not handles[fetch].is_ready())
                    out = handles[fetch].numpy()
                    # off the ledger before the boundary: the device is
                    # free again and the next phase is starved
                    self._inflight.clear()
                done.append(self._phase_open[1])
                with self._stats_lock:
                    for (k, _, since, step, _), t, saw in zip(
                            waited, done, seen):
                        held = t - max(self._device_free_ns, since)
                        self._device_ns[k] += held
                        self._device_free_ns = t
                        if saw and step is not None:
                            mean = self._usual_ns[step]
                            self._usual_ns[step] = \
                                mean + (held - mean) // 8 if mean else held
        finally:
            _watchdog.end("decode")
        return (out, ph["dispatch"] + ph["sync"] - launch0,
                self._phase_open[1])

    def _run_prefill(self, admitted: List[_Seq]):
        sid = next_step_id()
        with step_scope(sid), \
                RecordEvent("decode::prefill",
                            requests=len(admitted)) as parent:
            with self._phase("feed"):
                feed, bucket = self._prefill_feed(admitted)
                parent.set(bucket=f"{bucket[0]}x{bucket[1]}")
                _flight.note_step(sid, "decode_prefill", bucket)
                self._launching = ("prefill", len(admitted), bucket[1],
                                   admitted[0].rid)
            tokens, launch_ns, end_ns = self._launch(
                "prefill", self._prefill, feed, 1)
            with self._phase("emit"):
                self._first_tokens_out(
                    admitted, [int(tokens[seq._gather_idx])
                               for seq in admitted],
                    [seq._gather_idx for seq in admitted])
            self._launching = None
        self._active.extend(admitted)
        with self._stats_lock:
            self._prefill_batches += 1
            self._host_syncs += 1
            self._launches["prefill"] += 1
            self._launch_ns["prefill"] += launch_ns
            self._t_last = end_ns * 1e-9

    def _first_tokens_out(self, seqs: List[_Seq], toks: List[int],
                          rows: List[int]):
        """The prompt is in the cache and each sequence's first token is
        on the host: stamp it, stream it, index the prompt's blocks.
        ``rows``: each sequence's row of the launch's ``next_logits``."""
        now = _now_ns()
        for seq, tok, row in zip(seqs, toks, rows):
            if seq.logits is not None:
                seq.logits.append(
                    (_logits_row(self._handles[0].value, row), 1))
            seq.pos = int(seq.prompt.size)
            seq.t_first_token_ns = now
            self._emit(seq, tok)
            self._promote(seq)
        with self._stats_lock:
            self._first_tokens += len(seqs)
            self._first_token_ns += sum(now - s.t_admit_ns for s in seqs)

    def _promote(self, seq: _Seq):
        """Index every freshly-written FULL prompt block for
        cross-request reuse.  Only blocks holding nothing but prompt
        tokens qualify ((j+1)*bs <= prompt_len) — generation writes
        start past them, so a promoted block's bytes never change."""
        idx = self._prefix_index
        if idx is None:
            return
        bs = self.config.block_size
        plen = int(seq.prompt.size)
        for j in range(seq.hit_blocks, plen // bs):
            idx.promote(seq.prompt, j, seq.block_ids[j])

    # -- chunked prefill --------------------------------------------------
    def _chunk_round(self):
        """One chunk per chunk-queued sequence per scheduling round —
        long prompts make progress WITHOUT monopolising the device
        between decode chains (the anti-head-of-line interleave)."""
        for seq in list(self._chunking):
            self._chunk_step(seq)

    def _chunk_feed(self, seq: _Seq, start: int, end: int, final: bool):
        width = self.config.chunk_width
        n = end - start
        src = np.zeros((1, width), np.int64)
        src[0, :n] = seq.prompt[start:end]
        pos = np.zeros((1, width), np.int64)
        pos[0, :n] = np.arange(start, end)
        slots = np.full((1, width), -1, np.int32)
        slots[0, :n] = [self._slot(seq, p) for p in range(start, end)]
        table = np.zeros((1, self._mbps), np.int32)
        table[0, :len(seq.block_ids)] = seq.block_ids
        ctx = np.array([end], np.int32)
        last = np.full((1, 1), n - 1 if final else 0, np.int64)
        feed = {"src_ids": src, "pos_ids": pos, "slot_ids": slots,
                "block_table": table, "ctx_len": ctx, "last_pos": last}
        if self._state_bytes:
            feed["state_slot"] = self._state_rows(1, [(0, seq)])
            # the sequence's first chunk starts its state from zero
            feed["state_fresh"] = np.array([start == 0], np.int32)
        return feed

    def _chunk_step(self, seq: _Seq):
        plen = int(seq.prompt.size)
        start = seq._chunk_off
        end = min(plen, start + self.config.chunk_width)
        final = end >= plen
        sid = next_step_id()
        _flight.note_step(sid, "decode_chunk", (start, end))
        with step_scope(sid), \
                RecordEvent("decode::chunk", tokens=end - start,
                            final=final,
                            state_rows=int(bool(self._state_bytes))):
            self._launching = ("chunk", 1, end - start, seq.rid)
            with self._phase("feed"):
                feed = self._chunk_feed(seq, start, end, final)
            # only the FINAL chunk's first generated token crosses to
            # the host — intermediate chunks stay async (a later
            # launch's sync waits them out and books their device time)
            toks, launch_ns, end_ns = self._launch(
                "chunk", self._chunk, feed, 1 if final else None)
            seq._chunk_off = end
            if final:
                with self._phase("emit"):
                    self._first_tokens_out([seq], [int(toks[0])], [0])
            self._launching = None
        with self._stats_lock:
            self._chunk_steps += 1
            self._chunk_tokens += end - start
            if final:
                self._host_syncs += 1
            self._launches["chunk"] += 1
            self._launch_ns["chunk"] += launch_ns
            self._t_last = end_ns * 1e-9
        if final:
            self._chunking.remove(seq)
            self._active.append(seq)

    # -- decode step ------------------------------------------------------
    def _decode_feed_arrays(self, bucket_b: int, live: List[_Seq],
                            pad_only: bool = False):
        tok = np.zeros((bucket_b,), np.int64)
        pos = np.zeros((bucket_b,), np.int64)
        slots = np.full((bucket_b, 1), -1, np.int32)
        table = np.zeros((bucket_b, self._mbps), np.int32)
        ctx = np.zeros((bucket_b,), np.int32)
        if not pad_only:
            for i, seq in enumerate(live):
                tok[i] = seq.out_tokens[-1]
                pos[i] = seq.pos
                slots[i, 0] = self._slot(seq, seq.pos)
                table[i, :len(seq.block_ids)] = seq.block_ids
                ctx[i] = seq.pos + 1
        feed = {"token_ids": tok, "pos_ids": pos, "slot_ids": slots,
                "block_table": table, "ctx_len": ctx}
        if self._state_bytes:
            feed["state_slot"] = self._state_rows(
                bucket_b, () if pad_only else enumerate(live))
        return feed

    def _chain_feed_arrays(self, bucket_b: int, live: List[_Seq],
                           pad_only: bool = False):
        """Chain feeds = decode-step feeds + the per-row chain-control
        vectors (remaining token budget, EOS id, sampling policy).
        Slot/ctx-len entries are placeholders — the device scan
        recomputes them per iteration from the block table."""
        cfg = self.config
        feed = self._decode_feed_arrays(bucket_b, live,
                                        pad_only=pad_only)
        left = np.zeros((bucket_b,), np.int32)
        eos = np.full((bucket_b,), -1, np.int64)
        if not pad_only:
            for i, seq in enumerate(live):
                left[i] = seq.max_new - len(seq.out_tokens)
                if seq.eos is not None:
                    eos[i] = int(seq.eos)
        feed["steps_left"] = left
        feed["eos_ids"] = eos
        if cfg.sampling:
            temp = np.zeros((bucket_b,), np.float32)
            top_k = np.zeros((bucket_b,), np.int32)
            top_p = np.zeros((bucket_b,), np.float32)
            seeds = np.zeros((bucket_b,), np.int32)
            if not pad_only:
                for i, seq in enumerate(live):
                    temp[i] = seq.temperature
                    top_k[i] = seq.top_k
                    top_p[i] = seq.top_p
                    seeds[i] = seq.seed
            feed.update({"temperature": temp, "top_k": top_k,
                         "top_p": top_p, "seeds": seeds})
        return feed

    def _pick_chain(self) -> int:
        """Chain-length scheduling: the SHORT chain when admittable
        work is waiting (a pending request that fits blocks + slots, or
        a prompt mid-chunk) so it isn't parked behind a long device
        loop; otherwise the smallest chain covering the longest
        remaining budget — no wasted scan iterations, no extra
        syncs."""
        cfg = self.config
        lengths = self._chain_lengths
        if len(lengths) == 1:
            return lengths[0]
        if self._chunking:
            return lengths[0]
        with self._cond:
            slots_left = (cfg.max_batch_size - len(self._active)
                          - len(self._chunking))
            if slots_left > 0:
                avail = self._availability()
                for seq in self._pending:
                    # full-span pricing here (ignores prefix hits) —
                    # conservative: at worst we chain short once more
                    need = blocks_needed(int(seq.prompt.size),
                                         seq.max_new, cfg.block_size)
                    if need <= avail:
                        return lengths[0]
        remaining = max(seq.max_new - len(seq.out_tokens)
                        for seq in self._active)
        for length in lengths:
            if length >= remaining:
                return length
        return lengths[-1]

    def _chain_step(self):
        """Run ONE device chain over every live sequence: L decode
        steps, one host sync.  -1 entries in the fetched [L, B] matrix
        mark rows that finished mid-chain (the device froze them)."""
        cfg = self.config
        live = self._active
        sid = next_step_id()
        with step_scope(sid), \
                RecordEvent("decode::chain", live=len(live)) as parent:
            with self._phase("feed"):
                length = self._pick_chain()
                self._launching = ("chain", len(live), length, live[0].rid)
                bucket_b = next(b for b in cfg.batch_buckets
                                if b >= len(live))
                parent.set(bucket=bucket_b, chain=length)
                if self._state_bytes:
                    parent.set(state_rows=bucket_b)
                feed = self._chain_feed_arrays(bucket_b, live)
                _flight.note_step(sid, "decode_chain",
                                  (length, bucket_b, len(live)))
            # tokens: [length, bucket_b]
            tokens, launch_ns, end_ns = self._launch(
                "chain", self._chains[length], feed, 0)
            emitted = 0
            with self._phase("emit"):
                for i, seq in enumerate(live):
                    if seq.logits is not None:
                        # [length, B, V] on the device: this row's steps
                        seq.logits.append(
                            (_logits_row(self._handles[1].value, i),
                             int((tokens[:, i] >= 0).sum())))
                for s in range(length):
                    for i, seq in enumerate(live):
                        tok = int(tokens[s, i])
                        if tok < 0:
                            continue
                        seq.pos += 1
                        seq.steps += 1
                        self._emit(seq, tok)
                        emitted += 1
                # the pages the chain's cache reads touched, from what
                # the host holds anyway: at step s a row's ctx is one
                # past its position, which advanced while it emitted
                advanced = np.minimum(np.arange(length)[:, None],
                                      (tokens >= 0).sum(axis=0)[None, :])
                ctx = feed["pos_ids"][None, :] + advanced + 1
                pages_read = int(np.clip(-(-ctx // cfg.block_size), 1,
                                         self._mbps).sum())
            self._launching = None
        with self._stats_lock:
            self._kv_pages_read += pages_read
            self._kv_pages_spanned += length * bucket_b * self._mbps
            if self._state_bytes:
                # every row of the bucket moves a slot every step; a row
                # that emitted a token moved a live sequence's
                self._state_rows_launched += length * bucket_b
                self._state_rows_live += emitted
            self._decode_steps += length
            self._chains_run += 1
            self._host_syncs += 1
            self._launches["chain"] += 1
            self._launch_ns["chain"] += launch_ns
            self._chain_tokens += emitted
            self._chain_hist[length] = \
                self._chain_hist.get(length, 0) + 1
            self._decode_batch_hist[len(live)] = \
                self._decode_batch_hist.get(len(live), 0) + 1
            self._t_last = end_ns * 1e-9

    def _emit(self, seq: _Seq, tok: int):
        seq.out_tokens.append(tok)
        with self._stats_lock:
            self._tokens_out += 1
        if seq.on_token is not None:
            try:
                seq.on_token(tok)
            except Exception:      # noqa: BLE001 — user callback
                pass
        if seq.eos is not None and tok == seq.eos:
            seq.done = True
            seq.reason = "eos"
        elif len(seq.out_tokens) >= seq.max_new:
            seq.done = True

    def _retire(self):
        # the phase it is in already
        with RecordEvent("decode::retire",
                         {"starved": int(self._phase_open[2])}):
            self._retire_finished()

    def _retire_finished(self):
        with self._stats_lock:
            in_use = sum(len(s.block_ids)
                         for s in self._active + self._chunking)
            self._peak_blocks = max(self._peak_blocks, in_use)
            self._state_peak = max(self._state_peak,
                                   self._state_slots_in_use())
        finished = [s for s in self._active if s.done]
        if not finished:
            return
        with self._cond:
            self._active = [s for s in self._active if not s.done]
            for seq in finished:
                self._retired_blocks.update(seq.block_ids)
                if seq.state_slot is not None:
                    self._retired_state.add(seq.state_slot)
                self._release_blocks(seq)
            self._cond.notify_all()
        now = _now_ns()
        for seq in finished:
            seq.future.set_result(GenerationResult(
                seq.out_tokens, int(seq.prompt.size), seq.reason,
                seq.steps,
                timing={"rid": seq.rid, "submit": seq.t_submit_ns,
                        "admit": seq.t_admit_ns,
                        "first_token": seq.t_first_token_ns,
                        "done": now},
                logit_parts=seq.logits))
        with self._stats_lock:
            self._completed += len(finished)

    def _blocks_in_use(self) -> int:
        """Pool blocks some live sequence actually holds: refcount-0
        index entries are cached CONTENT, not usage — they are
        reclaimable on demand, so they count as free."""
        evictable = self._prefix_index.evictable() \
            if self._prefix_index is not None else 0
        return self.pool_blocks - len(self._free) - evictable

    def _state_slots_in_use(self) -> int:
        return self._state_slots - len(self._free_state)

    # -- warmup -----------------------------------------------------------
    def warmup(self) -> int:
        """Compile (or AOT-cache-load) the WHOLE executable grid from
        canonical feeds: every prefill (batch x seq) bucket and every
        decode batch bucket.  All warmup writes carry slot -1 /
        ctx_len 0, so the cache pools stay bitwise untouched.  Returns
        the combo count — a warm restart under ``flag("aot_cache_dir")``
        resolves all of them with 0 fresh compiles."""
        cfg = self.config
        K = self._pack
        n = 0

        def stateful(feed, rows, fresh=False):
            """Warm-up rows name the scratch slot."""
            if self._state_bytes:
                feed["state_slot"] = self._state_rows(rows)
                if fresh:
                    feed["state_fresh"] = np.ones((rows,), np.int32)
            return feed

        with self._run_lock:
            for sb in cfg.prefill_seq_buckets:
                for bb in cfg.prefill_batch_buckets:
                    feed = {
                        "src_ids": np.zeros((bb, sb), np.int64),
                        "pos_ids": np.zeros((bb, sb), np.int64),
                        "input_mask": np.zeros((bb, sb, K), np.float32),
                        "slot_ids": np.full((bb, sb), -1, np.int32),
                        "last_pos": np.zeros((bb, K), np.int64),
                    }
                    self._acquire(self._prefill)
                    self._prefill.run(stateful(feed, bb, fresh=True))
                    n += 1
            for length in self._chain_lengths:
                for bb in cfg.batch_buckets:
                    self._acquire(self._chains[length])
                    self._chains[length].run(self._chain_feed_arrays(
                        bb, [], pad_only=True))
                    n += 1
            if self._chunk is not None:
                width = cfg.chunk_width
                self._acquire(self._chunk)
                self._chunk.run(stateful({
                    "src_ids": np.zeros((1, width), np.int64),
                    "pos_ids": np.zeros((1, width), np.int64),
                    "slot_ids": np.full((1, width), -1, np.int32),
                    "block_table": np.zeros((1, self._mbps), np.int32),
                    "ctx_len": np.zeros((1,), np.int32),
                    "last_pos": np.zeros((1, 1), np.int64),
                }, 1, fresh=True))
                n += 1
            if self._owner is not None:
                self._owner.wait()
        return n

    # -- reference loop ---------------------------------------------------
    def _score_buckets(self) -> Tuple[int, ...]:
        cfg = self.config
        out = set(cfg.prefill_seq_buckets)
        out.add(cfg.max_seq_len)
        return tuple(sorted(out))

    def greedy_reference(self, feed, max_new_tokens: Optional[int] = None,
                         eos_token_id: Optional[int] = None
                         ) -> GenerationResult:
        """The unbatched greedy loop — the parity oracle AND the honest
        baseline: re-scores the FULL prefix through the cache-free
        scoring program for every emitted token (prefix padded to the
        seq-bucket ladder, so its compile count stays bounded), exactly
        the reference AnalysisPredictor serving shape.  Runs on an
        isolated snapshot of the engine's weights, so live traffic
        cannot perturb it and it cannot perturb the cache.  Every
        engine-generated sequence must match this token-for-token."""
        cfg = self.config
        prompt = self._normalize_prompt(feed)
        max_new = cfg.max_new_tokens if max_new_tokens is None \
            else int(max_new_tokens)
        eos = cfg.eos_token_id if eos_token_id is None else eos_token_id
        if int(prompt.size) + max_new > cfg.max_seq_len:
            raise InvalidArgumentError(
                f"prompt ({prompt.size}) + max_new_tokens ({max_new}) "
                f"exceeds max_seq_len={cfg.max_seq_len}")
        seq = list(int(t) for t in prompt)
        out_tokens: List[int] = []
        reason = "length"
        for _ in range(max_new):
            tok = int(self._score_prefix(seq)[1].numpy()[0])
            out_tokens.append(tok)
            seq.append(tok)
            if eos is not None and tok == eos:
                reason = "eos"
                break
        return GenerationResult(out_tokens, int(prompt.size), reason,
                                len(out_tokens))

    def _score_prefix(self, seq):
        """One cache-free scoring pass over the token prefix ``seq``
        (padded to the score-bucket ladder) on the reference weight
        snapshot: the ``[next_logits, next_tokens]`` fetch handles."""
        cur = len(seq)
        sb = next(b for b in self._score_buckets() if b >= cur)
        src = np.zeros((1, sb), np.int64)
        src[0, :cur] = seq
        pos = np.zeros((1, sb), np.int64)
        pos[0, :cur] = np.arange(cur)
        mask = np.zeros((1, sb, 1), np.float32)
        mask[0, :cur, 0] = 1.0
        last = np.full((1, 1), cur - 1, np.int64)
        with self._ref_lock:
            if self._score is None:
                self._score = self._exe.prepare(
                    self._programs.score,
                    feed_names=self._programs.score_feeds,
                    fetch_list=list(self._programs.fetch_names),
                    scope=self._reference_scope(), donate_state=False)
            return self._score.run({
                "src_ids": src, "pos_ids": pos, "input_mask": mask,
                "last_pos": last})

    def _reference_scope(self):
        """The reference loop's weights: fresh device copies (a host copy
        would re-cross the host link on every scored token) of what the
        scope holds once the serving side's device-resident state has
        flowed back into it, taken between two device rounds.  Weights
        pass through the donated steps unchanged, so the copy is the
        same whenever it is taken."""
        if self._ref_scope is None:
            import jax.numpy as jnp
            from ..framework.executor import Scope
            ref = Scope()
            with self._run_lock:
                if self._owner is not None:
                    self._owner.sync_scope()
                for name in self._scope.var_names():
                    if name not in self._programs.cache_vars:
                        ref.set_var(name, jnp.array(
                            self._scope.find_var(name), copy=True))
            self._ref_scope = ref
        return self._ref_scope

    def reference_logits(self, tokens) -> np.ndarray:
        """Next-token logits ``[vocab]`` the parity oracle assigns after
        the prefix ``tokens`` — what a caller needs to judge a token
        mismatch against the reference's own top-2 margin on a backend
        whose matmuls round (the chip's bf16-input f32 matmuls)."""
        seq = [int(t) for t in self._normalize_prompt(tokens)]
        if len(seq) > self.config.max_seq_len:
            raise InvalidArgumentError(
                f"prefix ({len(seq)} tokens) exceeds "
                f"max_seq_len={self.config.max_seq_len}")
        return self._score_prefix(seq)[0].numpy()[0]

    @property
    def scope(self):
        """The scope the served programs run in (weights, pools,
        counters).  Read it only while no round runs: before ``start()``
        or after ``shutdown()`` / ``close()`` has joined the worker."""
        return self._scope

    def close(self, timeout: float = 60.0) -> bool:
        """Tear the engine down where it is: the worker stops at its next
        round boundary, what is queued or in flight fails with
        ``UnavailableError``, and the pools' and counters' device memory
        goes back (the weights stay in ``scope``).  False if the worker
        did not stop in ``timeout``; the engine cannot serve afterwards.
        ``shutdown()`` is the graceful end: in-flight requests finish
        and the pools stay."""
        with self._cond:
            self._abort = True
        if not self.shutdown(drain=False, timeout=timeout):
            return False
        if self._closed:
            return True
        if self._owner is not None:
            self._owner.sync_scope()
        for prepared in (self._prefill, self._chunk, *self._chains.values()):
            if prepared is not None:
                prepared.close()
                prepared._state = None
        for name in self._programs.cache_vars:
            self._scope.vars.pop(name, None)
        self._closed = True
        return True

    # -- observability ----------------------------------------------------
    @property
    def compiled_executables(self) -> int:
        n = len(self._prefill._steps)
        for prepared in self._chains.values():
            n += len(prepared._steps)
        if self._chunk is not None:
            n += len(self._chunk._steps)
        if self._score is not None:
            n += len(self._score._steps)
        return n

    def stats(self) -> Dict[str, Any]:
        if not self._counters:
            return self._stats()
        # device counters are read between two rounds; the host's
        # counters are read in the same gap, so the two agree
        with self._stats_lock:
            self._lock_wanted += 1
        try:
            with self._run_lock:
                out = self._stats()
                if self._owner is not None:
                    self._owner.sync_scope()
                for kind, counters in self._counters.items():
                    for name, pairs in counters.items():
                        total = np.asarray(self._scope.find_var(name)) \
                            .astype(np.uint32).astype(np.int64)
                        for key, reduce in pairs:
                            by_kind = out.setdefault(key, {})
                            by_kind[kind] = by_kind.get(kind, 0) \
                                + reduce(total)
        finally:
            with self._stats_lock:
                self._lock_wanted -= 1
        return out

    def _stats(self) -> Dict[str, Any]:
        with self._stats_lock:
            elapsed = None
            if self._t_first is not None and self._t_last is not None:
                elapsed = max(self._t_last - self._t_first, 1e-9)
            out = {
                "submitted": self._submitted,
                "completed": self._completed,
                "failed": self._failed,
                "rejected": self._rejected,
                "tokens_out": self._tokens_out,
                "tokens_per_s": (self._tokens_out / elapsed)
                if elapsed else 0.0,
                "decode_steps": self._decode_steps,
                "kv_pages_read": self._kv_pages_read,
                "kv_pages_spanned": self._kv_pages_spanned,
                "prefill_batches": self._prefill_batches,
                "decode_batch_hist": dict(self._decode_batch_hist),
                "admission_waits": self._admission_waits,
                "state_slots": self._state_slots,
                "state_slots_peak": self._state_peak,
                "state_slot_reuses": self._state_reuses,
                "state_bytes_per_slot": self._state_bytes,
                "state_rows_launched": self._state_rows_launched,
                "state_rows_live": self._state_rows_live,
                "block_reuses": self._block_reuses,
                "pool_blocks": self.pool_blocks,
                "peak_blocks_used": self._peak_blocks,
                "peak_occupancy": self._peak_blocks /
                max(1, self.pool_blocks),
                "host_syncs": self._host_syncs,
                "chains_run": self._chains_run,
                "chain_tokens": self._chain_tokens,
                "chain_hist": dict(self._chain_hist),
                "chunk_steps": self._chunk_steps,
                "chunk_tokens": self._chunk_tokens,
                "interleaved_rounds": self._interleaved_rounds,
                "prefill_tokens": self._prefill_tokens,
                "launches": dict(self._launches),
                "launch_ns": dict(self._launch_ns),
                "device_ns": dict(self._device_ns),
                "slow_phase_ns": dict(self._slow_phase_ns),
                "slow_phases": [list(r) for r in self._slow_phases],
                "admitted": self._admitted,
                "queue_wait_ns": self._queue_wait_ns,
                "first_tokens": self._first_tokens,
                "first_token_ns": self._first_token_ns,
            }
            phase_ns = dict(self._phase_ns)
            starved_ns = dict(self._starved_ns)
            if self._phase_open is not None:
                name, t0, starved = self._phase_open
                so_far = _now_ns() - t0
                phase_ns[name] += so_far
                if starved:
                    starved_ns[name] += so_far
            out["phase_ns"] = phase_ns
            out["starved_ns"] = starved_ns
        out["cache_blocks_used"] = self._blocks_in_use()
        out["state_slots_in_use"] = self._state_slots_in_use()
        out["compile_count"] = self.compiled_executables
        idx = self._prefix_index
        out["prefix_hits"] = idx.hits if idx is not None else 0
        out["prefix_misses"] = idx.misses if idx is not None else 0
        out["prefix_bytes_saved"] = idx.bytes_saved \
            if idx is not None else 0
        out["prefix_evictions"] = idx.evictions if idx is not None else 0
        out["prefix_indexed_blocks"] = len(idx) if idx is not None else 0
        with self._cond:
            out["pending"] = len(self._pending)
            out["active"] = len(self._active) + len(self._chunking)
            out["unhealthy"] = self._unhealthy is not None
        return out


__all__ = ["DecodeConfig", "DecodeEngine", "GenerationResult",
           "blocks_needed"]
