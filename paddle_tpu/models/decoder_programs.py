"""The five-program scaffold of a served decoder, written once: a model
gives a layer stack, a head and a description of its caches, and
:func:`build_decoder_programs` returns the :class:`DecoderPrograms`
``serving.DecodeEngine`` runs (prefill, decode step, chains, chunk, score
and the one startup).

What a model provides (``LatentDecoder``, ``HybridDecoder``):

* ``name``, ``seed``, ``cfg.hidden_size``, ``cfg.vocab_size``;
* ``declare_cache(block, num_blocks, block_size, state_slots)`` -> the
  cache persistables of the CURRENT program, in whatever structure its
  layers read them: **block pools** (``[num_blocks, block_size, width]``,
  addressed through ``slot_ids`` / ``block_table``) and, for a model with
  recurrent layers, **state pools** (``[state_slots, ...]``, one slot a
  sequence, addressed through ``state_slot``);
* ``body(ids, pos2d, cache, attn_bias, tag, lift_1d)`` -> the hidden
  states after the last layer; ``cache`` is a :class:`CacheFeeds` (None
  in the cache-free score program), ``tag`` the launch kind for device
  counters (False: none);
* ``head(h2d)`` -> (float32 ``next_logits``, ``next_tokens``);
* ``cache_vars(kinds)`` -> the names of every persistable the programs
  of those launch kinds may write.

Feeds are the engine's: a model with state pools (``state_slots > 0``)
gains ``state_slot`` ``[B]`` in every caching program and ``state_fresh``
``[B]`` (1: the row's sequence starts in this launch) in prefill and
chunk.  The chain programs return every step's logits stacked
(``chain_logits``), read only for requests that asked.
"""

from __future__ import annotations

from typing import Optional

from .. import layers
from ..framework.core import Program, program_guard
from ..framework.layer_helper import LayerHelper
from .decoder import DecoderPrograms, _gather_last, _mask_bias


class CacheFeeds:
    """Cache wiring of one program: the model's pools beside the feeds
    the cache ops read.  ``table`` is None where attention runs on fresh
    keys (packed prefill); ``state_slot`` / ``fresh`` are None for a model
    without state pools, ``fresh`` also in a decode step."""

    def __init__(self, pools, slots, table=None, ctx_len=None, q_pos=None,
                 state_slot=None, fresh=None):
        self.pools, self.slots = pools, slots
        self.table, self.ctx_len, self.q_pos = table, ctx_len, q_pos
        self.state_slot, self.fresh = state_slot, fresh


def _data(name, shape, dtype):
    return layers.data(name, shape=shape, dtype=dtype,
                       append_batch_size=False)


def _program(seed):
    main = Program()
    main.random_seed = seed
    main._is_test = True
    return main


class _Builder:
    def __init__(self, model, num_blocks, block_size, mbps, state_slots):
        self.model, self.cfg = model, model.cfg
        self.num_blocks, self.block_size = num_blocks, block_size
        self.mbps, self.state_slots = mbps, state_slots

    def _pools(self, main):
        return self.model.declare_cache(main.global_block(), self.num_blocks,
                                        self.block_size, self.state_slots)

    def _state_feeds(self, with_fresh):
        """(state_slot var, fresh var, their feed names) — nothing for a
        model without state pools."""
        if not self.state_slots:
            return None, None, []
        slot = _data("state_slot", [-1], "int32")
        if not with_fresh:
            return slot, None, ["state_slot"]
        return slot, _data("state_fresh", [-1], "int32"), \
            ["state_slot", "state_fresh"]

    def prefill(self, startup, pack_max_segments, score_only=False):
        main = _program(self.model.seed)
        k = 1 if score_only else pack_max_segments
        extra = []
        with program_guard(main, startup):
            src = _data("src_ids", [-1, -1], "int64")
            pos = _data("pos_ids", [-1, -1], "int64")
            mask = _data("input_mask", [-1, -1, k], "float32")
            last_pos = _data("last_pos", [-1, k], "int64")
            cache = None
            if not score_only:
                slots = _data("slot_ids", [-1, -1], "int32")
                state_slot, fresh, extra = self._state_feeds(True)
                cache = CacheFeeds(self._pools(main), slots,
                                   state_slot=state_slot, fresh=fresh)
            x = self.model.body(src, pos, cache, _mask_bias(mask),
                                False if score_only else "prefill")
            self.model.head(_gather_last(x, last_pos, self.cfg))
        feeds = ["src_ids", "pos_ids", "input_mask", "last_pos"]
        return main, feeds + ([] if score_only else ["slot_ids"] + extra)

    def _decode_feeds(self, main):
        tok = _data("token_ids", [-1], "int64")
        pos = _data("pos_ids", [-1], "int64")
        slots = _data("slot_ids", [-1, 1], "int32")
        table = _data("block_table", [-1, self.mbps], "int32")
        ctx_len = _data("ctx_len", [-1], "int32")
        state_slot, _, extra = self._state_feeds(False)
        cache = CacheFeeds(self._pools(main), slots, table, ctx_len,
                           state_slot=state_slot)
        return tok, pos, slots, table, ctx_len, cache, \
            ["token_ids", "pos_ids", "slot_ids", "block_table",
             "ctx_len"] + extra

    def _decode_body(self, tok, pos, cache):
        x = self.model.body(tok, layers.unsqueeze(pos, axes=[1]), cache,
                            None, "chain", lift_1d=True)
        return self.model.head(
            layers.reshape(x, [-1, self.cfg.hidden_size]))

    def decode(self, startup):
        main = _program(self.model.seed)
        with program_guard(main, startup):
            tok, pos, _, _, _, cache, feeds = self._decode_feeds(main)
            self._decode_body(tok, pos, cache)
        return main, feeds

    def chain(self, startup, chain_length, with_sampling):
        """The decode-step network plus the trailing ``decode_chain``
        marker (executor.lower_decode_chain), which also stacks every
        step's logits (``chain_logits`` [chain, B, V])."""
        main = _program(self.model.seed)
        with program_guard(main, startup):
            tok, pos, slots, table, ctx_len, cache, feeds = \
                self._decode_feeds(main)
            steps_left = _data("steps_left", [-1], "int32")
            eos_ids = _data("eos_ids", [-1], "int64")
            sample = {}
            if with_sampling:
                sample = {"Temperature": _data("temperature", [-1],
                                               "float32"),
                          "TopK": _data("top_k", [-1], "int32"),
                          "TopP": _data("top_p", [-1], "float32"),
                          "Seeds": _data("seeds", [-1], "int32")}
            logits, tokens = self._decode_body(tok, pos, cache)
            block = main.global_block()
            out = block.create_var(name="chain_tokens",
                                   shape=(chain_length, -1), dtype="int64")
            out_logits = block.create_var(
                name="chain_logits",
                shape=(chain_length, -1, self.cfg.vocab_size),
                dtype="float32")
            inputs = {"TokenIds": [tok], "PosIds": [pos],
                      "SlotIds": [slots], "BlockTable": [table],
                      "CtxLen": [ctx_len], "StepsLeft": [steps_left],
                      "EosIds": [eos_ids], "Logits": [logits],
                      "Tokens": [tokens]}
            inputs.update({k: [v] for k, v in sample.items()})
            LayerHelper("decode_chain").append_op(
                type="decode_chain", inputs=inputs,
                outputs={"Out": [out], "LogitsOut": [out_logits]},
                attrs={"chain_length": chain_length,
                       "block_size": self.block_size,
                       "with_sampling": bool(with_sampling)})
        feeds = feeds + ["steps_left", "eos_ids"]
        if with_sampling:
            feeds += ["temperature", "top_k", "top_p", "seeds"]
        return main, feeds

    def chunk(self, startup):
        """Chunked prefill: a ``[B, C]`` prompt slice that WRITES its
        cache rows into the pools and READS attention through the block
        table, absolute ``pos_ids`` doubling as the causal bound."""
        main = _program(self.model.seed)
        with program_guard(main, startup):
            src = _data("src_ids", [-1, -1], "int64")
            pos = _data("pos_ids", [-1, -1], "int64")
            slots = _data("slot_ids", [-1, -1], "int32")
            table = _data("block_table", [-1, self.mbps], "int32")
            ctx_len = _data("ctx_len", [-1], "int32")
            last_pos = _data("last_pos", [-1, 1], "int64")
            state_slot, fresh, extra = self._state_feeds(True)
            cache = CacheFeeds(self._pools(main), slots, table, ctx_len,
                               q_pos=pos, state_slot=state_slot, fresh=fresh)
            x = self.model.body(src, pos, cache, None, "chunk")
            self.model.head(_gather_last(x, last_pos, self.cfg))
        return main, ["src_ids", "pos_ids", "slot_ids", "block_table",
                      "ctx_len", "last_pos"] + extra


def build_decoder_programs(model, num_blocks: int, block_size: int,
                           max_blocks_per_seq: int,
                           pack_max_segments: int = 1,
                           chain_lengths: tuple = (),
                           with_sampling: bool = False,
                           chunk_tokens: Optional[int] = None,
                           state_slots: int = 0) -> DecoderPrograms:
    from ..framework import unique_name
    b = _Builder(model, num_blocks, block_size, max_blocks_per_seq,
                 int(state_slots))
    startup = Program()
    startup.random_seed = model.seed
    with unique_name.guard(f"{model.name}@"):
        prefill, prefill_feeds = b.prefill(startup, pack_max_segments)
        # the other builds re-declare the same parameters; their
        # initializer ops go to throwaway startups (device counters are
        # engine state, zeroed with the pools)
        decode, decode_feeds = b.decode(Program())
        score, score_feeds = b.prefill(Program(), 1, score_only=True)
        chains, chain_feeds = {}, []
        for length in chain_lengths:
            chains[int(length)], chain_feeds = b.chain(
                Program(), int(length), with_sampling)
        chunk, chunk_feeds = None, []
        kinds = ["prefill", "chain"]
        if chunk_tokens:
            chunk, chunk_feeds = b.chunk(Program())
            kinds.append("chunk")
    return DecoderPrograms(
        prefill=prefill, decode=decode, score=score, startup=startup,
        cache_vars=model.cache_vars(kinds),
        prefill_feeds=prefill_feeds, decode_feeds=decode_feeds,
        score_feeds=score_feeds, chains=chains, chain_feeds=chain_feeds,
        chain_fetch_names=["chain_tokens", "chain_logits"],
        chunk=chunk, chunk_feeds=chunk_feeds)


__all__ = ["CacheFeeds", "build_decoder_programs"]
