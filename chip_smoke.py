#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the
chip.

One process drives the main path once, through the entry points a user
calls, at the full width of BERT-base (random weights from a seed), and
checks what comes out by the repo's own means:

* **leg K** — every Pallas kernel family (flash attention and the
  one-tile attention the trainer runs at sequence 128, each incl. the
  hardware-PRNG dropout path, the paged decode attention of the serving
  cell's decode step, fused LayerNorm / add+LN, bias+GELU, the Adam
  update, the int8/int4 dequant-accumulate pair) compiled by Mosaic at the
  shapes the models use and at the bound each routing gate admits, each
  against its jnp reference;
* **leg A** — the trainer: the ``bert_pretrain.s128`` cell's program
  (BERT-base, batch 96, seq 128, 20 masks, pure-bf16 Adam, dropout 0.1)
  through ``fluid.Executor(fluid.TPUPlace(0))``, fed by the
  double-buffered ``DataLoader`` into ``exe.prepare(...).run`` and then
  through ``Executor.run``; then a dropout-free A/B of the Pallas flags
  from the same weights and batch;
* **leg B** — the server: ``DecodeEngine(BertDecoder(BertConfig.base()))``
  with the serving cell's pool and its one decode bucket of 128 rows,
  answering 8 mixed-length requests submitted together (120 pad rows
  beside them) through 8-step chains under a watchdog, checked against
  ``engine.greedy_reference``;
* **leg M** — the pre-norm RoPE/GQA decoder with dropless experts
  (models/decoder_lm.py) at Mellum2-12B-A2.5B's widths: the grouped-head
  windowed flash kernels against the materialised-scores reference (a
  query block at a time) at the window's edge (1 023 / 1 024 / 1 025),
  32 query heads on 4 and the timed cell's own 8 192 tokens, the
  grouped matmul kernels against ``lax.ragged_dot`` over ragged groups
  with an empty expert, then one period of the model (3 sliding layers +
  1 full, experts 0-15 of 64, a 24 576-row vocabulary slice, 8 192
  tokens: the ``mellum2_train.s8k`` cell's step) through
  ``exe.prepare(...).run`` under pure-bf16 Adam and a watchdog;
* **leg L** — the served latent-attention decoder
  (models/latent_decoder.py) at DeepSeek-V3's widths: the paged absorbed
  decode kernel against the EXPANDED attention at the serving cell's
  shapes (128 rows of 128 heads, contexts 1 / 16 / 17 / 4 095 / 6 144,
  NaN in every slot no context owns), the sigmoid group-limited router
  against the plain reference (sets equal, the bias only selects), the
  grouped products at 0-8 tokens an expert with empty experts, then the
  ``deepseek_v3_decode.reason_closed`` cell's own engine — its pool, its
  chunk program and its 128-row chains under a watchdog — serving four
  prompts of 1 024-4 096 tokens whose logits are held to the reference's
  full forward pass;
* **leg H** — the served hybrid decoder (models/hybrid_decoder.py) at
  Olmo-Hybrid-7B's widths: the paged decode attention over bfloat16 K/V
  pools at 30 heads of 128 (64 rows, contexts 1 / 16 / 17 / 4 095 /
  12 288, NaN in every slot no context owns) against the gathered rows;
  the recurrent delta-rule kernel (``gdn_decode``, 16 rows) carried through
  1 024 tokens inside one ``lax.scan`` against the float32 recurrence — the
  state itself is compared, beside the same scan with the state rounded
  to bfloat16 after every token —
  and the chunked kernel (``gdn_chunk``) on a 1 024-token chunk against
  the reference's token scan; then the ``olmo_hybrid_serve.doc_closed``
  cell's own engine — its K/V pool, its state pool, its chunk program and
  its 64-row chains with both kernels inside, under a watchdog — serving
  four prompts of 1 024-12 288 tokens whose logits are held to the
  reference's full forward pass on the committed limits;
* **leg C** — four chips (run when >= 4 devices are visible): Fleet dp4
  with the bucketed grad all-reduce at per-chip batch 96, dp4-vs-one-chip
  loss parity, one dp2 x tp2 step, one ZeRO-1 flat-shard-Adam step.

No TPU means a non-zero exit before any model is built.  ``--cpu-dry-run``
runs every leg at tiny width on the CPU backend (Pallas kernels in
interpret mode) to debug this script before spending chip time; it proves
nothing about the chip.  ``--legs K,A`` selects legs (the four-chip run
of leg C does not need to repeat A and B at four times the chip time).

Step times printed here are smoke timings, not measurements.  The last
line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import faulthandler
import functools
import json
import os
import re
import sys
import time

LEGS = ("K", "A", "B", "M", "L", "H", "C")


def _say(msg):
    print(msg, flush=True)


class Sizes:
    """Model and traffic sizes: the real ones, or the tiny dry-run ones."""

    def __init__(self, dry: bool):
        from paddle_tpu.models.bert import BertConfig
        from paddle_tpu.ops.pallas.fused_ops import BG_MAX_D, LN_MAX_D
        self.dry = dry
        if dry:
            self.cfg = BertConfig(
                vocab_size=1024, hidden_size=128, num_hidden_layers=1,
                num_attention_heads=2, intermediate_size=512,
                max_position_embeddings=128, type_vocab_size=2)
            self.batch, self.seq, self.masks = 8, 32, 4
            # kernel leg: (B, H, S, D) for flash; rows for [R, D]
            # kernels (a ragged edge block); (rows, D_ln, D_gelu) of the
            # gate-bound check (the interpreter has no VMEM limit to
            # find, so the dry run only walks the code)
            self.flash = (1, 1, 128, 64)
            self.tile = (2, 2)
            # paged decode attention: rows, pages a row, page, heads
            self.paged = (8, 8, 8, 2)
            self.rows = (130,)
            self.bound = (8, 256, 256)
        else:
            self.cfg = BertConfig.base()
            self.batch, self.seq, self.masks = 96, 128, 20
            self.flash = (2, 4, 256, 64)
            # one-tile attention: (batch rows, heads) at D = 64 — the
            # trainer's 12 heads, two grid steps of four rows
            self.tile = (8, 12)
            # the serving cell's decode step: 128 rows, 32 pages of 16
            self.paged = (128, 32, 16, 12)
            # batch * seq plus a ragged edge block; a decode step's rows
            self.rows = (96 * 128 + 8, 8)
            self.bound = (4 * 128, LN_MAX_D, BG_MAX_D)

    def nodrop(self, layers=None):
        cfg = dataclasses.replace(self.cfg, hidden_dropout_prob=0.0,
                                  attention_probs_dropout_prob=0.0)
        if layers is not None:
            cfg = dataclasses.replace(cfg, num_hidden_layers=layers)
        return cfg


# ---------------------------------------------------------------------------
# leg K — the Pallas kernels, one family at a time
# ---------------------------------------------------------------------------


def _rel(a, b):
    """Max abs error relative to the reference's scale."""
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(float(np.max(np.abs(b))),
                                             1e-30))


def _check_kernel(name, ker, ref, args, tol, gtol):
    """Forward and every input gradient of ``ker`` against ``ref``, each
    side one jitted executable; the reference multiplies at full f32
    precision (the chip's default rounds f32 matmul inputs to bf16)."""
    import jax
    import jax.numpy as jnp
    argn = tuple(range(len(args)))

    def both(f):
        def loss(*a):
            y = f(*a).astype(jnp.float32)
            return jnp.sum(jnp.sin(y)), y
        return jax.jit(jax.value_and_grad(loss, argnums=argn,
                                          has_aux=True))
    (_, yk), gk = both(ker)(*args)
    with jax.default_matmul_precision("highest"):
        (_, yr), gr = both(ref)(*args)
    e = _rel(yk, yr)
    eg = max(_rel(a, b) for a, b in zip(gk, gr))
    _say(f"  {name} {tuple(args[0].shape)} {args[0].dtype}: fwd rel err "
         f"{e:.2e}, grad rel err {eg:.2e}")
    assert e < tol and eg < gtol, (name, e, eg)


def leg_kernels(S: Sizes):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from paddle_tpu.ops.attention_ops import (_merge_heads, _split_heads,
                                              reference_attention)
    from paddle_tpu.ops.pallas import attention_tile as at
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.pallas import fused_ops as F
    from paddle_tpu.ops.pallas import quant_kernels as qk
    from paddle_tpu.ops.quantize_wire import (CompressionSpec,
                                              dequantize_blockwise,
                                              quantize_blockwise)

    interp = S.dry               # the CPU has no Mosaic: interpret mode
    rng = np.random.RandomState(0)

    def randn(*shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32))

    # -- flash attention: forward + both backward kernels, f32 and the
    # bf16 operands pure-bf16 AMP feeds it, plain and causal -------------
    B, H, Sq, D = S.flash
    BH = B * H
    q, k, v = randn(B, H, Sq, D), randn(B, H, Sq, D), randn(B, H, Sq, D)
    mask = (rng.rand(B, 1, 1, Sq) > 0.2).astype(np.float32)
    mask[..., 0] = 1.0           # every row keeps a visible key
    bias = jnp.asarray((1 - mask) * -1e9) * jnp.ones((1, 1, Sq, 1))

    def flat(t):
        return t.reshape(BH, Sq, D)

    for dtype, causal, tol in ((jnp.float32, False, 2e-2),
                               (jnp.float32, True, 2e-2),
                               (jnp.bfloat16, False, 4e-2)):
        _check_kernel(
            f"flash_attention causal={causal}",
            lambda q, k, v: flat(fa.flash_attention_bshd(
                q, k, v, bias.astype(dtype), causal=causal,
                interpret=interp)),
            lambda q, k, v: fa._reference(
                *(flat(t).astype(jnp.float32) for t in (q, k, v)),
                bias.reshape(B, Sq, Sq), causal=causal),
            tuple(t.astype(dtype) for t in (q, k, v)), tol, tol)

    # -- flash attention dropout: the hardware PRNG (chip only — the
    # interpreter stubs it) ----------------------------------------------
    if not interp:
        rate = 0.1
        seed = jnp.asarray([42], jnp.int32)

        def drop(q, k, v, seed=seed):
            return flat(fa.flash_attention_bshd(
                q, k, v, dropout_rate=rate, seed=seed))
        o1 = drop(q, k, v)
        assert float(jnp.max(jnp.abs(o1 - drop(q, k, v)))) == 0.0, \
            "dropout is not deterministic in its seed"
        assert float(jnp.max(jnp.abs(
            o1 - drop(q, k, v, jnp.asarray([7], jnp.int32))))) > 0, \
            "the dropout seed has no effect"
        # regenerate the keep-mask with a one-op kernel (same
        # _dropout_mask, same linear block index) and hold the flash
        # kernels to a jnp reference that applies that explicit mask:
        # the forward and both backward kernels must draw the SAME mask
        nq, nk = Sq // fa.BLOCK_Q, Sq // fa.BLOCK_K

        def mask_kernel(seed_ref, m_ref):
            b, qi, kj = (pl.program_id(i) for i in range(3))
            keep = fa._dropout_mask(seed_ref, (b * nq + qi) * nk + kj,
                                    (fa.BLOCK_Q, fa.BLOCK_K), rate)
            m_ref[0] = keep.astype(jnp.float32)

        keep = pl.pallas_call(
            mask_kernel, grid=(BH, nq, nk),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
            out_specs=pl.BlockSpec((1, fa.BLOCK_Q, fa.BLOCK_K),
                                   lambda b, i, j: (b, i, j),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((BH, Sq, Sq), jnp.float32),
        )(seed)
        kr = float(jnp.mean(keep))
        _say(f"  hardware keep rate {kr:.4f} (want {1 - rate})")
        assert abs(kr - (1 - rate)) < 0.01, kr

        def masked_ref(q, k, v):
            s = jnp.einsum("bsd,btd->bst", flat(q), flat(k)) / np.sqrt(D)
            pd = keep * jax.nn.softmax(s, -1) / (1.0 - rate)
            return jnp.einsum("bst,btd->bsd", pd, flat(v))
        _check_kernel("flash_attention dropout", drop, masked_ref,
                      (q, k, v), 2e-2, 2e-2)

    # -- one-tile attention (attn_tile_fwd / attn_tile_bwd): what
    # fused_attention lowers to at Sq == Sk == 128, non-causal, in the
    # op's own (B, S, H*D) layout with the trainer's [B, 1, S, S] bias ---
    Bt, Ht = S.tile
    St, Dt = at.TILE, 64
    qt, kt, vt = (randn(Bt, St, Ht * Dt) for _ in range(3))
    mt = (rng.rand(Bt, 1, St, St) > 0.2).astype(np.float32)
    mt[0, 0, 3, :] = 0.0          # a fully masked row: uniform, no NaN
    bias_t = jnp.asarray((mt - 1.0) * 1e4)

    def tile_ref(q, k, v):
        return reference_attention(
            *(t.astype(jnp.float32) for t in (q, k, v)), bias_t, Ht, 0.0,
            None, True)

    for dtype, tol in ((jnp.float32, 2e-2), (jnp.bfloat16, 4e-2)):
        _check_kernel(
            "attention_tile",
            lambda q, k, v: at.attention_tile_bsd(
                q, k, v, bias_t, n_head=Ht, interpret=interp),
            tile_ref, tuple(t.astype(dtype) for t in (qt, kt, vt)),
            tol, tol)

    if not interp:
        rate = 0.1
        seed = jnp.asarray([42], jnp.int32)

        def tile_drop(q, k, v, seed=seed):
            return at.attention_tile_bsd(q, k, v, bias_t, n_head=Ht,
                                         dropout_rate=rate, seed=seed)
        o1 = tile_drop(qt, kt, vt)
        assert float(jnp.max(jnp.abs(o1 - tile_drop(qt, kt, vt)))) == 0.0, \
            "one-tile dropout is not deterministic in its seed"
        assert float(jnp.max(jnp.abs(
            o1 - tile_drop(qt, kt, vt, jnp.asarray([7], jnp.int32))))) > 0, \
            "the one-tile dropout seed has no effect"
        # the kernels seed per (batch row, 128-lane group) and draw the
        # group's heads stacked along the rows: regenerate exactly that
        groups, per = Ht * Dt // at.LANES, at.LANES // Dt

        def tile_mask_kernel(seed_ref, m_ref):
            b, g = pl.program_id(0), pl.program_id(1)
            keep = fa._dropout_mask(seed_ref, b * groups + g,
                                    (per * St, St), rate)
            m_ref[0, 0] = keep.astype(jnp.float32)

        keep_t = pl.pallas_call(
            tile_mask_kernel, grid=(Bt, groups),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
            out_specs=pl.BlockSpec((1, 1, per * St, St),
                                   lambda b, g: (b, g, 0, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((Bt, groups, per * St, St),
                                           jnp.float32),
        )(seed).reshape(Bt, Ht, St, St)
        kr = float(jnp.mean(keep_t))
        _say(f"  one-tile hardware keep rate {kr:.4f} (want {1 - rate})")
        assert abs(kr - (1 - rate)) < 0.01, kr

        def tile_masked_ref(q, k, v):
            qh, kh, vh = (_split_heads(t, Ht) for t in (q, k, v))
            s = jnp.einsum("bhsd,bhtd->bhst", qh, kh) / np.sqrt(Dt)
            pd = keep_t * jax.nn.softmax(s + bias_t, -1) / (1.0 - rate)
            return _merge_heads(jnp.einsum("bhst,bhtd->bhsd", pd, vh))
        _check_kernel("attention_tile dropout", tile_drop,
                      tile_masked_ref, (qt, kt, vt), 2e-2, 2e-2)

    # -- paged decode attention (paged_decode_attn): what a decode step's
    # cached fused_attention lowers to, at the serving cell's shapes,
    # against the gather composition it replaces.  Live contexts drawn
    # like the cell's traffic (prompts 16-128, a uniform share of outputs
    # of 32-256) beside the edges (1, a whole page, a page and one, the
    # whole table, nothing, one past the table); blocks scattered over a
    # pool whose every slot holds values; then NaN in every slot no live
    # context owns, which the kernel must not read into its result ------
    from paddle_tpu.ops.cache_ops import ctx_len_bias, gather_cache
    from paddle_tpu.ops.pallas import paged_attention as pa
    Bp, pages, blk, Hp = S.paged
    width = Hp * 64
    span = pages * blk

    def lognormal(median, lo, hi):
        return np.clip(np.round(np.exp(rng.randn(Bp) * 0.6) * median),
                       lo, hi).astype(np.int64)
    ctx = lognormal(64, 16, 128) + (
        rng.rand(Bp) * lognormal(96, 32, 256)).astype(np.int64)
    ctx[:6] = [1, blk, blk + 1, span, 0, span + 1]
    ctx = np.minimum(ctx, span + 1).astype(np.int32)
    need = np.clip(-(-ctx // blk), 1, pages)
    n_blocks = Bp * pages
    order = rng.permutation(n_blocks - 1) + 1        # block 0: nobody's
    table = np.zeros((Bp, pages), np.int32)
    owned = np.zeros((n_blocks, blk), bool)
    at_blk = 0
    for b in range(Bp):
        table[b, :need[b]] = order[at_blk:at_blk + need[b]]
        at_blk += need[b]
        for t in range(min(int(ctx[b]), span)):
            owned[table[b, t // blk], t % blk] = True
    qp = randn(Bp, 1, width)
    kp, vp = randn(n_blocks, blk, width), randn(n_blocks, blk, width)
    tb, cl = jnp.asarray(table), jnp.asarray(ctx)

    def gathered(q, kp, vp):
        keys, vals = gather_cache(kp, tb), gather_cache(vp, tb)
        return reference_attention(q, keys, vals,
                                   ctx_len_bias(cl, span), Hp, 0.0, None,
                                   True)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(gathered)(qp, kp, vp))
    live = ctx > 0
    hole = jnp.asarray(owned)[:, :, None]
    for name, k_in, v_in in (
            ("every slot holds values", kp, vp),
            ("NaN outside the live contexts",
             jnp.where(hole, kp, jnp.nan), jnp.where(hole, vp, jnp.nan))):
        got = np.asarray(pa.paged_decode_attention(
            qp, k_in, v_in, tb, cl, n_head=Hp, interpret=interp))
        assert np.isfinite(got).all(), name
        assert not got[~live].any(), "a row with nothing live wrote"
        e = _rel(got[live], want[live])
        _say(f"  paged_decode_attn {tuple(qp.shape)} over "
             f"{tuple(kp.shape)}, {int(need.sum())} of {Bp * pages} "
             f"pages live, {name}: rel err {e:.2e}")
        assert e < 1e-5, (name, e)

    # -- [R, D] kernels at the model's shapes, then each family at the
    # widest D its routing gate admits: a gate must not admit what the
    # compiler refuses ----------------------------------------------------
    def ln_ref(a, s_, b_):
        mu = jnp.mean(a, -1, keepdims=True)
        var = jnp.mean((a - mu) ** 2, -1, keepdims=True)
        return (a - mu) * jax.lax.rsqrt(var + 1e-5) * s_ + b_

    def row_kernels(R, d_ln, d_bg):
        sc = jnp.asarray((rng.rand(d_ln) + 0.5).astype(np.float32))
        _check_kernel(
            "fused_layer_norm",
            lambda a, s_, b_: F.layer_norm(a, s_, b_, 1e-5, interp),
            ln_ref, (randn(R, d_ln), sc, randn(d_ln)), 1e-4, 1e-3)
        _check_kernel(
            "fused_add_layer_norm",
            lambda a, b2, s_, b_: F.add_layer_norm(a, b2, s_, b_, 1e-5,
                                                   interp),
            lambda a, b2, s_, b_: ln_ref(a + b2, s_, b_),
            (randn(R, d_ln), randn(R, d_ln), sc, randn(d_ln)), 1e-4, 1e-3)
        _check_kernel(
            "fused_bias_gelu", lambda a, b_: F.bias_gelu(a, b_, interp),
            lambda a, b_: jax.nn.gelu(a + b_, approximate=False),
            (randn(R, d_bg), randn(d_bg)), 1e-4, 1e-3)

    for R in S.rows:
        row_kernels(R, S.cfg.hidden_size, S.cfg.intermediate_size)
    _say("  ... at the gate bounds:")
    row_kernels(*S.bound)

    # -- Adam: the op is one XLA composition (no kernel: PERF.md section
    # 6, PR 30), on the chip against float64 on the host, at the
    # trainers' shapes: 1-D (a bias, a ZeRO shard with a ragged row
    # count, the word embedding's numel), stacked experts, the word
    # embedding (30522 rows, two past a sublane tile), a router's 64
    # columns (half a lane tile) ----------------------------------------
    from paddle_tpu.ops.registry import get_op
    h, vocab = S.cfg.hidden_size, S.cfg.vocab_size
    shapes = ((1024,), (128 * 1027,), (vocab * h,),
              (16, 3 * h, 7 * 128), (vocab, h), (3 * h, 64))
    lr, b1p, b2p = (jnp.full((1,), c, jnp.float32)
                    for c in (0.01, 0.9, 0.999))
    for shape in shapes[:1] + shapes[3:] if S.dry else shapes:
        p0, g0, m0, v0 = (randn(*shape), randn(*shape), randn(*shape),
                          jnp.abs(randn(*shape)))
        out = jax.jit(lambda p, g, m, v: get_op("adam")(None, {
            "Param": [p], "Grad": [g], "Moment1": [m], "Moment2": [v],
            "LearningRate": [lr], "Beta1Pow": [b1p], "Beta2Pow": [b2p]},
            {}))(p0, g0, m0, v0)
        p64, g64, m64, v64 = (np.asarray(t, np.float64)
                              for t in (p0, g0, m0, v0))
        m_ref = 0.9 * m64 + 0.1 * g64
        v_ref = 0.999 * v64 + 0.001 * g64 * g64
        p_ref = p64 - 0.01 * np.sqrt(1 - 0.999) / (1 - 0.9) * m_ref / (
            np.sqrt(v_ref) + 1e-8)
        e = max(_rel(out["ParamOut"], p_ref), _rel(out["Moment1Out"], m_ref),
                _rel(out["Moment2Out"], v_ref))
        _say(f"  adam {shape}: rel err {e:.2e}")
        assert e < 1e-5, (shape, e)
        assert all(out[k].shape == shape for k in
                   ("ParamOut", "Moment1Out", "Moment2Out")), shape

    # -- quantized-collective receive stage (needs no mesh: the kernels
    # take the post-all_to_all payload) ----------------------------------
    n_peers, blocks = 4, 40
    for dtype in ("int8", "int4"):
        spec = CompressionSpec(dtype, block_size=256)
        ok, why = qk.supported(n_peers, blocks, spec, backend="tpu")
        assert ok, why
        payload, scales = quantize_blockwise(
            randn(n_peers * blocks * 256), spec)
        want = dequantize_blockwise(payload, scales, spec) \
            .reshape(n_peers, -1).sum(0)
        got = qk.dequant_accumulate(payload, scales, spec, n_peers,
                                    interpret=interp)
        e = _rel(got, want)
        _say(f"  dequant_accumulate {dtype}: rel err {e:.2e}")
        assert e < 1e-5, (dtype, e)
        if dtype == "int8":
            q2, s2 = qk.dequant_accumulate_requant(
                payload, scales, spec, n_peers, interpret=interp)
            e = _rel(dequantize_blockwise(q2, s2, spec), want)
            _say(f"  dequant_accumulate_requant int8: rel err {e:.2e} "
                 f"(one int8 rounding)")
            assert e < 2e-2, e


# ---------------------------------------------------------------------------
# shared: programs, route counters, device placement
# ---------------------------------------------------------------------------


def _build_pretrain(cfg, seed=0):
    """The ``s128`` cell's program: BERT pretrain + pure-bf16 Adam."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.contrib.mixed_precision import decorate
    from paddle_tpu.models import bert
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        _, total, _, _ = bert.build_pretrain_network(cfg)
        decorate(fluid.optimizer.Adam(1e-4),
                 use_pure_bf16=True).minimize(total)
    return main, startup, total


def _route_counters():
    """{(op, kernel, outcome, reason): count} from the registry's
    pallas_routes counters."""
    from paddle_tpu.observability import metrics
    out = {}
    for m in metrics.metrics_snapshot(include_serving=False)["metrics"]:
        if m["name"] == "pallas_routes":
            lb = m["labels"]
            out[(lb["op"], lb["kernel"], lb["outcome"], lb["reason"])] = \
                int(m["value"])
    return out


def _routes_since(before):
    now = _route_counters()
    return {k: v - before.get(k, 0) for k, v in now.items()
            if v - before.get(k, 0)}


def _check_routes(routes, S: Sizes, want_hits, allowed_fallbacks):
    """Print every routing decision by name; fail on a ``backend:``
    reason (the device went missing) or a fallback nobody expects."""
    for (op, kernel, outcome, reason), n in sorted(routes.items()):
        _say(f"  route {op} -> {kernel}: {outcome} x{n} ({reason})")
    if S.dry:
        # the CPU routes nothing onto Mosaic; the counters must say so
        assert routes and all(
            k[2] == "fallback" and "backend:cpu" in k[3]
            for k in routes), routes
        return
    hits = {k[1] for k in routes if k[2] == "hit"}
    assert not set(want_hits) - hits, \
        f"no Pallas hit for {sorted(set(want_hits) - hits)}"
    for (op, kernel, outcome, reason), n in routes.items():
        if outcome != "fallback":
            continue
        assert "backend:" not in reason, \
            f"{op}: the kernel tier saw no TPU ({reason})"
        assert any(re.fullmatch(pat, f"{kernel}|{reason}")
                   for pat in allowed_fallbacks), \
            f"unexpected Pallas fallback {op} -> {kernel}: {reason}"


def _assert_on_device(scope, device):
    """Every persistable of the scope is a jax.Array on ``device``."""
    import jax
    names = scope.var_names()
    assert names
    for name in names:
        v = scope.find_var(name)
        assert isinstance(v, jax.Array), (name, type(v))
        assert v.devices() == {device}, (name, v.devices())


def _compiles():
    from paddle_tpu.monitor import stat
    return int(stat("executor_compile_count").get())


# ---------------------------------------------------------------------------
# leg A — the trainer
# ---------------------------------------------------------------------------

#: |loss(flags on) - loss(flags off)| / loss, every A/B step: the two
#: sides differ by where bf16 rounding lands (flash accumulates P@V in
#: f32 from bf16 operands; the composition rounds P to bf16 first).
#: Half a bf16 ulp (2^-8); the v5e measured 1.0e-4 (PR 21).
AB_REL_TOL = 2e-3


def leg_trainer(S: Sizes, platform: str):
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.dataloader import DataLoader
    from paddle_tpu.models import bert

    n_prepared, n_run = 8, 2
    rng = np.random.RandomState(0)
    batch = bert.make_fake_batch(rng, S.cfg, batch_size=S.batch,
                                 seq_len=S.seq, num_masks=S.masks)
    main, startup, total = _build_pretrain(S.cfg)
    routes0 = _route_counters()
    exe = fluid.Executor(fluid.TPUPlace(0))
    assert exe._device.platform == platform, exe._device
    with fluid.scope_guard(fluid.Scope()):
        scope = fluid.global_scope()
        exe.run(startup)

        # hot loop 1: DataLoader double buffer -> PreparedStep.run
        loader = DataLoader.from_generator(capacity=4,
                                           use_double_buffer=True)
        loader.set_batch_generator(
            lambda: (batch for _ in range(n_prepared)),
            places=fluid.TPUPlace(0))
        prepared = exe.prepare(main, fetch_list=[total])
        losses, times = [], []
        flat_from = None
        t0 = time.perf_counter()
        for handles in loader.run_prepared(prepared):
            losses.append(float(handles[0]))      # blocks on the step
            times.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            if flat_from is None:
                flat_from = _compiles()
        assert len(losses) == n_prepared, losses
        assert _compiles() == flat_from, \
            "PreparedStep.run recompiled after its first step"
        assert all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0], \
            f"loss did not fall on a repeated batch: {losses}"
        _say(f"  prepared loop: loss {losses[0]:.4f} -> {losses[-1]:.4f} "
             f"over {n_prepared} steps; smoke timings first step "
             f"{times[0]:.1f} s (compile), later "
             f"{1e3 * float(np.median(times[1:])):.0f} ms/step")

        # hot loop 2: Executor.run on the same scope
        flat_from = None
        for _ in range(n_run):
            l, = exe.run(main, feed=batch, fetch_list=[total])
            assert np.isfinite(l).all(), l
            if flat_from is None:
                flat_from = _compiles()
        assert _compiles() == flat_from, \
            "Executor.run recompiled after its first step"
        assert float(l) < losses[0], (float(l), losses[0])
        _say(f"  Executor.run: loss {float(l):.4f} after {n_run} more")
        _assert_on_device(scope, exe._device)

    _check_routes(
        _routes_since(routes0), S,
        want_hits=("attention_tile", "fused_layer_norm"),
        allowed_fallbacks=())

    # -- A/B: dropout off, Pallas flags on vs off, same weights (same
    # startup seed) and batch; every step's loss within AB_REL_TOL ------
    from paddle_tpu import flags
    main, startup, total = _build_pretrain(S.nodrop())
    ab = {}
    saved = flags.get_flags(["use_flash_attention", "use_pallas_fused"])
    try:
        for side in (True, False):
            flags.set_flags({"use_flash_attention": side,
                             "use_pallas_fused": side})
            with fluid.scope_guard(fluid.Scope()):
                exe.run(startup)
                ab[side] = [float(exe.run(main, feed=batch,
                                          fetch_list=[total])[0])
                            for _ in range(3)]
    finally:
        flags.set_flags(saved)
    rel = max(abs(a - b) / abs(b) for a, b in zip(ab[True], ab[False]))
    _say(f"  A/B dropout off: flags on {ab[True]} vs off {ab[False]}, "
         f"max rel diff {rel:.2e} (tolerance {AB_REL_TOL})")
    assert rel < AB_REL_TOL, (ab, rel)
    assert ab[True][-1] < ab[True][0] and ab[False][-1] < ab[False][0], ab


# ---------------------------------------------------------------------------
# leg M — the RoPE/GQA decoder with dropless experts
# ---------------------------------------------------------------------------

MELLUM_ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782},
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}


def _gqa_reference_by_blocks(q, k, v, *, n_head, n_kv_head, window, blk):
    """``flash_gqa.reference`` a query block at a time: the scores of
    ``blk`` queries against every key, never ``[S, S]`` a head (8.6 GB
    at the cell's shapes), recomputed in the backward pass."""
    import jax
    import jax.numpy as jnp
    b, s, _ = q.shape
    d, group = q.shape[-1] // n_head, n_head // n_kv_head
    kh, vh = (t.reshape(b, s, n_kv_head, d) for t in (k, v))
    cols = jnp.arange(s)[None, :]

    @jax.checkpoint
    def block(qb, row0):
        rows = row0 + jnp.arange(blk)[:, None]
        ok = cols <= rows
        if window:
            ok = ok & (rows - cols < window)
        scores = jnp.einsum(
            "bqkgd,btkd->bkgqt", qb.reshape(b, blk, n_kv_head, group, d),
            kh, preferred_element_type=jnp.float32) / d ** 0.5
        probs = jax.nn.softmax(jnp.where(ok, scores, -1e30), axis=-1)
        return jnp.einsum("bkgqt,btkd->bqkgd", probs, vh).reshape(
            b, blk, n_head * d)

    out = jax.lax.map(
        lambda a: block(*a),
        (q.reshape(b, s // blk, blk, -1).swapaxes(0, 1),
         jnp.arange(0, s, blk)))
    return out.swapaxes(0, 1).reshape(q.shape)


def leg_decoder_lm(S: Sizes, platform: str):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.contrib.mixed_precision import decorate
    from paddle_tpu.models import decoder_lm as dl
    from paddle_tpu.ops.decoder_lm_ops import grouped_ffn
    from paddle_tpu.ops.pallas import flash_gqa as fg

    interp = S.dry
    rng = np.random.RandomState(0)

    def randn(*shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32))

    # -- attention: the window's edge, the model's head grouping, then
    # the timed cell's own shapes (8 192 tokens, 32 heads on 4, bf16) ----
    f32_bf16 = ((jnp.float32, 2e-2), (jnp.bfloat16, 4e-2))
    bf16 = f32_bf16[1:]
    if S.dry:
        d, blk, sub = 16, 8, 4
        cases = ((32, 7, 2, 1, f32_bf16), (32, 8, 2, 1, f32_bf16),
                 (32, 9, 4, 2, f32_bf16), (32, None, 4, 2, f32_bf16),
                 (64, 9, 8, 1, bf16), (64, None, 8, 1, bf16))
    else:
        d, blk, sub = 128, None, None
        cases = ((2048, 1023, 8, 1, f32_bf16), (2048, 1024, 8, 1, f32_bf16),
                 (2048, 1025, 8, 1, f32_bf16), (2048, 1024, 32, 4, f32_bf16),
                 (2048, None, 32, 4, f32_bf16),
                 (8192, 1024, 32, 4, bf16), (8192, None, 32, 4, bf16))
    for seq, window, h, hkv, dtypes in cases:
        args = (randn(1, seq, h * d), randn(1, seq, hkv * d),
                randn(1, seq, hkv * d))
        for dtype, tol in dtypes:
            kw = dict(n_head=h, n_kv_head=hkv, window=window)
            _check_kernel(
                f"flash_gqa window={window} heads={h}/{hkv}",
                lambda q, k, v: fg.flash_gqa_bsd(q, k, v, block=blk, sub=sub,
                                                 interpret=interp, **kw),
                lambda q, k, v: _gqa_reference_by_blocks(
                    *(t.astype(jnp.float32) for t in (q, k, v)),
                    blk=min(seq, 512), **kw),
                tuple(t.astype(dtype) for t in args), tol, tol)
    # how often the sub-tiling engages in the cell's two kinds of layer
    tile = fg.pick_sub(fg.pick_block(seq, blk), sub)
    for _, window, *_ in cases[-2:]:
        plain, masked, skipped = fg.subtile_counts(seq, tile.blk, sub, window)
        _say(f"  flash_gqa window={window} seq={seq}: {tile.rows} x "
             f"{tile.lanes} sub-tiles of {tile.blk}-tiles a kernel call and "
             f"query head: {plain} plain, {masked} masked, {skipped} skipped")

    # -- grouped products: ragged groups, one expert empty ---------------
    if S.dry:
        n, dm, f, e, el, k, tiles = 64, 16, 24, 8, 3, 2, (8,)
    else:
        n, dm, f, e, el, k, tiles = 2048, 2304, 896, 64, 16, 8, \
            (128, 256, 512)
    xs = randn(n, dm)
    wg, wu = randn(el, dm, f) * 0.02, randn(el, dm, f) * 0.02
    wd = randn(el, f, dm) * 0.02
    # a skewed router: expert 0 takes every token, expert 1 none
    logits = np.array(randn(n, e))
    logits[:, 0] = 30.0
    logits[:, 1] = -1e9
    w, idx = jax.lax.top_k(jax.nn.softmax(jnp.asarray(logits)), k)
    idx = idx.astype(jnp.int32)
    for tile_m in tiles:
        def run(backend, dtype):
            def loss(x_, w_, wg_, wu_, wd_):
                out, counts = grouped_ffn(
                    x_.astype(dtype), w_, idx, wg_.astype(dtype),
                    wu_.astype(dtype), wd_.astype(dtype), backend=backend,
                    tile_m=tile_m)
                return jnp.sum(jnp.sin(out.astype(jnp.float32))), counts
            return jax.jit(jax.value_and_grad(
                loss, argnums=(0, 1, 2, 3, 4), has_aux=True))
        ker = run("pallas_interpret" if interp else "pallas", jnp.bfloat16
                  if not interp else jnp.float32)
        (lk, ck), gk = ker(xs, w, wg, wu, wd)
        with jax.default_matmul_precision("highest"):
            (lr, cr), gr = run("xla", jnp.float32)(xs, w, wg, wu, wd)
        assert np.array_equal(np.asarray(ck), np.asarray(cr)), (ck, cr)
        assert int(ck[1]) == 0 and int(ck[0]) == n, ck
        eg = max(_rel(a, b) for a, b in zip(gk, gr))
        _say(f"  moe_gmm tile {tile_m}: counts {np.asarray(ck).tolist()}, "
             f"loss {float(lk):.4f} vs {float(lr):.4f}, grad rel err "
             f"{eg:.2e}")
        assert abs(float(lk) - float(lr)) < 2e-2 * max(abs(float(lr)), 1)
        assert eg < 6e-2, eg
        if not interp:
            jax.block_until_ready(ker(xs, w, wg, wu, wd))
            t0 = time.perf_counter()
            for _ in range(5):
                out = ker(xs, w, wg, wu, wd)
            jax.block_until_ready(out)
            _say(f"  moe_gmm tile {tile_m}: smoke timing "
                 f"{1e3 * (time.perf_counter() - t0) / 5:.2f} ms a "
                 f"forward + backward at {n} tokens")

    # -- the model: one period through the prepared path -----------------
    if S.dry:
        cfg = dataclasses.replace(dl.DecoderLMConfig.tiny(),
                                  held_experts=(0, 2))
        seq, steps = 32, 3
    else:
        cfg = dl.DecoderLMConfig(
            vocab_size=24576, num_hidden_layers=4, held_experts=(0, 16),
            rope_parameters=MELLUM_ROPE)
        seq, steps = 8192, 4
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        _, loss, _ = dl.build_lm_network(cfg)
        decorate(fluid.optimizer.Adam(1e-4),
                 use_pure_bf16=True).minimize(loss)
    batch = dl.make_fake_batch(rng, cfg, batch_size=1, seq_len=seq)
    routes0 = _route_counters()
    exe = fluid.Executor(fluid.TPUPlace(0))
    # kernels that pass alone can still hang inside the whole step
    # (PERF.md question 27): the steps run under a watchdog that dumps
    # every thread's stack and ends the process instead of the chip call
    faulthandler.dump_traceback_later(600, exit=True)
    try:
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            prepared = exe.prepare(main, fetch_list=[loss])
            losses, times, flat_from = [], [], None
            for _ in range(steps):
                t0 = time.perf_counter()
                losses.append(float(prepared.run(batch)[0]))
                times.append(time.perf_counter() - t0)
                if flat_from is None:
                    flat_from = _compiles()
            prepared.wait()
            assert _compiles() == flat_from, \
                "the decoder LM's step recompiled after its first step"
            stats = dict(prepared.stats)
            mem = jax.devices()[0].memory_stats() or {}
            prepared.sync_scope()
            _assert_on_device(fluid.global_scope(), exe._device)
    finally:
        faulthandler.cancel_dump_traceback_later()
    assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses
    expect = steps * cfg.num_hidden_layers * seq \
        * cfg.num_experts_per_tok * (cfg.held_experts[1]
                                     - cfg.held_experts[0]) \
        / cfg.num_experts
    _say(f"  decoder LM: loss {losses[0]:.4f} -> {losses[-1]:.4f} over "
         f"{steps} steps of {seq} tokens; smoke timings first step "
         f"{times[0]:.1f} s (compile), later "
         f"{1e3 * float(np.median(times[1:])):.0f} ms/step; "
         f"moe_assignments_local {stats['moe_assignments_local']} "
         f"(uniform routing would give {expect:.0f}), load max/mean "
         f"{stats['moe_expert_load_max'] / stats['moe_expert_load_mean']:.3f}"
         f"; peak_bytes_in_use {mem.get('peak_bytes_in_use')}")
    assert 0.5 * expect < stats["moe_assignments_local"] < 2 * expect
    _check_routes(
        _routes_since(routes0), S,
        want_hits=("flash_gqa_attention", "moe_grouped_matmul"),
        allowed_fallbacks=())


# ---------------------------------------------------------------------------
# leg B — the server
# ---------------------------------------------------------------------------


def _token_parity(engine, prompts, got, want):
    """Engine tokens ``got`` against the oracle's ``want``: exact where
    it holds.  The chip multiplies f32 matmuls at bf16 input precision,
    and the engine and the oracle reach the same logits through
    differently-shaped executables, so a near-tie can flip; the weaker
    statement that must then hold is that at the FIRST mismatch (same
    prefix on both sides) the engine's token trails the oracle's top
    token, in the oracle's own logits, by no more than four times the
    oracle's measured rounding noise e — its logits at default vs
    highest matmul precision on that same prefix.  (The engine prefers
    its token, so the exact margin is at most 2e of engine error; the
    oracle's reading of that margin adds its own 2e.)  Returns what was
    asserted."""
    import jax
    import numpy as np
    diverged = [(i, int(np.argmax(g != w)))
                for i, (g, w) in enumerate(zip(got, want))
                if not np.array_equal(g, w)]
    if not diverged:
        return "exact token parity with greedy_reference"
    worst = 0.0
    for i, t in diverged:
        prefix = np.concatenate([prompts[i], want[i][:t]])
        logits = engine.reference_logits(prefix)
        with jax.default_matmul_precision("highest"):
            exact = engine.reference_logits(prefix)
        noise = float(np.max(np.abs(logits - exact)))
        margin = float(logits[want[i][t]] - logits[got[i][t]])
        _say(f"  request {i}: first mismatch at token {t}, oracle margin "
             f"{margin:.3e}, oracle noise {noise:.3e}")
        assert 0.0 <= margin <= 4.0 * noise, (i, t, margin, noise)
        worst = max(worst, margin / max(noise, 1e-30))
    return (f"{len(got) - len(diverged)}/{len(got)} exact; "
            f"{len(diverged)} first mismatches inside 4x the oracle's "
            f"measured matmul rounding noise (worst {worst:.2f}x)")


def leg_server(S: Sizes, platform: str):
    import numpy as np
    from paddle_tpu.models.decoder import BertDecoder
    from paddle_tpu.serving import DecodeConfig, DecodeEngine

    if S.dry:
        # wider weights: the tiny untrained decoder otherwise repeats one
        # token and parity says little
        cfg = dataclasses.replace(S.cfg, initializer_range=0.5)
        dcfg = DecodeConfig(block_size=8, max_seq_len=64,
                            max_batch_size=8, batch_buckets=(8,),
                            prefill_seq_buckets=(32,),
                            prefill_batch_buckets=(8,),
                            chain_lengths=(1, 4), prefix_cache=True)
        plens, max_new, long_chain = range(4, 33, 4), 8, 4
    else:
        # BertConfig.base() as it is.  On the v5e (PR 21) its tokens
        # matched the oracle exactly, 8 of 8; the same width at
        # initializer_range 0.5 matched 6 of 8, the two first mismatches
        # at 0.06x the oracle's own rounding noise (logits in the
        # hundreds, +-4 of bf16-input noise) — the weaker branch of
        # _token_parity, which is why it exists.
        cfg = S.cfg
        # the serving cell's engine where the decode step is concerned:
        # pages of 16 (two f32 tiles, which the paged kernel copies
        # whole), 32 a row, the full-occupancy pool of 4 096 blocks
        # (4.83 GB) and ONE decode bucket of 128 rows, so the chain
        # executables are the cell's own and the 8 requests decode
        # beside 120 pad rows (position 0, a table of zeros)
        dcfg = DecodeConfig(block_size=16, max_seq_len=512,
                            max_batch_size=128, batch_buckets=(128,),
                            prefill_seq_buckets=(128,),
                            prefill_batch_buckets=(1, 2, 4, 8),
                            chain_lengths=(1, 8), prefix_cache=True)
        plens, max_new, long_chain = range(16, 129, 16), 32, 8
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int64)
               for n in plens]
    assert len(prompts) == 8

    routes0 = _route_counters()
    t0 = time.perf_counter()
    engine = DecodeEngine(BertDecoder(cfg, seed=3), dcfg)
    # a kernel that passes alone can still hang inside a whole step
    # (PERF.md question 27): the chains run under a watchdog that dumps
    # every thread's stack and ends the process instead of the chip call
    faulthandler.dump_traceback_later(900, exit=True)
    try:
        assert engine._exe._device.platform == platform, \
            engine._exe._device
        n_warm = engine.warmup()
        t_warm = time.perf_counter() - t0
        assert n_warm == dcfg.executable_grid, (n_warm,
                                                dcfg.executable_grid)
        flat_from = _compiles()
        t0 = time.perf_counter()
        futs = [engine.generate({"src_ids": p}, max_new_tokens=max_new)
                for p in prompts]
        results = [f.result(timeout=600) for f in futs]
        t_gen = time.perf_counter() - t0
        assert _compiles() == flat_from, \
            "the engine compiled after warmup()"
        st = engine.stats()
        assert st["completed"] == 8 and not st["failed"], st
        assert all(len(r.tokens) == max_new for r in results)
        assert any(b > 1 for b in st["decode_batch_hist"]), st
        assert st["chain_hist"].get(long_chain), st
        assert 0 < st["kv_pages_read"] < st["kv_pages_spanned"], st
        distinct = len({int(t) for r in results for t in r.tokens})
        _say(f"  engine: {n_warm} executables warm in {t_warm:.1f} s, "
             f"8 requests x {max_new} tokens ({distinct} distinct) in "
             f"{t_gen:.2f} s (smoke timings); decode_batch_hist "
             f"{st['decode_batch_hist']}, chain_hist {st['chain_hist']}, "
             f"host_syncs {st['host_syncs']}, kv pages read "
             f"{st['kv_pages_read']} of {st['kv_pages_spanned']} spanned")

        asserted = _token_parity(
            engine, prompts, [r.tokens for r in results],
            [engine.greedy_reference({"src_ids": p},
                                     max_new_tokens=max_new).tokens
             for p in prompts])
        _say(f"  parity asserted: {asserted}")
    finally:
        faulthandler.cancel_dump_traceback_later()
        engine.shutdown(drain=False)

    # the decode chains read the cache through the paged kernel, the
    # chunk program (Sq = 128) through gather + flash; nothing falls back
    _check_routes(
        _routes_since(routes0), S,
        want_hits=("flash_attention", "paged_decode_attention",
                   "cached_flash_attention", "fused_layer_norm"),
        allowed_fallbacks=())


# ---------------------------------------------------------------------------
# leg L — the served latent-attention decoder
# ---------------------------------------------------------------------------

def _expanded_row(q, lat, wkvb, h, dn, dr, scale):
    """One row's decode attention in the EXPANDED form, f32: q [H, dn +
    dr], lat [T, dc + dr] -> [H * dv]."""
    import jax
    import jax.numpy as jnp
    dc = wkvb.shape[0]
    kv = (lat[:, :dc] @ wkvb).reshape(lat.shape[0], h, -1)
    sc = jnp.einsum("hd,thd->ht", q[:, :dn], kv[..., :dn]) \
        + jnp.einsum("hd,td->ht", q[:, dn:], lat[:, dc:dc + dr])
    p = jax.nn.softmax(sc * scale, axis=-1)
    return jnp.einsum("ht,thd->hd", p, kv[..., dn:]).reshape(-1)


def leg_latent(S: Sizes, platform: str):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark.builders import serve_lm
    from benchmark.reference import deepseek_v3_jnp as ref
    from benchmark.run import apply_rehearsal, load_manifest, resolve_cell
    from paddle_tpu.ops.pallas.grouped_matmul import row_tile
    from paddle_tpu.ops import mla_ops
    from paddle_tpu.ops.decoder_lm_ops import (_sigmoid_group_topk,
                                               grouped_ffn)
    from paddle_tpu.ops.pallas import mla_paged

    resolved = resolve_cell(load_manifest(),
                            "deepseek_v3_decode.reason_closed")
    config = resolved["config"]
    if S.dry:
        apply_rehearsal(config, resolved["traffic"])
    cfg = serve_lm.decoder_config(config)
    rng = np.random.RandomState(0)

    # -- the paged absorbed kernel against the expanded form -------------
    h, dn, dr, dv, dc = cfg.num_attention_heads, cfg.qk_nope_head_dim, \
        cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    if S.dry:
        h, dn, dr, dv, dc, width, bs = 8, 16, 128, 16, 128, 256, 16
        rows, ctxs, pages = 8, (1, 16, 17, 47, 64), 4
    else:
        width, bs = cfg.latent_width, 16
        rows, ctxs, pages = 128, (1, 16, 17, 4095, 6144), 512
    attrs = {"n_head": h, "nope_dim": dn, "rope_dim": dr, "v_dim": dv,
             "scale": 0.1}
    ctx = np.array([ctxs[i % len(ctxs)] for i in range(rows)], np.int32)
    need = -(-ctx // bs)
    nb = int(need.sum()) + 1
    table = np.zeros((rows, pages), np.int32)
    order = rng.permutation(nb - 1) + 1
    at = 0
    for i, n in enumerate(need):
        table[i, :n] = order[at:at + n]
        at += n
    pool = rng.randn(nb, bs, width).astype(np.float32)
    pool[..., dc + dr:] = 0
    owned = np.zeros((nb, bs), bool)
    for i in range(rows):
        flat = table[i, :need[i]][:, None] * bs + np.arange(bs)[None]
        owned.reshape(-1)[flat.reshape(-1)[:ctx[i]]] = True
    pool[~owned] = np.nan
    pool = jnp.asarray(pool, jnp.bfloat16)
    q = jnp.asarray(rng.randn(rows, 1, h * (dn + dr)) * 0.5, jnp.bfloat16)
    wkvb = jnp.asarray(rng.randn(dc, h * (dn + dv)) * 0.05, jnp.bfloat16)
    tbl, ctx_d = jnp.asarray(table), jnp.asarray(ctx)

    @jax.jit
    def kernel(q, pool, tbl, ctx_d, wkvb):
        o = mla_paged.mla_paged_decode(
            mla_ops.absorb_query(q, wkvb, attrs, width), pool, tbl, ctx_d,
            latent_dim=dc, scale=attrs["scale"],
            interpret=mla_paged.pltpu.InterpretParams() if S.dry else False)
        return mla_ops.project_value(o, wkvb, attrs, jnp.float32)
    got = np.asarray(kernel(q, pool, tbl, ctx_d, wkvb))[:, 0]
    assert np.isfinite(got).all()
    worst = 0.0
    with jax.default_matmul_precision("highest"):
        expanded = jax.jit(_expanded_row, static_argnums=(3, 4, 5, 6))
        for i in range(len(ctxs)):            # one row of each context
            flat = (table[i, :need[i]][:, None] * bs
                    + np.arange(bs)[None]).reshape(-1)[:ctx[i]]
            lat = pool.reshape(-1, width)[flat].astype(jnp.float32)
            want = np.asarray(expanded(
                q[i, 0].astype(jnp.float32).reshape(h, dn + dr), lat,
                wkvb.astype(jnp.float32), h, dn, dr, attrs["scale"]))
            worst = max(worst, _rel(got[i], want))
    _say(f"  mla_paged_decode {rows} rows x {h} heads over {nb} blocks of "
         f"{bs} x {width} bf16, contexts {ctxs}, NaN outside them: rel err "
         f"{worst:.2e} against the expanded form")
    assert worst < 2e-2, worst

    # -- the router against the plain reference --------------------------
    m = serve_lm.reference_model(config)
    e = m["n_routed_experts"]
    n = 64 if S.dry else 1024
    logits = jnp.asarray(rng.randn(n, e).astype(np.float32))
    bias = jnp.asarray(rng.randn(e).astype(np.float32) * 0.05)
    rattrs = {"top_k": m["num_experts_per_tok"], "n_group": m["n_group"],
              "topk_group": m["topk_group"],
              "routed_scale": m["routed_scaling_factor"]}
    vals, idx = jax.jit(lambda l, b: _sigmoid_group_topk(l, b, rattrs))(
        logits, bias)
    wts, ridx = ref.route(jax.nn.sigmoid(logits), bias, m)
    same = (np.sort(idx, -1) == np.sort(ridx, -1)).all(-1)
    np.testing.assert_allclose(np.sort(vals, -1)[same],
                               np.sort(wts, -1)[same], rtol=1e-5)
    s_chosen = np.take_along_axis(np.asarray(jax.nn.sigmoid(logits)),
                                  np.asarray(idx), -1)
    np.testing.assert_allclose(
        vals, m["routed_scaling_factor"] * s_chosen
        / s_chosen.sum(-1, keepdims=True), rtol=1e-5)
    _say(f"  router: {int(same.sum())} of {n} tokens choose the "
         f"reference's set of {rattrs['top_k']} of {e} (groups "
         f"{rattrs['n_group']}, top {rattrs['topk_group']}); weights "
         f"carry no bias")
    assert same.mean() > 0.99, same.mean()

    # -- the grouped products at decode sizes ----------------------------
    d, f = cfg.hidden_size, cfg.moe_intermediate_size
    lo, hi = cfg.held_experts
    nt = 16 if S.dry else 128
    xs = jnp.asarray(rng.randn(nt, d) * 0.5, jnp.bfloat16)
    wg, wu = (jnp.asarray(rng.randn(hi - lo, d, f) * 0.02, jnp.bfloat16)
              for _ in range(2))
    wd = jnp.asarray(rng.randn(hi - lo, f, d) * 0.02, jnp.bfloat16)
    tidx = jnp.asarray(np.stack([rng.permutation(e)[:rattrs["top_k"]]
                                 for _ in range(nt)]).astype(np.int32))
    tw = jnp.asarray(rng.rand(nt, rattrs["top_k"]).astype(np.float32))
    outs = {}
    for backend in ("xla", "pallas_interpret" if S.dry else "pallas"):
        outs[backend], counts = jax.jit(
            lambda *a, backend=backend: grouped_ffn(
                *a, expert_offset=lo, backend=backend,
                tile_m=8 if S.dry else row_tile(tidx.size, e)))(
                    xs, tw, tidx, wg, wu, wd)
    a, b = (np.asarray(v, np.float32) for v in outs.values())
    counts = np.asarray(counts)
    _say(f"  grouped products: {nt} tokens, {int(counts.sum())} "
         f"assignments to {hi - lo} held experts of {e} (per expert "
         f"{counts.min()}-{counts.max()}, {int((counts == 0).sum())} "
         f"empty): rel err {_rel(b, a):.2e} against lax.ragged_dot")
    assert _rel(b, a) < 3e-2

    # -- the cell's engine -----------------------------------------------
    routes0 = _route_counters()
    t0 = time.perf_counter()
    engine = serve_lm.build_engine(config, seed=7)
    faulthandler.dump_traceback_later(1500, exit=True)
    try:
        assert engine._exe._device.platform == platform
        n_warm = engine.warmup()
        t_warm = time.perf_counter() - t0
        assert n_warm == engine.config.executable_grid
        flat_from = _compiles()
        tr = resolved["traffic"]["prompt"]
        plens = np.linspace(tr["min"], tr["max"], 4).astype(int)
        prompts = [rng.randint(0, m["vocab_size"], (n,)).astype(np.int64)
                   for n in plens]
        max_new = 6 if S.dry else 128
        engine.start()
        t0 = time.perf_counter()
        results = [f.result(timeout=900) for f in [
            engine.generate({"src_ids": p}, max_new_tokens=max_new,
                            return_logits=True) for p in prompts]]
        t_gen = time.perf_counter() - t0
        assert _compiles() == flat_from, "the engine compiled after warmup()"
        st = engine.stats()
        assert st["completed"] == 4 and not st["failed"], st
        assert st["chunk_steps"] >= 4 and st["chain_hist"], st
        assert st["moe_experts_hit"]["chain"] > 0, st
        _say(f"  engine: {n_warm} executables warm in {t_warm:.1f} s, 4 "
             f"prompts of {plens.tolist()} tokens x {max_new} new in "
             f"{t_gen:.2f} s (smoke timings); chunk_steps "
             f"{st['chunk_steps']}, chain_hist {st['chain_hist']}, experts "
             f"hit {st['moe_experts_hit']}, assignments "
             f"{st['moe_assignments_local']}")
        weights = serve_lm.close_and_take_weights(engine)
        readings = [serve_lm.compare(
            config["reference"], m, cfg.held_experts, weights, p,
            r.tokens, r.logits) for p, r in zip(prompts, results)]
        verdict = serve_lm.judge(config["reference"], readings)
        _say(f"  logits against the reference's full forward pass: "
             f"{json.dumps(verdict)}")
        assert verdict["ok"], readings
    finally:
        faulthandler.cancel_dump_traceback_later()
        engine.close(timeout=5.0)
    _check_routes(
        _routes_since(routes0), S,
        want_hits=("mla_paged_decode", "moe_grouped_matmul"),
        allowed_fallbacks=())


# ---------------------------------------------------------------------------
# leg H — the served hybrid linear / full attention decoder
# ---------------------------------------------------------------------------


def _hybrid_paged_read(S: Sizes, cfg, rng):
    """Leg H's paged read of bfloat16 K/V pools at the cell's shapes: a
    bucket of 64 rows (contexts drawn like the cell's traffic beside the
    edges, pad rows with nothing live at the end) over a 1 024-page
    table, NaN in every slot no live context owns.  The wide body (heads
    of whole lane tiles: MXU scores, two buffers) against the gathered
    rows and against the narrow body; then the wide body's three steps,
    each timed over 12 chained calls."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops.pallas import paged_attention as pa
    interpret = pa.pltpu.InterpretParams() if S.dry else False
    if S.dry:
        rows, edges, pages, bs, n_head, hidden = 8, (1, 16, 17, 47, 64), \
            4, 16, 2, 256
        ctx = np.array(edges + (33, 0, 0), np.int32)
    else:
        rows, edges, pages, bs, n_head, hidden = 64, (1, 16, 17, 4095,
                                                      12288), 1024, 16, \
            cfg.num_attention_heads, cfg.hidden_size

        def lognormal(n, median, sigma, lo, hi):
            return np.clip(np.round(np.exp(rng.randn(n) * sigma) * median),
                           lo, hi)
        # ~50 rows fit the cell's pool: prompts, and a uniform share of
        # the outputs on top
        ctx = np.zeros((rows,), np.int32)
        ctx[:50] = lognormal(50, 3072, 0.6, 1024, 12288) \
            + rng.rand(50) * lognormal(50, 512, 0.5, 128, 2048)
        ctx[:len(edges)] = edges
    need = -(-ctx // bs)
    nb = int(need.sum()) + 1
    table = np.zeros((rows, pages), np.int32)
    order = rng.permutation(nb - 1) + 1
    at = 0
    for i, n in enumerate(need):
        table[i, :n] = order[at:at + n]
        at += n

    def slots(i):
        """Row i's live positions as flat slots of a pool."""
        return (table[i, :need[i]][:, None] * bs
                + np.arange(bs)[None]).reshape(-1)[:ctx[i]]
    owned = np.zeros((nb * bs,), bool)
    for i in range(rows):
        owned[slots(i)] = True
    hole = jnp.asarray(owned.reshape(nb, bs, 1))
    pools = [jnp.where(hole, jax.random.normal(
        jax.random.PRNGKey(rng.randint(1 << 30)), (nb, bs, hidden),
        jnp.bfloat16), jnp.nan) for _ in range(2)]
    # float32, not bfloat16-exact: the kernel may round no operand that
    # is not bfloat16 as stored
    q = jnp.asarray(rng.randn(rows, 1, hidden), jnp.float32)

    # operands are arguments: a closure over them would be gigabytes of
    # HLO constants
    args = (q, *pools, jnp.asarray(table), jnp.asarray(ctx))

    def body(fn, **kw):
        return functools.partial(fn, n_head=n_head, interpret=interpret,
                                 **kw)
    narrow = body(pa.paged_decode_attention)
    wide = body(pa.paged_decode_attention_wide)       # as the route runs it
    forms = (
        ("narrow body (butterfly, one buffer, 4 pages)", narrow),
        ("wide: MXU scores, one buffer, 4 pages",
         body(pa.paged_decode_attention_wide, pages_per_step=4,
              prefetch=False)),
        ("wide: two buffers, 4 pages",
         body(pa.paged_decode_attention_wide, pages_per_step=4)),
        (f"wide: two buffers, {pa.PAGES_PER_STEP_WIDE} pages", wide),
        ("wide: two buffers, 16 pages",
         body(pa.paged_decode_attention_wide, pages_per_step=16)))
    got = np.asarray(wide(*args))
    assert np.isfinite(got).all()
    assert not got[ctx == 0].any(), "a row with nothing live wrote"
    off_narrow = _rel(got, np.asarray(narrow(*args)))
    worst = 0.0
    d = hidden // n_head
    with jax.default_matmul_precision("highest"):
        for i in range(len(edges) + 3):       # each edge, three drawn rows
            k, v = (p.reshape(-1, hidden)[slots(i)].astype(jnp.float32)
                    .reshape(-1, n_head, d) for p in pools)
            sc = jnp.einsum("hd,thd->ht", q[i, 0].reshape(n_head, d),
                            k) * d ** -0.5
            want = jnp.einsum("ht,thd->hd", jax.nn.softmax(sc, -1), v)
            worst = max(worst, _rel(got[i, 0], np.asarray(want).reshape(-1)))
    _say(f"  paged_decode_attn_wide {rows} rows x {n_head} heads of {d} "
         f"over {nb} bf16 blocks of {bs} x {hidden}, {int(ctx.sum())} live "
         f"positions (edges {edges}), NaN outside them: rel err "
         f"{worst:.2e} against the gathered rows, {off_narrow:.2e} "
         f"against the narrow body")
    # float32 arithmetic over bfloat16 pages
    assert worst < 1e-4 and off_narrow < 1e-4, (worst, off_narrow)
    calls = 1 if S.dry else 12
    for name, fn in forms:
        chained = jax.jit(lambda q, *rest, fn=fn: jax.lax.fori_loop(
            0, calls, lambda _, q: fn(q, *rest), q))
        chained(*args).block_until_ready()
        best = float("inf")
        for _ in range(1 if S.dry else 3):
            t0 = time.perf_counter()
            chained(*args).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        _say(f"    {name}: {best * 1e3:.2f} ms per {calls} calls "
             f"(smoke timing)")


def _hybrid_kernels(S: Sizes, cfg, rng):
    """Leg H's three kernels alone, at the cell's shapes."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark.reference import olmo_hybrid_jnp as ref
    from paddle_tpu.ops import linear_attn_ops as la
    from paddle_tpu.ops.pallas import gated_delta as gd
    interpret = gd.pltpu.InterpretParams() if S.dry else False
    _hybrid_paged_read(S, cfg, rng)

    # -- the two delta-rule kernels alone ----------------------------------
    h, dk, dv = cfg.linear_num_key_heads, cfg.linear_key_head_dim, \
        cfg.linear_value_head_dim
    b, steps = (2, 24) if S.dry else (16, 1024)

    def f32(*shape):
        return jnp.asarray(rng.randn(*shape), jnp.float32)

    q, k = la.l2_normalize(f32(steps, b, h, dk)) * dk ** -0.5, \
        la.l2_normalize(f32(steps, b, h, dk) + 0.3)
    v = f32(steps, b, h, dv)
    alpha = jnp.exp(-jnp.asarray(rng.uniform(1e-3, 1.6, (steps, b, h)),
                                 jnp.float32))
    beta = jnp.asarray(rng.uniform(0, 2, (steps, b, h)), jnp.float32)
    slot = jnp.asarray(rng.permutation(b + 1)[:b], jnp.int32)
    pool0 = jnp.zeros((b + 1, h, dk, dv), jnp.float32)

    @jax.jit
    def by_kernel(pool, xs):
        def step(pool, xs):
            o, pool = gd.gdn_decode(*xs, pool, slot, interpret=interpret)
            return pool, o
        return jax.lax.scan(step, pool, xs)

    @jax.jit
    def by_recurrence(state, xs):
        with jax.default_matmul_precision("highest"):
            return jax.lax.scan(
                lambda s, xs: la.recurrent_step(s, *xs)[::-1], state, xs)
    t0 = time.perf_counter()
    pool, o_k = by_kernel(pool0, (q, k, v, alpha, beta))
    jax.block_until_ready(pool)
    t_k = time.perf_counter() - t0
    zero = jnp.zeros((b, h, dk, dv), jnp.float32)
    state, o_r = by_recurrence(zero, (q, k, v, alpha, beta))

    @jax.jit
    def coarse(state, xs):
        """The control: the state rounded to bfloat16 after every token."""
        def step(s, xs):
            o, s = la.recurrent_step(s, *xs)
            return ref.round_to_bfloat16(s), o
        with jax.default_matmul_precision("highest"):
            return jax.lax.scan(step, state, xs)[0]
    err_o = _rel(np.asarray(o_k), np.asarray(o_r))
    err_s = _rel(np.asarray(pool)[np.asarray(slot)], np.asarray(state))
    err_bf = _rel(np.asarray(coarse(zero, (q, k, v, alpha, beta))),
                  np.asarray(state))
    _say(f"  gdn_decode {b} rows x {h} heads of {dk} x {dv}, {steps} "
         f"tokens in one scan ({t_k:.2f} s with its compile): outputs rel "
         f"err {err_o:.2e}, the STATE after {steps} tokens {err_s:.2e} "
         f"against the float32 recurrence; a state rounded to bfloat16 "
         f"after every token reads {err_bf:.2e}")
    # the control is real only in integer arithmetic: on the chip XLA drops
    # a float32 -> bfloat16 -> float32 pair of converts (excess precision)
    assert err_o < 1e-4 and err_s < 1e-4 < err_bf / 4, (err_o, err_s, err_bf)

    tokens = 100 if S.dry else 1024
    ins = {"Q": f32(1, tokens, h * dk), "K": f32(1, tokens, h * dk) + 0.3,
           "V": f32(1, tokens, h * dv), "A": f32(1, tokens, h),
           "B": f32(1, tokens, h),
           "ALog": jnp.log(jnp.asarray(rng.uniform(1, 16, h), jnp.float32)),
           "DtBias": f32(h) - 3.0}
    prep = la._prepare({n: [t] for n, t in ins.items()},
                       {"n_head": h, "beta_scale": 2.0})
    pad = -tokens % gd.SUB_CHUNK
    parts = la.wy_transform(*(la._pad_time(t, pad) for t in prep))
    o_c, pool_c = jax.jit(lambda *a: gd.gdn_chunk(*a, interpret=interpret))(
        *parts, pool0[:2], jnp.array([1], jnp.int32),
        jnp.array([1], jnp.int32))
    qh, kh, vh, g, bt = prep
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda *a: ref.delta_rule(*a, frozenset(), 1 << 30))(
            qh[0].transpose(1, 0, 2), kh[0].transpose(1, 0, 2),
            vh[0].transpose(1, 0, 2), jnp.exp(g[0]).T, bt[0].T)
    got = np.asarray(o_c).reshape(h, tokens + pad, dv)[:, :tokens]
    err_c = _rel(got, np.asarray(want).transpose(1, 0, 2))
    _say(f"  gdn_chunk {tokens} tokens x {h} heads in sub-chunks of "
         f"{gd.SUB_CHUNK}: rel err {err_c:.2e} against the token scan")
    assert err_c < 1e-3, err_c


def _stated_precision(ref_cfg, m, weights, prompt, result):
    """Relative L2 over one request's served rows: the float32 reference
    with its residual stream, then with every activation, kept in
    bfloat16 (``olmo_hybrid_jnp.ROUNDINGS``) off the float32 reference,
    and the served logits off each."""
    import numpy as np
    from benchmark.reference import olmo_hybrid_jnp as ref
    plen, n = int(prompt.size), int(result.tokens.size)
    pad_to = ref_cfg["pad_to"]
    seq = np.zeros(-(-(plen + n) // pad_to) * pad_to, np.int64)
    seq[:plen], seq[plen:plen + n] = prompt, result.tokens

    def logits(*wrong):
        return np.asarray(ref.logits(
            weights, seq, m, layer_prefix=ref_cfg["layer_prefix"],
            wrong=wrong, q_block=ref_cfg["q_block"], chunk=ref_cfg["chunk"],
            rows=(plen - 1, plen - 1 + n)))

    def off(a, b):
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))
    exact = logits()
    served = np.asarray(result.logits, np.float32)
    out = {"prompt": plen, "tokens": n, "served_off_reference": off(served,
                                                                    exact)}
    for name in ref.ROUNDINGS:
        rounded = logits(name)
        out[name] = {"off_reference": off(rounded, exact),
                     "served_off_it": off(served, rounded)}
    return out


def leg_hybrid(S: Sizes, platform: str):
    import gc
    import numpy as np
    from benchmark.builders import serve_hybrid
    from benchmark.run import apply_rehearsal, load_manifest, resolve_cell

    resolved = resolve_cell(load_manifest(), "olmo_hybrid_serve.doc_closed")
    config = resolved["config"]
    if S.dry:
        apply_rehearsal(config, resolved["traffic"])
    cfg = serve_hybrid.decoder_config(config)
    rng = np.random.RandomState(0)
    # a function of their own: the kernels' operands (gigabytes) are gone
    # when it returns, and the engine below fills the chip
    _hybrid_kernels(S, cfg, rng)
    gc.collect()

    # -- the cell's engine ---------------------------------------------------
    routes0 = _route_counters()
    t0 = time.perf_counter()
    engine = serve_hybrid.build_engine(config, seed=7)
    t_build = time.perf_counter() - t0
    faulthandler.dump_traceback_later(1800, exit=True)
    try:
        assert engine._exe._device.platform == platform
        n_warm = engine.warmup()
        t_warm = time.perf_counter() - t0
        assert n_warm == engine.config.executable_grid
        flat_from = _compiles()
        tr = resolved["traffic"]["prompt"]
        m = serve_hybrid.reference_model(config)
        plens = np.linspace(tr["min"], tr["max"], 4).astype(int)
        prompts = [rng.randint(0, m["vocab_size"], (n,)).astype(np.int64)
                   for n in plens]
        max_new = 6 if S.dry else 128
        engine.start()
        t0 = time.perf_counter()
        results = [f.result(timeout=1500) for f in [
            engine.generate({"src_ids": p}, max_new_tokens=max_new,
                            return_logits=True) for p in prompts]]
        t_gen = time.perf_counter() - t0
        assert _compiles() == flat_from, "the engine compiled after warmup()"
        st = engine.stats()
        assert st["completed"] == 4 and not st["failed"], st
        assert st["chunk_steps"] >= 4 and st["chain_hist"], st
        assert st["state_slots_peak"] == 4 and not st["state_slots_in_use"]
        _say(f"  engine: built in {t_build:.1f} s (programs, startup, "
             f"pools), {n_warm} executables warm in {t_warm:.1f} s, 4 "
             f"prompts of {plens.tolist()} tokens x {max_new} new in "
             f"{t_gen:.2f} s (smoke timings); chunk_steps "
             f"{st['chunk_steps']}, chain_hist {st['chain_hist']}, state "
             f"rows launched / live {st['state_rows_launched']} / "
             f"{st['state_rows_live']}")
        weights = serve_hybrid.close_and_take_weights(engine)
        readings = [serve_hybrid.compare(config["reference"], m, weights, p,
                                         r.tokens, r.logits)
                    for p, r in zip(prompts, results)]
        verdict = serve_hybrid.judge(config["reference"], readings)
        _say(f"  logits against the reference's full forward pass: "
             f"{json.dumps(verdict)}")
        assert verdict["ok"], readings
        # where the distance comes from, at THESE widths: the f32 reference
        # with the stated bfloat16 applied to its own activations, against
        # itself and against the served logits of the shortest request
        floor = _stated_precision(config["reference"], m, weights,
                                  prompts[0], results[0])
        _say(f"  the reference in the stated precision: {json.dumps(floor)}")
        # a rounding is real (integer arithmetic: XLA drops a pair of
        # converts on the chip) and of bfloat16's order
        assert all(1e-3 < floor[r]["off_reference"] < 0.2
                   for r in ("residual_bfloat16", "activations_bfloat16")), \
            floor
    finally:
        faulthandler.cancel_dump_traceback_later()
        engine.close(timeout=5.0)
    _check_routes(
        _routes_since(routes0), S,
        want_hits=("paged_decode_attention_wide", "gdn_decode", "gdn_chunk"),
        allowed_fallbacks=())


# ---------------------------------------------------------------------------
# leg C — four chips
# ---------------------------------------------------------------------------

#: dp4 vs one chip on the same global batch, dropout off: the same math
#: with the batch mean taken in another order (8.6e-8 on four v5e chips,
#: PR 21)
DP_PARITY_REL_TOL = 1e-4


def _mesh_run(exe, program, feed, fetch, steps=1):
    import numpy as np
    out = None
    for _ in range(steps):
        out, = exe.run(program, feed=feed, fetch_list=[fetch])
        assert np.isfinite(out).all(), out
    return float(np.mean(out))


def leg_four_chips(S: Sizes, platform: str):
    import jax
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.distributed.fleet import (DistributedStrategy,
                                              UserDefinedRoleMaker,
                                              distributed_optimizer,
                                              fleet)
    from paddle_tpu.models import bert
    from paddle_tpu.parallel import build_mesh

    devs = jax.devices()[:4]
    assert all(d.platform == platform for d in devs), devs
    exe = fluid.Executor(fluid.TPUPlace(0))
    # the parity, tp and ZeRO-1 steps run at full width and cut depth:
    # what they prove (placement, collectives, kernels under shard_map)
    # does not depend on the layer count, and each full-depth compile
    # is charged four chips
    cut = 1 if S.dry else 2

    def fleet_program(cfg, **strategy_kw):
        """BERT pretrain through fleet.distributed_optimizer: pure-bf16
        AMP + Adam, dp over every visible device."""
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 0
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            _, total, _, _ = bert.build_pretrain_network(cfg)
            fleet.init(UserDefinedRoleMaker(0, 1))
            strategy = DistributedStrategy()
            strategy.amp = True
            strategy.mesh = build_mesh({"dp": 4}, devs)
            for k, v in strategy_kw.items():
                setattr(strategy, k, v)
            distributed_optimizer(fluid.optimizer.Adam(1e-4),
                                  strategy).minimize(total)
        return main, startup, total, fleet.main_program

    # -- C1: dp4, default bucketed c_fused_allreduce_sum, per-chip batch
    # as on one chip ------------------------------------------------------
    rng = np.random.RandomState(0)
    big = bert.make_fake_batch(rng, S.cfg, batch_size=4 * S.batch,
                               seq_len=S.seq, num_masks=S.masks)
    main, startup, total, prog = fleet_program(S.cfg)
    assert any(op.type == "c_fused_allreduce_sum"
               for op in main.global_block().ops)
    routes0 = _route_counters()
    with fluid.scope_guard(fluid.Scope()):
        scope = fluid.global_scope()
        exe.run(startup)
        t0 = time.perf_counter()
        first = _mesh_run(exe, prog, big, total)
        t_first = time.perf_counter() - t0
        flat_from = _compiles()
        last = _mesh_run(exe, prog, big, total, steps=4)
        assert _compiles() == flat_from, "the dp4 step recompiled"
        assert last < first, (first, last)
        # where things live: every persistable spans the four chips ...
        ids = set()
        for name in scope.var_names():
            v = scope.find_var(name)
            if hasattr(v, "sharding"):
                ids.update(d.id for d in v.sharding.device_set)
                assert len(v.sharding.device_set) == 4, (name,
                                                         v.sharding)
        assert len(ids) == 4, ids
        # ... the compiled step takes each feed split on dim 0 over
        # them, and carries the grad all-reduce
        step, lowered = exe.lower_for_audit(
            prog._program, big, [total.name], scope, mesh=prog._mesh,
            axis_names=prog._axis_names, batch_axis=prog._batch_axis)
        compiled = lowered.compile()
        feed_sh = compiled.input_shardings[0][0]
        for name in step.feed_names:
            sh, shape = feed_sh[name], big[name].shape
            assert len(sh.device_set) == 4, (name, sh)
            assert sh.shard_shape(shape)[0] * 4 == shape[0], (name, sh)
        assert "all-reduce" in compiled.as_text()
        _say(f"  dp4 global batch {4 * S.batch}: loss {first:.4f} -> "
             f"{last:.4f} over 5 steps; persistables on devices "
             f"{sorted(ids)}, feeds split 4-way on dim 0, all-reduce in "
             f"the HLO; smoke timing first step {t_first:.1f} s")
    _check_routes(
        _routes_since(routes0), S,
        want_hits=("attention_tile", "fused_layer_norm"),
        allowed_fallbacks=())

    # -- C2: parity — dp4 vs one chip, one global batch, dropout off ----
    cfg = S.nodrop(layers=cut)
    small = bert.make_fake_batch(np.random.RandomState(1), cfg,
                                 batch_size=S.batch, seq_len=S.seq,
                                 num_masks=S.masks)
    main1, startup1, total1 = _build_pretrain(cfg)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup1)
        one = _mesh_run(exe, main1, small, total1)
    _, startup4, total4, prog4 = fleet_program(cfg)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup4)
        four = _mesh_run(exe, prog4, small, total4)
    rel = abs(four - one) / abs(one)
    _say(f"  parity at global batch {S.batch}, {cut} layer(s): one chip "
         f"{one:.5f} vs dp4 {four:.5f}, rel diff {rel:.2e} (tolerance "
         f"{DP_PARITY_REL_TOL})")
    assert rel < DP_PARITY_REL_TOL, (one, four)

    # -- C3: one dp2 x tp2 step (Megatron layers, head-sharded flash) ---
    mesh = build_mesh({"dp": 2, "tp": 2}, devs)
    tcfg = dataclasses.replace(S.cfg, num_hidden_layers=cut)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        _, loss = bert.build_pretrain_network_parallel(tcfg, tp_degree=2)
        fluid.optimizer.Adam(1e-4).minimize(loss)
    prog = fluid.CompiledProgram(main).with_mesh(
        mesh, loss_name=loss.name, batch_axis="dp")
    pbatch = bert.make_fake_parallel_batch(
        np.random.RandomState(2), tcfg, batch_size=2 * S.batch // 4,
        seq_len=S.seq)
    with fluid.scope_guard(fluid.Scope()):
        scope = fluid.global_scope()
        exe.run(startup)
        l1 = _mesh_run(exe, prog, pbatch, loss)
        l2 = _mesh_run(exe, prog, pbatch, loss)
        assert l2 < l1, (l1, l2)
        split = [n for n in scope.var_names()
                 if hasattr(scope.find_var(n), "sharding")
                 and not scope.find_var(n).sharding.is_fully_replicated]
        assert split, "no parameter is sharded over tp"
        w = scope.find_var(split[0])
        assert len(w.sharding.device_set) == 4, w.sharding
    _say(f"  dp2 x tp2, {cut} layer(s): loss {l1:.4f} -> {l2:.4f}; "
         f"{len(split)} persistables sharded over tp (e.g. {split[0]} "
         f"{tuple(w.shape)} as {w.sharding.shard_shape(w.shape)})")

    # -- C4: ZeRO-1 sharded update — Adam on the 128-aligned flat state
    # shards, under shard_map --------------------------------------------
    _, startupz, totalz, progz = fleet_program(
        S.nodrop(layers=cut), sharded_update=True)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startupz)
        z1 = _mesh_run(exe, progz, small, totalz)
        z2 = _mesh_run(exe, progz, small, totalz)
    assert z2 < z1, (z1, z2)
    assert abs(z1 - four) / abs(four) < DP_PARITY_REL_TOL, (z1, four)
    _say(f"  ZeRO-1 sharded update, {cut} layer(s): loss {z1:.5f} -> "
         f"{z2:.5f}, first step equal to plain dp4")


# ---------------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="tiny width on the CPU backend, to debug this "
                         "script; proves nothing about the chip")
    ap.add_argument("--legs", default=",".join(LEGS),
                    help="comma-separated subset of K,A,B,M,L,H,C")
    args = ap.parse_args(argv)
    legs = [x.strip().upper() for x in args.legs.split(",") if x.strip()]
    if not legs or set(legs) - set(LEGS):
        ap.error(f"--legs takes a subset of {','.join(LEGS)}")

    if args.cpu_dry_run:
        os.environ["JAX_PLATFORMS"] = "cpu"
        # four host devices, so leg C's mesh code runs too; unoptimised
        # host code, because the dry run is all compile time
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=4 "
            "--xla_backend_optimization_level=0 "
            "--xla_llvm_disable_expensive_passes=true")
    import jax
    from paddle_tpu import flags
    from paddle_tpu.framework.core import require_tpu
    if args.cpu_dry_run:
        devs = jax.devices()
        device = {"platform": devs[0].platform,
                  "kind": devs[0].device_kind, "count": len(devs)}
    else:
        device = require_tpu()       # exits non-zero without a TPU
    _say(f"chip_smoke: jax {jax.__version__}, platform: "
         f"{device['platform']}, device_kind: {device['kind']}, devices: "
         f"{device['count']}" + ("  [CPU DRY RUN]" * args.cpu_dry_run))

    # (the dry run leaves the cache off: nothing it compiles is reused)
    cache_dir = None if args.cpu_dry_run else flags.enable_compile_cache()
    cache = {"requests": 0, "hits": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            cache["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
    jax.monitoring.register_event_listener(on_event)
    def entries():
        """Executables in the persistent compilation cache."""
        if not cache_dir or not os.path.isdir(cache_dir):
            return 0
        return sum(n.endswith("-cache") for n in os.listdir(cache_dir))
    _say(f"compile cache: {cache_dir}, {entries()} entries before")

    S = Sizes(args.cpu_dry_run)
    run = {"K": lambda: leg_kernels(S),
           "A": lambda: leg_trainer(S, device["platform"]),
           "B": lambda: leg_server(S, device["platform"]),
           "M": lambda: leg_decoder_lm(S, device["platform"]),
           "L": lambda: leg_latent(S, device["platform"]),
           "H": lambda: leg_hybrid(S, device["platform"]),
           "C": lambda: leg_four_chips(S, device["platform"])}
    summary = []
    t_all = time.perf_counter()
    for leg in LEGS:
        if leg not in legs:
            summary.append(f"leg {leg}: not selected")
            continue
        if leg == "C" and device["count"] < 4:
            summary.append(f"leg C: not run, {device['count']} device(s)")
            continue
        _say(f"== leg {leg} ==")
        t0 = time.perf_counter()
        before = dict(cache), entries()
        run[leg]()                   # an assertion ends the run
        new = entries() - before[1]
        line = (f"leg {leg}: passed in {time.perf_counter() - t0:.0f} s "
                f"(compile cache: "
                f"{cache['hits'] - before[0]['hits']} hits of "
                f"{cache['requests'] - before[0]['requests']} requests, "
                f"{new} new entries)")
        _say(line)
        summary.append(line)
    _say(f"compile cache: {cache_dir}, {entries()} entries after")
    _say(f"chip_smoke summary: platform: {device['platform']}, "
         f"device_kind: {device['kind']}, devices: {device['count']}, "
         f"wall {time.perf_counter() - t_all:.0f} s; "
         + "; ".join(summary))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
