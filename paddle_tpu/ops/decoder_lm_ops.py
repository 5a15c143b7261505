"""Ops of a pre-norm rotary decoder with dropless sparse experts (the
reference framework has none of them; re-derived from the published
equations of the open decoder families: RMSNorm, rotary position
embedding in the rotate-half form with the default and YaRN frequency
tables, softmax-top-k routing WITHOUT a capacity bound, SiLU-gated
experts as grouped matrix products).

* ``rms_norm``            ``x / sqrt(mean(x^2) + eps) * g``, statistics in f32.
* ``rotary_embedding``    rotates every head of a ``[B, S, heads * D]``
  projection at positions ``0..S-1``.
* ``moe_topk_router``     ``softmax(x W_r)`` over ALL experts in f32 at
  the highest matmul precision, the ``top_k`` largest, optionally
  renormalised.
* ``moe_grouped_ffn``     the experts HELD HERE (``expert_offset`` ..
  ``+ E_local``: one chip's share of an expert-parallel layer) applied to
  the assignments routed to them, summed per token with the router's
  weights.  Assignments are sorted by expert and every one is computed:
  there is no capacity and nothing is dropped; assignments to experts
  held elsewhere are skipped and add nothing (their owner adds them —
  over all the shares the parts sum to the whole layer,
  tests/test_decoder_lm.py).  Also returns the per-expert counts.
* ``moe_load_stats``      folds those counts into a persistable counter
  on the device, read by ``PreparedStep.stats`` at a blocking point.
* ``lm_head_logits``      the untied head alone, float32 logits (serving).
* ``lm_head_loss``        the untied head and the per-token cross-entropy
  in one op, by blocks of rows: f32 logits exist one block at a time;
  what is kept for the backward pass is the logits in the compute dtype
  and the per-row logsumexp (at 8 192 x 24 576 the plain pair keeps two
  f32 copies and their cotangent: 2.4 GB against 0.4).

The grouped products run on the Pallas kernels of
``ops/pallas/grouped_matmul.py`` when the registry routes there (TPU,
lane-aligned widths) and on ``lax.ragged_dot`` otherwise; the two paths
share the routing layout and are held to each other in interpret mode.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .moe_ops import _group_size
from .registry import pallas_route, register, x


@register("rms_norm")
def _rms_norm(ctx, ins, attrs):
    xv, scale = x(ins, "X"), x(ins, "Scale")
    xf = xv.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                       + attrs.get("epsilon", 1e-6))
    return {"Y": (y * scale.astype(jnp.float32)).astype(xv.dtype)}


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------

def rope_inv_freq(head_dim: int, attrs) -> tuple:
    """(inv_freq [head_dim / 2] float32, attention_factor) from the
    ``rope_*`` attrs; ``rope_type`` ``default`` or ``yarn`` (the blend of
    ``inv_freq`` and ``inv_freq / factor`` by the linear ramp between the
    two correction dimensions, as ``transformers`` computes it).  Host
    arithmetic in float64: the table is a constant of the program."""
    base = float(attrs.get("rope_theta", 10000.0))
    pos_freqs = base ** (np.arange(0, head_dim, 2, dtype=np.float64)
                         / head_dim)
    kind = attrs.get("rope_type", "default")
    if kind == "default":
        return (1.0 / pos_freqs).astype(np.float32), 1.0
    if kind != "yarn":
        raise ValueError(f"rotary_embedding: rope_type {kind!r}")
    factor = float(attrs["factor"])
    orig = float(attrs["original_max_position_embeddings"])

    def correction_dim(rotations):
        return head_dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(attrs.get("beta_fast", 32)))),
              0)
    high = min(math.ceil(correction_dim(float(attrs.get("beta_slow", 1)))),
               head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    inv_freq = ramp / (factor * pos_freqs) + (1.0 - ramp) / pos_freqs
    attention_factor = attrs.get("attention_factor")
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return inv_freq.astype(np.float32), float(attention_factor)


@register("rotary_embedding")
def _rotary_embedding(ctx, ins, attrs):
    """Every head of ``X`` [B, S, heads * head_dim] rotated at positions
    ``Pos`` [B, S] when given (a served decoder: positions come from the
    feed, a decode step's from its context length), else ``0..S-1``.
    Rotate-half pairing, or under ``interleaved`` ADJACENT pairs
    ``(x_2i, x_2i+1)`` together (the DeepSeek family's published form);
    under ``rotary_dim`` only the LAST ``rotary_dim`` columns of every
    head rotate (a head of ``[nope | rope]`` parts), or under
    ``rotary_leading`` the FIRST (a ``partial_rotary_factor``)."""
    xv = x(ins, "X")
    b, s, width = xv.shape
    d = int(attrs["head_dim"])
    r = int(attrs.get("rotary_dim") or d)
    pos = x(ins, "Pos")
    if pos is None:
        # from TRACED shapes, so one program serves every length
        pos = jnp.arange(s)[None, :]
    inv_freq, factor = rope_inv_freq(r, attrs)
    freqs = pos.astype(jnp.float32)[:, :, None, None] * inv_freq
    heads = xv.reshape(b, s, width // d, d)
    leading = bool(attrs.get("rotary_leading"))
    xh = (heads[..., :r] if leading else heads[..., d - r:]) \
        .astype(jnp.float32)
    if attrs.get("interleaved"):
        cos, sin = jnp.cos(freqs) * factor, jnp.sin(freqs) * factor
        x1, x2 = xh[..., 0::2], xh[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                        axis=-1).reshape(xh.shape)
    else:
        emb = jnp.concatenate([freqs, freqs], axis=-1)
        rot = jnp.concatenate([-xh[..., r // 2:], xh[..., :r // 2]],
                              axis=-1)
        out = xh * (jnp.cos(emb) * factor) + rot * (jnp.sin(emb) * factor)
    out = out.astype(xv.dtype)
    if r < d:
        out = jnp.concatenate([out, heads[..., r:]] if leading
                              else [heads[..., :d - r], out], axis=-1)
    return {"Out": out.reshape(b, s, width)}


# ---------------------------------------------------------------------------
# routing without a capacity
# ---------------------------------------------------------------------------

@register("moe_topk_router")
def _moe_topk_router(ctx, ins, attrs):
    xv, w = x(ins, "X"), x(ins, "W")
    xf = xv.reshape(-1, xv.shape[-1]).astype(jnp.float32)
    # a rounded logit moves a token to another expert: f32 at the
    # highest precision (64 columns: 0.06 % of the layer's FLOPs)
    logits = jnp.matmul(xf, w.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    if attrs.get("scoring", "softmax") == "sigmoid":
        vals, idx = _sigmoid_group_topk(logits, x(ins, "Bias"), attrs)
        return {"TopkWeight": vals, "TopkIndex": idx.astype(jnp.int32)}
    vals, idx = lax.top_k(jax.nn.softmax(logits, axis=-1),
                          int(attrs["top_k"]))
    if attrs.get("norm_topk_prob", True):
        vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    if "routed_scale" in attrs:
        vals = vals * float(attrs["routed_scale"])
    return {"TopkWeight": vals, "TopkIndex": idx.astype(jnp.int32)}


def _sigmoid_group_topk(logits, bias, attrs):
    """``scoring="sigmoid"``: ``s = sigmoid(logits)``; ``Bias`` [E] is
    added for SELECTION only (it chooses, it never weighs); the experts
    form ``n_group`` groups scored by the sum of their two largest
    ``s + b``, the ``topk_group`` best groups stay, and the ``top_k``
    largest ``s + b`` among them are chosen.  Weights ``s_k / sum s``
    (``norm_topk_prob``) times ``routed_scale``."""
    n, e = logits.shape
    k = int(attrs["top_k"])
    ng, kg = int(attrs.get("n_group", 1)), int(attrs.get("topk_group", 1))
    s = jax.nn.sigmoid(logits)
    pick = s if bias is None else s + bias.astype(jnp.float32)[None, :]
    if ng > 1:
        gscore = jnp.sum(lax.top_k(pick.reshape(n, ng, e // ng), 2)[0],
                         axis=-1)
        # a group stays iff fewer than topk_group groups beat it (ties
        # to the lower index, as top_k breaks them)
        _, gidx = lax.top_k(gscore, kg)
        keep = jnp.zeros((n, ng), jnp.bool_).at[
            jnp.arange(n)[:, None], gidx].set(True)
        pick = jnp.where(jnp.repeat(keep, e // ng, axis=1), pick, -jnp.inf)
    _, idx = lax.top_k(pick, k)
    vals = jnp.take_along_axis(s, idx, axis=-1)
    if attrs.get("norm_topk_prob", True):
        vals = vals / (jnp.sum(vals, axis=-1, keepdims=True) + 1e-20)
    return vals * float(attrs.get("routed_scale", 1.0)), idx


def routing_layout(idx, expert_offset: int, e_local: int, tile_m: int):
    """The sorted-by-expert layout of the assignments ``idx`` [N, k]
    routed to experts ``expert_offset .. + e_local``; every other
    assignment gets the out-of-range row ``M`` (a gather there reads
    zero, a scatter there is dropped).

    Returns (row of each assignment [N, k], token feeding each row [M]
    (``N`` where the row is padding), counts [e_local], group of each
    tile, number of real tiles, M)."""
    from .pallas.grouped_matmul import padded_rows, tile_layout
    n, k = idx.shape
    a = n * k
    m = padded_rows(a, e_local, tile_m)
    key = idx.reshape(a) - expert_offset
    key = jnp.where((key >= 0) & (key < e_local), key, e_local)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    counts = jnp.zeros((e_local + 1,), jnp.int32).at[key].add(1)[:e_local]
    first_row, tile_group, num_active = tile_layout(counts, tile_m,
                                                    m // tile_m)
    first_sorted = jnp.cumsum(counts) - counts
    skey = key[order]
    held = skey < e_local
    g = jnp.minimum(skey, e_local - 1)
    dest = jnp.where(held, first_row[g] + jnp.arange(a, dtype=jnp.int32)
                     - first_sorted[g], m)
    row_of = jnp.zeros((a,), jnp.int32).at[order].set(dest)
    token_of_row = jnp.full((m,), n, jnp.int32).at[dest].set(
        order // k, mode="drop")
    return row_of.reshape(n, k), token_of_row, counts, tile_group, \
        num_active, m


def _take_rows(t, rows):
    """Rows of ``t``; an out-of-range row reads as zero."""
    return jnp.take(t, rows, axis=0, mode="fill", fill_value=0)


def _combine(rows_val, row_of, weights=None):
    """Per token the sum over its assignments of ``rows_val[row]``
    (times its weight), accumulated in f32, one assignment slot at a
    time so no [N, k, d] tensor is formed."""
    out = jnp.zeros((row_of.shape[0], rows_val.shape[1]), jnp.float32)
    for j in range(row_of.shape[1]):
        part = _take_rows(rows_val, row_of[:, j]).astype(jnp.float32)
        if weights is not None:
            part = part * weights[:, j:j + 1].astype(jnp.float32)
        out = out + part
    return out


def _silu_parts(g, u):
    gf, uf = g.astype(jnp.float32), u.astype(jnp.float32)
    sg = jax.nn.sigmoid(gf)
    return gf, uf, sg, gf * sg


@functools.lru_cache(maxsize=None)
def _make_grouped_ffn(tile_m: int, interpret: bool):
    """The experts' part on the Pallas grouped-matmul kernels, with its
    own backward: what is kept is the layer's input, the layout and the
    two hidden projections; the sorted copy of the input is gathered
    again and never stored."""
    from .pallas.grouped_matmul import gmm, tgmm
    kw = dict(tile_m=tile_m, interpret=interpret)

    def forward(xf, w, wg, wu, wd, row_of, token_of_row, tg, na):
        xs = _take_rows(xf, token_of_row)
        g, u = gmm(xs, wg, tg, na, **kw), gmm(xs, wu, tg, na, **kw)
        _, uf, _, silu = _silu_parts(g, u)
        y = gmm((silu * uf).astype(xf.dtype), wd, tg, na, **kw)
        return _combine(y, row_of, w).astype(xf.dtype), (g, u)

    @jax.custom_vjp
    def f(xf, w, wg, wu, wd, row_of, token_of_row, tg, na, counts):
        return forward(xf, w, wg, wu, wd, row_of, token_of_row, tg, na)[0]

    def fwd(xf, w, wg, wu, wd, row_of, token_of_row, tg, na, counts):
        out, gu = forward(xf, w, wg, wu, wd, row_of, token_of_row, tg, na)
        return out, (xf, w, wg, wu, wd, row_of, token_of_row, tg, na,
                     counts, gu)

    def bwd(res, dout):
        xf, w, wg, wu, wd, row_of, token_of_row, tg, na, counts, (g, u) \
            = res
        e_local, dt = wg.shape[0], xf.dtype
        xs = _take_rows(xf, token_of_row)
        m = xs.shape[0]
        dys = _take_rows(dout, token_of_row)
        w_row = jnp.zeros((m,), jnp.float32).at[row_of.reshape(-1)].set(
            w.reshape(-1).astype(jnp.float32), mode="drop")[:, None]
        # out = sum_a w_a (h_a W_d): with G = dout_a W_d^T, dh = w_a G and
        # dw_a = <G, h_a> — the expert's output is never needed again
        big_g = gmm(dys, wd, tg, na, transpose_rhs=True, **kw) \
            .astype(jnp.float32)
        gf, uf, sg, silu = _silu_parts(g, u)
        h = silu * uf
        dw_row = jnp.sum(big_g * h, axis=-1, keepdims=True)
        dh = big_g * w_row
        dg = (dh * uf * (sg * (1.0 + gf * (1.0 - sg)))).astype(dt)
        du = (dh * silu).astype(dt)
        tk = dict(groups=e_local, **kw)
        dwd = tgmm((h * w_row).astype(dt), dys, tg, na, counts, **tk)
        dwg = tgmm(xs, dg, tg, na, counts, **tk)
        dwu = tgmm(xs, du, tg, na, counts, **tk)
        dxs = gmm(dg, wg, tg, na, transpose_rhs=True, **kw) \
            .astype(jnp.float32) \
            + gmm(du, wu, tg, na, transpose_rhs=True, **kw) \
            .astype(jnp.float32)
        dx = _combine(dxs, row_of).astype(dt)
        dw = _take_rows(dw_row, row_of.reshape(-1)).reshape(w.shape) \
            .astype(w.dtype)

        def f0(t):
            return np.zeros(t.shape, jax.dtypes.float0)

        return (dx, dw, dwg, dwu, dwd, f0(row_of), f0(token_of_row),
                f0(tg), f0(na), f0(counts))

    f.defvjp(fwd, bwd)
    return f


def grouped_ffn(xf, w, idx, wg, wu, wd, *, expert_offset=0, backend="xla",
                tile_m=None):
    """(out [N, d], counts [E_local]) — see the module docstring.
    ``backend``: ``pallas``, ``pallas_interpret`` (the kernels in
    interpret mode, tests) or ``xla`` (``lax.ragged_dot``, differentiated
    by JAX)."""
    e_local = wg.shape[0]
    if backend != "xla":
        from .pallas.grouped_matmul import TILE_M
        tile_m = tile_m or TILE_M
        row_of, token_of_row, counts, tg, na, _ = routing_layout(
            idx, expert_offset, e_local, tile_m)
        fn = _make_grouped_ffn(tile_m, backend == "pallas_interpret")
        return fn(xf, w, wg, wu, wd, row_of, token_of_row, tg, na,
                  counts), counts
    # rows sorted by expert without padding; held-elsewhere rows last,
    # past the sum of the group sizes, where ragged_dot writes zero
    row_of, token_of_row, counts, _, _, _ = routing_layout(
        idx, expert_offset, e_local, 1)
    a = idx.size
    real = jnp.arange(a) < jnp.sum(counts)
    xs = _take_rows(xf, token_of_row[:a])
    hid = jax.nn.silu(lax.ragged_dot(xs, wg, counts)) \
        * lax.ragged_dot(xs, wu, counts)
    y = jnp.where(real[:, None], lax.ragged_dot(hid, wd, counts), 0)
    return _combine(y, row_of, w).astype(xf.dtype), counts


@register("moe_grouped_ffn")
def _moe_grouped_ffn(ctx, ins, attrs):
    xv, w, idx = x(ins, "X"), x(ins, "TopkWeight"), x(ins, "TopkIndex")
    wg, wu, wd = x(ins, "WGate"), x(ins, "WUp"), x(ins, "WDown")
    route, _ = pallas_route("moe_grouped_ffn", ins, attrs)
    from .pallas.grouped_matmul import row_tile
    out, counts = grouped_ffn(
        xv.reshape(-1, xv.shape[-1]), w, idx, wg, wu, wd,
        expert_offset=int(attrs.get("expert_offset", 0)),
        backend="pallas" if route is not None else "xla",
        # from what the op sees: the assignments and the router's width
        tile_m=attrs.get("tile_m") or row_tile(
            idx.size, int(attrs.get("num_experts") or wg.shape[0])))
    return {"Out": out.reshape(xv.shape), "ExpertCount": counts}


#: layout of the persistable load counter: [E_local] per-expert totals,
#: then the sum over steps of the fullest expert's count, then the steps
LOAD_STATS_EXTRA = 2


@register("moe_load_stats")
def _moe_load_stats(ctx, ins, attrs):
    counts, acc = x(ins, "Count"), x(ins, "Acc")
    parts = [counts, jnp.max(counts, keepdims=True),
             jnp.ones((1,), counts.dtype)]
    if attrs.get("count_hit"):
        # one more slot: the held experts with at least one assignment
        parts.append(jnp.sum(counts > 0, keepdims=True)
                     .astype(counts.dtype))
    return {"AccOut": acc + jnp.concatenate(parts).astype(acc.dtype)}


# ---------------------------------------------------------------------------
# head + loss by row blocks
# ---------------------------------------------------------------------------

HEAD_BLOCK_ROWS = 2048


@jax.custom_vjp
def head_loss(xf, w, label):
    """Per-row ``logsumexp(x W) - (x W)[label]`` [N] f32."""
    return _head_loss_fwd(xf, w, label)[0]


def _head_loss_fwd(xf, w, label):
    n, d = xf.shape
    rb = _group_size(n, HEAD_BLOCK_ROWS)   # largest divisor of n in reach

    def block(args):
        xb, lb = args
        lg = jnp.dot(xb, w, preferred_element_type=jnp.float32)
        m = jnp.max(lg, axis=-1, keepdims=True)
        lse = (m + jnp.log(jnp.sum(jnp.exp(lg - m), axis=-1,
                                   keepdims=True)))[:, 0]
        picked = jnp.take_along_axis(lg, lb[:, None], axis=-1)[:, 0]
        return lse - picked, lse, lg.astype(xf.dtype)

    loss, lse, logits = lax.map(
        block, (xf.reshape(n // rb, rb, d), label.reshape(n // rb, rb)))
    return loss.reshape(n), (xf, w, label, lse, logits)


def _head_loss_bwd(res, g):
    xf, w, label, lse, logits = res
    n, d = xf.shape
    nb, rb, v = logits.shape

    def block(dw, args):
        xb, lb, lseb, lgb, gb = args
        p = jnp.exp(lgb.astype(jnp.float32) - lseb[:, None])
        hot = lax.broadcasted_iota(jnp.int32, (rb, v), 1) == lb[:, None]
        dl = ((p - hot.astype(jnp.float32)) * gb[:, None]).astype(xf.dtype)
        dxb = lax.dot_general(dl, w, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
        dw = dw + lax.dot_general(xb, dl, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        return dw, dxb.astype(xf.dtype)

    dw, dx = lax.scan(
        block, jnp.zeros((d, v), jnp.float32),
        (xf.reshape(nb, rb, d), label.reshape(nb, rb), lse, logits,
         g.astype(jnp.float32).reshape(nb, rb)))
    return dx.reshape(n, d), dw.astype(w.dtype), \
        np.zeros(label.shape, jax.dtypes.float0)


head_loss.defvjp(_head_loss_fwd, _head_loss_bwd)


@register("lm_head_loss")
def _lm_head_loss(ctx, ins, attrs):
    xv, w, label = x(ins, "X"), x(ins, "W"), x(ins, "Label")
    loss = head_loss(xv.reshape(-1, xv.shape[-1]), w,
                     label.reshape(-1).astype(jnp.int32))
    return {"Loss": loss.reshape(label.shape)}


@register("lm_head_logits")
def _lm_head_logits(ctx, ins, attrs):
    """The untied head of a SERVED decoder: ``x W`` accumulated and
    returned in float32 whatever the operands' dtype (bfloat16 logits
    would round away the lead a greedy token is chosen by)."""
    xv, w = x(ins, "X"), x(ins, "W")
    return {"Out": jnp.dot(xv, w, preferred_element_type=jnp.float32)}
