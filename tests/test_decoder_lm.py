"""The pre-norm RoPE/GQA decoder with dropless experts
(models/decoder_lm.py, ops/decoder_lm_ops.py, ops/pallas/flash_gqa.py,
ops/pallas/grouped_matmul.py) at a small size on the CPU — hidden 64, 4
query heads on 2, 8 experts top-2, window 8, 32 tokens, 4 layers (three
sliding to one full) — against the plain reference
benchmark/reference/mellum_jnp.py; the Pallas kernels in interpret mode
against their jnp specs; and the share test: the parts of an MoE layer
that the four shares of an ep4 deployment compute sum to the uncut
layer."""

import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu.fluid as fluid  # noqa: E402
from benchmark.reference import mellum_jnp as ref  # noqa: E402
from paddle_tpu.models import decoder_lm as dl  # noqa: E402

GRADS = ["lm_layer_0_qkv_w", "lm_layer_1_router_w",
         "lm_layer_2_expert_gate_w", "lm_layer_3_expert_down_w",
         "lm_layer_3_o_w", "word_embedding", "lm_head_w",
         "final_norm_scale"]


def _build(cfg, amp=False, seed=7):
    from paddle_tpu.contrib.mixed_precision import decorate
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        _, loss, hidden = dl.build_lm_network(cfg)
        opt = fluid.optimizer.Adam(1e-4)
        (decorate(opt, use_pure_bf16=True) if amp else opt).minimize(loss)
    return main, startup, loss, hidden


def _weights(scope):
    return {n: np.asarray(scope.find_var(n)) for n in scope.var_names()
            if not (n.startswith("@") or "moment" in n or "pow_acc" in n
                    or n.startswith("learning_rate")
                    or n.endswith(".load_stats"))}


@pytest.fixture(scope="module")
def tiny_run():
    """One f32 step of the tiny share (experts 2..5 of 8) and the
    reference on the same weights and batch."""
    held = (2, 6)
    cfg = dataclasses.replace(dl.DecoderLMConfig.tiny(), held_experts=held)
    main, startup, loss, hidden = _build(cfg)
    exe = fluid.Executor(fluid.TPUPlace(0))
    batch = dl.make_fake_batch(np.random.RandomState(0), cfg, 2, 32)
    with fluid.scope_guard(fluid.Scope()):
        scope = fluid.global_scope()
        exe.run(startup)
        w = _weights(scope)
        out = exe.run(main, feed=batch, fetch_list=[loss, hidden]
                      + [n + "@GRAD" for n in GRADS])
        acc = {n: np.asarray(scope.find_var(n)) for n in scope.var_names()
               if n.endswith(".load_stats")}
        after = _weights(scope)
    m = dataclasses.asdict(cfg)
    want, grads, routed = ref.lm_loss_and_grads(
        w, batch, GRADS, m, held=held, q_block=16, row_block=32)
    return dict(cfg=cfg, m=m, held=held, w=w, batch=batch, out=out, acc=acc,
                after=after, want=float(want), grads=grads, routed=routed)


def test_loss_and_logits_match_the_reference(tiny_run):
    r = tiny_run
    assert abs(float(r["out"][0]) - r["want"]) / r["want"] < 1e-5
    logits = np.asarray(r["out"][1], np.float32) @ r["w"]["lm_head_w"]
    want = np.asarray(ref.logits(r["w"], r["batch"]["src_ids"], r["m"],
                                 held=r["held"]))
    assert np.max(np.abs(logits - want)) < 1e-4 * np.max(np.abs(want))


@pytest.mark.parametrize("name", GRADS)
def test_gradient_matches_the_reference(tiny_run, name):
    got = np.asarray(tiny_run["out"][2 + GRADS.index(name)], np.float32)
    want = np.asarray(tiny_run["grads"][name])
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 1e-4


@pytest.mark.parametrize("name", ["lm_layer_0_qkv_w",
                                  "lm_layer_3_expert_down_w"])
def test_the_steps_update_is_adams_first_step(tiny_run, name):
    """What the comparison of a ``train_lm`` cell holds the optimizer to:
    the parameter's change against the reference's Adam on the fetched
    gradient; a state left unchanged would read 1."""
    r = tiny_run
    want = ref.adam_first_step(r["out"][2 + GRADS.index(name)], 1e-4)
    got = r["after"][name].astype(np.float64) - r["w"][name]
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 1e-3
    assert np.linalg.norm(0 * got - want) / np.linalg.norm(want) == 1.0


@pytest.mark.parametrize("key", ["softmax_dtype", "residual_dtype"])
def test_a_lowered_part_of_the_reference_reads_differently(tiny_run, key):
    """The probe's controls: the f32 parts computed in bfloat16 move the
    reference's loss and gradient; left out, it is the yardstick."""
    r = tiny_run
    name = "lm_layer_0_qkv_w"
    low, grads, _ = ref.lm_loss_and_grads(
        r["w"], r["batch"], [name], {**r["m"], key: jnp.bfloat16},
        held=r["held"], q_block=16, row_block=32)
    assert 0 < abs(float(low) - r["want"]) / r["want"] < 2e-2
    want = np.asarray(r["grads"][name])
    err = np.linalg.norm(np.asarray(grads[name]) - want) \
        / np.linalg.norm(want)
    assert 1e-3 < err < 0.5


def test_device_counters_equal_the_references_count(tiny_run):
    r = tiny_run
    want = np.asarray(ref.local_counts(r["routed"], r["held"],
                                       r["cfg"].num_experts))
    for i in range(r["cfg"].num_hidden_layers):
        acc = r["acc"][f"lm_layer_{i}_moe.load_stats"]
        assert acc[:4].tolist() == want[i].tolist()
        assert acc[4] == want[i].max() and acc[5] == 1


def test_tolerance_refuses_a_window_off_by_one_and_a_missing_factor(
        tiny_run):
    """The comparison is tight enough: a reference with the window one
    wider, or without YaRN's attention factor, is far outside what the
    program agrees to."""
    r = tiny_run
    got = np.asarray(r["out"][2], np.float32)
    rope = {**r["m"]["rope_parameters"], "full_attention": {
        **r["m"]["rope_parameters"]["full_attention"],
        "attention_factor": 1.0}}
    for broken in ({"sliding_window": 9}, {"rope_parameters": rope}):
        _, grads, _ = ref.lm_loss_and_grads(
            r["w"], r["batch"], GRADS[:1], {**r["m"], **broken},
            held=r["held"], q_block=16, row_block=32)
        want = np.asarray(grads[GRADS[0]])
        assert np.linalg.norm(got - want) / np.linalg.norm(want) > 1e-2


def test_amp_build_trains_and_counts_through_the_prepared_path():
    cfg = dataclasses.replace(dl.DecoderLMConfig.tiny(),
                              held_experts=(0, 4), initializer_range=0.02)
    main, startup, loss, _ = _build(cfg, amp=True)
    exe = fluid.Executor(fluid.TPUPlace(0))
    batch = dl.make_fake_batch(np.random.RandomState(1), cfg, 2, 32)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        w = _weights(fluid.global_scope())
        prepared = exe.prepare(main, fetch_list=[loss])
        assert prepared.stats["moe_assignments_local"] == 0
        losses = [float(prepared.run(batch)[0]) for _ in range(5)]
        prepared.wait()
        stats = dict(prepared.stats)
    want, _, routed = ref.lm_loss_and_grads(
        w, batch, [], dataclasses.asdict(cfg), held=(0, 4), q_block=16,
        row_block=32)
    assert abs(losses[0] - float(want)) / float(want) < 5e-3
    assert losses[-1] < losses[0]
    first = int(np.asarray(ref.local_counts(routed, (0, 4), 8)).sum())
    # five steps on nearly the same weights: about five times step one's
    assert 4 * first <= stats["moe_assignments_local"] <= 6 * first
    assert stats["moe_expert_load_max"] >= stats["moe_expert_load_mean"] > 0


def test_prepared_step_folds_any_declared_device_counter():
    """``program._device_counters`` is generic: name -> (stat key,
    reduce) pairs; the executor knows nothing of what they count."""
    cfg = dataclasses.replace(dl.DecoderLMConfig.tiny(), held_experts=(0, 4))
    main, startup, loss, _ = _build(cfg)
    name = "lm_layer_0_moe.load_stats"
    main._device_counters[name] += (("layer0_steps",
                                     lambda gain: int(gain[-1])),)
    exe = fluid.Executor(fluid.TPUPlace(0))
    batch = dl.make_fake_batch(np.random.RandomState(2), cfg, 2, 32)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        prepared = exe.prepare(main, fetch_list=[loss])
        for _ in range(3):
            prepared.run(batch)
        prepared.wait()
        assert prepared.stats["layer0_steps"] == 3
        prepared.run(batch)
        prepared.wait()
        assert prepared.stats["layer0_steps"] == 4
        assert prepared.stats["moe_assignments_local"] > 0


def test_the_cells_seed_picks_a_draw_and_an_order():
    """benchmark/builders/train_lm.py: the traffic file lists draws of
    like work; ``--seed`` picks one and permutes its sequences."""
    from benchmark.builders import train_lm
    traffic = {"draws": [11, 12, 13], "global_batch": 1, "seq_len": 16,
               "distinct_batches": 8}
    assert [train_lm.draw_of(traffic, s) for s in (0, 4, 2 ** 31 + 7)] \
        == [11, 12, 11 + (2 ** 31 + 7) % 3]
    a, b, c = (train_lm.lm_batches(traffic, 256, s) for s in (3, 6, 4))

    def rows(batches):
        return sorted(tuple(x["src_ids"][0]) for x in batches)
    assert rows(a) == rows(b) != rows(c)          # 3 and 6: draw 11
    assert [tuple(x["src_ids"][0]) for x in a] \
        != [tuple(x["src_ids"][0]) for x in b]    # in another order
    for x in a:     # labels are the tokens one step on
        assert (x["src_ids"][0, 1:] == x["labels"][0, :-1]).all()


def test_amp_keeps_the_routers_weights_in_fp32():
    cfg = dl.DecoderLMConfig.tiny()
    main, _, _, _ = _build(cfg, amp=True)
    ops = main.global_block().ops
    ffn = [op for op in ops if op.type == "moe_grouped_ffn"]
    assert len(ffn) == 4
    for op in ffn:
        assert "cast" not in op.inputs["TopkWeight"][0]
        assert "cast_bfloat16" in op.inputs["X"][0]
        assert "cast_bfloat16" in op.inputs["WGate"][0]


# ---------------------------------------------------------------------------
# the share test
# ---------------------------------------------------------------------------

def test_four_shares_sum_to_the_uncut_layer():
    """ep4 over 8 experts: shares (0,2) (2,4) (4,6) (6,8), each through
    the PROGRAM's op pair with only its own experts' weights, sum to the
    reference's whole layer."""
    from paddle_tpu.ops.decoder_lm_ops import grouped_ffn
    cfg = dl.DecoderLMConfig.tiny()
    m = dataclasses.asdict(cfg)
    rng = np.random.RandomState(3)
    d, f, e = cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts
    w = {"p_router_w": rng.randn(d, e).astype(np.float32) * 0.5,
         "p_expert_gate_w": rng.randn(e, d, f).astype(np.float32) * 0.2,
         "p_expert_up_w": rng.randn(e, d, f).astype(np.float32) * 0.2,
         "p_expert_down_w": rng.randn(e, f, d).astype(np.float32) * 0.2}
    x = rng.randn(2, 32, d).astype(np.float32)
    whole = np.asarray(ref.moe_layer(w, x, m, "p"))
    vals, idx = ref.route(jnp.asarray(x.reshape(-1, d)),
                          jnp.asarray(w["p_router_w"]), m)
    total = np.zeros_like(x.reshape(-1, d))
    counted = 0
    for lo in range(0, e, 2):
        out, counts = grouped_ffn(
            jnp.asarray(x.reshape(-1, d)), vals, idx.astype(jnp.int32),
            *(jnp.asarray(w[f"p_expert_{k}_w"][lo:lo + 2])
              for k in ("gate", "up", "down")), expert_offset=lo)
        share = np.asarray(ref.moe_layer(
            {k: (v if k == "p_router_w" else v[lo:lo + 2])
             for k, v in w.items()}, x, m, "p", held=(lo, lo + 2)))
        assert np.allclose(np.asarray(out), share.reshape(-1, d), atol=1e-4)
        total += np.asarray(out)
        counted += int(np.asarray(counts).sum())
    assert np.allclose(total, whole.reshape(-1, d), atol=2e-4)
    assert counted == x.shape[0] * x.shape[1] * cfg.num_experts_per_tok


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_dropless_under_a_router_biased_to_one_expert(backend):
    """Every token's first choice is expert 1: it takes all 64 tokens (a
    capacity of 1.25 x the mean would keep 20), none is dropped, and the
    result is the dense computation."""
    from paddle_tpu.ops.decoder_lm_ops import grouped_ffn
    rng = np.random.RandomState(5)
    n, d, f, e, k = 64, 16, 24, 8, 2
    x = jnp.asarray(rng.randn(n, d).astype(np.float32))
    logits = rng.randn(n, e).astype(np.float32)
    logits[:, 1] += 20.0
    vals, idx = jax.lax.top_k(jax.nn.softmax(jnp.asarray(logits)), k)
    wg, wu = (jnp.asarray(rng.randn(e, d, f).astype(np.float32) * 0.3)
              for _ in range(2))
    wd = jnp.asarray(rng.randn(e, f, d).astype(np.float32) * 0.3)
    out, counts = grouped_ffn(x, vals, idx.astype(jnp.int32), wg, wu, wd,
                              backend=backend, tile_m=8)
    assert int(counts[1]) == n and int(counts.sum()) == n * k
    dense = jnp.einsum("nef,efd->ned",
                       jax.nn.silu(jnp.einsum("nd,edf->nef", x, wg))
                       * jnp.einsum("nd,edf->nef", x, wu), wd)
    want = jnp.einsum("nk,nkd->nd", vals,
                      jnp.take_along_axis(dense, idx[:, :, None], axis=1))
    assert np.allclose(np.asarray(out), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize("tile_m", [8, 16])
def test_grouped_kernels_match_ragged_dot_with_an_empty_expert(tile_m):
    from paddle_tpu.ops.decoder_lm_ops import grouped_ffn
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    n, d, f, e, el, k = 48, 16, 24, 8, 3, 2
    x = jax.random.normal(ks[0], (n, d))
    wg = jax.random.normal(ks[1], (el, d, f)) * .3
    wu = jax.random.normal(ks[2], (el, d, f)) * .3
    wd = jax.random.normal(ks[3], (el, f, d)) * .3
    w, idx = jax.lax.top_k(jax.nn.softmax(
        jax.random.normal(ks[4], (n, e))), k)
    idx = jnp.where(idx == 3, 7, idx).astype(jnp.int32)   # expert 3: none
    g = jax.random.normal(ks[5], (n, d))

    def run(backend):
        def loss(*a):
            out, c = grouped_ffn(a[0], a[1], idx, *a[2:], expert_offset=2,
                                 backend=backend, tile_m=tile_m)
            return jnp.sum(out * g), c
        return jax.value_and_grad(loss, (0, 1, 2, 3, 4),
                                  has_aux=True)(x, w, wg, wu, wd)
    (l1, c1), g1 = run("xla")
    (l2, c2), g2 = run("pallas_interpret")
    assert c1.tolist() == c2.tolist() and int(c1[1]) == 0
    assert abs(float(l1) - float(l2)) < 1e-4
    for a, b in zip(g1, g2):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4


# ---------------------------------------------------------------------------
# attention: window and grouped heads
# ---------------------------------------------------------------------------

def _sub_tiled(window, sub=8, seq=64, h=8, hkv=1, blk=32):
    """DMA tiles of 32 computed in ``sub`` sub-tiles (8 x 8; a pair:
    rows x lanes), 8 query heads on one K/V head."""
    return (seq, h, hkv, 8, window, blk, sub)


@pytest.mark.parametrize("seq,h,hkv,d,window,blk,sub", [
    (32, 4, 2, 16, 8, 8, None), (32, 4, 2, 16, None, 8, None),
    (32, 4, 4, 16, 8, 16, None), (64, 8, 2, 8, 20, 8, None),
    (32, 4, 1, 16, 1, 8, None), (32, 2, 1, 16, 7, 8, None),
    (32, 2, 1, 16, 9, 8, None),
    # the window's edge inside a sub-tile, on a sub-tile boundary and one
    # either side of it; on the tile boundary and one either side
    _sub_tiled(12), _sub_tiled(15), _sub_tiled(16), _sub_tiled(17),
    _sub_tiled(31), _sub_tiled(32), _sub_tiled(33),
    # one position, past the sequence, full causal
    _sub_tiled(1), _sub_tiled(100), _sub_tiled(None),
    # three tiles to a sequence: a plain tile between the two crossed
    _sub_tiled(40, seq=96), _sub_tiled(None, seq=96),
    # sub-tiles that are not square
    _sub_tiled(17, sub=(16, 8)), _sub_tiled(23, sub=(8, 16)),
    _sub_tiled(None, sub=(16, 8)),
    # 1 and 2 query heads a K/V head; 16, which take two steps of 8
    _sub_tiled(17, h=2, hkv=2), _sub_tiled(17, h=4, hkv=2),
    _sub_tiled(9, h=16, hkv=1, seq=32, blk=16)])
def test_flash_gqa_kernels_in_interpret_mode(seq, h, hkv, d, window, blk,
                                             sub):
    from paddle_tpu.ops.pallas import flash_gqa as fg
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (2, seq, h * d))
    k = jax.random.normal(ks[1], (2, seq, hkv * d))
    v = jax.random.normal(ks[2], (2, seq, hkv * d))
    g = jax.random.normal(ks[3], (2, seq, h * d))
    kw = dict(n_head=h, n_kv_head=hkv, window=window)

    def both(fn, **extra):
        return jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a, **kw, **extra) * g), (0, 1, 2))(
                q, k, v)
    l1, g1 = both(fg.flash_gqa_bsd, block=blk, sub=sub, interpret=True)
    l2, g2 = both(fg.reference)
    assert abs(float(l1) - float(l2)) < 1e-3
    for a, b in zip(g1, g2):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4


def test_window_blocks_are_skipped_not_masked():
    """The key axis of the grid holds only the blocks a query block can
    see: 3 of 16 at window 1024 and blocks of 512; all 16 without."""
    from paddle_tpu.ops.pallas import flash_gqa as fg
    assert fg.grid_steps(8192, 512, 1024) == 3
    assert fg.grid_steps(8192, 512, 0) == 16
    assert fg.grid_steps(32, 8, 9) == 2 and fg.grid_steps(32, 8, 10) == 3


@pytest.mark.parametrize("seq,blk,sub,window", [
    (8192, 512, 128, 1024), (8192, 512, 128, 0),
    (8192, 512, None, 1024), (8192, 512, None, 0),
    (64, 32, 8, 17), (96, 32, (16, 8), 40), (64, 16, 4, 100),
    (32, 8, 8, 9)])
def test_subtile_counts_match_the_mask(seq, blk, sub, window):
    """``subtile_counts`` reads the plan the kernel bodies are unrolled
    from; here every sub-tile of every tile is looked up in the mask
    itself: nothing visible lies in a skipped one or outside the grid's
    tiles, nothing hidden in a plain one."""
    from paddle_tpu.ops.pallas import flash_gqa as fg
    _, rows, lanes = fg.pick_sub(blk, sub)
    i, j = np.arange(seq)[:, None], np.arange(seq)[None, :]
    ok = (j <= i) & ((i - j < window) if window else True)
    tiles = ok.reshape(seq // blk, blk, seq // blk, blk).swapaxes(1, 2)
    want = dict(plain=0, masked=0, skipped=0)
    steps = fg.grid_steps(seq, blk, window)
    for qi in range(seq // blk):
        for kj in range(seq // blk):
            tile = tiles[qi, kj]
            if not 0 <= qi - kj < steps:
                assert not tile.any()
                continue
            subs = tile.reshape(blk // rows, rows, blk // lanes,
                                lanes).swapaxes(1, 2).reshape(-1, rows * lanes)
            full, some = subs.all(axis=1), subs.any(axis=1)
            want["plain"] += int(full.sum())
            want["masked"] += int((some & ~full).sum())
            want["skipped"] += int((~some).sum())
    assert fg.subtile_counts(seq, blk, sub, window) == tuple(want.values())


def test_subtile_counts_at_the_cell_shapes():
    """Mellum2's attention at the timed size, a kernel call and query
    head.  In 128 x 128 sub-tiles a query block's 48 are 28 plain, 8
    masked, 12 skipped under the window; in what ``pick_sub`` chooses,
    256 x 128 (rows of 128 ran slower: PERF.md, PR 35), its 24 are 12,
    8 and 4.  Whole tiles skip nothing."""
    from paddle_tpu.ops.pallas import flash_gqa as fg
    assert fg.pick_sub(fg.pick_block(8192)) == (512, 256, 128)
    assert fg.pick_sub(fg.pick_block(384)) == (128, 128, 128)
    assert fg.subtile_counts(8192, 512, 128, 1024) == (420, 120, 180)
    assert fg.subtile_counts(8192, 512, 128, 0) == (2016, 64, 96)
    assert fg.subtile_counts(8192, 512, None, 1024) == (180, 120, 60)
    assert fg.subtile_counts(8192, 512, None, 0) == (992, 64, 32)
    assert fg.subtile_counts(8192, 512, 512, 1024) == (15, 30, 0)
    far = [fg.subtile_counts(512 * n, 512, 128, 1024) for n in (15, 16)]
    assert tuple(b - a for a, b in zip(*far)) == (28, 8, 12)


def test_fused_attention_takes_window_and_kv_heads_by_attrs():
    from paddle_tpu.models.bert import fused_attention
    from paddle_tpu.ops.pallas import flash_gqa as fg
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        q = fluid.layers.data("q", [2, 32, 64], append_batch_size=False)
        k = fluid.layers.data("k", [2, 32, 32], append_batch_size=False)
        v = fluid.layers.data("v", [2, 32, 32], append_batch_size=False)
        out = fused_attention(q, k, v, None, 4, 0.0, True, "t", causal=True,
                              window=8, num_kv_heads=2)
        plain = fused_attention(q, q, q, None, 4, 0.0, True, "u")
    assert "window" not in plain.block.ops[-1].attrs
    rng = np.random.RandomState(0)
    feed = {n: rng.randn(2, 32, w).astype(np.float32)
            for n, w in (("q", 64), ("k", 32), ("v", 32))}
    got, = fluid.Executor(fluid.TPUPlace(0)).run(
        main, feed=feed, fetch_list=[out])
    want = fg.reference(*(jnp.asarray(feed[n]) for n in "qkv"), n_head=4,
                        n_kv_head=2, window=8)
    assert np.allclose(got, np.asarray(want), atol=1e-5)


def test_routes_at_the_published_widths_are_static():
    """0 compiles: on a TPU the model's attention takes the grouped-head
    kernels and its experts the grouped matmul; BERT's route is what it
    was."""
    from paddle_tpu.framework.analysis import kernel_routing_report
    cfg = dl.DecoderLMConfig(vocab_size=1024, num_hidden_layers=4,
                             held_experts=(0, 16))
    main, _, _, _ = _build(cfg)
    rep = kernel_routing_report(
        main, feed_shapes={"src_ids": ((1, 8192), "int64"),
                           "labels": ((1, 8192), "int64")},
        backend="tpu")
    rows = {(r["op"], r["kernel"]) for r in rep["rows"]
            if r["route"] == "pallas"}
    assert ("fused_attention", "flash_gqa_attention") in rows
    assert ("moe_grouped_ffn", "moe_grouped_matmul") in rows
    assert not any(k in ("flash_attention", "attention_tile")
                   for _, k in rows)


# ---------------------------------------------------------------------------
# the small ops
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_rotary_tables_match_the_reference(kind):
    from paddle_tpu.ops.decoder_lm_ops import rope_inv_freq
    rope = {"full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}}[kind]
    got, factor = rope_inv_freq(128, rope)
    want, want_factor = ref.rope_inv_freq(128, rope)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert factor == want_factor
    if kind == "full_attention":
        plain = 1.0 / 500000 ** (np.arange(0, 128, 2) / 128)
        # fast dimensions keep their frequency, slow ones are divided by 16
        assert np.isclose(got[0], plain[0])
        assert np.isclose(got[-1], plain[-1] / 16)
        assert factor == pytest.approx(0.1 * np.log(16) + 1)


def test_lm_head_loss_is_the_plain_head_and_cross_entropy():
    from paddle_tpu.ops.decoder_lm_ops import head_loss
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(ks[0], (96, 16))
    w = jax.random.normal(ks[1], (16, 40))
    label = jax.random.randint(ks[2], (96,), 0, 40)

    def plain(x, w):
        logp = jax.nn.log_softmax(x @ w)
        return -jnp.take_along_axis(logp, label[:, None], 1)[:, 0]
    scale = jnp.arange(96.0)
    l1, g1 = jax.value_and_grad(
        lambda x, w: jnp.sum(head_loss(x, w, label) * scale), (0, 1))(x, w)
    l2, g2 = jax.value_and_grad(
        lambda x, w: jnp.sum(plain(x, w) * scale), (0, 1))(x, w)
    assert abs(float(l1) - float(l2)) < 1e-2
    for a, b in zip(g1, g2):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-3


def test_specs_of_the_new_ops():
    from paddle_tpu.ops.registry import OP_SPECS, SpecMismatch, VarSig
    x = VarSig((2, 32, 64), "float32")
    out = OP_SPECS["moe_topk_router"].infer(
        {"X": [x], "W": [VarSig((64, 8), "float32")]}, {"top_k": 2})
    assert out["TopkWeight"][0] == VarSig((64, 2), "float32")
    assert out["TopkIndex"][0] == VarSig((64, 2), "int32")
    out = OP_SPECS["moe_grouped_ffn"].infer(
        {"X": [x], "WGate": [VarSig((4, 64, 32), "float32")]}, {})
    assert out["ExpertCount"][0] == VarSig((4,), "int32")
    attn = OP_SPECS["fused_attention"].infer
    kv = VarSig((2, 32, 32), "float32")
    assert attn({"Q": [x], "K": [kv], "V": [kv]},
                {"n_head": 4, "num_kv_heads": 2})["Out"][0] == x
    with pytest.raises(SpecMismatch):
        attn({"Q": [x], "K": [kv], "V": [kv]}, {"n_head": 4})
    with pytest.raises(SpecMismatch):
        OP_SPECS["lm_head_loss"].infer(
            {"X": [x], "W": [VarSig((32, 100), "float32")],
             "Label": [VarSig((2, 32), "int64")]}, {})
