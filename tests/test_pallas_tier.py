"""The per-op Pallas lowering tier (ops/registry.py pallas channel):
static routing report, hit/fallback metrics counters, interpret-mode
parity of the grafted kernels (ring-attention-via-flash,
dequant-accumulate), and the census: every route's ``kernels=`` in the
TPU-lowered module of the smallest program that hits the route."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp


def _bert_tiny_train():
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import bert
    cfg = bert.BertConfig.tiny()
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        feeds, total, mlm, nsp = bert.build_pretrain_network(cfg)
        fluid.optimizer.Adam(1e-4).minimize(total)
    return cfg, main_p, startup, total


def _feed_arrays(cfg, seq):
    from paddle_tpu.models import bert
    data = bert.make_fake_batch(np.random.RandomState(0), cfg,
                                batch_size=4, seq_len=seq, num_masks=3)
    return {k: np.asarray(v) for k, v in data.items()}


# ---------------------------------------------------------------------------
# static routing report
# ---------------------------------------------------------------------------


def test_routing_report_flash_hit_at_128_fallback_at_64():
    from paddle_tpu.framework.analysis import kernel_routing_report
    cfg, main_p, _, total = _bert_tiny_train()
    rep = kernel_routing_report(main_p, feed_shapes=_feed_arrays(cfg, 128),
                                backend="tpu")
    # seq 128 is one tile: both layers take the one-tile route
    assert rep["summary"]["attention_tile"]["pallas"] == 2
    assert rep["summary"]["attention_tile"]["fallback"] == 0
    assert "flash_attention" not in rep["summary"]
    assert rep["summary"]["fused_layer_norm"]["pallas"] > 0
    # the optimizer update has no kernel route: XLA's own fusion
    assert "fused_adam" not in rep["summary"]
    rep64 = kernel_routing_report(main_p,
                                  feed_shapes=_feed_arrays(cfg, 64),
                                  backend="tpu")
    fb = [r for r in rep64["rows"] if r["op"] == "fused_attention"]
    assert fb and all(r["route"] == "fallback" for r in fb)
    assert all("seq" in r["reason"] for r in fb)


def test_proglint_kernels_json_embeds_the_routing_report():
    import io
    import json
    from tools.proglint import lint
    cfg, main_p, _, total = _bert_tiny_train()
    sink = io.StringIO()
    assert lint(main_p, fetch_names=[total.name], kernels=True,
                as_json=True, out=sink) == 0
    rows = json.loads(sink.getvalue())["kernel_routing"]["rows"]
    assert rows and all(r["route"] in ("pallas", "fallback")
                        and r["reason"] for r in rows)


def test_routing_report_zero_compiles(monkeypatch):
    """The report is pure static analysis — no Executor compile, no jax
    trace may happen."""
    from paddle_tpu.framework import executor as executor_mod
    from paddle_tpu.framework.analysis import kernel_routing_report

    def _boom(*a, **kw):
        raise AssertionError("kernel_routing_report triggered a compile")

    monkeypatch.setattr(executor_mod.Executor, "_compile", _boom)
    monkeypatch.setattr(jax, "jit",
                        lambda *a, **kw: _boom())
    cfg, main_p, _, _ = _bert_tiny_train()
    rep = kernel_routing_report(main_p, feed_shapes=_feed_arrays(cfg, 128),
                                backend="tpu")
    assert rep["rows"]


def test_routing_report_cpu_backend_all_fallback():
    from paddle_tpu.framework.analysis import kernel_routing_report
    cfg, main_p, _, _ = _bert_tiny_train()
    rep = kernel_routing_report(main_p, feed_shapes=_feed_arrays(cfg, 128),
                                backend="cpu")
    assert all(r["route"] == "fallback" for r in rep["rows"])
    assert any("backend:cpu" in r["reason"] for r in rep["rows"])


def test_routing_report_ring_route_with_sp_mesh():
    """A fused_attention op stamped with _seq_axis routes to the ring
    flash kernel when the sp shard tiles, with the sp size taken from
    the mesh map."""
    from paddle_tpu.framework.analysis import kernel_routing_report
    from paddle_tpu.framework.core import Program, program_guard

    main_p = Program()
    with program_guard(main_p, Program()):
        b = main_p.global_block()
        for n, shape in (("q", (2, 512, 128)), ("k", (2, 512, 128)),
                         ("v", (2, 512, 128))):
            b.create_var(name=n, shape=shape, dtype="float32",
                         is_data=True)
        b.create_var(name="o", shape=(2, 512, 128), dtype="float32")
        b.append_op(type="fused_attention",
                    inputs={"Q": ["q"], "K": ["k"], "V": ["v"]},
                    outputs={"Out": ["o"]},
                    attrs={"n_head": 2, "_seq_axis": "sp"})
    rep = kernel_routing_report(main_p, backend="tpu",
                                mesh_axes={"sp": 4})
    (row,) = rep["rows"]
    assert row["kernel"] == "ring_flash_attention"
    assert row["route"] == "pallas"          # 512/4 = 128 tiles
    rep8 = kernel_routing_report(main_p, backend="tpu",
                                 mesh_axes={"sp": 8})
    (row8,) = rep8["rows"]
    assert row8["route"] == "fallback"       # 512/8 = 64 does not
    assert "seq" in row8["reason"]


# ---------------------------------------------------------------------------
# hit/fallback counters (the _warned_fallback replacement)
# ---------------------------------------------------------------------------


def _attn_sigs(s, hidden=128):
    from paddle_tpu.ops.registry import VarSig
    sig = VarSig((2, s, hidden), "float32")
    return {"Q": [sig], "K": [sig], "V": [sig]}


def test_pallas_route_counters_every_fallback_counted():
    from paddle_tpu.observability import metrics
    from paddle_tpu.ops.pallas import lowering_target
    from paddle_tpu.ops.registry import pallas_route

    metrics.reset_metrics()
    attrs = {"n_head": 2}
    with lowering_target("tpu"):
        for _ in range(3):
            route, reason = pallas_route("fused_attention",
                                         _attn_sigs(100), attrs)
            assert route is None and "seq" in reason
        route, reason = pallas_route("fused_attention", _attn_sigs(128),
                                     attrs)
        assert route is not None and route.kernel == "attention_tile"
        route, reason = pallas_route("fused_attention", _attn_sigs(256),
                                     attrs)
        assert route is not None and route.kernel == "flash_attention"
    # a fallback is filed under the last (general) route in play and
    # carries every route's reason
    c_fb = metrics.counter("pallas_routes", op="fused_attention",
                           kernel="flash_attention", outcome="fallback",
                           reason="one-tile:100x100; seq:100x100%128")
    assert c_fb.get() == 3            # EVERY fallback counted, not one
    for kernel in ("attention_tile", "flash_attention"):
        c_hit = metrics.counter("pallas_routes", op="fused_attention",
                                kernel=kernel, outcome="hit",
                                reason="supported")
        assert c_hit.get() == 1


def _tile_route_cases():
    from paddle_tpu.ops.registry import VarSig
    plain = _attn_sigs(128)
    bias = lambda *shape: [VarSig(shape, "float32")]
    pool = [VarSig((16, 128, 128), "float32")]
    cached = {"Q": plain["Q"], "KPool": pool, "VPool": pool,
              "BlockTable": [VarSig((2, 1), "int32")],
              "CtxLen": [VarSig((2,), "int32")]}
    return [
        # (id, ins, attrs, axis_sizes, kernel the table picks)
        ("one-tile", plain, {}, None, "attention_tile"),
        ("one-tile-mask-bias", dict(plain, AttnBias=bias(2, 1, 128, 128)),
         {}, None, "attention_tile"),
        ("one-tile-key-bias", dict(plain, AttnBias=bias(2, 1, 1, 128)),
         {}, None, "attention_tile"),
        ("per-head-bias", dict(plain, AttnBias=bias(2, 2, 128, 128)),
         {}, None, "flash_attention"),
        ("causal", plain, {"causal": True}, None, "flash_attention"),
        ("seq256", _attn_sigs(256), {}, None, "flash_attention"),
        ("cached", cached, {"_cached": True}, None,
         "cached_flash_attention"),
        ("cached-decode-step", dict(cached, Q=[VarSig((2, 1, 128),
                                                      "float32")]),
         {"_cached": True}, None, "paged_decode_attention"),
        # a head of whole lane tiles takes the wide body; heads that
        # share a tile (64: the serving cell's, 32) keep the narrow one
        ("cached-decode-step-heads-of-32",
         dict(cached, Q=[VarSig((2, 1, 128), "float32")]),
         {"_cached": True, "n_head": 4}, None, "paged_decode_attention"),
        ("cached-decode-step-heads-of-128",
         dict(cached, Q=[VarSig((2, 1, 128), "float32")]),
         {"_cached": True, "n_head": 1}, None,
         "paged_decode_attention_wide"),
        ("cached-decode-step-heads-of-128-bf16-pools", {
            "Q": [VarSig((64, 1, 3840), "bfloat16")],
            "KPool": [VarSig((13312, 16, 3840), "bfloat16")],
            "VPool": [VarSig((13312, 16, 3840), "bfloat16")],
            "BlockTable": [VarSig((64, 1024), "int32")],
            "CtxLen": [VarSig((64,), "int32")]},
         {"_cached": True, "n_head": 30}, None,
         "paged_decode_attention_wide"),
        ("cached-decode-step-heads-of-256",
         dict(cached, Q=[VarSig((2, 1, 256), "float32")],
              KPool=[VarSig((16, 128, 256), "float32")],
              VPool=[VarSig((16, 128, 256), "float32")]),
         {"_cached": True, "n_head": 1}, None,
         "paged_decode_attention_wide"),
        ("cached-chunk-heads-of-128", cached,
         {"_cached": True, "n_head": 1}, None, "cached_flash_attention"),
        ("cached-chunk-qpos", dict(cached, QPos=[VarSig((2, 128),
                                                        "int64")]),
         {"_cached": True}, None, "cached_flash_attention"),
        ("ring-stamped", _attn_sigs(512), {"_seq_axis": "sp"}, {"sp": 4},
         "ring_flash_attention"),
    ]


@pytest.mark.parametrize(
    "ins,attrs,axis_sizes,want",
    [pytest.param(*c[1:], id=c[0]) for c in _tile_route_cases()])
def test_route_table_picks_tile_only_for_one_tile(ins, attrs, axis_sizes,
                                                  want):
    """The one-tile route takes (Sq == Sk == 128, non-causal, no pool,
    no ring, head-shared bias) and nothing else; causal, cached,
    ring-stamped and S = 256 keep the routes they had."""
    from paddle_tpu.ops.pallas import lowering_target
    from paddle_tpu.ops.registry import pallas_route
    attrs = {"n_head": 2, **attrs}
    with lowering_target("tpu"):
        route, reason = pallas_route("fused_attention", ins, attrs,
                                     axis_sizes=axis_sizes, count=False)
    assert route is not None, reason
    assert route.kernel == want


def test_fused_attention_op_counts_tile_hits_and_lowers_its_kernels():
    """Trace-time census: the op impl's plain branch resolves the
    one-tile route (hit counter by op, kernel, reason) and the traced
    program holds that route's kernels in the op's (B, S, H*D) layout —
    no transpose around them; a causal op of the same shape still
    counts and lowers the blockwise route's."""
    from paddle_tpu.observability import metrics
    from paddle_tpu.ops.pallas import lowering_target
    from paddle_tpu.ops.registry import LoweringContext, get_op

    impl = get_op("fused_attention")
    q = jnp.zeros((4, 128, 128), jnp.bfloat16)
    bias = jnp.zeros((4, 1, 128, 128), jnp.float32)

    def loss(causal):
        def f(q, k, v):
            ctx = LoweringContext(jax.random.PRNGKey(0), is_test=False)
            out = impl(ctx, {"Q": [q], "K": [k], "V": [v],
                             "AttnBias": [bias]},
                       {"n_head": 2, "dropout_rate": 0.1,
                        "causal": causal})["Out"]
            return jnp.sum(out.astype(jnp.float32))
        return f

    def hits(kernel):
        return metrics.counter("pallas_routes", op="fused_attention",
                               kernel=kernel, outcome="hit",
                               reason="supported").get()

    metrics.reset_metrics()
    with lowering_target("tpu"):
        tile = str(jax.make_jaxpr(jax.grad(loss(False), (0, 1, 2)))(q, q, q))
        assert (hits("attention_tile"), hits("flash_attention")) == (1, 0)
        flash = str(jax.make_jaxpr(jax.grad(loss(True), (0, 1, 2)))(q, q, q))
        assert (hits("attention_tile"), hits("flash_attention")) == (1, 1)
    routes = _table_pairs()
    for name in routes[("fused_attention", "attention_tile")].kernels:
        assert name in tile
    assert "flash_" not in tile and "transpose[" not in tile
    for name in routes[("fused_attention", "flash_attention")].kernels:
        assert name in flash
    assert "attn_tile" not in flash


def test_pallas_route_flag_and_backend_reasons():
    from paddle_tpu import flags
    from paddle_tpu.ops.pallas import lowering_target
    from paddle_tpu.ops.registry import pallas_route

    route, reason = pallas_route("fused_attention", _attn_sigs(128),
                                 {"n_head": 2}, backend="cpu")
    assert route is None and "backend:cpu" in reason
    flags.set_flags({"use_flash_attention": False})
    try:
        with lowering_target("tpu"):
            route, reason = pallas_route("fused_attention",
                                         _attn_sigs(128), {"n_head": 2})
        assert route is None and "flag:use_flash_attention=off" in reason
    finally:
        flags.set_flags({"use_flash_attention": True})


def test_fallback_warning_names_effective_backend(caplog):
    """Cross-lowering for TPU on this CPU host must log the LOWERING
    platform (tpu), not jax.default_backend() (cpu) — the old
    attention_ops warn-once got this wrong."""
    import logging
    from paddle_tpu.ops import registry
    from paddle_tpu.ops.pallas import lowering_target

    registry._PALLAS_WARNED.clear()
    with caplog.at_level(logging.WARNING, logger="paddle_tpu.ops.registry"):
        with lowering_target("tpu"):
            registry.pallas_route("fused_attention", _attn_sigs(72),
                                  {"n_head": 2})
    msgs = [r.getMessage() for r in caplog.records
            if "pallas kernel" in r.getMessage()]
    assert msgs and "backend tpu" in msgs[0]
    assert "cpu" not in msgs[0]


def test_pallas_table_enumerates_the_tier():
    from paddle_tpu.ops.registry import pallas_table
    table = pallas_table()
    for op in ("fused_attention", "layer_norm",
               "fused_add_layernorm", "fused_elemwise_activation",
               "multihead_matmul", "c_quant_allreduce_sum",
               "c_fused_quant_allreduce_sum", "quant_reduce_scatter"):
        assert op in table, op
    assert "adam" not in table and "adamw" not in table
    kernels = {r.kernel for routes in table.values() for r in routes}
    assert {"attention_tile", "flash_attention", "ring_flash_attention",
            "fused_layer_norm", "dequant_accumulate"} <= kernels


# ---------------------------------------------------------------------------
# interpret-mode parity: the three grafted hot paths
# ---------------------------------------------------------------------------


def _sp_mesh(n):
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:n]), ("sp",))


def test_ring_attention_flash_matches_einsum_composition():
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from paddle_tpu.parallel.ring_attention import ring_attention

    mesh = _sp_mesh(4)
    B, H, S, D = 1, 2, 512, 64
    rng = np.random.RandomState(0)
    q, k, v = (rng.randn(B, H, S, D).astype(np.float32) for _ in range(3))
    mask = (rng.rand(B, S) > 0.15).astype(np.float32)
    mask[:, 0] = 1.0

    def make(use_flash, causal):
        def g(q, k, v, m):
            return ring_attention(q, k, v, "sp", causal=causal, kv_mask=m,
                                  use_flash=use_flash,
                                  interpret=use_flash)
        return jax.jit(shard_map(
            g, mesh=mesh,
            in_specs=(P(None, None, "sp"),) * 3 + (P(None, "sp"),),
            out_specs=P(None, None, "sp"), check_vma=False))

    for causal in (False, True):
        ref = make(False, causal)(q, k, v, mask)
        out = make(True, causal)(q, k, v, mask)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5,
                                   err_msg=f"causal={causal}")


def test_ring_attention_flash_grads_match():
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from paddle_tpu.parallel.ring_attention import ring_attention

    mesh = _sp_mesh(4)
    B, H, S, D = 1, 1, 512, 64
    rng = np.random.RandomState(1)
    q, k, v = (rng.randn(B, H, S, D).astype(np.float32) for _ in range(3))
    mask = np.ones((B, S), np.float32)

    def loss(use_flash):
        def g(q, k, v, m):
            return ring_attention(q, k, v, "sp", causal=True, kv_mask=m,
                                  use_flash=use_flash,
                                  interpret=use_flash)
        fn = jax.jit(shard_map(
            g, mesh=mesh,
            in_specs=(P(None, None, "sp"),) * 3 + (P(None, "sp"),),
            out_specs=P(None, None, "sp"), check_vma=False))
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v, mask)))

    gr = jax.grad(loss(False), argnums=(0, 1, 2))(q, k, v)
    gk = jax.grad(loss(True), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gr, gk):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=2e-4, err_msg=f"d{name}")


def test_flash_with_lse_grads_include_lse_cotangent():
    """The (out, lse) variant must propagate a NON-ZERO lse cotangent
    correctly (the ring merge differentiates through lse) — checked
    against jax.grad of the jnp logsumexp composition."""
    from paddle_tpu.ops.pallas.flash_attention import \
        flash_attention_with_lse

    B, H, S, D = 1, 1, 128, 64
    rng = np.random.RandomState(2)
    q, k, v = (jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
               for _ in range(3))

    def ker(q, k, v):
        o, lse = flash_attention_with_lse(q, k, v, interpret=True)
        return jnp.sum(jnp.sin(o)) + jnp.sum(jnp.cos(lse))

    def ref(q, k, v):
        s = jnp.einsum("bhsd,bhtd->bhst", q, k) / np.sqrt(D)
        o = jnp.einsum("bhst,bhtd->bhsd", jax.nn.softmax(s, axis=-1), v)
        lse = jax.scipy.special.logsumexp(s, axis=-1)
        return jnp.sum(jnp.sin(o)) + jnp.sum(jnp.cos(lse))

    gk = jax.grad(ker, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gr, gk):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=5e-4, err_msg=f"d{name}")


def test_sharded_update_pads_flat_shards_to_128():
    """ZeRO-1 flat shards are 128-aligned (whole lanes) and the grad
    scatter carries the matching align attr."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.framework.core import Program, program_guard
    from paddle_tpu.optimizer import ShardedUpdateOptimizer

    main_p, startup = Program(), Program()
    with program_guard(main_p, startup):
        x = fluid.layers.data("x", shape=[100], dtype="float32")
        y = fluid.layers.fc(x, size=77)      # 100*77 + 77: neither tiles
        loss = fluid.layers.reduce_mean(y)
        ShardedUpdateOptimizer(fluid.optimizer.Adam(1e-3),
                               nranks=8).minimize(loss)
    scatters = [op for op in main_p.global_block().ops
                if op.type == "zero_reduce_scatter"]
    assert scatters
    for op in scatters:
        assert op.attrs.get("align") == 128
        out = main_p.global_block()._find_var_recursive(
            op.outputs["Out"][0])
        assert out.shape[0] % (8 * 128) == 0


def test_dequant_accumulate_parity_int8_int4():
    from paddle_tpu.ops.pallas import quant_kernels as qk
    from paddle_tpu.ops.quantize_wire import (CompressionSpec,
                                              dequantize_blockwise,
                                              quantize_blockwise)

    rng = np.random.RandomState(4)
    for dtype in ("int8", "int4"):
        spec = CompressionSpec(dtype=dtype, block_size=256)
        n, sb = 8, 12
        numel = sb * spec.block_size
        qs, ss = zip(*(quantize_blockwise(
            jnp.asarray(rng.randn(numel).astype(np.float32)), spec)
            for _ in range(n)))
        payload, scales = jnp.concatenate(qs, 0), jnp.concatenate(ss, 0)
        ref = sum(dequantize_blockwise(q, s, spec)
                  for q, s in zip(qs, ss))
        got = qk.dequant_accumulate(payload, scales, spec, n,
                                    interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5, err_msg=dtype)


def test_dequant_accumulate_requant_matches_jnp_requantize():
    from paddle_tpu.ops.pallas import quant_kernels as qk
    from paddle_tpu.ops.quantize_wire import (CompressionSpec,
                                              dequantize_blockwise,
                                              quantize_blockwise)

    rng = np.random.RandomState(5)
    spec = CompressionSpec(dtype="int8", block_size=256)
    n, sb = 4, 16
    numel = sb * spec.block_size
    qs, ss = zip(*(quantize_blockwise(
        jnp.asarray(rng.randn(numel).astype(np.float32)), spec)
        for _ in range(n)))
    payload, scales = jnp.concatenate(qs, 0), jnp.concatenate(ss, 0)
    ref = sum(dequantize_blockwise(q, s, spec) for q, s in zip(qs, ss))
    q2r, s2r = quantize_blockwise(ref, spec)
    q2k, s2k = qk.dequant_accumulate_requant(payload, scales, spec, n,
                                             interpret=True)
    # round-to-nearest on near-identical f32 sums: payloads bit-match
    assert bool(jnp.all(q2k == q2r))
    np.testing.assert_allclose(np.asarray(s2k), np.asarray(s2r),
                               rtol=1e-6)


def test_dequant_kernel_gate_mirrors_kernel():
    from paddle_tpu.ops.pallas import quant_kernels as qk
    from paddle_tpu.ops.quantize_wire import CompressionSpec

    i8 = CompressionSpec(dtype="int8", block_size=256)
    assert qk.supported(8, 16, i8, backend="tpu") == (True, "")
    ok, why = qk.supported(8, 16, i8, backend="cpu")
    assert not ok and "backend" in why
    ok, why = qk.supported(1, 16, i8, backend="tpu")
    assert not ok and "peers" in why
    odd = CompressionSpec(dtype="int8", block_size=192)
    ok, why = qk.supported(8, 16, odd, backend="tpu")
    assert not ok and "block-size" in why
    bf = CompressionSpec(dtype="bfloat16")
    ok, why = qk.supported(8, 16, bf, backend="tpu")
    assert not ok and "wire-dtype" in why


# ---------------------------------------------------------------------------
# the census: every route's kernels in the TPU-lowered module of its op
# ---------------------------------------------------------------------------


def _tpu_kernel_names(fn, *args):
    """``kernel_name``s of the ``tpu_custom_call``s in ``fn`` cross-lowered
    for TPU on this host (no chip: a kernel Mosaic's front end refuses
    fails the export)."""
    from paddle_tpu.ops.pallas import lowering_target
    with lowering_target("tpu"):
        txt = jax.export.export(jax.jit(fn), platforms=("tpu",))(
            *args).mlir_module()
    return set(re.findall(r'kernel_name = "(\w+)"', txt))


def _op_fn(op, ins, attrs, out="Out", grad=True, mesh=None, specs=None,
           is_test=False):
    """``(fn, args)``: the registered impl of ``op`` over the float arrays
    of ``ins`` (slot -> array), differentiated when ``grad``; under
    ``shard_map`` over ``mesh`` with ``specs`` (slot -> PartitionSpec)
    when the route needs a mesh axis."""
    from paddle_tpu.ops.registry import LoweringContext, get_op
    impl = get_op(op)
    slots = list(ins)
    axis_names = tuple(mesh.axis_names) if mesh is not None else ()

    def fwd(*arrays):
        ctx = LoweringContext(jax.random.PRNGKey(0), mesh=mesh,
                              axis_names=axis_names, is_test=is_test)
        o = impl(ctx, {s: [a] for s, a in zip(slots, arrays)},
                 attrs)[out]
        return o[0] if isinstance(o, (list, tuple)) else o

    args = [ins[s] for s in slots]
    run = fwd
    if grad:
        wrt = tuple(i for i, a in enumerate(args)
                    if jnp.issubdtype(a.dtype, jnp.floating))
        # the value too: a gradient alone drops the forward kernel
        run = jax.value_and_grad(
            lambda *a: jnp.sum(fwd(*a).astype(jnp.float32)), wrt)
    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        in_specs = tuple(specs.get(s, P()) for s in slots)
        out_specs = (P(), tuple(in_specs[i] for i in wrt)) if grad \
            else specs.get(out, P())
        run = jax.shard_map(run, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)
    return run, args


def _mesh(n, axis):
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:n]), (axis,))


def _f32(*shape):
    return jnp.zeros(shape, jnp.float32)


def _attention(attrs, s=128, hidden=128, bias=False, **kw):
    ins = {"Q": _f32(2, s, hidden), "K": _f32(2, s, hidden),
           "V": _f32(2, s, hidden)}
    if bias:
        ins["AttnBias"] = _f32(2, 1, s, s)
    return [_op_fn("fused_attention", ins, attrs, **kw)]


def _ring_attention():
    from jax.sharding import PartitionSpec as P
    seq = P(None, "sp")
    return _attention({"n_head": 2, "_seq_axis": "sp"}, s=512,
                      mesh=_mesh(4, "sp"),
                      specs={"Q": seq, "K": seq, "V": seq})


def _cached_attention():
    pool = _f32(4, 128, 128)
    ins = {"Q": _f32(1, 128, 128), "KPool": pool, "VPool": pool,
           "BlockTable": jnp.zeros((1, 1), jnp.int32),
           "CtxLen": jnp.full((1,), 128, jnp.int32)}
    return [_op_fn("fused_attention", ins,
                   {"n_head": 2, "_cached": True, "is_test": True},
                   is_test=True)]


def _paged_decode_attention():
    """A decode step's cached read: one query token a row."""
    pool = _f32(8, 8, 128)
    ins = {"Q": _f32(2, 1, 128), "KPool": pool, "VPool": pool,
           "BlockTable": jnp.zeros((2, 4), jnp.int32),
           "CtxLen": jnp.full((2,), 9, jnp.int32)}
    return [_op_fn("fused_attention", ins,
                   {"n_head": 2, "_cached": True, "is_test": True},
                   grad=False, is_test=True)]


def _paged_decode_attention_wide():
    """The same with one head of 128: a head of whole lane tiles."""
    pool = jnp.zeros((8, 16, 128), jnp.bfloat16)
    ins = {"Q": _f32(2, 1, 128), "KPool": pool, "VPool": pool,
           "BlockTable": jnp.zeros((2, 4), jnp.int32),
           "CtxLen": jnp.full((2,), 17, jnp.int32)}
    return [_op_fn("fused_attention", ins,
                   {"n_head": 1, "_cached": True, "is_test": True},
                   grad=False, is_test=True)]


def _mla_paged_decode():
    """A decode step's read of the paged latent cache: one query token a
    row, 8 heads of 128+128 / v 128 over 256-wide bfloat16 rows."""
    bf = jnp.bfloat16
    ins = {"Q": jnp.zeros((2, 1, 8 * 256), bf),
           "WKVB": jnp.zeros((128, 8 * 256), bf),
           "Pool": jnp.zeros((8, 16, 256), bf),
           "BlockTable": jnp.zeros((2, 4), jnp.int32),
           "CtxLen": jnp.full((2,), 17, jnp.int32)}
    return [_op_fn("mla_attention", ins,
                   {"n_head": 8, "nope_dim": 128, "rope_dim": 128,
                    "v_dim": 128, "scale": 0.1, "_cached": True},
                   grad=False, is_test=True)]


def _gated_delta_rule(s):
    """One token a row against the state pool (the recurrent route) or a
    chunk of ``s`` tokens started fresh (the chunked route)."""
    h, dk, dv = 2, 16, 128
    ins = {"Q": _f32(2, s, h * dk), "K": _f32(2, s, h * dk),
           "V": _f32(2, s, h * dv), "A": _f32(2, s, h), "B": _f32(2, s, h),
           "ALog": _f32(h), "DtBias": _f32(h),
           "StatePool": _f32(3, h, dk, dv),
           "StateSlot": jnp.zeros((2,), jnp.int32)}
    if s > 1:
        ins["Fresh"] = jnp.ones((2,), jnp.int32)
    return [_op_fn("gated_delta_rule", ins, {"n_head": h}, grad=False,
                   is_test=True)]


def _grouped_ffn():
    n, d, f, e, k = 256, 128, 128, 2, 2
    ins = {"X": _f32(n, d), "TopkWeight": _f32(n, k),
           "TopkIndex": jnp.zeros((n, k), jnp.int32),
           "WGate": _f32(e, d, f), "WUp": _f32(e, d, f),
           "WDown": _f32(e, f, d)}
    return [_op_fn("moe_grouped_ffn", ins, {})]


def _norm(op, residual=False):
    ins = {"X": _f32(16, 128), "Scale": _f32(128), "Bias": _f32(128)}
    if residual:
        ins["Residual"] = _f32(16, 128)
    return [_op_fn(op, ins, {"begin_norm_axis": 1}, out="Y")]


def _bias_gelu():
    return [_op_fn("fused_elemwise_activation",
                   {"X": _f32(16, 128), "Y": _f32(128)},
                   {"functor_list": ["elementwise_add", "gelu"]})]


def _multihead_matmul():
    qkv = _f32(1, 2, 128, 64)
    return [_op_fn("multihead_matmul", {"Q": qkv, "K": qkv, "V": qkv},
                   {"is_test": True}, grad=False, is_test=True)]


def _quant_collective(op, dtypes):
    """One program a wire dtype: int8 round-to-nearest fuses the
    requantisation into the receive kernel, int4 does not."""
    from jax.sharding import PartitionSpec as P
    return [_op_fn(op, {"X": _f32(8, 8 * 256)},
                   {"_axis_name": "dp",
                    "quant_spec": {"dtype": dt, "block_size": 256}},
                   grad=False, mesh=_mesh(8, "dp"),
                   specs={"X": P("dp"), "Out": P("dp")})
            for dt in dtypes]


#: (op, route) -> the smallest programs that hit it, between them holding
#: every kernel the route names
ROUTE_CASES = {
    ("fused_attention", "attention_tile"): lambda: _attention(
        {"n_head": 2, "dropout_rate": 0.1}, bias=True),
    ("fused_attention", "flash_attention"): lambda: _attention(
        {"n_head": 2, "causal": True}, s=256),
    ("fused_attention", "flash_gqa_attention"): lambda: _attention(
        {"n_head": 2, "num_kv_heads": 1, "window": 128, "causal": True},
        s=256, hidden=256),
    ("fused_attention", "ring_flash_attention"): _ring_attention,
    ("fused_attention", "cached_flash_attention"): _cached_attention,
    ("fused_attention", "paged_decode_attention"): _paged_decode_attention,
    ("fused_attention", "paged_decode_attention_wide"):
        _paged_decode_attention_wide,
    ("mla_attention", "mla_paged_decode"): _mla_paged_decode,
    ("gated_delta_rule", "gdn_decode"): lambda: _gated_delta_rule(1),
    ("gated_delta_rule", "gdn_chunk"): lambda: _gated_delta_rule(128),
    ("moe_grouped_ffn", "moe_grouped_matmul"): _grouped_ffn,
    ("layer_norm", "fused_layer_norm"): lambda: _norm("layer_norm"),
    ("fused_add_layernorm", "fused_add_layer_norm"): lambda: _norm(
        "fused_add_layernorm", residual=True),
    ("fused_elemwise_activation", "fused_bias_gelu"): _bias_gelu,
    ("multihead_matmul", "flash_attention"): _multihead_matmul,
    ("c_quant_allreduce_sum", "dequant_accumulate"):
        lambda: _quant_collective("c_quant_allreduce_sum",
                                  ("int8", "int4")),
    ("c_fused_quant_allreduce_sum", "dequant_accumulate"):
        lambda: _quant_collective("c_fused_quant_allreduce_sum",
                                  ("int8", "int4")),
    ("quant_reduce_scatter", "dequant_accumulate"):
        lambda: _quant_collective("quant_reduce_scatter", ("int8",)),
}


def _table_pairs():
    import paddle_tpu.fluid  # noqa: F401 - registers the spec library
    from paddle_tpu.ops.registry import pallas_table
    return {(op, r.kernel): r for op, routes in pallas_table().items()
            for r in routes}


def test_every_route_has_a_census_case():
    """A route cannot land without its case below."""
    assert set(_table_pairs()) == set(ROUTE_CASES)


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs the 8-device virtual CPU mesh")
@pytest.mark.parametrize("op,route", sorted(ROUTE_CASES),
                         ids=lambda v: v)
def test_route_kernels_in_tpu_module(op, route):
    """The route table's ``kernels=`` field is the one statement of which
    kernels a route puts in a TPU module; this is its one check."""
    from paddle_tpu.observability import metrics
    want = _table_pairs()[(op, route)].kernels
    metrics.reset_metrics()
    found = set()
    for fn, args in ROUTE_CASES[(op, route)]():
        found |= _tpu_kernel_names(fn, *args)
    assert metrics.counter("pallas_routes", op=op, kernel=route,
                           outcome="hit", reason="supported").get() > 0
    assert set(want) <= found, (sorted(want), sorted(found))
