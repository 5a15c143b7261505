"""The per-op Pallas lowering tier (ops/registry.py pallas channel):
static routing report, hit/fallback metrics counters, interpret-mode
parity of the grafted kernels (ring-attention-via-flash,
dequant-accumulate), and the KERNEL_CENSUS_r15.json artifact
contract produced by tools/verify_lowering.py --census."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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
    attrs = dict(attrs, n_head=2)
    with lowering_target("tpu"):
        route, reason = pallas_route("fused_attention", ins, attrs,
                                     axis_sizes=axis_sizes, count=False)
    assert route is not None, reason
    assert route.kernel == want
    if want == "attention_tile":
        assert route.kernels == ("attn_tile_fwd", "attn_tile_bwd")
    else:
        assert route.kernels == ("flash_fwd", "flash_bwd_dq",
                                 "flash_bwd_dkv")


def test_fused_attention_op_counts_tile_hits_and_lowers_its_kernels():
    """Trace-time census: the op impl's plain branch resolves the
    one-tile route (hit counter by op, kernel, reason) and the traced
    program holds attn_tile_fwd / attn_tile_bwd in the op's (B, S, H*D)
    layout — no transpose around them; a causal op of the same shape
    still counts and lowers flash_*."""
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
    assert "attn_tile_fwd" in tile and "attn_tile_bwd" in tile
    assert "flash_" not in tile and "transpose[" not in tile
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
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
# KERNEL_CENSUS_r15.json artifact contract
# ---------------------------------------------------------------------------


def test_kernel_census_artifact_contract():
    path = os.path.join(REPO, "KERNEL_CENSUS_r15.json")
    assert os.path.exists(path), \
        "run: python tools/verify_lowering.py --census"
    with open(path) as f:
        art = json.load(f)
    assert art["artifact"] == "KERNEL_CENSUS"
    assert art["revision"] == "r15"
    assert art["lowered_for"] == "tpu"
    assert art["ok"] is True
    secs = art["sections"]
    # every grafted kernel is present as a custom call in the TPU-
    # cross-lowered module of its hot path
    for k in ("attn_tile_fwd", "attn_tile_bwd"):
        assert k in secs["single_device_bert_tiny_seq128"]["kernels"]
    assert "flash_fwd" not in secs["single_device_bert_tiny_seq128"]["kernels"]
    assert "fused_adam" not in secs["single_device_bert_tiny_seq128"]["kernels"]
    assert "flash_fwd" in secs["ring_attention_sp4"]["kernels"]
    for k in ("flash_bwd_dq", "flash_bwd_dkv"):
        assert k in secs["ring_attention_sp4_grad"]["kernels"]
    assert "dequant_accumulate_requant" in secs["quant_int8_dp8"]["kernels"]
    assert "dequant_accumulate" in secs["quant_int4_dp8"]["kernels"]
    for s in secs.values():
        assert s["complete"], s["leg"]
        assert s["tpu_custom_call_sites"] > 0
    # parity recorded and within bounds; quantized legs carry PR 6's
    # end-to-end wire-tier contract
    par = art["parity"]
    for key in ("ring_flash_vs_einsum_fwd", "ring_flash_vs_einsum_grad",
                "dequant_acc_int8", "dequant_acc_int4"):
        assert par[key]["measured"] <= par[key]["bound"], key
    assert par["ring_flash_vs_einsum_fwd"]["bound"] <= 1e-5
    assert secs["quant_int8_dp8"]["wire_tier_parity_bound"] == 5e-2
    assert secs["quant_int4_dp8"]["wire_tier_parity_bound"] == 2.5e-1
    # the embedded static routing report agrees with the module census
    rep = secs["single_device_bert_tiny_seq128"]["routing_report"]
    assert rep["summary"]["attention_tile"]["pallas"] > 0
    assert rep["summary"]["fused_layer_norm"]["pallas"] > 0


def test_census_selftest_wired_into_preflight():
    with open(os.path.join(REPO, "tools", "preflight.sh")) as f:
        sh = f.read()
    assert "verify_lowering.py --selftest" in sh
