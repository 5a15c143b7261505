"""The main path's Pallas kernels compiled by the TPU's own compiler at
real widths, for a v5e that is described and not attached
(``jax.experimental.topologies``): what Mosaic refuses — a slice off the
tiling, a scoped-VMEM overrun — fails here at no chip time.  Interpret
mode cannot show either.  A compile that passes says nothing about
results or speed (chip_smoke.py leg K and the benchmark do).

Everything that touches the topology lives in fixtures of THIS file: one
process at a time may load the TPU's library, and under pytest-xdist only
the worker that is given this file may try.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu here, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _route_kernels(route):
    """The kernel names the route table gives ``route``."""
    import paddle_tpu.fluid  # noqa: F401 - registers the spec library
    from paddle_tpu.ops.registry import pallas_table
    return next(r.kernels for routes in pallas_table().values()
                for r in routes if r.kernel == route)


@pytest.fixture
def no_compile_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("dtype,batch,n_head,d,rate,bias_shape", [
    # the s128 cell's attention: pure-bf16 AMP, batch 96, bert.py's mask
    pytest.param(jnp.bfloat16, 96, 12, 64, 0.1, (96, 1, 128, 128),
                 id="bert-base-bf16-b96-dropout"),
    # the benchmark's dropout-off reference build, and an f32 caller
    pytest.param(jnp.bfloat16, 8, 12, 64, 0.0, (8, 1, 128, 128),
                 id="bert-base-bf16-b8"),
    pytest.param(jnp.float32, 8, 12, 64, 0.0, (8, 1, 1, 128),
                 id="bert-base-f32-key-mask"),
    # the widest f32 row the shape rule admits, one head to a lane group
    pytest.param(jnp.float32, 2, 13, 128, 0.1, None,
                 id="f32-at-the-width-bound"),
])
def test_attention_tile_kernels_compile_for_v5e(one_chip, no_compile_cache,
                                                dtype, batch, n_head, d,
                                                rate, bias_shape):
    from paddle_tpu.ops.pallas import attention_tile as at
    assert at.tiles(at.TILE, at.TILE, n_head, d, bias_shape=bias_shape)[0]

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    x = sds((batch, at.TILE, n_head * d), dtype)
    bias = None if bias_shape is None else sds(bias_shape, jnp.float32)

    def step(q, k, v, g, bias, seed):
        out, vjp = jax.vjp(lambda *a: at.attention_tile_bsd(
            *a, bias, n_head=n_head, dropout_rate=rate, seed=seed), q, k, v)
        return (out,) + vjp(g)

    compiled = jax.jit(step).lower(
        x, x, x, x, bias, sds((1,), jnp.int32)).compile()
    txt = compiled.as_text()
    assert all(k in txt for k in _route_kernels("attention_tile"))


@pytest.mark.parametrize("window", [1024, None],
                         ids=["sliding-1024", "full-causal"])
def test_flash_gqa_kernels_compile_for_v5e(one_chip, no_compile_cache,
                                           window):
    """Mellum2's attention at the timed size: 8 192 tokens, 32 query
    heads of 128 on 4 K/V heads, bf16, forward and both backward
    kernels, in the op's own (B, S, H * D) layout."""
    from paddle_tpu.ops.pallas import flash_gqa as fg

    def sds(width):
        return jax.ShapeDtypeStruct((1, 8192, width), jnp.bfloat16,
                                    sharding=one_chip)

    def step(q, k, v, g):
        out, vjp = jax.vjp(lambda *a: fg.flash_gqa_bsd(
            *a, n_head=32, n_kv_head=4, window=window), q, k, v)
        return (out,) + vjp(g)

    txt = jax.jit(step).lower(sds(4096), sds(512), sds(512),
                              sds(4096)).compile().as_text()
    assert all(k in txt for k in _route_kernels("flash_gqa_attention"))


@pytest.mark.parametrize("tile_m", [128, 256])
def test_grouped_matmul_kernels_compile_for_v5e(one_chip, no_compile_cache,
                                                tile_m):
    """Mellum2's experts at the timed size: 8 192 tokens top-8 over 64,
    16 held, 2304 x 896, bf16 — the worst-case buffer (65 536 rows and a
    tile an expert)."""
    from paddle_tpu.ops.decoder_lm_ops import grouped_ffn

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def step(x, w, idx, wg, wu, wd, g):
        out, vjp = jax.vjp(lambda *a: grouped_ffn(
            a[0], a[1], idx, *a[2:], backend="pallas",
            tile_m=tile_m)[0], x, w, wg, wu, wd)
        return (out,) + vjp(g)

    txt = jax.jit(step).lower(
        sds((8192, 2304)), sds((8192, 8), jnp.float32),
        sds((8192, 8), jnp.int32), sds((16, 2304, 896)),
        sds((16, 2304, 896)), sds((16, 896, 2304)),
        sds((8192, 2304))).compile().as_text()
    assert all(k in txt for k in _route_kernels("moe_grouped_matmul"))


@pytest.mark.parametrize("shape", [(16, 2304, 896), (2304, 24576),
                                   (768, 3072), (30522, 768), (2304, 64)],
                         ids=lambda s: "x".join(map(str, s)))
def test_adam_compiles_in_place_for_v5e(one_chip, no_compile_cache, shape):
    """The ``adam`` op at the models' parameter shapes (stacked experts,
    the head's slice, BERT's FFN and embedding, a router), as a TPU trace
    lowers it: one XLA fusion over the donated buffers in the tensor's
    own layout.  No kernel (it lost to this on the v5e, PERF.md section
    6, PR 30), no ``f32[n / 128, 128]`` array (a flat view's signature)
    and no reshape, copy or transpose of a whole operand: any of them is
    a relayout on the tiled layout, 39.8 ms of a 322 ms step once."""
    import re
    from paddle_tpu.ops.pallas import lowering_target
    from paddle_tpu.ops.registry import get_op

    def sds(sh=shape):
        return jax.ShapeDtypeStruct(sh, jnp.float32, sharding=one_chip)

    def step(p, g, m, v, lr, b1p, b2p):
        out = get_op("adam")(None, {
            "Param": [p], "Grad": [g], "Moment1": [m], "Moment2": [v],
            "LearningRate": [lr], "Beta1Pow": [b1p], "Beta2Pow": [b2p]}, {})
        return out["ParamOut"], out["Moment1Out"], out["Moment2Out"]

    with lowering_target("tpu"):
        txt = jax.jit(step, donate_argnums=(0, 2, 3)).lower(
            sds(), sds(), sds(), sds(), sds((1,)), sds((1,)),
            sds((1,))).compile().as_text()
    assert "tpu_custom_call" not in txt
    n = 1
    for d in shape:
        n *= d
    dims = ",".join(map(str, shape))
    assert f"f32[{n // 128},128]" not in txt
    assert txt.count("input_output_alias") == 1 and \
        txt.count("may-alias") + txt.count("must-alias") >= 3
    moved = re.findall(
        rf"= f32\[{dims}\]\S* (?:reshape|copy|transpose)\(", txt)
    assert not moved, moved


@pytest.mark.parametrize("batch", [128, 8, 1])
def test_paged_decode_attention_compiles_for_v5e(one_chip, no_compile_cache,
                                                 batch):
    """The serving cell's decode-step attention at its own shapes (a
    bucket of ``batch`` rows, 32 pages of 16 over pools of 4 096 blocks,
    12 heads of 64, float32) as it sits in a chain: inside a scan whose
    carry is the pool, written just before it is read.  The pool reaches
    the kernel in place — no ``[B, T, H]`` gather of the cache and no
    copy of a pool exists in the compiled module."""
    import re
    from paddle_tpu.ops.pallas import lowering_target
    from paddle_tpu.ops.registry import get_op

    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    attrs = {"n_head": 12, "_cached": True, "is_test": True}

    def chain(q, k_pool, v_pool, table, pos):
        def step(carry, _):
            k_pool, v_pool, pos, q = carry
            slots = (table[:, 0] * 16 + pos % 16)[:, None]
            wrote = get_op("cache_write")(None, {
                "KPool": [k_pool], "VPool": [v_pool], "K": [q], "V": [q],
                "Slots": [slots]}, {})
            k_pool, v_pool = wrote["KPoolOut"], wrote["VPoolOut"]
            out = get_op("fused_attention")(None, {
                "Q": [q], "KPool": [k_pool], "VPool": [v_pool],
                "BlockTable": [table], "CtxLen": [pos + 1]}, attrs)["Out"]
            return (k_pool, v_pool, pos + 1, out), None
        return jax.lax.scan(step, (k_pool, v_pool, pos, q), None,
                            length=8)[0]

    with lowering_target("tpu"):
        txt = jax.jit(chain, donate_argnums=(1, 2)).lower(
            sds((batch, 1, 768)), sds((4096, 16, 768)),
            sds((4096, 16, 768)), sds((batch, 32), jnp.int32),
            sds((batch,), jnp.int32)).compile().as_text()
    assert all(k in txt for k in _route_kernels("paged_decode_attention"))
    # heads of 64 share a lane tile: the narrow body alone
    assert not any(k in txt for k in
                   _route_kernels("paged_decode_attention_wide"))
    assert f"f32[{batch},512,768]" not in txt
    moved = re.findall(r"= f32\[4096,16,768\]\S* copy\(", txt)
    assert not moved, moved


@pytest.mark.parametrize("batch", [128, 32])
def test_mla_paged_decode_compiles_for_v5e(one_chip, no_compile_cache,
                                           batch):
    """The latent serving cell's decode-step attention at its own shapes:
    ``batch`` rows of 128 heads of 128+64 / v 128 over a pool of 34 688
    blocks of 16 x 640 bfloat16 (512 + 64 live values a row) through a
    512-page table, as ``mla_attention`` lowers it for a TPU — query
    absorption, the paged kernel, the value up-projection.  The pool
    reaches the kernel in place: no ``[B, T, W]`` gather of the cache
    exists in the compiled module."""
    from paddle_tpu.ops.pallas import lowering_target
    from paddle_tpu.ops.registry import get_op

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    attrs = {"n_head": 128, "nope_dim": 128, "rope_dim": 64, "v_dim": 128,
             "scale": 0.25, "_cached": True}

    def step(q, wkvb, pool, table, ctx):
        return get_op("mla_attention")(None, {
            "Q": [q], "WKVB": [wkvb], "Pool": [pool], "BlockTable": [table],
            "CtxLen": [ctx]}, attrs)["Out"]

    with lowering_target("tpu"):
        txt = jax.jit(step).lower(
            sds((batch, 1, 128 * 192)), sds((512, 128 * 256)),
            sds((34688, 16, 640)), sds((batch, 512), jnp.int32),
            sds((batch,), jnp.int32)).compile().as_text()
    assert all(k in txt for k in _route_kernels("mla_paged_decode"))
    assert f"bf16[{batch},8192,640]" not in txt


@pytest.mark.parametrize("tokens", [128, 1024],
                         ids=["decode-128-rows", "chunk-1024-tokens"])
def test_wide_expert_grouped_matmul_compiles_for_v5e(
        one_chip, no_compile_cache, tokens):
    """DeepSeek-V3's experts (7168 x 2048, 16 held of 256, top-8, bf16) at
    the serving cell's two sizes: a weight block of 29 MB does not fit
    VMEM twice, so the products run by column blocks."""
    from paddle_tpu.ops.decoder_lm_ops import grouped_ffn
    from paddle_tpu.ops.pallas.grouped_matmul import column_block, row_tile
    assert column_block(7168, 2048, 2) < 2048
    tile_m = row_tile(tokens * 8, 256)

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def step(x, w, idx, wg, wu, wd):
        return grouped_ffn(x, w, idx, wg, wu, wd, backend="pallas",
                           tile_m=tile_m)[0]

    txt = jax.jit(step).lower(
        sds((tokens, 7168)), sds((tokens, 8), jnp.float32),
        sds((tokens, 8), jnp.int32), sds((16, 7168, 2048)),
        sds((16, 7168, 2048)), sds((16, 2048, 7168))).compile().as_text()
    assert "moe_gmm" in txt


HYBRID_GDN = {"n_head": 30, "beta_scale": 2.0}


def _gdn_inputs(sds, batch, s):
    f32 = jnp.float32
    return {"Q": [sds((batch, s, 2880))], "K": [sds((batch, s, 2880))],
            "V": [sds((batch, s, 5760))], "A": [sds((batch, s, 30))],
            "B": [sds((batch, s, 30))], "ALog": [sds((30,), f32)],
            "DtBias": [sds((30,), f32)]}


@pytest.mark.parametrize("batch", [64, 32])
def test_hybrid_decode_step_compiles_for_v5e(one_chip, no_compile_cache,
                                             batch):
    """The hybrid serving cell's decode step at its own shapes, as it
    sits in a chain (a scan that carries every pool): the full layers'
    read of bfloat16 K/V pools (13 312 blocks of 16 x 3 840, 30 heads of
    128, a 1 024-page table) by the paged kernel's wide body (heads of
    whole lane tiles: the narrow body is not in the module), and the
    linear layers' recurrence on a float32 state pool of 65 slots x 30 x
    96 x 192 by ``gdn_decode``.  No ``[B, T, H]`` gather of the K/V cache
    and no copy of a pool exists in the compiled module; inside the
    kernel no float32 copy of a step's pages (``[P, 3840]``) exists
    either: the bfloat16 pages go to the MXU as they lie."""
    import re
    from paddle_tpu.ops.pallas import lowering_target
    from paddle_tpu.ops.registry import get_op

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    attn = {"n_head": 30, "_cached": True, "is_test": True}

    def chain(q, k_pool, v_pool, table, pos, state, slot, gdn):
        def step(carry, _):
            k_pool, v_pool, state, pos, q = carry
            slots = (table[:, 0] * 16 + pos % 16)[:, None]
            wrote = get_op("cache_write")(None, {
                "KPool": [k_pool], "VPool": [v_pool], "K": [q], "V": [q],
                "Slots": [slots]}, {})
            k_pool, v_pool = wrote["KPoolOut"], wrote["VPoolOut"]
            out = get_op("fused_attention")(None, {
                "Q": [q], "KPool": [k_pool], "VPool": [v_pool],
                "BlockTable": [table], "CtxLen": [pos + 1]}, attn)["Out"]
            lin = get_op("gated_delta_rule")(None, dict(
                gdn, StatePool=[state], StateSlot=[slot]), HYBRID_GDN)
            q = out + jnp.pad(lin["Out"], ((0, 0), (0, 0), (0, 0)))[
                ..., :3840]
            return (k_pool, v_pool, lin["StatePoolOut"], pos + 1, q), None
        return jax.lax.scan(step, (k_pool, v_pool, state, pos, q), None,
                            length=8)[0]

    with lowering_target("tpu"):
        txt = jax.jit(chain, donate_argnums=(1, 2, 5)).lower(
            sds((batch, 1, 3840)), sds((13312, 16, 3840)),
            sds((13312, 16, 3840)), sds((batch, 1024), jnp.int32),
            sds((batch,), jnp.int32), sds((65, 30, 96, 192), jnp.float32),
            sds((batch,), jnp.int32),
            _gdn_inputs(sds, batch, 1)).compile().as_text()
    for route in ("paged_decode_attention_wide", "gdn_decode"):
        assert all(k in txt for k in _route_kernels(route)), route
    assert not re.search(r"paged_decode_attn(?!_wide)", txt)
    assert f"bf16[{batch},16384,3840]" not in txt
    moved = re.findall(
        r"= (?:bf16\[13312,16,3840\]|f32\[65,30,96,192\])\S* copy\(", txt)
    assert not moved, moved
    from paddle_tpu.ops.pallas import paged_attention as pa
    kernel = str(jax.make_jaxpr(
        lambda *a: pa.paged_decode_attention_wide(*a, n_head=30))(
            sds((batch, 1, 3840)), sds((13312, 16, 3840)),
            sds((13312, 16, 3840)), sds((batch, 1024), jnp.int32),
            sds((batch,), jnp.int32)))
    pages = pa.PAGES_PER_STEP_WIDE * 16
    assert f"bf16[2,{pages},3840]" in kernel
    assert not re.search(rf"f32\[(?:2,)?{pages},3840\]", kernel)


def test_hybrid_chunk_delta_rule_compiles_for_v5e(one_chip,
                                                  no_compile_cache):
    """A 1 024-token prefill chunk's linear-attention mixer at the
    published widths: the WY transform in XLA and the chain over 16
    sub-chunks on ``gdn_chunk``, the state read from and written to the
    row's slot of the donated pool."""
    from paddle_tpu.ops.pallas import lowering_target
    from paddle_tpu.ops.registry import get_op

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def chunk(state, slot, fresh, valid, gdn):
        out = get_op("gated_delta_rule")(None, dict(
            gdn, StatePool=[state], StateSlot=[slot], Fresh=[fresh],
            Valid=[valid]), HYBRID_GDN)
        return out["Out"], out["StatePoolOut"]

    with lowering_target("tpu"):
        txt = jax.jit(chunk, donate_argnums=(0,)).lower(
            sds((65, 30, 96, 192), jnp.float32), sds((1,), jnp.int32),
            sds((1,), jnp.int32), sds((1, 1024), jnp.bool_),
            _gdn_inputs(sds, 1, 1024)).compile().as_text()
    assert all(k in txt for k in _route_kernels("gdn_chunk"))
    assert "f32[65,30,96,192]{3,2,1,0} copy(" not in txt
