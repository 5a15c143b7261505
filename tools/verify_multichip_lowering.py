"""Multi-chip lowering verification without hardware: cross-lower the
dp2/tp2/sp2 BERT TRAINING step for platforms=("tpu",) on the 8-device
virtual CPU mesh and report the XLA collectives in the TPU module — the
sharded path's grad all-reduces, Megatron f/g pair, and ring-attention
permutes are checked invariants, not claims.

The report is a per-collective CENSUS (op kind, count, total payload
bytes); ``collective_census`` / ``ordering_census`` / ``donation_ratio``
/ ``lower_dp8_bert_census`` / ``fsdp_zero3_section`` are what the tier-1
tests import (tests/test_tpu_lowering.py, test_grad_comm.py,
test_overlap.py, test_shard_planner.py).

Each census row also carries true WIRE accounting (ring cost model over
the op's replica-group size): ``wire_bytes`` (what the schedule actually
moves over ICI), ``logical_bytes`` (the same payload priced at ≥fp32
master width) and ``compression_ratio`` = logical/wire — 1.0 for
full-precision rows, ≈4 for int8 payloads — and a ``by_dtype`` byte
breakdown that the zero-full-precision-collectives test asserts on.

Usage:
    python tools/verify_multichip_lowering.py             # dp2xtp2xsp2 report
    python tools/verify_multichip_lowering.py --selftest  # dp8 wire tiers
    python tools/verify_multichip_lowering.py --overlap   # ready-order census
    python tools/verify_multichip_lowering.py --fsdp      # ZeRO-3 census
"""

import json
import os
import re
import sys

COLLECTIVES = ("all_reduce", "all_gather", "collective_permute",
               "all_to_all", "reduce_scatter")

_DTYPE_BYTES = {"f64": 8, "i64": 8, "u64": 8, "f32": 4, "i32": 4, "u32": 4,
                "bf16": 2, "f16": 2, "i16": 2, "u16": 2, "i8": 1, "u8": 1,
                "i1": 1}



def _tensor_elems_dtype(ty):
    """(elems, dtype) of one 'NxMx...xdtype' tensor type string; elems 0
    when a dim is dynamic."""
    parts = ty.split("x")
    dtype = parts[-1]
    n = 1
    for d in parts[:-1]:
        try:
            n *= int(d)
        except ValueError:
            return 0, dtype    # dynamic dim — don't count
    return n, dtype


def _tensor_bytes(ty):
    """bytes of one 'NxMx...xdtype' tensor type string."""
    n, dtype = _tensor_elems_dtype(ty)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _group_size(line):
    """Replica-group size of a collective op line (the n of the ring
    cost model), from ``replica_groups = dense<..> : tensor<GxNxi64>``."""
    m = re.search(r"replica_groups[^:]*:\s*tensor<(\d+)x(\d+)xi64>", line)
    return int(m.group(2)) if m else None


def _wire_bytes(kind, n, result_bytes):
    """Ring-schedule wire bytes for one collective, from its RESULT
    bytes: all_reduce moves the payload twice ((n-1)/n each for the
    reduce-scatter and all-gather passes), gather/all_to_all once, and
    a reduce_scatter's wire payload is its n× larger input."""
    ring = (n - 1) / n if n and n > 1 else 1.0
    if kind == "all_reduce":
        return 2.0 * ring * result_bytes
    if kind == "reduce_scatter":
        return ring * (n if n else 1) * result_bytes
    if kind in ("all_gather", "all_to_all"):
        return ring * result_bytes
    return float(result_bytes)       # collective_permute: one hop


def collective_census(mlir_txt):
    """Per-collective census of a StableHLO module: op kind → {count,
    bytes, by_dtype, wire_bytes, logical_bytes, compression_ratio}.

    ``bytes`` is the summed payload (result tensors) of that collective
    kind.  ``wire_bytes`` applies the
    ring cost model (see :func:`_wire_bytes`) at the payload's actual
    element width; ``logical_bytes`` prices the same elements at master
    width (≥4 bytes — a bf16/int8 payload is a compressed view of fp32
    values; int4 payloads are packed 2-per-byte int8 carriers, so their
    census ratio understates the true 8× which the cross-tier
    :func:`quant_dp8_section` measures directly).
    ``compression_ratio`` = logical/wire, 1.0 when unknown.

    Region-carrying ops (all_reduce, reduce_scatter) print their type on
    the closing ``}) : ... ->`` line; region-free ops carry it inline."""
    census = {k: {"count": 0, "bytes": 0, "by_dtype": {},
                  "wire_bytes": 0, "logical_bytes": 0} for k in COLLECTIVES}
    pending = None
    for line in mlir_txt.splitlines():
        m = re.search(r"stablehlo\.(\w+)", line)
        kind = m.group(1) if m and m.group(1) in COLLECTIVES else None
        if kind:
            census[kind]["count"] += 1
            if "->" not in line:
                # type comes on the region-close line; replica_groups is
                # on this opening line
                pending = (kind, _group_size(line))
                continue
            target, n = kind, _group_size(line)
        elif pending and "->" in line and line.lstrip().startswith("})"):
            (target, n), pending = pending, None
        else:
            continue
        row = census[target]
        res = line.rsplit("->", 1)[-1]
        for ty in re.findall(r"tensor<([^>]+)>", res):
            elems, dtype = _tensor_elems_dtype(ty)
            width = _DTYPE_BYTES.get(dtype, 4)
            b = elems * width
            row["bytes"] += b
            row["by_dtype"][dtype] = row["by_dtype"].get(dtype, 0) + b
            row["wire_bytes"] += int(_wire_bytes(target, n, b))
            row["logical_bytes"] += int(
                _wire_bytes(target, n, elems * max(width, 4)))
    out = {}
    for k, v in census.items():
        if not v["count"]:
            continue
        v["compression_ratio"] = round(
            v["logical_bytes"] / v["wire_bytes"], 3) \
            if v["wire_bytes"] else 1.0
        out[k] = v
    return out


#: module ops counted as "backward/forward compute" by the ordering
#: census — the GEMM family is what the overlap scheduler hides behind
COMPUTE_OPS = ("dot_general", "dot", "convolution")


def ordering_census(mlir_txt):
    """Collective-vs-compute ORDERING of a StableHLO module: one row per
    collective with its line position and how many GEMM-class compute
    ops appear AFTER it in the module text (jaxpr emission order — the
    order the trace scheduled them).  A tail-fused grad sync shows every
    all_reduce with ``compute_after == 0``; the overlap scheduler's
    ready-order buckets each precede the remaining backward GEMMs."""
    events = []
    for i, line in enumerate(mlir_txt.splitlines()):
        m = re.search(r"stablehlo\.(\w+)", line)
        if not m:
            continue
        kind = m.group(1)
        if kind in COLLECTIVES:
            events.append((i, "collective", kind))
        elif kind in COMPUTE_OPS:
            events.append((i, "compute", kind))
    compute_pos = [i for i, t, _ in events if t == "compute"]
    rows = []
    for i, t, kind in events:
        if t != "collective":
            continue
        rows.append({"line": i, "kind": kind,
                     "compute_after": sum(1 for p in compute_pos
                                          if p > i)})
    return rows


def donation_ratio(mlir_txt):
    """(donated_args, total_args) of @main — the buffer-donation census
    (tf.aliasing_output annotations; the XLA image of the reference's
    inplace/memory-reuse passes)."""
    sig = re.search(r"func\.func public @main\((.*?)\)\s*->", mlir_txt,
                    re.DOTALL).group(1)
    total = sig.count("tensor<")
    donated = sig.count("tf.aliasing_output")
    return donated, total


def _env8():
    os.environ['JAX_PLATFORMS'] = 'cpu'
    os.environ['XLA_FLAGS'] = (os.environ.get('XLA_FLAGS', '') +
                               ' --xla_force_host_platform_device_count=8'
                               ).strip()
    import jax
    jax.config.update('jax_platforms', 'cpu')
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def lower_dp8_bert_census(mode):
    """Cross-lower the dp8 BERT-tiny BUCKETED train step for TPU with
    the grad collectives at wire tier ``mode`` ∈ {fp32, bf16, int8,
    int4} and return the module's collective census."""
    import jax
    import numpy as np
    from jax import export as jexp

    import paddle_tpu.fluid as fluid
    from paddle_tpu.framework.compiler import BuildStrategy, make_mesh
    from paddle_tpu.models import bert
    from paddle_tpu.ops.pallas import lowering_target

    cfg = bert.BertConfig.tiny()
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        feeds, total, mlm, nsp = bert.build_pretrain_network(cfg)
        fluid.optimizer.Adam(1e-4).minimize(total)
    mesh = make_mesh(8, "dp")
    bs = BuildStrategy()
    bs.fuse_all_reduce_ops = True
    if mode == "bf16":
        bs.allreduce_compress_dtype = "bfloat16"
    elif mode in ("int8", "int4"):
        bs.allreduce_quant_spec = {"dtype": mode, "block_size": 256}
    elif mode != "fp32":
        raise ValueError(f"unknown wire tier {mode!r}")
    fluid.CompiledProgram(main_p).with_data_parallel(
        loss_name=total.name, mesh=mesh, build_strategy=bs)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        data = bert.make_fake_batch(np.random.RandomState(0), cfg,
                                    batch_size=8, seq_len=64, num_masks=3)
        feed = {k: np.asarray(v) for k, v in data.items()}
        step = exe._compile(main_p, feed, [total.name], scope, mesh,
                            ("dp",), "dp")
        state = {n: np.asarray(scope.find_var(n))
                 for n in step.state_in_names}
        with lowering_target("tpu"):
            exported = jexp.export(step.fn, platforms=("tpu",))(
                feed, state, jax.random.PRNGKey(0))
    return collective_census(exported.mlir_module())


def _dp8_overlap_build(mode, overlap, min_buckets=8):
    """Build the dp8 BERT-tiny bucketed train step with the grad sync
    at wire tier ``mode`` and (optionally) overlap-aware ready-order
    scheduling.  Returns (program, mesh, strategy, loss_var)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.framework.compiler import BuildStrategy, make_mesh
    from paddle_tpu.models import bert

    cfg = bert.BertConfig.tiny()
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        feeds, total, mlm, nsp = bert.build_pretrain_network(cfg)
        fluid.optimizer.Adam(1e-4).minimize(total)
    mesh = make_mesh(8, "dp")
    bs = BuildStrategy()
    bs.fuse_all_reduce_ops = True
    bs.overlap_grad_sync = overlap
    bs.overlap_min_buckets = min_buckets
    if mode == "bf16":
        bs.allreduce_compress_dtype = "bfloat16"
    elif mode in ("int8", "int4"):
        bs.allreduce_quant_spec = {"dtype": mode, "block_size": 256}
    fluid.CompiledProgram(main_p).with_data_parallel(
        loss_name=total.name, mesh=mesh, build_strategy=bs)
    return main_p, startup, mesh, total


def _dp8_run_and_lower(main_p, startup, mesh, total, steps=2):
    """Train ``steps`` dp8 steps (losses collected bitwise-comparable)
    and cross-lower the step for TPU; returns (losses, mlir_txt)."""
    import jax
    import numpy as np
    from jax import export as jexp

    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import bert
    from paddle_tpu.ops.pallas import lowering_target

    cfg = bert.BertConfig.tiny()
    scope = fluid.Scope()
    losses = []
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        rng = np.random.RandomState(0)
        feed = None
        for _ in range(steps):
            data = bert.make_fake_batch(rng, cfg, batch_size=8,
                                        seq_len=64, num_masks=3)
            feed = {k: np.asarray(v) for k, v in data.items()}
            l, = exe.run(main_p, feed=feed, fetch_list=[total.name])
            losses.append(np.asarray(l))
        step = exe._compile(main_p, feed, [total.name], scope, mesh,
                            ("dp",), "dp")
        state = {n: np.asarray(scope.find_var(n))
                 for n in step.state_in_names}
        with lowering_target("tpu"):
            exported = jexp.export(step.fn, platforms=("tpu",))(
                feed, state, jax.random.PRNGKey(0))
    return losses, exported.mlir_module()


def overlap_dp8_section(min_buckets=8):
    """The overlap-scheduling proof: the dp8
    BERT-tiny grad sync, tail-fused vs ready-order overlapped —

    * ordering census of both lowered modules: tail mode's grad-sync
      all_reduces all have 0 compute after them; overlap mode shows
      ≥ 4 interleaved grad-sync collectives, each preceding later
      backward GEMMs in the module;
    * bit-parity: the overlapped run's per-step losses equal the
      tail-fused run's BITWISE (overlap moves the collectives, not the
      math), plus the same ready-order IR lowered with
      ``flag("overlap_lowering") = False`` (identical buckets, tail
      placement) as the schedule-only control."""
    import numpy as np
    from paddle_tpu import flags

    import paddle_tpu.fluid as fluid  # noqa: F401 (env init)

    def census_of(txt):
        rows = ordering_census(txt)
        ar = [r for r in rows if r["kind"] == "all_reduce"]
        return rows, sum(1 for r in ar if r["compute_after"] > 0)

    # tail-fused baseline
    losses_tail, txt_tail = _dp8_run_and_lower(
        *_dp8_overlap_build("fp32", overlap=False))
    rows_tail, inter_tail = census_of(txt_tail)

    # ready-order overlapped
    losses_ov, txt_ov = _dp8_run_and_lower(
        *_dp8_overlap_build("fp32", overlap=True,
                            min_buckets=min_buckets))
    rows_ov, inter_ov = census_of(txt_ov)

    # schedule-only control: same ready-order IR, hooks disabled
    flags.set_flags({"overlap_lowering": False})
    try:
        losses_ctl, _ = _dp8_run_and_lower(
            *_dp8_overlap_build("fp32", overlap=True,
                                min_buckets=min_buckets))
    finally:
        flags.set_flags({"overlap_lowering": True})

    bit_tail = bool(all(np.array_equal(a, b)
                        for a, b in zip(losses_ov, losses_tail)))
    bit_ctl = bool(all(np.array_equal(a, b)
                       for a, b in zip(losses_ov, losses_ctl)))
    return {
        "module": "dp8_bert_tiny_train_bucketed",
        "overlap_min_buckets": min_buckets,
        "tail_fused": {
            "grad_sync_collectives": sum(
                1 for r in rows_tail if r["kind"] == "all_reduce"),
            "interleaved": inter_tail,
            "ordering": rows_tail,
        },
        "overlapped": {
            "grad_sync_collectives": sum(
                1 for r in rows_ov if r["kind"] == "all_reduce"),
            "interleaved": inter_ov,
            "ordering": rows_ov,
        },
        "loss_bit_parity_vs_tail_fused": bit_tail,
        "loss_bit_parity_vs_tail_sunk_control": bit_ctl,
        "losses": [float(np.asarray(l).reshape(())) for l in losses_ov],
    }


def overlap_main(argv):
    """``--overlap``: run the overlap-scheduling census (ordering census
    + bit-parity; tests/test_overlap.py asserts both live)."""
    _env8()
    section = overlap_dp8_section()
    ov, tail = section["overlapped"], section["tail_fused"]
    ok = (ov["interleaved"] >= 4
          and tail["interleaved"] == 0
          and ov["grad_sync_collectives"] >
          tail["grad_sync_collectives"]
          and section["loss_bit_parity_vs_tail_fused"]
          and section["loss_bit_parity_vs_tail_sunk_control"])
    print(f"overlap census {'OK' if ok else 'FAILED'}: "
          f"{ov['interleaved']}/{ov['grad_sync_collectives']} "
          f"interleaved grad-sync collectives (tail mode: "
          f"{tail['interleaved']}/{tail['grad_sync_collectives']}), "
          f"bit parity vs tail-fused="
          f"{section['loss_bit_parity_vs_tail_fused']}")
    return 0 if ok else 1


def quant_dp8_section():
    """The wire-compression comparison: total ring-model wire bytes of
    the dp8 BERT bucketed grad sync per dtype tier, and the headline
    compression ratios (tests/test_grad_comm.py asserts ≥3.5×
    int8-vs-fp32 / ≥1.9× int8-vs-bf16 live)."""
    modes = {}
    for mode in ("fp32", "bf16", "int8", "int4"):
        census = lower_dp8_bert_census(mode)
        modes[mode] = {
            "census": census,
            "total_wire_bytes": sum(r["wire_bytes"]
                                    for r in census.values()),
            "total_logical_bytes": sum(r["logical_bytes"]
                                       for r in census.values()),
        }
    w = {m: modes[m]["total_wire_bytes"] for m in modes}
    ratios = {
        "bf16_vs_fp32": round(w["fp32"] / w["bf16"], 3),
        "int8_vs_fp32": round(w["fp32"] / w["int8"], 3),
        "int8_vs_bf16": round(w["bf16"] / w["int8"], 3),
        "int4_vs_fp32": round(w["fp32"] / w["int4"], 3),
    }
    return {"module": "dp8_bert_tiny_train_bucketed",
            "modes": modes, "ratios": ratios}


def fsdp_zero3_section(fsdp=8):
    """ZeRO-3 census on the fsdp8 BERT-tiny train step: prove the
    lowering keeps NO
    full-parameter resident copies (per-device resident parameter bytes
    = full ÷ fsdp, measured on the LIVE sharded state arrays after a
    real step) and gathers parameters only in per-layer windows (one
    ``fsdp_all_gather`` per sharded param at its first forward use; the
    compiled module carries the matching all_gather ops AND the
    reduce_scatter ops their autodiff transpose becomes)."""
    import jax
    import numpy as np
    from jax import export as jexp

    import paddle_tpu.fluid as fluid
    from paddle_tpu.framework.compiler import BuildStrategy, CompiledProgram
    from paddle_tpu.framework.fsdp import apply_fsdp_sharding
    from paddle_tpu.framework.mesh_layout import MeshLayout
    from paddle_tpu.models import bert
    from paddle_tpu.ops.pallas import lowering_target
    from paddle_tpu.ops.registry import dtype_nbytes

    cfg = bert.BertConfig.tiny()
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        feeds, total, mlm, nsp = bert.build_pretrain_network(cfg)
        fluid.optimizer.Adam(1e-4).minimize(total)
    layout = MeshLayout(data=1, fsdp=fsdp, tp=1)
    rewrite = apply_fsdp_sharding(main_p, layout)
    main_p._mesh_layout = layout
    mesh = layout.build_mesh()
    bs = BuildStrategy()
    bs.fuse_all_reduce_ops = True
    prog = CompiledProgram(main_p).with_mesh(
        mesh, loss_name=total.name, batch_axis=layout.batch_axes,
        build_strategy=bs)

    block = main_p.global_block()
    gather_ops = [op for op in block.ops if op.type == "fsdp_all_gather"]
    sharded = {r["param"]: r for r in rewrite["sharded"]}
    assert len(gather_ops) == len(sharded), \
        f"{len(gather_ops)} gathers for {len(sharded)} sharded params"
    windows = {op.input_names()[0]: list(op.attrs["_window"])
               for op in gather_ops}

    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        data = bert.make_fake_batch(np.random.RandomState(0), cfg,
                                    batch_size=8, seq_len=64, num_masks=3)
        feed = {k: np.asarray(v) for k, v in data.items()}
        exe.run(prog, feed=feed, fetch_list=[total])
        # live proof: each sharded param's per-device resident buffer is
        # its 1/fsdp shard, never the full tensor
        resident, full_bytes = 0, 0
        for pname, rec in sharded.items():
            arr = scope.find_var(pname)
            fb = int(np.prod(arr.shape)) * dtype_nbytes(str(arr.dtype))
            sb = int(arr.addressable_shards[0].data.nbytes)
            assert sb * fsdp == fb, \
                f"{pname}: shard {sb} B × {fsdp} != full {fb} B — " \
                f"full-parameter resident copy detected"
            resident += sb
            full_bytes += fb
        # cross-lower for TPU and census the module: the forward gathers
        # and their reduce_scatter transposes must both be present
        step = exe._compile(main_p, feed, [total.name], scope, mesh,
                            tuple(mesh.axis_names), layout.batch_axes)
        state = {n: np.asarray(scope.find_var(n))
                 for n in step.state_in_names}
        with lowering_target("tpu"):
            exported = jexp.export(step.fn, platforms=("tpu",))(
                feed, state, jax.random.PRNGKey(0))
    census = collective_census(exported.mlir_module())
    ag = census.get("all_gather", {}).get("count", 0)
    rs = census.get("reduce_scatter", {}).get("count", 0)
    assert ag >= len(sharded), \
        f"module has {ag} all_gather ops for {len(sharded)} sharded params"
    assert rs >= 1, "no reduce_scatter in module — the gather transpose " \
                    "(ZeRO-3 grad sync over fsdp) is missing"
    return {
        "module": "fsdp8_bert_tiny_train",
        "fsdp_degree": fsdp,
        "sharded_params": len(sharded),
        "skipped_params": [[n, why] for n, why in rewrite["skipped"]],
        "full_param_bytes": full_bytes,
        "resident_param_bytes_per_device": resident,
        "resident_ratio": round(full_bytes / resident, 3) if resident
        else None,
        "gather_windows": windows,
        "module_census": census,
        "module_all_gather_count": ag,
        "module_reduce_scatter_count": rs,
    }


def selftest():
    """The quant census ratios must clear their floors."""
    _env8()
    section = quant_dp8_section()
    r = section["ratios"]
    print("dp8 quant census ratios:", json.dumps(r))
    for m, info in section["modes"].items():
        print(f"  {m}: wire={info['total_wire_bytes']} "
              f"logical={info['total_logical_bytes']}")
    ok = (r["int8_vs_fp32"] >= 3.5 and r["int8_vs_bf16"] >= 1.9
          and r["int4_vs_fp32"] >= r["int8_vs_fp32"]
          and r["bf16_vs_fp32"] >= 1.7)
    print("census selftest", "OK" if ok else "FAILED")
    return 0 if ok else 1


def main():
    _env8()
    import jax
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import bert
    from paddle_tpu.parallel import build_mesh
    from paddle_tpu.ops.pallas import lowering_target
    from jax import export as jexp

    devs = jax.devices()[:8]
    mesh = build_mesh({"dp": 2, "tp": 2, "sp": 2}, devs)
    cfg = bert.BertConfig.tiny()
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        feeds, loss = bert.build_pretrain_network_parallel(
            cfg, tp_degree=2, seq_axis="sp")
        fluid.optimizer.Adam(1e-4).minimize(loss)
    from jax.sharding import PartitionSpec as P
    feed_specs = {f.name: P("dp", "sp") for f in feeds}
    # NOT dead code: with_mesh MUTATES `main_p` in place — it inserts the
    # scale + c_allreduce_sum grad-sync ops over dp and sp (the
    # GradAllReduce transpiler rewrite); without it the lowered module
    # carries only the Megatron/ring collectives (15 all_reduce vs 53)
    fluid.CompiledProgram(main_p).with_mesh(
        mesh, loss_name=loss.name, batch_axis="dp", seq_axis="sp",
        feed_specs=feed_specs)
    exe = fluid.Executor()
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    batch = bert.make_fake_parallel_batch(rng, cfg, batch_size=4, seq_len=64)
    with fluid.scope_guard(scope):
        exe.run(startup)
        feed = {k: np.asarray(v) for k, v in batch.items()}
        step = exe._compile(main_p, feed, [loss.name], scope, mesh,
                            tuple(mesh.axis_names), "dp", seq_axis="sp",
                            feed_specs=feed_specs)
        state = {n: np.asarray(scope.find_var(n))
                 for n in step.state_in_names}
        key = jax.random.PRNGKey(0)
        with lowering_target('tpu'):
            exported = jexp.export(step.fn, platforms=('tpu',))(feed, state,
                                                                key)
    txt = exported.mlir_module()
    census = collective_census(txt)
    donated, total = donation_ratio(txt)
    counts = {k: v["count"] for k, v in census.items()}
    # static collective/donation soundness over the SAME program the
    # census lowers (framework/analysis.py): a silently-dropped donation
    # or divergent collective schedule fails the run, not just the
    # numbers (regression gate for the PR 2 silent-donation-drop class)
    from paddle_tpu.framework.analysis import (check_collective_consistency,
                                               verify_program)
    vr = verify_program(main_p, startup=startup, fetch_names=[loss.name])
    check_collective_consistency([main_p, main_p.clone()], vr)
    soundness_errs = [d.format() for d in vr.errors()]
    lines = [
        "Multi-chip TPU cross-lowering (dp2 x tp2 x sp2 BERT-tiny train step)",
        f"platforms: {tuple(exported.platforms)}",
        f"module bytes: {len(txt)}",
        f"collectives: {counts}",
        "census (count / payload bytes): " + ", ".join(
            f"{k}={v['count']}/{v['bytes']}" for k, v in census.items()),
        f"arg donation: {donated}/{total}",
        f"static soundness: {'OK' if not soundness_errs else 'FAIL'} "
        f"({len(soundness_errs)} error(s))",
        f"verdict: {'OK' if counts.get('all_reduce', 0) >= 10 and counts.get('collective_permute', 0) >= 3 and not soundness_errs else 'MISSING COLLECTIVES OR UNSOUND'}",
    ]
    # dp8 wire-compression comparison across dtype tiers (int8 buckets
    # ≥3.5× fewer wire bytes than fp32)
    quant = quant_dp8_section()
    lines.append("dp8 quant wire ratios: " + json.dumps(quant["ratios"]))
    out = "\n".join(lines + soundness_errs)
    print(out)


def fsdp_main(argv):
    """``--fsdp``: run the ZeRO-3 census."""
    _env8()
    section = fsdp_zero3_section()
    print(f"fsdp census OK: {section['sharded_params']} sharded params, "
          f"resident ratio {section['resident_ratio']}x, "
          f"{section['module_all_gather_count']} all_gather / "
          f"{section['module_reduce_scatter_count']} reduce_scatter in "
          f"module")
    return 0


if __name__ == "__main__":
    if "--fsdp" in sys.argv:
        sys.exit(fsdp_main(sys.argv[1:]))
    if "--overlap" in sys.argv:
        sys.exit(overlap_main(sys.argv[1:]))
    if "--selftest" in sys.argv:
        sys.exit(selftest())
    main()
