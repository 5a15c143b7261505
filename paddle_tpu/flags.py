"""Process-level flags (ref: platform/flags.cc:33-485 gflags definitions +
pybind/global_value_getter_setter.cc runtime get/set).

The reference defines ~40 gflags read from ``FLAGS_*`` env vars at process
start and settable at runtime via ``fluid.get_flags``/``set_flags``.  Same
contract here; flags whose job XLA now owns (memory fractions, cudnn
autotune) are accepted for script compatibility and documented as no-ops.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Iterable, List, Union

_REGISTRY: Dict[str, Any] = {}
_NOOP: set = set()


def _register(name: str, default, noop: bool = False):
    env = os.environ.get(f"FLAGS_{name}")
    if env is not None:
        if isinstance(default, bool):
            default = env.lower() in ("1", "true", "yes")
        elif isinstance(default, int):
            default = int(env)
        elif isinstance(default, float):
            default = float(env)
        else:
            default = env
    _REGISTRY[name] = default
    if noop:
        _NOOP.add(name)


# live flags (consulted by the framework)
_register("check_nan_inf", False)          # ref: platform/flags.cc:44
# PS RPC call deadline in seconds (ref: grpc_client.h:247 deadlines via
# FLAGS_rpc_deadline, default 180000ms) and in-call reconnect retries
# (ref: FLAGS_rpc_retry_times)
_register("rpc_deadline", 180.0)
_register("rpc_retry_times", 3)
# per-op localization: run ops eagerly and name the op that produced the
# first NaN/Inf (ref: framework/details/nan_inf_utils.h pinpoints the op);
# slower — debug only
_register("check_nan_inf_per_op", False)
_register("use_flash_attention", True)     # pallas kernel gate (TPU-new)
_register("use_pallas_fused", True)        # fused LN/add-LN/bias-gelu kernels
# reuse the device copy of a feed array fed repeatedly: sound only when the
# caller promises not to mutate the buffer in place, signalled by freezing
# it (arr.flags.writeable = False) — the analog of the reference's
# buffered_reader keeping the staged GPU copy alive
# (ref: operators/reader/buffered_reader.cc:92 double-buffer slots)
_register("cache_feed_arrays", True)
# capacity of the host→device feed cache above (entries).  The old
# hardcoded 64 thrashes under a serving stream of distinct frozen request
# tensors; read live per lookup so a serving process can widen it at
# runtime.  0 disables caching.  Hit/miss counters surface in
# profiler.step_breakdown()["feed_cache"].
_register("feed_cache_size", 64)
_register("benchmark", False)              # ref: flags.cc benchmark
# prepared fast path (Executor.prepare): how many steps the host may run
# ahead of the device before blocking once on the oldest in-flight step —
# backpressure instead of lockstep (the role ExecutionStrategy's
# num_iteration_per_drop_scope plays for the reference's scope churn,
# ref: details/execution_strategy.h).  0 disables the window (unbounded
# run-ahead; fetch reads are then the only device syncs).
_register("max_inflight_steps", 2)
_register("print_executor_cache_hits", False)
# static program verification (framework/analysis.py — the
# InferShape/PADDLE_ENFORCE safety net): Executor.prepare and
# CompiledProgram verify each program once per (_uid, _version) and raise
# InvalidArgumentError diagnostics anchored at the op's creation site
_register("verify_programs", True)
# pass-boundary invariant checking: PassBuilder.apply / apply_pass verify
# the program before/after each pass (defined-var + fetch-reachability
# diff) — catches a fusion pass that breaks well-formedness at the pass
# boundary instead of at compile.  Off by default (lint/CI turns it on).
_register("verify_passes", False)
# static per-device HBM budget in GiB (framework/memory_analysis.py):
# Executor.prepare / Executor._compile / CompiledProgram._variant_for
# estimate the program's per-device peak HBM (sharding- and
# donation-aware, from op_spec shape/dtype inference) and raise
# InvalidArgumentError BEFORE any XLA trace/compile when the estimate
# exceeds the budget — the failure names the top live tensors and their
# creation sites instead of an opaque HLO buffer after a multi-minute
# compile.  0 (default) disables the gate.
#
# Mapping from the reference's runtime allocator flags (both accepted
# below as no-ops, since XLA owns the allocator here):
#   * fraction_of_gpu_memory_to_use=0.92 capped the arena the allocator
#     could grow into → here the analog is a STATIC pre-compile gate:
#     set hbm_budget_gb to (fraction × device HBM), e.g. 0.92 × 16 for
#     a v5e chip, and over-budget programs are rejected up front;
#   * eager_delete_tensor_gb tuned WHEN dead tensors were garbage-
#     collected at runtime → liveness is static now (XLA frees at
#     last-use by construction); the analyzer's lint profile
#     (donation-gap / fetch-retention / grad-accum-doubling) reports
#     the retention bugs that flag used to paper over.
_register("hbm_budget_gb", 0.0)
# checkpoint-write resilience (io.py): transient OSError/IOError on a
# checkpoint file write retries up to this many times with bounded
# exponential backoff (base below, doubling, capped at 2 s) before the
# error propagates.  Every retry bumps the ``checkpoint::retry`` metrics
# counter and drops a flight-recorder breadcrumb, so a flaky blob store
# is visible instead of silently slowing saves.  0 disables retries.
_register("checkpoint_retries", 3)
_register("checkpoint_retry_backoff_s", 0.05)
# persistent AOT executable cache directory (framework/aot_cache.py):
# when set, single-device compiles (Executor._compile with no mesh — the
# serving regime) serialize their XLA executables to disk
# (jax.experimental.serialize_executable) keyed by program CONTENT hash
# (the versioned desc, not the per-process _uid) × feed signature ×
# fetch list × device kind × jax version × trace-time flags, so a
# RESTARTED process deserializes in ~ms instead of re-tracing+compiling
# — the warm-restart story autoscaling serving replicas need (a cold
# bucket-grid warmup was 9.7 s/process on the CPU BERT-tiny bench).
# Writes are atomic (tmp + rename); a corrupt/stale entry falls back to
# recompile and is rewritten.  Empty (default) disables the cache.
# Hit/miss/store/error counters surface in
# profiler.step_breakdown()["aot_cache"].
_register("aot_cache_dir", "")
# always-on crash flight recorder (observability/flight.py): keep a
# lock-light ring of recent steps/spans and dump a diagnostic bundle on
# uncaught executor/serving exceptions and non-finite loss.  The
# enabled-path cost in the prepared hot loop is one flag lookup + one
# deque append per step (inside the ≤5% telemetry-overhead budget
# tests/test_observability.py asserts); turning it off removes even that.
_register("flight_recorder", True)
# where flight bundles land (empty = current working directory)
_register("flight_dump_dir", "")
# MFU denominator override in FLOP/s (observability/flops.py): 0 = auto
# from the device-kind peak table (TPU generations) with a CPU fallback
_register("device_peak_flops", 0.0)
# overlap-aware collective scheduling (compiler.insert_grad_sync +
# executor.lower_block_with_backward): when a strategy requests
# overlap_grad_sync, ready-ordered grad-sync buckets are emitted INSIDE
# the backward sweep (each bucket's fused all-reduce fires right after
# its last contributing backward op) via custom-vjp hooks, so wire time
# hides under the remaining backward compute.  This flag is the lowering
# switch: off, the same ready-ordered buckets trace at program tail
# (identical IR, identical math — the bit-parity baseline
# tests/test_overlap.py compares against).
_register("overlap_lowering", True)
# assumed ICI ring bandwidth in GB/s per device for the STATIC
# exposed-comm roofline (memory_analysis.exposed_comm_model):
# wire_time = wire_bytes / (ici_gbps · 1e9).  The default is a v5e-class
# per-chip ICI figure; override per fabric.  Only the ranking between
# configs consumes it, so absolute accuracy matters less than ordering.
_register("ici_gbps", 90.0)
# fraction of a training step's compute that sits in the backward sweep
# and can hide overlap-scheduled grad-sync wire time
# (memory_analysis.exposed_comm_model).  The historical hard-coded value
# was 2/3 — backward GEMMs are 2 of the 3 fwd+bwd GEMM units the op_spec
# ``flops`` channel prices — and the default preserves that constant
# bit-for-bit (planner rankings are unchanged at the default).  Exposed
# as a flag so the measured-cost calibration loop can fit it from
# telemetry instead of trusting the analytic 2/3.
_register("overlap_compute_frac", 2.0 / 3.0)
# when the static hbm_budget_gb gate rejects a TRAINING program, attempt
# activation rematerialization first (framework/pipe.plan_remat): insert
# recompute checkpoints at the liveness-identified peak (the cheapest-to-
# recompute residual boundaries), re-estimate, and only raise if the
# program still does not fit.  The inserted checkpoints ride the backward
# op's existing ``checkpoints`` attr (jax.checkpoint segments).  Off by
# default: budget rejection stays loud unless the caller opts into the
# automatic memory/compute trade (the auto-shard planner prices remat
# explicitly regardless of this flag).
_register("remat_on_reject", False)
# quant-small-bucket lint threshold (framework/analysis.py, surfaced by
# tools/proglint.py): a blockwise-quantized collective whose payload is
# under this many KiB pays more in per-block scale tensors + the extra
# all_to_all/all_gather stage than the narrower wire dtype saves —
# the verifier warns so tiny buckets stay full-precision (raise
# fuse_grad_size_in_MB to coalesce them instead).  0 disables the lint.
_register("quant_min_bucket_kb", 16)
# -- self-healing step runtime (framework/guardrails.py +
# observability/watchdog.py) ------------------------------------------------
# non-finite step defense: compute a fused all-finite reduction over the
# loss + raw parameter gradients INSIDE the compiled step and gate every
# written persistable with jnp.where on the result — a NaN/Inf step
# leaves params and optimizer state BITWISE unchanged (no host sync; the
# flag is part of the executable identity).  Off by default: the gate
# adds extra state plumbing every census/baseline would have to absorb.
_register("guard_nonfinite", False)
# consecutive-skip budget: after this many non-finite steps IN A ROW the
# prepared loop escalates to a controlled abort — flight bundle (with
# the offending step's feed/RNG/program as replayable sidecars for
# tools/replay_step.py) + GuardrailViolation.  0 disables escalation
# (steps keep skipping forever).
_register("max_skipped_steps", 10)
# unified dynamic loss scaling for NON-AMP runs: scale the loss by the
# guard's scale state before backward, unscale the grads, and drive the
# scale through the SAME backoff/regrow policy the AMP decorator's
# update_loss_scaling op uses (guardrails.scale_policy_update).  When
# the program already carries AMP dynamic scaling this flag is ignored
# (pick-one: AMP owns the scale; the guard still gates the update).
_register("guard_loss_scale", False)
_register("guard_loss_scale_init", 2.0 ** 15)
_register("guard_incr_every_n_steps", 1000)
_register("guard_incr_ratio", 2.0)
_register("guard_decr_ratio", 0.5)
_register("guard_loss_scale_max", 2.0 ** 16)
# hang watchdog (observability/watchdog.py): when > 0, a daemon monitor
# thread checks the step/serving/checkpoint progress beacons and, if a
# unit of work has been in flight longer than this many seconds, dumps
# all-thread stacks + a flight bundle and bumps watchdog::trip — a
# silent wedge (stalled collective, deadlocked worker) becomes a
# diagnosable event.  0 (default) disables the watchdog.
_register("step_deadline_s", 0.0)
# when the watchdog trips: also abort the process (os._exit) with
# WATCHDOG_EXIT_CODE so a supervisor can restart it.  Off by default —
# dump-and-continue is the observability mode; abort is the production
# unattended-run mode.
_register("watchdog_abort", False)

# accepted no-ops: XLA owns these concerns (ref: flags.cc lines noted)
_register("fraction_of_gpu_memory_to_use", 0.92, noop=True)   # :343
_register("eager_delete_tensor_gb", 0.0, noop=True)           # :257
_register("allocator_strategy", "auto_growth", noop=True)     # :316
_register("cudnn_deterministic", False, noop=True)            # :133
_register("cudnn_exhaustive_search", False, noop=True)
_register("conv_workspace_size_limit", 512, noop=True)
_register("memory_fraction_of_eager_deletion", 1.0, noop=True)
_register("fuse_parameter_memory_size", -1, noop=True)
_register("communicator_send_queue_size", 20, noop=True)      # :200
_register("sync_nccl_allreduce", True, noop=True)


def get_flags(flags: Union[str, Iterable[str]]) -> Dict[str, Any]:
    """ref: fluid.get_flags (pybind/global_value_getter_setter.cc)."""
    names: List[str] = [flags] if isinstance(flags, str) else list(flags)
    out = {}
    for n in names:
        key = n[6:] if n.startswith("FLAGS_") else n
        if key not in _REGISTRY:
            raise ValueError(f"flag {n!r} is not registered")
        out[n] = _REGISTRY[key]
    return out


def set_flags(flags: Dict[str, Any]):
    """ref: fluid.set_flags."""
    for n, v in flags.items():
        key = n[6:] if n.startswith("FLAGS_") else n
        if key not in _REGISTRY:
            raise ValueError(f"flag {n!r} is not registered")
        _REGISTRY[key] = v


def flag(name: str):
    """Internal fast accessor."""
    return _REGISTRY[name]


#: libtpu flags that let the compiler's latency-hiding scheduler keep the
#: ready-ordered grad-sync collectives where the trace put them (async
#: collectives overlapped with compute instead of re-sunk to the tail).
#: These are process-start flags — they must be in LIBTPU_INIT_ARGS
#: before the first backend touch, which is why they are plumbed as data
#: here instead of set_flags entries.  They are TPU-compiler flags:
#: XLA_FLAGS does not know them and aborts start-up on any of the four
#: (jaxlib 0.9.0, on the CPU and on the chip alike); libtpu 0.0.34
#: accepts all four through LIBTPU_INIT_ARGS (v5e, PR 21).
OVERLAP_XLA_FLAGS = (
    "--xla_tpu_enable_latency_hiding_scheduler=true",
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
)


def apply_overlap_xla_flags(environ=None):
    """Append any missing overlap flags to ``LIBTPU_INIT_ARGS`` in
    ``environ`` (default ``os.environ``).  Call BEFORE the first jax
    backend initialisation; returns the flags that were added."""
    env = os.environ if environ is None else environ
    current = env.get("LIBTPU_INIT_ARGS", "")
    added = [f for f in OVERLAP_XLA_FLAGS if f not in current]
    if added:
        env["LIBTPU_INIT_ARGS"] = (current + " " + " ".join(added)).strip()
    return added


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory.  Call before the first compile (chip_smoke.py).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and
    no path is set here.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache`` (git-ignored): the directory is part of
    the cache key, so a path built from a temp dir, pid or clock would
    never hit.  The thresholds drop to zero so the many small decode
    executables are cached too."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
