"""Optimizer update ops (ref: operators/optimizers/*.cc — sgd_op, momentum_op,
adam_op, lamb_op, lars_momentum_op, adagrad_op, rmsprop_op, adadelta_op,
adamax_op, ftrl_op, decayed_adagrad_op, dpsgd_op).

In the reference each optimizer op mutates Param/accumulators in place; here
outputs (ParamOut, MomentOut, ...) are new arrays the executor writes back to
the same variable names — the functional-update equivalent.  XLA fuses the
whole update chain into a couple of kernels, which is what the reference's
fuse_optimizer_ops_pass hand-builds (ref: framework/ir/fuse_optimizer_ops_pass/)."""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from .registry import register, x


@register("sgd")
def _sgd(ctx, ins, attrs):
    p, g, lr = x(ins, "Param"), x(ins, "Grad"), x(ins, "LearningRate")
    return {"ParamOut": p - lr.astype(p.dtype) * g.astype(p.dtype)}


@register("momentum")
def _momentum(ctx, ins, attrs):
    p, g, v, lr = x(ins, "Param"), x(ins, "Grad"), x(ins, "Velocity"), \
        x(ins, "LearningRate")
    mu = attrs.get("mu", 0.9)
    use_nesterov = attrs.get("use_nesterov", False)
    lr = lr.astype(p.dtype)
    g = g.astype(p.dtype)
    # L2 regularization folded into the op (ref: momentum_op.h regularization_method)
    if attrs.get("regularization_method", "") == "l2_decay":
        g = g + attrs.get("regularization_coeff", 0.0) * p
    v_out = mu * v + g
    if use_nesterov:
        p_out = p - (g + mu * v_out) * lr
    else:
        p_out = p - lr * v_out
    return {"ParamOut": p_out, "VelocityOut": v_out}


@register("lars_momentum")
def _lars_momentum(ctx, ins, attrs):
    p, g, v, lr = x(ins, "Param"), x(ins, "Grad"), x(ins, "Velocity"), \
        x(ins, "LearningRate")
    mu = attrs.get("mu", 0.9)
    lars_coeff = attrs.get("lars_coeff", 0.001)
    lars_wd = attrs.get("lars_weight_decay", 0.0005)
    eps = attrs.get("epsilon", 0.0)
    p_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
    g_norm = jnp.sqrt(jnp.sum(jnp.square(g)))
    local_lr = jnp.where(
        (p_norm > 0) & (g_norm > 0),
        lr * lars_coeff * p_norm / (g_norm + lars_wd * p_norm + eps), lr)
    v_out = mu * v + local_lr * (g + lars_wd * p)
    return {"ParamOut": p - v_out, "VelocityOut": v_out}


@register("adam")
def _adam(ctx, ins, attrs):
    p, g, lr = x(ins, "Param"), x(ins, "Grad"), x(ins, "LearningRate")
    m1, m2 = x(ins, "Moment1"), x(ins, "Moment2")
    b1p, b2p = x(ins, "Beta1Pow"), x(ins, "Beta2Pow")
    beta1 = attrs.get("beta1", 0.9)
    beta2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-8)
    g = g.astype(m1.dtype)
    lr_t = lr * jnp.sqrt(1 - b2p) / (1 - b1p)

    if attrs.get("lazy_mode") and ins.get("SparseRows"):
        # SelectedRows semantics (ref: selected_rows.h:32 + adam_op.h's
        # lazy sparse branch): rows the batch never touched keep their
        # param AND moments — no decay drift for cold embedding rows.
        # TPU-natively the "sparse" update is a dense masked select (a
        # gather/scatter would defeat XLA's static layout); bandwidth
        # equals one masked pass, which is what the MXU-adjacent VPU
        # does best.
        ids = jnp.concatenate([jnp.reshape(i, (-1,))
                               for i in ins["SparseRows"]])
        touched = jnp.zeros((p.shape[0],), bool).at[ids].set(True)
        rowsel = touched.reshape((-1,) + (1,) * (p.ndim - 1))
        m1_new = beta1 * m1 + (1 - beta1) * g
        m2_new = beta2 * m2 + (1 - beta2) * g * g
        p_new = p - lr_t.astype(p.dtype) * (
            m1_new / (jnp.sqrt(m2_new) + eps)).astype(p.dtype)
        return {"ParamOut": jnp.where(rowsel, p_new, p),
                "Moment1Out": jnp.where(rowsel, m1_new, m1),
                "Moment2Out": jnp.where(rowsel, m2_new, m2),
                "Beta1PowOut": b1p * beta1, "Beta2PowOut": b2p * beta2}

    # one elementwise composition, left to XLA on purpose: it fuses the
    # update in the tensor's own tiled layout, over the donated buffers,
    # often into the matmul that produces the gradient.  A kernel needs a
    # 2-D view of every tensor, and a view that is not free relays p, g,
    # m and v; measured on the v5e, PERF.md section 6 (PR 30).
    m1_out = beta1 * m1 + (1 - beta1) * g
    m2_out = beta2 * m2 + (1 - beta2) * g * g
    p_out = p - lr_t.astype(p.dtype) * (
        m1_out / (jnp.sqrt(m2_out) + eps)).astype(p.dtype)
    return {"ParamOut": p_out, "Moment1Out": m1_out, "Moment2Out": m2_out,
            "Beta1PowOut": b1p * beta1, "Beta2PowOut": b2p * beta2}


@register("adamw")
def _adamw(ctx, ins, attrs):
    coeff = attrs.get("coeff", 0.01)
    p, lr = x(ins, "Param"), x(ins, "LearningRate")
    out = _adam(ctx, ins, attrs)
    if not attrs.get("with_decay", True):
        return out
    out["ParamOut"] = out["ParamOut"] - lr.astype(p.dtype) * coeff * p
    return out


@register("lamb")
def _lamb(ctx, ins, attrs):
    """ref: operators/optimizers/lamb_op.h — layer-adaptive large-batch."""
    p, g, lr = x(ins, "Param"), x(ins, "Grad"), x(ins, "LearningRate")
    m1, m2 = x(ins, "Moment1"), x(ins, "Moment2")
    b1p, b2p = x(ins, "Beta1Pow"), x(ins, "Beta2Pow")
    beta1 = attrs.get("beta1", 0.9)
    beta2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-6)
    wd = attrs.get("weight_decay", 0.01)
    g = g.astype(m1.dtype)
    m1_out = beta1 * m1 + (1 - beta1) * g
    m2_out = beta2 * m2 + (1 - beta2) * g * g
    m1_hat = m1_out / (1 - b1p)
    m2_hat = m2_out / (1 - b2p)
    r = m1_hat / (jnp.sqrt(m2_hat) + eps) + wd * p
    p_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
    r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
    ratio = jnp.where((p_norm > 0) & (r_norm > 0), p_norm / r_norm, 1.0)
    p_out = p - (lr * ratio).astype(p.dtype) * r.astype(p.dtype)
    return {"ParamOut": p_out, "Moment1Out": m1_out, "Moment2Out": m2_out,
            "Beta1PowOut": b1p * beta1, "Beta2PowOut": b2p * beta2}


@register("adagrad")
def _adagrad(ctx, ins, attrs):
    p, g, mom, lr = x(ins, "Param"), x(ins, "Grad"), x(ins, "Moment"), \
        x(ins, "LearningRate")
    eps = attrs.get("epsilon", 1e-6)
    mom_out = mom + g * g
    p_out = p - lr.astype(p.dtype) * g / (jnp.sqrt(mom_out) + eps)
    return {"ParamOut": p_out, "MomentOut": mom_out}


@register("decayed_adagrad")
def _decayed_adagrad(ctx, ins, attrs):
    p, g, mom, lr = x(ins, "Param"), x(ins, "Grad"), x(ins, "Moment"), \
        x(ins, "LearningRate")
    decay = attrs.get("decay", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    mom_out = decay * mom + (1 - decay) * g * g
    p_out = p - lr.astype(p.dtype) * g / (jnp.sqrt(mom_out) + eps)
    return {"ParamOut": p_out, "MomentOut": mom_out}


@register("rmsprop")
def _rmsprop(ctx, ins, attrs):
    p, g, lr = x(ins, "Param"), x(ins, "Grad"), x(ins, "LearningRate")
    ms, mom = x(ins, "MeanSquare"), x(ins, "Moment")
    mg = x(ins, "MeanGrad")
    rho = attrs.get("decay", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    momentum = attrs.get("momentum", 0.0)
    ms_out = rho * ms + (1 - rho) * g * g
    if attrs.get("centered", False):
        mg_out = rho * mg + (1 - rho) * g
        denom = ms_out - mg_out * mg_out + eps
    else:
        mg_out = mg
        denom = ms_out + eps
    mom_out = momentum * mom + lr.astype(p.dtype) * g / jnp.sqrt(denom)
    return {"ParamOut": p - mom_out, "MomentOut": mom_out,
            "MeanSquareOut": ms_out, "MeanGradOut": mg_out}


@register("adadelta")
def _adadelta(ctx, ins, attrs):
    p, g = x(ins, "Param"), x(ins, "Grad")
    avg_sq_g, avg_sq_u = x(ins, "AvgSquaredGrad"), x(ins, "AvgSquaredUpdate")
    rho = attrs.get("rho", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    g2 = rho * avg_sq_g + (1 - rho) * g * g
    update = -jnp.sqrt((avg_sq_u + eps) / (g2 + eps)) * g
    u2 = rho * avg_sq_u + (1 - rho) * update * update
    return {"ParamOut": p + update, "AvgSquaredGradOut": g2,
            "AvgSquaredUpdateOut": u2}


@register("adamax")
def _adamax(ctx, ins, attrs):
    p, g, lr = x(ins, "Param"), x(ins, "Grad"), x(ins, "LearningRate")
    mom, inf_norm, b1p = x(ins, "Moment"), x(ins, "InfNorm"), x(ins, "Beta1Pow")
    beta1 = attrs.get("beta1", 0.9)
    beta2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-8)
    mom_out = beta1 * mom + (1 - beta1) * g
    inf_out = jnp.maximum(beta2 * inf_norm, jnp.abs(g))
    lr_t = lr / (1 - b1p)
    p_out = p - lr_t.astype(p.dtype) * mom_out / (inf_out + eps)
    # beta1_pow advances each step (the reference does this in
    # AdamaxOptimizer._finish_update, optimizer.py)
    return {"ParamOut": p_out, "MomentOut": mom_out, "InfNormOut": inf_out,
            "Beta1PowOut": b1p * beta1}


@register("ftrl")
def _ftrl(ctx, ins, attrs):
    p, g, lr = x(ins, "Param"), x(ins, "Grad"), x(ins, "LearningRate")
    sq, lin = x(ins, "SquaredAccumulator"), x(ins, "LinearAccumulator")
    l1 = attrs.get("l1", 0.0)
    l2 = attrs.get("l2", 0.0)
    lr_power = attrs.get("lr_power", -0.5)
    new_sq = sq + g * g
    if lr_power == -0.5:
        sigma = (jnp.sqrt(new_sq) - jnp.sqrt(sq)) / lr
    else:
        sigma = (jnp.power(new_sq, -lr_power) - jnp.power(sq, -lr_power)) / lr
    lin_out = lin + g - sigma * p
    if lr_power == -0.5:
        denom = jnp.sqrt(new_sq) / lr + 2 * l2
    else:
        denom = jnp.power(new_sq, -lr_power) / lr + 2 * l2
    pre = jnp.clip(lin_out, -l1, l1) - lin_out
    p_out = pre / denom
    return {"ParamOut": p_out, "SquaredAccumOut": new_sq,
            "LinearAccumOut": lin_out}


@register("dpsgd")
def _dpsgd(ctx, ins, attrs):
    """Differentially-private SGD (ref: optimizers/dpsgd_op.h): clip grad
    to `clip` L2-norm, add gaussian noise sigma*clip/batch_size."""
    import jax
    p, g, lr = x(ins, "Param"), x(ins, "Grad"), x(ins, "LearningRate")
    clip = attrs.get("clip", 10.0)
    batch_size = attrs.get("batch_size", 16.0)
    sigma = attrs.get("sigma", 1.0)
    norm = jnp.sqrt(jnp.sum(jnp.square(g)))
    scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))
    noise = jax.random.normal(ctx.next_key(), g.shape, g.dtype) * \
        (sigma * clip / batch_size)
    return {"ParamOut": p - lr.astype(p.dtype) * (g * scale + noise)}


# ---------------------------------------------------------------------------
# AMP loss-scaling support ops (ref: operators/amp/)
# ---------------------------------------------------------------------------


@register("check_finite_and_unscale")
def _check_finite_and_unscale(ctx, ins, attrs):
    xs = ins["X"]
    scale = x(ins, "Scale")
    finite = jnp.array(True)
    outs = []
    for g in xs:
        finite = jnp.logical_and(finite, jnp.all(jnp.isfinite(g)))
        outs.append(g / scale.astype(g.dtype))
    found_inf = jnp.logical_not(finite)
    outs = [jnp.where(found_inf, jnp.zeros_like(g), g) for g in outs]
    return {"Out": outs, "FoundInfinite": found_inf}


@register("amp_check_finite_and_scale")
def _amp_check_finite_and_scale(ctx, ins, attrs):
    return _check_finite_and_unscale(ctx, ins, attrs)


@register("update_loss_scaling")
def _update_loss_scaling(ctx, ins, attrs):
    """ref: operators/amp/update_loss_scaling_op.h — dynamic loss scale.

    The backoff/regrow math lives in
    framework/guardrails.scale_policy_update — ONE policy shared with
    the non-AMP guardrail scale state, so fp16/bf16/fp32 runs recover
    through the same code path."""
    from ..framework.guardrails import scale_policy_update
    found_inf = x(ins, "FoundInfinite")
    scale = x(ins, "PrevLossScaling")
    good = x(ins, "InGoodSteps")
    bad = x(ins, "InBadSteps")
    new_scale, good_new, bad_new = scale_policy_update(
        found_inf, scale, good, bad,
        incr_every_n_steps=attrs.get("incr_every_n_steps", 1000),
        decr_every_n_nan_or_inf=attrs.get("decr_every_n_nan_or_inf", 2),
        incr_ratio=attrs.get("incr_ratio", 2.0),
        decr_ratio=attrs.get("decr_ratio", 0.5))
    outs = [jnp.where(found_inf, jnp.zeros_like(g), g) for g in ins.get("X", [])]
    return {"Out": outs, "LossScaling": new_scale,
            "OutGoodSteps": good_new, "OutBadSteps": bad_new}


@register("average_accumulates")
def _average_accumulates(ctx, ins, attrs):
    """Sliding-window parameter averaging accumulator (ref:
    operators/optimizers/average_accumulates_op.h, used by ModelAverage
    optimizer.py:3069).

    State machine (identical to the reference, expressed with jnp.where so
    the step stays one static XLA program):
      num_updates += 1; num_accumulates += 1; sum_1 += param
      if num_updates % kMaxNumAccumulates == 0: sum_2 += sum_1; sum_1 = 0
      if num_accumulates >= min_average_window and
         num_accumulates >= min(max_average_window,
                                num_updates * average_window_rate):
          sum_3 = sum_1 + sum_2; sum_1 = sum_2 = 0
          old_num_accumulates = num_accumulates; num_accumulates = 0
    """
    p = x(ins, "param")
    s1, s2, s3 = x(ins, "in_sum_1"), x(ins, "in_sum_2"), x(ins, "in_sum_3")
    num_acc = x(ins, "in_num_accumulates")
    old_num = x(ins, "in_old_num_accumulates")
    num_upd = x(ins, "in_num_updates")
    rate = attrs.get("average_window", 0.0)
    max_win = attrs.get("max_average_window", 10000)
    min_win = attrs.get("min_average_window", 10000)
    k_max = 16384  # kMaxNumAccumulates in the reference

    num_upd = num_upd + 1
    num_acc = num_acc + 1
    s1 = s1 + p.astype(s1.dtype)
    roll = (num_upd % k_max) == 0
    s2 = jnp.where(roll, s2 + s1, s2)
    s1 = jnp.where(roll, jnp.zeros_like(s1), s1)
    window = jnp.minimum(jnp.asarray(float(max_win)),
                         num_upd.astype(jnp.float32) * rate)
    shift = jnp.logical_and(num_acc >= min_win,
                            num_acc.astype(jnp.float32) >= window)
    s3 = jnp.where(shift, s1 + s2, s3)
    s1 = jnp.where(shift, jnp.zeros_like(s1), s1)
    s2 = jnp.where(shift, jnp.zeros_like(s2), s2)
    old_num = jnp.where(shift, num_acc, old_num)
    num_acc = jnp.where(shift, jnp.zeros_like(num_acc), num_acc)
    return {"out_sum_1": s1, "out_sum_2": s2, "out_sum_3": s3,
            "out_num_accumulates": num_acc,
            "out_old_num_accumulates": old_num,
            "out_num_updates": num_upd}


@register("dgc_momentum")
def _dgc_momentum(ctx, ins, attrs):
    """Deep Gradient Compression momentum step (ref: operators/dgc_op.cc +
    optimizers/momentum via DGCMomentumOptimizer optimizer.py:1143).

    DGC keeps two accumulators: U (momentum-corrected velocity) and V (the
    residual of unsent gradient mass).  Each step the top-(1-s) fraction of
    |V| by magnitude is "sent" (here: kept dense and psum'd over ICI — the
    bandwidth motivation for sparsifying disappears on TPU interconnect, but
    the *convergence semantics* of masked updates + residual accumulation
    are preserved exactly).  Before ``rampup_begin_step`` it is plain
    momentum.  The sparsity ratio ramps through ``sparsity`` over
    ``rampup_step`` steps; the top-k threshold is computed as a dynamic
    quantile so the program stays shape-static.
    """
    p, g, lr = x(ins, "Param"), x(ins, "Grad"), x(ins, "LearningRate")
    u, v = x(ins, "U"), x(ins, "V")
    step = x(ins, "CurrentStep")
    mu = attrs.get("momentum", 0.9)
    use_nesterov = attrs.get("use_nesterov", False)
    rampup_begin = float(attrs.get("rampup_begin_step", 0.0))
    rampup_step = max(float(attrs.get("rampup_step", 1.0)), 1.0)
    sparsity = list(attrs.get("sparsity", [0.999]))

    lr = lr.astype(p.dtype)
    g = g.astype(p.dtype)
    stepf = step.reshape(()).astype(jnp.float32)

    # sparsity schedule: index into the sparsity list over the ramp window
    prog = jnp.clip((stepf - rampup_begin) / rampup_step, 0.0, 1.0)
    sched = jnp.asarray(sparsity, jnp.float32)
    idx = jnp.minimum((prog * len(sparsity)).astype(jnp.int32),
                      len(sparsity) - 1)
    ratio = sched[idx]

    # momentum correction (DGC paper eq. 4): U accumulates, V holds residual
    u_new = mu * u + g
    v_new = v + u_new
    absv = jnp.abs(v_new).reshape(-1)
    thr = jnp.quantile(absv.astype(jnp.float32), ratio).astype(p.dtype)
    mask = (jnp.abs(v_new) >= thr).astype(p.dtype)
    sent = v_new * mask                    # dense "encoded" gradient
    v_keep = v_new * (1.0 - mask)
    u_keep = u_new * (1.0 - mask)

    dgc_on = stepf >= rampup_begin
    plain_update = g + mu * u_new if use_nesterov else u_new
    p_out = jnp.where(dgc_on, p - lr * sent, p - lr * plain_update)
    u_out = jnp.where(dgc_on, u_keep, u_new)
    v_out = jnp.where(dgc_on, v_keep, v)
    return {"ParamOut": p_out, "UOut": u_out, "VOut": v_out}
