"""Optimizers (ref: python/paddle/fluid/optimizer.py — Optimizer base :56,
SGD:914, Momentum:1008, LarsMomentum:1558, Adagrad:1672, Adam:1788,
Adamax:2054, DecayedAdagrad:2321, Adadelta:2431, RMSProp:2550, Ftrl:2738,
Lamb:2897, plus wrapper optimizers RecomputeOptimizer:4479 and
GradientMergeOptimizer:4949 in incubate/).

Same architecture as the reference: ``minimize = append_backward +
apply_gradients``; accumulators are persistable vars initialised in the
startup program; each parameter gets one optimizer *op* appended to the main
program.  XLA fuses the whole per-param update chain (the hand-built
fuse_optimizer_ops_pass of the reference comes for free)."""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from .framework.core import (Parameter, Variable, default_main_program,
                             default_startup_program, grad_var_name)
from .framework import unique_name
from .framework.backward import append_backward
from .framework.layer_helper import LayerHelper
from .framework.initializer import ConstantInitializer
from .layers import math_ops
from .regularizer import append_regularization_ops


class Optimizer:
    def __init__(self, learning_rate, regularization=None, grad_clip=None,
                 name=None, parameter_list=None):
        self._learning_rate = learning_rate
        self.regularization = regularization
        self._grad_clip = grad_clip
        self._name = name
        self._accumulators: Dict[str, Dict[str, Variable]] = {}
        self._lr_var: Optional[Variable] = None
        self.type = getattr(self, "type", "sgd")
        # dygraph-mode state (ref: optimizer.py accepts parameter_list in
        # dygraph; accumulators live on the optimizer, step drives LR)
        self._parameter_list = list(parameter_list) if parameter_list else None
        self._eager_accs: Dict[int, Dict[str, object]] = {}
        self._eager_step = 0

    # -- learning rate ---------------------------------------------------
    def _create_global_learning_rate(self):
        if self._lr_var is not None:
            return
        from .lr_scheduler import LRScheduler
        if isinstance(self._learning_rate, Variable):
            self._lr_var = self._learning_rate
            return
        if isinstance(self._learning_rate, LRScheduler):
            self._lr_var = self._learning_rate._create_ops()
            return
        name = unique_name.generate("learning_rate")
        main = default_main_program().global_block()
        startup = default_startup_program().global_block()
        self._lr_var = main.create_var(name=name, shape=(1,),
                                       dtype="float32", persistable=True)
        sv = startup.create_var(name=name, shape=(1,), dtype="float32",
                                persistable=True)
        startup.append_op(type="fill_constant", outputs={"Out": [sv]},
                          attrs={"shape": [1], "dtype": "float32",
                                 "value": float(self._learning_rate)})

    @property
    def learning_rate_var(self):
        return self._lr_var

    def _param_lr(self, param):
        """Per-parameter LR multiplier (ref: optimizer.py _create_param_lr —
        ParamAttr(learning_rate=...) scales the global LR)."""
        mult = getattr(param, "optimize_attrs", {}).get("learning_rate", 1.0)
        if mult == 1.0:
            return self._lr_var
        block = default_main_program().global_block()
        scaled = block.create_var(
            name=unique_name.generate(f"{param.name}_lr"),
            shape=(1,), dtype="float32")
        block.append_op(type="scale", inputs={"X": [self._lr_var]},
                        outputs={"Out": [scaled]},
                        attrs={"scale": float(mult)})
        return scaled

    # -- accumulators (ref: optimizer.py _add_accumulator) ---------------
    def _add_accumulator(self, name, param, fill_value=0.0, shape=None,
                         dtype=None):
        if name not in self._accumulators:
            self._accumulators[name] = {}
        if param.name in self._accumulators[name]:
            return self._accumulators[name][param.name]
        var_name = unique_name.generate(f"{param.name}_{name}")
        shape = list(shape if shape is not None else param.shape)
        dtype = dtype or param.dtype
        main = default_main_program().global_block()
        startup = default_startup_program().global_block()
        v = main.create_var(name=var_name, shape=shape, dtype=dtype,
                            persistable=True)
        sv = startup.create_var(name=var_name, shape=shape, dtype=dtype,
                                persistable=True)
        # moment/accumulator shards follow the param's tp sharding
        da = getattr(param, "dist_attr", None)
        if da and (shape == list(param.shape)):
            v.dist_attr = da
            sv.dist_attr = da
        startup.append_op(type="fill_constant", outputs={"Out": [sv]},
                          attrs={"shape": shape, "dtype": dtype,
                                 "value": float(fill_value)})
        self._accumulators[name][param.name] = v
        return v

    def _get_accumulator(self, name, param):
        return self._accumulators[name][param.name]

    # -- to be overridden ------------------------------------------------
    def _create_accumulators(self, block, parameters):
        pass

    def _append_optimize_op(self, block, param_and_grad):
        raise NotImplementedError

    # -- main entry points (ref: optimizer.py minimize/apply_gradients) --
    def backward(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, callbacks=None, checkpoints=None):
        return append_backward(loss, parameter_list, no_grad_set,
                               checkpoints=checkpoints)

    def apply_gradients(self, params_grads):
        prog = default_main_program()
        # ops go to the CURRENT block so wrappers can gate the whole apply
        # inside a conditional region (GradientMerge's exact skip);
        # accumulators are persistable state and always live globally
        block = prog.current_block()
        params_grads = append_regularization_ops(params_grads,
                                                 self.regularization)
        grad_clip = self._grad_clip
        if grad_clip is None:
            from .clip import get_gradient_clip
            grad_clip = get_gradient_clip()
        if grad_clip is not None:
            params_grads = grad_clip(params_grads)
        self._create_global_learning_rate()
        self._create_accumulators(prog.global_block(),
                                  [p for p, _ in params_grads])
        opt_ops = []
        for pg in params_grads:
            opt_ops.append(self._append_optimize_op(block, pg))
        return opt_ops

    def apply_optimize(self, loss, startup_program, params_grads):
        return self.apply_gradients(params_grads)

    # -- dygraph (eager) path (ref: optimizer.py dygraph branch of
    # minimize; imperative mode applies the same optimizer ops directly) --
    _EAGER_ACCS = {
        "sgd": [], "dpsgd": [],
        "momentum": [("velocity", "Velocity", "VelocityOut", None, False)],
        "lars_momentum": [("velocity", "Velocity", "VelocityOut",
                           None, False)],
        "adam": [("moment1", "Moment1", "Moment1Out", None, False),
                 ("moment2", "Moment2", "Moment2Out", None, False),
                 ("beta1_pow_acc", "Beta1Pow", "Beta1PowOut",
                  "_beta1", True),
                 ("beta2_pow_acc", "Beta2Pow", "Beta2PowOut",
                  "_beta2", True)],
        "adagrad": [("moment", "Moment", "MomentOut", "_initial", False)],
        "decayed_adagrad": [("moment", "Moment", "MomentOut", None, False)],
        "rmsprop": [("mean_square", "MeanSquare", "MeanSquareOut",
                     None, False),
                    ("mean_grad", "MeanGrad", "MeanGradOut", None, False),
                    ("momentum", "Moment", "MomentOut", None, False)],
        "adadelta": [("avg_squared_grad", "AvgSquaredGrad",
                      "AvgSquaredGradOut", None, False),
                     ("avg_squared_update", "AvgSquaredUpdate",
                      "AvgSquaredUpdateOut", None, False)],
        "adamax": [("moment", "Moment", "MomentOut", None, False),
                   ("inf_norm", "InfNorm", "InfNormOut", None, False),
                   ("beta1_pow_acc", "Beta1Pow", "Beta1PowOut",
                    "_beta1", True)],
        "ftrl": [("squared", "SquaredAccumulator", "SquaredAccumOut",
                  None, False),
                 ("linear", "LinearAccumulator", "LinearAccumOut",
                  None, False)],
    }
    _EAGER_ACCS["adamw"] = _EAGER_ACCS["adam"]
    _EAGER_ACCS["lamb"] = _EAGER_ACCS["adam"]

    def _eager_attrs(self, param):
        t = self.type
        if t == "momentum":
            return {"mu": self._momentum, "use_nesterov": self._use_nesterov}
        if t == "lars_momentum":
            return {"mu": self._momentum, "lars_coeff": self._lars_coeff,
                    "lars_weight_decay": self._lars_weight_decay,
                    "epsilon": self._epsilon}
        if t in ("adam", "adamw"):
            return self._op_attrs()
        if t == "lamb":
            wd = self._weight_decay
            if self._exclude_fn is not None and self._exclude_fn(param):
                wd = 0.0
            return {"beta1": self._beta1, "beta2": self._beta2,
                    "epsilon": self._epsilon, "weight_decay": wd}
        if t == "adagrad":
            return {"epsilon": self._epsilon}
        if t == "decayed_adagrad":
            return {"decay": self._decay, "epsilon": self._epsilon}
        if t == "rmsprop":
            return {"decay": self._rho, "epsilon": self._epsilon,
                    "momentum": self._momentum, "centered": self._centered}
        if t == "adadelta":
            return {"rho": self._rho, "epsilon": self._epsilon}
        if t == "adamax":
            return {"beta1": self._beta1, "beta2": self._beta2,
                    "epsilon": self._epsilon}
        if t == "ftrl":
            return {"l1": self._l1, "l2": self._l2,
                    "lr_power": self._lr_power}
        if t == "dpsgd":
            return {"clip": self._clip, "batch_size": self._batch_size,
                    "sigma": self._sigma}
        return {}

    def _eager_lr(self):
        import jax.numpy as jnp
        from .lr_scheduler import LRScheduler
        if isinstance(self._learning_rate, LRScheduler):
            return self._learning_rate.eager_value(self._eager_step)
        return jnp.asarray([float(self._learning_rate)], jnp.float32)

    def current_step_lr(self):
        return float(np.asarray(self._eager_lr())[0])

    def _dygraph_minimize(self, loss, parameter_list=None):
        import jax.numpy as jnp
        from .ops.registry import get_op, LoweringContext
        from .dygraph.tracer import tracer as _dytracer
        from .regularizer import L2Decay, L1Decay

        if self.type not in self._EAGER_ACCS:
            raise NotImplementedError(
                f"optimizer type {self.type!r} has no dygraph path")
        params = parameter_list or self._parameter_list
        if params is None:
            raise ValueError(
                "dygraph minimize needs parameter_list (pass it to the "
                "optimizer constructor or to minimize())")
        op_fn = get_op(self.type)
        lr = self._eager_lr()
        # regularization BEFORE clipping, matching apply_gradients order
        pgs = []
        for p in params:
            if p._grad is None:
                continue
            g = p._grad
            reg = getattr(p, "regularizer", None) or self.regularization
            if isinstance(reg, L2Decay):
                g = g + reg.coeff * p.value
            elif isinstance(reg, L1Decay):
                g = g + reg.coeff * jnp.sign(p.value)
            pgs.append((p, g))
        if self._grad_clip is not None:
            pgs = self._grad_clip._eager_clip(pgs)
        for p, g in pgs:
            accs = self._eager_accs.get(id(p))
            if accs is None:
                accs = {}
                for key, _, _, fill_attr, scalar in \
                        self._EAGER_ACCS[self.type]:
                    fill = getattr(self, fill_attr) if fill_attr else 0.0
                    shape = (1,) if scalar else p.value.shape
                    accs[key] = jnp.full(shape, fill,
                                         dtype=jnp.float32 if scalar
                                         else p.value.dtype)
                self._eager_accs[id(p)] = accs
            mult = getattr(p, "optimize_attrs", {}).get("learning_rate", 1.0)
            ins = {"Param": [p.value], "Grad": [g],
                   "LearningRate": [lr * mult]}
            for key, in_slot, _, _, _ in self._EAGER_ACCS[self.type]:
                ins[in_slot] = [accs[key]]
            res = op_fn(LoweringContext(_dytracer().next_key()), ins,
                        self._eager_attrs(p))
            p.set_value(res["ParamOut"])
            for key, _, out_slot, _, _ in self._EAGER_ACCS[self.type]:
                if out_slot in res:
                    accs[key] = res[out_slot]
        self._eager_step += 1
        return None, [(p, g) for p, g in pgs]

    def state_dict(self):
        """Optimizer accumulators for save_dygraph (.pdopt)."""
        sd = {"__step__": np.asarray([self._eager_step])}
        names = {id(p): p.name for p in (self._parameter_list or [])}
        for pid, accs in self._eager_accs.items():
            pname = names.get(pid, str(pid))
            for key, v in accs.items():
                sd[f"{pname}@{key}"] = np.asarray(v)
        return sd

    def set_state_dict(self, sd):
        import jax.numpy as jnp
        self._eager_step = int(np.asarray(sd.get("__step__", [0]))[0]) \
            if "__step__" in sd else 0
        names = {p.name: id(p) for p in (self._parameter_list or [])}
        for k, v in sd.items():
            if "@" not in k:
                continue
            pname, key = k.rsplit("@", 1)
            pid = names.get(pname)
            if pid is not None:
                self._eager_accs.setdefault(pid, {})[key] = jnp.asarray(v)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        from .dygraph.base import in_dygraph_mode
        if in_dygraph_mode():
            return self._dygraph_minimize(loss, parameter_list)
        # ops must land in LOSS's program even when minimize is called
        # outside the program_guard that built the net (ref: optimizer.py
        # minimize wraps in program_guard(loss.block.program))
        from .framework.core import program_guard
        with program_guard(loss.block.program,
                           startup_program or default_startup_program()):
            params_grads = self.backward(loss, startup_program,
                                         parameter_list, no_grad_set)
            opt_ops = self.apply_gradients(params_grads)
        return opt_ops, params_grads


class SGDOptimizer(Optimizer):
    type = "sgd"

    def _append_optimize_op(self, block, pg):
        p, g = pg
        return block.append_op(
            type="sgd",
            inputs={"Param": [p], "Grad": [g],
                    "LearningRate": [self._param_lr(p)]},
            outputs={"ParamOut": [p]})


class MomentumOptimizer(Optimizer):
    type = "momentum"

    def __init__(self, learning_rate, momentum=0.9, use_nesterov=False,
                 regularization=None, grad_clip=None, name=None,
                 parameter_list=None):
        super().__init__(learning_rate, regularization, grad_clip, name,
                         parameter_list=parameter_list)
        self._momentum = momentum
        self._use_nesterov = use_nesterov

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("velocity", p)

    def _append_optimize_op(self, block, pg):
        p, g = pg
        v = self._get_accumulator("velocity", p)
        return block.append_op(
            type="momentum",
            inputs={"Param": [p], "Grad": [g], "Velocity": [v],
                    "LearningRate": [self._param_lr(p)]},
            outputs={"ParamOut": [p], "VelocityOut": [v]},
            attrs={"mu": self._momentum, "use_nesterov": self._use_nesterov})


class LarsMomentumOptimizer(Optimizer):
    type = "lars_momentum"

    def __init__(self, learning_rate, momentum=0.9, lars_coeff=0.001,
                 lars_weight_decay=0.0005, epsilon=0, regularization=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, regularization, grad_clip, name)
        self._momentum = momentum
        self._lars_coeff = lars_coeff
        self._lars_weight_decay = lars_weight_decay
        self._epsilon = epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("velocity", p)

    def _append_optimize_op(self, block, pg):
        p, g = pg
        v = self._get_accumulator("velocity", p)
        return block.append_op(
            type="lars_momentum",
            inputs={"Param": [p], "Grad": [g], "Velocity": [v],
                    "LearningRate": [self._param_lr(p)]},
            outputs={"ParamOut": [p], "VelocityOut": [v]},
            attrs={"mu": self._momentum, "lars_coeff": self._lars_coeff,
                   "lars_weight_decay": self._lars_weight_decay,
                   "epsilon": self._epsilon})


class AdamOptimizer(Optimizer):
    type = "adam"

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, regularization=None, grad_clip=None,
                 lazy_mode=False, name=None, parameter_list=None):
        super().__init__(learning_rate, regularization, grad_clip, name,
                         parameter_list=parameter_list)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._lazy_mode = lazy_mode

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment1", p)
            self._add_accumulator("moment2", p)
            self._add_accumulator("beta1_pow_acc", p, fill_value=self._beta1,
                                  shape=[1])
            self._add_accumulator("beta2_pow_acc", p, fill_value=self._beta2,
                                  shape=[1])

    def _lookup_ids_for(self, block, param):
        """Ids vars of every lookup_table op reading ``param`` — the rows
        the batch touched (SelectedRows rows; ref: selected_rows.h:32,
        adam_op.h lazy_mode sparse branch).

        Lazy mode only applies when lookup_table ops are the param's SOLE
        gradient contributors: the reference takes the sparse branch only
        when the grad var really is SelectedRows (adam_op.cc grad type
        dispatch), and a param with another consumer (e.g. tied in/out
        embeddings reused in a matmul) gets a dense grad whose non-lookup
        rows a masked update would silently freeze."""
        ids = []
        for op in block.ops:
            if op.type == "backward":
                break          # consumers live in the forward section
            if param.name not in op.input_names():
                continue
            if op.type in ("lookup_table", "lookup_table_v2"):
                ids.extend(n for n in op.inputs.get("Ids", ())
                           if n not in ids)
            else:
                return []      # dense contributor present → dense Adam
        return ids

    def _append_optimize_op(self, block, pg):
        p, g = pg
        m1 = self._get_accumulator("moment1", p)
        m2 = self._get_accumulator("moment2", p)
        b1p = self._get_accumulator("beta1_pow_acc", p)
        b2p = self._get_accumulator("beta2_pow_acc", p)
        inputs = {"Param": [p], "Grad": [g],
                  "LearningRate": [self._param_lr(p)],
                  "Moment1": [m1], "Moment2": [m2],
                  "Beta1Pow": [b1p], "Beta2Pow": [b2p]}
        attrs = self._op_attrs()
        if getattr(self, "_lazy_mode", False):
            rows = self._lookup_ids_for(block, p)
            if rows:
                inputs["SparseRows"] = rows
                attrs["lazy_mode"] = True
        return block.append_op(
            type=self.type,
            inputs=inputs,
            outputs={"ParamOut": [p], "Moment1Out": [m1], "Moment2Out": [m2],
                     "Beta1PowOut": [b1p], "Beta2PowOut": [b2p]},
            attrs=attrs)

    def _op_attrs(self):
        return {"beta1": self._beta1, "beta2": self._beta2,
                "epsilon": self._epsilon}


class AdamWOptimizer(AdamOptimizer):
    type = "adamw"

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, weight_decay=0.01, **kw):
        super().__init__(learning_rate, beta1, beta2, epsilon, **kw)
        self._coeff = weight_decay

    def _op_attrs(self):
        attrs = super()._op_attrs()
        attrs["coeff"] = self._coeff
        return attrs


class LambOptimizer(AdamOptimizer):
    type = "lamb"

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01,
                 beta1=0.9, beta2=0.999, epsilon=1e-6, regularization=None,
                 grad_clip=None, exclude_from_weight_decay_fn=None, name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon,
                         regularization=regularization, grad_clip=grad_clip,
                         name=name)
        self._weight_decay = lamb_weight_decay
        self._exclude_fn = exclude_from_weight_decay_fn

    def _append_optimize_op(self, block, pg):
        p, g = pg
        m1 = self._get_accumulator("moment1", p)
        m2 = self._get_accumulator("moment2", p)
        b1p = self._get_accumulator("beta1_pow_acc", p)
        b2p = self._get_accumulator("beta2_pow_acc", p)
        wd = self._weight_decay
        if self._exclude_fn is not None and self._exclude_fn(p):
            wd = 0.0
        return block.append_op(
            type="lamb",
            inputs={"Param": [p], "Grad": [g],
                    "LearningRate": [self._param_lr(p)],
                    "Moment1": [m1], "Moment2": [m2],
                    "Beta1Pow": [b1p], "Beta2Pow": [b2p]},
            outputs={"ParamOut": [p], "Moment1Out": [m1], "Moment2Out": [m2],
                     "Beta1PowOut": [b1p], "Beta2PowOut": [b2p]},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon, "weight_decay": wd})


class AdagradOptimizer(Optimizer):
    type = "adagrad"

    def __init__(self, learning_rate, epsilon=1e-6, initial_accumulator_value=0.0,
                 regularization=None, grad_clip=None, name=None):
        super().__init__(learning_rate, regularization, grad_clip, name)
        self._epsilon = epsilon
        self._initial = initial_accumulator_value

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p, fill_value=self._initial)

    def _append_optimize_op(self, block, pg):
        p, g = pg
        m = self._get_accumulator("moment", p)
        return block.append_op(
            type="adagrad",
            inputs={"Param": [p], "Grad": [g], "Moment": [m],
                    "LearningRate": [self._param_lr(p)]},
            outputs={"ParamOut": [p], "MomentOut": [m]},
            attrs={"epsilon": self._epsilon})


class DecayedAdagradOptimizer(Optimizer):
    type = "decayed_adagrad"

    def __init__(self, learning_rate, decay=0.95, epsilon=1e-6,
                 regularization=None, grad_clip=None, name=None):
        super().__init__(learning_rate, regularization, grad_clip, name)
        self._decay, self._epsilon = decay, epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)

    def _append_optimize_op(self, block, pg):
        p, g = pg
        m = self._get_accumulator("moment", p)
        return block.append_op(
            type="decayed_adagrad",
            inputs={"Param": [p], "Grad": [g], "Moment": [m],
                    "LearningRate": [self._param_lr(p)]},
            outputs={"ParamOut": [p], "MomentOut": [m]},
            attrs={"decay": self._decay, "epsilon": self._epsilon})


class RMSPropOptimizer(Optimizer):
    type = "rmsprop"

    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, regularization=None, grad_clip=None,
                 name=None):
        super().__init__(learning_rate, regularization, grad_clip, name)
        self._rho, self._epsilon = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("mean_square", p)
            self._add_accumulator("mean_grad", p)
            self._add_accumulator("momentum", p)

    def _append_optimize_op(self, block, pg):
        p, g = pg
        return block.append_op(
            type="rmsprop",
            inputs={"Param": [p], "Grad": [g],
                    "MeanSquare": [self._get_accumulator("mean_square", p)],
                    "MeanGrad": [self._get_accumulator("mean_grad", p)],
                    "Moment": [self._get_accumulator("momentum", p)],
                    "LearningRate": [self._param_lr(p)]},
            outputs={"ParamOut": [p],
                     "MeanSquareOut": [self._get_accumulator("mean_square", p)],
                     "MeanGradOut": [self._get_accumulator("mean_grad", p)],
                     "MomentOut": [self._get_accumulator("momentum", p)]},
            attrs={"decay": self._rho, "epsilon": self._epsilon,
                   "momentum": self._momentum, "centered": self._centered})


class AdadeltaOptimizer(Optimizer):
    type = "adadelta"

    def __init__(self, learning_rate, epsilon=1e-6, rho=0.95,
                 regularization=None, grad_clip=None, name=None):
        super().__init__(learning_rate, regularization, grad_clip, name)
        self._rho, self._epsilon = rho, epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("avg_squared_grad", p)
            self._add_accumulator("avg_squared_update", p)

    def _append_optimize_op(self, block, pg):
        p, g = pg
        return block.append_op(
            type="adadelta",
            inputs={"Param": [p], "Grad": [g],
                    "AvgSquaredGrad": [self._get_accumulator("avg_squared_grad", p)],
                    "AvgSquaredUpdate": [self._get_accumulator("avg_squared_update", p)]},
            outputs={"ParamOut": [p],
                     "AvgSquaredGradOut": [self._get_accumulator("avg_squared_grad", p)],
                     "AvgSquaredUpdateOut": [self._get_accumulator("avg_squared_update", p)]},
            attrs={"rho": self._rho, "epsilon": self._epsilon})


class AdamaxOptimizer(Optimizer):
    type = "adamax"

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, regularization=None, grad_clip=None, name=None):
        super().__init__(learning_rate, regularization, grad_clip, name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)
            self._add_accumulator("inf_norm", p)
            self._add_accumulator("beta1_pow_acc", p, fill_value=self._beta1,
                                  shape=[1])

    def _append_optimize_op(self, block, pg):
        p, g = pg
        return block.append_op(
            type="adamax",
            inputs={"Param": [p], "Grad": [g],
                    "LearningRate": [self._param_lr(p)],
                    "Moment": [self._get_accumulator("moment", p)],
                    "InfNorm": [self._get_accumulator("inf_norm", p)],
                    "Beta1Pow": [self._get_accumulator("beta1_pow_acc", p)]},
            outputs={"ParamOut": [p],
                     "MomentOut": [self._get_accumulator("moment", p)],
                     "InfNormOut": [self._get_accumulator("inf_norm", p)],
                     "Beta1PowOut": [self._get_accumulator("beta1_pow_acc", p)]},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon})


class FtrlOptimizer(Optimizer):
    type = "ftrl"

    def __init__(self, learning_rate, l1=0.0, l2=0.0, lr_power=-0.5,
                 regularization=None, grad_clip=None, name=None):
        super().__init__(learning_rate, regularization, grad_clip, name)
        self._l1, self._l2, self._lr_power = l1, l2, lr_power

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("squared", p)
            self._add_accumulator("linear", p)

    def _append_optimize_op(self, block, pg):
        p, g = pg
        return block.append_op(
            type="ftrl",
            inputs={"Param": [p], "Grad": [g],
                    "SquaredAccumulator": [self._get_accumulator("squared", p)],
                    "LinearAccumulator": [self._get_accumulator("linear", p)],
                    "LearningRate": [self._param_lr(p)]},
            outputs={"ParamOut": [p],
                     "SquaredAccumOut": [self._get_accumulator("squared", p)],
                     "LinearAccumOut": [self._get_accumulator("linear", p)]},
            attrs={"l1": self._l1, "l2": self._l2,
                   "lr_power": self._lr_power})


class DpsgdOptimizer(Optimizer):
    type = "dpsgd"

    def __init__(self, learning_rate, clip=10.0, batch_size=16.0, sigma=1.0,
                 name=None):
        super().__init__(learning_rate, name=name)
        self._clip, self._batch_size, self._sigma = clip, batch_size, sigma

    def _append_optimize_op(self, block, pg):
        p, g = pg
        return block.append_op(
            type="dpsgd",
            inputs={"Param": [p], "Grad": [g],
                    "LearningRate": [self._param_lr(p)]},
            outputs={"ParamOut": [p]},
            attrs={"clip": self._clip, "batch_size": self._batch_size,
                   "sigma": self._sigma})


class RecomputeOptimizer(Optimizer):
    """Activation recomputation wrapper (ref: optimizer.py:4479).

    ``checkpoints`` mark segment boundaries; the executor lowers segments
    with ``jax.checkpoint`` (executor._segment_at_checkpoints)."""

    def __init__(self, optimizer):
        self._optimizer = optimizer
        self._checkpoints = None

    def _set_checkpoints(self, checkpoints):
        self._checkpoints = checkpoints

    def __getattr__(self, item):
        return getattr(self._optimizer, item)

    def backward(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, callbacks=None, checkpoints=None):
        # wrappers stacked on top (e.g. GradientMerge) reach the inner
        # optimizer through here; inject our checkpoints
        return self._optimizer.backward(
            loss, startup_program, parameter_list, no_grad_set, callbacks,
            checkpoints=checkpoints or self._checkpoints)

    def apply_gradients(self, params_grads):
        return self._optimizer.apply_gradients(params_grads)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        from .framework.core import program_guard
        with program_guard(loss.block.program,
                           startup_program or default_startup_program()):
            params_grads = self.backward(loss, startup_program,
                                         parameter_list, no_grad_set)
            opt_ops = self.apply_gradients(params_grads)
        return opt_ops, params_grads


class GradientMergeOptimizer(Optimizer):
    """Gradient accumulation over k micro-steps (ref: optimizer.py:4949).

    Accumulates grads into persistable buffers and applies the inner
    optimizer every ``k_steps`` runs, gated by lax.cond-free arithmetic
    (the update is multiplied by a 0/1 apply-mask, keeping the step a single
    static XLA program)."""

    def __init__(self, inner_optimizer, k_steps=1, avg=True):
        self._inner = inner_optimizer
        self.k_steps = k_steps
        self.avg = avg

    def __getattr__(self, item):
        return getattr(self._inner, item)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        from .framework.core import program_guard
        with program_guard(loss.block.program,
                           startup_program or default_startup_program()):
            return self._minimize_impl(loss, startup_program,
                                       parameter_list, no_grad_set)

    def _minimize_impl(self, loss, startup_program, parameter_list,
                       no_grad_set):
        main = default_main_program().global_block()
        startup = default_startup_program().global_block()
        params_grads = self._inner.backward(loss, startup_program,
                                            parameter_list, no_grad_set)
        # apply_mask = (step % k == 0)
        maskf, inv_mask = _periodic_mask(main, startup, self.k_steps, "gm")

        merged = []
        for p, g in params_grads:
            acc_name = unique_name.generate(f"{p.name}_gm_acc")
            acc = main.create_var(name=acc_name, shape=p.shape, dtype=p.dtype,
                                  persistable=True)
            sacc = startup.create_var(name=acc_name, shape=p.shape,
                                      dtype=p.dtype, persistable=True)
            startup.append_op(type="fill_constant", outputs={"Out": [sacc]},
                              attrs={"shape": list(p.shape), "dtype": p.dtype,
                                     "value": 0.0})
            main.append_op(type="sum", inputs={"X": [acc, g]},
                           outputs={"Out": [acc]})
            eff_name = unique_name.generate(f"{p.name}_gm_eff")
            eff = main.create_var(name=eff_name, shape=p.shape, dtype=p.dtype)
            scale = 1.0 / self.k_steps if self.avg else 1.0
            main.append_op(type="scale", inputs={"X": [acc]},
                           outputs={"Out": [eff]}, attrs={"scale": scale})
            merged.append((p, eff))
            # reset acc when applied: acc *= (1 - mask)
            main.append_op(type="elementwise_mul",
                           inputs={"X": [acc], "Y": [inv_mask]},
                           outputs={"Out": [acc]}, attrs={"axis": -1})

        # EXACT skip: the whole inner apply (params AND optimizer state —
        # Adam moments etc. must not decay on skip steps) runs inside one
        # lax.cond region, selected by step % k == 0 (ref: the reference
        # gates apply with a conditional_block the same way,
        # optimizer.py:4949 GradientMergeOptimizer._true_apply_gradients)
        from .layers.control_flow import cond as cond_layer
        from .layers import tensor_ops as T
        prog = default_main_program()
        gb = prog.global_block()
        pred = T.cast(maskf, "bool")
        written = []

        def true_fn():
            blk = prog.current_block()
            start = len(blk.ops)
            self._inner.apply_gradients(merged)
            seen = []
            for op in blk.ops[start:]:
                for n in op.output_names():
                    if n not in seen:
                        seen.append(n)
            written[:] = [n for n in seen
                          if n in gb.vars and gb.vars[n].persistable]
            return [gb.vars[n] for n in written]

        def false_fn():
            return [T.assign(gb.vars[n]) for n in written]

        outs = cond_layer(pred, true_fn, false_fn, name="gm_apply")
        opt_ops = []
        for n, o in zip(written, outs):
            opt_ops.append(main.append_op(
                type="assign", inputs={"X": [o]}, outputs={"Out": [n]}))
        return opt_ops, merged


class ShardedUpdateOptimizer(Optimizer):
    """ZeRO-1 sharded weight update (ref: "Automatic Cross-Replica
    Sharding of Weight Update in Data-Parallel Training",
    arXiv:2004.13336; the reference fleet's ``sharding`` stage-1).

    Rewrites data-parallel grad sync + optimizer apply from

        all_reduce(g);  p = update(p, g)            # every replica, full

    into

        g_shard = reduce_scatter(flat(g)) / n       # zero_reduce_scatter
        p_shard = slice(flat(p))                    # zero_shard_slice
        p_shard = update(p_shard, g_shard)          # inner optimizer op
        p       = all_gather(p_shard)               # zero_all_gather

    Optimizer accumulators are created at SHARD granularity (flat, padded
    to n·⌈numel/n⌉, ``dist_attr`` over the data axis) so each replica
    holds 1/n of the optimizer state — the ZeRO-1 memory saving — and the
    update math runs on 1/n of the elements.  Wire bytes match one
    all-reduce (reduce-scatter + all-gather).

    Composition rules:
      * only elementwise update rules may be sharded — LAMB/LARS need
        full-tensor norms and are rejected;
      * norm-based gradient clipping is rejected (a shard-local norm
        would clip each replica differently); ``GradientClipByValue``
        composes fine;
      * tensor-parallel params (``dist_attr`` set) keep the classic
        dense all-reduce + full update — ZeRO shards only the replicated
        params;
      * the flat 1/n state shards are ordinary persistables, so the
        prepared fast path (``Executor.prepare``) keeps them
        device-resident and donated between steps — checkpointing goes
        through io.save_*, which flushes via ``sync_prepared_state``
        before reading the scope (sharded state is never saved stale).
    """

    _ELEMENTWISE = {"sgd", "momentum", "adam", "adamw", "adagrad",
                    "decayed_adagrad", "rmsprop", "adadelta", "adamax",
                    "ftrl", "dpsgd"}

    def __init__(self, optimizer, nranks, axis_name="dp",
                 compress_dtype=None, quant_spec=None):
        base = getattr(optimizer, "type", None)
        if base not in self._ELEMENTWISE:
            raise ValueError(
                f"sharded_update: optimizer type {base!r} is not an "
                f"elementwise update rule (LAMB/LARS trust ratios need "
                f"full-tensor norms) — supported: "
                f"{sorted(self._ELEMENTWISE)}")
        self._inner = optimizer
        self._nranks = int(nranks)
        self._axes = tuple(axis_name) if isinstance(axis_name, (tuple, list)) \
            else (axis_name,)
        self._compress = compress_dtype
        # blockwise int8/int4 wire compression for the grad reduce-scatter
        # (quant_reduce_scatter; ops/quantize_wire.py).  The param
        # all-gather half stays full precision — it moves updated
        # WEIGHTS, whose error would accumulate step over step.
        from .ops.quantize_wire import CompressionSpec
        self._quant = CompressionSpec.from_attr(quant_spec)
        if self._quant is not None and self._quant.dtype == "bfloat16":
            self._compress, self._quant = "bfloat16", None

    def __getattr__(self, item):
        return getattr(self._inner, item)

    def backward(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, callbacks=None, checkpoints=None):
        return self._inner.backward(loss, startup_program, parameter_list,
                                    no_grad_set, callbacks, checkpoints)

    def _check_clip(self):
        from .clip import (get_gradient_clip, GradientClipByNorm,
                           GradientClipByGlobalNorm)
        clip = self._inner._grad_clip or get_gradient_clip()
        if isinstance(clip, (GradientClipByNorm, GradientClipByGlobalNorm)):
            raise NotImplementedError(
                "sharded_update: norm-based gradient clipping would use "
                "shard-local norms (each replica clips differently) — "
                "use GradientClipByValue or disable sharded_update")

    def apply_gradients(self, params_grads):
        self._check_clip()
        prog = default_main_program()
        block = prog.current_block()
        n = self._nranks
        data_axis = self._axes[0]
        axis_attr = self._axes if len(self._axes) > 1 else data_axis
        shard_pairs, gathers, plain = [], [], []
        # quantized grad scatter pads flat payloads so every rank's shard
        # is a whole number of quantization blocks — the param slice must
        # use the same alignment or param/grad shards would cover
        # different element ranges.  Unquantized shards align to 128, a
        # whole number of lanes: zero-padding is update-inert (0 grad
        # keeps 0 param/moments) and shard boundaries don't change the
        # math.
        if self._quant is not None:
            align = self._quant.block_size
        else:
            align = 128
        for p, g in params_grads:
            if getattr(p, "dist_attr", None) or \
                    getattr(p, "is_distributed", False):
                plain.append((p, g))
                continue
            numel = int(np.prod(p.shape)) if len(tuple(p.shape)) else 1
            padded = numel + (-numel % (n * align))
            gsh = block.create_var(
                name=unique_name.generate(f"{p.name}_grad_zshard"),
                shape=(padded,), dtype=p.dtype)
            scatter_attrs = {"ring_id": 0, "_axis_name": axis_attr,
                             "scale": 1.0 / n}
            if self._quant is not None:
                scatter_type = "quant_reduce_scatter"
                scatter_attrs["quant_spec"] = self._quant.to_attr()
            else:
                scatter_type = "zero_reduce_scatter"
                scatter_attrs["align"] = align
                if self._compress:
                    scatter_attrs["compress_dtype"] = self._compress
            block.append_op(
                type=scatter_type, inputs={"X": [g]},
                outputs={"Out": [gsh]}, attrs=scatter_attrs)
            psh = block.create_var(
                name=unique_name.generate(f"{p.name}_zshard"),
                shape=(padded,), dtype=p.dtype)
            # accumulators created from the shard var inherit this layout
            # (flat, sharded over the data axis) — the ZeRO-1 state shard
            psh.dist_attr = (data_axis,)
            psh.regularizer = getattr(p, "regularizer", None)
            psh.optimize_attrs = dict(getattr(p, "optimize_attrs", {}) or {})
            psh.trainable = True
            block.append_op(
                type="zero_shard_slice", inputs={"X": [p]},
                outputs={"Out": [psh]},
                attrs={"ring_id": 0, "_axis_name": data_axis,
                       **({"align": align} if align > 1 else {})})
            shard_pairs.append((psh, gsh))
            gathers.append((psh, p, numel))
        opt_ops = []
        if shard_pairs:
            opt_ops += self._inner.apply_gradients(shard_pairs)
        for psh, p, numel in gathers:
            opt_ops.append(block.append_op(
                type="zero_all_gather", inputs={"X": [psh]},
                outputs={"Out": [p]},
                attrs={"ring_id": 0, "_axis_name": data_axis,
                       "numel": numel, "shape": list(p.shape)}))
        if plain:
            # tp/ep-sharded params: classic mean-scale + dense all-reduce
            # over the data axes their shards do NOT cover, full update
            for p, g in plain:
                da = tuple(getattr(p, "dist_attr", None) or ())
                axes = tuple(a for a in self._axes if a not in da)
                block.append_op(type="scale", inputs={"X": [g]},
                                outputs={"Out": [g]},
                                attrs={"scale": 1.0 / n})
                if axes:
                    block.append_op(
                        type="c_allreduce_sum", inputs={"X": [g]},
                        outputs={"Out": [g]},
                        attrs={"ring_id": 0,
                               "_axis_name": axes if len(axes) > 1
                               else axes[0]})
            opt_ops += self._inner.apply_gradients(plain)
        return opt_ops

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        from .framework.core import program_guard
        with program_guard(loss.block.program,
                           startup_program or default_startup_program()):
            params_grads = self.backward(loss, startup_program,
                                         parameter_list, no_grad_set)
            opt_ops = self.apply_gradients(params_grads)
        return opt_ops, params_grads


def _persistable_scalar(main, startup, prefix, value=0.0):
    """Create a persistable (1,) float32 var in main+startup, startup-filled
    with ``value``.  Shared by every step-counter/accumulator below."""
    name = unique_name.generate(prefix)
    v = main.create_var(name=name, shape=(1,), dtype="float32",
                        persistable=True)
    sv = startup.create_var(name=name, shape=(1,), dtype="float32",
                            persistable=True)
    startup.append_op(type="fill_constant", outputs={"Out": [sv]},
                      attrs={"shape": [1], "dtype": "float32",
                             "value": float(value)})
    return v


def _step_counter(main, startup, prefix):
    """Persistable step counter incremented once per main-program run."""
    step = _persistable_scalar(main, startup, f"{prefix}_step")
    main.append_op(type="increment", inputs={"X": [step]},
                   outputs={"Out": [step]}, attrs={"step": 1.0})
    return step


def _periodic_mask(main, startup, k, prefix="pm"):
    """Append a persistable step counter + ``mask = (step % k == 0)`` ops;
    returns (maskf, inv_maskf) float32 (1,) vars.  Shared scaffolding for
    the k-periodic wrapper optimizers (GradientMerge, Lookahead)."""
    step = _step_counter(main, startup, prefix)
    modk = main.create_var(name=unique_name.generate(f"{prefix}_modk"),
                           shape=(1,), dtype="float32")
    main.append_op(type="elementwise_mod", inputs={
        "X": [step], "Y": [_const_var(main, startup, float(k))]},
        outputs={"Out": [modk]}, attrs={"axis": -1})
    mask = main.create_var(name=unique_name.generate(f"{prefix}_mask"),
                           shape=(1,), dtype="bool")
    main.append_op(type="equal", inputs={
        "X": [modk], "Y": [_const_var(main, startup, 0.0)]},
        outputs={"Out": [mask]})
    maskf = main.create_var(name=unique_name.generate(f"{prefix}_maskf"),
                            shape=(1,), dtype="float32")
    main.append_op(type="cast", inputs={"X": [mask]},
                   outputs={"Out": [maskf]},
                   attrs={"out_dtype": "float32"})
    inv = main.create_var(name=unique_name.generate(f"{prefix}_inv"),
                          shape=(1,), dtype="float32")
    main.append_op(type="scale", inputs={"X": [maskf]},
                   outputs={"Out": [inv]},
                   attrs={"scale": -1.0, "bias": 1.0})
    return maskf, inv


def _swap_context(executor, apply_program, restore_fn, need_restore):
    """Shared apply()/restore() contextmanager for the param-swapping
    averaging optimizers (ModelAverage, EMA)."""
    import contextlib

    @contextlib.contextmanager
    def _ctx():
        # the swap program reads params/accumulators through the scope —
        # flush any prepared fast-path state first (PreparedStep keeps the
        # training state device-resident between explicit sync points, so
        # averaged weights must not be computed from pre-training values)
        from .framework.executor import global_scope, sync_prepared_state
        sync_prepared_state(global_scope())
        executor.run(apply_program)
        try:
            yield
        finally:
            if need_restore:
                restore_fn(executor)
    return _ctx()


def _const_var(main, startup, value):
    name = unique_name.generate("const")
    v = main.create_var(name=name, shape=(1,), dtype="float32",
                        persistable=True)
    sv = startup.create_var(name=name, shape=(1,), dtype="float32",
                            persistable=True)
    startup.append_op(type="fill_constant", outputs={"Out": [sv]},
                      attrs={"shape": [1], "dtype": "float32",
                             "value": float(value)})
    return v


class DGCMomentumOptimizer(Optimizer):
    """Deep Gradient Compression momentum (ref: optimizer.py:1143
    DGCMomentumOptimizer; kernels operators/dgc_op.cc,
    details/sparse_all_reduce_op_handle.cc).

    The reference sparsifies gradients to save NCCL bandwidth; on TPU the
    allreduce rides ICI and stays dense, but the DGC *convergence semantics*
    (momentum correction, masked top-k updates, local residual accumulation,
    momentum factor masking) are reproduced exactly by the ``dgc_momentum``
    op.  ``num_trainers`` and the clip-norm knob are accepted for script
    compatibility."""

    type = "dgc_momentum"

    def __init__(self, learning_rate, momentum, rampup_begin_step,
                 rampup_step=1, sparsity=None, use_nesterov=False,
                 local_grad_clip_norm=None, num_trainers=None,
                 regularization=None, grad_clip=None, name=None):
        super().__init__(learning_rate, regularization, grad_clip, name)
        self._momentum = momentum
        self._use_nesterov = use_nesterov
        self._rampup_begin_step = rampup_begin_step
        self._rampup_step = rampup_step
        self._sparsity = list(sparsity or [0.999])
        self._step_var = None

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("u_velocity", p)
            self._add_accumulator("v_residual", p)
        if self._step_var is None:
            main = default_main_program().global_block()
            startup = default_startup_program().global_block()
            self._step_var = _persistable_scalar(main, startup, "dgc_step")

    def _append_optimize_op(self, block, pg):
        p, g = pg
        return block.append_op(
            type="dgc_momentum",
            inputs={"Param": [p], "Grad": [g],
                    "LearningRate": [self._param_lr(p)],
                    "U": [self._get_accumulator("u_velocity", p)],
                    "V": [self._get_accumulator("v_residual", p)],
                    "CurrentStep": [self._step_var]},
            outputs={"ParamOut": [p],
                     "UOut": [self._get_accumulator("u_velocity", p)],
                     "VOut": [self._get_accumulator("v_residual", p)]},
            attrs={"momentum": self._momentum,
                   "use_nesterov": self._use_nesterov,
                   "rampup_begin_step": float(self._rampup_begin_step),
                   "rampup_step": float(self._rampup_step),
                   "sparsity": self._sparsity})

    def apply_gradients(self, params_grads):
        opt_ops = super().apply_gradients(params_grads)
        block = default_main_program().global_block()
        block.append_op(type="increment", inputs={"X": [self._step_var]},
                        outputs={"Out": [self._step_var]},
                        attrs={"step": 1.0})
        return opt_ops


class ModelAverage(Optimizer):
    """Sliding-window parameter averaging (ref: optimizer.py:3069
    ModelAverage; op operators/optimizers/average_accumulates_op.h).

    Appends an ``average_accumulates`` op per parameter to the main program;
    ``apply()`` swaps parameters for their windowed average (inference-time
    weights), ``restore()`` swaps back.  Like the reference, apply/restore
    are standalone programs run against the shared scope."""

    def __init__(self, average_window_rate, min_average_window=10000,
                 max_average_window=10000, regularization=None, name=None):
        super().__init__(0.0, regularization, None, name)
        self.average_window = average_window_rate
        self.min_average_window = min_average_window
        self.max_average_window = max_average_window
        self._params = [
            v for v in default_main_program().global_block().vars.values()
            if isinstance(v, Parameter) and v.trainable]
        main = default_main_program().global_block()
        for p in self._params:
            self._add_accumulator("sum_1", p)
            self._add_accumulator("sum_2", p)
            self._add_accumulator("sum_3", p)
            self._add_accumulator("num_accumulates", p, shape=(1,),
                                  dtype="int32")
            self._add_accumulator("old_num_accumulates", p, shape=(1,),
                                  dtype="int32")
            self._add_accumulator("num_updates", p, shape=(1,),
                                  dtype="int32")
            acc = {n: self._get_accumulator(n, p) for n in
                   ("sum_1", "sum_2", "sum_3", "num_accumulates",
                    "old_num_accumulates", "num_updates")}
            main.append_op(
                type="average_accumulates",
                inputs={"param": [p],
                        "in_sum_1": [acc["sum_1"]],
                        "in_sum_2": [acc["sum_2"]],
                        "in_sum_3": [acc["sum_3"]],
                        "in_num_accumulates": [acc["num_accumulates"]],
                        "in_old_num_accumulates":
                            [acc["old_num_accumulates"]],
                        "in_num_updates": [acc["num_updates"]]},
                outputs={"out_sum_1": [acc["sum_1"]],
                         "out_sum_2": [acc["sum_2"]],
                         "out_sum_3": [acc["sum_3"]],
                         "out_num_accumulates": [acc["num_accumulates"]],
                         "out_old_num_accumulates":
                             [acc["old_num_accumulates"]],
                         "out_num_updates": [acc["num_updates"]]},
                attrs={"average_window": float(self.average_window),
                       "min_average_window": int(self.min_average_window),
                       "max_average_window": int(self.max_average_window)})
        self._apply_program, self._restore_program = self._build_swap()

    def _build_swap(self):
        from .framework.core import Program, program_guard
        apply_prog, restore_prog = Program(), Program()
        acc_names = {p.name: {n: self._get_accumulator(n, p).name
                              for n in ("sum_1", "sum_2", "sum_3",
                                        "num_accumulates",
                                        "old_num_accumulates")}
                     for p in self._params}
        with program_guard(apply_prog, Program()):
            blk = apply_prog.global_block()
            for p in self._params:
                names = acc_names[p.name]
                pv = blk.create_var(name=p.name, shape=p.shape,
                                    dtype=p.dtype, persistable=True)
                backup = blk.create_var(name=f"{p.name}@MA_BACKUP",
                                        shape=p.shape, dtype=p.dtype,
                                        persistable=True)
                blk.append_op(type="assign", inputs={"X": [pv]},
                              outputs={"Out": [backup]})
                sums = []
                for n in ("sum_1", "sum_2", "sum_3"):
                    sums.append(blk.create_var(
                        name=names[n], shape=p.shape, dtype=p.dtype,
                        persistable=True))
                total = blk.create_var(name=f"{p.name}@MA_SUM",
                                       shape=p.shape, dtype=p.dtype)
                blk.append_op(type="sum", inputs={"X": sums},
                              outputs={"Out": [total]})
                counts = []
                for n in ("num_accumulates", "old_num_accumulates"):
                    counts.append(blk.create_var(
                        name=names[n], shape=(1,), dtype="int32",
                        persistable=True))
                cnt = blk.create_var(name=f"{p.name}@MA_CNT", shape=(1,),
                                     dtype="int32")
                blk.append_op(type="sum", inputs={"X": counts},
                              outputs={"Out": [cnt]})
                cntf = blk.create_var(name=f"{p.name}@MA_CNTF", shape=(1,),
                                      dtype=p.dtype)
                blk.append_op(type="cast", inputs={"X": [cnt]},
                              outputs={"Out": [cntf]},
                              attrs={"out_dtype": p.dtype})
                one = blk.create_var(name=f"{p.name}@MA_ONE", shape=(1,),
                                     dtype=p.dtype)
                blk.append_op(type="fill_constant", outputs={"Out": [one]},
                              attrs={"shape": [1], "dtype": p.dtype,
                                     "value": 1.0})
                denom = blk.create_var(name=f"{p.name}@MA_DEN", shape=(1,),
                                       dtype=p.dtype)
                blk.append_op(type="elementwise_max",
                              inputs={"X": [cntf], "Y": [one]},
                              outputs={"Out": [denom]}, attrs={"axis": -1})
                blk.append_op(type="elementwise_div",
                              inputs={"X": [total], "Y": [denom]},
                              outputs={"Out": [pv]}, attrs={"axis": -1})
        with program_guard(restore_prog, Program()):
            blk = restore_prog.global_block()
            for p in self._params:
                pv = blk.create_var(name=p.name, shape=p.shape,
                                    dtype=p.dtype, persistable=True)
                backup = blk.create_var(name=f"{p.name}@MA_BACKUP",
                                        shape=p.shape, dtype=p.dtype,
                                        persistable=True)
                blk.append_op(type="assign", inputs={"X": [backup]},
                              outputs={"Out": [pv]})
        return apply_prog, restore_prog

    def apply(self, executor, need_restore=True):
        """Context manager swapping params for averaged values
        (ref: optimizer.py ModelAverage.apply)."""
        return _swap_context(executor, self._apply_program, self.restore,
                             need_restore)

    def restore(self, executor):
        executor.run(self._restore_program)


class ExponentialMovingAverage:
    """EMA of parameters (ref: optimizer.py:3378 ExponentialMovingAverage).

    ``update()`` appends ``ema = decay_t * ema + (1 - decay_t) * param`` ops
    to the main program (decay_t ramps as min(decay, (1+step)/(10+step))
    when ``thres_steps`` is given, matching the reference); ``apply()``
    swaps in bias-corrected EMA weights, ``restore()`` swaps back."""

    def __init__(self, decay=0.999, thres_steps=None, name=None):
        self._decay = decay
        self._thres_steps = thres_steps
        self._name = name or ""
        self._ema_vars = {}
        self._params = []
        self._step_var = None
        self._apply_program = None
        self._restore_program = None

    def update(self):
        main = default_main_program().global_block()
        startup = default_startup_program().global_block()
        self._params = [v for v in main.vars.values()
                        if isinstance(v, Parameter) and v.trainable]
        self._step_var = _step_counter(main, startup, "ema")
        # running ∏ decay_t for exact bias correction even when thres_steps
        # ramps the decay (apply divides by 1 - ∏decay_t)
        self._decay_prod = _persistable_scalar(main, startup,
                                               "ema_decay_prod", 1.0)
        # decay_t: constant, or ramped by the thres_steps variable
        if self._thres_steps is not None:
            t = self._thres_steps
            ramp = main.create_var(name=unique_name.generate("ema_ramp"),
                                   shape=(1,), dtype="float32")
            num = main.create_var(name=unique_name.generate("ema_num"),
                                  shape=(1,), dtype="float32")
            den = main.create_var(name=unique_name.generate("ema_den"),
                                  shape=(1,), dtype="float32")
            main.append_op(type="scale", inputs={"X": [t]},
                           outputs={"Out": [num]},
                           attrs={"scale": 1.0, "bias": 1.0})
            main.append_op(type="scale", inputs={"X": [t]},
                           outputs={"Out": [den]},
                           attrs={"scale": 1.0, "bias": 10.0})
            main.append_op(type="elementwise_div",
                           inputs={"X": [num], "Y": [den]},
                           outputs={"Out": [ramp]}, attrs={"axis": -1})
            decay_var = main.create_var(
                name=unique_name.generate("ema_decay"), shape=(1,),
                dtype="float32")
            cd = _const_var(main, startup, self._decay)
            main.append_op(type="elementwise_min",
                           inputs={"X": [ramp], "Y": [cd]},
                           outputs={"Out": [decay_var]}, attrs={"axis": -1})
        else:
            decay_var = _const_var(main, startup, self._decay)
        self._decay_var_name = decay_var.name
        main.append_op(type="elementwise_mul",
                       inputs={"X": [self._decay_prod], "Y": [decay_var]},
                       outputs={"Out": [self._decay_prod]},
                       attrs={"axis": -1})
        for p in self._params:
            ema_name = unique_name.generate(f"{p.name}_ema")
            ema = main.create_var(name=ema_name, shape=p.shape,
                                  dtype=p.dtype, persistable=True)
            sev = startup.create_var(name=ema_name, shape=p.shape,
                                     dtype=p.dtype, persistable=True)
            startup.append_op(type="fill_constant", outputs={"Out": [sev]},
                              attrs={"shape": list(p.shape),
                                     "dtype": p.dtype, "value": 0.0})
            self._ema_vars[p.name] = ema
            # ema = decay*ema + (1-decay)*param
            t1 = main.create_var(name=unique_name.generate("ema_t1"),
                                 shape=p.shape, dtype=p.dtype)
            main.append_op(type="elementwise_mul",
                           inputs={"X": [ema], "Y": [decay_var]},
                           outputs={"Out": [t1]}, attrs={"axis": -1})
            omd = main.create_var(name=unique_name.generate("ema_omd"),
                                  shape=(1,), dtype="float32")
            main.append_op(type="scale", inputs={"X": [decay_var]},
                           outputs={"Out": [omd]},
                           attrs={"scale": -1.0, "bias": 1.0})
            t2 = main.create_var(name=unique_name.generate("ema_t2"),
                                 shape=p.shape, dtype=p.dtype)
            main.append_op(type="elementwise_mul",
                           inputs={"X": [p], "Y": [omd]},
                           outputs={"Out": [t2]}, attrs={"axis": -1})
            main.append_op(type="elementwise_add",
                           inputs={"X": [t1], "Y": [t2]},
                           outputs={"Out": [ema]}, attrs={"axis": -1})
        self._apply_program, self._restore_program = self._build_swap()

    def _build_swap(self):
        from .framework.core import Program, program_guard
        apply_prog, restore_prog = Program(), Program()
        with program_guard(apply_prog, Program()):
            blk = apply_prog.global_block()
            # exact bias correction: factor = 1 - ∏decay_t (tracked by the
            # update ops; correct under thres_steps decay ramping too)
            prod = blk.create_var(name=self._decay_prod.name, shape=(1,),
                                  dtype="float32", persistable=True)
            factor = blk.create_var(name=unique_name.generate("ema_factor"),
                                    shape=(1,), dtype="float32")
            blk.append_op(type="scale", inputs={"X": [prod]},
                          outputs={"Out": [factor]},
                          attrs={"scale": -1.0, "bias": 1.0})
            for p in self._params:
                pv = blk.create_var(name=p.name, shape=p.shape,
                                    dtype=p.dtype, persistable=True)
                ema = blk.create_var(name=self._ema_vars[p.name].name,
                                     shape=p.shape, dtype=p.dtype,
                                     persistable=True)
                backup = blk.create_var(name=f"{p.name}@EMA_BACKUP",
                                        shape=p.shape, dtype=p.dtype,
                                        persistable=True)
                blk.append_op(type="assign", inputs={"X": [pv]},
                              outputs={"Out": [backup]})
                blk.append_op(type="elementwise_div",
                              inputs={"X": [ema], "Y": [factor]},
                              outputs={"Out": [pv]}, attrs={"axis": -1})
        with program_guard(restore_prog, Program()):
            blk = restore_prog.global_block()
            for p in self._params:
                pv = blk.create_var(name=p.name, shape=p.shape,
                                    dtype=p.dtype, persistable=True)
                backup = blk.create_var(name=f"{p.name}@EMA_BACKUP",
                                        shape=p.shape, dtype=p.dtype,
                                        persistable=True)
                blk.append_op(type="assign", inputs={"X": [backup]},
                              outputs={"Out": [pv]})
        return apply_prog, restore_prog

    def apply(self, executor, need_restore=True):
        return _swap_context(executor, self._apply_program, self.restore,
                             need_restore)

    def restore(self, executor):
        executor.run(self._restore_program)


class LookaheadOptimizer:
    """Lookahead wrapper (ref: optimizer.py:4788 LookaheadOptimizer):
    fast weights step with the inner optimizer every step; every ``k``
    steps the slow weights move ``alpha`` toward the fast weights and the
    fast weights reset to the slow weights.  The k-periodic swap is
    expressed with a 0/1 mask so the step stays one static XLA program."""

    def __init__(self, inner_optimizer, alpha=0.5, k=5):
        assert inner_optimizer is not None
        assert 0.0 <= alpha <= 1.0
        assert k >= 1 and isinstance(k, int)
        self.inner_optimizer = inner_optimizer
        self.alpha = alpha
        self.k = k
        self.type = "lookahead"

    def __getattr__(self, item):
        return getattr(self.inner_optimizer, item)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        from .framework.core import program_guard
        opt_ops, params_grads = self.inner_optimizer.minimize(
            loss, startup_program, parameter_list, no_grad_set)
        with program_guard(loss.block.program,
                           startup_program or default_startup_program()):
            self._append_lookahead(params_grads)
        return opt_ops, params_grads

    def _append_lookahead(self, params_grads):
        main = default_main_program().global_block()
        startup = default_startup_program().global_block()
        maskf, inv = _periodic_mask(main, startup, self.k, "la")
        for p, _ in params_grads:
            slow_name = unique_name.generate(f"{p.name}_slow")
            slow = main.create_var(name=slow_name, shape=p.shape,
                                   dtype=p.dtype, persistable=True)
            sslow = startup.create_var(name=slow_name, shape=p.shape,
                                       dtype=p.dtype, persistable=True)
            # slow weights start equal to the initialised fast weights
            startup.append_op(type="assign", inputs={"X": [p.name]},
                              outputs={"Out": [sslow]})
            # slow' = slow + mask*alpha*(fast - slow)
            diff = main.create_var(name=unique_name.generate("la_diff"),
                                   shape=p.shape, dtype=p.dtype)
            main.append_op(type="elementwise_sub",
                           inputs={"X": [p], "Y": [slow]},
                           outputs={"Out": [diff]}, attrs={"axis": -1})
            scaled = main.create_var(name=unique_name.generate("la_sc"),
                                     shape=p.shape, dtype=p.dtype)
            main.append_op(type="scale", inputs={"X": [diff]},
                           outputs={"Out": [scaled]},
                           attrs={"scale": float(self.alpha)})
            masked = main.create_var(name=unique_name.generate("la_msk"),
                                     shape=p.shape, dtype=p.dtype)
            main.append_op(type="elementwise_mul",
                           inputs={"X": [scaled], "Y": [maskf]},
                           outputs={"Out": [masked]}, attrs={"axis": -1})
            main.append_op(type="elementwise_add",
                           inputs={"X": [slow], "Y": [masked]},
                           outputs={"Out": [slow]}, attrs={"axis": -1})
            # fast' = mask*slow' + (1-mask)*fast
            t1 = main.create_var(name=unique_name.generate("la_t1"),
                                 shape=p.shape, dtype=p.dtype)
            main.append_op(type="elementwise_mul",
                           inputs={"X": [slow], "Y": [maskf]},
                           outputs={"Out": [t1]}, attrs={"axis": -1})
            t2 = main.create_var(name=unique_name.generate("la_t2"),
                                 shape=p.shape, dtype=p.dtype)
            main.append_op(type="elementwise_mul",
                           inputs={"X": [p], "Y": [inv]},
                           outputs={"Out": [t2]}, attrs={"axis": -1})
            main.append_op(type="elementwise_add",
                           inputs={"X": [t1], "Y": [t2]},
                           outputs={"Out": [p]}, attrs={"axis": -1})


class LocalSGDOptimizer:
    """Local SGD (ref: transpiler/collective.py:270 LocalSGD,
    fleet/meta_optimizers/localsgd_optimizer.py): workers step locally
    (no per-step grad allreduce) and every ``k_steps`` the parameters are
    averaged across the data-parallel axis.  The averaging is a masked
    ``c_allreduce_sum`` + divide, which lowers to an XLA AllReduce over ICI
    under the executor's shard_map; on a single device it is identity."""

    def __init__(self, inner_optimizer, k_steps=1, begin_step=1,
                 axis_name="dp"):
        self.inner_optimizer = inner_optimizer
        self.k_steps = k_steps
        self.begin_step = begin_step
        self.axis_name = axis_name
        self.type = "localsgd"

    def __getattr__(self, item):
        return getattr(self.inner_optimizer, item)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        from .framework.core import program_guard
        opt_ops, params_grads = self.inner_optimizer.minimize(
            loss, startup_program, parameter_list, no_grad_set)
        with program_guard(loss.block.program,
                           startup_program or default_startup_program()):
            self._append_avg(params_grads)
        return opt_ops, params_grads

    def _append_avg(self, params_grads):
        main = default_main_program().global_block()
        startup = default_startup_program().global_block()
        step = _step_counter(main, startup, "localsgd")
        params = [p for p, _ in params_grads]
        main.append_op(
            type="local_sgd_sync",
            inputs={"Step": [step], "Params": params},
            outputs={"Out": params},
            attrs={"k_steps": float(self.k_steps),
                   "begin_step": float(self.begin_step),
                   "ring_id": 0, "_axis_name": self.axis_name})


# public aliases matching the reference's exports (optimizer.py bottom)
SGD = SGDOptimizer
Momentum = MomentumOptimizer
Adam = AdamOptimizer
AdamW = AdamWOptimizer
Adamax = AdamaxOptimizer
Adagrad = AdagradOptimizer
DecayedAdagrad = DecayedAdagradOptimizer
Adadelta = AdadeltaOptimizer
RMSProp = RMSPropOptimizer
Ftrl = FtrlOptimizer
Lamb = LambOptimizer
LarsMomentum = LarsMomentumOptimizer
Dpsgd = DpsgdOptimizer
