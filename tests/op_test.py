"""OpTest harness — the analog of the reference's numeric-checking op test
base (ref: python/paddle/fluid/tests/unittests/op_test.py:170).

A test declares op type, numpy inputs, attrs, and expected outputs computed
in numpy; ``check_output`` runs the single op through a tiny Program on the
executor and compares.  ``check_grad`` compares the executor's autodiff
grads (vjp over the lowered block, the analog of grad-op makers) against
central finite differences (ref: op_test.py:57 get_numeric_gradient)."""

from __future__ import annotations

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.framework.core import (Program, program_guard,
                                       reset_default_programs)
from paddle_tpu.framework.backward import append_backward


class OpTest:
    op_type: str = ""

    def _build_program(self, inputs, attrs, output_slots):
        main = Program()
        startup = Program()
        with program_guard(main, startup):
            block = main.global_block()
            in_map = {}
            feed = {}
            for slot, arrs in inputs.items():
                arrs = arrs if isinstance(arrs, list) else [arrs]
                names = []
                for i, a in enumerate(arrs):
                    a = np.asarray(a)
                    name = f"{slot.lower()}_{i}"
                    block.create_var(name=name, shape=a.shape,
                                     dtype=str(a.dtype), stop_gradient=False)
                    feed[name] = a
                    names.append(name)
                in_map[slot] = names
            out_map = {}
            out_vars = {}
            for slot, n in output_slots.items():
                names = []
                for i in range(n):
                    name = f"out_{slot.lower()}_{i}"
                    v = block.create_var(name=name, shape=(), dtype="float32")
                    names.append(name)
                    out_vars.setdefault(slot, []).append(v)
                out_map[slot] = names
            block.append_op(type=self.op_type, inputs=in_map,
                            outputs=out_map, attrs=attrs or {})
        return main, startup, feed, out_vars

    def check_output(self, inputs, attrs, expected_outputs, atol=1e-5,
                     rtol=1e-5):
        """expected_outputs: {slot: np_array or [np_arrays]}"""
        output_slots = {}
        for slot, v in expected_outputs.items():
            output_slots[slot] = len(v) if isinstance(v, list) else 1
        main, startup, feed, out_vars = self._build_program(
            inputs, attrs, output_slots)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            fetch = [v for vs in out_vars.values() for v in vs]
            results = exe.run(main, feed=feed, fetch_list=fetch)
        idx = 0
        for slot, exp in expected_outputs.items():
            exps = exp if isinstance(exp, list) else [exp]
            for e in exps:
                got = results[idx]
                idx += 1
                np.testing.assert_allclose(
                    got, np.asarray(e), atol=atol, rtol=rtol,
                    err_msg=f"{self.op_type} output slot {slot}")
        return results

    def check_grad(self, inputs, attrs, output_slot, grad_input_slots,
                   delta=1e-3, atol=1e-3, rtol=1e-3, out_index=0):
        """Compare autodiff grads vs central finite differences w.r.t. the
        sum of ``output_slot[out_index]``."""
        output_slots = {output_slot: out_index + 1}
        main, startup, feed, out_vars = self._build_program(
            inputs, attrs, output_slots)
        with program_guard(main, startup):
            block = main.global_block()
            out = out_vars[output_slot][out_index]
            # scalar target: reduce_sum of the output
            target = block.create_var(name="grad_target", shape=(),
                                      dtype="float32")
            block.append_op(type="reduce_sum", inputs={"X": [out]},
                            outputs={"Out": [target]},
                            attrs={"dim": [], "keep_dim": False,
                                   "reduce_all": True})
            grad_names = []
            wrt = []
            for slot in grad_input_slots:
                for i in range(len(inputs[slot]
                                   if isinstance(inputs[slot], list)
                                   else [inputs[slot]])):
                    wrt.append(f"{slot.lower()}_{i}")
            block.append_op(
                type="backward",
                inputs={"Loss": [target]},
                outputs={"Grads": [n + "@GRAD" for n in wrt]},
                attrs={"loss_name": "grad_target", "param_names": wrt,
                       "checkpoints": None, "loss_scale": 1.0})
            for n in wrt:
                block.create_var(name=n + "@GRAD", shape=feed[n].shape,
                                 dtype=str(feed[n].dtype))
                grad_names.append(n + "@GRAD")

        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            analytic = exe.run(main, feed=feed, fetch_list=grad_names)

        # numeric: central differences on a scalar function of each
        # input — ONE forward program and executor for every evaluation
        # (a fresh build per evaluation recompiled 2x per element)
        exe2 = fluid.Executor(fluid.CPUPlace())
        s2 = fluid.Scope()
        main2, startup2, _, out_vars2 = self._build_program(
            inputs, attrs, {output_slot: out_index + 1})
        with fluid.scope_guard(s2):
            exe2.run(startup2)

        def run_sum(feed_over):
            with fluid.scope_guard(s2):
                r = exe2.run(main2, feed=feed_over,
                             fetch_list=[out_vars2[output_slot][out_index]])
            return float(np.sum(r[0]))

        for gi, name in enumerate(wrt):
            base = feed[name].astype(np.float64)
            numeric = np.zeros_like(base)
            flat = base.reshape(-1)
            num_flat = numeric.reshape(-1)
            for j in range(flat.size):
                f2 = {k: v.copy() for k, v in feed.items()}
                fp = flat.copy()
                fp[j] += delta
                f2[name] = fp.reshape(base.shape).astype(feed[name].dtype)
                up = run_sum(f2)
                fm = flat.copy()
                fm[j] -= delta
                f2[name] = fm.reshape(base.shape).astype(feed[name].dtype)
                down = run_sum(f2)
                num_flat[j] = (up - down) / (2 * delta)
            np.testing.assert_allclose(
                analytic[gi], numeric, atol=atol, rtol=rtol,
                err_msg=f"{self.op_type} grad w.r.t. {name}")


def make_op_test(op_type_):
    t = OpTest()
    t.op_type = op_type_
    return t
