"""Python-free serving demo on real hardware.

Exports a BERT-tiny classification artifact cross-lowered for TPU, then
serves it through the C PJRT loader (native/src/pjrt_serve.cc) against a
PJRT plugin exporting ``GetPjrtApi`` — no Python in the serving process.
This process pins its own JAX to the CPU, so the loader child is the one
process that takes the chip.

  python tools/serve_demo.py <plugin.so> [out_dir]

For the TPU the plugin is libtpu's:
  python -c "import libtpu; print(libtpu.get_library_path())"
"""

import os
import subprocess
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

def main():
    if len(sys.argv) < 2:
        raise SystemExit(__doc__)
    PLUGIN = sys.argv[1]
    OUT = sys.argv[2] if len(sys.argv) > 2 else "/tmp/pjrt_serve_bundle"
    # export happens on CPU (cross-lowering — no chip needed); only the C
    # loader touches the TPU
    os.environ["JAX_PLATFORMS"] = "cpu"
    import paddle_tpu.fluid as fluid
    from paddle_tpu.framework.export import save_compiled_inference_model
    from paddle_tpu.models import bert

    cfg = bert.BertConfig.tiny()
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        feeds, total, mlm, nsp = bert.build_pretrain_network(cfg,
                                                             is_test=True)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    batch = bert.make_fake_batch(rng, cfg, batch_size=2, seq_len=64,
                                 num_masks=4)
    with fluid.scope_guard(scope):
        exe.run(startup)
        save_compiled_inference_model(
            OUT, sorted(batch), [total], exe, batch, main_program=main_p,
            scope=scope, platforms=("tpu",))
    print(f"exported TPU serving bundle to {OUT}")

    from paddle_tpu.native.build import pjrt_serve_path
    loader = pjrt_serve_path()
    print(f"loader: {loader}; plugin: {PLUGIN}")
    p = subprocess.run([loader, PLUGIN, OUT], capture_output=True,
                       text=True, timeout=900)
    sys.stdout.write(p.stdout)
    sys.stderr.write(p.stderr[-2000:])
    if p.returncode != 0 or "PJRT_SERVE_OK" not in p.stdout:
        raise SystemExit(f"serve demo failed rc={p.returncode}")
    print("SERVE_DEMO_OK (python-free PJRT serving on TPU)")


if __name__ == "__main__":
    main()
