"""Python-free PJRT serving loader.

The serving bundle written by ``save_compiled_inference_model`` must be
loadable by the C loader (native/src/pjrt_serve.cc) through the PJRT C
API with no Python/JAX/protobuf at serve time.  On this CPU CI host no
CPU PJRT plugin .so ships, so the END-TO-END run needs a chip
(tools/serve_demo.py with libtpu's plugin); here we assert everything up
to the plugin boundary: the loader BUILDS, the bundle is complete and self-consistent,
and the manifest matches the module calling convention.
"""

import os
import struct
import subprocess

import numpy as np
import pytest

import paddle_tpu.fluid as fluid


@pytest.fixture(scope="module")
def bundle(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("serve_bundle"))
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4], dtype="float32")
        h = fluid.layers.fc(x, 8, act="relu", name="serve_fc1")
        y = fluid.layers.fc(h, 3, act="softmax", name="serve_fc2")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        from paddle_tpu.framework.export import \
            save_compiled_inference_model
        save_compiled_inference_model(
            d, ["x"], [y], exe, {"x": np.ones((2, 4), np.float32)},
            main_program=main, scope=scope)
    return d


def test_bundle_complete(bundle):
    for f in ("compiled.stablehlo", "module.mlir.bc", "manifest.json",
              "serve_manifest.txt", "state.npz"):
        assert os.path.exists(os.path.join(bundle, f)), f
    # manifest args match the bin files and the module's calling
    # convention (kept vars only)
    lines = open(os.path.join(bundle, "serve_manifest.txt")
                 ).read().splitlines()
    args = [l.split() for l in lines if l.startswith("arg ")]
    outs = [l.split() for l in lines if l.startswith("out ")]
    assert args and outs
    for a in args:
        idx, kind, name, dtype, nd = a[1], a[2], a[3], a[4], int(a[5])
        dims = [int(x) for x in a[6:6 + nd]]
        p = os.path.join(bundle, "args", f"{idx}.bin")
        assert os.path.exists(p), p
        nbytes = np.dtype(dtype).itemsize * int(np.prod(dims or [1]))
        assert os.path.getsize(p) == nbytes, (p, dims, dtype)
    # the module bytecode really is MLIR (bytecode files start "MLïR")
    head = open(os.path.join(bundle, "module.mlir.bc"), "rb").read(4)
    assert head[:2] == b"ML", head


def test_loader_builds():
    from paddle_tpu.native.build import pjrt_serve_path
    exe = pjrt_serve_path()
    assert os.path.exists(exe) and os.access(exe, os.X_OK)
    # wrong usage exits 2 with usage text — proves the binary runs
    p = subprocess.run([exe], capture_output=True, text=True)
    assert p.returncode == 2 and "usage" in p.stderr


def test_loader_rejects_bad_bundle(tmp_path):
    from paddle_tpu.native.build import pjrt_serve_path
    exe = pjrt_serve_path()
    p = subprocess.run([exe, "/nonexistent/plugin.so", str(tmp_path)],
                       capture_output=True, text=True)
    assert p.returncode == 1
    assert "serve_manifest" in p.stderr


def test_end_to_end_with_plugin_if_available(bundle):
    plugin = os.environ.get("PJRT_PLUGIN_PATH")
    if not plugin or not os.path.exists(plugin):
        pytest.skip("no PJRT plugin .so on this host (the hardware leg "
                    "is tools/serve_demo.py)")
    from paddle_tpu.native.build import pjrt_serve_path
    exe = pjrt_serve_path()
    p = subprocess.run([exe, plugin, bundle], capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "PJRT_SERVE_OK" in p.stdout
