"""What a CPU can check of chip_smoke.py's contract: the dry run walks
every leg of the script at tiny width, and without a TPU the script
exits non-zero and prints no result.  (That the legs pass ON the chip is
the chip run's job; CHANGES.md records it.)  Plus the two start-up
helpers the chip entry points rely on."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(*args, **env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run([sys.executable, SCRIPT, *args], env=env,
                          cwd=REPO, capture_output=True, text=True,
                          timeout=600)


def test_cpu_dry_run_walks_every_leg():
    p = _run("--cpu-dry-run")
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["ok"] is True
    assert result["device"]["platform"] == "cpu"
    summary = lines[-2]
    assert summary.startswith("chip_smoke summary:")
    assert "platform: cpu" in summary
    for leg in "KABHC":
        assert f"leg {leg}: passed" in summary, summary


def test_without_a_tpu_it_fails_and_prints_no_result():
    p = _run(JAX_PLATFORMS="cpu")
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert '"ok"' not in p.stdout


def test_compile_cache_helper_sets_one_fixed_path(monkeypatch):
    import jax
    from paddle_tpu import flags
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: calls.append((key, value)))
    # the env var wins and no path is set in code
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert flags.enable_compile_cache() == "/some/dir"
    assert "jax_compilation_cache_dir" not in dict(calls)
    # otherwise: the same in-checkout path on every call
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    del calls[:]
    want = os.path.join(REPO, ".jax_cache")
    assert flags.enable_compile_cache() == want
    assert flags.enable_compile_cache() == want
    assert [v for k, v in calls
            if k == "jax_compilation_cache_dir"] == [want, want]


def test_overlap_flags_go_to_libtpu_init_args():
    from paddle_tpu import flags
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    added = flags.apply_overlap_xla_flags(env)
    assert list(added) == list(flags.OVERLAP_XLA_FLAGS)
    assert env["LIBTPU_INIT_ARGS"].split() == list(flags.OVERLAP_XLA_FLAGS)
    # XLA_FLAGS aborts start-up on a --xla_tpu_* flag: never touched
    assert env["XLA_FLAGS"] == "--xla_force_host_platform_device_count=8"
    assert flags.apply_overlap_xla_flags(env) == []
