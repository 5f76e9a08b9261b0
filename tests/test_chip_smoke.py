"""chip_smoke.py on the CPU: it refuses to run without a TPU, and its main
routine runs end to end at a tiny size when a test stands in for the
chip (the MNIST federation in place of the CIFAR one, and four virtual
CPU devices for the four-chip exchange)."""
import importlib.util
import json
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
TINY = ["--dataset", "mnist", "--n-train", "600", "--M", "2",
        "--aggregate", "pallas", "--seed", "0"]


def _load():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fake_chip(chips):
    return {"platform": "cpu", "kind": "cpu", "count": len(jax.devices())}


def test_chip_smoke_refuses_cpu(capsys):
    cs = _load()
    with pytest.raises(SystemExit) as exc:
        cs.main([])
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_chip_smoke_main_tiny(monkeypatch, capsys):
    cs = _load()
    monkeypatch.setattr(cs, "require_tpu", _fake_chip)
    # the kernels run in interpret mode here, which compiles no TPU
    # custom calls; tests/test_tpu_compile.py covers their TPU compile
    monkeypatch.setattr(cs, "assert_kernels_compiled", lambda hlo: None)
    monkeypatch.setattr(cs, "FEDERATION", TINY)
    # the cache placement is tested on its own; leave the worker's config
    monkeypatch.setattr(cs, "use_compile_cache", lambda: None)
    cs.main([])
    out = capsys.readouterr().out.splitlines()
    assert json.loads(out[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu",
                               "count": 1}}
    assert sum(line.startswith("round ") for line in out) == cs.ROUNDS
    assert sum("recluster:" in line for line in out) == 2
    assert any(line.startswith("check round 5: pallas vs jnp")
               for line in out)


def test_chip_smoke_exchange_on_four_devices():
    """The --chips 4 phase on four virtual CPU devices (a fresh process:
    the device count is fixed when JAX starts)."""
    code = ("import sys, jax; sys.path.insert(0, sys.argv[1]); "
            "import chip_smoke as cs; "
            "cs.require_tpu = lambda chips: {'platform': 'cpu', "
            "'kind': 'cpu', 'count': len(jax.devices())}; "
            "cs.main(['--chips', '4'])")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    run = subprocess.run([sys.executable, "-c", code, ROOT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stderr[-4000:]
    last = json.loads(run.stdout.splitlines()[-1])
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": 4}}


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_placement(monkeypatch, tmp_path, env_dir):
    """$JAX_COMPILATION_CACHE_DIR wins and nothing else is set; without
    it the cache goes to the one fixed directory in the checkout."""
    from repro.launch import compile_cache
    was = jax.config.jax_compilation_cache_dir
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / env_dir))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.use_compile_cache()
        if env_dir:
            assert got == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir == was
        else:
            want = os.path.realpath(os.path.join(ROOT, ".jax_cache"))
            assert got == want
            assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
