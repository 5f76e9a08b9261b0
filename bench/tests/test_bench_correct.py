"""``correct`` on the CPU, at a size a test run holds: a sound run passes
its limits; the control (the reference in bfloat16 in the program's place)
and each fault planted in the timed path fail them.

The cell is ``bench_testkit.tiny_bench``'s MNIST federation, held to the
limits of ``mnist_mlp.paper_fig3``. On the CPU the program computes in
float32 as the reference does, so a sound run reads 0 on every number."""
import time

import jax
import jax.numpy as jnp
import pytest

import bench_testkit as kit
import compare
import run
from repro.fl import client, engine


def run_tiny(tmp_path, monkeypatch, seconds="0.5"):
    bench = kit.tiny_bench(tmp_path)
    monkeypatch.setattr(run, "CACHE_DIR", tmp_path / "jax_cache")
    return run.run(["--workload", kit.TINY, "--seed", str(2**31 + 3),
                    "--seconds", seconds, "--trace", "0"],
                   require_chip=False, t0=time.perf_counter(), bench=bench)


def test_sound_run_is_correct(tmp_path, monkeypatch):
    out = run_tiny(tmp_path, monkeypatch)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"rounds_per_s", "setup_s"}
    assert list(out)[-1] == "checks"
    assert "cluster_mismatch" in out["checks"]


def unchanged_state(g_opt, unflatten, g_sum, g_params, g_opt_state):
    return g_params, g_opt_state


def half_batch_loss(logits, labels):
    h = logits.shape[0] // 2
    logp = jax.nn.log_softmax(logits[:h])
    return -jnp.mean(jnp.take_along_axis(logp, labels[:h, None], axis=1))


def shifted_picks(select):
    def wrapped(*a, **kw):
        out = select(*a, **kw)
        d = kw["d"]
        shift = lambda i: jnp.where(i < d, (i + 1) % d, i)
        return (shift(out[0]),) + out[1:]
    return wrapped


FAULTS = {
    "state_unchanged": ("apply_global", lambda: unchanged_state),
    "half_batch": ("softmax_xent", lambda: half_batch_loss),
    "picks_altered": ("rage_select_segmented",
                      lambda: shifted_picks(engine.rage_select_segmented)),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_in_the_timed_path_is_not_correct(fault, tmp_path,
                                                monkeypatch):
    name, make = FAULTS[fault]
    module = client if name == "softmax_xent" else engine
    monkeypatch.setattr(module, name, make())
    out = run_tiny(tmp_path, monkeypatch, seconds="0.2")
    assert not out["correct"], out["checks"]


def test_control_is_not_correct(tmp_path):
    """The reference in bfloat16 against the reference in float32, over
    the tiny cell's checked rounds."""
    bench = kit.tiny_bench(tmp_path)
    cell = run.harness.resolve(kit.load(tmp_path / "BENCHMARK.json"),
                               kit.TINY, bench)
    shards, _ = run.harness.synth.federation_data(cell["config"], 11)
    ok, checks = compare.judge(run.harness.control_gaps(cell, shards, 11),
                               cell["limits"])
    assert not ok, checks
