"""The scope report rehearses on the CPU at a tiny size: set-up as a run
makes it, the traced calls, the engine's chunk programs counted."""
import bench_testkit as kit  # noqa: I001  (import paths, CPU first)
import jax
import scope_report


def test_scope_report_rehearses_on_the_cpu(tmp_path):
    bench = kit.tiny_bench(tmp_path)
    cache_on = jax.config.jax_enable_compilation_cache
    try:
        out = scope_report.main(["--workload", kit.TINY,
                                 "--seed", "2147483659"],
                                require_chip=False, bench=bench)
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
    # a chunk program the engine built in the window compiled there
    assert out["compiles_in_window"] >= sum(out["retraces"].values())
    assert out["device"]["platform"] == "cpu"
    assert out["rounds"] == 2                   # one call of eval_every 2
    assert set(out["retraces"]) == {"length", "packing"}
    assert out["retraces"]["length"] == 0       # set-up built the length
    # a CPU trace has no device plane: nothing by scope or span
    assert "scope_s" not in out and "span_s" not in out


def test_an_engine_without_the_counter_reads_none():
    class Parent:
        pass

    assert scope_report.retraces_of(Parent()) is None
    eng = Parent()
    eng.retraces = {"length": 1, "packing": 2}
    assert scope_report.retraces_of(eng) == {"length": 1, "packing": 2}
