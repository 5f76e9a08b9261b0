"""Device time by the round's named scopes, host time by the engine's
``fl.*`` spans, and idle gaps labelled by the innermost span, on
hand-built traces whose device events carry a scope path."""
import pytest

import bench_testkit as kit
import scopes
import trace_reduce as tr

MS = 1_000_000  # ns
ROUND = "jit(chunk)/while/body/closed_call"


def ev(name, start_ms, dur_ms, *path):
    return [name, start_ms * MS, dur_ms * MS, *path]


def trace(ops, spans, worker=()):
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [ev("jit(chunk)", 0, 100)]},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": spans},
            {"name": "recluster_0", "events": list(worker)}]},
    ]


OPS = [
    ev("while.2", 0, 60, "jit(chunk)/while"),
    ev("fusion.1", 2, 20, f"{ROUND}/local_phase/jit(phase)/vmap()/mul"),
    ev("sort.4", 22, 6, f"{ROUND}/local_phase/jit(phase)/"
       "vmap(candidate_report)/jit(sort)/sort"),
    ev("fusion.7", 28, 4, f"{ROUND}/transpose(jvp(local_phase))/dot"),
    ev("segmented_age_topk.1", 32, 3,
       f"{ROUND}/selection/jit(rage_select_segmented)/pallas_call"),
    ev("sparse_aggregate", 35, 5, f"{ROUND}/aggregation/pallas_call"),
    ev("add_fusion", 40, 1, f"{ROUND}/global_update/add"),
    ev("copy.9", 41, 1, ROUND),
    ev("reduce.3", 42, 1),
    ev("fusion.2", 80, 10, "jit(_eval_impl)/eval/dot_general"),
]
SPANS = [
    ev("bench.window", 0, 100), ev("bench.call", 0, 100),
    ev("fl.chunk", 0, 100), ev("fl.dispatch", 0, 1),
    ev("fl.device_wait", 1, 42), ev("fl.host_stop", 43, 57),
    ev("fl.metrics_pull", 43, 2), ev("fl.bookkeep", 45, 20),
    ev("fl.eval", 78, 14), ev("fl.recluster.join", 92, 6),
]
WORKER = [ev("fl.recluster.compute", 43, 50)]


def test_device_time_goes_to_the_innermost_listed_scope():
    s = scopes.reduce(trace(OPS, SPANS, WORKER), "bench.window")
    ms = {k: round(v * 1e3, 9) for k, v in s["scope_s"].items()}
    # sort under vmap(candidate_report) is the report's, not the local
    # phase's; the backward pass's transpose(jvp(...)) is the phase's;
    # the while container counts nowhere; unscoped ops read ""
    assert ms == {"local_phase": 24, "candidate_report": 6, "selection": 3,
                  "aggregation": 5, "global_update": 1, "eval": 10, "": 2}
    assert scopes.phase_cover(s["scope_s"]) == pytest.approx(39 / 41)
    ops = {k: [[n, round(v * 1e3, 9)] for n, v in top]
           for k, top in s["scope_ops"].items()}
    assert ops["local_phase"] == [["fusion", 24]]
    assert ops["candidate_report"] == [["sort", 6]]
    assert ops[""] == [["copy", 1], ["reduce", 1]]


@pytest.mark.parametrize("path,scope", [
    ("a/local_phase/b", "local_phase"),
    ("a/local_phase/jit(phase)/candidate_report/top_k", "candidate_report"),
    ("a/local_phase/jit(phase)/vmap(candidate_report)/sort",
     "candidate_report"),
    ("a/transpose(jvp(local_phase))/dot", "local_phase"),
    ("jit(_eval_impl)/eval/dot", "eval"),
    ("a/local_phase_x/b", ""),
    ("jit(chunk)/while/body", ""),
    ("", ""),
])
def test_innermost_scope_matches_bare_and_wrapped_components(path, scope):
    assert scopes.innermost_scope(path) == scope


def test_fl_spans_are_counted_and_timed_inside_the_window():
    s = scopes.reduce(trace(OPS, SPANS, WORKER), "bench.window")
    got = {k: (v["count"], round(v["s"] * 1e3, 9))
           for k, v in s["span_s"].items()}
    assert got == {"fl.chunk": (1, 100), "fl.dispatch": (1, 1),
                   "fl.device_wait": (1, 42), "fl.host_stop": (1, 57),
                   "fl.metrics_pull": (1, 2), "fl.bookkeep": (1, 20),
                   "fl.eval": (1, 14), "fl.recluster.join": (1, 6),
                   "fl.recluster.compute": (1, 50)}


def idle_ms(s):
    return {k: round(v * 1e3, 9) for k, v in s["idle_by_span"].items()}


def test_idle_time_goes_to_the_innermost_span_at_each_instant():
    # [60, 80]: bookkeep [45, 65] is innermost to 65, host_stop to 78,
    # eval after; [90, 100]: eval to 92, the join to 98, host_stop after
    s = scopes.reduce(trace(OPS, SPANS), "bench.window")
    assert idle_ms(s) == {"fl.bookkeep": 5, "fl.host_stop": 15,
                          "fl.eval": 4, "fl.recluster.join": 6}
    # each of the longest gaps carries the label that holds most of it
    assert s["idle_gaps"] == [["fl.host_stop", pytest.approx(0.020)],
                              ["fl.recluster.join", pytest.approx(0.010)]]
    # the worker thread's spans are not what the driver was doing
    w = scopes.reduce(trace(OPS, SPANS, WORKER), "bench.window")
    assert (w["idle_by_span"], w["idle_gaps"]) \
        == (s["idle_by_span"], s["idle_gaps"])
    s = scopes.reduce(trace(OPS, SPANS + [ev("fl.eval", 50, 30)]),
                      "bench.window")
    assert idle_ms(s)["fl.eval"] == 17
    assert s["idle_gaps"][0] == ["fl.eval", pytest.approx(0.020)]


def test_idle_between_calls_goes_to_the_window():
    spans = [ev("bench.window", 0, 100), ev("bench.call", 0, 70),
             ev("bench.call", 75, 25)]
    planes = trace(OPS, spans)
    s = scopes.reduce(planes, "bench.window")
    assert idle_ms(s) == {"bench.call": 25, "bench.window": 5}
    whole = tr.reduce(scopes.plain(planes), "bench.window")
    assert sum(s["idle_by_span"].values()) == pytest.approx(
        whole["window_s"] - whole["busy_s"])


def test_a_program_without_scopes_or_spans_reads_unscoped():
    """The parent program: three-element events, no fl.* span."""
    ops = [o[:3] for o in OPS]
    spans = [ev("bench.window", 0, 100), ev("bench.call", 0, 100)]
    s = scopes.reduce(trace(ops, spans), "bench.window")
    assert set(s["scope_s"]) == {""}
    assert s["span_s"] == {}
    assert s["idle_by_span"] and not any(
        k.startswith("fl.") for k in s["idle_by_span"])
    assert scopes.phase_cover(s["scope_s"]) == 0.0
    assert scopes.phase_cover({}) is None


def test_nothing_to_read_without_a_device_plane_or_a_window():
    planes = trace(OPS, SPANS)
    assert scopes.reduce(planes, "bench.missing") is None
    assert scopes.reduce(planes[1:], "bench.window") is None


def test_plain_cuts_the_scope_path_and_keeps_the_rest():
    planes = trace(OPS, SPANS, WORKER)
    cut = scopes.plain(planes)
    assert all(len(e) == 3 for p in cut for ln in p["lines"]
               for e in ln["events"])
    s = tr.reduce(cut, "bench.window")
    assert s["busy_s"] == pytest.approx(0.070)
    assert s["op_s"]["sort"] == pytest.approx(0.006)


def test_op_paths_read_the_compiled_text():
    """A TPU op event names its HLO instruction; the compiled text gives
    the instruction's op_name, and so its scope."""
    import jax
    import jax.numpy as jnp

    def chunk(x):
        def body(c, _):
            with jax.named_scope("local_phase"):
                y = jnp.sin(c) * 2
            with jax.named_scope("global_update"):
                return c + y, y.sum()
        return jax.lax.scan(body, x, None, length=3)

    text = jax.jit(chunk).lower(jnp.ones(8)).compile().as_text()
    paths = scopes.op_paths([text])
    assert paths and {m for m, _ in paths} == {"jit_chunk"}
    found = {scopes.innermost_scope(p) for p in paths.values()}
    assert {"local_phase", "global_update"} <= found
    (module, op), path = next((k, p) for k, p in paths.items()
                              if scopes.innermost_scope(p) == "local_phase")
    modules = [["jit_chunk(5312106733150293116)", 100, 50],
               ["jit__eval_impl(1888614934102720968)", 160, 10]]
    starts = [m[1] for m in modules]
    event = [f"%{op} = f32[8]{{0}} fusion(...)", 120, 1]
    assert scopes.scope_path(event, modules, starts, paths) == path
    # outside the module's run, or an instruction the text lacks: nothing
    assert scopes.scope_path([event[0], 155, 1], modules, starts,
                             paths) is None
    assert scopes.scope_path(["%nowhere.1 = f32[]", 120, 1], modules,
                             starts, paths) is None


def test_recorded_tpu_trace_with_scopes():
    """A slice of a real TPU trace of the scoped program: the last 5.5 ms
    of a chunk's device ops and the host stop after it, each op carrying
    the op_name the compiled text gave it."""
    rec = kit.load(kit.BENCH / "tests" / "data" / "tpu_trace_scopes.json")
    s = scopes.reduce(rec["planes"], "bench.window")
    for scope in ("local_phase", "candidate_report", "aggregation",
                  "selection", "global_update", "eval"):
        assert s["scope_s"][scope] > 0, scope
    assert scopes.phase_cover(s["scope_s"]) > 0.9
    assert {"fl.device_wait", "fl.host_stop", "fl.metrics_pull",
            "fl.eval"} <= set(s["span_s"])
    # the host stop's idle time is put down to its parts
    idle = s["idle_by_span"]
    assert idle["fl.metrics_pull"] > idle["fl.host_stop"]
    assert sum(v for k, v in idle.items() if k.startswith("fl.")
               and k != "fl.chunk") > 0.9 * sum(idle.values())
    # the benchmark's own reduction reads it as before
    whole = tr.reduce(scopes.plain(rec["planes"]), "bench.window")
    assert sum(idle.values()) == pytest.approx(
        whole["window_s"] - whole["busy_s"])
