"""The engine's own tracing: named scopes on the round's phases (HLO
``op_name`` metadata), ``fl.*`` host spans on the profiler's clock, and
the ``retraces`` counter of chunk programs built."""
import glob
import os
import re
import sys

import jax
import pytest

from repro.configs.base import RAgeKConfig
from repro.data.federated import paper_mnist_split
from repro.data.synthetic import mnist_like
from repro.fl import FederatedEngine
from repro.launch import fl_train

PHASES = ("local_phase", "candidate_report", "selection", "aggregation",
          "global_update")
M = 4
HP = dict(r=30, k=6, H=2, M=M, lr=2e-3, batch_size=16, method="rage_k")


@pytest.fixture(scope="module")
def federation():
    (xtr, ytr), test = mnist_like(n_train=1200, n_test=400, seed=0)
    return paper_mnist_split(xtr, ytr, seed=0), test


def make_engine(federation, **kw):
    shards, test = federation
    return FederatedEngine("mlp", shards, test,
                           RAgeKConfig(**{**HP, **kw}), seed=0)


def op_names(hlo_text: str) -> set:
    return set(re.findall(r'op_name="([^"]*)"', hlo_text))


def scoped(names: set, scope: str) -> bool:
    """Some op_name path holds ``scope`` as a component, bare or wrapped
    in transforms (``vmap(candidate_report)``)."""
    comp = re.compile(rf"(^|/)(\w+\()*{scope}\)*(/|$)")
    return any(comp.search(n) for n in names)


@pytest.mark.parametrize("candidates", ["threshold", "sort"])
def test_compiled_round_and_eval_carry_every_scope(federation, candidates):
    e = make_engine(federation, candidates=candidates)
    fn = e._chunk(2)
    chunk = op_names(fn.func.lower(e._data, e._pack(), **fn.keywords)
                     .compile().as_text())
    for scope in PHASES:
        assert scoped(chunk, scope), scope
    assert not scoped(chunk, "eval")
    ev = op_names(e._eval.lower(e.params_s, e.state_s).compile().as_text())
    assert scoped(ev, "eval")
    e.close()


def host_lines(trace_dir: str) -> list:
    """Each host thread's ``fl.*`` spans as (name, start_ns, end_ns)."""
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                     for ev in line.events if ev.name.startswith("fl.")]
            if spans:
                out.append(spans)
    return out


def within(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_run_scanned_writes_nested_host_spans(federation, tmp_path):
    e = make_engine(federation)
    e.run_scanned(M, eval_every=M)                  # compile outside
    with jax.profiler.trace(str(tmp_path)):
        e.run_scanned(M, eval_every=M)              # ends on a recluster
    e.close()
    lines = host_lines(str(tmp_path))
    main, = [ln for ln in lines if any(n == "fl.chunk" for n, *_ in ln)]
    by = {}
    for sp in main:
        by.setdefault(sp[0], []).append(sp)
    chunk, = by["fl.chunk"]
    for name in ("fl.dispatch", "fl.device_wait", "fl.host_stop"):
        span, = by[name]
        assert within(span, chunk), name
    assert "fl.retrace" not in by                   # nothing new to build
    stop, = by["fl.host_stop"]
    for name in ("fl.metrics_pull", "fl.bookkeep", "fl.eval"):
        span, = by[name]
        assert within(span, stop), name
    assert by["fl.dispatch"][0][2] <= by["fl.device_wait"][0][1] \
        <= by["fl.device_wait"][0][2] <= stop[1]
    # the recluster runs on the worker thread, on its own line
    workers = [ln for ln in lines if ln is not main]
    assert any(n == "fl.recluster.compute" for ln in workers
               for n, *_ in ln)
    assert not any(n == "fl.recluster.compute" for n, *_ in main)


def test_a_new_chunk_program_is_a_retrace_span(federation, tmp_path):
    e = make_engine(federation)
    with jax.profiler.trace(str(tmp_path)):
        e.run_scanned(2, eval_every=2)
    e.close()
    main, = [ln for ln in host_lines(str(tmp_path))
             if any(n == "fl.chunk" for n, *_ in ln)]
    names = [n for n, *_ in main]
    assert names.count("fl.retrace") == 1 and "fl.dispatch" not in names


def test_retraces_count_new_packing_once(federation):
    e = make_engine(federation)
    keys = [(e._num_seg, e._max_seg)]
    e.run_scanned(M, eval_every=M)
    assert e.retraces == {"length": 1, "packing": 0}
    for _ in range(5):
        # the bounds the next call's chunk is built for (the recluster
        # that ended the last call was joined by its eval)
        keys.append((e._num_seg, e._max_seg))
        before = dict(e.retraces)
        e.run_scanned(M, eval_every=M)
        new = keys[-1] not in keys[:-1]
        assert e.retraces == {"length": 1,
                              "packing": before["packing"] + int(new)}
    # the clustering merged clients at least once, and only the calls
    # that met new bounds counted
    assert len(set(keys)) >= 2
    assert e.retraces["packing"] == len(set(keys)) - 1
    # one compiled text per chunk program built, then the eval program's
    texts = e.program_texts()
    assert len(texts) == len(set(keys)) + 1
    for text in texts[:-1]:
        assert all(scoped(op_names(text), s) for s in PHASES)
    assert scoped(op_names(texts[-1]), "eval")
    e.close()


def test_a_new_chunk_length_counts_as_length(federation):
    e = make_engine(federation, M=100)              # no recluster
    e.run_scanned(2, eval_every=2)
    e.run_scanned(1, eval_every=1)
    e.run_scanned(2, eval_every=2)
    assert e.retraces == {"length": 2, "packing": 0}
    e.close()


def test_fl_train_profile_dir_writes_spans_and_program_texts(
        monkeypatch, tmp_path, capsys):
    monkeypatch.setattr(sys, "argv", [
        "fl_train", "--n-train", "600", "--rounds", "2",
        "--profile-dir", str(tmp_path)])
    cache_on = jax.config.jax_enable_compilation_cache
    try:
        fl_train.main()                 # compiles with the cache off
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
    names = {n for line in host_lines(str(tmp_path)) for n, *_ in line}
    assert {"fl.chunk", "fl.retrace", "fl.device_wait", "fl.host_stop",
            "fl.eval"} <= names
    # two one-round chunks of one length, and the eval program
    assert len(list(tmp_path.glob("program*.hlo.txt"))) == 2
    assert "chunk programs built: {'length': 1, 'packing': 0}" \
        in capsys.readouterr().out
