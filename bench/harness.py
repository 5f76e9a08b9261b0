"""The benchmark's cells: discovery from ``BENCHMARK.json``, the federation
each cell drives, its set-up, its measured window, its traced window and
its check against the plain reference.

A cell names a configuration (``bench/configs/<config>.json`` with its
plain reference ``<config>.py`` beside it) and a traffic mix
(``bench/traffic/<mix>.json``); its limits are ``bench/limits/<cell>.json``
and each per-layer metric is read by ``bench/metrics/<metric>.py``. Adding
any of them is adding files and entries: nothing here names a cell.

A mix gives the job's cadence: ``eval_every`` rounds per
``FederatedEngine.run_scanned`` call (one scan chunk and one host stop,
as ``fl_train`` cuts a run), ``overrides`` of the configuration's protocol,
and how many calls set-up makes (``warm_calls``), how many of them the
reference follows (``check_calls``) and how many the traced run records
(``trace_calls``).
"""
from __future__ import annotations

import gc
import json
import math
import tempfile
import time
from pathlib import Path

import jax
import numpy as np

import compare
import synth
import trace_reduce
import work
from peaks import peak
from reference import Reference, Trajectory, check_plan, load_module

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


# ---------------------------------------------------------------------------
# discovery
# ---------------------------------------------------------------------------

def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(spec: dict, workload: str, bench: Path = BENCH) -> dict:
    """Everything one cell needs, found by the names in ``spec`` (the
    parsed ``BENCHMARK.json``)."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg = load_json(bench.parent / configs[cell["config"]]["file"])
    mix = load_json(bench / "traffic" / f"{cell['traffic']}.json")

    def applies(m):
        return workload in m.get("workloads", [workload])

    return {
        "name": workload,
        "chips": cell["chips"],
        "config": cfg,
        "model": bench / "configs" / f"{cell['config']}.py",
        "mix": mix,
        "limits": load_json(bench / "limits" / f"{workload}.json"),
        "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
        "per_layer": [dict(m, reader=bench / "metrics" / f"{m['name']}.py")
                      for m in spec["per_layer"] if applies(m)],
    }


def protocol(cell: dict) -> dict:
    return {**cell["config"]["protocol"], **cell["mix"].get("overrides", {})}


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def build_engine(cell: dict, shards: list, test: tuple, seed: int):
    """The engine ``repro.launch.fl_train.build_engine`` builds for this
    federation, on the benchmark's own data."""
    from repro.configs.base import RAgeKConfig
    from repro.fl import FederatedEngine

    eng = cell["config"]["engine"]
    return FederatedEngine(
        cell["config"]["model"], shards, test, RAgeKConfig(**protocol(cell)),
        seed=seed, ef=eng["ef"], global_opt=eng["global_opt"],
        aggregate_impl=eng["aggregate_impl"], selection=eng["selection"],
        compute=eng["compute"])


def split_clients(tree, n: int) -> list:
    return [jax.tree_util.tree_map(lambda a: np.asarray(a[i]), tree)
            for i in range(n)]


def snapshot(engine, picks: list, params0) -> Trajectory:
    """The program's trajectory so far, copied to the host. The request
    counts come from ``freq_matrix``, which either age layout gives (the
    hierarchical one keeps them on the host); the age rows are keyed by
    cluster in both, so a client's ages are its cluster's row."""
    labels = engine.cluster_of
    return Trajectory(
        picks=list(picks), labels=[labels], params0=params0,
        params=jax.device_get(engine.g_params),
        global_mu=jax.device_get(engine.g_opt_state.mu),
        client_mu=split_clients(jax.device_get(engine.opt_s.mu), engine.n),
        client_ages=np.asarray(engine.age.cluster_age)[labels],
        freq=np.array(engine.freq_matrix))


class CompileCounter:
    """Counts XLA compilations (and loads from the persistent cache) while
    ``on``."""

    def __init__(self):
        self.on, self.names = False, []
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **kw):
        if self.on and event == BACKEND_COMPILE:
            self.names.append(kw.get("fun_name", "?"))

    def close(self):
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(self)


class Run:
    """One run of one cell: set-up, then a measured or a traced window,
    then the check."""

    def __init__(self, cell: dict, seed: int, t0: float):
        self.cell, self.seed, self.t0 = cell, seed, t0
        self.mix = cell["mix"]
        self.proto = protocol(cell)
        check_plan(self.proto)
        self.every = self.mix["eval_every"]
        M = self.proto["M"]
        if M % self.every and self.every % M:
            raise ValueError("eval_every and M must divide one another, so "
                             "that every call is one scan chunk")
        self.compiles = CompileCounter()

    # -- set-up ------------------------------------------------------------
    def setup(self):
        """Data, engine, and ``warm_calls`` calls of the window's own
        ``run_scanned``; the state after ``check_calls`` of them is kept
        on the host for the check."""
        mix = self.mix
        self.phases = {"start": time.perf_counter() - self.t0}
        self.shards, self.test = synth.federation_data(self.cell["config"],
                                                       self.seed)
        self.phases["data"] = time.perf_counter() - self.t0
        self.engine = build_engine(self.cell, self.shards, self.test,
                                   self.seed)
        params0 = jax.device_get(self.engine.g_params)
        self.phases["engine"] = time.perf_counter() - self.t0
        picks, self.check_losses = [], []
        for call in range(mix["warm_calls"]):
            with jax.profiler.TraceAnnotation("bench.warm"):
                res = self.engine.run_scanned(self.every,
                                              eval_every=self.every)
            self.phases[f"call{call + 1}"] = time.perf_counter() - self.t0
            if call < mix["check_calls"]:
                picks += res.requested
                self.check_losses += res.loss
            if call + 1 == mix["check_calls"]:
                self.prog = snapshot(self.engine, picks, params0)
        self.setup_s = time.perf_counter() - self.t0

    def _call(self):
        with jax.profiler.TraceAnnotation("bench.call"):
            return self.engine.run_scanned(self.every, eval_every=self.every)

    # -- measured window ---------------------------------------------------
    def window(self, seconds: float) -> dict:
        """Calls until ``seconds`` have passed (to the nearer call end):
        rounds completed over the wall time of all of them."""
        rounds = failed = 0
        self.compiles.on = True
        start = time.perf_counter()
        while True:
            t = time.perf_counter()
            res = self._call()
            end = time.perf_counter()
            rounds += len(res.n_active)
            failed += sum(1 for q in res.n_quarantined if q)
            if end - start + (end - t) / 2 >= seconds:
                break
        self.compiles.on = False
        return {"rounds": rounds, "failed": failed, "wall_s": end - start}

    # -- traced window -----------------------------------------------------
    def traced(self) -> tuple:
        """``trace_calls`` calls under the profiler: (reduced trace, rounds,
        recluster wait in the window, participants summed over its
        rounds)."""
        calls = self.mix["trace_calls"]
        wait0 = self.engine.recluster_wait_s
        rounds = active = 0
        self.compiles.on = True
        with tempfile.TemporaryDirectory(prefix="bench-trace-") as d:
            jax.profiler.start_trace(d)
            try:
                with jax.profiler.TraceAnnotation("bench.window"):
                    for _ in range(calls):
                        n_active = self._call().n_active
                        rounds += len(n_active)
                        active += sum(n_active)
            finally:
                jax.profiler.stop_trace()
            self.compiles.on = False
            summary = trace_reduce.reduce(trace_reduce.load_xplane(d),
                                          "bench.window")
        return (summary, rounds, self.engine.recluster_wait_s - wait0,
                active)

    def memory_stats(self) -> dict:
        return jax.devices()[0].memory_stats() or {}

    # -- the check ---------------------------------------------------------
    def check(self, explain: bool = False):
        """Free the program's state, follow the checked rounds with the
        plain reference and compare; with ``explain``, also the worst leaf
        behind each leaf gap."""
        self.engine.close()
        del self.engine
        gc.collect()
        t = time.perf_counter()
        ref = follow(self.cell, self.shards, self.seed)
        numbers = compare.gaps(self.prog, ref, checked_evals(self.cell),
                               self.check_losses)
        self.check_s = time.perf_counter() - t
        return (numbers, compare.explain(self.prog, ref)) if explain \
            else numbers


def checked_evals(cell: dict) -> list:
    """The evaluation rounds that end the checked calls."""
    every = cell["mix"]["eval_every"]
    return [every * (c + 1) for c in range(cell["mix"]["check_calls"])]


def follow(cell: dict, shards: list, seed: int, dtype=None,
           precision: str = "highest", make=Reference) -> Trajectory:
    """The plain reference over the cell's checked rounds; ``dtype``
    bfloat16 makes it the control, ``make`` a subclass with a fault
    planted."""
    import jax.numpy as jnp
    model = load_module(cell["model"], f"ref_{cell['config']['name']}")
    return make(cell["config"], protocol(cell), model, shards, seed,
                dtype=dtype or jnp.float32, precision=precision
                ).run(checked_evals(cell)[-1])


def stand_in_gaps(cell: dict, got: Trajectory, ref: Trajectory,
                  explain: bool = False):
    """The numbers of a reference run put in the program's place (the
    control, a witness, a planted fault) against the reference."""
    rounds = checked_evals(cell)
    losses = [compare.participant_mean(got.losses[t - 1]) for t in rounds]
    numbers = compare.gaps(got, ref, rounds, losses)
    return (numbers, compare.explain(got, ref)) if explain else numbers


def control_gaps(cell: dict, shards: list, seed: int,
                 explain: bool = False):
    """The numbers the control reads: the reference in bfloat16 in the
    program's place, against the reference."""
    import jax.numpy as jnp
    ref = follow(cell, shards, seed)
    return stand_in_gaps(cell, follow(cell, shards, seed, jnp.bfloat16), ref,
                         explain)


def per_layer(cell: dict, summary, rounds: int, stall_s: float,
              device_kind: str, active: int | None = None) -> dict:
    """Each per-layer metric of the cell that its reader finds something
    to read for, with its unit. ``active`` is the participants summed over
    the window's rounds (every client in every round where None): the
    readers' ``n`` is the participants of a round, whose gradients the
    report and the aggregation handle, and ``samples`` counts the batches
    they train at the batch size the shards allow."""
    proto = protocol(cell)
    cfg = cell["config"]
    if active is None:
        active = rounds * len(cfg["clients"])
    bs = min(proto["batch_size"], min(synth.shard_lengths(cfg)))
    ctx = {"trace": summary, "rounds": rounds, "config": cfg,
           "protocol": proto, "d": cfg["n_params"],
           "n": active / rounds if rounds else len(cfg["clients"]),
           "samples": active * proto["H"] * bs,
           "recluster_wait_s": stall_s, "work": work,
           "peak": peak(device_kind) if summary else None}
    out = {}
    for m in cell["per_layer"]:
        value = load_module(m["reader"], f"metric_{m['name']}").read(ctx)
        if value is not None and math.isfinite(value):
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
