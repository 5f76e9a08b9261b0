"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python bench/calibrate.py --workload cifar_cnn.paper_h100 \
        --seeds 1 2 3 --control-seeds 1 2 3 --fault-seeds 1 2 3 \
        --witness-seeds 1 2 3

For each ``--seeds`` seed: the program's first ``check_calls`` calls of the
cell against the plain reference (the lower readings). For each
``--control-seeds`` seed: the control, the reference in bfloat16 in the
program's place (the upper readings). For each ``--fault-seeds`` seed: the
reference with a fault planted, in the program's place: half of each batch
left out of the loss, and every requested index moved to its neighbour
where selection produces it; where the cell's clients take part in turns,
also each round's participants moved to the next client id, the ages of a
client that sits a round out reset at the round's picks, and its Adam
first moment decayed as by a step. For each ``--witness-seeds`` seed: the
reference with its products at the default precision (one bfloat16 pass,
as the program's) in the program's place, a second sound run that shows
how far two precisions part on their own. Every reading is judged against
the cell's limits as a run judges the program; one JSON line per reading
on standard output, with ``correct``. The benchmark's own runs do not run
this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import run  # sets the import paths and the chip's environment
from reference import B1

harness = run.harness
compare = run.compare


class HalfBatch(harness.Reference):
    """The reference with half of each batch left out, the loss the mean
    over the rest."""

    def _loss(self, params, bn, x, y):
        h = x.shape[0] // 2
        return super()._loss(params, bn, x[:h], y[:h])


class AlteredPicks(harness.Reference):
    """The reference with every requested index moved to its neighbour."""

    def select(self, cands, ids):
        picks = super().select(cands, ids)
        return np.where(picks < self.d, (picks + 1) % self.d, picks)


class ShiftedPlan(harness.Reference):
    """Each round's participants moved to the next client id."""

    def plan(self):
        return np.roll(super().plan(), 1)


class IdleAgesReset(harness.Reference):
    """The cluster ages of a client that sits the round out reset at the
    round's picks."""

    def select(self, cands, ids):
        picks = super().select(cands, ids)
        picked = np.unique(picks[picks < self.d])
        for i in sorted(set(range(self.n)) - set(ids.tolist())):
            self.ages[int(self.cluster_of[i])][picked] = 0
        return picks


class IdleAdamStep(harness.Reference):
    """The Adam first moment of a client that sits the round out decays as
    by a step with no gradient."""

    def step(self):
        idle = np.nonzero(~self.plan())[0]
        super().step()
        for i in idle:
            count, mu, nu = self.client_opt[i]
            self.client_opt[i] = (count, jax.tree_util.tree_map(
                lambda m: m * B1, mu), nu)


FAULTS = {"half_batch": HalfBatch, "picks_altered": AlteredPicks}
PARTICIPATION_FAULTS = {"plan_shifted": ShiftedPlan,
                        "idle_ages_reset": IdleAgesReset,
                        "idle_adam_step": IdleAdamStep}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    for kind in ("seeds", "control-seeds", "fault-seeds", "witness-seeds"):
        ap.add_argument(f"--{kind}", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    spec = harness.load_json(run.ROOT / "BENCHMARK.json")
    cell = harness.resolve(spec, args.workload)
    run.use_compile_cache()
    device = run.require_chips(cell["chips"])
    cell["mix"]["warm_calls"] = cell["mix"]["check_calls"]

    def emit(kind, seed, t, numbers, worst=None, **extra):
        ok, _ = compare.judge(numbers, cell["limits"])
        print(json.dumps(dict(kind=kind, seed=seed, correct=ok, gaps=numbers,
                              worst=worst, seconds=time.perf_counter() - t,
                              device=device, **extra)), flush=True)

    for seed in args.seeds:
        t = time.perf_counter()
        r = harness.Run(cell, seed, t)
        r.setup()
        numbers, worst = r.check(explain=True)
        r.compiles.close()
        emit("program", seed, t, numbers, worst, phases=r.phases)
    stand_ins = {"control": (args.control_seeds, dict(dtype=jnp.bfloat16)),
                 "witness": (args.witness_seeds, dict(precision="default"))}
    faults = dict(FAULTS)
    if harness.protocol(cell).get("schedule", "full") != "full":
        faults.update(PARTICIPATION_FAULTS)
    stand_ins.update({name: (args.fault_seeds, dict(make=make))
                      for name, make in faults.items()})
    for seed in sorted({s for seeds, _ in stand_ins.values() for s in seeds}):
        shards, _ = harness.synth.federation_data(cell["config"], seed)
        ref = harness.follow(cell, shards, seed)
        for kind, (seeds, kw) in stand_ins.items():
            if seed in seeds:
                t = time.perf_counter()
                got = harness.follow(cell, shards, seed, **kw)
                numbers, worst = harness.stand_in_gaps(cell, got, ref,
                                                       explain=True)
                emit(kind, seed, t, numbers, worst)
    sys.stdout.flush()


if __name__ == "__main__":
    main()
