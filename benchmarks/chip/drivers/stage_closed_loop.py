"""Closed loop over a cohort of scans through the stages of the mix's
``dag``: each scan goes through the DAG, one ``LocalRunner.run`` per stage
unit, the next scan only once the last has committed.

Mix parameters: ``dag`` (stage names), ``cohort_scans`` (drawn from the
seed), ``warmup_scans`` (drawn from ``warmup_seed``, fixed, so a stage that
compiles per volume finds their programs in the persistent cache after a
checkout's first run) and ``check_scans`` (how many committed scans the
check compares with the reference). Each scan is a session of the
protocols the DAG's stages take as input, at the configuration's shapes.
The window ends when the last scan started before the deadline has
committed every stage, or early, with a note, if the cohort runs out.
"""
from __future__ import annotations

import gc
import shutil
import time
from typing import Dict, List

import numpy as np

from chipbench import generator
from chipbench.harness import (SetupError, load_module, log, read_outputs,
                               timed, timed_pipeline)


class Driver:
    def __init__(self, run):
        self.run = run
        self.dag = list(run.cell.traffic["dag"])
        self.spans: List[tuple] = []

    def _ref(self, stage: str):
        return load_module(self.run.cell.bench_dir / "reference"
                           / f"{stage}.py")

    def setup(self):
        from repro.core import (LocalRunner, builtin_pipelines,
                                generate_jobs, ingest_directory,
                                write_raw_dump)
        tr, run = self.run.cell.traffic, self.run
        pipes = builtin_pipelines()
        unknown = [s for s in self.dag if s not in pipes]
        if unknown:
            raise SetupError(f"the program has no stage {unknown}")
        protos = sorted({p for s in self.dag
                         for p in pipes[s].spec.required_suffixes})
        missing = [p for p in protos if p not in run.scans]
        if missing:
            raise SetupError(f"{run.cell.name}: the configuration states no "
                             f"{missing} scan for {self.dag}")
        scans = {p: run.scans[p] for p in protos}
        rngs = (generator.child_rngs(tr["warmup_seed"],
                                     int(tr["warmup_scans"]))
                + generator.child_rngs(run.seed, int(tr["cohort_scans"])))
        raw = run.workdir / "raw"
        self.sessions: List[Dict[str, np.ndarray]] = []
        for i, rng in enumerate(rngs):
            session = generator.make_session(rng, scans)
            for proto, vol in session.items():
                write_raw_dump(raw / f"sub-{i:03d}_ses-01_{proto}.raw.npz",
                               vol, subject=f"{i:03d}", session="01",
                               protocol=proto,
                               resolution_mm=scans[proto]["resolution_mm"])
            self.sessions.append(session)
        manifest, records = ingest_directory(raw, run.workdir / "bids",
                                             "cohort", device_qa=True)
        bad = [r.source for r in records if r.status != "ok"]
        if bad:
            raise SetupError(f"cohort scans not accepted: {bad}")
        shutil.rmtree(raw)
        self.pipes, self.units, self.runners = {}, {}, {}
        for stage in self.dag:
            pipe = timed_pipeline(pipes[stage], run.stage(stage), self.spans)
            plan = generate_jobs(manifest, pipe,
                                 run.workdir / "jobs" / stage)
            self.pipes[stage] = pipe
            self.units[stage] = {int(u.subject): u for u in plan.units}
            self.runners[stage] = LocalRunner(pipe, manifest.root)
        self.next = 0
        for _ in range(int(tr["warmup_scans"])):
            if not all(r["ok"] for r in self._scan()):
                raise SetupError("warm-up unit did not commit")
        self.spans.clear()

    def control(self):
        """Each stage's reference, a precision step lower, in the stage's
        place: the units run, commit and are checked as the program's."""
        for stage in self.dag:
            ref, cfg = self._ref(stage), self.run.stage(stage)

            def low(inputs, ref=ref, cfg=cfg):
                return ref.reference({k: np.asarray(v, np.float32)
                                      for k, v in inputs.items()}, cfg,
                                     lowp=True)
            self.pipes[stage].fn = timed(stage, low, self.spans)

    def _scan(self) -> List[dict]:
        from jax.profiler import TraceAnnotation
        i, out = self.next, []
        self.next += 1
        for stage in self.dag:
            with TraceAnnotation(f"unit.{stage}"):
                res = self.runners[stage].run([self.units[stage][i]])[0]
            out.append({"stage": stage, "scan": i, "ok": res.status == "ok",
                        "seconds": res.seconds, "error": res.error})
        return out

    def window(self, deadline: float):
        run = self.run
        while time.perf_counter() < deadline:
            if self.next >= len(self.sessions):
                log("the cohort ran dry: the window ends early")
                break
            n0 = len(self.spans)
            units = self._scan()
            for u in units:
                u["stage_s"] = sum(b - a for name, a, b in self.spans[n0:]
                                   if name == u["stage"])
                log(f"unit {u['stage']} scan {u['scan']}: "
                    f"{u['seconds']:.3f} s, stage {u['stage_s']:.3f} s")
            run.units.extend(units)
        scans = sorted({u["scan"] for u in run.units})
        run.attempted = len(scans)
        run.scans_committed = sum(
            all(u["ok"] for u in run.units if u["scan"] == s) for s in scans)
        run.failed = run.attempted - run.scans_committed

    def close(self):
        pass

    def check(self, sample_rng) -> Dict[str, tuple]:
        """A sample of the window's scans, drawn from the seed: every
        stage's committed outputs, read back against their provenance and
        compared with the reference run on the scan's session."""
        run = self.run
        scans = sorted({u["scan"] for u in run.units})
        k = min(int(run.cell.traffic["check_scans"]), len(scans))
        sample = sorted(sample_rng.choice(scans, size=k, replace=False)) \
            if k else []
        compared: Dict[str, tuple] = {}
        for stage in self.dag:
            ref, cfg = self._ref(stage), run.stage(stage)
            protos = self.pipes[stage].spec.required_suffixes
            prov_bad = sum(not u["ok"] for u in run.units
                           if u["stage"] == stage)
            worst: Dict[str, float] = {}
            for s in sample:
                got, bad = read_outputs(self.units[stage][s], ref.OUTPUTS)
                prov_bad += bad
                if got is None:
                    continue
                want = ref.reference(
                    {p: self.sessions[s][p].astype(np.float32)
                     for p in protos}, cfg)
                for name, v in ref.gaps(got, want, cfg).items():
                    worst[name] = max(worst.get(name, 0.0), v)
                del got, want
                gc.collect()
            for name, limit in ref.LIMITS.items():
                compared[f"{stage}.{name}"] = (worst.get(name, float("inf")),
                                               limit)
            compared[f"{stage}.provenance"] = (prov_bad, 0)
        return compared
