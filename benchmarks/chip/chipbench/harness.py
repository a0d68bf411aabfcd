"""Run one benchmark cell: set up, measure a window, check, report.

Everything that belongs to one configuration, traffic mix or metric is a
file that this module finds by the name ``BENCHMARK.json`` gives it:

* ``configs/<config>.json``: the scans (protocol, shape, generator
  parameters), stage settings, the program's environment knobs, and the
  shapes of the ``--tiny`` rehearsal;
* ``traffic/<traffic>.json``: the mix's parameters; its ``kind`` names the
  driver that runs it;
* ``drivers/<kind>.py``: a ``Driver(run)`` with ``setup()``,
  ``window(deadline)``, ``check(rng) -> {name: (value, limit)}`` and
  ``control()``, which puts the control in the program's place;
* ``metrics/<metric>.py``: a reader, ``read(run) -> float | None``; a
  metric ``<base>.<suffix>`` without a file of its own is read by
  ``<base>.py``;
* ``reference/<stage>.py``: the plain reference a stage's outputs are
  compared with, its control and its limits.

The program is reached only through its public entry points:
``write_raw_dump`` and ``ingest_directory`` for ingest, and
``builtin_pipelines`` -> ``generate_jobs`` -> ``LocalRunner.run`` for the
stages, each stage wrapped in a ``Pipeline`` built from its own spec so the
harness can time it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import hashlib
import importlib.util
import inspect
import io
import json
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from . import trace as trace_mod

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
ENV_KNOBS = ("REPRO_STREAM_INGEST", "REPRO_STREAM_CHUNK_MB", "REPRO_DEVICE_QA",
             "REPRO_SCAN_UNROLL")


class SetupError(RuntimeError):
    """The run cannot be measured (no chip, a bad cell, a dry window)."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- the cell, from BENCHMARK.json and the files it names ---------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: Path


def _applies(metric: dict, cell: str, e2e_names: List[str]) -> bool:
    """A metric with a ``workloads`` key is read in those cells; an
    end-to-end metric without one in every cell, and a per-layer metric
    without one in every cell that reports the metric it ``moves``."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def load_cell(workload: str, spec_path: Path) -> Cell:
    spec = json.loads(spec_path.read_text())
    root = spec_path.parent
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SetupError(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    w = cells[workload]
    cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
    bench_dir = root / spec["paths"][0]
    config = json.loads((root / cfg["file"]).read_text())
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"] if _applies(m, workload, [])]
    names = [m["name"] for m in e2e]
    per = [m for m in spec["per_layer"] if _applies(m, workload, names)]
    return Cell(workload, int(w["chips"]), config, traffic, e2e, per,
                bench_dir)


@contextlib.contextmanager
def env_knobs(config: dict):
    """Fix the program's environment knobs for the run, and put back what
    the process had afterwards."""
    knobs = config.get("env", {})
    old = {k: os.environ.get(k) for k in ENV_KNOBS}
    for k in ENV_KNOBS:
        if k in knobs:
            os.environ[k] = str(knobs[k])
        else:
            os.environ.pop(k, None)
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


# -- JAX's compile events -----------------------------------------------------

class CompileMeter:
    """Sums JAX's own compile events while active: tracing and lowering,
    and backend compile (a persistent-cache hit is timed as its retrieval).
    Each event is kept with its end on the host clock, for the idle-gap
    attribution of a traced run. One per process: JAX's listeners cannot be
    removed."""

    _instance: Optional["CompileMeter"] = None
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    @classmethod
    def get(cls) -> "CompileMeter":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def __init__(self):
        import jax
        self.active = False
        self.events: List[tuple] = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, duration, **_):
        if self.active and event in self.EVENTS:
            self.events.append((time.perf_counter(), float(duration)))

    @contextlib.contextmanager
    def measure(self):
        self.events = []
        self.active = True
        try:
            yield self
        finally:
            self.active = False

    @property
    def seconds(self) -> float:
        return sum(d for _, d in self.events)


# -- the run, as the metric readers see it ------------------------------------

@dataclasses.dataclass
class Run:
    cell: Cell
    seed: int
    seconds: float
    tiny: bool
    workdir: Path
    device_kind: str = ""
    setup_s: float = 0.0
    window_s: float = 0.0
    units: List[dict] = dataclasses.field(default_factory=list)
    scans_committed: int = 0
    bytes_accepted: int = 0
    payload_bytes_folded: int = 0
    stream_wall_s: float = 0.0
    compile_s: float = 0.0
    compile_events: List[tuple] = dataclasses.field(default_factory=list)
    trace: Optional[trace_mod.Trace] = None
    attempted: int = 0
    failed: int = 0

    @property
    def scans(self) -> Dict[str, dict]:
        """Each protocol's scan as the configuration states it (shape and
        generator parameters), with the rehearsal's changes when tiny."""
        scans = self.cell.config["scans"]
        if self.tiny:
            scans = {k: {**v, **self.cell.config["tiny"]["scans"].get(k, {})}
                     for k, v in scans.items()}
        return scans

    @property
    def shapes(self) -> Dict[str, List[int]]:
        return {k: list(v["shape"]) for k, v in self.scans.items()}

    def stage(self, name: str) -> dict:
        """The configuration's settings of a stage; a stage it does not
        name runs at the program's own."""
        return self.cell.config.get("stages", {}).get(name, {})


# -- the stage path: generate_jobs -> LocalRunner -> run_unit -----------------

def timed_pipeline(pipe, stage_cfg: dict, spans: List[tuple]):
    """The stage as the program builds it, with the configuration's changes
    passed as keywords (and written into the spec, so the digest and
    provenance say what ran), timed to ``block_until_ready``."""
    from repro.core import Pipeline
    changed = {k: v for k, v in stage_cfg.items()
               if k in pipe.spec.config and pipe.spec.config[k] != v}
    params = inspect.signature(pipe.fn).parameters
    for k in changed:
        if k not in params:
            raise SetupError(f"{pipe.name} takes no keyword {k!r}")
    fn = functools.partial(pipe.fn, **changed) if changed else pipe.fn
    spec = dataclasses.replace(pipe.spec,
                               config={**pipe.spec.config, **changed})
    return Pipeline(spec, timed(pipe.name, fn, spans))


def timed(name: str, fn: Callable, spans: List[tuple]) -> Callable:
    """``fn`` with a host span ``stage.<name>`` around it that ends at
    ``block_until_ready``; each call appends ``(name, t0, t1)``."""
    import jax
    from jax.profiler import TraceAnnotation

    def call(inputs):
        with TraceAnnotation(f"stage.{name}"):
            t0 = time.perf_counter()
            out = jax.block_until_ready(fn(inputs))
            spans.append((name, t0, time.perf_counter()))
        return out
    return call


def read_outputs(unit, names) -> tuple:
    """A committed unit's outputs, each file's bytes checked against the
    sha256 its provenance recorded. Returns (arrays, mismatches)."""
    from repro.core import Provenance
    prov = Provenance.load(Path(unit.out_dir))
    if prov is None or prov.status != "ok" \
            or prov.pipeline_digest != unit.pipeline_digest:
        return None, 1
    arrays, bad = {}, 0
    for name in names:
        fname = f"sub-{unit.subject}_ses-{unit.session}_{name}.npy"
        data = (Path(unit.out_dir) / fname).read_bytes()
        if hashlib.sha256(data).hexdigest() != prov.outputs.get(fname):
            bad += 1
        arrays[name] = np.load(io.BytesIO(data), allow_pickle=False)
    return arrays, bad


# -- the run ------------------------------------------------------------------

def _process_age_s() -> float:
    """Seconds since this process started (Linux), else 0."""
    try:
        ticks = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1]
                    .split()[19])
        up = float(Path("/proc/uptime").read_text().split()[0])
        return max(0.0, up - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


def metric_reader(bench_dir: Path, name: str):
    """The reader of a metric: ``metrics/<name>.py``, or for a name
    ``<base>.<suffix>`` without a file of its own the reader of ``<base>``
    (one quantity reported under a name per end-to-end metric it moves)."""
    parts = name.split(".")
    for n in range(len(parts), 0, -1):
        path = bench_dir / "metrics" / (".".join(parts[:n]) + ".py")
        if path.is_file():
            return load_module(path)
    raise SetupError(f"no reader for metric {name!r}")


def _metric_values(run: Run, metrics: List[dict]) -> Dict[str, dict]:
    out = {}
    for m in metrics:
        v = metric_reader(run.cell.bench_dir, m["name"]).read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def load_driver(run: Run):
    """The traffic's driver, ``drivers/<kind>.py``, built for this run."""
    kind = run.cell.traffic["kind"]
    path = run.cell.bench_dir / "drivers" / f"{kind}.py"
    if not path.is_file():
        raise SetupError(f"no driver for traffic kind {kind!r}")
    return load_module(path).Driver(run)


def _device_info(devices) -> dict:
    peak = 0
    for d in devices:
        try:
            peak = max(peak, int((d.memory_stats() or {})
                                 .get("peak_bytes_in_use", 0)))
        except Exception:  # noqa: BLE001 — a backend without memory stats
            pass
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def compile_cache(cell: Cell) -> Path:
    """Give the program its persistent compile cache: a fixed directory of
    this cell's own inside the checkout, so no cell's entries displace
    another's and nothing is shared with any other checkout. The program's
    own ``enable_compile_cache`` takes it from ``JAX_COMPILATION_CACHE_DIR``
    and keeps its other settings (the one-second floor among them)."""
    import jax
    from repro.launch.env import enable_compile_cache
    path = cell.bench_dir / ".cache" / cell.name
    path.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(path)
    jax.config.update("jax_compilation_cache_dir", str(path))
    return enable_compile_cache()


def _drop_new_entries(cache: Optional[Path], before: set) -> int:
    """Remove the cache entries the window wrote, so that every run's
    window meets the cache as set-up left it: a program that compiles per
    scan compiles again, whatever earlier runs of the same seed wrote."""
    if cache is None or not cache.is_dir():
        return 0
    new = [p for p in cache.iterdir() if p.name not in before]
    for p in new:
        if p.is_dir():
            shutil.rmtree(p, ignore_errors=True)
        else:
            p.unlink(missing_ok=True)
    return len(new)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             tiny: bool = False, spec_path: Optional[Path] = None,
             workdir: Optional[Path] = None, patch: Optional[Callable] = None,
             control: bool = False) -> dict:
    """One run of one cell; returns the result line as a dict. ``tiny``
    skips the look for a chip and runs the configuration's rehearsal
    shapes (for the CPU tests, never for a measurement).
    ``patch(run, driver)`` lets a test break the timed path after set-up.
    ``control`` puts the cell's control in the program's place (each
    stage's reference a precision step lower; see the drivers), so its
    ``compared`` are the control's readings and ``correct`` has to come
    out false; the benchmark's own runs never do."""
    t_start = time.time() - _process_age_s()
    cell = load_cell(workload, spec_path or ROOT / "BENCHMARK.json")
    with env_knobs(cell.config):
        import jax
        devices = jax.devices()
        if not tiny and (devices[0].platform != "tpu"
                         or len(devices) < cell.chips):
            raise SetupError(
                f"{workload} needs {cell.chips} TPU chip(s); JAX found "
                f"{len(devices)} {devices[0].platform} device(s)")
        cache = None
        if not tiny:
            cache = compile_cache(cell)
            log(f"compile cache: {cache}")
        workdir = Path(workdir or cell.bench_dir / ".work" / workload)
        shutil.rmtree(workdir, ignore_errors=True)
        workdir.mkdir(parents=True)
        run = Run(cell, seed, seconds, tiny, workdir,
                  device_kind=devices[0].device_kind)
        try:
            return _measure(run, devices, trace, t_start, patch, control,
                            cache)
        finally:
            shutil.rmtree(workdir, ignore_errors=True)


def _measure(run: Run, devices, traced: bool, t_start, patch, control,
             cache):
    import jax
    from jax.profiler import TraceAnnotation
    driver = load_driver(run)
    meter = CompileMeter.get()
    log(f"{run.cell.name}: set-up (seed {run.seed})")
    driver.setup()
    os.sync()           # set-up's writes reach the disk before the window
    log(f"set-up done at {time.time() - t_start:.1f} s")
    if control:
        driver.control()
    if patch is not None:
        patch(run, driver)
    cached = {p.name for p in cache.iterdir()} if cache else set()
    tdir = run.workdir / "trace"
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0          # spans come from annotations
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
    t_wall0 = time.time()
    run.setup_s = t_wall0 - t_start
    t0 = time.perf_counter()
    try:
        with meter.measure(), TraceAnnotation("window"):
            driver.window(t0 + run.seconds)
    finally:
        driver.close()
    run.window_s = time.perf_counter() - t0
    run.compile_s = meter.seconds
    run.compile_events = list(meter.events)
    if traced:
        jax.profiler.stop_trace()
        run.trace = trace_mod.load(str(tdir))
    device = _device_info(devices)
    log(f"window {run.window_s:.3f} s, {run.attempted} attempted, "
        f"{run.failed} failed, compile {run.compile_s:.3f} s, "
        f"{_drop_new_entries(cache, cached)} cache entries written")
    metrics = _metric_values(run, run.cell.per_layer if traced
                             else run.cell.end_to_end)
    result = {"correct": False, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device}
    if traced:
        lo, hi = run.trace.window()
        off = lo - t0 * 1e9
        extra = [("compile", end * 1e9 + off - d * 1e9, end * 1e9 + off)
                 for end, d in run.compile_events]
        device["busy_s"] = trace_mod.busy_s(run.trace, lo, hi)
        device["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = trace_mod.breakdown(run.trace, lo, hi, extra)
    gc.collect()
    t_check = time.perf_counter()
    compared = driver.check(np.random.default_rng(
        np.random.SeedSequence([int(run.seed), 1])))
    log(f"check {time.perf_counter() - t_check:.1f} s")
    result["correct"] = bool(compared) and all(
        v <= lim for v, lim in compared.values())
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in compared.items()}
    return result
