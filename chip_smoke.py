#!/usr/bin/env python3
"""Chip smoke: the data plane's main path, once, on one TPU at acquisition size.

From ``--seed`` it makes one subject x one session of scanner dumps at the
paper's shapes (a 1 mm T1w of 256x256x176 and a DWI of 96x96x60 x 100
directions), then runs what a user runs:

1. ``write_raw_dump`` -> ``ingest_directory(device_qa=True)`` with streaming
   on: every scan is folded through the Pallas QA+checksum kernel on the
   chip while its bytes stream. Each scan's verdict must equal the numpy
   oracle (``repro.kernels.checksum.ref``) and the one-shot kernel bit for
   bit, and its stream report must say the device fold ran, compiled.
2. The kernels on their own at the same sizes: the plain checksum, one-shot
   QA on float32 and on the scanner's int16, and the batched kernel over a
   shape bucket of four DWI volumes, each against the oracle.
3. ``DatasetManifest.scan`` -> ``generate_jobs`` for ``bias_correct``,
   ``affine_register``, ``segment_unest`` and ``dwi_prequal`` ->
   ``LocalRunner`` -> ``run_unit``: every unit must commit ``ok`` with its
   ``provenance.json``. Each stage is then called again, timed to
   ``block_until_ready``, for its steady seconds.
4. Each stage once more on a cropped volume, on the chip and on JAX's CPU
   device under ``jax.default_matmul_precision("highest")``, compared within
   the tolerances in ``TOLERANCES``.

Earlier lines report the JAX version and device, where each stage's output
lived, compile and steady seconds, peak device bytes and the reference
errors; ``chiprun_out/chip_smoke.json`` holds the full report. The last line
is ``{"ok": ..., "device": {"platform", "kind", "count"}}``; ``ok`` is true
only on a TPU with every check passed, and the exit code is 0 only then.

    python chip_smoke.py              # on the chip, acquisition size
    python chip_smoke.py --tiny       # rehearsal at toy size; ends ok: false
                                      # without a TPU
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
import tempfile
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

FULL = {"t1w": (256, 256, 176), "dwi": (96, 96, 60, 100),
        "crop": (64, 64, 64), "dwi_crop": (64, 64, 48)}
# the crop is a 64^3 cube because segment_unest patches at 4 voxels and its
# attention chunks 2048 queries: 64^3 gives 4096 tokens, two whole chunks
TINY = {"t1w": (32, 32, 24), "dwi": (16, 16, 12, 8),
        "crop": (16, 16, 16), "dwi_crop": (12, 12, 8)}
STAGES = ("bias_correct", "affine_register", "segment_unest", "dwi_prequal")

# Chip vs CPU reference (highest matmul precision) on the cropped volume:
# (output, max |chip - ref| / max |ref|) bounds, with their reasons.
TOLERANCES = {
    "bias_correct": (
        {"T1w_biascorr": 2e-4, "bias_field": 2e-4},
        "float32 throughout, contractions at HIGHEST precision; the chip "
        "differs in reduction order, SVD algorithm and exp/log "
        "approximations, and the fitted log-field's error passes through "
        "exp into the corrected image (a v5e measured 1.0e-4 and 3.1e-5 "
        "at seed 0)"),
    "affine_register": (
        {"T1w_reg": 1e-4, "affine": 1e-6, "reg_loss": 5e-6},
        "float32 with the grid contraction at HIGHEST precision; 60 "
        "gradient steps over 262k-voxel reductions whose order differs on "
        "the chip, and trilinear sampling turns a coordinate difference "
        "into an intensity difference scaled by the image gradient (a v5e "
        "measured 1.5e-5, 1.7e-7 and 5.2e-7 at seed 0; each bound is 6-10x "
        "that)"),
    "segment_unest": (
        {"class_logits": 1.2e-2},
        "the backbone runs in bfloat16 by design, so the chip's op order "
        "and rounding move the logits: a v5e measured 5.6e-3 at seed 0, "
        "and the bound gives that 2x headroom. Backbone faults exceed it "
        "(on the CPU at this crop: a dropped layer 1.3e-1, 64 tokens of one "
        "attention chunk misplaced 9.8e-1, every layer's update 10% short "
        "1.7e-2). Labels must agree wherever the reference's top-2 logit "
        "margin exceeds twice this bound"),
    "dwi_prequal": (
        {"dwi_denoised": 0.0},
        "a host NumPy SVD: the same code on the same host, so exact"),
}


def log(msg: str) -> None:
    print(msg, flush=True)


@contextlib.contextmanager
def compile_timing(into: dict):
    """Wall seconds of the block, and the program's compile counters over it
    (``repro.core.trace``): tracing + lowering + backend compile (a
    persistent-cache hit is timed as its retrieval), and cache hits and
    misses."""
    from repro.core import trace
    before = trace.counters()
    t0 = time.perf_counter()
    try:
        yield
    finally:
        after = trace.counters()

        def grew(key):
            return after.get(key, 0) - before.get(key, 0)
        backend = grew("compile.backend.s")
        into.update(wall_s=time.perf_counter() - t0,
                    compile_s=grew("compile.trace.s") + grew("compile.lower.s")
                    + backend,
                    backend_compile_s=backend,
                    cache_hits=grew("compile.cache_hits"),
                    cache_misses=grew("compile.cache_misses"))


class Smoke:
    def __init__(self, args, device):
        self.args = args
        self.device = device
        self.sizes = TINY if args.tiny else FULL
        self.checks: list = []
        self.report: dict = {"checks": self.checks, "stages": {},
                             "ingest": {}, "kernels": {}, "reference": {}}

    # -- bookkeeping ----------------------------------------------------------

    def check(self, name: str, ok: bool, detail: str = "") -> bool:
        ok = bool(ok)
        self.checks.append({"name": name, "ok": ok, "detail": detail})
        log(f"  [{'ok' if ok else 'FAIL'}] {name}{': ' + detail if detail else ''}")
        return ok

    def peak_bytes(self):
        """Peak device bytes since start-up (the runtime keeps no resettable
        peak, so each stage reads the running maximum); None where the
        backend reports no memory stats."""
        try:
            stats = self.device.memory_stats() or {}
        except Exception:  # noqa: BLE001 — not every backend reports
            stats = {}
        return stats.get("peak_bytes_in_use")

    # -- data -----------------------------------------------------------------

    def make_scans(self, rng):
        """A head-like phantom with tissue classes, a smooth bias field and
        scanner noise, as int16 the way scanners write it; the DWI is that
        anatomy attenuated per gradient direction."""
        import numpy as np
        shape = self.sizes["t1w"]
        g = [np.linspace(-1, 1, s, dtype=np.float32) for s in shape]
        gx, gy, gz = np.meshgrid(*g, indexing="ij", sparse=True)
        r = np.sqrt((gx / 0.8) ** 2 + (gy / 0.9) ** 2 + (gz / 0.85) ** 2)
        anat = (20.0 + 380.0 * (r < 1.0) + 200.0 * (r < 0.75)
                + 150.0 * (r < 0.4)).astype(np.float32)
        bias = 1.0 + 0.15 * gx + 0.1 * gy * gy - 0.05 * gz
        noise = rng.standard_normal(shape, dtype=np.float32) * 6.0
        t1w = np.clip(anat * bias + noise, 0, 32767).astype(np.int16)

        dshape = self.sizes["dwi"]
        X, Y, Z, V = dshape
        h = [np.linspace(-1, 1, s, dtype=np.float32) for s in (X, Y, Z)]
        hx, hy, hz = np.meshgrid(*h, indexing="ij", sparse=True)
        s0 = 30.0 + 500.0 * (np.sqrt(hx ** 2 + hy ** 2 + hz ** 2) < 0.8)
        bval = np.where(np.arange(V) % 10 == 0, 0.0, 1.0).astype(np.float32)
        atten = np.exp(-0.7 * bval * rng.uniform(0.5, 1.5, V)
                       .astype(np.float32))
        dwi = s0[..., None] * atten + rng.standard_normal(
            dshape, dtype=np.float32) * 8.0
        return t1w, np.clip(dwi, 0, 32767).astype(np.int16)

    # -- phases ---------------------------------------------------------------

    def ingest(self, work: Path, t1w, dwi):
        import numpy as np
        from repro.core import ingest_directory, write_raw_dump
        from repro.kernels.checksum import qa_checksum_ref, qa_stats
        log("phase: ingest (streamed device QA)")
        raw = work / "raw"
        write_raw_dump(raw / "sub-01_ses-01_T1w.raw.npz", t1w, subject="01",
                       session="01", protocol="T1w")
        write_raw_dump(raw / "sub-01_ses-01_dwi.raw.npz", dwi, subject="01",
                       session="01", protocol="dwi")
        timing: dict = {}
        with compile_timing(timing):
            manifest, records = ingest_directory(raw, work / "bids", "smoke",
                                                 device_qa=True)
        self.report["ingest"]["timing"] = timing
        log(f"  ingest wall {timing['wall_s']:.3f}s, compile "
            f"{timing['compile_s']:.3f}s")
        on_tpu = self.device.platform == "tpu"
        for rec in records:
            self.check(f"ingest {rec.source} accepted", rec.status == "ok",
                       rec.reason)
            st = rec.stream or {}
            ran = (st.get("device_qa"), st.get("qa_backend"),
                   st.get("qa_interpret"))
            # on the chip: the device fold, compiled; off it: the host fold
            want = ("device", False) if on_tpu else ("host", None)
            self.check(f"ingest {rec.source} fold backend",
                       ran == (True,) + want,
                       f"device_qa/backend/interpret = {ran}, want "
                       f"{(True,) + want}")
            if rec.status != "ok":
                continue
            vol = np.load(rec.dest)
            sums, qa, cnt = qa_checksum_ref(vol)
            sums = sums.view(np.uint32)
            oracle = {"s1": int(sums[0]), "s2": int(sums[1]),
                      "vmin": float(qa[0]), "vmax": float(qa[1]),
                      "vsum": float(qa[2]), "finite_count": int(cnt[0])}
            one_shot = dataclasses.asdict(qa_stats(vol))
            self.check(f"ingest {rec.source} QAStats == numpy oracle",
                       rec.qa == oracle, f"{rec.qa} vs {oracle}")
            self.check(f"ingest {rec.source} QAStats == one-shot kernel",
                       rec.qa == one_shot)
            self.report["ingest"][rec.source] = {
                "qa": rec.qa, "stream": rec.stream, "shape": list(vol.shape)}
            log(f"  {rec.source}: {vol.nbytes} bytes in {st.get('chunks')} "
                f"chunks, stream wall {st.get('wall_s')}s, device "
                f"{st.get('device_s')}s")
        return manifest

    def kernels(self, t1w, dwi):
        import jax
        import numpy as np
        from repro.kernels.checksum import (device_checksum,
                                            device_checksum_ref, qa_checksum,
                                            qa_checksum_batched,
                                            qa_checksum_batched_ref,
                                            qa_checksum_ref)
        log("phase: kernels vs numpy oracle")
        t1f = t1w.astype(np.float32)
        bucket = np.ascontiguousarray(
            np.moveaxis(dwi[..., :4], -1, 0).astype(np.float32))
        cases = [
            ("device_checksum f32 T1w", lambda: device_checksum(t1f),
             lambda: device_checksum_ref(t1f)),
            ("one-shot QA f32 T1w", lambda: qa_checksum(t1f),
             lambda: qa_checksum_ref(t1f)),
            ("one-shot QA int16 T1w", lambda: qa_checksum(t1w),
             lambda: qa_checksum_ref(t1w)),
            ("batched QA, 4 DWI volumes", lambda: qa_checksum_batched(bucket),
             lambda: qa_checksum_batched_ref(bucket)),
        ]
        for name, run, ref in cases:
            timing: dict = {}
            with compile_timing(timing):
                got = jax.block_until_ready(run())
            t0 = time.perf_counter()
            jax.block_until_ready(run())
            timing["steady_s"] = time.perf_counter() - t0
            got = got if isinstance(got, tuple) else (got,)
            want = ref()
            want = want if isinstance(want, tuple) else (want,)
            same = all(np.array_equal(np.asarray(g), w)
                       for g, w in zip(got, want))
            self.report["kernels"][name] = timing
            self.check(f"{name} == oracle bit for bit", same,
                       f"compile {timing['compile_s']:.3f}s, steady "
                       f"{timing['steady_s']:.6f}s")

    def stages(self, work: Path, manifest):
        import jax
        import numpy as np
        from repro.core import (LocalRunner, Provenance, builtin_pipelines,
                                generate_jobs, load_unit_inputs)
        log("phase: stages through generate_jobs -> LocalRunner -> run_unit")
        pipes = builtin_pipelines()
        for name in STAGES:
            pipe = pipes[name]
            plan = generate_jobs(manifest, pipe, work / "jobs" / name)
            rep: dict = {"units": len(plan.units)}
            self.check(f"{name}: one unit generated", len(plan.units) == 1,
                       f"{len(plan.units)} units")
            timing: dict = {}
            with compile_timing(timing):
                results = LocalRunner(pipe, manifest.root).run(plan.units)
            rep["first_call"] = timing
            for res in results:
                prov = Provenance.load(Path(res.unit.out_dir))
                self.check(f"{name}: unit committed ok",
                           res.status == "ok" and prov is not None
                           and prov.status == "ok",
                           res.error or "")
            # the second call, on the same inputs, for steady seconds; a
            # stage that is not one jitted program traces again here and
            # reads the persistent cache, which the counters count apart
            inputs = load_unit_inputs(plan.units[0], manifest.root)[0]
            steady: dict = {}
            with compile_timing(steady):
                out = jax.block_until_ready(pipe.fn(inputs))
            rep["second_call"] = steady
            rep["steady_s"] = steady["wall_s"]
            rep["output_devices"] = {
                k: (str(next(iter(v.devices())))
                    if isinstance(v, jax.Array) else "host")
                for k, v in out.items()}
            rep["peak_bytes_in_use"] = self.peak_bytes()
            self.sanity(name, inputs, {k: np.asarray(v)
                                       for k, v in out.items()})
            del out
            self.report["stages"][name] = rep
            log(f"  {name}: first call {timing['wall_s']:.3f}s (compile "
                f"{timing['compile_s']:.3f}s, backend "
                f"{timing['backend_compile_s']:.3f}s, cache hits "
                f"{timing['cache_hits']}), steady {rep['steady_s']:.3f}s "
                f"(compile {steady['compile_s']:.3f}s, backend "
                f"{steady['backend_compile_s']:.3f}s, cache hits "
                f"{steady['cache_hits']}), "
                f"outputs on {sorted(set(rep['output_devices'].values()))}, "
                f"peak bytes {rep['peak_bytes_in_use']}")

    def sanity(self, name, inputs, out):
        import numpy as np
        finite = all(np.all(np.isfinite(v)) for v in out.values())
        t1 = inputs["T1w"].shape
        if name == "bias_correct":
            ok = (out["T1w_biascorr"].shape == t1
                  and float(np.std(np.log(out["bias_field"]))) > 1e-3)
        elif name == "affine_register":
            ok = out["T1w_reg"].shape == t1 and out["affine"].shape == (3, 4)
        elif name == "segment_unest":
            seg = out["segmentation"]
            ok = seg.shape == t1 and seg.min() >= 0 and seg.max() < 4
        else:
            ok = out["dwi_denoised"].shape == inputs["dwi"].shape
        self.check(f"{name}: outputs finite, shaped and non-trivial",
                   finite and ok)

    def reference(self, t1w, dwi):
        import jax
        import numpy as np
        from repro.core import builtin_pipelines
        log("phase: chip vs CPU reference (highest precision) on a crop")
        cpu = jax.devices("cpu")[0]

        def crop(vol, size):
            lo = [(s - c) // 2 for s, c in zip(vol.shape, size)]
            return np.ascontiguousarray(vol[tuple(
                slice(a, a + c) for a, c in zip(lo, size))]).astype(np.float32)

        inputs = {"T1w": crop(t1w, self.sizes["crop"]),
                  "dwi": crop(dwi, self.sizes["dwi_crop"])}
        pipes = builtin_pipelines()
        for name in STAGES:
            pipe = pipes[name]
            got = {k: np.asarray(v) for k, v in pipe.fn(inputs).items()}
            with jax.default_device(cpu), \
                    jax.default_matmul_precision("highest"):
                want = {k: np.asarray(v) for k, v in pipe.fn(inputs).items()}
            bounds, why = TOLERANCES[name]
            errs = {}
            for k, bound in bounds.items():
                scale = float(np.max(np.abs(want[k]))) or 1.0
                errs[k] = float(np.max(np.abs(got[k].astype(np.float64)
                                              - want[k]))) / scale
                self.check(f"{name} {k} vs CPU reference",
                           errs[k] <= bound,
                           f"max rel err {errs[k]:.3e} <= {bound:.3e}")
            if name == "segment_unest":
                errs["labels"] = self.label_agreement(got, want,
                                                      bounds["class_logits"])
            self.report["reference"][name] = {"errors": errs,
                                              "bounds": bounds, "why": why}

    def label_agreement(self, got, want, bound):
        import numpy as np
        ref = want["class_logits"]
        top2 = np.sort(ref, axis=-1)[:, -2:]
        margin = top2[:, 1] - top2[:, 0]
        decided = margin > 2 * bound * float(np.max(np.abs(ref)))
        same = np.argmax(got["class_logits"], -1) == np.argmax(ref, -1)
        agree = float(np.mean(same[decided])) if decided.any() else 1.0
        self.check("segment_unest labels agree where the margin decides",
                   agree == 1.0,
                   f"{int(decided.sum())}/{decided.size} patches decided, "
                   f"agreement {agree:.6f}; all patches "
                   f"{float(np.mean(same)):.6f}")
        return agree

    def run(self) -> bool:
        import numpy as np
        rng = np.random.default_rng(self.args.seed)
        t0 = time.perf_counter()
        t1w, dwi = self.make_scans(rng)
        log(f"scans: T1w {t1w.shape} {t1w.dtype}, DWI {dwi.shape} "
            f"{dwi.dtype} (seed {self.args.seed}, "
            f"{time.perf_counter() - t0:.3f}s)")
        with tempfile.TemporaryDirectory(prefix=".chip_smoke-",
                                         dir=ROOT) as td:
            work = Path(td)
            manifest = self.ingest(work, t1w, dwi)
            self.kernels(t1w, dwi)
            self.stages(work, manifest)
        self.reference(t1w, dwi)
        return all(c["ok"] for c in self.checks)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="toy shapes, to rehearse every phase without a chip")
    args = ap.parse_args(argv)
    result: dict = {"ok": False}
    try:
        import jax
        dev = jax.devices()[0]
        result["device"] = {"platform": dev.platform,
                            "kind": dev.device_kind,
                            "count": len(jax.devices())}
        log(f"jax {jax.__version__}, device {dev.device_kind} "
            f"({dev.platform}) x{len(jax.devices())}")
        if dev.platform != "tpu" and not args.tiny:
            log("no TPU: the acquisition-size run needs the chip "
                "(--tiny rehearses every phase at toy size)")
        else:
            from repro.launch.env import enable_compile_cache
            log(f"compile cache: {enable_compile_cache()}")
            smoke = Smoke(args, dev)
            t0 = time.perf_counter()
            passed = smoke.run()
            smoke.report["total_s"] = time.perf_counter() - t0
            smoke.report["device"] = result["device"]
            failed = [c["name"] for c in smoke.checks if not c["ok"]]
            log(f"checks: {len(smoke.checks) - len(failed)}/"
                f"{len(smoke.checks)} passed in "
                f"{smoke.report['total_s']:.1f}s"
                + (f"; failed: {failed}" if failed else ""))
            report = ROOT / "chiprun_out" / "chip_smoke.json"
            report.parent.mkdir(parents=True, exist_ok=True)
            report.write_text(json.dumps(smoke.report, indent=1,
                                         default=str))
            result["ok"] = passed and dev.platform == "tpu"
    except Exception:  # noqa: BLE001 — any failure is a failed smoke
        traceback.print_exc()
        result["ok"] = False
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
