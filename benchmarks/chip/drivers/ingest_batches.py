"""Batches of sessions' raw scanner dumps, each batch one
``ingest_directory(device_qa=True)`` call into one dataset.

Mix parameters: ``sessions_per_batch``, ``batches`` (written in set-up),
``distinct_sessions`` (drawn from the seed and reused under new subject
ids, which keeps the dumps written in set-up to a few GB) and
``check_files`` (accepted files re-read against their sha256). A session
holds every protocol the configuration states. The warm-up ingests one
session into a dataset of its own. The window ends with the batch that was
started before the deadline, or early, with a note, if the dumps run out.
"""
from __future__ import annotations

import hashlib
import json
import shutil
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import Dict

from chipbench import generator
from chipbench.harness import SetupError, load_module, log


class Driver:
    def __init__(self, run):
        self.run = run
        self.ref = load_module(run.cell.bench_dir / "reference" / "ingest.py")
        self._restore = None

    def setup(self):
        from repro.core import ingest_directory, write_raw_dump
        tr, run = self.run.cell.traffic, self.run
        scans = run.scans
        self.res = {k: v["resolution_mm"] for k, v in scans.items()}
        self.distinct = [generator.make_session(rng, scans) for rng in
                         generator.child_rngs(run.seed,
                                              int(tr["distinct_sessions"]))]
        per = int(tr["sessions_per_batch"])
        self.batches = []
        for b in range(int(tr["batches"])):
            bdir = run.workdir / "raw" / f"batch-{b:03d}"
            dumps = []
            for s in range(per):
                sub = f"b{b:03d}s{s}"
                which = (b * per + s) % len(self.distinct)
                for proto, vol in self.distinct[which].items():
                    path = bdir / f"sub-{sub}_ses-01_{proto}.raw.npz"
                    write_raw_dump(path, vol, subject=sub, session="01",
                                   protocol=proto,
                                   resolution_mm=self.res[proto])
                    dumps.append({"source": path.name, "which": which,
                                  "proto": proto, "subject": sub,
                                  "bytes": path.stat().st_size})
            self.batches.append((bdir, dumps))
        self.bids = run.workdir / "bids"
        warm = run.workdir / "raw" / "warmup"
        for proto, vol in self.distinct[0].items():
            write_raw_dump(warm / f"sub-warmup_ses-01_{proto}.raw.npz", vol,
                           subject="warmup", session="01", protocol=proto,
                           resolution_mm=self.res[proto])
        _, records = ingest_directory(warm, self.bids, "warmup",
                                      device_qa=True)
        if any(r.status != "ok" for r in records):
            raise SetupError("warm-up session not accepted")
        shutil.rmtree(warm)
        shutil.rmtree(self.bids / "warmup")
        self.done = []

    def control(self):
        """The reference's lower precision in the program's place: each
        dump's volume is rounded to bfloat16 where ingest converts it, so
        ingest hashes, folds and commits what the control stores."""
        import repro.core.ingest as ingest
        real = ingest._convert
        lower = self.ref.lower

        def convert(raw):
            vol, meta, err = real(raw)
            return (None if vol is None else lower(vol)), meta, err
        ingest._convert = convert
        self._restore = lambda: setattr(ingest, "_convert", real)

    def window(self, deadline: float):
        from jax.profiler import TraceAnnotation
        from repro.core import ingest_directory
        run = self.run
        b = 0
        while time.perf_counter() < deadline:
            if b >= len(self.batches):
                log("the raw dumps ran dry: the window ends early")
                break
            bdir, dumps = self.batches[b]
            t0 = time.perf_counter()
            with TraceAnnotation("ingest.batch"):
                _, records = ingest_directory(bdir, self.bids, "cohort",
                                              device_qa=True)
            stream = sum(float((r.stream or {}).get("wall_s", 0.0))
                         for r in records)
            log(f"batch {b}: {time.perf_counter() - t0:.3f} s, streams "
                f"{stream:.3f} s")
            self.done.append((dumps, {r.source: r for r in records}))
            b += 1
        for dumps, recs in self.done:
            for d in dumps:
                run.attempted += 1
                r = recs.get(d["source"])
                if r is None or r.status != "ok":
                    run.failed += 1
                    continue
                run.bytes_accepted += d["bytes"]
                st = r.stream or {}
                run.stream_wall_s += float(st.get("wall_s", 0.0))
                if st.get("qa_backend") == "device":
                    run.payload_bytes_folded += int(st.get("nbytes", 0))

    def close(self):
        if self._restore is not None:
            self._restore()
            self._restore = None

    def check(self, sample_rng) -> Dict[str, tuple]:
        """Every accepted scan's record (sha256, QA stats, checksum, path,
        sidecar) against the reference, and a sample of the committed files
        re-read against their sha256."""
        run, ref = self.run, self.ref
        keys = sorted({(d["which"], d["proto"]) for dumps, _ in self.done
                       for d in dumps})
        with ThreadPoolExecutor(max_workers=4) as pool:
            want = dict(zip(keys, pool.map(
                lambda k: ref.expected(self.distinct[k[0]][k[1]]), keys)))
        counts = dict.fromkeys(ref.LIMITS, 0)
        accepted = []
        for dumps, recs in self.done:
            for d in dumps:
                r = recs.get(d["source"])
                if r is None or r.status != "ok":
                    counts["missing"] += 1
                    continue
                w = want[(d["which"], d["proto"])]
                dest = Path(r.dest)
                side = dest.with_suffix(".json")
                meta = json.loads(side.read_text()) if side.exists() else {}
                ok = (r.sha256 == w["sha256"] and r.qa == w["qa"]
                      and r.checksum == w["checksum"]
                      and dest.name == f"sub-{d['subject']}_ses-01_"
                                       f"{d['proto']}.npy"
                      and meta.get("subject") == d["subject"]
                      and meta.get("protocol") == d["proto"]
                      and meta.get("resolution_mm") == self.res[d["proto"]]
                      and meta.get("matrix") == list(
                          self.distinct[d["which"]][d["proto"]].shape))
                counts["records"] += not ok
                st = r.stream or {}
                on_chip = (st.get("device_qa") is True
                           and st.get("qa_backend") == "device"
                           and st.get("qa_interpret") is False)
                counts["fold_off_chip"] += not (on_chip or run.tiny)
                accepted.append((dest, w["sha256"]))
        k = min(int(run.cell.traffic["check_files"]), len(accepted))
        picked = [accepted[i] for i in sorted(
            sample_rng.choice(len(accepted), size=k, replace=False))] \
            if k else []

        def file_sha(dest: Path) -> str:
            return hashlib.sha256(dest.read_bytes()).hexdigest() \
                if dest.exists() else ""
        with ThreadPoolExecutor(max_workers=4) as pool:
            got = list(pool.map(file_sha, [p[0] for p in picked]))
        counts["files"] += sum(g != p[1] for g, p in zip(got, picked))
        if not self.done:
            counts["missing"] += 1
        return {f"ingest.{k}": (v, ref.LIMITS[k]) for k, v in counts.items()}
