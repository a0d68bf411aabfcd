"""Socket-transport throughput: cold vs warm per-host input cache, plus the
coordinator hot path at six-figure backlog depth.

The paper's cost case hinges on the storage->compute link (0.60 Gb/s lab
network vs 0.33 Gb/s cloud); the RPC cluster keeps that link off the
coordinator socket (control plane only) and shortens it with the per-host
content-addressed cache (``repro.dist.cache``). This bench measures the
data plane and the control plane:

* **Fetch stage, cold vs warm** (arm ``fetch``) — per-unit input
  fetch+verify latency and Gb/s through ``safe_load_unit_inputs`` with a
  fresh cache (miss: read shared storage, hash, insert) and a warm one
  (hit: read node-local blob, re-hash, skip storage + insert). Warm must be
  strictly below cold — this is an acceptance gate, checked in-process and
  recorded in the JSON. On one machine both "links" are the same disk, so
  the gap here is the cache's *overheadless* floor; on a real cluster the
  cold path crosses the network and the gap widens to the paper's
  0.60-vs-0.33 framing.
* **End-to-end over the wire** (arm ``e2e``) — a 32-unit run through
  ``ClusterRunner`` with ``transport="rpc"`` (every lease/complete/heartbeat
  is an RPC) plus one *separate-process* worker joined via
  ``python -m repro.dist.rpc work``, cold then warm cache. Reported as
  images/s and input-Gb/s; provenance ``cache_hit`` counts come along so the
  artifact shows the warm run really was served locally.
* **Coordinator hot path** (arm ``hotpath``) — a synthetic 100k-unit
  backlog (``REPRO_BENCH_BACKLOG_UNITS`` overrides) drained by four nodes
  through batched grants/completes while a heartbeat thread pushes summary
  deltas and measures its own latency. Two queue builds race: the shipped
  index-backed :class:`~repro.dist.queue.WorkQueue` and a reconstruction of
  the pre-index coordinator (Bloom re-probe per score, blind FIFO fill and
  blind tail-half steal past its 512-entry scan cap). The acceptance gate:
  the index-backed queue must grant strictly faster *and* hold heartbeat
  p99 latency strictly lower — the cap's placement blindness was the bug,
  but the fix has to pay for itself on the same lock. A socket micro-arm
  rides along, draining 2048 units per-op over JSON-lines vs batched over
  binary frames.

``REPRO_RPC_BENCH_ARMS`` (comma list, default ``fetch,e2e,hotpath``)
selects arms, so CI can split the data-plane and control-plane runs across
matrix entries. Runs in a thread-pinned subprocess like the other executor
benches (see ``_pin``); writes ``benchmarks/out/rpc_throughput.json`` (CI
artifact; override with ``REPRO_BENCH_JSON``).
"""
from __future__ import annotations

import json
import os
import shutil
import statistics
import subprocess
import sys
import tempfile
import threading
import time
from collections import deque
from pathlib import Path

from ._pin import run_pinned

N_SUBJECTS = 16
SESSIONS = 2                        # 32 units
SHAPE = (64, 64, 64)                # 1 MiB float32 input per unit
PIPELINE = "bias_correct"
FETCH_REPS = 5
# the paper's storage->compute link speeds (§3): the lab-network setup the
# cost argument depends on, and the cloud-storage baseline it beats. Keeping
# both in the artifact makes the repo's effective Gb/s trajectory comparable
# across PRs against a fixed yardstick.
PAPER_REFERENCE_GBPS = {"lab_network": 0.60, "cloud_storage": 0.33}

ARMS_ENV = "REPRO_RPC_BENCH_ARMS"
DEFAULT_ARMS = "fetch,e2e,hotpath"

HOTPATH_UNITS_ENV = "REPRO_BENCH_BACKLOG_UNITS"
HOTPATH_UNITS = 100_000
HOTPATH_NODES = 4
HOTPATH_BATCH = 32                  # grants/completes per round trip
HOTPATH_DEADLINE_S = 300.0          # hard stop per queue variant
WIRE_UNITS = 2048                   # socket micro-arm backlog

_INPROC_FLAG = "REPRO_RPC_BENCH_INPROC"
_JSON_OUT = Path(__file__).resolve().parent / "out" / "rpc_throughput.json"


def _median_fetch(units, root, cache):
    """Per-unit fetch+verify latency (s) and total bytes through the stage."""
    from repro.core.workflow import safe_load_unit_inputs
    lats = []
    nbytes = 0
    for u in units:
        t0 = time.perf_counter()
        loaded = safe_load_unit_inputs(u, root, cache=cache)
        lats.append(time.perf_counter() - t0)
        assert loaded is not None
        nbytes += sum(a.nbytes for a in loaded[0].values())
    return statistics.median(lats), nbytes, sum(lats)


def _spawn_worker(addr: str, data_root: Path, cache_dir: Path):
    """The external worker host. The bench process's own nodes compute on
    the default device, which may be a chip that serves one process at a
    time, so this host computes on the CPU (JAX_PLATFORMS=cpu)."""
    env = dict(os.environ,
               PYTHONPATH=str(Path(__file__).resolve().parents[1] / "src")
               + os.pathsep + os.environ.get("PYTHONPATH", ""),
               REPRO_CACHE_DIR=str(cache_dir), JAX_PLATFORMS="cpu")
    return subprocess.Popen(
        [sys.executable, "-m", "repro.dist.rpc", "work", "--addr", addr,
         "--pipeline", PIPELINE, "--data-root", str(data_root),
         "--node-id", "bench-ext"],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)


def _run_fetch(td: Path, ds, units, rows, report):
    from repro.dist import BlobServer, InputCache, PeerFabric

    # -- fetch stage: cold vs warm, interleaved medians ----------------------
    cold_meds, warm_meds = [], []
    gb = 0.0
    cold_total = warm_total = 0.0
    for rep in range(FETCH_REPS):
        cache = InputCache(td / f"cache-{rep}", max_bytes=1 << 30)
        cold, nbytes, cold_sum = _median_fetch(units, ds.root, cache)
        warm, _, warm_sum = _median_fetch(units, ds.root, cache)
        cold_meds.append(cold)
        warm_meds.append(warm)
        cold_total += cold_sum
        warm_total += warm_sum
        gb = nbytes * 8 / 1e9
    cold_ms = statistics.median(cold_meds) * 1e3
    warm_ms = statistics.median(warm_meds) * 1e3
    warm_below_cold = warm_ms < cold_ms
    rows.append(("rpc_fetch_unit_latency_cold_ms", round(cold_ms, 4),
                 f"median per-unit input fetch+verify, cache miss "
                 f"(median of {FETCH_REPS} reps)"))
    rows.append(("rpc_fetch_unit_latency_warm_ms", round(warm_ms, 4),
                 "as above on the warmed host cache"))
    rows.append(("rpc_fetch_gbps_cold",
                 round(gb * FETCH_REPS / cold_total, 3),
                 "input bits moved / cold fetch-stage seconds"))
    rows.append(("rpc_fetch_gbps_warm",
                 round(gb * FETCH_REPS / warm_total, 3),
                 "as above served from the host cache"))
    rows.append(("rpc_warm_below_cold", int(warm_below_cold),
                 "acceptance gate: warm unit latency strictly below cold"))

    # -- fetch stage, third arm: warm-from-peer ------------------------------
    # one host's cache holds every blob and serves it over the blob
    # fabric; a cold sibling fetches content-addressed from that peer
    # instead of reading shared storage. Cold-from-storage vs warm-local
    # vs warm-from-peer is the paper's 0.60/0.33 Gb/s framing with the
    # node-to-node link as the third path.
    peer_meds = []
    peer_total = 0.0
    peer_hits = peer_fallbacks = 0
    for rep in range(FETCH_REPS):
        serve = InputCache(td / f"peer-serve-{rep}", max_bytes=1 << 30)
        _median_fetch(units, ds.root, serve)     # warm the serving host
        with BlobServer(serve) as srv:
            fetcher = InputCache(td / f"peer-fetch-{rep}", max_bytes=1 << 30)
            fetcher.attach_fabric(PeerFabric(
                lambda ds_, _s=serve.summary, _a=srv.addr_str:
                    {d: [_a] for d in ds_ if d in _s}))
            peer, _, peer_sum = _median_fetch(units, ds.root, fetcher)
        fst = fetcher.stats()
        peer_hits += fst["peer_hits"]
        peer_fallbacks += fst["misses"] - fst["peer_hits"]
        peer_meds.append(peer)
        peer_total += peer_sum
    peer_ms = statistics.median(peer_meds) * 1e3
    rows.append(("rpc_fetch_unit_latency_peer_ms", round(peer_ms, 4),
                 "as cold, served from a warm peer over the blob fabric "
                 "instead of shared storage"))
    rows.append(("rpc_fetch_gbps_peer",
                 round(gb * FETCH_REPS / peer_total, 3),
                 f"input bits moved / peer fetch-stage seconds "
                 f"({peer_hits} peer hits, {peer_fallbacks} storage "
                 f"fallbacks); paper reference "
                 f"{PAPER_REFERENCE_GBPS['lab_network']} (lab) vs "
                 f"{PAPER_REFERENCE_GBPS['cloud_storage']} (cloud)"))
    report["fetch"] = {
        "cold_ms_median": cold_ms, "warm_ms_median": warm_ms,
        "peer_ms_median": peer_ms,
        "cold_ms_samples": [round(m * 1e3, 4) for m in cold_meds],
        "warm_ms_samples": [round(m * 1e3, 4) for m in warm_meds],
        "peer_ms_samples": [round(m * 1e3, 4) for m in peer_meds],
        "peer_hits": peer_hits, "peer_fallbacks": peer_fallbacks,
        "warm_below_cold": warm_below_cold,
    }
    return (None if warm_below_cold else
            f"warm-cache fetch latency {warm_ms:.3f}ms not below cold "
            f"{cold_ms:.3f}ms — cache regression")


def _run_e2e(td: Path, ds, pipe, rows, report):
    from repro.core import Provenance, query_available_work
    from repro.dist import ClusterRunner

    # local nodes talk to the coordinator over the socket transport; one
    # genuinely separate worker process joins the same queue
    deriv = Path(ds.root) / "derivatives"
    host_cache = td / "host-cache"
    ext_cache = td / "ext-cache"
    in_bits = SHAPE[0] * SHAPE[1] * SHAPE[2] * 4 * 8 * N_SUBJECTS * SESSIONS
    e2e = {}
    for phase in ("cold", "warm"):
        units_now, _ = query_available_work(ds, pipe)
        runner = ClusterRunner(pipe, ds.root, nodes=2, transport="rpc",
                               poll_s=0.03, cache_dir=host_cache)
        got = {}
        t = threading.Thread(
            target=lambda: got.update(r=runner.run(units_now)))
        t0 = time.time()
        t.start()
        while runner.server is None and t.is_alive():
            time.sleep(0.005)
        worker = (None if runner.server is None else
                  _spawn_worker(runner.server.addr_str, ds.root, ext_cache))
        t.join()
        dt = time.time() - t0
        if worker is not None:
            worker.wait(timeout=60)
        results = got.get("r", [])
        ok = sum(r.status == "ok" for r in results)
        hits = sum(1 for u in units_now
                   if (p := Provenance.load(Path(u.out_dir))) is not None
                   and p.cache_hit)
        # bytes served per link (coordinator-host cache counters; the
        # external worker's cache adds to the real saving but reports in
        # its own process) -> effective storage-link Gb/s vs the paper's
        cstats = runner.stats.cache or {}
        bfc = cstats.get("bytes_from_cache", 0)
        bfs = cstats.get("bytes_from_storage", 0)
        e2e[phase] = {"seconds": round(dt, 3), "ok": ok,
                      "units": len(units_now), "cache_hit_commits": hits,
                      "external_worker_role": "cpu-host",
                      "images_per_s": round(ok / dt, 3),
                      "gbps": round(in_bits / dt / 1e9, 3),
                      "bytes_from_cache": bfc,
                      "bytes_from_storage": bfs,
                      "storage_gbps": round(bfs * 8 / dt / 1e9, 3),
                      "remote_nodes": runner.stats.remote_nodes,
                      "processed": runner.stats.processed}
        rows.append((f"rpc_e2e_images_per_s_{phase}", e2e[phase]["images_per_s"],
                     f"{ok}/{len(units_now)} ok in {dt:.2f}s over socket "
                     f"transport, {hits} cache-hit commits; the external "
                     f"worker is a CPU host (JAX_PLATFORMS=cpu)"))
        rows.append((f"rpc_e2e_effective_gbps_{phase}",
                     e2e[phase]["gbps"],
                     f"input bits consumed / wall-clock "
                     f"({bfc} B from cache, {bfs} B from storage); paper "
                     f"reference {PAPER_REFERENCE_GBPS['lab_network']} "
                     f"(lab) vs {PAPER_REFERENCE_GBPS['cloud_storage']} "
                     f"(cloud)"))
        shutil.rmtree(deriv, ignore_errors=True)
    report["e2e"] = e2e


def _hotpath_units(n: int, pool: int):
    """Synthetic WorkUnits with manifest digests drawn from a shared pool:
    each digest recurs in ~4 units (once per access pattern), so summaries
    actually overlap the backlog the way a real campaign's inputs do."""
    from repro.core.query import WorkUnit
    mib = 1 << 20
    return [WorkUnit(
        dataset="hot", subject=f"s{i:06d}", session="01",
        pipeline=PIPELINE, pipeline_digest="bench",
        inputs={"T1w": f"in/{i}_a.nii", "T2w": f"in/{i}_b.nii"},
        out_dir=f"derivatives/{PIPELINE}/s{i:06d}/01",
        input_digests={"T1w": f"d{i % pool:08d}",
                       "T2w": f"d{(i * 7 + 3) % pool:08d}"},
        input_bytes={"T1w": mib, "T2w": mib}) for i in range(n)]


def _run_hotpath(rows, report):
    from repro.dist.cache import DigestSummary
    from repro.dist.placement import best_node, unit_local_bytes
    from repro.dist.queue import WorkQueue
    from repro.dist.rpc import QueueClient, QueueServer

    n = max(HOTPATH_NODES, int(os.environ.get(HOTPATH_UNITS_ENV,
                                              str(HOTPATH_UNITS))))
    pool = max(1, n // 2)
    units = _hotpath_units(n, pool)

    # the drain threads are CPU-bound pure Python; at the default 5ms GIL
    # switch interval the heartbeat's measured latency is mostly scheduler
    # handoff, not the lock holds the gate is about
    switch0 = sys.getswitchinterval()
    sys.setswitchinterval(0.0005)

    # each node's cache holds a contiguous quarter of the digest pool; the
    # wire carries the Bloom filter plus the exact digest list, exactly the
    # InputCache.summary_sync() shape
    wires = {}
    share = max(1, pool // HOTPATH_NODES)
    for j in range(HOTPATH_NODES):
        held = [f"d{d:08d}" for d in range(j * share,
                                           min(pool, (j + 1) * share))]
        summ = DigestSummary(m=1 << 16)
        for d in held:
            summ.add(d)
        wires[f"hp{j}"] = {"v": 1, "full": summ.to_wire(), "digests": held}

    class _CappedQueue(WorkQueue):
        """The pre-index coordinator, reconstructed for the baseline: every
        score is a live Bloom re-probe (:func:`unit_local_bytes`) and any
        backlog fill or steal past the old 512-entry scan cap degrades to
        the blind FIFO / tail-half shape it used to."""
        SCAN_CAP = 512

        def _local_bytes(self, idx, node_id):
            if not self.locality:
                return 0
            return unit_local_bytes(self.units[idx],
                                    self._summaries.get(node_id))

        def _best_node(self, idx, candidates):
            return best_node(self.units[idx], candidates,
                             self._summaries if self.locality else {},
                             {nd: len(q) for nd, q in self._queues.items()})

        def _fill_from_backlog(self, node_id):
            if len(self._backlog_seq) <= self.SCAN_CAP:
                return super()._fill_from_backlog(node_id)
            alive = max(1, sum(1 for nd in self._queues
                               if nd not in self._dead))
            k = max(1, len(self._backlog_seq) // alive)
            q = self._queues[node_id]
            for _ in range(k):
                idx = self._backlog_pop_fifo()
                if idx is None:
                    break
                q.append(idx)

        def _steal_into(self, thief):
            lens = {nd: len(q) for nd, q in self._queues.items()
                    if nd != thief and nd not in self._dead and len(q)}
            if not lens:
                return
            deepest = max(lens.values())
            tied = sorted(nd for nd, l in lens.items() if l == deepest)
            victim = tied[self._steal_rr % len(tied)]
            self._steal_rr += 1
            vq = self._queues[victim]
            k = max(1, len(vq) // 2)
            if ((self._node_scores(thief) or self._node_scores(victim))
                    and len(vq) <= self.SCAN_CAP):
                order = sorted(range(len(vq)),
                               key=lambda p: (self._local_bytes(vq[p], victim),
                                              -self._local_bytes(vq[p], thief),
                                              -p))
                take = set(order[:k])
                grabbed = [vq[p] for p in sorted(take)]
                self._queues[victim] = deque(idx for p, idx in enumerate(vq)
                                             if p not in take)
                self.locality_stats["steals_scored"] += 1
                self.locality_stats["stolen_local_bytes"] += \
                    sum(self._local_bytes(i, thief) for i in grabbed)
            else:
                grabbed = [vq.pop() for _ in range(k)]
                grabbed.reverse()
                self.locality_stats["steals_blind"] += 1
            self._queues[thief].extend(grabbed)
            self.steals[thief] += 1

    def drive(queue_cls):
        t0 = time.perf_counter()
        q = queue_cls(units, [f"hp{j}" for j in range(HOTPATH_NODES)],
                      partition="backlog", locality=True, lease_ttl_s=3600.0)
        build_ms = (time.perf_counter() - t0) * 1e3
        for nid, wire in wires.items():
            assert q.put_summary(nid, wire)
        # prime each node's backlog fill outside the clock: the fill is a
        # once-per-registration event in both variants, and the arm gates on
        # the steady-state grant path, not the registration burst
        primed = 0
        for j in range(HOTPATH_NODES):
            got = q.next_units(f"hp{j}", 1)
            q.complete_batch([{"idx": lease.unit_idx, "node_id": f"hp{j}",
                               "status": "ok"} for _u, lease in got])
            primed += len(got)
        stop = threading.Event()
        tail = threading.Event()
        granted = [0] * HOTPATH_NODES
        granted[0] = primed
        hb_lat = []

        def drain(j):
            # the pause between batches stands in for compute: without it
            # the four drains hold the lock back-to-back and the heartbeat
            # only ever measures total saturation, where any two
            # implementations converge. With it, heartbeat latency tracks
            # what one grant/complete batch holds the lock for — the
            # quantity the old scan cap existed to bound
            nid = f"hp{j}"
            while not stop.is_set():
                got = q.next_units(nid, HOTPATH_BATCH)
                if not got:
                    break
                q.complete_batch([{"idx": lease.unit_idx, "node_id": nid,
                                   "status": "ok"} for _u, lease in got])
                granted[j] += len(got)
                stop.wait(0.0005)

        def beat():
            # node-level liveness with a piggybacked summary delta (one
            # digest in, one out: a churning LRU cache); the latency a real
            # worker's heartbeat would see behind the grant lock. Samples
            # count only while every drain is busy (``tail`` unset): the
            # gate is about steady-state granting, not the end-of-queue
            # scramble where idle nodes churn steals in both variants
            i = 0
            while not stop.is_set():
                delta = {"v": 1, "add": [f"d{(i + 1) % pool:08d}"],
                         "drop": [f"d{i % pool:08d}"]}
                h0 = time.perf_counter()
                q.heartbeat("hp0", summary_delta=delta)
                if not tail.is_set():
                    hb_lat.append(time.perf_counter() - h0)
                i += 1
                stop.wait(0.0001)

        drains = [threading.Thread(target=drain, args=(j,), daemon=True)
                  for j in range(HOTPATH_NODES)]
        hb = threading.Thread(target=beat, daemon=True)
        t0 = time.perf_counter()
        for t in drains:
            t.start()
        hb.start()
        deadline = t0 + HOTPATH_DEADLINE_S
        while (all(t.is_alive() for t in drains)
               and time.perf_counter() < deadline):
            time.sleep(0.005)
        tail.set()                     # first node ran dry: steady state over
        while (any(t.is_alive() for t in drains)
               and time.perf_counter() < deadline):
            time.sleep(0.01)
        elapsed = time.perf_counter() - t0
        stop.set()
        for t in drains:
            t.join(timeout=30)
        hb.join(timeout=30)
        lat = sorted(hb_lat)
        p99_ms = (lat[int(0.99 * (len(lat) - 1))] * 1e3) if lat else 0.0
        ls = q.locality_stats
        return {"grants": sum(granted),
                "grants_per_s": round(sum(granted) / elapsed, 1),
                "hb_p99_ms": round(p99_ms, 4),
                "hb_samples": len(lat),
                "seconds": round(elapsed, 3),
                "build_ms": round(build_ms, 2),
                "scored_grants": ls["scored_grants"],
                "blind_grants": ls["blind_grants"],
                "warm_fraction": round(ls["local_bytes_granted"]
                                       / max(1, ls["input_bytes_granted"]), 4),
                "finished": q.finished()}

    try:
        capped = drive(_CappedQueue)
        indexed = drive(WorkQueue)
    finally:
        sys.setswitchinterval(switch0)
    for label, r in (("capped", capped), ("index", indexed)):
        rows.append((f"rpc_hotpath_grants_per_s_{label}", r["grants_per_s"],
                     f"{r['grants']}/{n} units granted+completed in "
                     f"{r['seconds']}s by {HOTPATH_NODES} nodes (batch "
                     f"{HOTPATH_BATCH}); queue build {r['build_ms']}ms"))
        rows.append((f"rpc_hotpath_hb_p99_ms_{label}", r["hb_p99_ms"],
                     f"p99 heartbeat+delta latency over {r['hb_samples']} "
                     f"beats behind the grant lock"))
        rows.append((f"rpc_hotpath_warm_fraction_{label}", r["warm_fraction"],
                     f"cache-local / total input bytes granted "
                     f"({r['scored_grants']} scored, {r['blind_grants']} "
                     f"blind grants) — the placement the cap was blind to"))
    hot_ok = (indexed["grants_per_s"] > capped["grants_per_s"]
              and indexed["hb_p99_ms"] < capped["hb_p99_ms"])
    rows.append(("rpc_hotpath_index_wins", int(hot_ok),
                 "acceptance gate: index-backed queue grants strictly "
                 "faster AND holds heartbeat p99 strictly lower than the "
                 "512-capped baseline"))
    report["hotpath"] = {"units": n, "nodes": HOTPATH_NODES,
                         "batch": HOTPATH_BATCH, "capped": capped,
                         "index": indexed, "index_wins": hot_ok}

    # -- socket micro-arm: per-op JSON-lines vs batched binary frames --------
    wunits = units[:WIRE_UNITS]
    wire = {}
    for mode in ("perop_jsonl", "batched_binary"):
        wq = WorkQueue(wunits, partition="backlog", locality=False,
                       lease_ttl_s=3600.0)
        with QueueServer(wq) as srv:
            cli = QueueClient(srv.address, binary=(mode == "batched_binary"))
            try:
                cli.register("w0")
                t0 = time.perf_counter()
                if mode == "batched_binary":
                    while True:
                        got = cli.next_units("w0", HOTPATH_BATCH)
                        if not got:
                            break
                        cli.complete_batch(
                            [{"idx": lease.unit_idx, "node_id": "w0",
                              "status": "ok"} for _u, lease in got])
                else:
                    while True:
                        one = cli.next_unit("w0")
                        if one is None:
                            break
                        cli.complete(one[1].unit_idx, "w0", "ok")
                dt = time.perf_counter() - t0
            finally:
                cli.close()
        wire[mode] = round(len(wunits) / dt, 1)
    rows.append(("rpc_wire_perop_jsonl_units_per_s", wire["perop_jsonl"],
                 f"{len(wunits)} units granted+completed per-op over "
                 f"JSON-lines (2 round trips per unit)"))
    rows.append(("rpc_wire_batched_binary_units_per_s",
                 wire["batched_binary"],
                 f"as above, batches of {HOTPATH_BATCH} over binary frames "
                 f"(2 round trips per {HOTPATH_BATCH} units)"))
    report["wire"] = wire
    return (None if hot_ok else
            f"index-backed hot path not strictly better: grants/s "
            f"{indexed['grants_per_s']} vs capped {capped['grants_per_s']}, "
            f"hb p99 {indexed['hb_p99_ms']}ms vs {capped['hb_p99_ms']}ms")


def _run_inproc():
    arms = {a.strip() for a in
            os.environ.get(ARMS_ENV, DEFAULT_ARMS).split(",") if a.strip()}
    rows = []
    report: dict = {"units": N_SUBJECTS * SESSIONS, "shape": list(SHAPE),
                    "arms": sorted(arms)}
    gate_errors = []
    if arms & {"fetch", "e2e"}:
        from repro.core import (builtin_pipelines, query_available_work,
                                synthesize_dataset)
        with tempfile.TemporaryDirectory() as td:
            td = Path(td)
            ds = synthesize_dataset(td / "ds", "rpcbench",
                                    n_subjects=N_SUBJECTS,
                                    sessions_per_subject=SESSIONS,
                                    shape=SHAPE)
            pipe = builtin_pipelines()[PIPELINE]
            units, _ = query_available_work(ds, pipe)
            if "fetch" in arms:
                err = _run_fetch(td, ds, units, rows, report)
                if err:
                    gate_errors.append(err)
            if "e2e" in arms:
                _run_e2e(td, ds, pipe, rows, report)
    if "hotpath" in arms:
        err = _run_hotpath(rows, report)
        if err:
            gate_errors.append(err)
    report["paper_reference_gbps"] = PAPER_REFERENCE_GBPS
    out = Path(os.environ.get("REPRO_BENCH_JSON", _JSON_OUT))
    out.parent.mkdir(parents=True, exist_ok=True)
    report["rows"] = [[n, v, d] for n, v, d in rows]
    out.write_text(json.dumps(report, indent=1))
    # gates fail *after* the JSON lands, so the artifact always shows the
    # numbers the failure is about
    if gate_errors:
        raise RuntimeError("; ".join(gate_errors))
    return rows


def run():
    """Benchmark entry (benchmarks.run): re-exec pinned — see ``_pin``."""
    return run_pinned("benchmarks.rpc_throughput", "rpc_",
                      _INPROC_FLAG, _run_inproc, timeout=1800)


if __name__ == "__main__":
    for row in run():
        print(",".join(str(c) for c in row))
