"""Networked WorkQueue transport + per-host input cache: protocol unit tests
(renew vs reap, register/backlog, JSON-lines framing), cache behaviour under
size pressure, and the ISSUE acceptance run — a 64-unit chaos schedule over
the socket transport with a worker in a genuinely separate process."""
import json
import os
import socket
import subprocess
import sys
import threading
import time
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from conftest import wait_until

from repro.core import (Provenance, builtin_pipelines, query_available_work,
                        synthesize_dataset)
from repro.core.workflow import load_unit_inputs
from repro.dist import (ClusterRunner, InputCache, QueueClient, QueueServer,
                        WorkQueue, cache_from_env)

REPO_ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture()
def dataset(tmp_path):
    return synthesize_dataset(tmp_path / "ds", "rpcds", n_subjects=4,
                              sessions_per_subject=2, shape=(10, 10, 10))


def _work(dataset):
    pipe = builtin_pipelines()["bias_correct"]
    units, _ = query_available_work(dataset, pipe)
    return pipe, units


# ---------------------------------------------------------------------------
# input cache
# ---------------------------------------------------------------------------

def test_cache_hit_returns_identical_digest_and_bytes(dataset, tmp_path):
    pipe, units = _work(dataset)
    cache = InputCache(tmp_path / "cache", max_bytes=1 << 30)
    i1, sums1, hit1, hb1, *_ = load_unit_inputs(units[0], dataset.root,
                                                cache=cache)
    i2, sums2, hit2, hb2, *_ = load_unit_inputs(units[0], dataset.root,
                                                cache=cache)
    assert (hit1, hit2) == (False, True)
    assert sums1 == sums2                       # provenance-identical digests
    for k in i1:
        assert np.array_equal(i1[k], i2[k])
    assert cache.stats()["hits"] == 1 and cache.stats()["misses"] == 1


def test_cache_eviction_under_size_pressure(dataset, tmp_path):
    pipe, units = _work(dataset)
    one_input = (Path(dataset.root) / units[0].inputs["T1w"]).stat().st_size
    # room for roughly two blobs: filling with 8 units must evict
    cache = InputCache(tmp_path / "cache", max_bytes=int(one_input * 2.5))
    for u in units:
        load_unit_inputs(u, dataset.root, cache=cache)
    st = cache.stats()
    assert st["evictions"] >= len(units) - 3
    assert st["bytes"] <= int(one_input * 2.5)
    assert cache.blob_count() <= 2
    # evicted entries re-fetch (miss), survivors still hit
    _, _, hit_last, *_ = load_unit_inputs(units[-1], dataset.root, cache=cache)
    _, _, hit_first, *_ = load_unit_inputs(units[0], dataset.root, cache=cache)
    assert hit_last is True                     # most recent blob survived
    assert hit_first is False                   # LRU victim re-fetched


def test_cache_lru_order_touch_on_hit(tmp_path, dataset):
    pipe, units = _work(dataset)
    one = (Path(dataset.root) / units[0].inputs["T1w"]).stat().st_size
    cache = InputCache(tmp_path / "c", max_bytes=int(one * 2.5))
    load_unit_inputs(units[0], dataset.root, cache=cache)
    load_unit_inputs(units[1], dataset.root, cache=cache)
    load_unit_inputs(units[0], dataset.root, cache=cache)   # touch 0
    load_unit_inputs(units[2], dataset.root, cache=cache)   # evicts 1, not 0
    assert load_unit_inputs(units[0], dataset.root, cache=cache)[2] is True
    assert load_unit_inputs(units[1], dataset.root, cache=cache)[2] is False


def test_cache_oversize_input_passes_through_without_wiping(dataset, tmp_path):
    """An input bigger than the whole budget is served but never inserted —
    inserting it would evict every warm blob for a blob that is itself
    immediately evicted."""
    pipe, units = _work(dataset)
    one = (Path(dataset.root) / units[0].inputs["T1w"]).stat().st_size
    cache = InputCache(tmp_path / "c", max_bytes=one + 1)   # fits exactly one
    load_unit_inputs(units[0], dataset.root, cache=cache)   # warm blob
    big = tmp_path / "big.npy"
    np.save(big, np.zeros(one, dtype=np.float64))           # > max_bytes
    arr, digest, origin, nbytes, _ = cache.fetch_array(big)
    assert origin == "storage" and arr.nbytes > cache.max_bytes
    st = cache.stats()
    assert st["evictions"] == 0 and st["blobs"] == 1        # warm blob intact
    assert load_unit_inputs(units[0], dataset.root, cache=cache)[2] is True


def test_cache_corrupt_blob_degrades_to_miss(dataset, tmp_path):
    pipe, units = _work(dataset)
    cache = InputCache(tmp_path / "cache")
    _, sums, *_ = load_unit_inputs(units[0], dataset.root, cache=cache)
    digest = next(iter(sums.values()))
    (cache.blob_dir / digest).write_bytes(b"garbage")
    arr, sums2, hit, *_ = load_unit_inputs(units[0], dataset.root, cache=cache)
    assert hit is False                          # verified hit failed -> miss
    assert sums2 == sums                         # refetched, digest intact


def test_cache_persists_across_instances(dataset, tmp_path):
    pipe, units = _work(dataset)
    c1 = InputCache(tmp_path / "cache")
    load_unit_inputs(units[0], dataset.root, cache=c1)
    c2 = InputCache(tmp_path / "cache")          # restarted worker
    _, _, hit, *_ = load_unit_inputs(units[0], dataset.root, cache=c2)
    assert hit is True


def test_cache_source_change_is_not_served_stale(dataset, tmp_path):
    pipe, units = _work(dataset)
    cache = InputCache(tmp_path / "cache")
    src = Path(dataset.root) / units[0].inputs["T1w"]
    _, sums1, *_ = load_unit_inputs(units[0], dataset.root, cache=cache)
    arr = np.load(src) + 1.0
    np.save(src, arr)                            # source mutated in place
    os.utime(src, ns=(1, 1))                     # force a new mtime key too
    _, sums2, hit, *_ = load_unit_inputs(units[0], dataset.root, cache=cache)
    assert hit is False
    assert sums1 != sums2                        # new content, new digest


def test_cache_from_env(tmp_path, monkeypatch):
    monkeypatch.delenv("REPRO_CACHE_DIR", raising=False)
    assert cache_from_env() is None
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path / "envcache"))
    monkeypatch.setenv("REPRO_CACHE_MAX_MB", "2")
    cache = cache_from_env()
    assert cache is not None and cache.max_bytes == 2 * 2**20


# ---------------------------------------------------------------------------
# renew / register / backlog (queue-level, no sockets)
# ---------------------------------------------------------------------------

def test_renew_refreshes_valid_lease_only(dataset):
    pipe, units = _work(dataset)
    q = WorkQueue(units, ["a", "b"])
    unit, lease = q.next_unit("a")
    assert q.renew(lease.unit_idx, "a", lease.epoch) is True
    assert q.renew(lease.unit_idx, "b", lease.epoch) is False   # wrong holder
    assert q.renew(lease.unit_idx, "a", lease.epoch + 7) is False
    q.complete(lease.unit_idx, "a", "ok")
    assert q.renew(lease.unit_idx, "a", lease.epoch) is False   # retired
    # the retired-unit rejection is routine (renew raced its own completion)
    # and stays out of the WAN-health counter; the two stale ones count
    assert q.renew_rejections == 2


def test_renew_racing_reap_is_rejected_after_epoch_bump(dataset):
    """The WAN failure ISSUE names: a node's lease is reaped and re-granted
    (epoch bump) while its renew is in flight — the stale renewal must be
    rejected and the exactly-one-retirement invariant preserved."""
    t = {"now": 0.0}
    pipe, units = _work(dataset)
    q = WorkQueue(units, ["a", "b"], lease_ttl_s=1.0, now=lambda: t["now"])
    unit, lease = q.next_unit("a")
    t["now"] = 1.5
    q.heartbeat("b")
    assert lease.unit_idx in q.reap()            # a reaped, unit requeued
    # the re-grant bumps the epoch; a's in-flight renew names the old one
    got = None
    while got is None or got[1].unit_idx != lease.unit_idx:
        got = q.next_unit("b")
    assert got[1].epoch == lease.epoch + 1
    assert q.renew(lease.unit_idx, "a", lease.epoch) is False
    # and the zombie's completion is ignored: b's grant is authoritative
    q.complete(lease.unit_idx, "a", "failed")
    assert q.pending() == len(units)
    q.complete(lease.unit_idx, "b", "ok")
    assert q.done_status()[lease.unit_idx] == "ok"


def test_renew_twin_lease(dataset):
    pipe, units = _work(dataset)
    q = WorkQueue(units, ["a", "b"])
    unit, lease = q.next_unit("a")
    q.mark_started(lease.unit_idx)
    twin = q.speculate(lease.unit_idx, "b")
    assert q.renew(twin.unit_idx, "b", twin.epoch) is True
    assert q.renew(twin.unit_idx, "b", twin.epoch - 1) is False


def test_register_joins_and_dead_id_stays_dead(dataset):
    pipe, units = _work(dataset)
    q = WorkQueue(units, ["a"])
    assert q.register("late") is True
    assert "late" in q.alive_nodes()
    got = q.next_unit("late")                    # steals from a's deque
    assert got is not None
    q.mark_dead("late")
    assert q.register("late") is False


def test_zero_node_queue_holds_backlog_until_register(dataset):
    pipe, units = _work(dataset)
    q = WorkQueue(units)                         # no nodes yet
    assert q.pending() == len(units)
    assert q.register("w0")
    leased = [q.next_unit("w0") for _ in range(len(units))]
    assert all(l is not None for l in leased)
    assert q.next_unit("w0") is None             # drained
    # second registrant steals from the first's deque next time around
    assert q.register("w1")


def test_unknown_node_fails_soft(dataset):
    pipe, units = _work(dataset)
    q = WorkQueue(units, ["a"])
    assert q.next_unit("ghost") is None
    q.heartbeat("ghost")                         # dropped, not auto-joined
    assert "ghost" not in q.alive_nodes()
    assert q.reap() == []


# ---------------------------------------------------------------------------
# socket transport
# ---------------------------------------------------------------------------

def test_rpc_roundtrip_matches_inprocess_surface(dataset):
    pipe, units = _work(dataset)
    q = WorkQueue(units, ["a", "b"])
    with QueueServer(q) as srv:
        c = QueueClient(srv.address)
        unit, lease = c.next_unit("a")
        assert unit.job_id == q.units[lease.unit_idx].job_id
        assert lease.epoch == 1 and not lease.speculative
        c.mark_started(lease.unit_idx)
        assert c.renew(lease.unit_idx, "a", lease.epoch) is True
        twin = c.speculate(lease.unit_idx, "b")
        assert twin is not None and twin.speculative
        c.complete(lease.unit_idx, "a", "ok",
                   meta={"seconds": 0.25, "attempts": 1, "error": None})
        snap = c.results_snapshot()
        assert snap["primaries"][lease.unit_idx]["seconds"] == 0.25
        assert c.done_status() == q.done_status()
        assert c.pending() == len(units) - 1
        assert c.queue_depths() == q.queue_depths()
        assert c.active_leases() == q.active_leases()
        assert c.alive_nodes() == q.alive_nodes()
        assert isinstance(c.steals, dict) and isinstance(c.requeues, list)
        c.close()


def test_rpc_unknown_method_and_bad_params_report_not_crash(dataset):
    pipe, units = _work(dataset)
    q = WorkQueue(units, ["a"])
    with QueueServer(q) as srv:
        c = QueueClient(srv.address)
        with pytest.raises(RuntimeError, match="unknown method"):
            c._call("shutdown")
        with pytest.raises(RuntimeError, match="TypeError"):
            c._call("next_unit", nonsense=1)
        # the connection survives an errored request
        assert c.next_unit("a") is not None
        c.close()


def test_rpc_dropped_connection_raises_connection_error(dataset):
    pipe, units = _work(dataset)
    q = WorkQueue(units, ["a"])
    srv = QueueServer(q).start()
    c = QueueClient(srv.address)
    assert c.finished() is False
    srv.stop()
    with pytest.raises(ConnectionError):
        for _ in range(10):                      # buffered writes may need >1
            c.heartbeat("a")
            time.sleep(0.05)
    c.close()


def test_cluster_rpc_transport_completes_and_caches(dataset, tmp_path):
    """ClusterRunner + Node run unchanged over the socket: same results,
    provenance carries node ids, and a warm re-run commits cache hits."""
    pipe, units = _work(dataset)
    runner = ClusterRunner(pipe, dataset.root, nodes=2, transport="rpc",
                           poll_s=0.03, cache_dir=tmp_path / "host-cache")
    results = runner.run(units)
    assert sum(r.status == "ok" for r in results) == len(units)
    assert runner.stats.cache is not None
    assert runner.stats.cache["misses"] >= 1
    # wipe derivatives, keep the cache: the re-run is all hits
    import shutil
    shutil.rmtree(Path(dataset.root) / "derivatives")
    units2, _ = query_available_work(dataset, pipe)
    runner2 = ClusterRunner(pipe, dataset.root, nodes=2, transport="rpc",
                            poll_s=0.03, cache_dir=tmp_path / "host-cache")
    results2 = runner2.run(units2)
    assert sum(r.status == "ok" for r in results2) == len(units2)
    hit_commits = [Provenance.load(Path(u.out_dir)).cache_hit for u in units2]
    assert any(hit_commits)
    assert runner2.stats.cache["hits"] >= 1


# ---------------------------------------------------------------------------
# peer-fabric version skew, both directions
# ---------------------------------------------------------------------------

def test_new_client_downgrades_blob_addr_against_pre_fabric_server(dataset):
    """New worker vs old coordinator: a server whose queue predates
    ``blob_addr`` rejects it with a TypeError; the client sheds that one
    param and keeps its summary — fabric-invisible, still locality-aware."""
    pipe, units = _work(dataset)

    class _PreFabricQueue(WorkQueue):
        def register(self, node_id, summary=None):
            return super().register(node_id, summary=summary)

        def heartbeat(self, node_id, summary_delta=None):
            return super().heartbeat(node_id, summary_delta=summary_delta)

    q = _PreFabricQueue(units, [])
    with QueueServer(q) as srv:
        c = QueueClient(srv.address)
        wire = {"v": 1, "full": {"v": 1, "m": 8, "k": 2, "n": 1,
                                 "nz": [[0, 1]]}}
        assert c.register("w", summary=wire, blob_addr="wh:9") is True
        assert c._fabric_ok is False
        assert c._summaries_ok is True               # only one rung shed
        assert "w" in q.stats_snapshot()["summary_nodes"]
        c.heartbeat("w", blob_addr="wh:9")           # silently bare now
        assert c.next_unit("w") is not None          # scheduling unaffected
        c.close()


def test_new_client_downgrades_stepwise_against_ancient_server(dataset):
    """A coordinator that predates summaries AND the fabric: the client
    sheds blob_addr first, then the summary, and still registers."""
    pipe, units = _work(dataset)

    class _AncientQueue(WorkQueue):
        def register(self, node_id):
            return super().register(node_id)

    q = _AncientQueue(units, [])
    with QueueServer(q) as srv:
        c = QueueClient(srv.address)
        wire = {"v": 1, "full": {"v": 1, "m": 8, "k": 2, "n": 1,
                                 "nz": [[0, 1]]}}
        assert c.register("w", summary=wire, blob_addr="wh:9") is True
        assert c._fabric_ok is False and c._summaries_ok is False
        assert c.next_unit("w") is not None
        c.close()


def test_locate_blobs_returns_empty_against_pre_fabric_server(
        dataset, monkeypatch):
    """New fetcher vs old coordinator: ``locate_blobs`` degrades to ``{}``
    on the first "unknown method" (the pre-fabric behaviour: go read shared
    storage) and never pays a doomed RPC again."""
    from repro.dist import rpc as rpc_mod
    pipe, units = _work(dataset)
    monkeypatch.setattr(rpc_mod, "_METHODS",
                        rpc_mod._METHODS - {"locate_blobs"})
    q = WorkQueue(units, ["a"])
    with QueueServer(q) as srv:
        c = QueueClient(srv.address)
        assert c.locate_blobs(["d" * 64], node_id="a") == {}
        assert c._fabric_ok is False
        assert c.locate_blobs(["d" * 64]) == {}      # no second wire call
        # the downgrade also stops blob_addr advertisements cold
        assert c.register("w", blob_addr="wh:1") is True
        assert q.stats_snapshot()["fabric_nodes"] == []
        c.close()


def test_old_worker_is_fabric_invisible_on_new_coordinator(dataset):
    """Old worker vs new coordinator: a client that never sends blob_addr
    (the pre-fabric wire, byte for byte) is simply never routed to —
    everything else it does is untouched."""
    pipe, units = _work(dataset)
    q = WorkQueue(units, [])
    with QueueServer(q) as srv:
        c = QueueClient(srv.address)
        assert c._call("register", node_id="oldw") is True   # bare old wire
        c._call("heartbeat", node_id="oldw")
        assert q.stats_snapshot()["fabric_nodes"] == []
        assert q.locate_blobs(["d" * 64]) == {}
        assert c.next_unit("oldw") is not None
        c.close()


# ---------------------------------------------------------------------------
# invariant under transport / cache / renewal harassment
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("transport,cache,harass,locality,peers", [
    ("rpc", False, False, False, False),
    ("rpc", True, False, False, False),
    ("local", True, True, False, False),
    ("local", False, False, True, False),   # locality harassment mode
    ("rpc", False, True, True, False),      # both harassers over the socket
    ("local", False, False, False, True),   # peer-fabric harassment mode
    ("rpc", False, True, False, True),      # hostile peers over the socket
])
def test_cluster_invariant_over_transport(transport, cache, harass, locality,
                                          peers):
    from cluster_invariant import check_cluster_invariant
    check_cluster_invariant(2, 2, 3, True, 1, transport=transport,
                            cache=cache, harass_renew=harass,
                            harass_locality=locality, harass_peers=peers)


# ---------------------------------------------------------------------------
# acceptance: 64-unit chaos over the socket with a separate worker process
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_acceptance_64_units_chaos_over_socket_with_worker_process(tmp_path):
    """ISSUE acceptance: ClusterRunner completes a 64-unit chaos run over the
    socket transport with >=1 node in a separate OS process — one local node
    dies mid-run, one unit straggles into a twin — and every unit ends with
    exactly one ok provenance."""
    ds = synthesize_dataset(tmp_path / "ds", "acc-rpc", n_subjects=32,
                            sessions_per_subject=2, shape=(8, 8, 8))
    pipe = builtin_pipelines()["bias_correct"]
    units, _ = query_available_work(ds, pipe)
    assert len(units) == 64
    slow_id = units[5].job_id
    slept = {"n": 0}
    lock = threading.Lock()

    def chaos(unit, attempt):
        # hold local nodes back (bounded per unit) until the external
        # process has registered — a cold python booting jax takes seconds
        # on a loaded box, and the batched grant path drains 64 tiny units
        # faster than that — so it provably commits real work; unit 5
        # straggles once
        deadline = time.time() + 1.0
        while time.time() < deadline:
            srv = runner.server
            if srv is not None and "ext-0" in srv.queue.alive_nodes():
                break
            time.sleep(0.05)
        time.sleep(0.01)
        if unit.job_id == slow_id:
            with lock:
                first = slept["n"] == 0
                slept["n"] += 1
            if first:
                # straggle until anyone (twin, or the external worker)
                # commits the unit — bounded, not a fixed window
                wait_until(lambda: 5 in runner.server.queue.done_status(),
                           timeout=30, desc="unit 5 to be committed past "
                                            "the straggling primary")

    runner = ClusterRunner(pipe, ds.root, nodes=2, transport="rpc",
                           fault_hook=chaos, die_after={"node-1": 3},
                           lease_ttl_s=0.6, hb_interval_s=0.1,
                           straggler_factor=2.5, straggler_min_s=0.3,
                           poll_s=0.03, cache_dir=tmp_path / "host-cache")
    got = {}
    t = threading.Thread(target=lambda: got.update(r=runner.run(units)))
    t.start()
    deadline = time.time() + 30
    while runner.server is None and t.is_alive() and time.time() < deadline:
        time.sleep(0.01)
    assert runner.server is not None
    env = dict(os.environ,
               PYTHONPATH=str(REPO_ROOT / "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""),
               REPRO_CACHE_DIR=str(tmp_path / "ext-cache"),
               # a compile cache of its own: one warmed by earlier runs
               # would speed the worker up enough to change the schedule
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax-cache"))
    # worker output goes to a file: a pipe drained only after the run
    # would block the worker as soon as its logs filled the pipe buffer
    wlog = tmp_path / "worker.log"
    with open(wlog, "w") as out:
        worker = subprocess.Popen(
            [sys.executable, "-m", "repro.dist.rpc", "work",
             "--addr", runner.server.addr_str, "--pipeline", "bias_correct",
             "--data-root", str(ds.root), "--node-id", "ext-0"],
            env=env, cwd=REPO_ROOT, stdout=out, stderr=subprocess.STDOUT)
        t.join(timeout=300)
        worker.wait(timeout=60)
    wout = wlog.read_text()
    assert not t.is_alive(), "coordinator did not finish"
    results = got["r"]
    by_status = Counter(r.status for r in results)
    assert by_status["ok"] == 64
    ok_ids = [r.unit.job_id for r in results if r.status == "ok"]
    assert len(ok_ids) == len(set(ok_ids))
    # exactly one committed ok provenance per unit
    provs = [Provenance.load(Path(u.out_dir)) for u in units]
    assert all(p is not None and p.status == "ok"
               and p.pipeline_digest == pipe.digest() for p in provs)
    # the chaos happened: node death observed, and the external process
    # registered and committed work of its own
    assert "node-1" in runner.stats.dead_nodes
    assert "ext-0" in runner.stats.remote_nodes, wout
    ext_commits = [p for p in provs if p.node_id == "ext-0"]
    assert len(ext_commits) >= 1, (runner.stats.processed, wout)
    assert worker.returncode in (0, 3), wout


# ---------------------------------------------------------------------------
# frame caps + binary framing
# ---------------------------------------------------------------------------

def test_oversize_jsonl_request_rejected_then_connection_closed(dataset):
    """A request line past MAX_FRAME_BYTES used to balloon the server's
    memory via unbounded readline; now it gets one ProtocolError reply and
    the connection closes (the stream cannot be resynchronized)."""
    from repro.dist import rpc as rpc_mod
    pipe, units = _work(dataset)
    q = WorkQueue(units, ["a"])
    with QueueServer(q) as srv:
        s = socket.create_connection(srv.address, timeout=30)
        with s:
            s.sendall(b"{" + b"x" * (rpc_mod.MAX_FRAME_BYTES + 16))
            f = s.makefile("rb")
            resp = json.loads(f.readline())
            assert resp["ok"] is False and resp["id"] is None
            assert "ProtocolError" in resp["error"]
            assert f.readline() == b""           # server hung up
        # the server survives: a fresh client still gets work
        c = QueueClient(srv.address)
        assert c.next_unit("a") is not None
        c.close()


def test_oversize_binary_length_prefix_rejected(dataset):
    from repro.dist import rpc as rpc_mod
    pipe, units = _work(dataset)
    q = WorkQueue(units, ["a"])
    with QueueServer(q) as srv:
        s = socket.create_connection(srv.address, timeout=30)
        with s:
            n = rpc_mod.MAX_FRAME_BYTES + 1
            s.sendall(rpc_mod._FRAME_MAGIC + n.to_bytes(4, "big"))
            f = s.makefile("rb")
            assert f.read(1) == rpc_mod._FRAME_MAGIC
            rlen = int.from_bytes(f.read(4), "big")
            resp = json.loads(f.read(rlen))
            assert resp["ok"] is False and "ProtocolError" in resp["error"]
            assert f.read(1) == b""              # server hung up


def test_client_poisons_on_oversize_response(dataset):
    """A server reply past the cap must not be buffered to completion: the
    client raises ConnectionError and every later call fails fast."""
    from repro.dist import rpc as rpc_mod
    srv = socket.create_server(("127.0.0.1", 0))
    addr = srv.getsockname()

    def fake_server():
        conn, _ = srv.accept()
        with conn:
            conn.makefile("rb").readline()       # consume the request
            conn.sendall(b"x" * (rpc_mod.MAX_FRAME_BYTES + 16))
    t = threading.Thread(target=fake_server, daemon=True)
    t.start()
    c = QueueClient(addr)
    with pytest.raises(ConnectionError, match="exceeds frame cap"):
        c.finished()
    with pytest.raises(ConnectionError, match="is down"):
        c.pending()
    t.join(timeout=30)
    srv.close()


def test_client_upgrades_to_binary_after_first_response(dataset):
    pipe, units = _work(dataset)
    q = WorkQueue(units, ["a"])
    with QueueServer(q) as srv:
        c = QueueClient(srv.address)
        assert c._binary is False                # negotiated, never assumed
        assert c.finished() is False             # JSON-lines, sees "bin": 1
        assert c._binary is True
        unit, lease = c.next_unit("a")           # binary-framed round trip
        assert unit.job_id == q.units[lease.unit_idx].job_id
        c.complete(lease.unit_idx, "a", "ok")
        assert c.done_status()[lease.unit_idx] == "ok"
        c.close()


def test_binary_false_pins_client_to_jsonlines(dataset):
    pipe, units = _work(dataset)
    q = WorkQueue(units, ["a"])
    with QueueServer(q) as srv:
        c = QueueClient(srv.address, binary=False)
        for _ in range(3):
            assert c.finished() is False
        assert c._binary is False                # old-client wire, unchanged
        assert c.next_unit("a") is not None
        c.close()


# ---------------------------------------------------------------------------
# batched rpcs + version skew, both directions
# ---------------------------------------------------------------------------

def test_batched_grant_renew_complete_roundtrip(dataset):
    pipe, units = _work(dataset)
    q = WorkQueue(units, ["a"])
    with QueueServer(q) as srv:
        c = QueueClient(srv.address)
        got = c.next_units("a", 5)
        assert len(got) == 5 and c._batched_ok is True
        leases = [[lease.unit_idx, lease.epoch] for _u, lease in got]
        assert c.renew_batch("a", leases) == [True] * 5
        stale = [[leases[0][0], leases[0][1] + 7]] + leases[1:]
        assert c.renew_batch("a", stale) == [False] + [True] * 4
        c.complete_batch([{"idx": i, "node_id": "a", "status": "ok",
                           "meta": {"seconds": 0.5}} for i, _e in leases])
        snap = c.results_snapshot()
        assert all(snap["primaries"][i]["seconds"] == 0.5
                   for i, _e in leases)
        # a short batch means what a None from next_unit means
        rest = c.next_units("a", 10_000)
        assert len(rest) == len(units) - 5
        c.close()


def test_renew_batch_applies_summary_delta_once(dataset):
    pipe, units = _work(dataset)
    q = WorkQueue(units, ["a"])
    with QueueServer(q) as srv:
        c = QueueClient(srv.address)
        got = c.next_units("a", 2)
        leases = [[lease.unit_idx, lease.epoch] for _u, lease in got]
        digs = list(units[0].input_digests.values())
        assert c.renew_batch("a", leases, summary_delta={
            "v": 1, "add": digs, "drop": []}) == [True, True]
        assert "a" in c.summaries_snapshot()
        c.close()
    # delta lands exactly once: each digest adds one copy, so one discard
    # per digest empties the summary again
    for d in digs:
        q._summaries["a"].discard(d)
    assert len(q._summaries["a"]) == 0


def test_new_client_sheds_batching_against_pre_batch_server(
        dataset, monkeypatch):
    """New worker vs old coordinator: the batched methods aren't in the
    server's allowlist, so the first call reports "unknown method"; the
    client downgrades to per-op for good and the run proceeds."""
    from repro.dist import rpc as rpc_mod
    monkeypatch.setattr(
        rpc_mod, "_METHODS",
        frozenset(rpc_mod._METHODS
                  - {"next_units", "complete_batch", "renew_batch"}))
    pipe, units = _work(dataset)
    q = WorkQueue(units, ["a"])
    with QueueServer(q) as srv:
        c = QueueClient(srv.address)
        got = c.next_units("a", 4)
        assert len(got) == 4 and c._batched_ok is False
        leases = [[lease.unit_idx, lease.epoch] for _u, lease in got]
        digs = list(units[0].input_digests.values())
        verdicts = c.renew_batch("a", leases,
                                 summary_delta={"v": 1, "add": digs,
                                                "drop": []})
        assert verdicts == [True] * 4
        c.complete_batch([{"idx": i, "node_id": "a", "status": "ok"}
                          for i, _e in leases])
        assert sum(1 for s in c.done_status().values() if s == "ok") == 4
        c.close()
    for d in digs:                               # the piggyback landed once
        q._summaries["a"].discard(d)
    assert len(q._summaries["a"]) == 0


# ---------------------------------------------------------------------------
# reconnect + incarnation: clients ride out a coordinator restart
# ---------------------------------------------------------------------------

def test_client_reconnects_across_server_restart_on_same_port(dataset):
    pipe, units = _work(dataset)
    q1 = WorkQueue(units, [])
    srv1 = QueueServer(q1).start()
    host, port = srv1.address
    c = QueueClient(srv1.address)
    assert c.register("a") is True
    unit, lease = c.next_unit("a")
    srv1.crash()                         # no goodbye frames

    q2 = WorkQueue(units, [])
    srv2 = QueueServer(q2, host, port).start()
    try:
        # the next call redials transparently; the replayed register means
        # the brand-new queue already knows node "a" when the call lands
        assert c.pending() == len(units)
        assert "a" in q2.alive_nodes()
        u2, l2 = c.next_unit("a")
        c.complete(l2.unit_idx, "a", "ok")
        assert q2.done_status()[l2.unit_idx] == "ok"
        c.close()
    finally:
        srv2.stop()


def test_restart_hook_fires_on_incarnation_change(dataset):
    pipe, units = _work(dataset)
    q1 = WorkQueue(units, ["a"])
    srv1 = QueueServer(q1).start()
    host, port = srv1.address
    c = QueueClient(srv1.address)
    fired = []
    c.add_restart_hook(lambda: fired.append(c._incarnation))
    assert c.finished() is False
    assert fired == []                   # first incarnation is not a restart
    inc1 = c._incarnation
    assert inc1 == srv1.incarnation
    srv1.crash()
    srv2 = QueueServer(WorkQueue(units, ["a"]), host, port).start()
    try:
        c.pending()
        assert fired == [srv2.incarnation] and inc1 != srv2.incarnation
        c.pending()
        assert len(fired) == 1           # once per restart, not per call
        c.close()
    finally:
        srv2.stop()


def test_client_against_pre_incarnation_server(dataset):
    """Version skew: a server that never stamps ``inc`` (an old build) must
    leave a reconnect-capable client fully functional, hooks silent."""
    pipe, units = _work(dataset)
    q = WorkQueue(units, ["a"])
    with QueueServer(q) as srv:
        srv._srv.incarnation = None      # simulate the old wire
        c = QueueClient(srv.address)
        fired = []
        c.add_restart_hook(lambda: fired.append(1))
        assert c.finished() is False
        unit, lease = c.next_unit("a")
        c.complete(lease.unit_idx, "a", "ok")
        assert c.done_status()[lease.unit_idx] == "ok"
        assert c._incarnation is None and fired == []
        c.close()


def test_reconnect_false_preserves_poison_semantics(dataset):
    pipe, units = _work(dataset)
    q = WorkQueue(units, ["a"])
    srv = QueueServer(q).start()
    c = QueueClient(srv.address, reconnect=False)
    assert c.finished() is False
    srv.crash()
    with pytest.raises(ConnectionError):
        c.pending()
    # poisoned: fails fast forever, even if a server comes back
    with pytest.raises(ConnectionError, match="is down"):
        c.pending()
    c.close()


def test_reconnect_gives_up_after_window(dataset):
    pipe, units = _work(dataset)
    q = WorkQueue(units, ["a"])
    srv = QueueServer(q).start()
    c = QueueClient(srv.address, reconnect_window_s=0.5, backoff_s=0.05)
    assert c.finished() is False
    srv.crash()                          # nothing ever comes back up
    t0 = time.monotonic()
    with pytest.raises(ConnectionError, match="gave up"):
        c.pending()
    assert 0.3 <= time.monotonic() - t0 < 10.0
    with pytest.raises(ConnectionError, match="is down"):
        c.pending()                      # window exhausted -> poisoned
    c.close()


def test_server_stop_is_idempotent_and_drains_inflight(dataset):
    pipe, units = _work(dataset)

    class SlowQueue(WorkQueue):
        def pending(self):
            time.sleep(0.4)              # a handler mid-call during stop()
            return super().pending()

    q = SlowQueue(units, ["a"])
    srv = QueueServer(q, drain_s=5.0).start()
    c = QueueClient(srv.address)
    assert c.finished() is False
    res = {}

    def call():
        try:
            res["pending"] = c.pending()
        except ConnectionError as e:
            res["error"] = e
    t = threading.Thread(target=call, daemon=True)
    t.start()
    time.sleep(0.1)                      # let the call reach the handler
    srv.stop()
    srv.stop()                           # second stop: no-op, no exception
    t.join(timeout=10)
    # the drain let the in-flight reply escape before the socket died
    assert res.get("pending") == len(units), res
    c.close()
    srv.crash()                          # after stop: still a no-op
