"""Dependency-aware campaign DAGs: queue gating protocol, serialization /
rpc version skew, and staged end-to-end cluster runs.

The queue-level contract under test: a unit with ``depends_on`` is *parked*
— invisible to every grant path (own deque, backlog fill, stealing,
speculation) — until every in-queue parent has retired ``ok``/``skipped``.
A parent that fails terminally cascades every transitive descendant to a
terminal ``blocked`` status instead. Reaped/dead parents release nothing:
only a committed retirement does.
"""
import dataclasses
import hashlib
import json
from pathlib import Path
from typing import Dict

import numpy as np
import pytest

from repro.core import (Provenance, builtin_pipelines, query_available_work,
                        synthesize_dataset)
from repro.core.query import WorkUnit, dump_units, load_units
from repro.core.workflow import run_unit
from repro.dist import ClusterRunner, WorkQueue
from repro.dist.cache import InputCache
from repro.dist.rpc import _decode, _encode


def _unit(tag: str, deps=(), pipeline: str = "p") -> WorkUnit:
    return WorkUnit(dataset="dag", subject=tag, session="01",
                    pipeline=pipeline, pipeline_digest="pd",
                    inputs={"T1w": f"in/{tag}.npy"}, out_dir=f"/out/{tag}",
                    depends_on=[d.job_id if isinstance(d, WorkUnit) else d
                                for d in deps])


def _drain(q: WorkQueue, node: str):
    """Grant everything currently leasable to ``node``."""
    got = []
    while True:
        nxt = q.next_unit(node)
        if nxt is None:
            return got
        got.append(nxt)


# ---------------------------------------------------------------------------
# queue gating protocol
# ---------------------------------------------------------------------------

def test_chain_grants_strictly_in_order():
    a = _unit("a")
    b = _unit("b", deps=[a])
    c = _unit("c", deps=[b])
    q = WorkQueue([a, b, c], ["n0"])
    grants = _drain(q, "n0")
    assert [u.job_id for u, _ in grants] == [a.job_id]   # only the root
    q.complete(0, "n0", "ok")
    grants = _drain(q, "n0")
    assert [u.job_id for u, _ in grants] == [b.job_id]
    q.complete(1, "n0", "ok")
    (u, _), = _drain(q, "n0")
    assert u.job_id == c.job_id
    q.complete(2, "n0", "ok")
    assert q.finished()


def test_diamond_child_needs_both_parents_and_is_granted_once():
    root = _unit("r")
    left = _unit("l", deps=[root])
    right = _unit("g", deps=[root])
    sink = _unit("s", deps=[left, right])
    q = WorkQueue([root, left, right, sink], ["n0", "n1"])
    idx = {u.job_id: i for i, u in enumerate([root, left, right, sink])}
    (u, lease), = _drain(q, "n0") + _drain(q, "n1")
    assert u.job_id == root.job_id
    q.complete(lease.unit_idx, lease.node_id, "ok")
    mids = _drain(q, "n0") + _drain(q, "n1")
    assert sorted(u.job_id for u, _ in mids) == sorted(
        [left.job_id, right.job_id])
    # one parent done: the sink must stay parked
    q.complete(idx[left.job_id], "n0", "ok")
    assert _drain(q, "n0") + _drain(q, "n1") == []
    q.complete(idx[right.job_id], "n1", "ok")
    sinks = _drain(q, "n0") + _drain(q, "n1")
    assert [u.job_id for u, _ in sinks] == [sink.job_id]


def test_parked_child_is_invisible_to_steal_and_speculation():
    a = _unit("a")
    b = _unit("b", deps=[a])
    q = WorkQueue([a, b], ["busy", "idle"])
    # between stealing and backlog fill, both nodes combined can surface
    # only the root — the parked child is on no deque to be stolen from
    granted = _drain(q, "idle") + _drain(q, "busy")
    assert {u.job_id for u, _ in granted} == {a.job_id}
    # nor can the straggler path lease the parked child as a twin
    assert q.speculate(1, "idle") is None
    assert q.speculate(1, "busy") is None


def test_failed_parent_blocks_all_descendants_terminally():
    a = _unit("a")
    b = _unit("b", deps=[a])
    c = _unit("c", deps=[b])
    d = _unit("d")                                      # independent bystander
    q = WorkQueue([a, b, c, d], ["n0"])
    grants = {u.job_id: l for u, l in _drain(q, "n0")}
    assert set(grants) == {a.job_id, d.job_id}          # only the roots
    q.complete(grants[a.job_id].unit_idx, "n0", "failed")
    assert q.done_status()[1] == "blocked"
    assert q.done_status()[2] == "blocked"              # transitive
    # blocked units are terminal: never granted, and the queue can finish
    assert _drain(q, "n0") == []
    q.complete(grants[d.job_id].unit_idx, "n0", "ok")
    assert q.finished()
    dag = q.stats_snapshot()["dag"]
    assert dag["cancelled"] == 2 and dag["blocked"] == 0 and dag["ready"] == 0


def test_reaped_parent_re_blocks_child_until_rerun_commits():
    t = {"now": 0.0}
    a = _unit("a")
    b = _unit("b", deps=[a])
    q = WorkQueue([a, b], ["n0", "n1"], lease_ttl_s=1.0,
                  now=lambda: t["now"])
    granted = _drain(q, "n0") + _drain(q, "n1")
    assert [u.job_id for u, _ in granted] == [a.job_id]
    (_, lease), = granted
    holder, other = lease.node_id, ("n1" if lease.node_id == "n0" else "n0")
    # the holder goes silent past the TTL: the parent is reaped and requeued,
    # and the child must stay parked — a reaped parent committed nothing
    t["now"] = 1.5
    q.heartbeat(other)
    assert lease.unit_idx in q.reap()
    regrants = _drain(q, other)
    assert [u.job_id for u, _ in regrants] == [a.job_id]   # parent, not child
    (_, lease2), = regrants
    assert lease2.epoch > lease.epoch
    # a zombie completion from the dead holder still releases nothing
    q.complete(lease.unit_idx, holder, "ok")
    assert _drain(q, other) == []
    # the live re-run's commit finally releases the child
    q.complete(lease2.unit_idx, other, "ok")
    (u, _), = _drain(q, other)
    assert u.job_id == b.job_id


def test_child_released_to_dead_home_lands_in_backlog():
    a = _unit("a")
    b = _unit("b", deps=[a])
    others = [_unit(f"x{i}") for i in range(2)]
    q = WorkQueue([a, b] + others, ["n0", "n1"])
    # find and finish the parent from whichever deque holds it, then kill
    # the child's planned home before release
    grants = {u.job_id: l for u, l in _drain(q, "n0") + _drain(q, "n1")}
    child_home = "n1" if grants[a.job_id].node_id == "n0" else "n1"
    q.mark_dead(child_home)
    q.complete(grants[a.job_id].unit_idx, grants[a.job_id].node_id, "ok")
    alive = "n0" if child_home == "n1" else "n1"
    # the child is grantable to the surviving node (via backlog), not lost
    released = _drain(q, alive)
    assert b.job_id in {u.job_id for u, _ in released}


def test_cycle_and_self_dependency_are_rejected_at_construction():
    a = _unit("a")
    b = _unit("b", deps=[a])
    a.depends_on = [b.job_id]
    with pytest.raises(ValueError, match="cycle"):
        WorkQueue([a, b], ["n0"])
    s = _unit("s")
    s.depends_on = [s.job_id]
    with pytest.raises(ValueError, match="cycle"):
        WorkQueue([s], ["n0"])


def test_absent_parent_counts_as_satisfied():
    b = _unit("b", deps=["dag_p_sub-finished-long-ago_ses-01"])
    q = WorkQueue([b], ["n0"])
    (u, _), = _drain(q, "n0")
    assert u.job_id == b.job_id


def test_stats_snapshot_reports_per_stage_progress():
    s1 = [_unit(f"a{i}", pipeline="stage1") for i in range(3)]
    s2 = [_unit(f"b{i}", deps=[s1[i]], pipeline="stage2") for i in range(3)]
    q = WorkQueue(s1 + s2, ["n0"])
    dag = q.stats_snapshot()["dag"]
    assert dag == {"ready": 3, "blocked": 3, "cancelled": 0,
                   "per_stage": dag["per_stage"]}
    assert dag["per_stage"]["stage1"]["ready"] == 3
    assert dag["per_stage"]["stage2"]["blocked"] == 3
    grants = {u.job_id: l for u, l in _drain(q, "n0")}
    q.complete(grants[s1[0].job_id].unit_idx, "n0", "ok")
    q.complete(grants[s1[1].job_id].unit_idx, "n0", "failed")
    dag = q.stats_snapshot()["dag"]
    assert dag["per_stage"]["stage1"] == {
        "total": 3, "ok": 1, "failed": 1, "cancelled": 0, "blocked": 0,
        "ready": 1}
    assert dag["per_stage"]["stage2"] == {
        "total": 3, "ok": 0, "failed": 0, "cancelled": 1, "blocked": 1,
        "ready": 1}


# ---------------------------------------------------------------------------
# serialization + version skew
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _LegacyWorkUnit:
    """The pre-DAG WorkUnit schema, frozen here as the backcompat oracle:
    what an old coordinator's ``load_units`` would construct."""
    dataset: str
    subject: str
    session: str
    pipeline: str
    pipeline_digest: str
    inputs: Dict[str, str]
    out_dir: str
    input_digests: Dict[str, str] = dataclasses.field(default_factory=dict)
    input_bytes: Dict[str, int] = dataclasses.field(default_factory=dict)


def test_dump_load_round_trips_depends_on(tmp_path):
    a = _unit("a")
    b = _unit("b", deps=[a])
    path = dump_units([a, b], tmp_path / "units.json")
    back = load_units(path)
    assert back == [a, b]
    assert back[1].depends_on == [a.job_id]


def test_plain_units_serialize_in_the_exact_pre_dag_shape(tmp_path):
    a = _unit("a")
    path = dump_units([a], tmp_path / "units.json")
    rows = json.loads(path.read_text())
    assert "depends_on" not in rows[0]
    # an old loader accepts them unchanged...
    legacy = [_LegacyWorkUnit(**r) for r in rows]
    assert legacy[0].out_dir == a.out_dir
    # ...and a pre-DAG units file loads here as independent units
    q = WorkQueue(load_units(path), ["n0"])
    assert len(_drain(q, "n0")) == 1


def test_old_coordinator_rejects_dag_units_loudly(tmp_path):
    a = _unit("a")
    b = _unit("b", deps=[a])
    rows = json.loads(dump_units([a, b], tmp_path / "u.json").read_text())
    with pytest.raises(TypeError, match="depends_on"):
        [_LegacyWorkUnit(**r) for r in rows]


def test_rpc_wire_carries_deps_in_a_sidecar_old_decoders_shed():
    a = _unit("a")
    b = _unit("b", deps=[a])
    enc = _encode(b)
    assert enc["__deps__"] == [a.job_id]
    assert "depends_on" not in enc["__unit__"]
    assert _decode(enc) == b                     # new decoder restores edges
    # an old decoder reads only __unit__: the unit arrives dependency-free —
    # safe, because a coordinator only ever sends *ready* units to workers
    shed = WorkUnit(**enc["__unit__"])
    assert shed.depends_on == [] and shed.job_id == b.job_id
    legacy = _LegacyWorkUnit(**enc["__unit__"])  # even the pre-DAG dataclass
    assert legacy.out_dir == b.out_dir
    # independent units stay byte-identical to the pre-DAG wire shape
    assert "__deps__" not in _encode(a)
    assert _decode(_encode(a)) == a


# ---------------------------------------------------------------------------
# staged end-to-end cluster runs
# ---------------------------------------------------------------------------

@pytest.fixture()
def dataset(tmp_path):
    return synthesize_dataset(tmp_path, "dagds", n_subjects=4,
                              sessions_per_subject=1, shape=(8, 8, 8))


def _staged_units(dataset):
    """Stage 1: bias_correct from the manifest. Stage 2: affine_register
    consuming each session's stage-1 ``T1w_biascorr`` output — a real
    mixed-pipeline DAG (inputs that do not exist until the parent commits)."""
    pipes = builtin_pipelines()
    s1, _ = query_available_work(dataset, pipes["bias_correct"])
    s2 = []
    for u in s1:
        rel = (f"derivatives/bias_correct/sub-{u.subject}/ses-{u.session}/"
               f"sub-{u.subject}_ses-{u.session}_T1w_biascorr.npy")
        s2.append(WorkUnit(
            dataset=u.dataset, subject=u.subject, session=u.session,
            pipeline="affine_register",
            pipeline_digest=pipes["affine_register"].digest(),
            inputs={"T1w": rel},
            out_dir=str(Path(dataset.root) / "derivatives" /
                        "affine_register" / f"sub-{u.subject}" /
                        f"ses-{u.session}"),
            depends_on=[u.job_id]))
    return pipes, s1, s2


def test_staged_pipelines_run_end_to_end_in_one_queue(dataset):
    pipes, s1, s2 = _staged_units(dataset)
    runner = ClusterRunner(pipes, dataset.root, nodes=3)
    results = runner.run(s1 + s2)
    assert sum(r.status == "ok" for r in results) == len(s1) + len(s2)
    for parent, child in zip(s1, s2):
        pp = Provenance.load(Path(parent.out_dir))
        cp = Provenance.load(Path(child.out_dir))
        assert pp.status == "ok" and cp.status == "ok"
        # no child ran before its parent's commit
        assert cp.started_at >= pp.finished_at - 1e-6
        # the child consumed the exact bytes the parent committed
        assert cp.inputs[child.inputs["T1w"]] == pp.outputs[
            f"sub-{parent.subject}_ses-{parent.session}_T1w_biascorr.npy"]


def test_staged_run_with_node_death_still_orders_correctly(dataset):
    pipes, s1, s2 = _staged_units(dataset)
    runner = ClusterRunner(pipes, dataset.root, nodes=3,
                           die_after={"node-1": 1},
                           lease_ttl_s=0.5, hb_interval_s=0.1)
    results = runner.run(s1 + s2)
    assert sum(r.status == "ok" for r in results) == len(s1) + len(s2)
    for parent, child in zip(s1, s2):
        pp = Provenance.load(Path(parent.out_dir))
        cp = Provenance.load(Path(child.out_dir))
        assert cp.started_at >= pp.finished_at - 1e-6


def test_failed_stage_blocks_children_at_the_cluster_level(dataset):
    pipes, s1, s2 = _staged_units(dataset)
    poisoned = s1[0].job_id

    def poison(unit, attempt):
        if unit.job_id == poisoned:
            raise RuntimeError("synthetic stage-1 failure")

    runner = ClusterRunner(pipes, dataset.root, nodes=2, max_retries=1,
                           fault_hook=poison)
    results = runner.run(s1 + s2)
    by_id = {}
    for r in results:                  # primary result per unit, not twins
        if r.status != "speculative":
            by_id.setdefault(r.unit.job_id, r)
    assert by_id[poisoned].status == "failed"
    blocked = by_id[s2[0].job_id]
    assert blocked.status == "blocked"
    assert "depends_on" in (blocked.error or "")
    assert Provenance.load(Path(s2[0].out_dir)) is None  # never started
    # every other lineage completed untouched
    for parent, child in zip(s1[1:], s2[1:]):
        assert by_id[parent.job_id].status == "ok"
        assert by_id[child.job_id].status == "ok"


def test_unit_naming_unknown_pipeline_fails_without_crashing_node(dataset):
    pipes, s1, _ = _staged_units(dataset)
    bad = dataclasses.replace(s1[0], pipeline="no_such_stage",
                              out_dir=s1[0].out_dir + "-bad")
    runner = ClusterRunner({"bias_correct": pipes["bias_correct"]},
                           dataset.root, nodes=2)
    results = runner.run(s1[1:] + [bad])
    by_id = {r.unit.job_id: r for r in results}
    assert by_id[bad.job_id].status == "failed"
    assert "no_such_stage" in by_id[bad.job_id].error
    assert all(by_id[u.job_id].status == "ok" for u in s1[1:])


# ---------------------------------------------------------------------------
# deterministic invariant sweep (shared harness; the hypothesis twin draws
# random topologies in test_property.py)
# ---------------------------------------------------------------------------

# chain, diamond, two-stage fan-in QC gate — the canonical shapes
_TOPOLOGIES = {
    "chain": {1: [0], 2: [1], 3: [2]},
    "diamond": {1: [0], 2: [0], 3: [1, 2]},
    "fanin_gate": {4: [0, 1], 5: [2, 3], 6: [4, 5], 7: [4, 5]},
}


@pytest.mark.parametrize("topology", sorted(_TOPOLOGIES))
@pytest.mark.parametrize("fail_idx", [None, 0])
def test_dag_invariant_deterministic(topology, fail_idx):
    from cluster_invariant import check_cluster_invariant
    check_cluster_invariant(4, 2, 3, False, 0,
                            dag_edges=_TOPOLOGIES[topology],
                            fail_idx=fail_idx)


def test_dag_invariant_under_chaos():
    """The full gauntlet on a diamond: transient faults, one node death and
    a permanently failing root at once — gating and blocked-propagation must
    hold while leases are reaped and re-granted."""
    from cluster_invariant import check_cluster_invariant
    check_cluster_invariant(4, 2, 3, True, 1,
                            dag_edges=_TOPOLOGIES["diamond"], fail_idx=2)


# ---------------------------------------------------------------------------
# output write-through (producer placement's data plane)
# ---------------------------------------------------------------------------

def test_committed_outputs_are_written_through_to_the_cache(dataset,
                                                            tmp_path):
    pipes = builtin_pipelines()
    units, _ = query_available_work(dataset, pipes["bias_correct"])
    cache = InputCache(tmp_path / "cache")
    res = run_unit(units[0], pipes["bias_correct"], dataset.root, cache=cache)
    assert res.status == "ok"
    prov = Provenance.load(Path(units[0].out_dir))
    for name, digest in prov.outputs.items():
        blob = cache.read_blob(digest)
        assert blob is not None
        assert hashlib.sha256(blob).hexdigest() == digest
