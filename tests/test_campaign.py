"""Campaign planner: admission-time locality (``repro.core.campaign``) —
the shared grant/admission scorer, deterministic replayable plans,
per-shard script generation, queue seeding, and the planner's guarantees
under arbitrary cohorts/summaries (hypothesis)."""
import dataclasses
import json
import re
import shutil
from pathlib import Path

import pytest

from repro.core import builtin_pipelines, query_available_work, synthesize_dataset
from repro.core.campaign import (CAMPAIGN_VERSION, CampaignPlan, Cohort,
                                 admission_throttle, cohort_from_query,
                                 plan_campaign, summaries_from_queue)
from repro.core.query import Exclusion, load_units
from repro.core.workflow import generate_jobs
from repro.dist import ClusterRunner, DigestSummary, WorkQueue
from repro.dist.cache import (SUMMARY_WIRE_VERSION, load_summary_file,
                              save_summary_file, summaries_from_cache_dirs)


@pytest.fixture()
def dataset(tmp_path):
    return synthesize_dataset(tmp_path / "ds", "campds", n_subjects=8,
                              sessions_per_subject=2, shape=(10, 10, 10))


def _cohort(dataset):
    return cohort_from_query(dataset, builtin_pipelines()["bias_correct"])


def _summary_for(units):
    s = DigestSummary()
    for u in units:
        for d in u.input_digests.values():
            s.add(d)
    return s


# ---------------------------------------------------------------------------
# one scorer, two schedulers (the no-drift acceptance criterion)
# ---------------------------------------------------------------------------

def test_grant_and_admission_share_one_scorer_object():
    """Both call sites must resolve to the *same function object* in
    ``repro.dist.placement`` — duplicated scoring logic is how admission
    and grant ranking drift apart."""
    from repro.core import campaign as admission_site
    from repro.dist import placement
    from repro.dist import queue as grant_site
    assert grant_site.unit_local_bytes is placement.unit_local_bytes
    assert admission_site.unit_local_bytes is placement.unit_local_bytes
    assert grant_site.best_node is placement.best_node
    assert admission_site.best_node is placement.best_node


def test_grant_score_equals_admission_score(dataset):
    """The number a shard records is the number the queue leases with."""
    cohort = _cohort(dataset)
    units = cohort.units
    summ = {"a": _summary_for(units[:3])}
    plan = plan_campaign([cohort], summ)
    warm = next(s for s in plan.shards if s.node_id == "a")
    q = WorkQueue(units, ["a"], partition="backlog")
    q.put_summary("a", {"v": SUMMARY_WIRE_VERSION,
                        "full": summ["a"].to_wire()})
    granted_local = 0
    for _ in range(len(warm.unit_ids)):
        unit, lease = q.next_unit("a")
        assert unit.job_id in warm.unit_ids
        granted_local += lease.local_bytes
    assert granted_local == warm.est_local_bytes


# ---------------------------------------------------------------------------
# planner semantics
# ---------------------------------------------------------------------------

def test_plan_routes_units_to_warm_nodes_and_colds_the_rest(dataset):
    cohort = _cohort(dataset)
    units = cohort.units
    summaries = {"node-a": _summary_for(units[:5]),
                 "node-b": _summary_for(units[5:9])}
    plan = plan_campaign([cohort], summaries)
    assert plan.nodes == ["node-a", "node-b"]
    by_node = {s.node_id: s for s in plan.shards}
    assert set(by_node["node-a"].unit_ids) == {u.job_id for u in units[:5]}
    assert set(by_node["node-b"].unit_ids) == {u.job_id for u in units[5:9]}
    assert set(by_node[None].unit_ids) == {u.job_id for u in units[9:]}
    assert by_node[None].est_local_bytes == 0
    assert by_node["node-a"].est_local_bytes == \
        sum(u.total_input_bytes for u in units[:5])
    assert 0.0 < plan.est_local_fraction() < 1.0
    # every admitted unit exactly once
    assigned = plan.assigned_unit_ids()
    assert sorted(assigned) == sorted(u.job_id for u in units)


def test_plan_without_summaries_degrades_to_one_blind_shard(dataset):
    cohort = _cohort(dataset)
    plan = plan_campaign([cohort])
    assert plan.nodes == []
    assert len(plan.shards) == 1 and plan.shards[0].node_id is None
    assert plan.shards[0].unit_ids == [u.job_id for u in cohort.units]
    assert plan.est_local_fraction() == 0.0


def test_plan_admits_each_unit_once_across_overlapping_cohorts(dataset):
    cohort = _cohort(dataset)
    twin = dataclasses.replace(cohort)           # same dataset re-submitted
    plan = plan_campaign([cohort, twin], {"n0": _summary_for(cohort.units)})
    assigned = plan.assigned_unit_ids()
    assert sorted(assigned) == sorted(u.job_id for u in cohort.units)
    assert plan.cohorts[0]["admitted"] == len(cohort.units)
    assert plan.cohorts[1]["admitted"] == 0      # all duplicates


def test_plan_never_assigns_an_excluded_unit(dataset):
    cohort = _cohort(dataset)
    # poison the cohort: first two admitted sessions also appear excluded
    # (a planner must re-check, not trust the caller's disjointness)
    poisoned = dataclasses.replace(
        cohort, excluded=cohort.excluded + [
            Exclusion(u.subject, u.session, "late exclusion")
            for u in cohort.units[:2]])
    plan = plan_campaign([poisoned], {"n0": _summary_for(cohort.units)})
    assigned = set(plan.assigned_unit_ids())
    for u in cohort.units[:2]:
        assert u.job_id not in assigned
    assert sorted(assigned) == sorted(u.job_id for u in cohort.units[2:])
    # and the exclusions are recorded, with reasons, in the artifact
    reasons = {(e["subject"], e["session"]): e["reason"]
               for e in plan.excluded}
    assert reasons[(cohort.units[0].subject,
                    cohort.units[0].session)] == "late exclusion"


def test_max_shard_units_splits_arrays_deterministically(dataset):
    cohort = _cohort(dataset)
    plan = plan_campaign([cohort], {"n0": _summary_for(cohort.units)},
                         max_shard_units=3)
    warm = [s for s in plan.shards if s.node_id == "n0"]
    assert len(warm) == (len(cohort.units) + 2) // 3
    assert all(len(s.unit_ids) <= 3 for s in warm)
    assert [s.shard_id for s in plan.shards] == \
        [f"shard-{i:03d}" for i in range(len(plan.shards))]
    joined = [j for s in warm for j in s.unit_ids]
    assert sorted(joined) == sorted(u.job_id for u in cohort.units)


def test_admission_throttle_caps_on_free_disk():
    # plenty of disk: requested throttle stands
    assert admission_throttle({"disk_free_gb": 1024.0}, 1 << 20, 100) == 100
    # 1 GiB free, 64 MiB units, 4x footprint -> 4 concurrent tasks
    assert admission_throttle({"disk_free_gb": 1.0}, 64 << 20, 100) == 4
    # never below one, never crashes on degenerate inputs
    assert admission_throttle({"disk_free_gb": 0.001}, 1 << 30, 100) == 1
    assert admission_throttle({}, 1 << 30, 100) == 100
    assert admission_throttle(None, 0, 7) == 7


def test_campaign_version_mismatch_rejected(tmp_path, dataset):
    from repro.core.campaign import as_plan
    plan = plan_campaign([_cohort(dataset)])
    p = plan.save(tmp_path / "campaign.json")
    d = json.loads(p.read_text())
    d["version"] = CAMPAIGN_VERSION + 1
    p.write_text(json.dumps(d))
    with pytest.raises(ValueError, match="campaign version"):
        CampaignPlan.load(p)
    # the pre-parsed-dict intake must reject the same artifact identically,
    # not quietly misread a future plan
    with pytest.raises(ValueError, match="campaign version"):
        as_plan(d)
    with pytest.raises(TypeError):
        as_plan(42)


# ---------------------------------------------------------------------------
# determinism / replayability
# ---------------------------------------------------------------------------

def test_plan_is_deterministic_and_byte_replayable(dataset, tmp_path):
    cohort = _cohort(dataset)
    summ = {"n0": _summary_for(cohort.units[:4]),
            "n1": _summary_for(cohort.units[4:])}
    status = {"disk_free_gb": 10.0, "load_1m": 0.5}
    a = plan_campaign([cohort], summ, status=status)
    b = plan_campaign([cohort], summ, status=status)
    assert a.to_json() == b.to_json()
    p = a.save(tmp_path / "campaign.json")
    assert CampaignPlan.load(p).to_json() == a.to_json()
    assert CampaignPlan.load(p).save(tmp_path / "again.json").read_bytes() \
        == p.read_bytes()
    # a different world-state is visible in the stamp
    c = plan_campaign([cohort], summ, status={"disk_free_gb": 11.0})
    assert c.inputs_hash != a.inputs_hash


def test_summary_file_roundtrip_plans_identically(dataset, tmp_path):
    cohort = _cohort(dataset)
    summ = {"n0": _summary_for(cohort.units)}
    direct = plan_campaign([cohort], summ)
    via_file = plan_campaign(
        [cohort], load_summary_file(save_summary_file(tmp_path / "s.json",
                                                      summ)))
    assert via_file.to_json() == direct.to_json()
    # the planner also takes the path itself
    via_path = plan_campaign([cohort], tmp_path / "s.json")
    assert via_path.to_json() == direct.to_json()


# ---------------------------------------------------------------------------
# generate_jobs campaign mode (per-shard SLURM arrays)
# ---------------------------------------------------------------------------

def test_generate_jobs_campaign_mode_writes_shards_and_plan(dataset, tmp_path):
    pipe = builtin_pipelines()["bias_correct"]
    units, _ = query_available_work(dataset, pipe)
    summ = {"host-a": _summary_for(units[:6]), "host-b": _summary_for(units[6:])}
    sfile = save_summary_file(tmp_path / "summaries.json", summ)
    jp = generate_jobs(dataset, pipe, tmp_path / "jobs", summaries=sfile)
    assert jp.slurm_script is None               # sharded, not monolithic
    assert jp.campaign_file and Path(jp.campaign_file).exists()
    plan = CampaignPlan.load(jp.campaign_file)
    assert len(jp.shard_scripts) == len(plan.shards) == 2
    covered = []
    # the campaign-level throttle budget is split across the emitted
    # arrays, so submitting every shard at once cannot multiply it back up
    per_shard = plan.throttle // len(jp.shard_scripts)
    for sf, script in zip(jp.shard_units_files, jp.shard_scripts):
        shard_units = load_units(sf)
        covered.extend(u.job_id for u in shard_units)
        text = Path(script).read_text()
        assert f"--array=0-{len(shard_units) - 1}%{per_shard}" in text
        # every path the script references exists at submit time
        for raw in re.findall(r"(/[^\s\\$]+)", text):
            target = Path(raw.split("%")[0].rstrip("/"))
            assert target.exists(), f"{script} references missing {target}"
    assert sorted(covered) == sorted(u.job_id for u in units)
    # warm shards pinned to their host, cold shard untargeted
    texts = [Path(s).read_text() for s in jp.shard_scripts]
    assert any("--nodelist=host-a" in t for t in texts)
    assert any("--nodelist=host-b" in t for t in texts)


def test_generate_jobs_accepts_prebuilt_plan(dataset, tmp_path):
    pipe = builtin_pipelines()["bias_correct"]
    cohort = cohort_from_query(dataset, pipe)
    plan = plan_campaign([cohort], {"h": _summary_for(cohort.units)})
    jp = generate_jobs(dataset, pipe, tmp_path / "jobs", campaign=plan)
    assert Path(jp.campaign_file).read_text() == plan.to_json()
    assert len(jp.shard_scripts) == len(plan.shards)
    # the replay path: resubmitting an audited campaign.json, no re-plan
    saved = plan.save(tmp_path / "audited.json")
    jp2 = generate_jobs(dataset, pipe, tmp_path / "jobs2", campaign=saved)
    assert Path(jp2.campaign_file).read_text() == plan.to_json()
    assert [Path(s).name for s in jp2.shard_scripts] == \
        [Path(s).name for s in jp.shard_scripts]


def test_generate_jobs_schedules_units_a_stale_plan_missed(dataset, tmp_path):
    """Fail-soft parity with queue seeding: sessions admitted after planning
    (or dropped by a stale plan) must still get a script — in an untargeted
    catch-all shard — never be silently unscheduled."""
    pipe = builtin_pipelines()["bias_correct"]
    cohort = cohort_from_query(dataset, pipe)
    stale = plan_campaign(                       # plan covers only 4 units
        [dataclasses.replace(cohort, units=cohort.units[:4])],
        {"h": _summary_for(cohort.units[:4])})
    jp = generate_jobs(dataset, pipe, tmp_path / "jobs", campaign=stale)
    covered = [u.job_id for sf in jp.shard_units_files
               for u in load_units(sf)]
    assert sorted(covered) == sorted(u.job_id for u in cohort.units)
    assert len(covered) == len(set(covered))     # still exactly once
    catchall = [s for s in jp.shard_scripts if "shard-uncovered" in s]
    assert len(catchall) == 1
    text = Path(catchall[0]).read_text()
    assert "--nodelist" not in text              # untargeted: cold by nature
    assert f"--array=0-{len(cohort.units) - 4 - 1}%" in text


# ---------------------------------------------------------------------------
# queue seeding: the cluster starts on the planned partitions
# ---------------------------------------------------------------------------

def test_workqueue_seeds_partitions_from_plan(dataset):
    cohort = _cohort(dataset)
    units = cohort.units
    plan = plan_campaign([cohort], {"node-0": _summary_for(units[:5]),
                                    "node-1": _summary_for(units[5:])})
    q = WorkQueue(units, ["node-0", "node-1"], plan=plan)
    depths = q.queue_depths()
    assert depths == {"node-0": 5, "node-1": 11}
    # grants drain the node's own seeded shard — no backlog fill, no steal
    got = {q.next_unit("node-0")[0].job_id for _ in range(5)}
    assert got == {u.job_id for u in units[:5]}
    assert sum(q.steals.values()) == 0


def test_workqueue_seeds_from_parsed_campaign_json(dataset, tmp_path):
    """The loaded-from-disk JSON shape (plain dicts) and a campaign.json
    path both seed identically — the offline HPC path never holds live
    Shard objects."""
    cohort = _cohort(dataset)
    units = cohort.units
    plan = plan_campaign([cohort], {"node-0": _summary_for(units)})
    path = plan.save(tmp_path / "c.json")
    raw = json.loads(path.read_text())
    q = WorkQueue(units, ["node-0", "node-1"], plan=raw)
    assert q.queue_depths() == {"node-0": len(units), "node-1": 0}
    q2 = WorkQueue(units, ["node-0", "node-1"], plan=path)
    assert q2.queue_depths() == {"node-0": len(units), "node-1": 0}
    # a path to a future-version plan fails loud, not silently-backlogged
    raw["version"] += 1
    path.write_text(json.dumps(raw))
    with pytest.raises(ValueError, match="campaign version"):
        WorkQueue(units, ["node-0"], plan=path)


def test_workqueue_plan_fail_soft(dataset):
    """Stale plans degrade, never break: unknown unit ids are ignored,
    shards for absent nodes and unplanned units go to the backlog."""
    cohort = _cohort(dataset)
    units = cohort.units
    plan = plan_campaign([cohort], {"gone-node": _summary_for(units[:3])})
    ghost = dataclasses.replace(
        plan.shards[0], unit_ids=plan.shards[0].unit_ids + ["no_such_job"])
    plan = dataclasses.replace(plan, shards=[ghost] + plan.shards[1:])
    q = WorkQueue(units[:10], ["node-0"], plan=plan)
    # 3 planned-for-gone-node + 7 cold/unplanned -> all 10 via backlog
    assert q.queue_depths() == {"node-0": 0}
    drained = set()
    while True:
        nxt = q.next_unit("node-0")
        if nxt is None:
            break
        drained.add(nxt[0].job_id)
    assert drained == {u.job_id for u in units[:10]}


def test_cluster_runner_plan_starts_warm_end_to_end(dataset, tmp_path):
    """Warm per-node caches -> offline summary harvest -> plan -> a planned
    run (grant-time scoring OFF) still lands units on their warm hosts."""
    pipe = builtin_pipelines()["bias_correct"]
    units, _ = query_available_work(dataset, pipe)
    kw = dict(nodes=3, poll_s=0.02, cache_dir=tmp_path / "hosts",
              cache_per_node=True, straggler_factor=100.0)
    warm = ClusterRunner(pipe, dataset.root, locality=False, **kw)
    assert sum(r.status == "ok" for r in warm.run(units)) == len(units)
    shutil.rmtree(Path(dataset.root) / "derivatives")

    summaries = summaries_from_cache_dirs(tmp_path / "hosts")
    assert sorted(summaries) == ["node-0", "node-1", "node-2"]
    cohort = cohort_from_query(dataset, pipe)
    plan = plan_campaign([cohort], summaries)
    assert all(s.node_id for s in plan.shards)   # everything found a warm host

    runner = ClusterRunner(pipe, dataset.root, locality=False, plan=plan, **kw)
    results = runner.run(cohort.units)
    assert sum(r.status == "ok" for r in results) == len(cohort.units)
    totals = {}
    for st in runner.stats.cache_by_node.values():
        for k, v in st.items():
            if isinstance(v, (int, float)):     # skip peer_bytes_by_addr
                totals[k] = totals.get(k, 0) + v
    # the seeded partitions put most units back on their warm host even
    # with all grant-time scoring disabled (stealing may move a few)
    assert totals["hits"] > totals["misses"]


# ---------------------------------------------------------------------------
# pulling summaries from a live coordinator (in-process and over rpc)
# ---------------------------------------------------------------------------

def test_summaries_from_live_queue_and_over_rpc(dataset):
    from repro.dist import QueueClient, QueueServer
    cohort = _cohort(dataset)
    units = cohort.units
    q = WorkQueue(units, ["a", "b"])
    q.put_summary("a", {"v": SUMMARY_WIRE_VERSION,
                        "full": _summary_for(units[:4]).to_wire()})
    direct = summaries_from_queue(q)
    assert set(direct) == {"a"}
    with QueueServer(q) as srv:
        over_client = summaries_from_queue(QueueClient(srv.address))
        over_addr = summaries_from_queue(srv.addr_str)
    assert over_client == over_addr == direct
    plan = plan_campaign([cohort], direct)
    by_node = {s.node_id: s for s in plan.shards}
    assert set(by_node["a"].unit_ids) == {u.job_id for u in units[:4]}
    # a dead node's summary is not offered to the planner
    q.mark_dead("a")
    assert summaries_from_queue(q) == {}


# ---------------------------------------------------------------------------
# the planner invariant, deterministic grid (body in campaign_invariant.py;
# the hypothesis property driving the same body with random cohorts and
# summary states lives in test_property.py, the repo's hypothesis home, so
# environments without hypothesis skip only it, not this sweep)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_nodes,warm_frac,max_shard",
                         [(0, 0.0, None), (1, 1.0, None), (2, 0.5, None),
                          (3, 0.5, 2), (2, 1.0, 1)])
def test_campaign_invariant_grid(dataset, n_nodes, warm_frac, max_shard):
    from campaign_invariant import check_campaign_invariant
    cohort = _cohort(dataset)
    twin = dataclasses.replace(                  # overlap + a late exclusion
        cohort, excluded=cohort.excluded +
        [Exclusion(cohort.units[0].subject, cohort.units[0].session, "late")])
    warm = cohort.units[:int(len(cohort.units) * warm_frac)]
    per_node = (len(warm) // n_nodes + 1) if n_nodes else 0
    summaries = {f"n{i}": _summary_for(warm[i * per_node:(i + 1) * per_node])
                 for i in range(n_nodes)}
    check_campaign_invariant([cohort, twin], summaries,
                             status={"disk_free_gb": 8.0},
                             max_shard_units=max_shard)


def test_child_scores_only_its_own_parents_predicted_outputs():
    """Two DAG cohorts whose children read the same digest: cohort A's
    parent is warm on n0, so its child is predicted warm there; cohort B's
    parent has nothing to score and goes cold, so its child must stay cold
    too — A's prediction says nothing about when B's child's inputs land."""
    from campaign_invariant import check_campaign_invariant
    from repro.core.query import WorkUnit

    def unit(ds, subject, digests, deps=()):
        return WorkUnit(dataset=ds, subject=subject, session="01",
                        pipeline="p", pipeline_digest="pd",
                        inputs={f"in{i}": f"{subject}-{i}.npy"
                                for i in range(len(digests))},
                        out_dir=f"/out/{ds}/{subject}",
                        input_digests={f"in{i}": d
                                       for i, d in enumerate(digests)},
                        input_bytes={f"in{i}": 1
                                     for i in range(len(digests))},
                        depends_on=list(deps))

    pa, pb = unit("dsA", "s00", ["d0"]), unit("dsB", "s00", [])
    ca = unit("dsA", "s01", ["d1"], [pa.job_id])
    cb = unit("dsB", "s01", ["d1"], [pb.job_id])
    cohorts = [Cohort("dsA", "p", "pd", [pa, ca], []),
               Cohort("dsB", "p", "pd", [pb, cb], [])]
    summary = DigestSummary()
    summary.add("d0")
    plan = check_campaign_invariant(cohorts, {"n0": summary})
    node_of = {j: s.node_id for s in plan.shards for j in s.unit_ids}
    assert node_of == {pa.job_id: "n0", ca.job_id: "n0",
                       pb.job_id: None, cb.job_id: None}
