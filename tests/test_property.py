"""Hypothesis property tests on system invariants."""
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.integrity import fletcher64
from repro.core.cost import job_cost, PAPER_ENVS
from repro.kernels.checksum import device_checksum, device_checksum_ref
from repro.analysis.hlo_parse import split_computations, HloCosts

import jax.numpy as jnp


@given(st.binary(min_size=0, max_size=2048))
@settings(max_examples=50, deadline=None)
def test_fletcher64_deterministic_and_padded(data):
    a = fletcher64(data)
    assert a == fletcher64(data)
    assert 0 <= a < 2 ** 64


@given(st.binary(min_size=4, max_size=512), st.integers(0, 3))
@settings(max_examples=50, deadline=None)
def test_fletcher64_sensitive_to_flips(data, pos):
    flipped = bytearray(data)
    flipped[pos % len(data)] ^= 0x01
    if bytes(flipped) != data:
        assert fletcher64(data) != fletcher64(bytes(flipped))


@given(st.lists(st.floats(-1e3, 1e3, allow_nan=False, width=32),
                min_size=1, max_size=300))
@settings(max_examples=25, deadline=None)
def test_device_checksum_matches_ref(xs):
    arr = np.asarray(xs, np.float32)
    got = np.asarray(device_checksum(jnp.asarray(arr), interpret=True))
    ref = device_checksum_ref(arr)
    assert np.array_equal(got, ref)


@given(st.integers(1, 1000), st.floats(1.0, 600.0), st.floats(0.01, 100.0))
@settings(max_examples=50, deadline=None)
def test_job_cost_monotone(n_jobs, minutes, gb):
    """More jobs / longer jobs never cost less; cloud >= hpc per-hour."""
    for env in PAPER_ENVS.values():
        c1 = job_cost(env, n_jobs, minutes, gb)
        c2 = job_cost(env, n_jobs + 1, minutes, gb)
        c3 = job_cost(env, n_jobs, minutes * 2, gb)
        assert c2["dollars"] >= c1["dollars"]
        assert c3["dollars"] >= c1["dollars"]


@given(st.integers(1, 64), st.integers(1, 8))
@settings(max_examples=20, deadline=None)
def test_hlo_loop_multiplication(trips, nbytes_mb):
    """Synthetic HLO: collective inside a while body is multiplied by the
    trip count inferred from the condition."""
    n = nbytes_mb * 262144     # f32 elements per MB
    hlo = f"""
cond {{
  p = (s32[]) parameter(0)
  i = s32[] get-tuple-element(p), index=0
  t = s32[] constant({trips})
  ROOT lt = pred[] compare(i, t), direction=LT
}}

body {{
  p = (s32[]) parameter(0)
  ar = f32[{n}] all-reduce(x), to_apply=add
  ROOT out = (s32[]) tuple(i)
}}

ENTRY main {{
  w = (s32[]) while(init), condition=cond, body=body
  ROOT r = s32[] get-tuple-element(w), index=0
}}
"""
    costs = HloCosts(hlo)
    got = costs.collective_bytes()
    assert got["per_op"]["all-reduce"] == trips * n * 4


def test_split_computations_basic():
    hlo = "comp_a {\n  x = f32[2] parameter(0)\n}\n\nENTRY main {\n  y = f32[2] constant(0)\n}\n"
    comps = split_computations(hlo)
    assert set(comps) == {"comp_a", "main"}


@given(n_subjects=st.integers(1, 4), sessions=st.integers(1, 2),
       nodes=st.integers(1, 3), flaky=st.booleans(),
       die=st.integers(0, 3), harass_peers=st.booleans())
@settings(max_examples=8, deadline=None)
def test_cluster_exactly_one_ok_provenance_and_no_torn_files(
        n_subjects, sessions, nodes, flaky, die, harass_peers):
    """Distributed-executor invariant: for random unit lists, node counts and
    injected failures (transient faults + one node death), every unit ends
    with exactly one committed ok provenance, and a concurrent reader NEVER
    observes a partial output file or torn provenance (atomic tmp+rename).
    ``harass_peers`` additionally runs the blob fabric under hostile peers
    (dead addrs, corrupted bodies, Bloom false positives) — every peer
    failure must fall back to storage without disturbing the invariant.
    Body shared with the deterministic sweep in test_cluster.py."""
    from cluster_invariant import check_cluster_invariant
    check_cluster_invariant(n_subjects, sessions, nodes, flaky, die,
                            harass_peers=harass_peers)


@st.composite
def _dag_edges(draw, max_units=8):
    """Random acyclic ``{child_pos: [parent_pos, ...]}`` topologies: every
    parent index is strictly smaller than its child, so chains, diamonds and
    fan-in gates all appear but cycles cannot. Positions past the actual
    unit count are dropped by the harness's normalization."""
    edges = {}
    for c in range(1, max_units):
        ps = draw(st.lists(st.integers(0, c - 1), max_size=2, unique=True))
        if ps:
            edges[c] = sorted(ps)
    return edges


@given(n_subjects=st.integers(2, 4), nodes=st.integers(1, 3),
       flaky=st.booleans(), die=st.integers(0, 2), edges=_dag_edges(),
       fail=st.one_of(st.none(), st.integers(0, 7)))
@settings(max_examples=8, deadline=None)
def test_cluster_dag_gating_and_failure_propagation(
        n_subjects, nodes, flaky, die, edges, fail):
    """DAG extension of the executor invariant, over random topologies
    (chains, diamonds, fan-in gates) with chaos (transient faults, node
    death) and optionally one permanently failing unit: runnable units end
    with exactly one ok provenance, no child's provenance predates its last
    parent's commit, and a failed unit's transitive descendants end
    terminally ``blocked`` — no provenance, no output dir, surfaced in
    ``stats_snapshot()['dag']``. Body shared with the deterministic grid in
    test_dag.py / test_cluster.py."""
    from cluster_invariant import check_cluster_invariant
    check_cluster_invariant(n_subjects, 2, nodes, flaky, die,
                            dag_edges=edges, fail_idx=fail)


_DIGEST_POOL = [f"d{i}" for i in range(12)]


@st.composite
def _cohorts_and_summaries(draw):
    """Arbitrary campaign inputs: 1-3 cohorts of synthetic work units (0-2
    input digests each, drawn from a small pool so summaries genuinely
    overlap), exclusion lists that may name admitted sessions, an optional
    re-submitted duplicate cohort, and 0-3 per-node digest summaries."""
    import dataclasses
    from repro.core.campaign import Cohort
    from repro.core.query import Exclusion, WorkUnit
    from repro.dist import DigestSummary
    cohorts = []
    for c in range(draw(st.integers(1, 3))):
        units = []
        for i in range(draw(st.integers(0, 8))):
            digs = draw(st.lists(st.sampled_from(_DIGEST_POOL),
                                 max_size=2, unique=True))
            size = draw(st.integers(0, 1 << 16))
            units.append(WorkUnit(
                dataset=f"ds{c}", subject=f"s{i:02d}", session="01",
                pipeline="p", pipeline_digest="pd",
                inputs={f"in{k}": f"{i}-{k}.npy" for k in range(len(digs))},
                out_dir=f"/out/ds{c}/{i}",
                input_digests={f"in{k}": d for k, d in enumerate(digs)},
                input_bytes={f"in{k}": size for k in range(len(digs))}))
        # sprinkle depends_on edges onto later units (parents always earlier
        # in admission order, so the random DAG is acyclic by construction;
        # an excluded parent exercises the absent-parent-is-satisfied rule)
        for i in range(1, len(units)):
            ps = draw(st.lists(st.integers(0, i - 1), max_size=2,
                               unique=True))
            units[i].depends_on = [units[p].job_id for p in ps]
        excluded = [Exclusion(f"s{draw(st.integers(0, 9)):02d}", "01", "x")
                    for _ in range(draw(st.integers(0, 3)))]
        cohorts.append(Cohort(f"ds{c}", "p", "pd", units, excluded))
    if draw(st.booleans()):                      # overlapping re-submission
        cohorts.append(dataclasses.replace(cohorts[0]))
    summaries = {}
    for n in range(draw(st.integers(0, 3))):
        s = DigestSummary(m=512, k=3)
        for d in draw(st.lists(st.sampled_from(_DIGEST_POOL),
                               max_size=6, unique=True)):
            s.add(d)
        summaries[f"n{n}"] = s
    throttle = draw(st.integers(1, 64))
    status = {"disk_free_gb": draw(st.floats(0.0, 64.0, allow_nan=False))}
    max_shard = draw(st.one_of(st.none(), st.integers(1, 4)))
    return cohorts, summaries, throttle, status, max_shard


@given(_cohorts_and_summaries())
@settings(max_examples=40, deadline=None)
def test_campaign_plan_exactly_once_no_excluded_byte_replayable(case):
    """Campaign-planner invariant: for arbitrary cohorts and summary states,
    every admitted unit is assigned to exactly one shard, a unit its cohort
    excluded is never assigned, replanning — in memory and through the
    serialized campaign.json — is byte-identical, and a DAG child whose only
    warmth is its parents' predicted outputs is producer-placed onto the
    parents' node (the admission-time twin of the executor invariant below;
    body shared with the deterministic grid in test_campaign.py)."""
    from campaign_invariant import check_campaign_invariant
    cohorts, summaries, throttle, status, max_shard = case
    check_campaign_invariant(cohorts, summaries, throttle=throttle,
                             status=status, max_shard_units=max_shard)


@given(st.integers(2, 16), st.integers(2, 8), st.integers(2, 8))
@settings(max_examples=10, deadline=None)
def test_moe_dispatch_conservation(S, E, C):
    """Scatter-dispatch: every kept token appears exactly once in the buffer;
    combine-gather reconstructs identity when experts are identity."""
    import jax
    from repro.models.moe import _dispatch_seq
    key = jax.random.PRNGKey(S * 100 + E * 10 + C)
    x = jax.random.normal(key, (S, 4))
    sel = jax.random.randint(key, (S, 1), 0, E)
    w = jnp.ones((S, 1))
    buf, idx, keep = _dispatch_seq(x, sel, w, E, C)
    # gather back the kept tokens: must equal the originals
    kept = np.asarray(keep)[:, 0]
    got = np.asarray(buf)[np.asarray(idx)[:, 0][kept]]
    want = np.asarray(x)[kept]
    assert np.allclose(got, want, atol=1e-6)
    # buffer rows not pointed to by any kept slot are zero
    used = set(np.asarray(idx)[:, 0][kept].tolist())
    for row in range(E * C):
        if row not in used:
            assert np.allclose(np.asarray(buf)[row], 0.0)


# NaN and +-inf come from sampled_from: hypothesis refuses allow_nan with
# bounds. The finite draw includes float32 subnormals, which every fold
# flushes to zero (repro.kernels.checksum.ref.ftz_f32).
_QA_VALUES = st.one_of(
    st.floats(-1e4, 1e4, width=32),
    st.sampled_from([float("nan"), float("inf"), float("-inf"), -0.0,
                     1.805228351860303e-39, -1e-40]))


@given(st.lists(_QA_VALUES, min_size=0, max_size=600),
       st.integers(1, 3000),
       st.sampled_from(["float32", "float16", "int16", "uint8"]))
@settings(max_examples=40, deadline=None)
def test_chunked_qa_fold_bit_exact_vs_one_shot(xs, chunk, dtype):
    """Streaming ingest invariant (repro.core.stream): feeding a volume's
    bytes through the chunk-accumulating fused QA+checksum fold in ANY
    chunking — including chunk > volume and non-dividing tails — must be
    bit-identical to the one-shot kernel. (Deterministic slice of this sweep
    lives in test_stream.py for environments without hypothesis.)"""
    from repro.kernels.checksum import QAChecksumAccumulator, qa_stats
    arr = np.asarray(xs, np.float32)
    if dtype != "float32":
        with np.errstate(invalid="ignore", over="ignore"):
            arr = arr.astype(dtype)
    acc = QAChecksumAccumulator(arr.size, arr.dtype, interpret=True)
    data = arr.tobytes()
    for off in range(0, max(len(data), 1), chunk):
        acc.update(data[off:off + chunk])
    assert acc.finalize() == qa_stats(arr, interpret=True)
