"""``unest_wholebrain`` against its plain float32 reference, at a small
preset on the CPU: window 32 (token grid 8³, blocks of 2³: 64, 8 and 1 of
them), dims 16/32/64, depths 1/1/2, heads 2/2/4, 4 features, 5 classes. The
reference is the benchmark's copy, which imports nothing of the program and
draws the same weights from the same seed."""
import ast
import dataclasses
import functools
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (LocalRunner, Pipeline, builtin_pipelines,
                        generate_jobs, synthesize_dataset, trace, unest)
from repro.core.pipelines import UNEST_WHOLEBRAIN

ROOT = Path(__file__).resolve().parents[1]
SMALL = {**UNEST_WHOLEBRAIN, "classes": 5, "embed_dims": [16, 32, 64],
         "depths": [1, 1, 2], "num_heads": [2, 2, 4], "feature_size": 4,
         "roi": 32}
CFG = unest.UNesTConfig(classes=5, embed_dims=(16, 32, 64), depths=(1, 1, 2),
                        num_heads=(2, 2, 4), feature_size=4, roi=32)
SEED = SMALL["weight_seed"]


@pytest.fixture(scope="module")
def ref():
    path = ROOT / "benchmarks" / "chip" / "reference" / "unest_wholebrain.py"
    spec = importlib.util.spec_from_file_location("unest_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _float32_program(monkeypatch, vol, cfg=CFG):
    """The program's own sliding window with every activation and weight
    kept in float32, to hold its mathematics to the reference's at rounding
    level. A fresh jit: the module's compiled bfloat16 programs and cached
    weights are neither used nor replaced."""
    with monkeypatch.context() as m:
        m.setattr(unest, "BF16", jnp.float32)
        fn = jax.jit(unest._sliding_window.__wrapped__,
                     static_argnames="cfg")
        with jax.default_matmul_precision("highest"):
            out = fn(jnp.asarray(vol), unest._init_weights(cfg, SEED,
                                                           jnp.float32),
                     cfg=cfg)
        return {k: np.asarray(v) for k, v in out.items()}


def _forward():
    """A fresh jit of the program's forward at the small preset."""
    return jax.jit(functools.partial(unest.forward, cfg=CFG))


def _volume(shape, seed=0):
    rng = np.random.default_rng(seed)
    axes = np.meshgrid(*[np.linspace(-1, 1, s) for s in shape],
                       indexing="ij")
    blob = 400 * (sum(a * a for a in axes) < 0.6) + 100
    return (blob + rng.normal(0, 20, shape)).astype(np.float32)


def test_weights_are_the_references_draws(ref):
    got = unest._init_weights(CFG, SEED, jnp.float32)
    want = ref.weights(SMALL)
    assert set(got) == set(want)
    for k in got:
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]))


def test_one_window_logits_match_the_reference(ref, monkeypatch):
    x = jax.random.normal(jax.random.PRNGKey(3), (32, 32, 32))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref._window(ref.weights(SMALL), x, SMALL,
                                      lowp=False))
    got = np.asarray(_forward()(unest.weights(CFG, SEED), x[None])[0])
    scale = np.abs(want).max()
    # bfloat16 weights and activations (2^-9 relative rounding a step, over
    # about 20 layers) against float32: 0.7% of the largest logit measured;
    # 3% leaves room and still fails a layer left out or mis-wired
    assert np.abs(got - want).max() <= 3e-2 * scale
    with monkeypatch.context() as m, \
            jax.default_matmul_precision("highest"):
        m.setattr(unest, "BF16", jnp.float32)
        exact = np.asarray(_forward()(
            unest._init_weights(CFG, SEED, jnp.float32), x[None])[0])
    # the same mathematics in float32: summation order alone
    assert np.abs(exact - want).max() <= 1e-5 * scale


@pytest.mark.parametrize("n", [4, 2, 1])
def test_blocks_split_and_merge(n):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8, 8, 3))
    b = unest.blockify(x, n)
    s = 8 // n
    assert b.shape == (2, n ** 3, s ** 3, 3)
    np.testing.assert_array_equal(np.asarray(unest.deblockify(b, n)),
                                  np.asarray(x))
    # block 1 is the next block along the last axis; its token 1 the next
    # voxel along the last axis inside it
    if n > 1:
        np.testing.assert_array_equal(np.asarray(b[:, 1, 1]),
                                      np.asarray(x[:, 0, 0, s + 1]))


def test_aggregation_halves_the_grid_and_widens_the_channels():
    p = unest.weights(CFG, SEED)
    x = jnp.ones((2, 8, 8, 8, 16), jnp.bfloat16)
    assert unest.aggregate(p, 1, x, CFG.ln_eps).shape == (2, 4, 4, 4, 32)
    y = jnp.ones((2, 4, 4, 4, 32), jnp.bfloat16)
    assert unest.aggregate(p, 2, y, CFG.ln_eps).shape == (2, 2, 2, 2, 64)
    t, levels = jax.jit(functools.partial(unest.encode, cfg=CFG))(
        p, jnp.ones((1, 32, 32, 32, 1)))
    assert t.shape == (1, 8, 8, 8, 16)
    assert [h.shape for h in levels] == [(1, 8, 8, 8, 16), (1, 4, 4, 4, 32),
                                         (1, 2, 2, 2, 64)]


def test_window_starts():
    starts = unest.window_starts((256, 256, 176), 96, 0.7)
    assert len(starts) == 196
    assert sorted(set(starts[:, 0])) == [0, 28, 56, 84, 112, 140, 160]
    assert sorted(set(starts[:, 2])) == [0, 28, 56, 80]
    assert unest.n_windows((256, 256, 176), unest.UNesTConfig()) == 196
    # a volume smaller than the window is padded to one window
    assert unest.n_windows((32, 32, 24), unest.UNesTConfig()) == 1
    assert unest.padding((32, 32, 24), 96) == [(32, 32), (32, 32), (36, 36)]
    assert unest.window_starts((96, 96, 96), 96, 0.7).tolist() == [[0, 0, 0]]


def test_window_starts_are_the_references(ref):
    for shape in [(256, 256, 176), (40, 33, 32), (96, 97, 200)]:
        got = [tuple(s) for s in unest.window_starts(shape, 32, 0.7)]
        assert got == ref.starts(shape, 32, 0.7)


@pytest.mark.parametrize("classes", [5, 133])
def test_sliding_window_matches_the_reference(ref, monkeypatch, classes):
    """A volume of sizes that are no multiple of the window's stride, one
    axis shorter than the window (padded): the blend, count map and crop
    against the reference's window-by-window loop. At 133 classes the first
    128 are blended in a buffer of their own, classes minor."""
    vol = _volume((40, 33, 20))
    cfg = dataclasses.replace(CFG, classes=classes)
    small = {**SMALL, "classes": classes}
    assert [p[:2] for p in unest.blend_parts(classes, (40, 33, 32))] == \
        ([(0, 128), (128, 133)] if classes > 128 else [(0, classes)])
    assert unest.n_windows(vol.shape, cfg) == 4           # 2 x 2 x 1
    want = ref.reference({"T1w": vol}, small)
    exact = _float32_program(monkeypatch, vol, cfg)
    # float32 both sides: summation order alone
    np.testing.assert_allclose(exact["label_confidence"],
                               want["label_confidence"], rtol=0, atol=1e-5)
    clear = want["margin"] > 1e-4
    assert clear.mean() > 0.9
    np.testing.assert_array_equal(exact["segmentation"][clear],
                                  want["segmentation"][clear])
    got = {k: np.asarray(v) for k, v in
           unest.segment(vol, cfg, SEED).items()}
    assert got["segmentation"].dtype == np.uint8
    assert got["label_confidence"].shape == vol.shape
    gaps = ref.gaps(got, want, small)
    # bfloat16 program against float32: the confidence gap read 0.017 at
    # this preset; labels agree exactly wherever the reference's top two
    # probabilities lie apart by more than twice the benchmark's limit
    assert gaps["confidence"] <= 0.05 and gaps["labels"] == 0.0, gaps


def _small_pipeline():
    pipe = builtin_pipelines()["unest_wholebrain"]
    changed = {k: v for k, v in SMALL.items() if pipe.spec.config[k] != v}
    return Pipeline(dataclasses.replace(pipe.spec, config=SMALL),
                    functools.partial(pipe.fn, **changed))


def test_weights_are_built_once_over_two_units(tmp_path):
    unest._built_weights.cache_clear()
    ds = synthesize_dataset(tmp_path / "archive", "vds", n_subjects=2,
                            sessions_per_subject=1, shape=(32, 32, 32))
    pipe = _small_pipeline()
    plan = generate_jobs(ds, pipe, tmp_path / "jobs")
    before = trace.counters()
    results = LocalRunner(pipe, ds.root).run(plan.units)
    after = trace.counters()
    assert [r.status for r in results] == ["ok", "ok"]

    def grew(key):
        return after.get(key, 0) - before.get(key, 0)
    assert grew("unest.weights.n") == 1
    assert grew("unest.infer.n") == 2
    assert grew("unest.windows") == 2                     # one a unit
    for u in plan.units:
        seg = np.load(Path(u.out_dir) / f"sub-{u.subject}_ses-{u.session}"
                      "_segmentation.npy")
        assert seg.dtype == np.uint8 and seg.shape == (32, 32, 32)


def test_a_second_volume_of_the_same_shape_compiles_nothing():
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    unest._sliding_window.clear_cache()
    try:
        counts = []
        for seed in range(2):
            before = trace.counters().get("compile.backend.n", 0)
            jax.block_until_ready(unest.segment(_volume((36, 32, 24), seed),
                                                CFG, SEED))
            counts.append(trace.counters().get("compile.backend.n", 0)
                          - before)
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
    assert counts[0] >= 1 and counts[1] == 0


def test_unest_imports_nothing_of_the_language_model_stack():
    tree = ast.parse((ROOT / "src" / "repro" / "core" / "unest.py")
                     .read_text())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names]
    names += [("." * n.level) + (n.module or "") for n in ast.walk(tree)
              if isinstance(n, ast.ImportFrom)]
    assert not [m for m in names if "models" in m or "configs" in m], names
