"""Launcher-level behaviour: training driver, serving driver, SLURM writers,
and the allocator/XLA environment profile the launch scripts apply."""
import shlex
from pathlib import Path

import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.env import (ENV_PROFILE_ENV, apply_env_profile, env_profile,
                              format_exports)
from repro.launch.serve import serve_batch
from repro.launch.slurm import write_pod_launch, write_shard_script
from repro.launch.train import train


def test_train_driver_runs_and_checkpoints(tmp_path):
    params, losses = train("llama3.2-1b", steps=6, batch=2, seq=32,
                           data_dir=str(tmp_path / "d"),
                           ckpt_dir=str(tmp_path / "c"), ckpt_every=3,
                           log_every=3)
    assert len(losses) == 6
    assert all(np.isfinite(losses))
    assert list((tmp_path / "c").glob("step_*"))


def test_train_driver_resume(tmp_path):
    train("llama3.2-1b", steps=4, batch=2, seq=32,
          data_dir=str(tmp_path / "d"), ckpt_dir=str(tmp_path / "c"),
          ckpt_every=2)
    _, losses = train("llama3.2-1b", steps=6, batch=2, seq=32,
                      data_dir=str(tmp_path / "d"), ckpt_dir=str(tmp_path / "c"),
                      ckpt_every=2, resume=True)
    assert len(losses) == 2          # resumed at step 4


def test_serve_batch_shapes():
    cfg = get_config("llama3.2-1b").reduced()
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 16), dtype=np.int32)
    toks = serve_batch("llama3.2-1b", prompts, max_new=4)
    assert toks.shape == (2, 4)
    assert (toks >= 0).all() and (toks < cfg.vocab_size).all()


def test_pod_slurm_writer(tmp_path):
    p = write_pod_launch(tmp_path, arch="glm4-9b", n_hosts=64)
    s = Path(p).read_text()
    assert "#SBATCH --array=0-63" in s
    assert "JAX_NUM_PROCESSES=64" in s
    assert "--arch glm4-9b" in s and "--resume" in s


def test_dryrun_cli_reduced_smoke(tmp_path):
    """run_cell machinery on a tiny config via the library API (no 512-dev
    env needed: use the local mesh)."""
    import jax
    from repro.configs import SHAPE_BY_NAME
    from repro.dist.sharding import Rules
    from repro.launch.dryrun import rules_kind
    shape = SHAPE_BY_NAME["train_4k"]
    assert rules_kind(shape) == "train"
    assert rules_kind(SHAPE_BY_NAME["long_500k"]) == "long"
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    r = Rules(mesh, "train", "fsdp", global_batch=256)
    assert r.map["batch"]  # divisible on the 1x1 mesh


# ---------------------------------------------------------------------------
# environment profile (repro.launch.env)
# ---------------------------------------------------------------------------

def test_env_profile_sets_hygiene_and_merges_xla_flags():
    prof = env_profile("worker", base={})
    assert prof["TF_CPP_MIN_LOG_LEVEL"] == "4"
    assert "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD" in prof
    assert "--xla_force_host_platform_device_count=1" in prof["XLA_FLAGS"]


def test_env_profile_never_clobbers_operator_settings():
    base = {"TF_CPP_MIN_LOG_LEVEL": "0",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8 "
                         "--xla_dump_to=/tmp/x",
            "LD_PRELOAD": "/opt/custom.so"}
    prof = env_profile("coordinator", base=base)
    # operator-pinned vars stay out of the profile entirely; the XLA flag
    # the operator set by name wins, so XLA_FLAGS needs no merge at all
    assert "TF_CPP_MIN_LOG_LEVEL" not in prof
    assert "LD_PRELOAD" not in prof
    assert "XLA_FLAGS" not in prof


def test_env_profile_merges_only_missing_xla_flags():
    base = {"XLA_FLAGS": "--xla_dump_to=/tmp/x"}
    prof = env_profile("worker", base=base)
    assert prof["XLA_FLAGS"].startswith("--xla_dump_to=/tmp/x")
    assert "--xla_force_host_platform_device_count=1" in prof["XLA_FLAGS"]


def test_env_profile_unknown_role_rejected():
    with pytest.raises(ValueError, match="unknown role"):
        env_profile("gpu-wrangler")


def test_apply_env_profile_respects_off_switch(monkeypatch):
    monkeypatch.setenv(ENV_PROFILE_ENV, "off")
    assert apply_env_profile("worker") == {}
    assert format_exports("worker") == ""


def test_apply_env_profile_updates_environ(monkeypatch):
    monkeypatch.delenv(ENV_PROFILE_ENV, raising=False)
    monkeypatch.delenv("TF_CPP_MIN_LOG_LEVEL", raising=False)
    import os
    applied = apply_env_profile("worker")
    assert applied["TF_CPP_MIN_LOG_LEVEL"] == "4"
    assert os.environ["TF_CPP_MIN_LOG_LEVEL"] == "4"


def test_format_exports_emits_quoted_shell_lines():
    out = format_exports("worker", base={})
    lines = out.splitlines()
    assert all(line.startswith("export ") for line in lines)
    for line in lines:
        k, _, v = line[len("export "):].partition("=")
        assert shlex.split(v) == [shlex.split(v)[0]]   # one quoted value


def test_shard_script_evals_env_profile_before_python(tmp_path):
    p = write_shard_script(tmp_path, name="shard-000", n_units=4,
                           units_json="units.json",
                           manifest_json="manifest.json", data_root="/data")
    s = Path(p).read_text()
    assert 'eval "$(python -m repro.launch.env --role worker' in s
    # fail-soft on hosts where the package is missing, and the profile line
    # lands before the worker python starts
    assert "|| true" in s
    assert s.index("repro.launch.env") < s.index("repro.core.workflow")


@pytest.mark.parametrize("setting", ["set", "unset", "empty"])
def test_enable_compile_cache_lands_where_configured(tmp_path, setting):
    """In a fresh process (jax reads the variable at import): with the
    variable set the cache is written there; unset or empty, jax is pointed
    at the checkout's fixed directory, never a temporary or per-process
    path."""
    import json
    import os
    import subprocess
    import sys
    repo = Path(__file__).resolve().parents[1]
    # cache even this sub-second compile, so that something lands
    env = dict(os.environ, PYTHONPATH=str(repo / "src"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    from_env = setting == "set"
    if setting != "unset":
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path) if from_env else ""
    code = ("import json, jax, jax.numpy as jnp\n"
            "from repro.launch.env import enable_compile_cache\n"
            "p = enable_compile_cache()\n"
            + ("jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0))"
               ".block_until_ready()\n" if from_env else "")
            + "print(json.dumps([str(p), jax.config.jax_compilation_cache_dir"
              "]))\n")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    path, configured = json.loads(out.stdout.strip().splitlines()[-1])
    want = tmp_path if from_env else repo / ".jax_cache"
    assert Path(path) == Path(configured) == want
    if from_env:
        assert any(tmp_path.iterdir()), "nothing was cached"
