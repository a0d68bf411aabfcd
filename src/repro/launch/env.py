"""Allocator / XLA-flags environment profile for coordinator and worker
processes — the launch-script hygiene every serious JAX deployment carries
in its ``run.sh``, folded into the tree so ``python -m repro.dist.rpc
serve|work`` applies it without a wrapper script.

What the profile sets (and why):

* ``TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD`` — silence tcmalloc's
  large-allocation warnings for the multi-hundred-MB image volumes the
  pipelines routinely allocate; the reports are stderr noise at best and a
  per-allocation slowdown at worst.
* ``TF_CPP_MIN_LOG_LEVEL=4`` — mute the TF/XLA C++ banner and dataset
  warnings that otherwise swamp worker logs at fleet scale.
* ``XLA_FLAGS`` — ``--xla_force_host_platform_device_count=1``: control-
  plane and per-unit pipeline processes want one host device, not one per
  core (faster startup, no pointless intra-host sharding of tiny pipeline
  stages). Merged, never clobbered: flags the operator already set win.
* ``LD_PRELOAD`` → tcmalloc, when a known ``libtcmalloc`` exists on the
  host. A dynamic linker option can only take effect at process start, so
  :func:`apply_env_profile` exports it for *children* (the worker
  subprocesses a coordinator or launcher spawns) while
  :func:`format_exports` emits it for shell scripts that can set it before
  exec — the SLURM shard template evals the latter on the compute node.

Everything is fail-soft and override-safe: variables the process already
has keep their values, a missing tcmalloc just drops the preload, and
``REPRO_ENV_PROFILE=off`` disables the whole profile.

:func:`enable_compile_cache` turns on JAX's persistent compilation cache,
so a process that repeats a compile (each SLURM ``--run-one`` task is a new
process) reads it back instead. Entry points call it; library code never
does.
"""
from __future__ import annotations

import os
import shlex
from pathlib import Path
from typing import Dict, Mapping, Optional

ENV_PROFILE_ENV = "REPRO_ENV_PROFILE"     # "off"/"0"/"none" disables

ROLES = ("coordinator", "worker")

# allocator + logging hygiene, identical for both roles
_COMMON = {
    "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD": "60000000000",
    "TF_CPP_MIN_LOG_LEVEL": "4",
}

# per-role XLA flags, merged into any operator-set XLA_FLAGS
_XLA_FLAGS = {
    "coordinator": ["--xla_force_host_platform_device_count=1"],
    "worker": ["--xla_force_host_platform_device_count=1"],
}

# well-known tcmalloc locations (Debian/Ubuntu full + minimal builds);
# first hit wins, no hit = no preload
TCMALLOC_CANDIDATES = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
    "/usr/lib/libtcmalloc.so.4",
    "/usr/lib/libtcmalloc_minimal.so.4",
)


def _find_tcmalloc() -> Optional[str]:
    for cand in TCMALLOC_CANDIDATES:
        if os.path.exists(cand):
            return cand
    return None


def _merge_xla_flags(existing: str, wanted) -> str:
    """Append each wanted flag unless a flag with the same ``--name`` is
    already present (operator settings win; repeated application is a
    no-op)."""
    parts = existing.split()
    have = {p.split("=", 1)[0] for p in parts}
    for flag in wanted:
        if flag.split("=", 1)[0] not in have:
            parts.append(flag)
    return " ".join(parts)


def env_profile(role: str = "worker",
                base: Optional[Mapping[str, str]] = None) -> Dict[str, str]:
    """The variables the profile would set for ``role``, given the current
    (or a supplied) environment — only the ones that change: anything the
    environment already pins is left out (except ``XLA_FLAGS``, which is
    returned merged when new flags are added)."""
    if role not in ROLES:
        raise ValueError(f"unknown role {role!r} (want one of {ROLES})")
    base = os.environ if base is None else base
    out: Dict[str, str] = {}
    for k, v in _COMMON.items():
        if k not in base:
            out[k] = v
    merged = _merge_xla_flags(base.get("XLA_FLAGS", ""), _XLA_FLAGS[role])
    if merged != base.get("XLA_FLAGS", ""):
        out["XLA_FLAGS"] = merged
    if "LD_PRELOAD" not in base:
        tcm = _find_tcmalloc()
        if tcm is not None:
            out["LD_PRELOAD"] = tcm
    return out


def _disabled() -> bool:
    return os.environ.get(ENV_PROFILE_ENV, "").lower() in ("off", "0", "none")


def apply_env_profile(role: str = "worker") -> Dict[str, str]:
    """Apply the profile to ``os.environ`` (call before importing jax — the
    flags are read at import). Returns what was set. ``LD_PRELOAD`` set
    here cannot re-link the *current* process (the dynamic linker already
    ran); it still reaches every child process, which is where workers and
    their pipelines run. No-op when ``REPRO_ENV_PROFILE`` disables it."""
    if _disabled():
        return {}
    prof = env_profile(role)
    os.environ.update(prof)
    return prof


def format_exports(role: str = "worker",
                   base: Optional[Mapping[str, str]] = None) -> str:
    """The profile as ``export K=V`` shell lines (values quoted) — for
    launch scripts, where ``LD_PRELOAD`` can take effect before the python
    process starts. Empty string when the profile is disabled."""
    if _disabled():
        return ""
    prof = env_profile(role, base=base)
    return "\n".join(f"export {k}={shlex.quote(v)}"
                     for k, v in sorted(prof.items()))


def enable_compile_cache() -> Path:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory: ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX
    already reads it, so no other directory is set), else the fixed
    ``<checkout>/.jax_cache`` — never a pid-, time- or tempdir-based path,
    which no later process would find again. JAX's one-second floor
    stays: it keeps the stage compiles (tens of seconds on a TPU) and skips
    the many sub-second CPU compiles, whose cache hits each log a
    multi-kilobyte XLA:CPU feature-mismatch line in JAX 0.9. Call from an
    entry point, before the first compile."""
    import jax
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return Path(env_dir)
    path = Path(__file__).resolve().parents[3] / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    return path


def _main():
    import argparse
    ap = argparse.ArgumentParser(
        description="print the repro env profile as shell export lines "
                    "(eval \"$(python -m repro.launch.env --role worker)\")")
    ap.add_argument("--role", default="worker", choices=ROLES)
    args = ap.parse_args()
    exports = format_exports(args.role)
    if exports:
        print(exports)


if __name__ == "__main__":
    _main()
