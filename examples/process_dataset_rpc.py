"""Networked cluster processing (ROADMAP: cluster transport): the coordinator
serves its WorkQueue over a TCP JSON-lines socket, its own nodes talk to it
through the same client a remote machine would use, and a genuinely separate
worker *process* dials in, registers, steals work, and commits to shared
storage — with every host serving repeated inputs from its content-addressed
cache instead of shared storage (watch ``cache_hit`` flip to True on re-runs).

    PYTHONPATH=src python examples/process_dataset_rpc.py
"""
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
from collections import Counter
from pathlib import Path

from repro.core import (Provenance, builtin_pipelines, query_available_work,
                        synthesize_dataset)
from repro.dist import ClusterRunner
from repro.launch.env import enable_compile_cache

enable_compile_cache()

with tempfile.TemporaryDirectory() as td:
    td = Path(td)
    ds = synthesize_dataset(td / "ds", "MASIVar-rpc", n_subjects=10,
                            sessions_per_subject=2, shape=(16, 16, 16))
    pipe = builtin_pipelines()["bias_correct"]
    units, _ = query_available_work(ds, pipe)
    print(f"work query: {len(units)} units")
    print("roles: this process runs the coordinator and the nodes that "
          "compute on the default device; the worker process is a CPU host "
          "(JAX_PLATFORMS=cpu)")

    def run_once(tag):
        runner = ClusterRunner(pipe, ds.root, nodes=2, transport="rpc",
                               poll_s=0.03, cache_dir=td / "host-cache")
        got = {}
        t = threading.Thread(target=lambda: got.update(
            r=runner.run(query_available_work(ds, pipe)[0])))
        t.start()
        while runner.server is None and t.is_alive():
            time.sleep(0.01)

        # one worker host in its own process: joins via the CLI entrypoint,
        # with its own input cache (REPRO_CACHE_DIR) like a real machine.
        # This process's nodes hold the accelerator, and a chip serves one
        # process at a time, so the second host computes on the CPU
        env = dict(os.environ,
                   PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH", ""),
                   REPRO_CACHE_DIR=str(td / "ext-cache"), JAX_PLATFORMS="cpu")
        # its output goes to a file: a pipe read only after the run would
        # block the worker once its logs filled the pipe's buffer
        log = td / f"worker-{tag}.log"
        with open(log, "w") as out:
            worker = subprocess.Popen(
                [sys.executable, "-m", "repro.dist.rpc", "work",
                 "--addr", runner.server.addr_str, "--pipeline", pipe.name,
                 "--data-root", str(ds.root), "--node-id", "ext-host"],
                env=env, stdout=out, stderr=subprocess.STDOUT)
            t.join()
            worker.wait()
        said = [ln for ln in log.read_text().splitlines()
                if ln.startswith("ext-host")]
        print(f"[{tag}] worker process said: {' / '.join(said)}")
        results = got["r"]
        counts = Counter(r.status for r in results)
        st = runner.stats
        hits = sum(1 for u in units
                   if Provenance.load(Path(u.out_dir)).cache_hit)
        print(f"[{tag}] {counts['ok']}/{len(units)} ok "
              f"(+{counts.get('speculative', 0)} speculative) · "
              f"processed {st.processed} · remote nodes {st.remote_nodes}")
        print(f"[{tag}] coordinator-host cache: {st.cache} · "
              f"{hits} commits stamped cache_hit=True")
        assert counts["ok"] == len(units)

    run_once("cold")
    # wipe derivatives but keep the host caches: the re-run's inputs never
    # touch shared storage — this is the repeated-cohort path the per-host
    # cache exists for
    shutil.rmtree(Path(ds.root) / "derivatives")
    run_once("warm")
