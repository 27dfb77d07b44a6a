"""Spawn N local worker processes joined via jax.distributed on CPU — the
analog of the reference's N-JVMs-on-one-host test clouds (SURVEY.md §4:
multi-JVM loopback cloud), exercising real process boundaries that the
8-virtual-device single-process mesh cannot (per-process ingest,
make_array_from_process_local_data, coordination-service collectives)."""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER_PRELUDE = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, {repo!r})
    import jax
    jax.distributed.initialize(
        coordinator_address=os.environ["H2O3_TEST_COORD"],
        num_processes=int(os.environ["H2O3_TEST_NPROCS"]),
        process_id=int(os.environ["H2O3_TEST_RANK"]),
    )
""")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_workers(n: int, body: str, extra_env=None, timeout=300):
    """Run `body` (python source, after the jax.distributed prelude) in n
    local processes. Returns per-rank CompletedProcess; raises on any
    nonzero exit with the failing rank's output in the message."""
    coord = f"127.0.0.1:{free_port()}"
    script = WORKER_PRELUDE.format(repo=REPO) + textwrap.dedent(body)
    env = dict(os.environ)
    # exactly ONE cpu device per worker: replace the parent suite's
    # 8-virtual-device XLA_FLAGS rather than inheriting it. The workers are
    # CPU processes by construction — pinned explicitly, so a parent that
    # holds an accelerator never has a child reach for it
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["JAX_PLATFORMS"] = "cpu"
    env["H2O3_TEST_COORD"] = coord
    env["H2O3_TEST_NPROCS"] = str(n)
    if extra_env:
        env.update(extra_env)
    procs = []
    for rank in range(n):
        e = dict(env)
        e["H2O3_TEST_RANK"] = str(rank)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", script], env=e,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    for rank, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise AssertionError(f"worker {rank} timed out")
        outs.append(out)
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, (
            f"worker {rank} exited {p.returncode}:\n{out[-4000:]}")
    return outs
