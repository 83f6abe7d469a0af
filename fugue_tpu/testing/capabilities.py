"""Environment capability probes for tests whose prerequisites depend on
the container, not the code: multi-process CPU collectives (jax's CPU
backend only implements them in some builds).

Each probe runs in subprocesses with a hard timeout, caches its verdict
for the process lifetime, and returns ``(ok, reason)`` so tests can
``pytest.skip(reason)`` — a capability-check skip instead of a
container-dependent failure."""

import os
import socket
import subprocess
import sys
import textwrap
from typing import Dict, Tuple

_CACHE: Dict[str, Tuple[bool, str]] = {}

_COLLECTIVES_INNER = textwrap.dedent(
    """
    import sys
    import jax

    pid = int(sys.argv[1])
    jax.distributed.initialize(
        coordinator_address=sys.argv[2], num_processes=2, process_id=pid
    )
    import jax.numpy as jnp
    from jax.experimental import multihost_utils

    x = multihost_utils.process_allgather(jnp.ones((2,)) * (pid + 1))
    assert float(x.sum()) == 6.0, x
    print("COLLECTIVES_OK")
    """
)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def cpu_multiprocess_collectives(timeout: float = 90.0) -> Tuple[bool, str]:
    """Can two CPU-backend jax processes run a cross-process collective?
    Spawns two tiny subprocesses doing ``jax.distributed.initialize`` +
    ``process_allgather``; the known-bad container answer ("Multiprocess
    computations aren't implemented on the CPU backend") fails in a few
    seconds."""
    if "cpu_collectives" in _CACHE:
        return _CACHE["cpu_collectives"]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    coordinator = f"127.0.0.1:{_free_port()}"
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _COLLECTIVES_INNER, str(pid), coordinator],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
        )
        for pid in range(2)
    ]
    ok, reason = True, ""
    try:
        for p in procs:
            try:
                out, err = p.communicate(timeout=timeout)
            except subprocess.TimeoutExpired:
                ok, reason = (
                    False, "cross-process collective probe timed out"
                )
                break
            if p.returncode != 0 or "COLLECTIVES_OK" not in out:
                tail = (
                    err.strip().splitlines()[-1]
                    if err.strip()
                    else "no output"
                )
                ok, reason = False, (
                    f"CPU backend lacks multiprocess collectives: {tail}"
                )
                break
    finally:
        # one peer failing fast leaves the other blocked in the
        # coordinator rendezvous: kill and reap EVERY survivor on any
        # exit path, not just the timeout branch
        for q in procs:
            if q.poll() is None:
                q.kill()
            try:
                q.communicate(timeout=5)
            except Exception:  # pragma: no cover - already reaped/wedged
                pass
    _CACHE["cpu_collectives"] = (ok, reason)
    return ok, reason
