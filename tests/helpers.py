"""Shared helpers for the tests: worker spawning, ports and env for the multi-process ones,
and ``compiled`` for every comparison of a program function with an oracle."""

import os
import socket
import subprocess

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def worker_env(**extra) -> dict:
    """Env for spawned workers: repo on PYTHONPATH, one device per process."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    env.update(extra)
    return env


def spawn_and_collect(cmds, env, timeout=180):
    """Fan out worker commands and collect (rc, stdout, stderr) per worker.
    Always kills stragglers — a regression that deadlocks a worker must fail
    the test, not hang CI holding the rendezvous port."""
    procs = [
        subprocess.Popen(
            c, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True
        )
        for c in cmds
    ]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=timeout)
            outs.append((p.returncode, out, err))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    return outs


def compiled(fn, *args):
    """``fn(*args)`` as one compiled program.  A comparison of a program
    function with an oracle runs both sides through here: called eagerly, a
    model's two passes are dispatched one operation at a time, and every
    operation of a new shape is a compilation of its own (a composition that
    slices at a different end for every block took 16.9 s so and 1.3 s
    compiled, PR 48).  ``args`` are arrays or trees of them; whatever else
    ``fn`` needs, it closes over.  A precision context around the call holds
    inside it: it is read when the function is traced."""
    return jax.jit(fn)(*args)
