"""Real multi-process distributed bootstrap.

Spawns two OS processes that rendezvous through
``bagua_tpu.init_process_group(coordinator_address=...)`` (the analog of the
reference's torch-store NCCL-unique-id exchange) on the CPU backend, then
exercise ``broadcast_object`` across processes — the reference test strategy
of simulating multi-node with real processes on one host
(``tests/internal/multi_process.py``).
"""

import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow  # spawns OS-process gangs per test

from helpers import free_port, spawn_and_collect, worker_env

WORKER = textwrap.dedent(
    """
    import sys
    import jax
    jax.config.update("jax_platforms", "cpu")

    coordinator, proc_id = sys.argv[1], int(sys.argv[2])
    import bagua_tpu

    group = bagua_tpu.init_process_group(
        coordinator_address=coordinator, num_processes=2, process_id=proc_id
    )
    assert jax.process_count() == 2, jax.process_count()
    assert jax.process_index() == proc_id

    # broadcast a picklable object from process 1 (non-default src)
    obj = {"payload": [proc_id * 10, "hello"], "src": 1} if proc_id == 1 else None
    got = bagua_tpu.broadcast_object(obj, src=1)
    assert got == {"payload": [10, "hello"], "src": 1}, got

    # group spans both processes' devices
    assert group.size == jax.device_count()
    print(f"proc {proc_id} OK size={group.size}")
    """
)


def test_two_process_rendezvous_and_broadcast_object(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    coordinator = f"127.0.0.1:{free_port()}"
    outs = spawn_and_collect(
        [[sys.executable, str(script), coordinator, str(i)] for i in range(2)],
        worker_env(), timeout=150,
    )
    for code, out, err in outs:
        assert code == 0, f"worker failed:\n{out}\n{err}"
        assert "OK size=2" in out


DDP_WORKER = textwrap.dedent(
    """
    import sys
    import jax
    jax.config.update("jax_platforms", "cpu")

    coordinator, proc_id = sys.argv[1], int(sys.argv[2])
    import numpy as np
    import optax
    import bagua_tpu
    from bagua_tpu.algorithms import Algorithm
    from bagua_tpu.ddp import DistributedDataParallel
    from bagua_tpu.models.mlp import init_mlp, mse_loss

    group = bagua_tpu.init_process_group(
        coordinator_address=coordinator, num_processes=2, process_id=proc_id
    )
    assert group.size == 8 and group.spans_processes, group
    assert group.inter_size == 2 and group.intra_size == 4, group

    params = init_mlp(jax.random.PRNGKey(0), [12, 16, 4])  # same seed everywhere
    ddp = DistributedDataParallel(
        mse_loss, optax.sgd(0.05),
        Algorithm.init("gradient_allreduce", hierarchical=True),
        process_group=group,
    )
    state = ddp.init(params)

    # each process feeds a DIFFERENT local half of the global batch
    rng = np.random.RandomState(100 + proc_id)
    losses_seen = []
    for step in range(3):
        local = (
            rng.randn(16, 12).astype(np.float32),  # 4 ranks x 4 rows
            rng.randn(16, 4).astype(np.float32),
        )
        state, losses = ddp.train_step(state, ddp.shard_batch(local))
        local_losses = [float(s.data.reshape(-1)[0]) for s in losses.addressable_shards]
        losses_seen.append(local_losses)
    assert all(np.isfinite(l) for ls in losses_seen for l in ls), losses_seen

    # cross-process weight equality: every rank's copy must be identical after
    # hierarchical allreduce -- hash each local shard and allgather the hashes
    from jax.experimental import multihost_utils

    sums = np.array(
        [float(np.asarray(s.data).sum()) for l in jax.tree.leaves(state.params)
         for s in l.addressable_shards],
        dtype=np.float64,
    )
    all_sums = multihost_utils.process_allgather(sums)
    assert all_sums.shape[0] == 2, all_sums.shape
    np.testing.assert_allclose(all_sums[0], all_sums[1], rtol=0, atol=0)
    bagua_tpu.barrier()  # multi-host barrier path (cross-process device sync)
    print(f"proc {proc_id} DDP OK losses={losses_seen[-1]}")
    """
)


def test_two_process_ddp_train_step(tmp_path):
    """Full DDP training across 2 processes x 4 CPU devices: hierarchical
    gradient allreduce rides the inter (cross-process) axis, batches are fed
    per-process via shard_batch, and weights stay bitwise equal across
    processes (the reference bar: 2-node CI training,
    ``benchmark_master.sh:13-21``)."""
    script = tmp_path / "ddp_worker.py"
    script.write_text(DDP_WORKER)
    coordinator = f"127.0.0.1:{free_port()}"
    outs = spawn_and_collect(
        [[sys.executable, str(script), coordinator, str(i)] for i in range(2)],
        worker_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        timeout=240,
    )
    for code, out, err in outs:
        assert code == 0, f"worker failed:\n{out}\n{err}"
        assert "DDP OK" in out


BAGUARUN_WORKER = textwrap.dedent(
    """
    import os
    import jax
    jax.config.update("jax_platforms", "cpu")
    import bagua_tpu
    from bagua_tpu.distributed import init_from_env

    group = init_from_env()
    assert group.size == 2 and jax.process_count() == 2
    got = bagua_tpu.broadcast_object(
        {"from": 0} if jax.process_index() == 0 else None, src=0
    )
    assert got == {"from": 0}
    marker = os.path.join(os.environ["BAGUARUN_WORK"], f"node{os.environ['NODE_RANK']}")
    open(marker, "w").write("ok")
    """
)


def test_baguarun_subprocess_fanout(tmp_path):
    """baguarun analog (reference ``script/baguarun.py:36-113``): fan out one
    ``bagua_tpu.distributed.run`` per host with the right --node_rank.  The
    subprocess launcher simulates two hosts locally; the two single-worker
    gangs rendezvous into one jax.distributed world."""
    script = tmp_path / "worker.py"
    script.write_text(BAGUARUN_WORKER)
    env = worker_env(BAGUARUN_WORK=str(tmp_path))
    r = subprocess.run(
        [
            sys.executable, "-m", "bagua_tpu.distributed.baguarun",
            "--launcher", "subprocess", "--hosts", "hostA hostB",
            "--nproc_per_node", "1", "--master_port", str(free_port()),
            str(script),
        ],
        env=env, capture_output=True, text=True, timeout=180,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert (tmp_path / "node0").exists() and (tmp_path / "node1").exists()


AUTOTUNE_WORKER = textwrap.dedent(
    """
    import os, sys, time
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    import optax
    import bagua_tpu
    from bagua_tpu.algorithms import Algorithm
    from bagua_tpu.ddp import DistributedDataParallel
    from bagua_tpu.service.autotune_session import AutotuneSession
    from bagua_tpu.distributed import init_from_env
    from bagua_tpu.models.mlp import init_mlp, mse_loss
    from bagua_tpu.service.autotune_client import get_hyperparameters_service_client

    group = init_from_env()
    assert group.size == 2, group
    # the client must resolve the service from launcher-exported env
    client = get_hyperparameters_service_client()
    assert client.wait_until_ready(30), "autotune service unreachable via AUTO_TUNE_SERVER_ADDR"

    ddp = DistributedDataParallel(
        mse_loss, optax.sgd(0.05), Algorithm.init("gradient_allreduce"),
        process_group=group, bucket_size_bytes=1 << 10,
    )
    state = ddp.init(init_mlp(jax.random.PRNGKey(0), [16, 64, 64, 4]))
    n0 = ddp.plan.num_buckets
    session = AutotuneSession(ddp, "mh_model", client=client, interval=1)
    rng = np.random.RandomState(int(os.environ["RANK"]))
    changed = False
    for i in range(80):
        local = (rng.randn(8, 16).astype(np.float32), rng.randn(8, 4).astype(np.float32))
        state, _ = ddp.train_step(state, ddp.shard_batch(local))
        session.tick(16)
        if session.completed or ddp.plan.num_buckets != n0:
            changed = True
            break
        time.sleep(0.02)
    assert changed, "autotune never tuned: the per-rank check board never filled"
    marker = os.path.join(os.environ["AT_WORK"], f"tuned_{os.environ['RANK']}")
    open(marker, "w").write(str(ddp.plan.num_buckets))
    """
)


def test_multiprocess_autotune_tunes(tmp_path):
    """Launcher-hosted autotune service + 2 worker processes: the service's
    per-rank check board only fills because each process reports its own
    jax.process_index() (ADVICE fix), the client resolves the service from
    AUTO_TUNE_SERVER_ADDR, and both workers adopt a re-bucketed plan."""
    script = tmp_path / "worker.py"
    script.write_text(AUTOTUNE_WORKER)
    env = worker_env(AT_WORK=str(tmp_path))  # 1 device per process
    r = subprocess.run(
        [
            sys.executable, "-m", "bagua_tpu.distributed.run",
            "--nproc_per_node", "2", "--autotune_level", "1",
            "--autotune_warmup_time_s", "0", "--autotune_sampling_confidence_time_s", "0",
            "--autotune_max_samples", "3",
            "--master_port", str(free_port()), "--bagua_service_port", str(free_port()),
            "--monitor_interval", "0.2", str(script),
        ],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert (tmp_path / "tuned_0").exists() and (tmp_path / "tuned_1").exists()


EAGER_COLLECTIVES_WORKER = textwrap.dedent(
    """
    import sys
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import bagua_tpu
    from bagua_tpu import ReduceOp

    coordinator, proc_id = sys.argv[1], int(sys.argv[2])
    group = bagua_tpu.init_process_group(
        coordinator_address=coordinator, num_processes=2, process_id=proc_id
    )
    assert group.size == 8 and group.spans_processes
    mine = bagua_tpu.local_ranks(group)
    assert len(mine) == 4 and all(r // 4 == proc_id for r in mine), mine

    # rank r sends row r of the global (8, 8) arange matrix
    full = np.arange(64, dtype=np.float32).reshape(8, 8)
    x = full[mine]

    out = bagua_tpu.allreduce(x, op=ReduceOp.SUM)
    assert out.shape == (4, 8), out.shape
    np.testing.assert_allclose(out, np.tile(full.sum(0), (4, 1)))

    out = bagua_tpu.allgather(x)
    np.testing.assert_allclose(out, np.tile(full.reshape(-1), (4, 1)))

    out = bagua_tpu.reducescatter(x, op=ReduceOp.SUM)
    # rank r gets chunk r (rows of length 1) of the summed vector
    expect = np.stack([full.sum(0)[r:r + 1] for r in mine])
    np.testing.assert_allclose(out, expect)

    out = bagua_tpu.broadcast(x, src=3)
    np.testing.assert_allclose(out, np.tile(full[3], (4, 1)))

    out = bagua_tpu.alltoall(x)
    # rank r receives element r of every rank's row
    np.testing.assert_allclose(out, full.T[mine])

    out = bagua_tpu.reduce(x, dst=5, op=ReduceOp.SUM)
    for i, r in enumerate(mine):
        np.testing.assert_allclose(out[i], full.sum(0) if r == 5 else full[r])

    out = bagua_tpu.scatter(x, src=2)
    np.testing.assert_allclose(out, full[2].reshape(8, 1)[mine])

    out = bagua_tpu.gather(x, dst=1)
    for i, r in enumerate(mine):
        np.testing.assert_allclose(
            out[i], full.reshape(-1) if r == 1 else np.zeros(64))

    bagua_tpu.barrier()
    print(f"proc {proc_id} eager collectives OK", flush=True)
    """
)


def test_two_process_eager_collectives(tmp_path):
    """VERDICT r2 #6: the user-facing explicit collective set works across
    processes — each process passes its local-view stack and receives its own
    ranks' results, value-checked against the single-controller semantics."""
    script = tmp_path / "worker.py"
    script.write_text(EAGER_COLLECTIVES_WORKER)
    coordinator = f"127.0.0.1:{free_port()}"
    outs = spawn_and_collect(
        [[sys.executable, str(script), coordinator, str(i)] for i in range(2)],
        worker_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        timeout=240,
    )
    for code, out, err in outs:
        assert code == 0, f"worker failed:\n{out}\n{err}"
        assert "eager collectives OK" in out


def test_communication_primitives_example_two_process(tmp_path):
    """The communication_primitives example (reference 2-node CI smoke) runs
    under a real 2-process launch."""
    import os

    from helpers import REPO_ROOT

    env = worker_env(JAX_PLATFORMS="cpu")  # 1 device per process
    # The example is backend-agnostic (no jax.config override of its own), so
    # the workers are pinned to CPU through JAX_PLATFORMS.
    env["PYTHONPATH"] = REPO_ROOT
    r = subprocess.run(
        [
            sys.executable, "-m", "bagua_tpu.distributed.run",
            "--nproc_per_node", "2", "--master_port", str(free_port()),
            "--monitor_interval", "0.2",
            os.path.join(REPO_ROOT, "examples", "communication_primitives", "main.py"),
        ],
        env=env, capture_output=True, text=True, timeout=240,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"


SUBGROUP_BARRIER_WORKER = textwrap.dedent(
    """
    import sys, time
    import jax
    jax.config.update("jax_platforms", "cpu")
    import bagua_tpu
    from bagua_tpu.communication import new_group

    coordinator, proc_id = sys.argv[1], int(sys.argv[2])
    bagua_tpu.init_process_group(
        coordinator_address=coordinator, num_processes=3, process_id=proc_id
    )
    if proc_id == 2:
        # outside the subgroup: never calls barrier; a process-global sync
        # here would deadlock the others against this sleep
        time.sleep(8)
        print("proc 2 done (never joined the barrier)", flush=True)
        sys.exit(0)
    sub = new_group(ranks=[0, 1])
    assert sub.spans_processes and sub.size == 2
    t0 = time.monotonic()
    bagua_tpu.barrier(comm=sub)
    dt = time.monotonic() - t0
    assert dt < 6.0, f"barrier waited on the out-of-group process ({dt:.1f}s)"
    print(f"proc {proc_id} subgroup barrier OK in {dt:.2f}s", flush=True)
    """
)


def test_subgroup_barrier_excludes_outside_processes(tmp_path):
    """barrier() on a group spanning a strict subset of processes must
    synchronize only that subset — a process-global sync would deadlock
    against the third process, which never calls it."""
    script = tmp_path / "worker.py"
    script.write_text(SUBGROUP_BARRIER_WORKER)
    coordinator = f"127.0.0.1:{free_port()}"
    outs = spawn_and_collect(
        [[sys.executable, str(script), coordinator, str(i)] for i in range(3)],
        worker_env(),
    )
    for code, out, err in outs:
        assert code == 0, f"worker failed:\n{out}\n{err}"
    assert "proc 0 subgroup barrier OK" in outs[0][1]
    assert "proc 1 subgroup barrier OK" in outs[1][1]
