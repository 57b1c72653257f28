#!/usr/bin/env python3
"""Elastic training example (analog of the reference's
``examples/elastic_training/main.py``): checkpoint every epoch, resume from
the latest checkpoint on (re)start.  Run under the elastic launcher:

    python -m bagua_tpu.distributed.run --nproc_per_node 1 --max_restarts 3 \
        examples/elastic_training/main.py --ckpt-dir /tmp/elastic_ckpt
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax

import bagua_tpu
from bagua_tpu.algorithms import Algorithm
from bagua_tpu.models.mlp import init_mlp, mse_loss
from bagua_tpu.trainer import Trainer


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--ckpt-dir", default="/tmp/bagua_tpu_elastic")
    p.add_argument("--steps", type=int, default=200)
    args = p.parse_args()

    from bagua_tpu.distributed import init_from_env

    init_from_env()  # launcher-exported env (multi-host ready); local fallback
    with Trainer(
        mse_loss,
        optax.adam(1e-3),
        Algorithm.init("gradient_allreduce"),
        ckpt_dir=args.ckpt_dir,
        ckpt_interval=50,
        watchdog_timeout_s=120.0,
    ) as trainer:
        params = init_mlp(jax.random.PRNGKey(0), [32, 64, 8])
        state = trainer.init_state(params)
        start = int(state.step[0])
        n = bagua_tpu.get_default_group().size
        print(f"starting at step {start} on {n} {jax.devices()[0].platform} device(s)")

        rng = np.random.RandomState(0)

        def batches():
            for _ in range(args.steps - start):
                yield (
                    jnp.asarray(rng.randn(16 * n, 32), jnp.float32),
                    jnp.asarray(rng.randn(16 * n, 8), jnp.float32),
                )

        state = trainer.fit(state, batches(), log_every=50)
        print(f"done at step {int(state.step[0])}")


if __name__ == "__main__":
    main()
