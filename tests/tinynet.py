"""Tiny conv net shared by the CPU-mesh tests and the multi-process worker.

conv(3->8) + BN + relu + pool(4x) + fc: exercises every layer kind the real
models use, while keeping CPU compiles fast.  The strategy/step/loop code
under test is identical to what VGG/ResNet run (full models are covered by
tests/test_models.py and the benchmark's cells on the TPU).
"""

import jax
import jax.numpy as jnp

from cs744_ddp_tpu.models import layers
from cs744_ddp_tpu.train.loop import _shard_batches


def run_steps(trainer, n_steps, *, epoch=0, base_key=0):
    """Drive `n_steps` per-step train_step calls with the canonical step-key
    convention (fold the iteration index into the base key; the step folds
    the mesh position itself).  Shared by every cross-path equivalence
    oracle so they all compare the same computation.  Returns the losses."""
    key = jax.random.PRNGKey(base_key)
    losses = []
    for it, (imgs, labs) in enumerate(_shard_batches(
            trainer.train_split, trainer.world, trainer.global_batch, epoch,
            shuffle=True)):
        if it >= n_steps:
            break
        x, y = trainer._put(imgs, labs)
        trainer.state, loss = trainer.train_step(
            trainer.state, jax.random.fold_in(key, it), x, y)
        losses.append(float(loss))  # value fetch = completion fence
    return losses


def tiny_cnn():
    def init_fn(key, dtype=jnp.float32):
        k1, k2 = jax.random.split(key)
        params = {"conv": layers.conv2d_init(k1, 3, 8, 3, dtype)}
        params["bn"], bn_state = layers.batchnorm_init(8, dtype)
        params["fc"] = layers.linear_init(k2, 8 * 8 * 8, 10, dtype)
        return params, {"bn": bn_state}

    def apply_fn(params, state, x, *, train):
        y = layers.conv2d_apply(params["conv"], x)
        y, new_bn = layers.batchnorm_apply(params["bn"], state["bn"], y,
                                           train=train)
        y = layers.relu(y)
        y = layers.maxpool2x2(layers.maxpool2x2(y))  # 32 -> 8
        y = y.reshape(y.shape[0], -1)
        return layers.linear_apply(params["fc"], y), {"bn": new_bn}

    return init_fn, apply_fn


def tiny_cnn_nobn():
    """BN-free variant: with no batch statistics, a 1-device run and an
    N-device data-parallel run on the same global batch are mathematically
    identical — the tight cross-world averaging oracle."""

    def init_fn(key, dtype=jnp.float32):
        k1, k2 = jax.random.split(key)
        params = {"conv": layers.conv2d_init(k1, 3, 8, 3, dtype),
                  "fc": layers.linear_init(k2, 8 * 8 * 8, 10, dtype)}
        return params, {}

    def apply_fn(params, state, x, *, train):
        del train
        y = layers.conv2d_apply(params["conv"], x)
        y = layers.relu(y)
        y = layers.maxpool2x2(layers.maxpool2x2(y))  # 32 -> 8
        y = y.reshape(y.shape[0], -1)
        return layers.linear_apply(params["fc"], y), state

    return init_fn, apply_fn
