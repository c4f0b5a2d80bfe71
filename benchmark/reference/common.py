"""The plain reference's shared parts: layers, loss, input transform,
sampler, SGD and the loop that follows the first steps of training.

Straightforward jax.numpy in float32 at the backend's default matmul
precision (what the configurations state), no scan, no shard_map, no
donation, no custom gradient: autodiff differentiates the textbook forward
pass.  It imports nothing of the program and takes nothing the program
made: weights come from the seed by the configuration's own init, the batch
composition from its own sampler, the augmentation draws from its own use
of the step key.  A data-parallel cell is followed shard by shard on one
device: each shard's rows, its own BatchNorm statistics and augmentation
stream, then the mean of the shards' gradients.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

MEAN = np.array([125.3, 123.0, 113.9], np.float32) / 255.0
STD = np.array([63.0, 62.1, 66.7], np.float32) / 255.0
BN_MOMENTUM = 0.1
BN_EPS = 1e-5


# -- parameters (PyTorch defaults: U(+-1/sqrt(fan_in)) for weight and bias) --

def _uniform(key, shape, bound):
    return jax.random.uniform(key, shape, jnp.float32, -bound, bound)


def conv_init(key, cin, cout, k, bias=True):
    wkey, bkey = jax.random.split(key)
    bound = 1.0 / math.sqrt(cin * k * k)
    p = {"w": _uniform(wkey, (k, k, cin, cout), bound)}
    if bias:
        p["b"] = _uniform(bkey, (cout,), bound)
    return p


def linear_init(key, fin, fout):
    wkey, bkey = jax.random.split(key)
    bound = 1.0 / math.sqrt(fin)
    return {"w": _uniform(wkey, (fin, fout), bound),
            "b": _uniform(bkey, (fout,), bound)}


def bn_init(c):
    return ({"gamma": jnp.ones((c,), jnp.float32),
             "beta": jnp.zeros((c,), jnp.float32)},
            {"mean": jnp.zeros((c,), jnp.float32),
             "var": jnp.ones((c,), jnp.float32)})


# -- layers -----------------------------------------------------------------

def conv(p, x, stride=1, padding=1):
    y = lax.conv_general_dilated(
        x, p["w"], (stride, stride), [(padding, padding)] * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"] if "b" in p else y


def batchnorm(p, s, x, train):
    if not train:
        y = (x - s["mean"]) * lax.rsqrt(s["var"] + BN_EPS)
        return y * p["gamma"] + p["beta"], s
    n = x.shape[0] * x.shape[1] * x.shape[2]
    mean = jnp.mean(x, (0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), (0, 1, 2))      # biased
    y = (x - mean) * lax.rsqrt(var + BN_EPS) * p["gamma"] + p["beta"]
    # running statistics are statistics, not part of the graph
    mean, var = lax.stop_gradient(mean), lax.stop_gradient(var)
    unbiased = var * (n / max(n - 1, 1))
    return y, {"mean": (1 - BN_MOMENTUM) * s["mean"] + BN_MOMENTUM * mean,
               "var": (1 - BN_MOMENTUM) * s["var"] + BN_MOMENTUM * unbiased}


def maxpool2x2(x):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1),
                             (1, 2, 2, 1), "VALID")


def relu(x):
    return jnp.maximum(x, 0)


def linear(p, x):
    return x @ p["w"] + p["b"]


def cross_entropy_sum(logits, labels):
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(logz - picked)


# -- input path -------------------------------------------------------------

def normalize(images_u8):
    return (images_u8.astype(jnp.float32) / 255.0 - MEAN) / STD


def augment(key, images_u8):
    """RandomCrop(32, padding=4) + RandomHorizontalFlip + Normalize, one
    offset pair and one coin per image from the step's key."""
    n = images_u8.shape[0]
    kc, kf = jax.random.split(key)
    offs = jax.random.randint(kc, (n, 2), 0, 9, dtype=jnp.int32)
    flips = jax.random.bernoulli(kf, 0.5, (n,))
    padded = jnp.pad(images_u8, ((0, 0), (4, 4), (4, 4), (0, 0)))
    i32 = jnp.arange(32, dtype=jnp.int32)
    rows = offs[:, 0, None] + i32                               # [n,32]
    cols = offs[:, 1, None] + jnp.where(flips[:, None], 31 - i32, i32)
    img = jnp.arange(n)[:, None, None]
    return normalize(padded[img, rows[:, :, None], cols[:, None, :]])


def sampler_rows(n, world, seed):
    """[world, n/world] row indices: torch's DistributedSampler with a
    seed-fixed shuffle that is never reshuffled (the reference never calls
    set_epoch): the permutation wrap-padded to a multiple of `world`, rank r
    taking every world-th row from r."""
    perm = np.random.default_rng(seed).permutation(n)
    total = -(-n // world) * world
    if total > n:
        perm = np.concatenate([perm] * (-(-total // n)))[:total]
    return np.stack([perm[r::world] for r in range(world)])


# -- following the first steps ----------------------------------------------

def tree_norms(tree):
    """{path: l2 norm} over the leaves, as Python floats."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(k): float(jnp.sqrt(jnp.sum(jnp.square(
        jnp.asarray(v, jnp.float32))))) for k, v in flat}


def follow(init_fn, apply_fn, config, *, seed, world, per_chip_batch, train, test,
           steps=3, drop_half=False, skip_sync=False, freeze=False):
    """Train `steps` steps from the seed and evaluate; return every number
    the comparison reads.

    `init_fn(key)` and `apply_fn(params, state, x, train)` are what the
    configuration's reference module's `make(config)` returns.  `train`/`test` are (images uint8,
    labels int32) host arrays from the benchmark's generator.

    The last three arguments plant faults for the tests and the chip study,
    never for a benchmark run: `drop_half` trains on the first half of
    every shard's rows (the mean taken over the rest); `skip_sync` leaves
    the exchange out (every step takes shard 0's gradient alone); `freeze`
    is a step that returns its state unchanged (parameters, velocity and
    running statistics stay as the seed made them).
    """
    opt = config["optimizer"]
    lr, mu, wd = config["lr"], opt["momentum"], opt["weight_decay"]
    params, bn = init_fn(jax.random.PRNGKey(seed))
    vel = jax.tree.map(jnp.zeros_like, params)
    p0, bn0 = params, bn
    rows = sampler_rows(len(train[1]), world, seed)
    key_epoch = jax.random.fold_in(jax.random.PRNGKey(seed), 0)

    @jax.jit
    def shard_grad(params, bn, key, images, labels):
        def loss_fn(p):
            logits, new_bn = apply_fn(p, bn, augment(key, images), True)
            return cross_entropy_sum(logits, labels) / labels.shape[0], new_bn
        (loss, new_bn), g = jax.value_and_grad(loss_fn, has_aux=True)(params)
        return loss, new_bn, g

    @jax.jit
    def sgd(params, vel, g):
        d = jax.tree.map(lambda p, gg: gg + wd * p, params, g)
        vel = jax.tree.map(lambda v, dd: mu * v + dd, vel, d)
        return jax.tree.map(lambda p, v: p - lr * v, params, vel), vel

    out = {"loss": [], "grad_norms": [], "grad_sqnorm": []}
    for k in range(steps):
        key_step = jax.random.fold_in(key_epoch, k)
        parts = []
        for r in range(world):
            idx = rows[r, k * per_chip_batch:(k + 1) * per_chip_batch]
            if drop_half:
                idx = idx[:len(idx) // 2]
            parts.append(shard_grad(
                params, bn, jax.random.fold_in(key_step, r),
                jnp.asarray(train[0][idx]), jnp.asarray(train[1][idx])))
            if skip_sync:
                break
        mean = lambda *xs: sum(xs) / len(xs)
        loss = mean(*[p[0] for p in parts])
        bn = jax.tree.map(mean, *[p[1] for p in parts])
        g = jax.tree.map(mean, *[p[2] for p in parts])
        out["loss"].append(float(loss))
        out["grad_norms"].append(tree_norms(g))
        if freeze:
            bn = bn0
        else:
            params, vel = sgd(params, vel, g)
        if k == 0:
            out["momentum1_norms"] = tree_norms(vel)
    out["dparam_norms"] = tree_norms(
        jax.tree.map(lambda a, b: a - b, params, p0))
    out["bn_norms"] = tree_norms(bn)

    @jax.jit
    def eval_block(params, bn, images, labels):
        logits, _ = apply_fn(params, bn, normalize(images), False)
        return (cross_entropy_sum(logits, labels),
                jnp.sum(jnp.argmax(logits, -1) == labels))

    n = len(test[1])
    block = 2000 if n % 2000 == 0 else n
    loss_sum, correct = 0.0, 0
    for lo in range(0, n, block):
        l, c = eval_block(params, bn, jnp.asarray(test[0][lo:lo + block]),
                          jnp.asarray(test[1][lo:lo + block]))
        loss_sum += float(l)
        correct += int(c)
    out["eval_loss"] = loss_sum / n
    out["eval_correct"] = correct
    out["eval_n"] = n
    return out
