"""A residual of n streams mixed by manifold-constrained hyper-connections
(Xie et al., "mHC", arXiv:2512.24880, on Zhu et al., "Hyper-Connections",
arXiv:2409.19606): around a sublayer F, a position's streams X in R^{n x C}
are read into one input, and F's output is written back into all of them:

    u = vec(X)      m = (u / sqrt(mean(u^2) + eps)) Phi     Phi [nC, n^2 + 2n]
    H_pre  = sigmoid(a_pre m[:n] + b_pre)                       R^n
    H_post = 2 sigmoid(a_post m[n:2n] + b_post)                 R^n
    H_res  = sinkhorn(exp(clip(a_res m[2n:] + b_res)))          R^{n x n}
    y = F(sum_j H_pre[j] X[j])
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

`sinkhorn`: `iters` times, every column over its sum, then every row over
its sum (eps added to each sum), which takes a positive matrix to a doubly
stochastic one: the residual mix neither grows nor shrinks the streams.

Here the streams of one sequence lie streams first, x [n, P, C], so that
every pass is over whole [P, C] slabs, and the coefficients lie positions
last ([n, P], [n, n, P]: the 20 iterations run on 16 rows of P lanes).  All
of it float32, the narrow product at the highest precision (it is 0.01% of
a layer's operations); the mixes are sums of products written out, not
contractions, so no operand is rounded on the way to a matrix unit.
Memory-bound: a sublayer reads the streams for the norm and the product,
for the read and for the write, and writes them once.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def sinkhorn(m, iters: int, eps: float):
    """m [n, n, ...] positive -> doubly stochastic over its first two axes
    (rows, then columns), to what `iters` iterations reach."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)   # columns
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)   # rows
    return m


def res_gap(h_res):
    """The largest |row sum - 1| or |column sum - 1| of any position's
    H_res: how far the iterations stopped from the manifold."""
    rows, cols = jnp.sum(h_res, axis=1), jnp.sum(h_res, axis=0)
    return jnp.maximum(jnp.max(jnp.abs(rows - 1.0)),
                       jnp.max(jnp.abs(cols - 1.0)))


def coefficients(x, p, *, iters: int, eps: float, clamp):
    """x [n, P, C] -> (H_pre [n, P], H_post [n, P], H_res [n, n, P]),
    float32.  `p`: phi [n C, n^2 + 2n] (rows stream by stream), bias
    [n^2 + 2n] and alpha [3], both ordered pre, post, res."""
    n, positions, width = x.shape
    x32 = x.astype(jnp.float32)
    phi = p["phi"].astype(jnp.float32).reshape(n, width, n * n + 2 * n)
    # the norm's factor is a position's scalar: taken after the product
    inv = lax.rsqrt(jnp.mean(jnp.square(x32), axis=(0, 2)) + eps)    # [P]
    m = sum(jnp.dot(x32[j], phi[j], precision=lax.Precision.HIGHEST)
            for j in range(n))
    m = (m * inv[:, None]).T                        # [n^2 + 2n, P]
    bias, alpha = p["bias"].astype(jnp.float32), p["alpha"]
    h_pre = jax.nn.sigmoid(alpha[0] * m[:n] + bias[:n, None])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * m[n:2 * n] + bias[n:2 * n, None])
    with jax.named_scope("mhc_sinkhorn"):
        a = jnp.clip(alpha[2] * m[2 * n:] + bias[2 * n:, None], *clamp)
        h_res = sinkhorn(jnp.exp(a).reshape(n, n, positions), iters, eps)
    return h_pre, h_post, h_res


def read(x, h_pre):
    """The sublayer's input: sum_j H_pre[j] X[j], [P, C] in x's dtype."""
    x32 = x.astype(jnp.float32)
    return sum(h_pre[j][:, None] * x32[j]
               for j in range(x.shape[0])).astype(x.dtype)


def write(x, y, h_post, h_res):
    """X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y, like x."""
    n = x.shape[0]
    x32, y32 = x.astype(jnp.float32), y.astype(jnp.float32)
    return jnp.stack([
        sum(h_res[i, j][:, None] * x32[j] for j in range(n))
        + h_post[i][:, None] * y32 for i in range(n)]).astype(x.dtype)


def connect(sublayer, x, p, *, iters: int, eps: float, clamp):
    """One sublayer under its hyper-connection: x [n, P, C] ->
    (X' like x, what `sublayer` returns beside its output, `res_gap`).
    `sublayer`: [P, C] -> (y [P, C], aux); it norms its own input."""
    with jax.named_scope("mhc_mix"):
        h_pre, h_post, h_res = coefficients(x, p, iters=iters, eps=eps,
                                            clamp=clamp)
        h = read(x, h_pre)
    y, aux = sublayer(h)
    with jax.named_scope("mhc_mix"):
        return write(x, y, h_post, h_res), aux, \
            lax.stop_gradient(res_gap(h_res))
