"""Numerics pin for the Pallas fused BN->ReLU->MaxPool backward.

The kernel is a recorded NEGATIVE perf result (see the module docstring:
0.75x/0.91x vs the XLA composition on v5e) kept as working evidence and
scaffolding; this test keeps it CORRECT so the evidence stays live.  The
CPU CI runs the kernels in Pallas interpret mode — same math, no TPU.

The oracle is plain jax autodiff through the SAME forward math
(``_fwd_impl``'s double-rounded y), which makes the expected equality
exact in f32: routing, gating and reductions all coincide.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental.pallas import tpu as pltpu

from cs744_ddp_tpu.ops import bnpool_pallas as bp


def _ref_chain(x, gamma, beta):
    """Autodiff oracle mirroring _fwd_impl bit for bit."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, (0, 1, 2))
    if x.dtype == jnp.bfloat16:
        var = jnp.maximum(
            jnp.mean(jnp.square(xf), (0, 1, 2)) - jnp.square(mean), 0.0)
    else:
        var = jnp.mean(jnp.square(xf - mean), (0, 1, 2))
    inv = lax.rsqrt(var + bp.BN_EPS)
    xhat = (xf - mean) * inv
    xhat_act = xhat.astype(x.dtype).astype(jnp.float32)
    z = (xhat_act * gamma + beta).astype(x.dtype)
    y = jnp.maximum(z, jnp.zeros((), x.dtype))
    return lax.reduce_window(y, -jnp.inf, lax.max, (1, 2, 2, 1),
                             (1, 2, 2, 1), "VALID")


@pytest.mark.parametrize("shape", [(16, 32, 32, 64), (8, 16, 16, 128),
                                   (4, 8, 8, 64)])
def test_fused_backward_matches_autodiff_f32(shape):
    N, H, W, C = shape
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(k1, shape) * 2 + 0.3
    # Inject exact ties (quantized values) so first-match routing is hit.
    x = jnp.where(jax.random.bernoulli(k4, 0.3, shape),
                  jnp.round(x * 2) / 2, x)
    gamma = jax.random.normal(k2, (C,)) * 0.5 + 1.0
    beta = jax.random.normal(k3, (C,)) * 0.2
    w = jax.random.normal(jax.random.PRNGKey(9), (N, H // 2, W // 2, C))

    def loss_fused(x, g, b):
        p, _, _ = bp.bn_relu_pool(x, g, b)
        return jnp.sum(p * w)

    def loss_ref(x, g, b):
        return jnp.sum(_ref_chain(x, g, b) * w)

    with pltpu.force_tpu_interpret_mode():
        got = jax.grad(loss_fused, argnums=(0, 1, 2))(x, gamma, beta)
    want = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(x, gamma, beta)
    for g, r, name in zip(got, want, ("dx", "dgamma", "dbeta")):
        # f32-reduction-order differences only (chunked-sequential sums
        # in the kernel vs the oracle's pairwise reductions).
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=5e-4, atol=1e-4, err_msg=name)

    # Forward parity is bitwise (same math, same rounding).
    with pltpu.force_tpu_interpret_mode():
        pf, mean_f, var_f = jax.jit(bp.bn_relu_pool)(x, gamma, beta)
    np.testing.assert_array_equal(np.asarray(pf),
                                  np.asarray(jax.jit(_ref_chain)(
                                      x, gamma, beta)))


def test_fused_backward_bf16_routing_flips_are_rare_and_tie_shaped():
    """bf16 dx may differ from the autodiff oracle ONLY at routing flips
    between window elements within a couple of bf16 ulps (excess-
    precision/double-rounding ties — module docstring); the flip fraction
    must stay tiny and every flip site must be a genuine near-tie."""
    shape = (16, 32, 32, 64)
    N, H, W, C = shape
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(1), 4)
    x = (jax.random.normal(k1, shape) * 2 + 0.3)
    x = jnp.where(jax.random.bernoulli(k4, 0.3, shape),
                  jnp.round(x * 2) / 2, x).astype(jnp.bfloat16)
    gamma = jax.random.normal(k2, (C,)) * 0.5 + 1.0
    beta = jax.random.normal(k3, (C,)) * 0.2
    w = jax.random.normal(jax.random.PRNGKey(9), (N, H // 2, W // 2, C))

    def loss_fused(x, g, b):
        p, _, _ = bp.bn_relu_pool(x, g, b)
        return jnp.sum(p.astype(jnp.float32) * w)

    def loss_ref(x, g, b):
        return jnp.sum(_ref_chain(x, g, b).astype(jnp.float32) * w)

    with pltpu.force_tpu_interpret_mode():
        dx = jax.grad(loss_fused)(x, gamma, beta)
    dref = jax.jit(jax.grad(loss_ref))(x, gamma, beta)
    d = np.abs(np.asarray(dx, np.float32) - np.asarray(dref, np.float32))
    flip_sites = np.argwhere(d > 0.05)
    # Tiny fraction of elements...
    assert len(flip_sites) <= 2e-4 * d.size, len(flip_sites)
    # ...and every site sits in a window whose top-2 values are within a
    # couple of bf16 ulps (i.e. it IS a tie flip, not a routing bug).
    xf = np.asarray(x, np.float32)
    mean = xf.mean((0, 1, 2))
    var = np.maximum((xf ** 2).mean((0, 1, 2)) - mean ** 2, 0.0)
    inv = 1.0 / np.sqrt(var + bp.BN_EPS)
    xhat_act = np.asarray(jnp.asarray((xf - mean) * inv
                                      ).astype(jnp.bfloat16), np.float32)
    z = np.asarray(jnp.asarray(xhat_act * np.asarray(gamma)
                               + np.asarray(beta)).astype(jnp.bfloat16),
                   np.float32)
    y = np.maximum(z, 0.0)
    for (n, h, wq, c) in flip_sites[:64]:
        win = y[n, (h // 2) * 2:(h // 2) * 2 + 2,
                (wq // 2) * 2:(wq // 2) * 2 + 2, c].reshape(-1)
        top2 = np.sort(win)[-2:]
        rel = abs(top2[1] - top2[0]) / (abs(top2[1]) + 1e-9)
        assert rel < 2e-2, (tuple(int(v) for v in (n, h, wq, c)),
                            win.tolist())
