"""The hybrid decoder's linear-mixer front (ops/gdn.py `front`): the causal
convolution, `silu`, the split into q, k, v and the l2 norms of the key
heads, as the two Pallas kernels `gdn_front_fwd` / `gdn_front_bwd` in
Pallas' interpreter against the jax.numpy form the CPU runs, and the rule
that chooses between them.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from cs744_ddp_tpu.models import qwen3next                 # noqa: E402
from cs744_ddp_tpu.ops import gdn                           # noqa: E402


def close(a, b, tol=2e-6):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= tol * max(1.0, np.max(np.abs(b)))


def projection(length, key_width, value_width, seed=0, taps=4):
    """What the mixer's projection hands the front, [P, 2 kd + 2 vd] (q, k,
    v, then z), and the convolution's weight [taps, 2 kd + vd]."""
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (length, 2 * key_width + 2 * value_width))
    w = 0.5 * jax.random.normal(kw, (taps, 2 * key_width + value_width))
    return x, w


def forms(key_width, dk, tile, monkeypatch):
    monkeypatch.setattr(gdn, "FRONT_TILE", tile)
    kernel = lambda x, w: gdn.front(x, w, key_width, dk, kernels=True,
                                    interpret=True)
    plain = lambda x, w: gdn.front(x, w, key_width, dk)
    return kernel, plain


def pallas_calls(f, *x):
    return str(jax.make_jaxpr(f)(*x)).count("pallas_call")


# (tile, length, key heads x dk, value width): four tiles of 16; five of 8
# (one strip a tile, so every strip's halo is the tile before's); one whole
# tile; keys of 256 beside values of 384
FRONT_CASES = [(16, 64, (2, 128), 256), (8, 40, (1, 128), 128),
               (32, 32, (2, 128), 256), (16, 48, (1, 256), 384)]


@pytest.mark.parametrize("tile,length,keys,value_width", FRONT_CASES,
                         ids=lambda c: str(c))
def test_front_kernels_are_the_jax_numpy_front(tile, length, keys,
                                               value_width, monkeypatch):
    """q, k, v and z within a few float32 ulps of the jax.numpy form, and
    the gradients w.r.t. the projection (z's channels too) and the
    convolution's weight, through a cotangent on every output."""
    heads, dk = keys
    kd = heads * dk
    kernel, plain = forms(kd, dk, tile, monkeypatch)
    x, w = projection(length, kd, value_width)
    got, want = kernel(x, w), plain(x, w)
    assert [a.shape for a in got] == [(length, kd), (length, kd),
                                      (length, value_width),
                                      (length, value_width)]
    for a, b in zip(got, want):
        assert a.dtype == jnp.float32 and close(a, b)
    assert pallas_calls(kernel, x, w) == 1
    cots = [jax.random.normal(jax.random.PRNGKey(7 + i), a.shape)
            for i, a in enumerate(want)]
    loss = lambda f: lambda x, w: sum(
        jnp.sum(jnp.sin(o) * c) for o, c in zip(f(x, w), cots))
    grad = lambda f: jax.grad(loss(f), argnums=(0, 1))
    assert pallas_calls(grad(kernel), x, w) == 2
    for a, b in zip(grad(kernel)(x, w), grad(plain)(x, w)):
        assert a.shape == b.shape and close(a, b, 1e-5)


def test_front_position_zero_sees_zeros_to_its_left(monkeypatch):
    """The first position's taps reach left of the sequence, where the
    convolution reads zeros: q[0] = l2(silu(w[3] x[0])) dk^-1/2, and no
    later position moves it."""
    kernel, _ = forms(128, 128, 8, monkeypatch)
    x, w = projection(24, 128, 128)
    q = kernel(x, w)[0]
    u = jax.nn.silu(w[3, :128] * x[0, :128])
    assert close(q[0], u / jnp.sqrt(jnp.sum(u * u) + 1e-6) / np.sqrt(128))
    moved = kernel(x.at[1:].add(1.0), w)[0]
    assert np.array_equal(np.asarray(moved[0]), np.asarray(q[0]))
    assert not np.array_equal(np.asarray(moved[1]), np.asarray(q[1]))


@pytest.mark.parametrize("why,length,dk,value_width,dtype,kernels", [
    ("length not whole tiles", 72, 128, 128, jnp.float32, True),
    ("keys of 8", 64, 8, 128, jnp.float32, True),
    ("values not whole lanes", 64, 128, 96, jnp.float32, True),
    ("a bfloat16 projection", 64, 128, 128, jnp.bfloat16, True),
    ("off the TPU", 64, 128, 128, jnp.float32, False)],
    ids=lambda c: c if isinstance(c, str) else "")
def test_front_falls_back_to_the_jax_numpy_form(why, length, dk, value_width,
                                                dtype, kernels, monkeypatch):
    """Anything `front_plan` does not take runs the jax.numpy form with no
    `pallas_call`, forward or backward, and gives its numbers."""
    monkeypatch.setattr(gdn, "FRONT_TILE", 16)
    x, w = projection(length, dk, value_width)
    x = x.astype(dtype)
    front = lambda x, w: gdn.front(x, w, dk, dk, kernels=kernels,
                                   interpret=True)
    assert gdn.front_plan(length, dk, value_width, dtype, kernels) == 0
    grad = jax.grad(lambda x, w: jnp.sum(front(x, w)[0]), argnums=(0, 1))
    assert pallas_calls(front, x, w) == pallas_calls(grad, x, w) == 0
    for a, b in zip(front(x, w), gdn.front(x, w, dk, dk)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("shape,kernels,want", [
    (qwen3next.TINY._replace(lin_key_dim=128, lin_value_dim=128,
                             seq_len=256), True, 1),
    (qwen3next.TINY._replace(lin_key_dim=128, lin_value_dim=128,
                             seq_len=200), True, 0),
    (qwen3next.TINY._replace(lin_key_dim=128, lin_value_dim=128,
                             seq_len=256), False, 0),
    (qwen3next.TINY, True, 0)], ids=["wide", "ragged", "off-tpu", "tiny"])
def test_the_front_gauge_says_which_form_runs(shape, kernels, want):
    """`gdn_front_kernel` is 1 where `front_plan` takes the model's shape
    for a float32 projection, else 0; once, beside `gdn_kernel`."""
    _, apply_fn = qwen3next.make(shape, kernels=kernels)
    gauges = [(name, value) for name, value, _ in apply_fn.objective.gauges]
    assert gauges.count(("gdn_front_kernel", want)) == 1
    assert [name for name, _ in gauges].index("gdn_front_kernel") == \
        [name for name, _ in gauges].index("gdn_kernel") + 3
    assert want == int(gdn.front_plan(
        shape.seq_len, shape.lin_key_dim,
        shape.lin_value_heads * shape.lin_value_dim, jnp.float32, kernels) > 0)
