"""The latent-attention decoder with a hyper-connected residual
(models/xing4.py, ops/mla.py, ops/hyper.py, ops/moe.py `route_sigmoid` and
`shared_expert_ungated`, ops/attention.py `causal_attention` at a key size
beside another value size) at a tiny size on the CPU: hidden 64, 4 streams,
3 layers (dense, expert, expert), 4 heads of 16 + 8 (rotary) beside values
of 16, latent ranks 24 / 16, 8 sigmoid-routed experts of 32 of which 2 are
held, top-2, a shared expert of 32, L = 32, vocabulary 64.  The program
against the benchmark's plain reference
(benchmark/reference/latent_hc_causal.py, which imports nothing of the
program) on seeded weights.
"""

import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import latent_hc_causal as ref   # noqa: E402
from cs744_ddp_tpu import cli, models                      # noqa: E402
from cs744_ddp_tpu.models import xing4                     # noqa: E402
from cs744_ddp_tpu.models.sdar import rmsnorm              # noqa: E402
from cs744_ddp_tpu.obs import Telemetry                    # noqa: E402
from cs744_ddp_tpu.ops import attention, hyper, mla, moe, sgd  # noqa: E402
from cs744_ddp_tpu.train.loop import Trainer               # noqa: E402

CONFIG = json.load(open(os.path.join(
    ROOT, "benchmark", "tests", "tiny-xing4-f32.json")))
REAL = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "xing4.0-29b-a4b-ep8-f32.json")))
TINY = xing4.TINY


def close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= tol * max(1.0, np.max(np.abs(b)))


# -- YaRN and the softmax scale -----------------------------------------------

def test_yarn_frequencies_are_the_formula_by_hand():
    """The 32 frequencies of the published rotary part (d = 64, base
    10,000, factor 64 over 4096): dim(r) = d ln(4096 / (2 pi r)) / (2 ln
    base); low = floor(dim(32)) = 10, high = ceil(dim(1)) = 23; the fast
    pairs keep base^(-2i/d), the slow ones are that over 64, a ramp
    between."""
    got = mla.yarn_inv_freq(64, 1e4, 64.0, 4096, 32.0, 1.0)
    dim = lambda r: 64 * math.log(4096 / (2 * math.pi * r)) \
        / (2 * math.log(1e4))
    low, high = math.floor(dim(32)), math.ceil(dim(1))
    assert (low, high) == (10, 23) and got.shape == (32,)
    for i in range(32):
        f = 1e4 ** (-2 * i / 64)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        assert got[i] == pytest.approx(f / 64 * ramp + f * (1 - ramp),
                                       rel=1e-6)
    assert got[10] == pytest.approx(1e4 ** (-20 / 64), rel=1e-6)
    assert got[23] == pytest.approx(1e4 ** (-46 / 64) / 64, rel=1e-6)
    # the reference writes the same table out on its own
    z = ref.sizes(REAL)
    assert np.allclose(ref.yarn_frequencies(64, z), got, rtol=1e-6)


def test_softmax_scale_carries_yarns_mscale_squared():
    assert mla.yarn_mscale(64.0, 1.0) == pytest.approx(1.41589, abs=1e-5)
    assert mla.softmax_scale(192, 64.0, 1.0) == pytest.approx(0.144680,
                                                              abs=1e-6)
    assert mla.softmax_scale(192, 1.0, 1.0) == pytest.approx(192 ** -0.5)


# -- latent attention ---------------------------------------------------------

def mla_params(key, c=64, heads=4, nope=16, rope=8, v=16, rq=24, rkv=16):
    shapes = dict(w_qa=(c, rq), w_qb=(rq, heads * (nope + rope)),
                  w_kva=(c, rkv + rope), w_kvb=(rkv, heads * (nope + v)),
                  w_o=(heads * v, c))
    p = {name: 0.2 * jax.random.normal(jax.random.fold_in(key, i), shp)
         for i, (name, shp) in enumerate(shapes.items())}
    p["q_norm"] = 1.0 + 0.1 * jax.random.normal(jax.random.fold_in(key, 8),
                                                (rq,))
    p["kv_norm"] = 1.0 + 0.1 * jax.random.normal(jax.random.fold_in(key, 9),
                                                 (rkv,))
    return p


def mla_by_head(h, p, inv_freq, scale, heads=4, nope=16, rope=8, v=16,
                rkv=16):
    """A head at a time, a query row at a time: the rotary part as complex
    numbers on the pairs (i, i + rope / 2), ONE rotary key a position."""
    h, p = np.asarray(h, np.float64), jax.tree.map(
        lambda a: np.asarray(a, np.float64), p)
    n = lambda x, g: g * x / np.sqrt(np.mean(x * x, -1, keepdims=True) + 1e-6)
    P = h.shape[0]
    q = (n(h @ p["w_qa"], p["q_norm"]) @ p["w_qb"]).reshape(P, heads, -1)
    kva = h @ p["w_kva"]
    kv = (n(kva[:, :rkv], p["kv_norm"]) @ p["w_kvb"]).reshape(P, heads, -1)

    def turn(x):                    # [P, rope]
        half = rope // 2
        z = (x[:, :half] + 1j * x[:, half:]) * np.exp(
            1j * np.arange(P)[:, None] * np.asarray(inv_freq, np.float64))
        return np.concatenate([z.real, z.imag], -1)
    k_pe = turn(kva[:, rkv:])
    out = np.zeros((P, heads, v))
    for a in range(heads):
        qa = np.concatenate([q[:, a, :nope], turn(q[:, a, nope:])], -1)
        ka = np.concatenate([kv[:, a, :nope], k_pe], -1)
        for t in range(P):
            s = (ka[:t + 1] @ qa[t]) * scale
            w = np.exp(s - s.max())
            out[t, a] = (w / w.sum()) @ kv[:t + 1, a, nope:]
    return out.reshape(P, heads * v) @ p["w_o"]


def test_latent_attention_is_the_head_by_head_loop():
    key = jax.random.PRNGKey(0)
    p = mla_params(key)
    h = jax.random.normal(jax.random.PRNGKey(1), (24, 64))
    inv_freq = mla.yarn_inv_freq(8, 1e4, 4.0, 16, 32.0, 1.0)
    scale = mla.softmax_scale(24, 4.0, 1.0)
    got = mla.latent_attention(
        h, p, heads=4, nope=16, rope=8, v_dim=16, kv_rank=16,
        norm=lambda x, g: rmsnorm(x, g, 1e-6), positions=jnp.arange(24),
        inv_freq=inv_freq, scale=scale, kernels=False)
    assert got.shape == (24, 64)
    assert close(got, mla_by_head(h, p, inv_freq, scale), 1e-5)
    # causal: a later position's input does not move an earlier output
    moved = mla.latent_attention(
        h.at[10].add(1.0), p, heads=4, nope=16, rope=8, v_dim=16, kv_rank=16,
        norm=lambda x, g: rmsnorm(x, g, 1e-6), positions=jnp.arange(24),
        inv_freq=inv_freq, scale=scale, kernels=False) - got
    assert not np.any(np.asarray(moved)[:10]) and np.any(np.asarray(moved)[10])


def dense_causal(q, k, v):
    n = q.shape[2]
    seen = np.arange(n)[:, None] >= np.arange(n)[None, :]
    s = jnp.where(seen, jnp.einsum("shqd,shkd->shqk", q, k), -jnp.inf)
    return jnp.einsum("shqk,shkd->shqd", jax.nn.softmax(s, -1), v)


def qkv(n, dk, dv, heads=2):
    mk = lambda i, d: jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(1), i), (1, heads, n, d))
    return mk(0, dk) * dk ** -0.5, mk(1, dk), mk(2, dv)


@pytest.mark.parametrize("tile", [8, 32])
def test_blocked_causal_attention_takes_a_value_size_of_its_own(tile):
    """Keys of 24 beside values of 16, one query head a key/value head."""
    q, k, v = qkv(32, 24, 16)
    f = lambda *a: attention.causal_attention(*a, kernels=False, tile=tile)
    assert f(q, k, v).shape == (1, 2, 32, 16)
    assert close(f(q, k, v), dense_causal(q, k, v))
    grad = lambda fn: jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                               argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grad(f), grad(dense_causal)):
        assert close(a, b, 1e-4)


def test_pallas_causal_kernels_at_keys_of_192_and_values_of_128_interpreted():
    """The splash kernels under the library's causal mask at the published
    sizes (keys 192, values 128, a key/value head a query head), tiles of
    128, in Pallas' interpreter: forward and the gradients; bfloat16
    operands, so to 2^-6."""
    q, k, v = qkv(256, 192, 128)
    f = lambda *a: attention.causal_attention(*a, kernels=True,
                                              interpret=True, tile=128)
    assert f(q, k, v).shape == (1, 2, 256, 128)
    assert close(f(q, k, v), dense_causal(q, k, v), 2.0 ** -6)
    grad = lambda fn: jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                               argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grad(f), grad(dense_causal)):
        assert close(a, b, 2.0 ** -5)


# -- hyper-connections --------------------------------------------------------

def hc_params(key, n=4, c=6, std=0.5):
    return {"phi": std * jax.random.normal(key, (n * c, n * n + 2 * n)),
            "alpha": jnp.asarray([0.7, 0.5, 0.9]),
            "bias": xing4.hc_bias(n)
            + 0.3 * jax.random.normal(jax.random.fold_in(key, 1),
                                      (n * n + 2 * n,))}


def connect_by_loop(x, p, f, iters=20, eps=1e-6):
    """A position at a time, the 4 x 4 matrix as numpy, Sinkhorn a plain
    loop.  x [n, P, C] -> X' [n, P, C]."""
    x = np.asarray(x, np.float64)
    phi, alpha, bias = (np.asarray(p[k], np.float64)
                        for k in ("phi", "alpha", "bias"))
    n, P, c = x.shape
    sig = lambda a: 1 / (1 + np.exp(-a))
    reads = np.zeros((P, c))
    coeffs = []
    for t in range(P):
        u = x[:, t, :].reshape(-1)
        m = (u / np.sqrt(np.mean(u * u) + eps)) @ phi
        pre = sig(alpha[0] * m[:n] + bias[:n])
        post = 2 * sig(alpha[1] * m[n:2 * n] + bias[n:2 * n])
        M = np.exp(np.clip(alpha[2] * m[2 * n:] + bias[2 * n:], -30, 30)
                   ).reshape(n, n)
        for _ in range(iters):
            M = M / (M.sum(0, keepdims=True) + eps)
            M = M / (M.sum(1, keepdims=True) + eps)
        reads[t] = pre @ x[:, t, :]
        coeffs.append((post, M))
    y = np.asarray(f(reads), np.float64)
    out = np.zeros_like(x)
    for t, (post, M) in enumerate(coeffs):
        out[:, t, :] = M @ x[:, t, :] + post[:, None] * y[t][None, :]
    return out


def test_hyper_connection_is_the_position_by_position_loop():
    key = jax.random.PRNGKey(3)
    p = hc_params(key)
    x = jax.random.normal(jax.random.PRNGKey(4), (4, 10, 6))
    w = jax.random.normal(jax.random.PRNGKey(5), (6, 6))
    f = lambda h: jnp.tanh(h @ w)
    got, aux, gap = hyper.connect(lambda h: (f(h), "aux"), x, p, iters=20,
                                  eps=1e-6, clamp=(-30.0, 30.0))
    assert aux == "aux" and got.shape == x.shape
    assert close(got, connect_by_loop(x, p, f), 1e-5)
    # scales of 0.5-0.9 on a wide Phi: matrices far from the starting one,
    # which 20 iterations bring near the manifold, not onto it
    _, _, h_res = hyper.coefficients(x, p, iters=20, eps=1e-6,
                                     clamp=(-30.0, 30.0))
    assert float(gap) == pytest.approx(float(hyper.res_gap(h_res)))
    assert 0 < float(gap) < 0.1


def test_h_res_is_doubly_stochastic_after_20_iterations_and_not_after_one():
    """At the scales the model starts with (alpha 0.01 on the starting
    biases)."""
    p = dict(hc_params(jax.random.PRNGKey(6), c=8), bias=xing4.hc_bias(4),
             alpha=jnp.full((3,), 0.01))
    x = jax.random.normal(jax.random.PRNGKey(7), (4, 50, 8))
    kw = dict(eps=1e-6, clamp=(-30.0, 30.0))
    _, _, h_res = hyper.coefficients(x, p, iters=20, **kw)
    assert h_res.shape == (4, 4, 50) and np.all(np.asarray(h_res) > 0)
    assert np.allclose(np.sum(h_res, 0), 1.0, atol=1e-5)
    assert np.allclose(np.sum(h_res, 1), 1.0, atol=1e-5)
    assert float(hyper.res_gap(h_res)) < 1e-5
    _, _, once = hyper.coefficients(x, p, iters=1, **kw)
    assert float(hyper.res_gap(once)) > 0.05
    # the starting biases: the limit the docstring states, one iteration
    # far from it
    start = jnp.exp(xing4.hc_bias(4)[8:].reshape(4, 4, 1))
    limit = np.asarray(hyper.sinkhorn(start, 20, 1e-6))[..., 0]
    assert np.allclose(np.diag(limit), 0.711, atol=2e-3)
    assert np.allclose(limit[0, 1:], 0.096, atol=2e-3)
    assert float(hyper.res_gap(hyper.sinkhorn(start, 1, 1e-6))) > 0.4
    # the clamp bounds what is exponentiated: no inf from a huge bias
    big = dict(p, bias=p["bias"].at[8:].set(1e4))
    _, _, h = hyper.coefficients(x, big, iters=20, **kw)
    assert np.all(np.isfinite(np.asarray(h)))


# The kernels' cases: channels in whole lanes and two tiles of positions; Phi
# narrower by the width's root, so that m is as wide as at 6 channels.
KERNEL_CASE = dict(positions=2 * hyper.KERNEL_TILE, width=128, std=0.1)
HC = dict(iters=20, eps=1e-6, clamp=(-30.0, 30.0))
FORMS = pytest.mark.parametrize(
    "kernels,case", [(False, dict(positions=3, width=5, std=0.5)),
                     (True, KERNEL_CASE)], ids=["jax.numpy", "kernels"])


def interpreted(sublayer, x, p, kernels=True, **kw):
    """`connect` with the kernels in Pallas' interpreter."""
    return hyper.connect(sublayer, x, p, kernels=kernels, interpret=True,
                         **dict(HC, **kw))


def kernel_case(seed, positions=KERNEL_CASE["positions"],
                width=KERNEL_CASE["width"]):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(k[0], (4, positions, width)),
            hc_params(k[1], c=width, std=KERNEL_CASE["std"]),
            jax.random.normal(k[2], (width, width)) * width ** -0.5)


@FORMS
def test_hyper_connections_gradient_matches_finite_differences(kernels, case):
    """d loss / d (x, phi, alpha, bias) through the norm, the two sigmoids,
    the 20 iterations and the three mixes, against central differences of
    the position-by-position loop in float64: autodiff of the `jax.numpy`
    form, and the kernels' own backward pair."""
    c = case["width"]
    p = hc_params(jax.random.PRNGKey(8), c=c, std=case["std"])
    x = jax.random.normal(jax.random.PRNGKey(9), (4, case["positions"], c))
    w = jax.random.normal(jax.random.PRNGKey(10), (c, c)) * (5 / c) ** 0.5
    w64 = np.asarray(w, np.float64)

    def loss(x, p):
        out, _, _ = interpreted(lambda h: (jnp.tanh(h @ w), ()), x, p,
                                kernels=kernels)
        return jnp.sum(jnp.sin(out))

    def loop_loss(x, p):
        return float(np.sum(np.sin(connect_by_loop(
            x, p, lambda h: np.tanh(np.asarray(h, np.float64) @ w64)))))
    assert bool(hyper.plan(x.shape, x.dtype, kernels)) == kernels
    gx, gp = jax.grad(loss, argnums=(0, 1))(x, p)
    rng = np.random.default_rng(0)
    as64 = lambda a: np.asarray(a, np.float64)

    def probe(value, grad, put):
        for _ in range(6):
            idx = tuple(rng.integers(0, s) for s in value.shape)
            d = np.zeros(value.shape)
            d[idx] = 1e-6
            fd = (put(as64(value) + d) - put(as64(value) - d)) / 2e-6
            assert abs(fd - float(grad[idx])) <= 2e-4 * max(
                1.0, abs(fd)), (idx, fd, float(grad[idx]))
    probe(x, gx, lambda v: loop_loss(v, p))
    for name in ("phi", "alpha", "bias"):
        probe(p[name], gp[name],
              lambda v, name=name: loop_loss(x, dict(p, **{name: v})))


def test_the_kernels_are_the_jax_numpy_connection():
    """X', the sublayer's input h and `res_gap`: the four Pallas kernels
    in the interpreter, two tiles of positions, against the `jax.numpy`
    form."""
    x, p, w = kernel_case(11)
    f = lambda h: (jnp.tanh(h @ w), h)
    assert hyper.plan(x.shape, x.dtype, True) == hyper.KERNEL_TILE
    want, h_want, gap_want = interpreted(f, x, p, kernels=False)
    got, h_got, gap = interpreted(f, x, p)
    assert got.shape == x.shape and got.dtype == x.dtype
    assert close(got, want, 1e-5) and close(h_got, h_want, 1e-5)
    assert close(got, connect_by_loop(
        x, p, lambda h: np.tanh(h @ np.asarray(w, np.float64))), 1e-5)
    assert 0 < float(gap) < 0.1
    assert float(gap) == pytest.approx(float(gap_want), rel=1e-3)


def test_the_kernels_gradients_are_autodiffs_on_every_leaf():
    """x, the sublayer's own parameter (through y), phi, bias, alpha: the
    backward pair against autodiff of the `jax.numpy` form."""
    x, p, w = kernel_case(12)
    co = jax.random.normal(jax.random.PRNGKey(13), x.shape)

    def loss(kernels):
        def f(x, p, w):
            out, _, _ = interpreted(lambda h: (jnp.tanh(h @ w), ()), x, p,
                                    kernels=kernels)
            return jnp.sum(out * co)
        return jax.grad(f, argnums=(0, 1, 2))
    got, want = loss(True)(x, p, w), loss(False)(x, p, w)
    flat = lambda g: {jax.tree_util.keystr(k): a for k, a in
                      jax.tree_util.tree_leaves_with_path(g)}
    got, want = flat(got), flat(want)
    assert len(want) == 5 and got.keys() == want.keys()
    for name in want:
        assert got[name].shape == want[name].shape, name
        assert close(got[name], want[name], 2e-5 * float(
            jnp.max(jnp.abs(want[name])))), name


def test_one_sinkhorn_iteration_shows_in_the_kernels_as_in_jax_numpy():
    """A planted `sinkhorn_1` must stay visible: the iterations are the
    configuration's, inside the kernel too."""
    x, p, w = kernel_case(14)
    p = dict(p, bias=xing4.hc_bias(4), alpha=jnp.full((3,), 0.01))
    f = lambda h: (h @ w, ())
    run = lambda kernels, iters: interpreted(f, x, p, kernels=kernels,
                                             iters=iters)
    one, gap_one = run(True, 1)[::2]
    twenty, gap_twenty = run(True, 20)[::2]
    assert close(one, run(False, 1)[0], 1e-5)
    assert float(jnp.max(jnp.abs(one - twenty))) > 0.1
    assert float(gap_one) > 0.05 and float(gap_twenty) < 1e-5


@pytest.mark.parametrize("positions,width", [
    (2 * hyper.KERNEL_TILE, 96), (hyper.KERNEL_TILE + 64, 128)],
    ids=["channels-not-whole-lanes", "positions-not-whole-tiles"])
def test_a_shape_the_kernels_refuse_takes_the_jax_numpy_form(positions,
                                                              width):
    """Bit for bit, values and gradients: nothing of the kernels runs."""
    x, p, w = kernel_case(15, positions, width)
    assert hyper.plan(x.shape, x.dtype, True) == 0
    assert hyper.plan(x.shape, jnp.bfloat16, True) == 0

    def run(kernels):
        def f(x, p):
            out, _, gap = hyper.connect(lambda h: (jnp.tanh(h @ w), ()), x,
                                        p, kernels=kernels, **HC)
            return jnp.sum(jnp.sin(out)), (out, gap)
        return jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(x, p)
    got, want = jax.tree.leaves(run(True)), jax.tree.leaves(run(False))
    assert all(np.array_equal(a, b) for a, b in zip(got, want))


def test_the_kernels_run_under_the_scope_the_readers_class_them_by():
    """`benchmark/readers/lm.py` classes an instruction by the scope its
    `op_name` holds.  All four kernels' bodies (interpreted here: their
    operations carry the kernel's name), the backward pair's in the
    `custom_vjp`'s backward rule, hold `mhc_mix`; the sublayer's own
    operations, which the backward rule differentiates between the two,
    do not."""
    import re
    x, p, w = kernel_case(16)

    def sublayer(h):
        with jax.named_scope("stand_in"):
            return jnp.tanh(h @ w), ()
    step = jax.jit(jax.grad(lambda x, p, w: jnp.sum(
        interpreted(sublayer, x, p)[0] ** 2), argnums=(0, 1, 2)))
    names = set(re.findall(r'op_name="([^"]*)"',
                           step.lower(x, p, w).compile().as_text()))
    for kernel, backward in (("mhc_read_fwd", False), ("mhc_write_fwd", False),
                             ("mhc_write_bwd", True), ("mhc_read_bwd", True)):
        held = [n for n in names if f"/{kernel}/" in n]
        assert held, kernel
        assert all("mhc_mix" in n.split(f"/{kernel}/")[0] for n in held)
        assert all(("transpose(jvp(mhc_mix))" in n) == backward for n in held)
    inside = [n for n in names if "stand_in" in n]
    assert any("transpose" in n for n in inside)
    assert any("transpose" not in n for n in inside)
    assert not any("mhc_mix" in n for n in inside)


# -- sigmoid routing, the shares ----------------------------------------------

def expert_params(key, c=64, e=8, f=32, scale=0.3):
    shapes = dict(router=(c, e), w_gate=(e, c, f), w_up=(e, c, f),
                  w_down=(e, f, c), shared_gate=(c, f), shared_up=(c, f),
                  shared_down=(f, c))
    p = {name: scale * jax.random.normal(jax.random.fold_in(key, i), shp)
         for i, (name, shp) in enumerate(shapes.items())}
    p["router_bias"] = jnp.zeros((e,), jnp.float32)
    return p


def test_sigmoid_route_is_the_references_and_its_bias_only_chooses():
    """s = sigmoid(h W_r); the top k of s + b; w = 2 s / sum s over the
    chosen.  A bias that changes a choice changes WHO is chosen and never
    a weight's formula; no gradient reaches it."""
    p = expert_params(jax.random.PRNGKey(0))
    h = jax.random.normal(jax.random.PRNGKey(1), (40, 64))
    s = np.asarray(jax.nn.sigmoid(h @ p["router"]), np.float64)
    top_e, w = moe.route_sigmoid(h, p, 2, scale=2.0)
    for t in range(40):
        want = np.argsort(-s[t])[:2]
        assert set(np.asarray(top_e[t])) == set(want)
        picked = s[t, np.asarray(top_e[t])]
        assert np.allclose(w[t], 2.0 * picked / picked.sum(), rtol=1e-5)
    # a bias towards the expert that scored third at position 0
    third = int(np.argsort(-s[0])[2])
    bias = jnp.zeros((8,)).at[third].set(float(s[0].max()))
    top_b, w_b = moe.route_sigmoid(h, dict(p, router_bias=bias), 2, 2.0)
    assert third in np.asarray(top_b[0]) and third not in np.asarray(top_e[0])
    picked = s[0, np.asarray(top_b[0])]
    assert np.allclose(w_b[0], 2.0 * picked / picked.sum(), rtol=1e-5)
    g = jax.grad(lambda b: jnp.sum(moe.route_sigmoid(
        h, dict(p, router_bias=b), 2, 2.0)[1] ** 2))(bias)
    assert not np.any(np.asarray(g))
    # the reference, with the same bias, holds every expert
    z = dict(K=2, E=8, held=list(range(8)), route_scale=2.0)
    with_bias = dict(p, router_bias=bias)
    total = moe.shared_expert_ungated(h, with_bias)
    out, rows, _ = moe.expert_layer(
        h, with_bias, held=tuple(range(8)), num_experts=8, top_k=2,
        kernels=False, scoring=lambda *a: moe.route_sigmoid(*a, scale=2.0))
    assert int(rows) == 80
    assert close(total + out, ref.experts(h, with_bias, z))
    assert not close(total + out, ref.experts(h, p, z), 1e-3)


def test_eight_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """8 experts as 8 shares of one: the routed parts the shares give plus
    the ungated shared expert, which every chip computes alike, counted
    ONCE, are what the plain reference gives for the whole layer; the
    shares' rows add up to P * top_k."""
    p = expert_params(jax.random.PRNGKey(2))
    p["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(3), (8,))
    h = jax.random.normal(jax.random.PRNGKey(4), (48, 64), jnp.float32)
    z = dict(K=2, E=8, held=list(range(8)), route_scale=2.0)
    whole = ref.experts(h, p, z)
    total, rows = moe.shared_expert_ungated(h, p), 0
    scoring = lambda *a: moe.route_sigmoid(*a, scale=2.0)
    for e in range(8):
        part = dict(p, w_gate=p["w_gate"][e:e + 1], w_up=p["w_up"][e:e + 1],
                    w_down=p["w_down"][e:e + 1])
        out, n, _ = moe.expert_layer(h, part, held=(e,), num_experts=8,
                                     top_k=2, kernels=False, scoring=scoring)
        total, rows = total + out, rows + int(n)
    assert rows == 48 * 2
    assert close(total, whole)
    # each fault of the router is another layer
    assert not close(total, ref.experts(h, p, z, no_route_scale=True), 1e-3)
    assert not close(total, ref.experts(h, p, z, softmax_route=True), 1e-3)


def test_the_softmax_rule_is_still_the_default():
    """`expert_layer` without `scoring` routes as before this model."""
    p = expert_params(jax.random.PRNGKey(5))
    h = jax.random.normal(jax.random.PRNGKey(6), (16, 64))
    top_e, w = moe.route(h, p["router"], 2)
    probs = np.asarray(jax.nn.softmax(h @ p["router"], -1))
    assert np.array_equal(np.sort(np.asarray(top_e), -1),
                          np.sort(np.argsort(-probs, -1)[:, :2], -1))
    out, rows, _ = moe.expert_layer(h, p, held=tuple(range(8)), num_experts=8,
                                    top_k=2, kernels=False)
    weight = jnp.zeros((16, 8)).at[jnp.arange(16)[:, None], top_e].set(w)
    want = sum(weight[:, e, None] * ref.swiglu(
        h, p["w_gate"][e], p["w_up"][e], p["w_down"][e]) for e in range(8))
    assert int(rows) == 32 and close(out, want)


# -- the model and its objective against the plain reference ------------------

def config_for(layers, dense):
    return dict(CONFIG, num_hidden_layers=layers, first_k_dense_replace=dense)


def test_init_is_the_configurations_recipe():
    params, _ = xing4.make(TINY)[0](jax.random.PRNGKey(7))
    rparams = ref.init(CONFIG, jax.random.PRNGKey(7))
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    rflat = dict(jax.tree_util.tree_flatten_with_path(rparams)[0])
    assert set(k for k, _ in flat) == set(rflat)
    assert all(np.array_equal(v, rflat[k]) for k, v in flat)
    assert params["dense"]["hc_attn"]["phi"].shape == (1, 4 * 64, 24)
    assert params["sparse"]["w_gate"].shape == (2, 2, 64, 32)
    assert params["sparse"]["router"].shape == (2, 64, 8)
    bias = np.asarray(params["sparse"]["hc_mlp"]["bias"][0])
    assert np.allclose(bias[:4], [-1.5, -0.5, 0.5, 1.5])
    assert np.allclose(bias[4:8], [1.5, 0.5, -0.5, -1.5])
    assert np.allclose(bias[8:].reshape(4, 4)[1], [-1, 2, 1, 2])
    assert np.all(np.asarray(params["dense"]["hc_mlp"]["alpha"]) == 0.01)
    phi = params["sparse"]["hc_attn"]["phi"]
    assert abs(float(jnp.std(phi)) - xing4.HC_PHI_STD) < 1e-4
    assert CONFIG["hc_init"]["phi_std"] == xing4.HC_PHI_STD == 0.002
    assert abs(float(jnp.std(params["embed"])) - 1.0) < 0.05
    assert abs(float(jnp.std(params["sparse"]["router_bias"])) - 0.01) < 0.005


@pytest.mark.parametrize("layers,dense", [(3, 1), (2, 0), (3, 2)],
                         ids=["dense-expert-expert", "experts-only",
                              "two-dense"])
def test_loss_and_every_gradient_leaf_match_the_reference(layers, dense):
    """The published order (a leading dense layer, then expert layers), no
    dense layer, and two; gains, scales and biases moved off their starting
    values so that they count."""
    config = config_for(layers, dense)
    shape = TINY._replace(layers=layers, dense_layers=dense)
    init_fn, apply_fn = xing4.make(shape)
    params, _ = init_fn(jax.random.PRNGKey(0))

    def bump(path, a):
        name = path[-1].key
        if name in ("ln1", "ln2", "q_norm", "kv_norm", "final_norm"):
            return a + 0.1
        return a * 30 if name in ("alpha", "phi") else a
    params = jax.tree_util.tree_map_with_path(bump, params)
    toks = jax.random.randint(jax.random.PRNGKey(9), (2, 32), 0, 63)
    obj = apply_fn.objective

    def prog(p):
        loss, (_, extras) = obj.loss(apply_fn, p, {}, obj.prepare(None, toks))
        return loss, extras
    (loss, extras), grads = jax.jit(
        jax.value_and_grad(prog, has_aux=True))(params)
    z = ref.sizes(config)

    def plain(p):
        return sum(ref.sequence_loss(p, toks[s], z)[0] for s in range(2)) / 2
    rloss, rgrads = jax.jit(jax.value_and_grad(plain))(params)
    assert abs(float(loss) - float(rloss)) < 1e-5 * float(rloss)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    rflat = dict(jax.tree_util.tree_flatten_with_path(rgrads)[0])
    both = 5 + 2 + 6 + 2            # MLA, its two norms, two mixers, ln1/ln2
    assert len(flat) == 3 + (both + 3) + (both + 8)
    for k, g in flat:
        path = jax.tree_util.keystr(k)
        if not g.size:          # a kind of layer this share has none of
            assert g.shape == rflat[k].shape and dense in (0, layers)
            continue
        assert close(g, rflat[k], 2e-4), path
        if "router_bias" in path:       # a buffer: no gradient reaches it
            assert not np.any(np.asarray(g))
        else:
            assert np.any(np.asarray(g)), path
    rows, fullest, count, touched, gap = (float(e) for e in extras)
    assert count == 2 * 31 and 0 <= gap < 1e-4
    if layers > dense:
        assert 0 < fullest <= rows <= touched <= (layers - dense) * 2 * 32 * 2
    else:
        assert rows == touched == 0


def test_evaluation_counts_match_the_reference():
    init_fn, apply_fn = xing4.make(TINY)
    params, _ = init_fn(jax.random.PRNGKey(1))
    toks = jax.random.randint(jax.random.PRNGKey(2), (3, 32), 0, 63)
    labels = jnp.asarray([0, 0, -1])            # the last row is padding
    loss, hit, count = apply_fn.objective.eval_counts(
        apply_fn, params, {}, None, toks, labels)
    z = ref.sizes(CONFIG)
    want = [ref.sequence_loss(params, toks[s], z) for s in range(2)]
    assert float(loss) == pytest.approx(sum(float(l) for l, _ in want),
                                        rel=1e-5)
    assert int(hit) == sum(int(c) for _, c in want) and int(count) == 62


SCOPES = ("attn_mla", "mla_core", "mhc_mix", "mhc_sinkhorn", "mlp_dense",
          "moe_route", "moe_experts", "moe_shared", "lm_head")


def test_every_scope_is_in_the_compiled_train_program():
    """Forward and backward: the scopes the benchmark's readers class the
    device time by, in the `op_name` of the compiled train step's
    instructions (what `benchmark/readers/lm.py` reads)."""
    import re
    init_fn, apply_fn = xing4.make(TINY)
    params, _ = init_fn(jax.random.PRNGKey(0))
    toks = jnp.zeros((2, 32), jnp.int32)
    obj = apply_fn.objective
    step = jax.jit(jax.grad(lambda p: obj.loss(apply_fn, p, {}, toks)[0]))
    names = set(re.findall(r'op_name="([^"]*)"',
                           step.lower(params).compile().as_text()))
    for scope in SCOPES:
        held = [n for n in names if f"/{scope}/" in n]
        assert any("transpose(jvp" in n for n in held), scope
        assert any("transpose(jvp" not in n for n in held), scope
    assert any("/mhc_mix/mhc_sinkhorn/" in n for n in names)
    assert any("/attn_mla/mla_core/" in n for n in names)


def write_tokens(root, train, heldout):
    os.makedirs(os.path.join(root, "tokens"))
    np.save(os.path.join(root, "tokens", "train.npy"), train)
    np.save(os.path.join(root, "tokens", "heldout.npy"), heldout)


@pytest.mark.parametrize("devices", [1, 2])
def test_sgd_steps_and_test_model_through_trainer_match_the_reference(
        tmp_path, devices):
    """`Trainer.train_model` (staged epoch, scanned window, ring drain) and
    `test_model` against the reference followed step by step: the losses,
    the parameters' change, the evaluation; on two devices under `ddp`."""
    rng = np.random.default_rng(5)
    b = 2 * devices
    train = rng.integers(0, 63, (4 * b, 32), dtype=np.int32)
    heldout = rng.integers(0, 63, (5, 32), dtype=np.int32)     # ragged eval
    write_tokens(str(tmp_path), train, heldout)
    tel = Telemetry()
    tr = Trainer(model="xing4-tiny", strategy="ddp", num_devices=devices,
                 global_batch=b, data_dir=str(tmp_path), seed=11, init_seed=3,
                 sgd_cfg=sgd.SGDConfig(lr=0.01), limit_train_batches=3,
                 telemetry=tel, log=lambda s: None)
    assert tr.real_data
    p0 = jax.device_get(tr.state.params)
    timers = tr.train_model(0)
    eval_loss, correct, acc = tr.test_model()
    want = ref.follow(CONFIG, seed=11, weights_seed=3, world=devices,
                      per_chip_batch=2, train=train, heldout=heldout, steps=3)
    assert np.allclose(timers.losses, want["loss"], rtol=2e-5)
    assert abs(eval_loss - want["eval_loss"]) < 2e-5 * want["eval_loss"]
    assert correct == want["eval_correct"]
    moved = ref.tree_norms(jax.tree.map(
        lambda a, c: np.asarray(a) - c, jax.device_get(tr.state.params), p0))
    assert set(moved) == set(want["dparam_norms"])
    for leaf, norm in want["dparam_norms"].items():
        assert abs(moved[leaf] - norm) <= 1e-3 * norm + 1e-9, leaf
    # counters of the epoch, beside dispatches and host_round_trips
    totals = tel.counter_totals()
    assert totals["dispatches"] == totals["host_round_trips"] == 2
    assert totals["moe_rows_local"] == tr.last_epoch_extras["moe_rows_local"]
    # two expert layers: 32 positions x top-2 x 2 held of 8
    assert totals["moe_rows_expected"] == 3 * b * 32 * 2 * 2 * 2 / 8
    assert totals["tokens_predicted"] == 3 * b * 31
    assert totals["moe_rows_touched"] \
        == tr.last_epoch_extras["moe_rows_touched"]
    # a maximum, not a sum: no counter, a column of every step's event
    assert "mhc_res_gap" not in totals
    assert 0 <= tr.last_epoch_extras["mhc_res_gap"] < 1e-4
    steps = [r for r in tel.records if r["kind"] == "step"]
    assert len(steps) == 3 and all(
        "moe_rows_max_expert" in s and 0 <= s["mhc_res_gap"] < 1e-4
        for s in steps)
    assert tr.last_epoch_extras["mhc_res_gap"] == max(
        s["mhc_res_gap"] for s in steps)
    gauges = {(r["name"], r.get("kernel")): r["value"] for r in tel.records
              if r["kind"] == "gauge"}
    assert gauges["mla_kernel", None] == 0          # the CPU: jax.numpy
    assert gauges["mla_qk_dim", None] == 24 and gauges["mla_v_dim", None] == 16
    assert gauges["mhc_streams", None] == 4
    assert gauges["mhc_sinkhorn_iters", None] == 20
    assert gauges["mhc_kernel", None] == 0 and gauges["mhc_tile", None] == 0
    assert all(gauges["attn_tiles_visited", k] == 1
               for k in ("fwd", "dkv", "dq"))
    assert {r["rule"] for r in tel.records if r["kind"] == "gauge"
            and r["name"].startswith("attn_")} == {"causal"}
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import telemetry_report
    text = "\n".join(telemetry_report._mla_lines(tel.records)
                     + telemetry_report._mhc_lines(tel.records)
                     + telemetry_report._moe_lines(tel.records))
    assert "== latent attention ==" in text
    assert "(jax.numpy), a key/value head a query head: keys of 24, " \
        "values of 16" in text
    assert "== hyper-connections ==" in text
    assert "4 streams (jax.numpy), 20 Sinkhorn iterations a position" in text
    on = [dict(kind="gauge", name=name, value=v) for name, v in (
        ("mhc_streams", 4), ("mhc_sinkhorn_iters", 20), ("mhc_kernel", 1),
        ("mhc_tile", 128))]
    assert "4 streams (the Pallas kernels on tiles of 128 positions), 20 " \
        "Sinkhorn" in "\n".join(telemetry_report._mhc_lines(on))
    assert "over 3 steps" in text
    assert f"predicted tokens {3 * b * 31:,}" in text


def test_the_gauges_say_which_attention_runs_at_which_sizes():
    def gauges(shape, kernels):
        _, apply_fn = xing4.make(shape, kernels=kernels)
        return {name: value for name, value, _ in apply_fn.objective.gauges
                if not name.startswith("attn_")}
    real = xing4.Shape()
    assert gauges(real, True) == {
        "mla_kernel": 1, "mla_qk_dim": 192, "mla_v_dim": 128,
        "mhc_streams": 4, "mhc_sinkhorn_iters": 20,
        "mhc_kernel": 1, "mhc_tile": hyper.KERNEL_TILE}
    off = gauges(real, False)
    assert (off["mla_kernel"], off["mhc_kernel"], off["mhc_tile"]) == (0,) * 3
    assert gauges(TINY, None)["mla_kernel"] == 0    # the CPU
    # 64 channels are no whole lane: jax.numpy, whatever the backend
    assert gauges(TINY, True)["mla_kernel"] == 1
    assert gauges(TINY, True)["mhc_kernel"] == 0


def test_a_share_of_its_own_fields_and_leading_layers_within_the_layers():
    with pytest.raises(ValueError, match="leading dense layers"):
        models.get_model("xing4-tiny", layers=2, dense_layers=3)
    with pytest.raises(ValueError, match="block"):
        models.get_model("xing4-tiny", block=4)
    init_fn, apply_fn = models.get_model("xing4-tiny", layers=4,
                                         dense_layers=1, held=(1, 5),
                                         seq_len=16)
    shape = apply_fn.objective.shape
    assert (shape.layers, shape.held, shape.seq_len) == (4, (1, 5), 16)
    params = jax.eval_shape(lambda k: init_fn(k)[0], jax.random.PRNGKey(0))
    assert params["sparse"]["w_gate"].shape[:2] == (3, 2)
    assert params["dense"]["mlp_gate"].shape == (1, 64, 96)


def test_the_published_share_is_the_configurations_count():
    """`--model xing4.0-29b-a4b` resolves at the share the benchmark's
    configuration states, whose `counted` is the program's leaf shapes."""
    init_fn, apply_fn = models.get_model("xing4.0-29b-a4b")
    shape = apply_fn.objective.shape
    params = jax.eval_shape(lambda k: init_fn(k)[0], jax.random.PRNGKey(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    assert n == REAL["counted"]["all"] == 759_346_446
    assert (shape.layers, shape.dense_layers, len(shape.held), shape.vocab,
            shape.seq_len) == (
        REAL["num_hidden_layers"], REAL["first_k_dense_replace"],
        REAL["n_routed_experts"], REAL["vocab_size"], REAL["seq_len"])
    # every published width, under the catalog's keys
    assert (shape.hidden, shape.heads, shape.nope_dim, shape.rope_dim,
            shape.v_dim, shape.q_rank, shape.kv_rank, shape.dense_width,
            shape.expert_width, shape.num_experts, shape.top_k,
            shape.streams, shape.sinkhorn_iters) == tuple(
        REAL[k] if k != "n_routed_experts" else REAL["published"][k]
        for k in ("hidden_size", "num_attention_heads", "qk_nope_head_dim",
                  "qk_rope_head_dim", "v_head_dim", "q_lora_rank",
                  "kv_lora_rank", "intermediate_size",
                  "moe_intermediate_size", "n_routed_experts",
                  "num_experts_per_tok", "hc_mult", "hc_sinkhorn_iters"))
    ys = REAL["rope_scaling"]
    assert (shape.yarn_factor, shape.yarn_original, shape.yarn_beta,
            shape.yarn_mscale) == (
        ys["factor"], ys["original_max_position_embeddings"],
        (ys["beta_fast"], ys["beta_slow"]),
        (ys["mscale"], ys["mscale_all_dim"]))


def test_cli_trains_and_evaluates_the_decoder_with_its_shares_flags(tmp_path,
                                                                    capsys):
    """`python -m cs744_ddp_tpu.cli --model xing4-tiny` on the default
    path, the share's flags the decoders have in common; the flag only the
    block-diffusion decoder has is refused by name."""
    argv = ["--model", "xing4-tiny", "--strategy", "ddp",
            "--num-devices", "1", "--batch-size", "4", "--lr", "0.01",
            "--limit-train-batches", "2", "--data-dir", str(tmp_path),
            "--lm-layers", "3", "--lm-experts-held", "0-1",
            "--lm-seq-len", "16"]
    tr = cli.main(argv)
    assert tr.objective.shape.seq_len == 16 and len(tr.train_split) == 64
    out = capsys.readouterr().out
    assert "Test set: Average loss:" in out and "/240 (" in out   # 16 x 15
    with pytest.raises(ValueError, match="block"):
        cli.main(argv + ["--lm-block", "4"])
