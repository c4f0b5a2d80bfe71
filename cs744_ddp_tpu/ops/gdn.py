"""The gated delta rule (Gated DeltaNet's sequence mixer; Yang et al. 2024,
arXiv:2412.06464) and the short causal convolution in front of it.

Per head, a matrix-valued state S [dk, dv] that starts at 0 and, for every
position t in order, decays, is corrected toward the new value along the
new key, and is read by the query:

    S = exp(g_t) S          r = k_t^T S
    S = S + k_t (beta_t (v_t - r))^T          o_t = q_t^T S

with a data-dependent log-decay g_t <= 0 and write strength beta_t in
(0, 1).  That loop as written is the benchmark reference's
(benchmark/reference/hybrid_causal.py `recurrence`), which the tests hold
`delta_rule` to.

`delta_rule` computes the same in CHUNKS of C positions.  With G_t the sum
of g over the chunk's positions up to t and S_0 the state the chunk starts
from, the value written at t is

    u_t = beta_t (v_t - exp(G_t) k_t^T S_0)
          - beta_t sum_{s<t} exp(G_t - G_s) (k_t . k_s) u_s

a unit lower-triangular system (I + A) U = beta (V - exp(G) K S_0), solved
ONCE a chunk for both right-hand sides (T = (I + A)^-1 by the nilpotent
product (I + X)(I + X^2)(I + X^4)... with X = -A, log2(C) squarings: A is
strictly lower-triangular, so X^C = 0), and then

    U = T beta V - (T beta exp(G) K) S_0
    O = exp(G) Q S_0 + (Q K^T * D) U           D[t, s] = exp(G_t - G_s), s <= t
    S_C = exp(G_C) S_0 + (exp(G_C - G) K)^T U

Everything that does not need S_0 is computed for all chunks of a SEGMENT
(`SEGMENT` chunks) at once; a `lax.scan` over the segment's chunks carries
the state; an outer scan carries it from segment to segment, and its body
is recomputed in the backward pass, so that what the recurrence keeps for
its transpose is one state a segment and not the triangular systems of
every chunk of the sequence (4.9 GB a layer of a sequence at the published
widths against 2.2, where a step has 10 to spare: PERF.md section 6, PR 33).  All of
it in float32 at the highest matmul precision: the recurrence is 2% of the
model's operations and compounds its rounding over thousands of positions,
and the plain reference it is compared with is a float32 loop.  Every
exponent is of a difference G_t - G_s with s <= t, never positive.  The
backward pass is autodiff's.  A length that is not whole segments is
padded with positions that write nothing (beta 0) and do not decay (g 0).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# On the chip, the recurrence alone at 32 heads x 8192 x 128, forward +
# backward, ms: segments of 16 chunks of 64 42.6, of 4 35.2-35.7, of 1 34.0;
# 2 chunks of 128 33.3; the whole sequence one segment 56.8 (PERF.md
# section 6, PR 33): short segments keep a segment's triangular systems
# near the cores.
CHUNK = 64          # positions a chunk
SEGMENT = 4         # chunks recomputed together: 256 positions
_HI = lax.Precision.HIGHEST


def chunk_for(positions: int) -> int:
    """`CHUNK`, or a quarter of a sequence shorter than four of them, so
    that a short sequence (the CPU test size) still carries its state from
    chunk to chunk."""
    return max(1, min(CHUNK, positions // 4))


def causal_conv(x, w):
    """Depthwise causal convolution: x [P, C], w [taps, C] ->
    y[t] = sum_j w[j] x[t - (taps - 1) + j], zeros left of position 0."""
    taps = w.shape[0]
    with jax.named_scope("gdn_conv"):
        padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
        return sum(padded[j:j + x.shape[0]] * w[j].astype(x.dtype)
                   for j in range(taps))


def _unit_lower_inverse(a):
    """(I + a)^-1 for strictly lower-triangular a [..., C, C]."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=a.dtype)
    x = -a
    t = eye + x
    power = 2
    while power < c:
        x = jnp.matmul(x, x, precision=_HI)
        t = t + jnp.matmul(t, x, precision=_HI)
        power *= 2
    return t


def _segment(S0, q, k, v, g, beta):
    """One segment from the state S0 [h, dk, dv]: q, k [h, n, c, dk], v
    [h, n, c, dv], g, beta [h, n, c] (n chunks of c positions) ->
    (the state after it, o [h, n, c, dv])."""
    c = q.shape[2]
    G = jnp.cumsum(g, axis=-1)
    lower = jnp.tril(jnp.ones((c, c), bool))
    D = jnp.exp(jnp.where(lower, G[..., :, None] - G[..., None, :],
                          -jnp.inf))                        # s <= t
    mm = lambda a, b, spec: jnp.einsum(spec, a, b, precision=_HI)
    kk = mm(k, k, "hntd,hnsd->hnts")
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    T = _unit_lower_inverse(
        jnp.where(strict, beta[..., :, None] * D * kk, 0.0))
    U0 = mm(T, beta[..., None] * v, "hnts,hnsd->hntd")
    W = mm(T, (beta * jnp.exp(G))[..., None] * k, "hnts,hnsd->hntd")
    QK = D * mm(q, k, "hntd,hnsd->hnts")
    q_in = jnp.exp(G)[..., None] * q                        # reads S_0
    k_out = jnp.exp(G[..., -1:] - G)[..., None] * k         # writes S_C
    decay = jnp.exp(G[..., -1])                             # [h, n]

    def step(S, x):
        U0_c, W_c, QK_c, q_c, k_c, d_c = x
        U = U0_c - mm(W_c, S, "htk,hkv->htv")
        o = mm(q_c, S, "htk,hkv->htv") + mm(QK_c, U, "hts,hsv->htv")
        S = d_c[:, None, None] * S + mm(k_c, U, "htk,htv->hkv")
        return S, o
    chunk_first = lambda a: jnp.moveaxis(a, 1, 0)
    S, o = lax.scan(step, S0, tuple(map(
        chunk_first, (U0, W, QK, q_in, k_out, decay))))
    return S, jnp.moveaxis(o, 0, 1)


def delta_rule(q, k, v, g, beta, chunk: int = CHUNK,
               segment: int = SEGMENT):
    """The recurrence in chunks of `chunk` positions, `segment` chunks
    recomputed together in the backward pass (the module's docstring).
    q, k [heads, P, dk], v [heads, P, dv], g, beta [heads, P] -> o [heads,
    P, dv], float32."""
    heads, length, dk = q.shape
    c = min(chunk, length)
    n = min(segment, -(-length // c))           # chunks a segment
    pad = -length % (c * n)
    segments = (length + pad) // (c * n)

    def cut(a):
        a = a.astype(jnp.float32)
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape((heads, segments, n, c) + a.shape[2:])
        return jnp.moveaxis(a, 1, 0)            # segment first
    with jax.named_scope("gdn_recurrence"):
        S0 = jnp.zeros((heads, dk, v.shape[-1]), jnp.float32)
        _, o = lax.scan(
            jax.checkpoint(lambda S, x: _segment(S, *x)), S0,
            tuple(map(cut, (q, k, v, g, beta))))
        o = jnp.moveaxis(o, 0, 1).reshape(heads, segments * n * c, -1)
    return o[:, :length]
