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

Two forms of that, chosen in `delta_rule` from what the caller resolved
from the backend (`kernels`) and from the shapes, as ops/attention.py
chooses its kernels:

ON THE TPU, key and value sizes in whole lanes (multiples of 128): two
Pallas kernels under one `jax.custom_vjp`, reading q, k [P, key heads x dk]
and v [P, heads x dv] as the mixer's arrays lie (heads side by side in the
lanes; a key head's block serves its value heads).  `gdn_chunks_fwd`: a
grid of (blocks of heads, chunks), chunks in order, the state [dk, dv] of
each head in a VMEM scratch that lives across the chunk axis; a grid step
loads the chunk's q, k, v, g, beta, builds G, D, k k^T, T, U0, W, Q K^T *
D, U in VMEM (`_chunk`) and writes o [C, dv] — and, where a backward pass
will follow, the state the chunk started from and its inverted system T
(134 MB each a layer of a sequence at 32 heads x 64 chunks of 128).
`gdn_chunks_bwd` walks the chunks in reverse with the state's cotangent in
the scratch: it rebuilds the chunk from its inputs, saved state and saved
T, and pulls (do, dS) back through it with `jax.vjp` of the same `_chunk`,
so the two kernels share one definition; the inversion alone has its
transpose written down (`_solved`), and a key head's cotangents are summed
over its value heads in the kernel.  The chunk's other tensors never reach
HBM, so there is nothing to recompute in segments.

EVERYWHERE ELSE (the CPU tests, the benchmark's tiny rehearsal, key sizes
like the tiny model's 8): the same in `jax.numpy`.  Everything that does
not need S_0 is computed for all chunks of a SEGMENT (`SEGMENT` chunks) at
once; a `lax.scan` over the segment's chunks carries the state; an outer
scan carries it from segment to segment, and its body is recomputed in the
backward pass, which is autodiff's, so that what is kept for the transpose
is one state a segment and not the triangular systems of every chunk of
the sequence (4.9 GB a layer of a sequence at the published widths against
2.2: PERF.md section 6, PR 33).  The segments remain only here, where the
chunk tensors do go through memory.

Both in float32 at the highest matmul precision (Mosaic:
`#tpu.contract_precision<fp32>`): the recurrence is 2% of the model's
operations and compounds its rounding over thousands of positions, and the
plain reference it is compared with is a float32 loop.  Every exponent is
of a difference G_t - G_s with s <= t, never positive.  A length that is
not whole chunks (segments) is padded with positions that write nothing
(beta 0) and do not decay (g 0).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The jax.numpy form.  On the chip, the recurrence alone at 32 heads x 8192
# x 128, forward + backward, ms: segments of 16 chunks of 64 42.6, of 4
# 35.2-35.7, of 1 34.0; 2 chunks of 128 33.3; the whole sequence one
# segment 56.8 (PERF.md section 6, PR 33): short segments keep a segment's
# triangular systems near the cores.
CHUNK = 64          # positions a chunk
SEGMENT = 4         # chunks recomputed together: 256 positions
# The kernels' (PERF.md section 6, PR 34: the sweep of the kernels alone on
# the chip, `tools/gdn_alone.py`).
KERNEL_CHUNK = 128  # positions a chunk
KERNEL_HEADS = 4    # value heads a grid step (`_heads_a_step`)
_HI = lax.Precision.HIGHEST


def kernel_fits(dk: int, dv: int) -> bool:
    """The kernels' tiles are the MXU's: key and value sizes in whole
    lanes."""
    return dk % 128 == 0 and dv % 128 == 0


def chunk_for(positions: int) -> int:
    """`CHUNK`, or a quarter of a sequence shorter than four of them, so
    that a short sequence (the CPU test size) still carries its state from
    chunk to chunk."""
    return max(1, min(CHUNK, positions // 4))


def plan(positions: int, dk: int, dv: int, kernels: bool) -> tuple:
    """(whether the Pallas kernels run, the chunk of the form that runs)
    for a sequence of `positions` and these key and value sizes; `kernels`
    as models/qwen3next.py `make` resolves it from the backend."""
    if kernels and kernel_fits(dk, dv):
        return True, KERNEL_CHUNK
    return False, chunk_for(positions)


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


def _chunked(q, k, v, g, beta, chunk: int, segment: int):
    """The jax.numpy form: chunks of `chunk` positions, `segment` chunks
    recomputed together in the backward pass, which is autodiff's."""
    heads, length, dk = q.shape
    c = min(chunk, length)
    n = min(segment, -(-length // c))           # chunks a segment
    pad = -length % (c * n)
    segments = (length + pad) // (c * n)

    def cut(a):
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape((heads, segments, n, c) + a.shape[2:])
        return jnp.moveaxis(a, 1, 0)            # segment first
    S0 = jnp.zeros((heads, dk, v.shape[-1]), jnp.float32)
    _, o = lax.scan(
        jax.checkpoint(lambda S, x: _segment(S, *x)), S0,
        tuple(map(cut, (q, k, v, g, beta))))
    o = jnp.moveaxis(o, 0, 1).reshape(heads, segments * n * c, -1)
    return o[:, :length]


# -- the kernels ---------------------------------------------------------------

def _mm(a, b, contract=(1, 0)):
    """a . b over a's axis contract[0] and b's contract[1], float32 at the
    highest precision (Mosaic: `#tpu.contract_precision<fp32>`)."""
    return lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())), precision=_HI,
        preferred_element_type=jnp.float32)


@jax.custom_vjp
def _solved(a, T):
    """T = (I + a)^-1 as a forward kernel left it, with the transpose of
    the inversion written down: da = -T^T dT T^T, two products in place
    of the 2 log2(C) of `_unit_lower_inverse` and of their transposes."""
    return T


def _solved_fwd(a, T):
    return T, T


def _solved_bwd(T, dT):
    return -_mm(T, _mm(dT, T, (1, 1)), (0, 0)), jnp.zeros_like(T)


_solved.defvjp(_solved_fwd, _solved_bwd)


def _chunk(S0, q, k, v, g, beta, T=None):
    """One chunk of one head from the state S0 [dk, dv], as the module's
    docstring writes it: q, k [C, dk], v [C, dv]; g, beta [1, C] (ROWS: a
    lane a position, as they lie in memory) -> ((o [C, dv], the state
    after the chunk), T).  T [C, C], where given, is the chunk's inverted
    system from an earlier pass.  Of operations Mosaic lowers and
    transposes, so that the backward kernel is `jax.vjp` of this; nothing
    here leaves VMEM."""
    c = q.shape[0]
    t = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    s = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    lower, strict, eye = s <= t, s < t, s == t
    # a row [1, C] as a column [C, 1] and back: exact, no transpose unit
    column = lambda r: jnp.sum(jnp.where(eye, r, 0.0), 1, keepdims=True)
    row = lambda col: jnp.sum(jnp.where(eye, col, 0.0), 0, keepdims=True)
    G = jnp.sum(jnp.where(lower, g, 0.0), 1, keepdims=True)     # [C, 1]
    G_last = jnp.sum(g, 1, keepdims=True)                       # [1, 1]
    # exp of G_t - G_s on s <= t only: above the diagonal the difference is
    # positive, and its exponential may overflow
    D = jnp.where(lower, jnp.exp(jnp.where(lower, G - row(G), 0.0)), 0.0)
    b = column(beta)
    in_decay = jnp.exp(G)
    A = jnp.where(strict, b * D * _mm(k, k, (1, 1)), 0.0)
    T = _solved(A, _unit_lower_inverse(A) if T is None else T)
    U0 = _mm(T, b * v)
    W = _mm(T, (b * in_decay) * k)
    QK = D * _mm(q, k, (1, 1))
    U = U0 - _mm(W, S0)
    o = _mm(in_decay * q, S0) + _mm(QK, U)
    S = jnp.exp(G_last) * S0 + _mm(jnp.exp(G_last - G) * k, U, (0, 0))
    return (o, S), T


def _lanes_of(h: int, group: int, dk: int, dv: int):
    """Where value head h of a grid step lies: (its key head's lanes of q
    and k, its own lanes of v and o); `group` value heads a key head."""
    return (slice(h // group * dk, (h // group + 1) * dk),
            slice(h * dv, (h + 1) * dv))


def _forward_kernel(q, k, v, g, beta, o, *rest):
    """A grid step = a chunk of `g.shape[0]` value heads, side by side in
    the lanes as they lie in the mixer's arrays: q, k [C, key heads x dk],
    v, o [C, heads x dv]; g, beta [heads, 1, C].  A key head serves
    heads / key heads value heads in a row.  rest = (the states the chunks
    start from [heads, dk, dv] and their inverted systems [heads, C, C],
    only where a backward pass will want them,) the running state (VMEM
    scratch, alive across the chunk axis)."""
    state = rest[-1]
    heads, dk, dv = state.shape
    group = heads * dk // q.shape[1]            # value heads a key head

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)
    for h in range(heads):
        key, value = _lanes_of(h, group, dk, dv)
        S0 = state[h]
        (o[:, value], state[h]), T = _chunk(
            S0, q[:, key], k[:, key], v[:, value], g[h], beta[h])
        if len(rest) == 3:
            rest[0][h], rest[1][h] = S0, T


def _backward_kernel(q, k, v, g, beta, S0, T, do,
                     dq, dk, dv, dg, dbeta, dstate):
    """The chunks in reverse, laid out as `_forward_kernel`'s: a grid step
    rebuilds its chunk from the saved state, the saved inverse and its
    inputs, and pulls (do, the state's cotangent from the chunk after) back
    through it; a key head's cotangents are summed over its value heads;
    `dstate` is the VMEM scratch that hands the state's cotangent to the
    chunk before."""
    heads, d_k, d_v = dstate.shape
    group = heads * d_k // q.shape[1]

    @pl.when(pl.program_id(1) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)
    for h in range(heads):
        key, value = _lanes_of(h, group, d_k, d_v)
        _, pull, _ = jax.vjp(
            functools.partial(_chunk, T=T[h]), S0[h], q[:, key], k[:, key],
            v[:, value], g[h], beta[h], has_aux=True)
        dstate[h], dq_h, dk_h, dv[:, value], dg[h], dbeta[h] = pull(
            (do[:, value], dstate[h]))
        if h % group:
            dq[:, key] += dq_h
            dk[:, key] += dk_h
        else:
            dq[:, key], dk[:, key] = dq_h, dk_h


def _heads_a_step(heads: int, group: int) -> int:
    """Whole key heads' value heads, `KERNEL_HEADS` at most if that
    divides the heads, else one key head's."""
    hb = max(group, KERNEL_HEADS // group * group)
    while heads % hb:
        hb -= group
    return hb


def _specs(hb, group, c, dk, dv, chunk_of):
    """Block specs over [P, key heads x dk], [P, heads x dv] (a chunk of
    the positions, `hb` value heads of the lanes), [heads, n, 1, C] (a row a
    chunk of a head) and [heads, n, a, b] (a matrix a chunk of a head);
    `chunk_of` maps the grid's second index to the chunk."""
    lanes = lambda width: pl.BlockSpec(
        (c, width), lambda h, i: (chunk_of(i), h))
    matrix = lambda a, b: pl.BlockSpec(
        (hb, None, a, b), lambda h, i: (h, chunk_of(i), 0, 0))
    return (lanes(hb // group * dk), lanes(hb * dv), matrix(1, c),
            matrix(dk, dv), matrix(c, c))


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def _run_forward(q, k, v, g, beta, dk: int, interpret: bool, keep: bool):
    """q, k [P, key heads x dk], v [P, heads x dv] (P whole chunks), g, beta
    [heads, n, 1, C] -> [o [P, heads x dv]] (+ with `keep`: every chunk's
    starting state [heads, n, dk, dv] and inverted system [heads, n, C,
    C])."""
    heads, n, _, c = g.shape
    dv, group = v.shape[1] // heads, heads * dk // q.shape[1]
    hb = _heads_a_step(heads, group)
    keys, values, rows, state, system = _specs(
        hb, group, c, dk, dv, lambda i: i)
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    return pl.pallas_call(
        _forward_kernel, grid=(heads // hb, n),
        in_specs=[keys, keys, values, rows, rows],
        out_specs=[values] + [state, system] * keep,
        out_shape=[shape(*v.shape)]
        + [shape(heads, n, dk, dv), shape(heads, n, c, c)] * keep,
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32)],
        compiler_params=_PARAMS, interpret=interpret,
        name="gdn_chunks_fwd")(q, k, v, g, beta)


def _run_backward(q, k, v, g, beta, S0, T, do, interpret: bool):
    heads, n, dk, dv = S0.shape
    c = g.shape[-1]
    group = heads * dk // q.shape[1]
    hb = _heads_a_step(heads, group)
    keys, values, rows, state, system = _specs(
        hb, group, c, dk, dv, lambda i: n - 1 - i)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32)
    return pl.pallas_call(
        _backward_kernel, grid=(heads // hb, n),
        in_specs=[keys, keys, values, rows, rows, state, system, values],
        out_specs=[keys, keys, values, rows, rows],
        out_shape=[like(q), like(k), like(v), like(g), like(beta)],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32)],
        compiler_params=_PARAMS, interpret=interpret,
        name="gdn_chunks_bwd")(q, k, v, g, beta, S0, T, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kernels(q, k, v, g, beta, dk, interpret):
    with jax.named_scope("gdn_recurrence"):
        return _run_forward(q, k, v, g, beta, dk, interpret, keep=False)[0]


def _kernels_fwd(q, k, v, g, beta, dk, interpret):
    with jax.named_scope("gdn_recurrence"):
        o, S0, T = _run_forward(q, k, v, g, beta, dk, interpret, keep=True)
    return o, (q, k, v, g, beta, S0, T)


def _kernels_bwd(dk, interpret, kept, do):
    with jax.named_scope("gdn_recurrence"):
        return tuple(_run_backward(*kept, do, interpret))


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def _fused(q, k, v, g, beta, chunk: int, interpret: bool):
    """The kernels' form: heads side by side in the lanes (a reshape, no
    copy), the length padded to whole chunks, g and beta a row a chunk of
    a head."""
    length, heads, _ = v.shape
    pad = -length % chunk
    n = (length + pad) // chunk
    lanes = lambda a: jnp.pad(a.reshape(length, -1), ((0, pad), (0, 0)))
    rows = lambda a: jnp.pad(a.T, ((0, 0), (0, pad))).reshape(
        heads, n, 1, chunk)
    o = _kernels(lanes(q), lanes(k), lanes(v), rows(g), rows(beta),
                 q.shape[-1], interpret)
    return o[:length].reshape(v.shape)


def delta_rule(q, k, v, g, beta, chunk: int = CHUNK, segment: int = SEGMENT,
               *, kernels: bool = False, interpret: bool = False):
    """The recurrence (the module's docstring), positions first as the
    mixer has them: q, k [P, key heads, dk], v [P, heads, dv], g, beta [P,
    heads] -> o [P, heads, dv], float32; a key head serves heads / key
    heads value heads in a row.  `kernels` (the TPU, as models/qwen3next.py
    `make` resolves it) and key and value sizes the kernels fit take the
    Pallas kernels, in chunks of `KERNEL_CHUNK`; anything else the
    jax.numpy form in chunks of `chunk` and segments of `segment` chunks.
    `interpret` runs the kernels in Pallas' interpreter (the CPU tests)."""
    q, k, v, g, beta = (a.astype(jnp.float32) for a in (q, k, v, g, beta))
    fused, kernel_chunk = plan(v.shape[0], q.shape[-1], v.shape[-1], kernels)
    if fused:
        return _fused(q, k, v, g, beta, kernel_chunk, interpret)
    group = v.shape[1] // q.shape[1]
    heads_first = lambda a: jnp.moveaxis(a, 1, 0)
    per_value_head = lambda a: jnp.repeat(heads_first(a), group, axis=0)
    with jax.named_scope("gdn_recurrence"):
        return jnp.moveaxis(_chunked(
            per_value_head(q), per_value_head(k), heads_first(v), g.T, beta.T,
            chunk, segment), 0, 1)
