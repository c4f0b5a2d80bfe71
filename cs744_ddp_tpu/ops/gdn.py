"""The gated delta rule (Gated DeltaNet's sequence mixer; Yang et al. 2024,
arXiv:2412.06464) and the short causal convolution in front of it.

Per head, a matrix-valued state S [dk, dv] that starts at 0 and, for every
position t in order, decays, is corrected toward the new value along the
new key, and is read by the query:

    S = exp(g_t) S          r = k_t^T S
    S = S + k_t (beta_t (v_t - r))^T          o_t = q_t^T S

with a data-dependent log-decay g_t <= 0 and write strength beta_t in
(0, 1), or in (0, 2) where the model allows the transition I - beta k k^T
negative eigenvalues (models/olmo_hybrid.py; nothing below assumes beta <
1).  That loop as written is the benchmark reference's
(benchmark/reference/hybrid_causal.py `recurrence`), which the tests hold
`delta_rule` to.

`delta_rule` computes the same in CHUNKS of C positions.  With G_t the sum
of g over the chunk's positions up to t and S_0 the state the chunk starts
from, the value written at t is

    u_t = beta_t (v_t - exp(G_t) k_t^T S_0)
          - beta_t sum_{s<t} exp(G_t - G_s) (k_t . k_s) u_s

a unit lower-triangular system (I + A) U = beta (V - exp(G) K S_0), solved
ONCE a chunk for both right-hand sides (T = (I + A)^-1: its diagonal blocks
of `INVERSE_BLOCK` positions by substitution, then by doubling, each step
a true inverse of blocks twice the size: `_unit_lower_inverse`), and then

    U = T beta V - (T beta exp(G) K) S_0
    O = exp(G) Q S_0 + (Q K^T * D) U           D[t, s] = exp(G_t - G_s), s <= t
    S_C = exp(G_C) S_0 + (exp(G_C - G) K)^T U

Two forms of that, chosen in `delta_rule` from what the caller resolved
from the backend (`kernels`) and from the shapes, as ops/attention.py
chooses its kernels:

ON THE TPU, key and value sizes in whole lanes (multiples of 128), or in
whole quarters of them (keys of 96 and values of 192, `narrow_fits`): two
Pallas kernels under one `jax.custom_vjp`, reading q, k [P, key heads x dk]
and v [P, heads x dv] as the mixer's arrays lie (heads side by side in the
lanes; a key head's block serves its value heads; a grid step takes the
fewest heads whose lanes are whole, or all of them, a head a static slice
of the block's lanes that Mosaic pads to whole lanes in VMEM, and a last
block that reaches past the heads skips those it does not have).
`gdn_chunks_fwd`: a grid of (blocks of heads, chunks), chunks in order,
the state [dk, dv] of each head in a VMEM scratch that lives across the
chunk axis; a grid step
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

THE FRONT (`front`): what the mixer does between its projection and the
recurrence, the causal convolution of 4 taps over the first 2 kd + vd
channels of the projection, `silu`, the split into q, k, v, the l2 norm of
every key head of q and of k and q's scale dk^-1/2.  On the TPU with key
heads and value channels in whole lanes and the length in whole tiles of
`FRONT_TILE` (`front_plan`; key heads of 96 too, their parts starting on a
lane, `key_stride`), two Pallas kernels under one
`jax.custom_vjp`, both under the scope `gdn_conv`: `gdn_front_fwd` walks
tiles of positions, reads the projection's channels in place (the block's
channel index; the 8 positions before the tile as a second small block,
for the taps' halo), convolves, applies `silu` and the norms, and writes
q, k, v as the recurrence's kernels read them: a loop over each part's
groups of `FRONT_LANES` lanes (whole key heads; for heads of 96, groups of
4 heads and a last of the rest), `FRONT_STRIPS` strips of 8
positions a turn (the taps as sublane rolls; independent strips for the
scheduler: PERF.md section 6, PR 38); `gdn_front_bwd` walks the tiles in
reverse, recomputes all that from the same input, pulls dq, dk, dv back
through it, hands the cotangent of a tile's first positions to the tile
before in a VMEM scratch, sums the weight's cotangent over the tiles in its
resident output block, and writes the projection's cotangent once: its
first channels from the kernel, the rest (z's) by an update in place.  Float32
elementwise, in the jax.numpy form's order of terms; nothing kept but the
projection and the weight.  Everywhere else that jax.numpy form.

THE MIXER (`mixer`): the projections, the front, the recurrence and the
gated norm, as both hybrid decoders call them, beta's scale and the head
sizes from the caller.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

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
INVERSE_BLOCK = 16  # positions a diagonal block of `_unit_lower_inverse`
_HI = lax.Precision.HIGHEST


def kernel_fits(dk: int, dv: int) -> bool:
    """The kernels' tiles are the MXU's: key and value sizes in whole
    lanes."""
    return dk % 128 == 0 and dv % 128 == 0


def narrow_fits(dk: int, dv: int) -> bool:
    """Heads narrower than whole lanes that the kernels still take: key and
    value sizes in whole quarters of the lanes (keys of 96 and values of
    192), so that at most four heads fill whole lanes.  A grid step then
    takes such groups of heads (`_heads_a_step`) and a head is a static
    slice of their lanes, which Mosaic pads to whole lanes in VMEM."""
    return not kernel_fits(dk, dv) and dk % 32 == 0 and dv % 32 == 0


def chunk_for(positions: int) -> int:
    """`CHUNK`, or a quarter of a sequence shorter than four of them, so
    that a short sequence (the CPU test size) still carries its state from
    chunk to chunk."""
    return max(1, min(CHUNK, positions // 4))


def plan(positions: int, dk: int, dv: int, kernels: bool) -> tuple:
    """(whether the Pallas kernels run, the chunk of the form that runs)
    for a sequence of `positions` and these key and value sizes; `kernels`
    as models/qwen3next.py `make` resolves it from the backend."""
    if kernels and (kernel_fits(dk, dv) or narrow_fits(dk, dv)):
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


_hi = functools.partial(jnp.matmul, precision=_HI)
# a lane rotation in a kernel, jnp.roll elsewhere (pltpu.roll has no rule to
# run outside a jitted function)
_roll = jax.jit(pltpu.roll, static_argnums=(1, 2))


def _diagonal_inverse(a, b: int):
    """(I + a)^-1 on the diagonal blocks of b positions of a strictly
    lower-triangular a [..., C, C] (C whole blocks, b a power of 2), zero
    off them: forward substitution on the VPU, b - 1 steps of
    multiply-adds.  The blocks are folded into one slab [..., b, C], row i
    of the slab holding row i of every block, so that a step takes every
    block at once.  Step j's multipliers, the slab's column j of each block
    spread over the block's lanes, are selected lane by lane from the
    slab's rolls, which all steps share: none of them waits for a step."""
    c = a.shape[-1]
    lead = a.shape[:-2]
    t = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    s = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    same = (t & -b) == (s & -b)
    slab = jnp.sum(jnp.where(same, a, 0.0).reshape(lead + (c // b, b, c)),
                   axis=-3)
    axis = slab.ndim - 1
    offset = lax.broadcasted_iota(jnp.int32, slab.shape, axis) & (b - 1)
    rolled = {d: _roll(slab, d % c, axis) if d else slab
              for d in range(2 - b, b)}     # lane l: the slab's lane l - d

    def column(j):
        out = rolled[-j]
        for o in range(1, b):
            out = jnp.where(offset == o, rolled[o - j], out)
        return out
    row = lax.broadcasted_iota(jnp.int32, slab.shape, axis - 1)
    inv = jnp.where(row == offset, 1.0, 0.0)
    for j in range(b - 1):                      # row j is final: eliminate
        inv = inv - column(j) * inv[..., j:j + 1, :]
    full = jnp.broadcast_to(inv[..., None, :, :], lead + (c // b, b, c))
    return jnp.where(same, full.reshape(lead + (c, c)), 0.0)


def _halves(x, b: int):
    """x's rows [..., N, W] by blocks of b: (the first block of every pair,
    the second), [..., N / 2, W] each."""
    *lead, n, w = x.shape
    y = x.reshape(tuple(lead) + (n // (2 * b), 2, b, w))
    return tuple(y[..., h, :, :].reshape(tuple(lead) + (n // 2, w))
                 for h in (0, 1))


def _joined(first, second, b: int):
    """`_halves`' inverse."""
    *lead, n, w = first.shape
    y = jnp.stack([a.reshape(tuple(lead) + (n // b, b, w))
                   for a in (first, second)], axis=-3)
    return y.reshape(tuple(lead) + (2 * n, w))


@jax.custom_jvp
def _unit_lower_inverse(a):
    """(I + a)^-1 for strictly lower-triangular a [..., C, C]: the diagonal
    blocks of `INVERSE_BLOCK` positions by substitution
    (`_diagonal_inverse`), then doubling, each step exact.  With T the
    inverse of the blocks of b, the inverse of a block of 2b, [[L1, 0],
    [X, L2]], is T less T X T, X the part of a inside the blocks of 2b and
    outside those of b; only the rows of each pair's second block change,
    so a step's two products are taken on those rows alone.  Three steps
    from 16 to a chunk of 128; each is bounded, where the powers of a grow
    like binomials.  C not a power of 2 times the block is padded with
    positions that touch nothing."""
    c = a.shape[-1]
    b = INVERSE_BLOCK
    n = b
    while n < c:
        n *= 2
    if n > c:
        a = jnp.pad(a, ((0, 0),) * (a.ndim - 2) + ((0, n - c), (0, n - c)))
    inv = _diagonal_inverse(a, b)
    t = lax.broadcasted_iota(jnp.int32, (n, n), 0)
    s = lax.broadcasted_iota(jnp.int32, (n, n), 1)
    while b < n:
        x = jnp.where(((t & -2 * b) == (s & -2 * b)) & ((t & -b) != (s & -b)),
                      a, 0.0)
        kept, changed = _halves(inv, b)
        inv = _joined(kept, changed - _hi(_hi(changed, x), inv), b)
        b *= 2
    return inv[..., :c, :c]


@_unit_lower_inverse.defjvp
def _unit_lower_inverse_jvp(primals, tangents):
    """dT = -T da T: two products, where autodiff would have to pull the
    tangent through every step (and the rolls have no rule for it)."""
    T = _unit_lower_inverse(*primals)
    return T, -_hi(T, _hi(tangents[0], T))


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
    of pulling dT back through `_unit_lower_inverse`."""
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


def _each_head(heads: int, total: int, body):
    """body(h) for the grid step's `heads` value heads; where the last
    step's block reaches past the `total` heads (a ragged grid: 30 heads
    of 96 lanes in blocks of 4), only those that exist."""
    for h in range(heads):
        if total % heads:
            pl.when(pl.program_id(0) * heads + h < total)(
                functools.partial(body, h))
        else:
            body(h)


def _forward_kernel(q, k, v, g, beta, o, *rest, total: int):
    """A grid step = a chunk of `g.shape[0]` value heads, side by side in
    the lanes as they lie in the mixer's arrays: q, k [C, key heads x dk],
    v, o [C, heads x dv]; g, beta [heads, 1, C].  A key head serves
    heads / key heads value heads in a row.  rest = (the states the chunks
    start from [heads, dk, dv] and their inverted systems [heads, C, C],
    only where a backward pass will want them,) the running state (VMEM
    scratch, alive across the chunk axis).  `total`: the value heads of
    the whole grid."""
    state = rest[-1]
    heads, dk, dv = state.shape
    group = heads * dk // q.shape[1]            # value heads a key head

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    def head(h):
        key, value = _lanes_of(h, group, dk, dv)
        S0 = state[h]
        (o[:, value], state[h]), T = _chunk(
            S0, q[:, key], k[:, key], v[:, value], g[h], beta[h])
        if len(rest) == 3:
            rest[0][h], rest[1][h] = S0, T
    _each_head(heads, total, head)


def _backward_kernel(q, k, v, g, beta, S0, T, do,
                     dq, dk, dv, dg, dbeta, dstate, *, total: int):
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

    def head(h):
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
    _each_head(heads, total, head)


def _lane_unit(group: int, dk: int, dv: int) -> int:
    """The fewest value heads, whole key heads' of them, whose key and
    value lanes are both whole: one key head's for heads in whole lanes,
    four for keys of 96 and values of 192."""
    unit = group
    while (unit // group * dk) % 128 or (unit * dv) % 128:
        unit += group
    return unit


def _heads_a_step(heads: int, group: int, dk: int, dv: int) -> int:
    """Value heads a grid step: whole `_lane_unit`s, `KERNEL_HEADS` at most
    if that divides the heads, else one unit, whose last block is then
    ragged (`_each_head`); all of them where one unit is more."""
    unit = _lane_unit(group, dk, dv)
    if unit >= heads:
        return heads
    hb = max(unit, KERNEL_HEADS // unit * unit)
    while heads % hb and hb > unit:
        hb -= unit
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
    hb = _heads_a_step(heads, group, dk, dv)
    keys, values, rows, state, system = _specs(
        hb, group, c, dk, dv, lambda i: i)
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    return pl.pallas_call(
        functools.partial(_forward_kernel, total=heads),
        grid=(-(-heads // hb), n),
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
    hb = _heads_a_step(heads, group, dk, dv)
    keys, values, rows, state, system = _specs(
        hb, group, c, dk, dv, lambda i: n - 1 - i)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32)
    return pl.pallas_call(
        functools.partial(_backward_kernel, total=heads),
        grid=(-(-heads // hb), n),
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


# -- the front: convolution, silu, the q / k norms ----------------------------

FRONT_TILE = 128        # positions a grid step of the front's kernels
FRONT_LANES = 256       # lanes a turn of a kernel's loop takes at most
FRONT_STRIPS = 4        # strips of 8 positions a turn takes, one by one
FRONT_VMEM = 64 * 2 ** 20
_LANES = 128
_STRIP = 8
_L2_EPS = 1e-6


def key_stride(key_width: int) -> int:
    """q's width in whole lanes: where k's channels start in a projection
    laid out [q | zeros | k | zeros | v | z], and v's at twice that, the
    zeros filling q and k to whole lanes so that every part starts on a
    lane (`front`'s `stride`; none where the key heads are whole lanes)."""
    return -(-key_width // _LANES) * _LANES


def front_plan(positions: int, dk: int, value_width: int, dtype,
               kernels: bool) -> int:
    """The positions a grid step of the front's kernels, or 0 where the
    jax.numpy front runs: `kernels` as `plan` takes it, key heads (`dk`) in
    whole quarters of the lanes (whole lanes, or 96) and the value channels
    in whole lanes, the length in whole tiles, a float32 projection."""
    fits = (kernels and dk % 32 == 0 and value_width % _LANES == 0
            and dtype == jnp.float32 and positions % FRONT_TILE == 0)
    return FRONT_TILE if fits else 0


def _front_plain(x, w, key_width: int, dk: int, stride: int):
    """The jax.numpy front (`front`)."""
    positions, channels = x.shape[0], w.shape[1]
    u = jax.nn.silu(causal_conv(x[:, :channels], w))
    heads = lambda a: a.reshape(positions, -1, dk).astype(jnp.float32)
    l2 = lambda a: a * lax.rsqrt(
        jnp.sum(jnp.square(a), -1, keepdims=True) + _L2_EPS)
    q = l2(heads(u[:, :key_width])) * dk ** -0.5
    k = l2(heads(u[:, stride:stride + key_width]))
    return (q.reshape(positions, key_width), k.reshape(positions, key_width),
            u[:, 2 * stride:].astype(jnp.float32), x[:, channels:])


class _Front(NamedTuple):
    """What the front's kernels are built for, besides their operands'
    shapes."""
    key_width: int
    dk: int
    stride: int
    tile: int
    interpret: bool


def _width(total: int, unit: int) -> int:
    """Whole `unit`s, `FRONT_LANES` at most if that divides `total`."""
    width = max(unit, FRONT_LANES // unit * unit)
    while total % width and width > unit:
        width -= unit
    return width


def _parts(st: _Front, value_width: int):
    """(part, its first lane in the projection and in its own array, the
    lanes a turn of the loop takes: whole key heads in whole lanes of q, of
    k, whole lanes of v, how many such groups, the scale after the l2 norm:
    None for no norm).  Where a part is not whole groups (30 key heads of
    96: 7 groups of 4 and 2 heads), the rest is a last group of its own."""
    kd, dk, stride = st.key_width, st.dk, st.stride
    for part, base, total, unit, scale in (
            (0, 0, kd, dk * _LANES // math.gcd(dk, _LANES), dk ** -0.5),
            (1, stride, kd, dk * _LANES // math.gcd(dk, _LANES), 1.0),
            (2, 2 * stride, value_width, _LANES, None)):
        width = _width(total, unit) if total >= unit else total
        count = total // width
        yield part, base, 0, width, count, scale
        if total % width:
            yield part, base + count * width, count * width, \
                total % width, 1, scale


def _over_groups(st: _Front, value_width: int, body):
    """body(part, lanes in the projection, lanes in the part's own array,
    width, scale) for every group of lanes, a loop a part: the kernel's
    code is a part's, not a group's, long."""
    for part, base, own, width, count, scale in _parts(st, value_width):
        def group(g, carry, part=part, base=base, own=own, width=width,
                  scale=scale):
            at = lambda offset: pl.ds(
                pl.multiple_of(offset + g * width, _LANES), width)
            body(part, at(base), at(own), width, scale)
            return carry
        lax.fori_loop(0, count, group, 0)


def _turns(strips: int, body, carry):
    """body(strip, carry) over a tile's strips, `FRONT_STRIPS` of them a
    turn of the loop (independent work for the scheduler to interleave)."""
    per = max(1, min(FRONT_STRIPS, strips))
    while strips % per:
        per -= 1

    def turn(i, carry):
        for j in range(per):
            carry = body(i * per + j, carry)
        return carry
    return lax.fori_loop(0, strips // per, turn, carry)


def _strip_at(s):
    return pl.ds(pl.multiple_of(s * _STRIP, _STRIP), _STRIP)


def _rolled(a, shifts):
    """a [8, W] rolled down its rows by each shift."""
    return tuple(pltpu.roll(a, j, 0) for j in shifts)


def _taps_at(w, lanes, width: int):
    """The weight's rows (its taps) on a group's lanes, each as a whole
    strip."""
    return [jnp.broadcast_to(w[j:j + 1, lanes], (_STRIP, width))
            for j in range(w.shape[0])]


def _behind(taps: int):
    """The rolls that bring x[t - j] to row t, j = 1 .. taps - 1."""
    return tuple(range(1, taps))


def _ahead(taps: int):
    """The rolls that bring dc[t + m] to row t, m = 1 .. taps - 1."""
    return tuple(_STRIP - m for m in range(1, taps))


def _tapped(taps, terms):
    """sum_j terms[j] * taps[j], tap 0 first (the jax.numpy form's
    order)."""
    total = terms[0] * taps[0]
    for j in range(1, len(taps)):
        total = total + terms[j] * taps[j]
    return total


def _convolved(cur, rolled_cur, rolled_prev, taps):
    """A strip's convolution: cur [8, W] = x[t] and its `_behind` rolls
    and the strip before's -> (c [8, W], (x[t - taps + 1], .., x[t - 1],
    x[t]))."""
    row = lax.broadcasted_iota(jnp.int32, cur.shape, 0)
    back = [jnp.where(row >= j, r, p) for j, r, p in
            zip(_behind(len(taps)), rolled_cur, rolled_prev)]
    seen = tuple(reversed(back)) + (cur,)
    return _tapped(taps, seen), seen


def _by_head(a, dk: int):
    return [a[:, h * dk:(h + 1) * dk] for h in range(a.shape[1] // dk)]


def _activated(c, scale, dk: int):
    """silu, then (scale not None) the l2 norm over each key head of `dk`
    lanes and the scale: -> (the output, sigmoid(c), silu(c), the norms'
    factors, a [8, 1] a head)."""
    sig = jax.nn.sigmoid(c)
    u = c * sig
    if scale is None:
        return u, sig, u, None
    r = [lax.rsqrt(jnp.sum(a * a, 1, keepdims=True) + _L2_EPS)
         for a in _by_head(u, dk)]
    y = jnp.concatenate([a * f for a, f in zip(_by_head(u, dk), r)], 1)
    return (y if scale == 1.0 else y * scale), sig, u, r


def _front_fwd_kernel(st: _Front, x, halo, w, q, k, v):
    """A tile of positions: x [T, C] (the projection's first C = 2 kd + vd
    channels), halo [8, C] (the 8 positions before the tile), w [4, C] ->
    q, k [T, kd], v [T, vd]."""
    first = pl.program_id(0) == 0
    outs, behind = (q, k, v), _behind(w.shape[0])

    def group(part, lanes, own, width, scale):
        taps = _taps_at(w, lanes, width)
        prev = jnp.where(first, 0.0, halo[:, lanes])

        def strip(s, rolled_prev):
            at = _strip_at(s)
            cur = x[at, lanes]
            rolled_cur = _rolled(cur, behind)
            c, _ = _convolved(cur, rolled_cur, rolled_prev, taps)
            outs[part][at, own] = _activated(c, scale, st.dk)[0]
            return rolled_cur
        _turns(st.tile // _STRIP, strip, _rolled(prev, behind))
    _over_groups(st, v.shape[1], group)


def _front_bwd_kernel(st: _Front, x, halo, w, dq, dk, dv, dx, dw, dnext):
    """The tiles in reverse: x, halo, w as `_front_fwd_kernel`'s; dq, dk
    [T, kd], dv [T, vd] -> dx [T, C], dw [4, C] (resident: summed over the
    grid); `dnext` [8, C]: scratch, the cotangent of the convolution's
    output at the first 8 positions of the tile after."""
    i = pl.program_id(0)
    first = i == pl.num_programs(0) - 1
    strips = st.tile // _STRIP
    outs, behind, ahead = (dq, dk, dv), _behind(w.shape[0]), \
        _ahead(w.shape[0])

    @pl.when(i == 0)
    def _():
        dw[...] = jnp.zeros_like(dw)
        dnext[...] = jnp.zeros_like(dnext)

    def group(part, lanes, own, width, scale):
        taps = _taps_at(w, lanes, width)
        halo_g = jnp.where(first, 0.0, halo[:, lanes])
        dout = outs[part]

        def strip(r, carry):
            rolled_cur, rolled_next, acc, _ = carry
            s = strips - 1 - r
            at = _strip_at(s)
            prev = jnp.where(s == 0, halo_g,
                             x[_strip_at(jnp.maximum(s - 1, 0)), lanes])
            rolled_prev = _rolled(prev, behind)
            cur = x[at, lanes]
            c, seen = _convolved(cur, rolled_cur, rolled_prev, taps)
            _, sig, u, r = _activated(c, scale, st.dk)
            du = dout[at, own]
            if scale is not None:
                if scale != 1.0:
                    du = du * scale
                du = jnp.concatenate([
                    f * d - (f * f * f) * jnp.sum(a * d, 1, keepdims=True) * a
                    for a, d, f in zip(_by_head(u, st.dk),
                                       _by_head(du, st.dk), r)], 1)
            dc = du * (sig * (1.0 + c * (1.0 - sig)))
            acc = tuple(a + dc * b for a, b in zip(acc, seen))
            # dx[t] = sum_j w[j] dc[t + taps - 1 - j]: dc[t + m] from this
            # strip's rows and, past its end, the next strip's
            rolled_dc = _rolled(dc, ahead)
            row = lax.broadcasted_iota(jnp.int32, dc.shape, 0)
            later = [jnp.where(row < m, a, b) for m, a, b in
                     zip(ahead, rolled_dc, rolled_next)]
            dx[at, lanes] = _tapped(taps, tuple(reversed(later)) + (dc,))
            return rolled_prev, rolled_dc, acc, dc

        last = x[_strip_at(strips - 1), lanes]
        zero = jnp.zeros_like(last)
        _, _, acc, dc0 = _turns(strips, strip, (
            _rolled(last, behind), _rolled(dnext[:, lanes], ahead),
            (zero,) * len(taps), zero))
        dnext[:, lanes] = dc0
        for j, a in enumerate(acc):
            dw[j:j + 1, lanes] += jnp.sum(a, 0, keepdims=True)
    _over_groups(st, dv.shape[1], group)
    kd, stride = st.key_width, st.stride
    if stride > kd:             # the zeros' lanes after q and after k
        for start in (kd, stride + kd):
            dx[:, start:start + stride - kd] = jnp.zeros(
                (dx.shape[0], stride - kd), dx.dtype)


def _front_specs(st: _Front, taps: int, channels: int, tile_of):
    """Block specs over the projection [P, >= C] (a tile of positions, the
    first C channels; the 8 positions before it), the weight [4, C] whole,
    and a tile of positions of an array of `width` lanes; `tile_of` maps
    the grid's index to the tile."""
    t8 = st.tile // _STRIP
    return (pl.BlockSpec((st.tile, channels), lambda i: (tile_of(i), 0)),
            pl.BlockSpec((_STRIP, channels),
                         lambda i: (jnp.maximum(tile_of(i) * t8 - 1, 0), 0)),
            pl.BlockSpec((taps, channels), lambda i: (0, 0)),
            lambda width: pl.BlockSpec((st.tile, width),
                                       lambda i: (tile_of(i), 0)))


def _front_call(kernel, name, st, grid, in_specs, out_specs, out_shape,
                scratch=()):
    return pl.pallas_call(
        functools.partial(kernel, st), grid=(grid,), in_specs=in_specs,
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=list(scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=FRONT_VMEM),
        interpret=st.interpret, name=name)


def _run_front_fwd(st: _Front, x, w):
    positions, channels = x.shape[0], w.shape[1]
    kd, vd = st.key_width, w.shape[1] - 2 * st.stride
    block, halo, weight, lanes = _front_specs(st, *w.shape, lambda i: i)
    f32 = lambda width: jax.ShapeDtypeStruct((positions, width), jnp.float32)
    return _front_call(
        _front_fwd_kernel, "gdn_front_fwd", st, positions // st.tile,
        [block, halo, weight], [lanes(kd), lanes(kd), lanes(vd)],
        [f32(kd), f32(kd), f32(vd)])(x, x, w)


def _run_front_bwd(st: _Front, x, w, dq, dk, dv):
    """-> (the projection's cotangent [P, width of x], of which the kernel
    writes the first C channels, the weight's [4, C])."""
    positions, channels = x.shape[0], w.shape[1]
    n = positions // st.tile
    block, halo, weight, lanes = _front_specs(
        st, *w.shape, lambda i: n - 1 - i)
    return _front_call(
        _front_bwd_kernel, "gdn_front_bwd", st, n,
        [block, halo, weight, lanes(dq.shape[1]), lanes(dk.shape[1]),
         lanes(dv.shape[1])], [block, weight],
        [jax.ShapeDtypeStruct(x.shape, jnp.float32),
         jax.ShapeDtypeStruct(w.shape, jnp.float32)],
        scratch=[pltpu.VMEM((_STRIP, channels), jnp.float32)])(
            x, x, w, dq, dk, dv)


def _front_primal(x, w, st: _Front):
    with jax.named_scope("gdn_conv"):
        q, k, v = _run_front_fwd(st, x, w)
    return q, k, v, x[:, w.shape[1]:]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _front_kernels(x, w, st: _Front):
    return _front_primal(x, w, st)


def _front_kernels_fwd(x, w, st: _Front):
    return _front_primal(x, w, st), (x, w)


def _front_kernels_bwd(st: _Front, kept, cotangents):
    x, w = kept
    dq, dk, dv, dz = cotangents
    with jax.named_scope("gdn_conv"):
        dx, dw = _run_front_bwd(st, x, w, dq, dk, dv)
        if dz.shape[1]:                 # z's channels, in place
            dx = lax.dynamic_update_slice(dx, dz.astype(dx.dtype),
                                          (0, w.shape[1]))
    return dx, dw


_front_kernels.defvjp(_front_kernels_fwd, _front_kernels_bwd)


def front(x, w, key_width: int, dk: int, *, stride: int = None,
          kernels: bool = False, interpret: bool = False):
    """The mixer's front (the module's docstring): the projection x [P, 2
    stride + vd + z's width] and the convolution's weight w [4, 2 stride +
    vd] -> q, k [P, kd] (l2-normed a key head of `dk`, q scaled by
    dk^-1/2), v [P, vd], float32, heads side by side in the lanes, and z =
    the rest of x as it is.  q is x's first kd channels, k the kd from
    `stride` (default kd; `key_stride` where zeros fill q and k to whole
    lanes), v the rest of w's.  `kernels` (the TPU, as models/qwen3next.py
    `make` resolves it), shapes `front_plan` takes, parts that start on a
    lane and at most 9 taps (a strip's rows and its halo): the Pallas
    kernels; anything else the jax.numpy form.  `interpret` runs the
    kernels in Pallas' interpreter (the CPU tests)."""
    stride = key_width if stride is None else stride
    tile = front_plan(x.shape[0], dk, w.shape[1] - 2 * stride, x.dtype,
                      kernels)
    if not tile or w.shape[0] > _STRIP + 1 or stride % _LANES:
        return _front_plain(x, w, key_width, dk, stride)
    return _front_kernels(x, w.astype(jnp.float32),
                          _Front(key_width, dk, stride, tile, interpret))


# -- the mixer: projections, front, recurrence, gated norm --------------------

def mixer(h, w_qkvz, w_ba, p, *, key_heads: int, value_heads: int, dk: int,
          dv: int, eps: float, beta_scale: float = 1.0,
          kernels: bool = False):
    """Gated DeltaNet's sequence mixer, as both hybrid decoders run it
    (models/qwen3next.py, models/olmo_hybrid.py): h [P, H] -> (its output
    [P, H], beta [P, value heads]).

        [q | k | v | z] = h w_qkvz  (q and k each followed by zeros to
                                    `key_stride`, or by none: w_qkvz's
                                    width says which)
        [b | a] = h w_ba            (one each a value head)
        q, k, v = `front` of the first three (convolution p["conv"], silu,
                  the l2 norms, q's scale)
        beta = beta_scale sigmoid(b)      g = -exp(A_log) softplus(a + dt_bias)
        o = `delta_rule`, a key head serving value / key heads value heads
        y = (o rsqrt(mean(o^2) + eps) gdn_norm) silu(z), per head; y w_out

    `beta_scale` 2 lets the transition I - beta k k^T have eigenvalues in
    (-1, 1) instead of [0, 1) (negative eigenvalues)."""
    P = h.shape[0]
    hk, hv = key_heads, value_heads
    kd, vd = hk * dk, hv * dv
    f32 = jnp.float32
    with jax.named_scope("attn_gdn"):
        qkvz = jnp.dot(h, w_qkvz.astype(h.dtype))
        ba = jnp.dot(h, w_ba.astype(h.dtype)).astype(f32)
        q, k, v, z = front(qkvz, p["conv"], kd, dk,
                           stride=(w_qkvz.shape[1] - 2 * vd) // 2,
                           kernels=kernels)
        beta = jax.nn.sigmoid(ba[:, :hv])
        if beta_scale != 1:
            beta = beta_scale * beta
        g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, hv:] + p["dt_bias"])
        o = delta_rule(q.reshape(P, hk, dk), k.reshape(P, hk, dk),
                       v.reshape(P, hv, dv), g, beta, chunk_for(P),
                       kernels=kernels)                     # [P, hv, dv]
        y = o * lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + eps)
        y = y * p["gdn_norm"] * jax.nn.silu(z.reshape(P, hv, dv).astype(f32))
        return jnp.dot(y.reshape(P, vd).astype(h.dtype),
                       p["w_out"].astype(h.dtype)), beta
