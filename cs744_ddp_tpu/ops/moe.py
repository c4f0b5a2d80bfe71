"""One chip's share of a softmax-routed expert layer, dropless.

The layer is told which experts it holds (`held`, ids out of the model's
`num_experts`).  The router keeps its published width: it scores ALL the
experts, takes the `top_k` and renormalises over those; this chip then
computes, for every position, the weighted outputs of the chosen experts
that live here and nothing for the others.  What the absent experts would
have added is left out: that partial sum is the layer's result.

No row is ever dropped.  The positions' (position, expert) assignments are
sorted by local expert, absent ones last, into a static buffer of
``positions * top_k`` rows (the worst case: every choice lands here), so
the live rows are a PREFIX of it.  Everything past the router runs on a
prefix chosen from the routed count: the smallest rung of `ladder` that
holds the live rows, under a `lax.switch` whose branches are one function
at a static number of rows; the top rung is the whole buffer.  On that
prefix the three matrix products run as GROUPED products over the held
experts in expert order (the Pallas grouped matmul, megablox, visits row
tiles up to the last live row and no further), and the moves into and out
of it, the activation and every transpose touch the rung's rows and no
others: a layer costs what its rows cost, rounded up to a rung
(`prefix_rows`), whatever the buffer could hold.

The move into the prefix is a gather of rows; the two sums into positions
(out of it, and the gather's transpose) and the router weight's cotangent
are hand-written, by the prefix's rows on a short prefix and by the
positions' choices, as before the ladder, on a long one (`_short`).

Rows of the prefix past the live ones are never written by the kernels and
hold whatever the memory held; every read of them is behind a select.

A model with a SHARED expert adds `shared_expert` (models/qwen3next.py) or
`shared_expert_ungated` (models/xing4.py; its router is `route_sigmoid`)
beside the routed part: every position goes through it, every chip of the
deployment computes it alike, nothing of it is sorted or sliced.  """

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

GMM_TILING = (512, 512, 256)    # rows, contraction, columns
LADDER = (4, 2, 1)              # the rungs: the buffer's rows over these


def route(h, w_router, top_k: int):
    """(expert ids [P, k], weights [P, k]): softmax over ALL experts in
    float32, the k largest, renormalised over those (norm_topk_prob)."""
    logits = jnp.dot(h, w_router.astype(h.dtype),
                     preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_p, top_e = lax.top_k(probs, top_k)
    return top_e, top_p / jnp.sum(top_p, axis=-1, keepdims=True)


def local_plan(top_e, held):
    """Where each assignment goes in the buffer.

    Returns (order [N]: the assignment in each row, held experts' first and
    in `held` order, absent ones last, stable; local [P, k]: does the
    assignment's expert live here; group_sizes [len(held)]: rows per held
    expert)."""
    hit = top_e[..., None] == jnp.asarray(held, jnp.int32)
    local = jnp.any(hit, axis=-1)
    key = jnp.where(local, jnp.argmax(hit, axis=-1), len(held)).reshape(-1)
    iota = jnp.arange(key.shape[0], dtype=jnp.int32)
    sorted_key, order = lax.sort((key.astype(jnp.int32), iota), num_keys=1,
                                 is_stable=True)
    bounds = jnp.searchsorted(
        sorted_key, jnp.arange(len(held) + 1, dtype=jnp.int32), side="left")
    group_sizes = (bounds[1:] - bounds[:-1]).astype(jnp.int32)
    return order, local, group_sizes


def rows_of(order, shape):
    """slot [P, k]: the buffer row of each assignment (`order` inverted)."""
    iota = jnp.arange(order.shape[0], dtype=jnp.int32)
    return jnp.zeros_like(order).at[order].set(
        iota, unique_indices=True).reshape(shape)


# -- the ladder of prefixes ---------------------------------------------------

def ladder(n: int, kernels: bool) -> tuple:
    """The static prefixes of a buffer of `n` rows a layer may run on,
    least first: n/4, n/2 (whole row tiles of the grouped matmul on the
    kernel path) and n.  Few and far apart on purpose: a layer's cost then
    follows its rows in steps that few layers cross, not row by row.  (A
    rung at n/8 sat on the count even routing sends to one chip in eight,
    two layers in five crossed it, and the step time followed every seed's
    rows: PERF.md section 6, PR 30.)"""
    tile = GMM_TILING[0] if kernels else 1
    return tuple(sorted({min(n, -(-n // (d * tile)) * tile)
                         for d in LADDER}))


def _rung(total, rungs):
    """Index of the smallest rung that holds `total` rows."""
    return sum((total > r).astype(jnp.int32) for r in rungs[:-1]) \
        if len(rungs) > 1 else jnp.int32(0)


def prefix_rows(total, n: int, kernels: bool):
    """Rows a layer with `total` live rows in a buffer of `n` touches: its
    rung's."""
    rungs = ladder(n, kernels)
    return jnp.asarray(rungs, jnp.int32)[_rung(total, rungs)]


# -- moves into and out of the prefix -----------------------------------------
# Into the prefix: a gather of rows.  Out of it, a sum into positions, which
# is what the gather's transpose is too (`_sum_rows`).  Two ways, chosen by
# the prefix's static length (`_short`).  A short prefix is walked by its
# rows: a scatter-add of them, and the router weight's cotangent as a row
# dot on the prefix.  A long one (the top rungs: the worst case, which sets
# what the step reserves) is walked by the positions' choices as before the
# ladder: one [P, H] gather a choice, so that the [P, top_k, H] stack never
# exists and nothing of the prefix's size is held that was not held then.

def _short(rows: int, assignments: int) -> bool:
    """On the chip a scattered row costs about three gathered ones (PERF.md
    section 6, PR 30)."""
    return 3 * rows < assignments


def _sum_rows(buf, weights, src, live, slot, local):
    """out[p] = sum over position p's assignments with a row here of
    (weight *) buf[their row], float32.  `src` [nb]: the assignment in each
    row of the prefix, `live` [nb]: is it a live one; `slot`, `local`
    [P, k]: each assignment's row and whether it has one; `weights` [P, k]
    or None."""
    positions, k = slot.shape
    out = jnp.zeros((positions, buf.shape[1]), jnp.float32)
    if _short(buf.shape[0], slot.size):
        rows = buf.astype(jnp.float32)
        if weights is not None:
            rows = rows * weights.reshape(-1)[src, None].astype(jnp.float32)
        return out.at[src // k].add(jnp.where(live[:, None], rows, 0.0))
    for j in range(k):
        # a choice without a row here points past the prefix: the gather
        # clamps it and the select leaves it out
        rows = buf[slot[:, j]].astype(jnp.float32)
        if weights is not None:
            rows = rows * weights[:, j, None].astype(jnp.float32)
        out = out + jnp.where(local[:, j, None], rows, 0.0)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def dispatch(h, src, live, slot, local, dtype):
    """prefix[n] = h[position of the assignment `src[n]`], in `dtype`
    (cast before the gather: the buffer is top_k times h)."""
    return h.astype(dtype)[src // slot.shape[1]]


def _dispatch_fwd(h, src, live, slot, local, dtype):
    return dispatch(h, src, live, slot, local, dtype), \
        (src, live, slot, local, jnp.zeros((0,), h.dtype))


def _dispatch_bwd(dtype, res, d_buf):
    src, live, slot, local, like = res
    d_h = _sum_rows(d_buf, None, src, live, slot, local)
    return d_h.astype(like.dtype), None, None, None, None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(y, weights, src, live, slot, local):
    """out[p] = sum over p's local assignments of weight * y[their row]."""
    return _sum_rows(y, weights, src, live, slot, local).astype(y.dtype)


def _combine_fwd(y, weights, src, live, slot, local):
    return combine(y, weights, src, live, slot, local), \
        (y, weights, src, live, slot, local)


def _combine_bwd(res, d_out):
    y, weights, src, live, slot, local = res
    k = slot.shape[1]
    w_row = weights.reshape(-1)[src].astype(jnp.float32)
    if _short(y.shape[0], slot.size):
        # both cotangents from ONE gather of d_out's rows to the prefix:
        # the weight's is a row dot there, put back where its assignment is
        g = d_out[src // k].astype(jnp.float32)
        d_y = (w_row[:, None] * g).astype(y.dtype)
        dw_row = jnp.where(live, jnp.sum(y.astype(jnp.float32) * g, -1), 0.0)
        d_w = jnp.zeros((weights.size,), jnp.float32).at[src].set(
            dw_row, unique_indices=True).reshape(weights.shape)
    else:
        d_y = (w_row[:, None] * d_out[src // k]).astype(y.dtype)
        d_w = jnp.stack(
            [jnp.where(local[:, j],
                       jnp.sum(y[slot[:, j]].astype(jnp.float32) * d_out, -1),
                       0.0) for j in range(k)], axis=1)
    return d_y, d_w.astype(weights.dtype), None, None, None, None


combine.defvjp(_combine_fwd, _combine_bwd)


def _grouped(kernels: bool, interpret: bool):
    """(rows [N, K], weights [G, K, M], group_sizes [G]) -> [N, M] float32:
    the Pallas grouped matmul, or XLA's ragged dot off the TPU."""
    if not kernels:
        return lambda x, w, sizes: lax.ragged_dot(
            x, w.astype(x.dtype), sizes,
            preferred_element_type=jnp.float32)
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    def gmm(x, w, sizes):
        # bfloat16 operands: what the MXU makes of float32 ones at the
        # default precision; the sums stay float32.
        return megablox.gmm(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                            sizes, jnp.float32, GMM_TILING, None, None,
                            False, interpret)
    return gmm


def _on_prefix(nb: int, grouped, dtype, plan, h, weights, w_gate, w_up,
               w_down):
    """The experts on the first `nb` rows of the buffer (static; at least
    the live rows): h [P, H] -> [P, H] float32."""
    order, local, sizes = plan
    src = order[:nb]
    live = jnp.arange(nb, dtype=jnp.int32) < jnp.sum(sizes)
    slot = rows_of(order, local.shape)      # read on a long prefix only
    rows = dispatch(h, src, live, slot, local, dtype)
    gate = grouped(rows, w_gate, sizes)
    up = grouped(rows, w_up, sizes)
    act = (jax.nn.silu(gate) * up).astype(h.dtype)
    y = grouped(act, w_down, sizes)
    return combine(y, weights, src, live, slot, local)


def _on_ladder(rungs: tuple, grouped, dtype):
    """`_on_prefix` at the smallest of `rungs` that holds the live rows.
    The transpose is a switch of its own (the rung's forward pass again,
    then its transposes) and keeps the inputs only: autodiff through
    `lax.switch` would have every branch hand over every branch's
    intermediates, zeros for the ones not taken, sized by the buffer."""
    branch = lambda nb: functools.partial(_on_prefix, nb, grouped, dtype)

    @jax.custom_vjp
    def experts(plan, *diff):
        return lax.switch(_rung(jnp.sum(plan[2]), rungs),
                          [branch(nb) for nb in rungs], plan, *diff)

    def fwd(plan, *diff):
        return experts(plan, *diff), (plan, diff)

    def bwd(res, d_out):
        plan, diff = res

        def transposed(nb):
            return lambda plan, diff, d_out: jax.vjp(
                functools.partial(branch(nb), plan), *diff)[1](d_out)
        return (None,) + lax.switch(
            _rung(jnp.sum(plan[2]), rungs),
            [transposed(nb) for nb in rungs], plan, diff, d_out)

    experts.defvjp(fwd, bwd)
    return experts


def expert_layer(h, params, *, held, num_experts: int, top_k: int,
                 kernels: bool, interpret: bool = False, scoring=None):
    """h [P, H] -> (this chip's part of the layer's output [P, H],
    rows computed here, rows of the fullest held expert).  `params`: router
    [H, E]; w_gate, w_up [len(held), H, F]; w_down [len(held), F, H].
    `held`: the expert ids this chip holds, static.  `scoring(h, params,
    top_k)`: another rule than `route` (`route_sigmoid`, below)."""
    if params["router"].shape[1] != num_experts or \
            not all(0 <= e < num_experts for e in held):
        raise ValueError(f"moe: a router over {params['router'].shape[1]} "
                         f"experts, num_experts {num_experts}, held {held}")
    with jax.named_scope("moe_route"):
        top_e, weights = (scoring or _route_softmax)(h, params, top_k)
        plan = local_plan(top_e, held)
    with jax.named_scope("moe_experts"):
        experts = _on_ladder(
            ladder(h.shape[0] * top_k, kernels), _grouped(kernels, interpret),
            jnp.bfloat16 if kernels else h.dtype)
        # the kernels' operand cast out here, not in the rungs: it does not
        # depend on the sequence, and the compiler can move it out of a
        # loop over sequences but not out of a branch
        held_w = [params[name].astype(jnp.bfloat16) if kernels
                  else params[name] for name in ("w_gate", "w_up", "w_down")]
        out = experts(plan, h, weights, *held_w).astype(h.dtype)
    sizes = plan[2]
    return out, jnp.sum(sizes), jnp.max(sizes)


def shared_expert(h, params):
    """The expert every position goes through, times its own sigmoid gate:
    h [P, H] -> sigmoid(h w_sig) * W_d (silu(W_g h) * (W_u h)).  `params`:
    shared_gate, shared_up [H, F]; shared_down [F, H]; shared_sig [H, 1]."""
    with jax.named_scope("moe_shared"):
        dot = lambda x, name: jnp.dot(x, params[name].astype(x.dtype))
        act = jax.nn.silu(dot(h, "shared_gate")) * dot(h, "shared_up")
        return dot(act, "shared_down") * jax.nn.sigmoid(dot(h, "shared_sig"))


# -- a second scoring rule, an ungated shared expert (models/xing4.py) --------

def _route_softmax(h, params, top_k: int):
    return route(h, params["router"], top_k)


def route_sigmoid(h, params, top_k: int, scale: float = 1.0):
    """(expert ids [P, k], weights [P, k]): s = sigmoid(h W_r) over ALL
    experts in float32; the k largest of s + `router_bias` (a selection
    bias that only chooses: no gradient reaches it, and the weights are
    made of s alone); w = scale * s[chosen] / (sum s[chosen] + 1e-20).
    One group of experts (n_group = topk_group = 1: the group stage of
    `noaux_tc` is the identity)."""
    logits = jnp.dot(h, params["router"].astype(h.dtype),
                     preferred_element_type=jnp.float32)
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, top_e = lax.top_k(s + lax.stop_gradient(
        params["router_bias"].astype(jnp.float32)), top_k)
    top_s = jnp.take_along_axis(s, top_e, axis=-1)
    return top_e, scale * top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)


def shared_expert_ungated(h, params):
    """`shared_expert` without the sigmoid gate: h [P, H] ->
    W_d (silu(W_g h) * (W_u h)).  `params`: shared_gate, shared_up [H, F];
    shared_down [F, H]."""
    with jax.named_scope("moe_shared"):
        dot = lambda x, name: jnp.dot(x, params[name].astype(x.dtype))
        act = jax.nn.silu(dot(h, "shared_gate")) * dot(h, "shared_up")
        return dot(act, "shared_down")
