"""One chip's share of a softmax-routed expert layer, dropless.

The layer is told which experts it holds (`held`, ids out of the model's
`num_experts`).  The router keeps its published width: it scores ALL the
experts, takes the `top_k` and renormalises over those; this chip then
computes, for every position, the weighted outputs of the chosen experts
that live here and nothing for the others.  What the absent experts would
have added is left out: that partial sum is the layer's result.

No row is ever dropped.  The positions' (position, expert) assignments are
sorted by local expert, absent ones last, into a static buffer of
``positions * top_k`` rows (the worst case: every choice lands here); the
three matrix products run as GROUPED products over the held experts in
expert order, on the rows actually present: the Pallas grouped matmul
(megablox) visits row tiles up to the last live row and no further, so an
unevenly loaded expert costs what its rows cost.  The moves into and out of
the buffer are gathers in both directions (each has a hand-written
transpose, below: XLA's own would be a scatter-add of rows); they and the
activation touch the whole buffer.

Rows past the live ones are never written by the kernels and hold whatever
the memory held; every read of them is behind a select.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

GMM_TILING = (512, 512, 256)    # rows, contraction, columns


def route(h, w_router, top_k: int):
    """(expert ids [P, k], weights [P, k]): softmax over ALL experts in
    float32, the k largest, renormalised over those (norm_topk_prob)."""
    logits = jnp.dot(h, w_router.astype(h.dtype),
                     preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_p, top_e = lax.top_k(probs, top_k)
    return top_e, top_p / jnp.sum(top_p, axis=-1, keepdims=True)


def local_plan(top_e, held, num_experts: int):
    """Where each assignment goes in the buffer.

    Returns (order [N]: the assignment in each slot, slot [P, k]: each
    assignment's slot, local [P, k]: does its expert live here,
    group_sizes [len(held)]: rows per held expert, in `held` order)."""
    table = np.full((num_experts,), -1, np.int32)
    table[np.asarray(held)] = np.arange(len(held), dtype=np.int32)
    lidx = jnp.asarray(table)[top_e]
    local = lidx >= 0
    key = jnp.where(local, lidx, len(held)).reshape(-1)
    n = key.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    sorted_key, order = lax.sort((key, iota), num_keys=1, is_stable=True)
    slot = jnp.zeros((n,), jnp.int32).at[order].set(
        iota, unique_indices=True).reshape(top_e.shape)
    bounds = jnp.searchsorted(
        sorted_key, jnp.arange(len(held) + 1, dtype=jnp.int32), side="left")
    group_sizes = (bounds[1:] - bounds[:-1]).astype(jnp.int32)
    return order, slot, local, group_sizes


# -- moves into and out of the buffer: gathers both ways ---------------------
# One of a position's top_k choices at a time: a [P, H] gather each, so the
# [P, top_k, H] stack never exists.

def _gather_sum(buf, slot, local, weights=None):
    """sum_j where(local[:, j], w[:, j] * buf[slot[:, j]], 0), float32."""
    out = jnp.zeros((slot.shape[0], buf.shape[1]), jnp.float32)
    for j in range(slot.shape[1]):
        rows = buf[slot[:, j]].astype(jnp.float32)
        if weights is not None:
            rows = rows * weights[:, j, None].astype(jnp.float32)
        out = out + jnp.where(local[:, j, None], rows, 0.0)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def dispatch(h, order, slot, local, dtype):
    """buffer[n] = h[position of the assignment in slot n], in `dtype`
    (cast before the gather: the buffer is top_k times h)."""
    return h.astype(dtype)[order // slot.shape[1]]


def _dispatch_fwd(h, order, slot, local, dtype):
    return dispatch(h, order, slot, local, dtype), \
        (slot, local, jnp.zeros((0,), h.dtype))


def _dispatch_bwd(dtype, res, d_buf):
    slot, local, like = res
    return _gather_sum(d_buf, slot, local).astype(like.dtype), None, None, \
        None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(y, weights, order, slot, local):
    """out[p] = sum over p's local assignments of weight * y[their slot]."""
    return _gather_sum(y, slot, local, weights).astype(y.dtype)


def _combine_fwd(y, weights, order, slot, local):
    return combine(y, weights, order, slot, local), \
        (y, weights, order, slot, local)


def _combine_bwd(res, d_out):
    y, weights, order, slot, local = res
    k = slot.shape[1]
    w_slot = weights.reshape(-1)[order].astype(jnp.float32)
    d_y = (w_slot[:, None] * d_out[order // k]).astype(y.dtype)
    d_w = jnp.stack(
        [jnp.where(local[:, j],
                   jnp.sum(y[slot[:, j]].astype(jnp.float32) * d_out, -1),
                   0.0) for j in range(k)], axis=1).astype(weights.dtype)
    return d_y, d_w, None, None, None


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


def expert_layer(h, params, *, held, num_experts: int, top_k: int,
                 kernels: bool, interpret: bool = False):
    """h [P, H] -> (this chip's part of the layer's output [P, H],
    rows computed here, rows of the fullest held expert).

    `params`: router [H, E]; w_gate, w_up [len(held), H, F]; w_down
    [len(held), F, H].  `held`: the expert ids this chip holds, static."""
    grouped = _grouped(kernels, interpret)
    with jax.named_scope("moe_route"):
        top_e, weights = route(h, params["router"], top_k)
        order, slot, local, sizes = local_plan(top_e, held, num_experts)
    with jax.named_scope("moe_experts"):
        rows = dispatch(h, order, slot, local,
                        jnp.bfloat16 if kernels else h.dtype)
        gate = grouped(rows, params["w_gate"], sizes)
        up = grouped(rows, params["w_up"], sizes)
        act = (jax.nn.silu(gate) * up).astype(h.dtype)
        y = grouped(act, params["w_down"], sizes)
        out = combine(y, weights, order, slot, local).astype(h.dtype)
    return out, jnp.sum(sizes), jnp.max(sizes)
