"""One chip's share of a latent-attention mixture-of-experts decoder whose
residual is n streams wide (``model_type: xing4_0``; Xing4.0-29B-A4B's
config.json).

A position carries n = `hc_mult` streams X [n, C], all copies of its
token's embedding at the start and summed before the final norm and the
untied head.  Every layer has two sublayers, each under its own
manifold-constrained hyper-connection (ops/hyper.py: the streams are read
into one input by H_pre, the sublayer's output is written into all of them
by H_post, and the streams themselves are mixed by a doubly stochastic
H_res, 20 Sinkhorn-Knopp iterations a position):

    X = connect(h -> MLA(rmsnorm(h; ln1)), X)       ops/mla.py: latent
        attention, keys of 128 + 64 (rotary, YaRN) beside values of 128
    X = connect(h -> FFN(rmsnorm(h; ln2)), X)

The first `dense_layers` layers' FFN is a SwiGLU of `dense_width`; the
others' is the expert layer (ops/moe.py): s = sigmoid(h W_r) over ALL
experts, the top k of s + a selection bias, w = 2 s / sum s over the
chosen, this chip's held experts' part of the sum, plus one shared expert,
ungated and whole.  No bias in any projection.

What is held HERE is an argument of the factory (`Shape`: the layers, the
leading dense ones among them; the expert ids; the rows of the vocabulary),
as for the other decoders, whose `rmsnorm` and objective this model uses.
The two kinds of layer sit in two subtrees, each stacked and scanned, the
dense one first; inside a layer the sequences go one at a time, each
recomputed in the backward pass.  The carried activation is [S, n, P, C]:
streams before positions, so that every pass of a mixer is over whole
[P, C] slabs.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import attention, hyper, mla, moe
from . import qwen3next
from .sdar import rmsnorm


class Shape(NamedTuple):
    """Widths as published, and this chip's share."""
    hidden: int = 3584
    heads: int = 32
    nope_dim: int = 128                     # qk_nope_head_dim
    rope_dim: int = 64                      # qk_rope_head_dim
    v_dim: int = 128
    q_rank: int = 768
    kv_rank: int = 512
    dense_width: int = 9216
    expert_width: int = 1024
    shared_width: int = 1024                # n_shared_experts 1
    num_experts: int = 64
    top_k: int = 4
    route_scale: float = 2.0                # routed_scaling_factor
    streams: int = 4                        # hc_mult
    sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    res_clamp: Tuple[float, float] = (-30.0, 30.0)
    rope_theta: float = 1e4
    yarn_factor: float = 64.0
    yarn_original: int = 4096
    yarn_beta: Tuple[float, float] = (32.0, 1.0)    # fast, slow
    yarn_mscale: Tuple[float, float] = (1.0, 1.0)   # mscale, mscale_all_dim
    eps: float = 1e-6
    layers: int = 5                         # of 40, the dense ones among them
    dense_layers: int = 1                   # of 2 (first_k_dense_replace)
    held: Tuple[int, ...] = tuple(range(8))     # of 64: 8 chips a layer
    vocab: int = 16384                      # of 131,072: an eighth
    seq_len: int = 4096


TINY = Shape(hidden=64, heads=4, nope_dim=16, rope_dim=8, v_dim=16, q_rank=24,
             kv_rank=16, dense_width=96, expert_width=32, shared_width=32,
             num_experts=8, top_k=2, yarn_factor=4.0, yarn_original=16,
             layers=3, dense_layers=1, held=(0, 1), vocab=64, seq_len=32)

HC_BIAS_RAMP = 1.0      # b_pre rises and b_post falls over the streams
HC_PHI_STD = 0.002      # a tenth of the other matrices': see `init_params`


def hc_bias(n: int):
    """A hyper-connection's starting biases [n^2 + 2n] (pre, post, res):
    b_pre a ramp rising over the streams and b_post the same falling (the
    stream read most is written least: what a sublayer writes reaches the
    next one's input through H_res), b_res = 2 on the diagonal + (j - i):
    a positive matrix whose Sinkhorn limit is 0.71 on the diagonal, 0.096
    off it, and which one iteration leaves 0.46 from doubly stochastic."""
    ramp = HC_BIAS_RAMP * (jnp.arange(n, dtype=jnp.float32) - (n - 1) / 2)
    i = jnp.arange(n, dtype=jnp.float32)
    res = 2.0 * jnp.eye(n) + (i[None, :] - i[:, None])
    return jnp.concatenate([ramp, -ramp, res.reshape(-1)])


def init_params(key, shape: Shape):
    """Normal, std 0.02, every matrix (the router too); norm gains 1; the
    embedding's rows std 1 (a position's route follows its token, as for
    models/sdar.py); the selection bias normal 0.01, drawn once; a mixer's
    alpha 0.01, its biases `hc_bias` and its Phi normal `HC_PHI_STD` (the
    dynamic part of a mix starts small; at 0.02 the gradient of a mixer's
    three scales, 16,384 signed terms, moves by a tenth with the rounding
    of the matrix units' operands, and a leaf of three numbers shows that
    in its norm: PERF.md section 6, PR 35).  One
    key per drawn leaf, in this order: embed, the dense layers' (MLA's
    w_qa, w_qb, w_kva, w_kvb, w_o, the two Phi, then mlp_gate, mlp_up,
    mlp_down), the expert layers' (the same eight, then router,
    router_bias, w_gate, w_up, w_down, shared_gate, shared_up,
    shared_down), head."""
    s = shape
    n, c, g = s.streams, s.hidden, len(s.held)
    nd, ns = s.dense_layers, s.layers - s.dense_layers
    qk, mix = s.nope_dim + s.rope_dim, s.streams * (s.streams + 2)

    def common(lead):
        return [("w_qa", lead + (c, s.q_rank)),
                ("w_qb", lead + (s.q_rank, s.heads * qk)),
                ("w_kva", lead + (c, s.kv_rank + s.rope_dim)),
                ("w_kvb", lead + (s.kv_rank,
                                  s.heads * (s.nope_dim + s.v_dim))),
                ("w_o", lead + (s.heads * s.v_dim, c)),
                ("hc_attn", lead + (n * c, mix)),
                ("hc_mlp", lead + (n * c, mix))]
    f, fs, fd = s.expert_width, s.shared_width, s.dense_width
    dense = common((nd,)) + [
        ("mlp_gate", (nd, c, fd)), ("mlp_up", (nd, c, fd)),
        ("mlp_down", (nd, fd, c))]
    sparse = common((ns,)) + [
        ("router", (ns, c, s.num_experts)),
        ("router_bias", (ns, s.num_experts)),
        ("w_gate", (ns, g, c, f)), ("w_up", (ns, g, c, f)),
        ("w_down", (ns, g, f, c)), ("shared_gate", (ns, c, fs)),
        ("shared_up", (ns, c, fs)), ("shared_down", (ns, fs, c))]
    drawn = ([("embed", None, (s.vocab, c))]
             + [("dense", name, shp) for name, shp in dense]
             + [("sparse", name, shp) for name, shp in sparse]
             + [("head", None, (c, s.vocab))])
    keys = jax.random.split(key, len(drawn))
    out = {"dense": {}, "sparse": {}}
    own_std = {"router_bias": 0.01, "hc_attn": HC_PHI_STD,
               "hc_mlp": HC_PHI_STD}
    ones = lambda *shp: jnp.ones(shp, jnp.float32)
    for k, (group, name, shp) in zip(keys, drawn):
        std = 1.0 if group == "embed" else own_std.get(name, 0.02)
        leaf = std * jax.random.normal(k, shp, jnp.float32)
        if name is None:
            out[group] = leaf
        elif name.startswith("hc_"):
            lead = shp[0]
            out[group][name] = {
                "phi": leaf, "alpha": jnp.full((lead, 3), 0.01, jnp.float32),
                "bias": jnp.tile(hc_bias(n), (lead, 1))}
        else:
            out[group][name] = leaf
    for group, lead in (("dense", nd), ("sparse", ns)):
        out[group].update(ln1=ones(lead, c), ln2=ones(lead, c),
                          q_norm=ones(lead, s.q_rank),
                          kv_norm=ones(lead, s.kv_rank))
    return {"embed": out["embed"], "dense": out["dense"],
            "sparse": out["sparse"], "final_norm": ones(c),
            "head": out["head"]}


def _mlp_dense(h, p):
    """The leading layers' feed-forward: W_d (silu(W_g h) * (W_u h));
    no routed rows."""
    with jax.named_scope("mlp_dense"):
        dot = lambda x, name: jnp.dot(x, p[name].astype(x.dtype))
        y = dot(jax.nn.silu(dot(h, "mlp_gate")) * dot(h, "mlp_up"),
                "mlp_down")
    return y, (jnp.int32(0),) * 3


def _mlp_experts(shape: Shape, kernels: bool, h, p):
    """An expert layer's feed-forward -> (y, (rows computed by this chip's
    experts, rows of its fullest expert, rows of the dropless buffer
    touched))."""
    s = shape
    y, rows, fullest = moe.expert_layer(
        h, p, held=s.held, num_experts=s.num_experts, top_k=s.top_k,
        kernels=kernels, scoring=functools.partial(
            moe.route_sigmoid, scale=s.route_scale))
    return y + moe.shared_expert_ungated(h, p), (
        rows, fullest, moe.prefix_rows(rows, h.shape[0] * s.top_k, kernels))


def _layer(shape: Shape, kernels: bool, feed_forward, rotary, x, p):
    """One sequence through one layer: x [n, P, C] -> (x, the feed-forward's
    three counts, the larger `res_gap` of the layer's two mixers)."""
    s = shape
    hc = dict(iters=s.sinkhorn_iters, eps=s.hc_eps, clamp=s.res_clamp,
              kernels=kernels)
    norm = lambda a, gain: rmsnorm(a, gain, s.eps)
    positions, inv_freq, scale, attn_factor = rotary

    def attend(h):
        return mla.latent_attention(
            norm(h, p["ln1"]), p, heads=s.heads, nope=s.nope_dim,
            rope=s.rope_dim, v_dim=s.v_dim, kv_rank=s.kv_rank, norm=norm,
            positions=positions, inv_freq=inv_freq, scale=scale,
            attn_factor=attn_factor, kernels=kernels), ()
    x, _, gap_a = hyper.connect(attend, x, p["hc_attn"], **hc)
    x, counts, gap_f = hyper.connect(
        lambda h: feed_forward(norm(h, p["ln2"]), p), x, p["hc_mlp"], **hc)
    return x, counts, jnp.maximum(gap_a, gap_f)


def make(shape: Shape = Shape(), kernels=None):
    """(init_fn, apply_fn) for one share.  `kernels`: the Pallas attention,
    grouped-matmul and hyper-connection kernels; None = wherever the
    default backend is a TPU (a deviceless compile for a described TPU
    passes True)."""
    s = shape
    if not 0 <= s.dense_layers <= s.layers or s.rope_dim % 2:
        raise ValueError(
            f"xing4: {s.dense_layers} leading dense layers of {s.layers}, "
            f"a rotary part of {s.rope_dim}")
    fast, slow = s.yarn_beta
    mscale, mscale_all = s.yarn_mscale
    inv_freq = mla.yarn_inv_freq(s.rope_dim, s.rope_theta, s.yarn_factor,
                                 s.yarn_original, fast, slow)
    scale = mla.softmax_scale(s.nope_dim + s.rope_dim, s.yarn_factor,
                              mscale_all)
    attn_factor = mla.yarn_mscale(s.yarn_factor, mscale) \
        / mla.yarn_mscale(s.yarn_factor, mscale_all)

    def init_fn(key):
        return init_params(key, s), {}

    def resolved() -> bool:
        return jax.default_backend() == "tpu" if kernels is None else kernels

    def apply_fn(params, bn_state, x, train=True, compute_dtype=None):
        """x: token ids [S, L].  Returns (hidden [S, L, C]: the streams'
        sum after the final norm, {}, (rows computed by this chip's experts
        summed over layers, rows of the fullest held expert of any layer,
        rows of the dropless buffers touched, the largest `res_gap` of any
        mixer))."""
        del train                       # no dropout, no batch statistics
        on_tpu = resolved()
        h = params["embed"][x]
        if compute_dtype is not None:
            h = h.astype(compute_dtype)
        # every stream starts as the embedding
        h = jnp.broadcast_to(h[:, None], (h.shape[0], s.streams) + h.shape[1:])
        rotary = (jnp.arange(x.shape[1]), inv_freq, scale, attn_factor)

        def stack(feed_forward):
            def layer(x, p):
                # one sequence at a time, each recomputed in the backward
                # pass (models/sdar.py `layer`); ONE checkpoint a layer
                x, (rows, fullest, touched), gap = lax.map(jax.checkpoint(
                    lambda x_seq: _layer(s, on_tpu, feed_forward, rotary,
                                         x_seq, p)), x)
                return x, (jnp.sum(rows), jnp.max(fullest), jnp.sum(touched),
                           jnp.max(gap))
            return layer
        h, dense = lax.scan(stack(_mlp_dense), h, params["dense"])
        h, sparse = lax.scan(
            stack(functools.partial(_mlp_experts, s, on_tpu)), h,
            params["sparse"])
        rows, fullest, touched, gap = (
            jnp.concatenate([a, b]) for a, b in zip(dense, sparse))
        hidden = rmsnorm(jnp.sum(h, axis=1), params["final_norm"], s.eps)
        return hidden, bn_state, (jnp.sum(rows), jnp.max(fullest),
                                  jnp.sum(touched), jnp.max(gap))

    apply_fn.objective = NextToken(s, resolved)
    return init_fn, apply_fn


class NextToken(qwen3next.NextToken):
    """Next-token prediction (models/qwen3next.py `NextToken`: the loss, the
    evaluation, nothing drawn) with this model's columns of the metric
    ring, `mhc_res_gap` a maximum among them, and its gauges."""

    extras = qwen3next.NextToken.extras + (("mhc_res_gap", "max"),)

    def __init__(self, shape: Shape, kernels=lambda: False):
        super().__init__(shape, kernels)
        self.per_example = {"moe_rows_expected": (
            shape.seq_len * shape.top_k * len(shape.held)
            * (shape.layers - shape.dense_layers) / shape.num_experts)}

    @functools.cached_property
    def gauges(self):
        """Whether the attention runs as the Pallas kernels (1) or as
        jax.numpy (0), its key and value sizes, what the causal kernels'
        tiles cost at this length, the residual's width and its
        iterations, whether the hyper-connections run as theirs (float32
        streams: what `apply_fn` carries unless told a compute dtype) and
        on which tile of positions: a recorder's, once."""
        s = self.shape
        tile = hyper.plan((s.streams, self.seq_len, s.hidden), jnp.float32,
                          self.kernels())
        return [("mla_kernel", int(self.kernels()), {}),
                ("mla_qk_dim", s.nope_dim + s.rope_dim, {}),
                ("mla_v_dim", s.v_dim, {}),
                ("mhc_streams", s.streams, {}),
                ("mhc_sinkhorn_iters", s.sinkhorn_iters, {}),
                ("mhc_kernel", int(tile > 0), {}),
                ("mhc_tile", tile, {})] \
            + attention.causal_tile_gauges(self.seq_len)

    def loss(self, apply_fn, params, bn_state, x, labels=None,
             compute_dtype=None):
        (loss, _, count), new_bn, (rows, fullest, touched, gap) = \
            self._counts(apply_fn, params, bn_state, x, compute_dtype)
        extras = (rows.astype(jnp.float32), fullest.astype(jnp.float32),
                  jnp.sum(count).astype(jnp.float32),
                  touched.astype(jnp.float32), gap.astype(jnp.float32))
        return jnp.mean(loss), (new_bn, extras)
