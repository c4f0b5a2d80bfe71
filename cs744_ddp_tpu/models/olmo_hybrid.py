"""One chip's share of a dense hybrid decoder whose linear layers have
negative eigenvalues (``model_type: olmo_hybrid``; Olmo-Hybrid-7B's
config.json).

Layers in the published pattern ``linear, linear, linear, full``
(`layer_types`), no experts anywhere.  Every layer is post-norm (OLMo 3's
block: no norm on a sublayer's input), for a residual stream x [P, H]:

    h = x + rms(mixer(x); ln_mix)        x = h + rms(mlp(h); ln_mlp)
    rms(x; w) = x * rsqrt(mean(x^2) + eps) * w                 w starts at 1
    mlp(h) = (silu(h W_gate) * h W_up) W_down                  (`mlp_dense`)

then ``rms(x; final)`` and an untied head.  No bias anywhere.

  linear mixer (Gated DeltaNet, ops/gdn.py `mixer`), heads of 96 (keys) and
    192 (values), a key head to a value head:
    q, k, v, z = h W_q, h W_k, h W_v, h W_g    (z: the output gate)
    [q | k | v] = silu(causal depthwise conv, 4 taps, of [q | k | v])
    q, k L2-normalised over the head, q scaled by dk^-1/2
    beta = 2 sigmoid(h W_b): the transition I - beta k k^T has eigenvalues
    in (-1, 1) (`linear_allow_neg_eigval`)
    g = -exp(A_log) * softplus(h W_a + dt_bias)
    o = the gated delta rule; y = (rms_head(o) * w_norm) * silu(z); y W_out

  full mixer (ops/attention.py `causal_attention`): no rotary (NoPE:
    `rope_theta` null), q = rms(h W_q; q_norm) and k = rms(h W_k; k_norm)
    over the whole width (not per head), v = h W_v; 30 heads of 128,
    o = softmax(q k^T / sqrt(128) + causal) v; o W_o, no output gate.

The leaves keep the published shapes; the mixer's projection is their
concatenation, q's and k's columns each followed by zeros to whole lanes
(`gdn.key_stride`: 2,880 -> 2,944), so that the front's kernels find every
part on a lane.  What is held HERE is an argument of the factory (`Shape`:
the layers, whole periods in the published order; the rows of the
vocabulary), as for the other decoders; the parameters of the two kinds sit
in two subtrees stacked by period (models/qwen3next.py's structure).
Inside a layer the sequences go one at a time, and each half of a layer
(the mixer and its norm, the MLP and its norm) is recomputed in the
backward pass under a checkpoint of its own (a full layer's keeps its
attention kernels' output, models/mellum2.py).  Training is next-token
prediction; the step's extras
count how many of the linear layers' betas pass 1 (`gdn_beta_over_one` of
`gdn_beta_entries`): the share of negative eigenvalues.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import attention, gdn
from . import qwen3next
from .sdar import rmsnorm


class Shape(NamedTuple):
    """Widths as published, and this chip's share."""
    hidden: int = 3840
    heads: int = 30                         # query and key/value heads
    head_dim: int = 128                     # hidden / heads
    lin_key_heads: int = 30
    lin_value_heads: int = 30
    lin_key_dim: int = 96
    lin_value_dim: int = 192
    conv_taps: int = 4
    mlp_width: int = 11008                  # intermediate_size
    beta_scale: float = 2.0                 # linear_allow_neg_eigval
    eps: float = 1e-6
    interval: int = 4                       # three linear, then one full
    layers: int = 4                         # of 32: one period
    vocab: int = 12544                      # of 100,352: an eighth
    seq_len: int = 8192


TINY = Shape(hidden=64, heads=4, head_dim=16, lin_key_heads=2,
             lin_value_heads=2, lin_key_dim=8, lin_value_dim=16,
             mlp_width=96, layers=4, vocab=64, seq_len=32)


def init_params(key, shape: Shape):
    """Normal, std 0.02, every matrix (the convolution's taps too); norm
    gains 1; A_log = log(U(0.001, 16)), dt_bias 1; the embedding's rows std
    1 (models/qwen3next.py's recipe).  One key per drawn leaf, in this
    order: embed, the linear layers' (w_q, w_k, w_v, w_g, w_b, w_a, conv,
    w_out, A_log, w_gate, w_up, w_down), the full layers' (wq, wk, wv, wo,
    w_gate, w_up, w_down), head."""
    s = shape
    n, h, f = s.layers // s.interval, s.hidden, s.mlp_width
    hv = s.lin_value_heads
    kd, vd = s.lin_key_heads * s.lin_key_dim, hv * s.lin_value_dim
    q = s.heads * s.head_dim

    def mlp(lead):
        return [("w_gate", lead + (h, f)), ("w_up", lead + (h, f)),
                ("w_down", lead + (f, h))]
    L, F = (n, s.interval - 1), (n,)
    linear = [("w_q", L + (h, kd)), ("w_k", L + (h, kd)),
              ("w_v", L + (h, vd)), ("w_g", L + (h, vd)),
              ("w_b", L + (h, hv)), ("w_a", L + (h, hv)),
              ("conv", L + (s.conv_taps, 2 * kd + vd)),
              ("w_out", L + (vd, h)), ("A_log", L + (hv,))] + mlp(L)
    full = [("wq", F + (h, q)), ("wk", F + (h, q)), ("wv", F + (h, q)),
            ("wo", F + (q, h))] + mlp(F)
    drawn = ([("embed", None, (s.vocab, h))]
             + [("linear", name, shp) for name, shp in linear]
             + [("full", name, shp) for name, shp in full]
             + [("head", None, (h, s.vocab))])
    out = {"linear": {}, "full": {}}
    for k, (group, name, shp) in zip(jax.random.split(key, len(drawn)),
                                     drawn):
        if name == "A_log":
            leaf = jnp.log(jax.random.uniform(k, shp, jnp.float32, 1e-3, 16.0))
        else:
            leaf = (1.0 if group == "embed" else 0.02) \
                * jax.random.normal(k, shp, jnp.float32)
        if name is None:
            out[group] = leaf
        else:
            out[group][name] = leaf
    ones = lambda *shp: jnp.ones(shp, jnp.float32)
    out["linear"].update(ln_mix=ones(*L, h), ln_mlp=ones(*L, h),
                         dt_bias=ones(*L, hv),
                         gdn_norm=ones(*L, s.lin_value_dim))
    out["full"].update(ln_mix=ones(*F, h), ln_mlp=ones(*F, h),
                       q_norm=ones(*F, q), k_norm=ones(*F, q))
    return {"embed": out["embed"],
            "periods": {"linear": out["linear"], "full": out["full"]},
            "final_norm": ones(h), "head": out["head"]}


def _mlp(h, p):
    with jax.named_scope("mlp_dense"):
        w = lambda name: p[name].astype(h.dtype)
        return jnp.dot(jax.nn.silu(jnp.dot(h, w("w_gate"))) * jnp.dot(
            h, w("w_up")), w("w_down"))


def _lanes(w, width: int):
    """w [.., C] followed by zeros to `width` columns."""
    return jnp.pad(w, ((0, 0),) * (w.ndim - 1) + ((0, width - w.shape[-1]),))


def _linear_mixer(shape: Shape, kernels: bool, x, p):
    """One sequence through a linear layer's mixer half: x [P, H] -> (x +
    rms(mixer(x)), betas over 1, betas)."""
    s = shape
    kd = s.lin_key_heads * s.lin_key_dim
    stride = gdn.key_stride(kd)
    conv = p["conv"]
    front = dict(p, conv=jnp.concatenate(
        [_lanes(conv[:, :kd], stride), _lanes(conv[:, kd:2 * kd], stride),
         conv[:, 2 * kd:]], 1))
    with jax.named_scope("attn_gdn"):
        w_qkvz = jnp.concatenate([_lanes(p["w_q"], stride),
                                  _lanes(p["w_k"], stride), p["w_v"],
                                  p["w_g"]], 1)
        w_ba = jnp.concatenate([p["w_b"], p["w_a"]], 1)
    y, beta = gdn.mixer(
        x, w_qkvz, w_ba, front, key_heads=s.lin_key_heads,
        value_heads=s.lin_value_heads, dk=s.lin_key_dim, dv=s.lin_value_dim,
        eps=s.eps, beta_scale=s.beta_scale, kernels=kernels)
    return (x + rmsnorm(y, p["ln_mix"], s.eps), jnp.sum(beta > 1.0),
            jnp.asarray(beta.size, jnp.int32))


def _full_mixer(shape: Shape, kernels: bool, h, p):
    """h [P, H] -> causal softmax attention without rotary [P, H]."""
    s = shape
    P, D = h.shape[0], s.head_dim
    proj = lambda w: jnp.dot(h, p[w].astype(h.dtype))
    q = rmsnorm(proj("wq"), p["q_norm"], s.eps).reshape(P, s.heads, D)
    k = rmsnorm(proj("wk"), p["k_norm"], s.eps).reshape(P, s.heads, D)
    v = proj("wv").reshape(P, s.heads, D)
    q = q * jnp.asarray(D ** -0.5, q.dtype)
    # heads first, in the kernels' operand type (models/mellum2.py)
    core = jnp.bfloat16 if kernels else h.dtype
    lay = lambda a: a.transpose(1, 0, 2)[None].astype(core)
    o = attention.causal_attention(lay(q), lay(k), lay(v), kernels=kernels)
    o = o[0].transpose(1, 0, 2).astype(h.dtype)
    return jnp.dot(o.reshape(P, s.heads * D), p["wo"].astype(h.dtype))


def _full_attention(shape: Shape, kernels: bool, x, p):
    """One sequence through a full layer's attention half: x [P, H] -> (x +
    rms(attention(x)), 0, 0)."""
    s = shape
    zero = jnp.zeros((), jnp.int32)
    return (x + rmsnorm(_full_mixer(s, kernels, x, p), p["ln_mix"], s.eps),
            zero, zero)


def _mlp_half(shape: Shape, x, p):
    """A layer's MLP half: x [P, H] -> x + rms(mlp(x))."""
    return x + rmsnorm(_mlp(x, p), p["ln_mlp"], shape.eps)


def make(shape: Shape = Shape(), kernels=None):
    """(init_fn, apply_fn) for one share.  `kernels`: the Pallas attention
    and delta-rule kernels; None = wherever the default backend is a TPU (a
    deviceless compile for a described TPU passes True)."""
    s = shape
    if s.layers % s.interval or s.lin_value_heads % s.lin_key_heads:
        raise ValueError(
            f"olmo_hybrid: layers {s.layers} / interval {s.interval} (whole "
            f"periods), linear value heads {s.lin_value_heads} / key heads "
            f"{s.lin_key_heads}")

    def init_fn(key):
        return init_params(key, s), {}

    def resolved() -> bool:
        return jax.default_backend() == "tpu" if kernels is None else kernels

    def apply_fn(params, bn_state, x, train=True, compute_dtype=None):
        """x: token ids [S, L].  Returns (hidden [S, L, H] after the final
        norm, {}, (the linear layers' betas over 1, their betas: sums over
        the layers, positions and heads))."""
        del train                       # no dropout, no batch statistics
        on_tpu = resolved()
        h = params["embed"][x]
        if compute_dtype is not None:
            h = h.astype(compute_dtype)

        def layer(mixer, policy=None):
            def run(x, p):
                # one sequence at a time, each half of the layer recomputed
                # in the backward pass under a checkpoint of its own: the
                # mixer's and the MLP's temporaries (0.5 and 1.0 GB at the
                # published widths) are then never live together, for one
                # more [P, H] kept a layer (PERF.md section 4)
                def one(x_seq):
                    h, over, entries = jax.checkpoint(
                        lambda x: mixer(s, on_tpu, x, p), policy=policy)(x_seq)
                    return jax.checkpoint(
                        lambda h: _mlp_half(s, h, p))(h), over, entries
                x, over, entries = lax.map(one, x)
                return x, jnp.stack([jnp.sum(over), jnp.sum(entries)])
            return run

        def period(x, p):
            x, lin = lax.scan(layer(_linear_mixer), x, p["linear"])
            x, last = layer(_full_attention, attention.KEEP_ATTN_RESIDUALS)(
                x, p["full"])
            return x, jnp.concatenate([lin, last[None]])
        h, counts = lax.scan(period, h, params["periods"])
        counts = counts.reshape(-1, 2)          # a row a layer
        hidden = rmsnorm(h, params["final_norm"], s.eps)
        return hidden, bn_state, (jnp.sum(counts[:, 0]),
                                  jnp.sum(counts[:, 1]))

    apply_fn.objective = NextToken(s, resolved)
    return init_fn, apply_fn


class NextToken(qwen3next.NextToken):
    """Next-token prediction (models/qwen3next.py `NextToken`: the loss, the
    evaluation, nothing drawn) with this model's extras, no expert counters,
    and its gauges."""

    extras = (("tokens_predicted", "sum"), ("gdn_beta_over_one", "sum"),
              ("gdn_beta_entries", "sum"))

    def __init__(self, shape: Shape, kernels=lambda: False):
        self.shape, self.kernels = shape, kernels
        self.seq_len, self.vocab = shape.seq_len, shape.vocab
        self.example_shape = (shape.seq_len,)
        self.per_example = {}

    @functools.cached_property
    def gauges(self):
        """Which form of the recurrence runs (1 the Pallas kernels, 0 the
        jax.numpy one) and its chunking; which form of the front, and its
        kernels' tile of positions (0: the jax.numpy front), for a float32
        projection; the diagonal block the chunks' inversion starts from
        (both forms'); what the causal kernels' tiles cost at this length
        (the TPU's path): a recorder's, once."""
        s = self.shape
        fused, c = gdn.plan(self.seq_len, s.lin_key_dim, s.lin_value_dim,
                            self.kernels())
        tile = gdn.front_plan(self.seq_len, s.lin_key_dim,
                              s.lin_value_heads * s.lin_value_dim,
                              jnp.float32, self.kernels())
        return [("gdn_kernel", int(fused), {}), ("gdn_chunk", c, {}),
                ("gdn_chunks_per_sequence", -(-self.seq_len // c), {}),
                ("gdn_front_kernel", int(tile > 0), {}),
                ("gdn_front_tile", tile, {}),
                ("gdn_inverse_block", gdn.INVERSE_BLOCK, {})] \
            + attention.causal_tile_gauges(self.seq_len)

    def loss(self, apply_fn, params, bn_state, x, labels=None,
             compute_dtype=None):
        """-> (mean over the sequences of the per-sequence loss,
        (new_bn, extras))."""
        (loss, _, count), new_bn, (over, entries) = self._counts(
            apply_fn, params, bn_state, x, compute_dtype)
        extras = (jnp.sum(count).astype(jnp.float32),
                  over.astype(jnp.float32), entries.astype(jnp.float32))
        return jnp.mean(loss), (new_bn, extras)
