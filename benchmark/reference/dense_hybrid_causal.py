"""The plain reference of a dense hybrid decoder whose linear layers have
negative eigenvalues (`olmo_hybrid`) trained on next-token prediction: the
equations below in straightforward jax.numpy, float32 at the
configuration's `matmul_precision` (the backend's default, as the
benchmark's other references: PERF.md section 2), loss and gradients by
autodiff.  It imports nothing of the program and takes nothing the program
made: the weights come from the weights' seed by the configuration's own
recipe, written out here.

Layer i is of the kind `layer_types[i]` names.  Every layer, x [P, C], is
post-norm (OLMo 3's block):

    h = x + rms(mixer(x); ln_mix);   x = h + rms(mlp(h); ln_mlp)
    rms(x; w) = w x / sqrt(mean(x^2) + eps);  mlp(h) = (silu(h Wg) h Wu) Wd
    linear: q, k, v, z = h W_q, h W_k, h W_v, h W_g; [q | k | v] through the
            causal convolution of 4 taps and silu; q, k L2-normalised per
            head, q times dk^-1/2; beta = 2 sigmoid(h W_b);
            g = -exp(A_log) softplus(h W_a + dt_bias);
            S_t = exp(g_t) S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T,
            o_t = S_t q_t, TOKEN BY TOKEN (`hybrid_causal.recurrence`, in
            float32 elementwise arithmetic); y = rms_head(o) w_norm silu(z);
            y W_o
    full:   q = rms(h W_q; q_norm), k = rms(h W_k; k_norm) over the whole
            width, v = h W_v; no rotary; softmax(q k^T / sqrt(D) + causal) v
            a HEAD at a time on explicit blocks of `ROW_BLOCK` query rows
            against all the keys; then W_o
    logits = rms(x; g_f) W_head

Departures, each for memory and none for the mathematics: a sequence at a
time; the recurrence's segments and the attention's blocks recomputed in
the backward pass; each layer recomputed (`jax.checkpoint`).

Planted faults, for the tests and the chip study only (`follow`'s last
arguments; each a run-time select between the sound value and the faulty
one, so that ONE compiled program serves the sound run and every fault):
`beta_sigmoid` (beta = sigmoid(b): no negative eigenvalues), `pre_norm`
(the norms on the sublayers' inputs instead of their outputs),
`rope_on_full` (rotate-half rotary, base 10,000, on the full layers' q and
k), `qk_norm_per_head` (the full layers' q and k norms over each head of
128, the gain's slice of it), and `drop_half`, `freeze` as the other
references' (`drop_half`: where a step has ONE sequence, the second half of
its predicted positions left out).  `dtype` is the lower-precision
control: the WHOLE computation in that type.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference.hybrid_causal import conv4, recurrence, tree_norms
from benchmark.reference.window_causal import default_frequencies, rotate

ROW_BLOCK = 256         # query rows worked on at a time
ROPE_THETA = 10000.0    # the fault `rope_on_full`'s base
FAULTS = ("beta_sigmoid", "pre_norm", "rope_on_full", "qk_norm_per_head")


def sizes(config: dict) -> dict:
    """The configuration's widths and share under short names."""
    return dict(
        C=config["hidden_size"], HQ=config["num_attention_heads"],
        D=config["hidden_size"] // config["num_attention_heads"],
        HK=config["linear_num_key_heads"], HV=config["linear_num_value_heads"],
        DK=config["linear_key_head_dim"], DV=config["linear_value_head_dim"],
        TAPS=config["linear_conv_kernel_dim"],
        F=config["intermediate_size"], NL=config["num_hidden_layers"],
        kinds=config["layer_types"][:config["num_hidden_layers"]],
        V=config["vocab_size"], L=config["seq_len"],
        neg=2.0 if config["linear_allow_neg_eigval"] else 1.0,
        eps=config["rms_norm_eps"])


# -- the configuration's init ------------------------------------------------

def init(config: dict, key):
    """The configuration's init (`assumed.init`).  One key per drawn leaf,
    split in the order the file states; the layers of a kind stacked by
    period, the linear ones by their place in it too."""
    z = sizes(config)
    period = z["kinds"].index("full_attention") + 1
    n, c, f = z["NL"] // period, z["C"], z["F"]
    kd, vd, hv = z["HK"] * z["DK"], z["HV"] * z["DV"], z["HV"]
    q = z["HQ"] * z["D"]

    def mlp(lead):
        return [("w_gate", lead + (c, f)), ("w_up", lead + (c, f)),
                ("w_down", lead + (f, c))]
    S, F = (n, period - 1), (n,)
    drawn = [("embed", None, (z["V"], c))]
    drawn += [("linear", name, shape) for name, shape in [
        ("w_q", S + (c, kd)), ("w_k", S + (c, kd)), ("w_v", S + (c, vd)),
        ("w_g", S + (c, vd)), ("w_b", S + (c, hv)), ("w_a", S + (c, hv)),
        ("conv", S + (z["TAPS"], 2 * kd + vd)), ("w_out", S + (vd, c)),
        ("A_log", S + (hv,))] + mlp(S)]
    drawn += [("full", name, shape) for name, shape in [
        ("wq", F + (c, q)), ("wk", F + (c, q)), ("wv", F + (c, q)),
        ("wo", F + (q, c))] + mlp(F)]
    drawn += [("head", None, (c, z["V"]))]
    w = {"linear": {}, "full": {}}
    for k, (group, name, shape) in zip(jax.random.split(key, len(drawn)),
                                       drawn):
        if name == "A_log":
            leaf = jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                              1e-3, 16.0))
        else:
            leaf = (1.0 if group == "embed" else 0.02) \
                * jax.random.normal(k, shape, jnp.float32)
        if name is None:
            w[group] = leaf
        else:
            w[group][name] = leaf
    ones = lambda *s: jnp.ones(s, jnp.float32)
    w["linear"].update(ln_mix=ones(*S, c), ln_mlp=ones(*S, c),
                       dt_bias=ones(*S, hv), gdn_norm=ones(*S, z["DV"]))
    w["full"].update(ln_mix=ones(*F, c), ln_mlp=ones(*F, c),
                     q_norm=ones(*F, q), k_norm=ones(*F, q))
    return {"embed": w["embed"],
            "periods": {"linear": w["linear"], "full": w["full"]},
            "final_norm": ones(c), "head": w["head"]}


# -- the layers --------------------------------------------------------------

def rmsnorm(x, g, eps):
    return g * x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def mlp(h, p):
    return (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]


def linear_mixer(h, p, z, beta_sigmoid=False):
    """Gated DeltaNet with negative eigenvalues: h [P, C] -> [P, C]."""
    P = h.shape[0]
    kd = z["HK"] * z["DK"]
    u = jax.nn.silu(conv4(jnp.concatenate(
        [h @ p["w_q"], h @ p["w_k"], h @ p["w_v"]], 1), p["conv"]))
    q = u[:, :kd].reshape(P, z["HK"], z["DK"])
    k = u[:, kd:2 * kd].reshape(P, z["HK"], z["DK"])
    v = u[:, 2 * kd:].reshape(P, z["HV"], z["DV"])
    gate = (h @ p["w_g"]).reshape(P, z["HV"], z["DV"])
    sig = jax.nn.sigmoid(h @ p["w_b"])
    beta = jnp.where(beta_sigmoid, sig, z["neg"] * sig)          # fault
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(h @ p["w_a"] + p["dt_bias"])
    l2 = lambda x: x * lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                                 + 1e-6)
    rep = z["HV"] // z["HK"]
    q = jnp.repeat(l2(q), rep, axis=1) / math.sqrt(z["DK"])
    k = jnp.repeat(l2(k), rep, axis=1)
    o = recurrence(q, k, v, g.astype(v.dtype), beta.astype(v.dtype))
    y = rmsnorm(o, p["gdn_norm"], z["eps"]) * jax.nn.silu(gate)
    return y.reshape(P, -1) @ p["w_out"]


def full_mixer(h, p, z, pos, rope_on_full=False, qk_norm_per_head=False):
    """Causal softmax attention without rotary, a head at a time: h [P, C]
    -> [P, C]."""
    P, D, HQ = h.shape[0], z["D"], z["HQ"]

    def qk_norm(x, gain):
        whole = rmsnorm(x, gain, z["eps"]).reshape(P, HQ, D)
        each = rmsnorm(x.reshape(P, HQ, D), gain.reshape(HQ, D), z["eps"])
        return jnp.where(qk_norm_per_head, each, whole)          # fault
    q = qk_norm(h @ p["wq"], p["q_norm"])
    k = qk_norm(h @ p["wk"], p["k_norm"])
    v = (h @ p["wv"]).reshape(P, HQ, D)
    freq = default_frequencies(D, ROPE_THETA)
    q = jnp.where(rope_on_full, rotate(q, pos, freq, 1.0), q)     # fault
    k = jnp.where(rope_on_full, rotate(k, pos, freq, 1.0), k)
    q = q / math.sqrt(D)
    rows = min(ROW_BLOCK, P)
    keys = jnp.arange(P)

    def head(qkv):
        qh, kh, vh = qkv                            # [P, D] each

        @jax.checkpoint
        def block(start):
            qb = lax.dynamic_slice_in_dim(qh, start, rows, 0)
            s = qb @ kh.T
            seen = keys[None, :] <= (start + jnp.arange(rows))[:, None]
            s = jnp.where(seen, s, -jnp.inf)
            return jax.nn.softmax(s, -1) @ vh
        return lax.map(block, jnp.arange(0, P, rows)).reshape(P, D)
    heads_first = lambda a: a.transpose(1, 0, 2)
    o = lax.map(head, (heads_first(q), heads_first(k), heads_first(v)))
    return heads_first(o).reshape(P, HQ * D) @ p["wo"]


def hidden_states(params, tokens, z, pre_norm=False, beta_sigmoid=False,
                  **faults):
    """One sequence: ids [L] -> the final states [L, C]."""
    x = params["embed"][tokens]
    pos = jnp.arange(tokens.shape[0])

    def block(sub, x, p, gain):
        """x + rms(sub(x)), or with `pre_norm` x + sub(rms(x))."""
        y = sub(jnp.where(pre_norm, rmsnorm(x, p[gain], z["eps"]), x))
        return x + jnp.where(pre_norm, y, rmsnorm(y, p[gain], z["eps"]))

    def layer(full):
        @jax.checkpoint
        def run(x, p):
            if full:
                mix = lambda h: full_mixer(h, p, z, pos, **faults)
            else:
                mix = lambda h: linear_mixer(h, p, z, beta_sigmoid)
            x = block(mix, x, p, "ln_mix")
            return block(lambda h: mlp(h, p), x, p, "ln_mlp")
        return run
    at = lambda tree, *idx: jax.tree.map(lambda a: a[idx], tree)
    period = z["kinds"].index("full_attention") + 1
    for i, kind in enumerate(z["kinds"]):
        n, place = divmod(i, period)
        if kind == "full_attention":
            x = layer(True)(x, at(params["periods"]["full"], n))
        else:
            x = layer(False)(x, at(params["periods"]["linear"], n, place))
    return rmsnorm(x, params["final_norm"], z["eps"])


def sequence_loss(params, tokens, z, first_half=False, **faults):
    """(mean next-token loss of one sequence over its L - 1 predicted
    positions, tokens predicted right).  `first_half` (the fault
    `drop_half` at one sequence a step): the mean over the first half of
    them."""
    logits = hidden_states(params, tokens, z, **faults)[:-1] @ params["head"]
    target = tokens[1:]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, target[:, None], axis=-1)[:, 0]
    n = len(target)
    kept = jnp.where(first_half, jnp.arange(n) < n // 2, True)
    return jnp.sum(jnp.where(kept, nll, 0.0)) / jnp.sum(kept), \
        jnp.sum(jnp.argmax(logits, -1) == target)


def follow(config, *, seed, weights_seed, world, per_chip_batch, train,
           heldout, steps=3, drop_half=False, freeze=False, dtype="float32",
           **faults):
    """Train `steps` steps from the seeds and evaluate; return every number
    benchmark/correct.py reads.  `train` [N, L]: the stream's first
    sequences, step k taking rows [k*world*b, (k+1)*world*b), shard r its
    b = per_chip_batch rows of those; `heldout` [M, L].  `seed` drew the
    tokens; the objective draws nothing.  `faults`: of `FAULTS`."""
    del seed
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise TypeError(f"follow: no fault {sorted(unknown)}")
    z = sizes(config)
    b = per_chip_batch
    opt = config["optimizer"]
    lr, mu, wd = config["lr"], opt["momentum"], opt["weight_decay"]
    params = jax.tree.map(lambda a: a.astype(dtype),
                          init(config, jax.random.PRNGKey(weights_seed)))
    # the initial weights and, between updates, the velocity wait on the
    # host: weights, velocity and two gradients are 15 GB at the real sizes
    p0 = jax.device_get(params)
    vel = jax.tree.map(np.zeros_like, p0)

    # every fault a traced flag: one program for the sound run and all
    flags = {name: jnp.asarray(bool(faults.get(name))) for name in FAULTS}
    flags["first_half"] = jnp.asarray(bool(drop_half and b == 1))

    @jax.jit
    def seq_grad(params, tokens, flags):
        return jax.value_and_grad(lambda p: sequence_loss(
            p, tokens, z, **flags)[0])(params)

    def sgd(params, vel, g):
        d = jax.tree.map(lambda p, gg: gg + wd * p, params, g)
        vel = jax.tree.map(lambda v, dd: mu * v + dd, vel, d)
        return jax.tree.map(lambda p, v: p - lr * v, params, vel), vel
    sgd = jax.jit(sgd, donate_argnums=(0, 1))
    add = jax.jit(lambda a, c: jax.tree.map(jnp.add, a, c),
                  donate_argnums=(0,))
    scale = jax.jit(lambda a, c: jax.tree.map(lambda x: x * c, a),
                    donate_argnums=(0,))
    out = {"loss": [], "grad_norms": []}
    for k in range(steps):
        losses, g = [], None
        for r in range(world):
            rows = jnp.asarray(train[(k * world + r) * b:
                                     (k * world + r + 1) * b])
            use = range(b // 2) if drop_half and b > 1 else range(b)
            total, g_sum = 0.0, None
            for s in use:
                loss, gs = seq_grad(params, rows[s], flags)
                total += float(loss)
                g_sum = gs if g_sum is None else add(g_sum, gs)
                del gs
            losses.append(total / len(use))
            g_sum = scale(g_sum, 1.0 / (len(use) * world))
            g = g_sum if g is None else add(g, g_sum)   # mean of the shards
            del g_sum
        out["loss"].append(sum(losses) / len(losses))
        out["grad_norms"].append(tree_norms(g))
        if not freeze:
            params, new_vel = sgd(params, jax.device_put(vel), g)
            vel = jax.device_get(new_vel)
            del new_vel
        if k == 0:
            out["momentum1_norms"] = tree_norms(vel)
        del g
    out["dparam_norms"] = tree_norms(jax.tree.map(
        lambda a, c: np.asarray(a, np.float32) - np.asarray(c, np.float32),
        jax.device_get(params), p0))

    seq_eval = jax.jit(lambda params, tokens, flags: sequence_loss(
        params, tokens, z, **flags))
    flags["first_half"] = jnp.asarray(False)    # the evaluation is whole
    loss_sum, correct = 0.0, 0
    for row in heldout:
        l, c = seq_eval(params, jnp.asarray(row), flags)
        loss_sum += float(l)
        correct += int(c)
    out["eval_loss"] = loss_sum / len(heldout)
    out["eval_correct"] = correct
    out["eval_n"] = len(heldout)
    return out
