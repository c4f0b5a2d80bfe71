"""The plain reference of a mixture-of-experts decoder with sliding-window
and full softmax layers (`mellum`) trained on next-token prediction: the
equations below in straightforward jax.numpy, float32 at the
configuration's `matmul_precision` (the backend's default, as the
benchmark's other references: PERF.md section 2), loss and gradients by
autodiff.  It imports nothing of the program and takes nothing the program
made: the weights come from the weights' seed by the configuration's own
recipe, written out here.

Layer i is of the kind `layer_types[i]` names.  Every layer, x [P, C]:

    x = x + attn(rms(x; ln1));   x = x + moe(rms(x; ln2))
    rms(x; w) = w x / sqrt(mean(x^2) + eps)
    attn: q = h W_q, k = h W_k, v = h W_v, heads of D; q and k turned by
          rotate-half over the whole head, the pair (i, i + D/2) by
          position x f_i, cos and sin times a factor; a query head a uses
          the key/value head a // (heads / kv_heads);
          sliding: f_i = base^(-2i/D), factor 1, query i sees the keys j
                   with i - window < j <= i
          full:    f_i YaRN's (`yarn_frequencies`), factor attention_factor,
                   query i sees the keys j <= i
          softmax(q k^T / sqrt(D) + mask) v, then W_o
    moe:  softmax over ALL experts, the top k renormalised, a LOOP over the
          experts held here; no shared expert
    logits = rms(x; g_f) W_head

Attention is computed a HEAD at a time on explicit blocks of `ROW_BLOCK`
query rows against ALL the keys, the mask a dense band or a dense triangle
over them, where the program hands all heads to a kernel that visits the
band's tiles alone.

Departures, each for memory and none for the mathematics: a sequence at a
time; attention in blocks; each layer recomputed in the backward pass
(`jax.checkpoint`); what the experts held elsewhere would add is left out,
as the configuration's share says.

Planted faults, for the tests and the chip study only (`follow`'s last
arguments; each a run-time select between the sound value and the faulty
one, so that ONE compiled program serves the sound run and every fault):
`no_window` (the sliding layers causal), `yarn_on_all` (YaRN's frequencies
and factor on the sliding layers too), `no_yarn` (the default frequencies
on the full layers), `no_attn_factor` (the full layers' factor 1), and
`drop_half`, `freeze` as the other references' (`drop_half`: half of every
step's sequences left out; where a step has ONE sequence, the second half
of its predicted positions).  `dtype` is the lower-precision control: the
WHOLE computation in that type.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

ROW_BLOCK = 256         # query rows worked on at a time
FAULTS = ("no_window", "yarn_on_all", "no_yarn", "no_attn_factor")


def sizes(config: dict) -> dict:
    """The configuration's widths and share under short names."""
    rp = config["rope_parameters"]
    full, sliding = rp["full_attention"], rp["sliding_attention"]
    return dict(
        C=config["hidden_size"], HQ=config["num_attention_heads"],
        HKV=config["num_key_value_heads"], D=config["head_dim"],
        F=config["moe_intermediate_size"], K=config["num_experts_per_tok"],
        E=config["published"]["num_experts"], held=config["experts_held"],
        NL=config["num_hidden_layers"],
        kinds=config["layer_types"][:config["num_hidden_layers"]],
        V=config["vocab_size"], L=config["seq_len"],
        W=config["sliding_window"], eps=config["rms_norm_eps"],
        theta_sliding=float(sliding["rope_theta"]),
        theta=float(full["rope_theta"]), factor=float(full["factor"]),
        original=full["original_max_position_embeddings"],
        beta_fast=full["beta_fast"], beta_slow=full["beta_slow"],
        attn_factor=float(full["attention_factor"]))


# -- the configuration's init ------------------------------------------------

def init(config: dict, key):
    """The configuration's init (`assumed.init`).  One key per drawn leaf,
    split in the order the file states; the layers of a kind stacked by
    period, the sliding ones by their place in it too."""
    z = sizes(config)
    period = z["kinds"].index("full_attention") + 1
    n, c, g = z["NL"] // period, z["C"], len(z["held"])
    q, kv = z["HQ"] * z["D"], z["HKV"] * z["D"]

    def layer(lead):
        return [("wq", lead + (c, q)), ("wk", lead + (c, kv)),
                ("wv", lead + (c, kv)), ("wo", lead + (q, c)),
                ("router", lead + (c, z["E"])),
                ("w_gate", lead + (g, c, z["F"])),
                ("w_up", lead + (g, c, z["F"])),
                ("w_down", lead + (g, z["F"], c))]
    S, F = (n, period - 1), (n,)
    drawn = [("embed", None, (z["V"], c))]
    drawn += [("sliding", name, shape) for name, shape in layer(S)]
    drawn += [("full", name, shape) for name, shape in layer(F)]
    drawn += [("head", None, (c, z["V"]))]
    w = {"sliding": {}, "full": {}}
    for k, (group, name, shape) in zip(jax.random.split(key, len(drawn)),
                                       drawn):
        leaf = (1.0 if group == "embed" else 0.02) \
            * jax.random.normal(k, shape, jnp.float32)
        if name is None:
            w[group] = leaf
        else:
            w[group][name] = leaf
    ones = lambda *s: jnp.ones(s, jnp.float32)
    for group, lead in (("sliding", S), ("full", F)):
        w[group].update(ln1=ones(*lead, c), ln2=ones(*lead, c))
    return {"embed": w["embed"],
            "periods": {"sliding": w["sliding"], "full": w["full"]},
            "final_norm": ones(c), "head": w["head"]}


# -- the layers --------------------------------------------------------------

def rmsnorm(x, g, eps):
    return g * x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def default_frequencies(d, theta):
    return np.asarray([theta ** (-2.0 * i / d) for i in range(d // 2)],
                      np.float32)


def yarn_frequencies(d, z):
    """The d / 2 rotary frequencies by hand: f_i = base^(-2i/d); dim(r) =
    d ln(original / (2 pi r)) / (2 ln base); low = floor(dim(beta_fast)),
    high = ceil(dim(beta_slow)), clamped to [0, d - 1]; ramp_i = clip((i -
    low) / (high - low), 0, 1); (f_i / factor) ramp_i + f_i (1 - ramp_i)."""
    dim = lambda r: d * math.log(z["original"] / (2 * math.pi * r)) \
        / (2 * math.log(z["theta"]))
    low = max(math.floor(dim(z["beta_fast"])), 0)
    high = min(math.ceil(dim(z["beta_slow"])), d - 1)
    out = []
    for i in range(d // 2):
        f = z["theta"] ** (-2.0 * i / d)
        ramp = min(max((i - low) / ((high - low) or 0.001), 0.0), 1.0)
        out.append(f / z["factor"] * ramp + f * (1.0 - ramp))
    return np.asarray(out, np.float32)


def rotate(x, pos, freq, factor):
    """x [P, heads, d]: the pair (i, i + d/2) turned by pos x freq_i, cos
    and sin times `factor`."""
    d = x.shape[-1]
    ang = pos.astype(jnp.float32)[:, None] * freq[None, :]
    cos = (jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
           * factor)[:, None, :].astype(x.dtype)
    sin = (jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
           * factor)[:, None, :].astype(x.dtype)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def attention(h, p, z, pos, full, no_window=False, yarn_on_all=False,
              no_yarn=False, no_attn_factor=False):
    """h [P, C] -> [P, C], a head at a time.  `full`: a full layer (static);
    the faults are traced flags."""
    P, D = h.shape[0], z["D"]
    q = (h @ p["wq"]).reshape(P, z["HQ"], D)
    k = (h @ p["wk"]).reshape(P, z["HKV"], D)
    v = (h @ p["wv"]).reshape(P, z["HKV"], D)
    yarn = jnp.asarray(yarn_frequencies(D, z))
    plain = jnp.asarray(default_frequencies(
        D, z["theta"] if full else z["theta_sliding"]))
    if full:
        freq = jnp.where(no_yarn, plain, yarn)                  # fault
        factor = jnp.where(no_attn_factor, 1.0, z["attn_factor"])  # fault
    else:
        freq = jnp.where(yarn_on_all, yarn, plain)              # fault
        factor = jnp.where(yarn_on_all, z["attn_factor"], 1.0)
    q = rotate(q, pos, freq, factor) / math.sqrt(D)
    k = rotate(k, pos, freq, factor)
    group = z["HQ"] // z["HKV"]
    rows = min(ROW_BLOCK, P)
    keys = jnp.arange(P)
    # the window's lower edge; a causal layer (or the fault) has none
    reach = P if full else jnp.where(no_window, P, z["W"])

    def head(qkv):
        qh, kh, vh = qkv                            # [P, D] each

        @jax.checkpoint
        def block(start):
            qb = lax.dynamic_slice_in_dim(qh, start, rows, 0)
            s = qb @ kh.T
            i = (start + jnp.arange(rows))[:, None]
            seen = (keys[None, :] <= i) & (keys[None, :] > i - reach)
            s = jnp.where(seen, s, -jnp.inf)
            return jax.nn.softmax(s, -1) @ vh
        return lax.map(block, jnp.arange(0, P, rows)).reshape(P, D)
    heads_first = lambda a: a.transpose(1, 0, 2)
    o = lax.map(head, (heads_first(q),
                       heads_first(jnp.repeat(k, group, axis=1)),
                       heads_first(jnp.repeat(v, group, axis=1))))
    return heads_first(o).reshape(P, z["HQ"] * D) @ p["wo"]


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def experts(h, p, z):
    """h [P, C] -> this share's part of the routed experts' sum."""
    probs = jax.nn.softmax(h @ p["router"], axis=-1)        # all E experts
    top_p, top_e = lax.top_k(probs, z["K"])
    w = top_p / jnp.sum(top_p, -1, keepdims=True)
    out = jnp.zeros_like(h)
    for g, e in enumerate(z["held"]):
        weight = jnp.sum(jnp.where(top_e == e, w, 0.0), -1)  # 0 if not chosen
        out = out + weight[:, None].astype(h.dtype) * swiglu(
            h, p["w_gate"][g], p["w_up"][g], p["w_down"][g])
    return out


def hidden_states(params, tokens, z, **faults):
    """One sequence: ids [L] -> the final states [L, C]."""
    x = params["embed"][tokens]
    pos = jnp.arange(tokens.shape[0])

    def layer(full):
        @jax.checkpoint
        def run(x, p):
            x = x + attention(rmsnorm(x, p["ln1"], z["eps"]), p, z, pos,
                              full, **faults)
            return x + experts(rmsnorm(x, p["ln2"], z["eps"]), p, z)
        return run
    at = lambda tree, *idx: jax.tree.map(lambda a: a[idx], tree)
    period = z["kinds"].index("full_attention") + 1
    for i, kind in enumerate(z["kinds"]):
        n, place = divmod(i, period)
        if kind == "full_attention":
            x = layer(True)(x, at(params["periods"]["full"], n))
        else:
            x = layer(False)(x, at(params["periods"]["sliding"], n, place))
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


def tree_norms(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(k): float(jnp.sqrt(jnp.sum(jnp.square(
        jnp.asarray(v, jnp.float32))))) for k, v in flat}


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
    # host: weights, velocity and two gradients are 10 GB at the real sizes
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
