"""The plain reference of a hybrid linear-attention mixture-of-experts
decoder (`qwen3_next`) trained on next-token prediction: the equations of
ISSUE 33 in straightforward jax.numpy, float32 at the backend's default
matmul precision (what the configuration states, the benchmark's practice:
PERF.md section 2), loss and gradients by autodiff.  It imports nothing of
the program and takes nothing the program made: the weights come from the
weights' seed by the configuration's own recipe, written out here.

Layer i (0-based) is full attention iff (i + 1) % full_attention_interval
== 0, else linear attention (Gated DeltaNet).  The linear layers'
recurrence is computed TOKEN BY TOKEN, as the description writes it:

    S = exp(g_t) S;  r = k_t^T S;  S = S + k_t (beta_t (v_t - r))^T;
    o_t = q_t^T S

in float32 elementwise arithmetic (no matrix unit, so no operand is
rounded), where the program computes it in chunks with a triangular solve.

Departures from the published description, each for memory and none for
the mathematics: a sequence at a time; the recurrence's backward pass
recomputes segments of `SEGMENT` positions (the state of every position
would be 17 GB a layer); attention a block of query rows at a time; each
layer recomputed in the backward pass (`jax.checkpoint`); what the experts
held elsewhere would add is left out, as the configuration's share says,
and the shared expert is computed whole.

Planted faults, for the tests and the chip study only (`follow`'s last
arguments): `no_decay` (g = 0: the state never forgets), `no_shared_gate`
(the shared expert's sigmoid gate left out), `drop_half` (half of every
step's sequences left out), `freeze` (a step that leaves its state as it
was).  `dtype` is the lower-precision control: the WHOLE computation in
that type (`bfloat16`: weights, activations, the recurrent state, gradients
and the optimizer's state), which the configuration's float32 must be told
from.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

ROW_BLOCK = 256         # query rows worked on at a time
SEGMENT = 64            # positions of the recurrence recomputed together


def sizes(config: dict) -> dict:
    """The configuration's widths and share under short names."""
    return dict(
        H=config["hidden_size"], HQ=config["num_attention_heads"],
        HKV=config["num_key_value_heads"], D=config["head_dim"],
        R=int(config["head_dim"] * config["partial_rotary_factor"]),
        HK=config["linear_num_key_heads"], HV=config["linear_num_value_heads"],
        DK=config["linear_key_head_dim"], DV=config["linear_value_head_dim"],
        TAPS=config["linear_conv_kernel_dim"],
        F=config["moe_intermediate_size"],
        FS=config["shared_expert_intermediate_size"],
        K=config["num_experts_per_tok"],
        E=config["published"]["num_experts"], held=config["experts_held"],
        NL=config["num_hidden_layers"], I=config["full_attention_interval"],
        V=config["vocab_size"], L=config["seq_len"],
        theta=float(config["rope_theta"]), eps=config["rms_norm_eps"])


def init(config: dict, key):
    """The configuration's init (`assumed.init`).  One key per drawn leaf,
    split in the order the file states."""
    z = sizes(config)
    n, lin = z["NL"] // z["I"], z["I"] - 1
    h, f, fs, g = z["H"], z["F"], z["FS"], len(z["held"])
    kd, vd = z["HK"] * z["DK"], z["HV"] * z["DV"]
    q, kv = z["HQ"] * z["D"], z["HKV"] * z["D"]

    def expert_layer(lead):
        return [("router", lead + (h, z["E"])),
                ("w_gate", lead + (g, h, f)), ("w_up", lead + (g, h, f)),
                ("w_down", lead + (g, f, h)),
                ("shared_gate", lead + (h, fs)), ("shared_up", lead + (h, fs)),
                ("shared_down", lead + (fs, h)), ("shared_sig", lead + (h, 1))]
    L, F = (n, lin), (n,)
    drawn = [("embed", None, (z["V"], h))]
    drawn += [("linear", name, shape) for name, shape in [
        ("w_qkvz", L + (h, 2 * kd + 2 * vd)), ("w_ba", L + (h, 2 * z["HV"])),
        ("conv", L + (z["TAPS"], 2 * kd + vd)), ("w_out", L + (vd, h)),
        ("A_log", L + (z["HV"],))] + expert_layer(L)]
    drawn += [("full", name, shape) for name, shape in [
        ("wq", F + (h, 2 * q)), ("wk", F + (h, kv)), ("wv", F + (h, kv)),
        ("wo", F + (q, h))] + expert_layer(F)]
    drawn += [("head", None, (h, z["V"]))]
    keys = jax.random.split(key, len(drawn))
    w = {"linear": {}, "full": {}}
    for k, (group, name, shape) in zip(keys, drawn):
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
    zeros = lambda *s: jnp.zeros(s, jnp.float32)
    ones = lambda *s: jnp.ones(s, jnp.float32)
    w["linear"].update(ln1=zeros(*L, h), ln2=zeros(*L, h),
                       dt_bias=ones(*L, z["HV"]),
                       gdn_norm=ones(*L, z["DV"]))
    w["full"].update(ln1=zeros(*F, h), ln2=zeros(*F, h),
                     q_norm=zeros(*F, z["D"]), k_norm=zeros(*F, z["D"]))
    return {"embed": w["embed"],
            "periods": {"linear": w["linear"], "full": w["full"]},
            "final_norm": zeros(h), "head": w["head"]}


def norm(x, w, eps):
    """Zero-centred RMSNorm."""
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * (1 + w)


def rope_partial(x, pos, theta, r):
    """x [P, heads, D]: rotate-half on the first r of D, the rest kept."""
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    xr = x[..., :r]
    rot = jnp.concatenate([-xr[..., r // 2:], xr[..., :r // 2]], -1)
    return jnp.concatenate(
        [xr * cos.astype(x.dtype) + rot * sin.astype(x.dtype), x[..., r:]],
        -1)


def conv4(x, w):
    """Causal depthwise convolution, x [P, C], w [taps, C]: position t sees
    t - taps + 1 .. t, zeros before position 0."""
    taps = w.shape[0]
    out = jnp.zeros_like(x)
    for j in range(taps):
        shift = taps - 1 - j
        moved = jnp.concatenate(
            [jnp.zeros((shift, x.shape[1]), x.dtype), x[:x.shape[0] - shift]])
        out = out + w[j] * moved
    return out


def recurrence(q, k, v, g, beta):
    """Token by token.  q, k [P, HV, DK], v [P, HV, DV], g, beta [P, HV]
    -> o [P, HV, DV].  The state is of v's dtype (float32 but in the
    control)."""
    P = q.shape[0]
    seg = math.gcd(P, SEGMENT)

    def token(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[:, None, None] * S
        r = jnp.sum(k_t[:, :, None] * S, axis=1)
        S = S + k_t[:, :, None] * (b_t[:, None] * (v_t - r))[:, None, :]
        return S, jnp.sum(q_t[:, :, None] * S, axis=1)

    @jax.checkpoint
    def segment(S, xs):
        return lax.scan(token, S, xs)
    S0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), v.dtype)
    cut = lambda a: a.reshape((P // seg, seg) + a.shape[1:])
    _, o = lax.scan(segment, S0, tuple(map(cut, (q, k, v, g, beta))))
    return o.reshape((P,) + o.shape[2:])


def linear_mixer(h, p, z, no_decay=False):
    """Gated DeltaNet: h [P, H] -> [P, H]."""
    P = h.shape[0]
    kd, vd = z["HK"] * z["DK"], z["HV"] * z["DV"]
    qkvz = h @ p["w_qkvz"]
    ba = h @ p["w_ba"]
    u = jax.nn.silu(conv4(qkvz[:, :2 * kd + vd], p["conv"]))
    q = u[:, :kd].reshape(P, z["HK"], z["DK"])
    k = u[:, kd:2 * kd].reshape(P, z["HK"], z["DK"])
    v = u[:, 2 * kd:].reshape(P, z["HV"], z["DV"])
    zg = qkvz[:, 2 * kd + vd:].reshape(P, z["HV"], z["DV"])
    beta = jax.nn.sigmoid(ba[:, :z["HV"]])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, z["HV"]:] + p["dt_bias"])
    if no_decay:                                    # planted fault
        g = jnp.zeros_like(g)
    l2 = lambda x: x * lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                                 + 1e-6)
    rep = z["HV"] // z["HK"]
    q = jnp.repeat(l2(q), rep, axis=1) / math.sqrt(z["DK"])
    k = jnp.repeat(l2(k), rep, axis=1)
    o = recurrence(q, k, v, g.astype(v.dtype), beta.astype(v.dtype))
    y = o * lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + z["eps"]) \
        * p["gdn_norm"] * jax.nn.silu(zg)
    return y.reshape(P, vd) @ p["w_out"]


def full_mixer(h, p, z, pos):
    """Gated grouped-query softmax attention: h [P, H] -> [P, H]."""
    P, D = h.shape[0], z["D"]
    qg = (h @ p["wq"]).reshape(P, z["HQ"], 2 * D)
    q, gate = qg[..., :D], qg[..., D:]
    k = (h @ p["wk"]).reshape(P, z["HKV"], D)
    v = (h @ p["wv"]).reshape(P, z["HKV"], D)
    q = rope_partial(norm(q, p["q_norm"], z["eps"]), pos, z["theta"], z["R"])
    k = rope_partial(norm(k, p["k_norm"], z["eps"]), pos, z["theta"], z["R"])
    group = z["HQ"] // z["HKV"]
    rows = min(ROW_BLOCK, P)
    keys = jnp.arange(P)

    @jax.checkpoint
    def block(start):
        qb = lax.dynamic_slice_in_dim(q, start, rows, 0)
        kk = jnp.repeat(k, group, axis=1)               # [P, HQ, D]
        vv = jnp.repeat(v, group, axis=1)
        s = jnp.einsum("qhd,khd->hqk", qb, kk) / math.sqrt(D)
        seen = keys[None, :] <= (start + jnp.arange(rows))[:, None]
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), vv)
    o = lax.map(block, jnp.arange(0, P, rows)).reshape(P, z["HQ"], D)
    return (o * jax.nn.sigmoid(gate)).reshape(P, z["HQ"] * D) @ p["wo"]


def experts(h, p, z, no_shared_gate=False):
    """h [P, H] -> this share's routed part + the shared expert."""
    probs = jax.nn.softmax(h @ p["router"], axis=-1)        # all E experts
    top_p, top_e = lax.top_k(probs, z["K"])
    w = top_p / jnp.sum(top_p, -1, keepdims=True)
    out = jnp.zeros_like(h)
    for g, e in enumerate(z["held"]):
        weight = jnp.sum(jnp.where(top_e == e, w, 0.0), -1)  # 0 if not chosen
        y = (jax.nn.silu(h @ p["w_gate"][g]) * (h @ p["w_up"][g])) \
            @ p["w_down"][g]
        out = out + weight[:, None].astype(h.dtype) * y
    shared = (jax.nn.silu(h @ p["shared_gate"]) * (h @ p["shared_up"])) \
        @ p["shared_down"]
    if not no_shared_gate:                          # else: planted fault
        shared = shared * jax.nn.sigmoid(h @ p["shared_sig"])
    return out + shared


def hidden_states(params, tokens, z, no_decay=False, no_shared_gate=False):
    """One sequence: ids [L] -> the final states [L, H]."""
    x = params["embed"][tokens]
    pos = jnp.arange(tokens.shape[0])

    @jax.checkpoint
    def linear_layer(x, p):
        x = x + linear_mixer(norm(x, p["ln1"], z["eps"]), p, z, no_decay)
        return x + experts(norm(x, p["ln2"], z["eps"]), p, z, no_shared_gate)

    @jax.checkpoint
    def full_layer(x, p):
        x = x + full_mixer(norm(x, p["ln1"], z["eps"]), p, z, pos)
        return x + experts(norm(x, p["ln2"], z["eps"]), p, z, no_shared_gate)
    at = lambda tree, *idx: jax.tree.map(lambda a: a[idx], tree)
    for i in range(z["NL"]):
        period, place = divmod(i, z["I"])
        if (i + 1) % z["I"] == 0:
            x = full_layer(x, at(params["periods"]["full"], period))
        else:
            x = linear_layer(x, at(params["periods"]["linear"], period,
                                   place))
    return norm(x, params["final_norm"], z["eps"])


def sequence_loss(params, tokens, z, **faults):
    """(mean next-token loss of one sequence over its L - 1 predicted
    positions, tokens predicted right)."""
    logits = hidden_states(params, tokens, z, **faults)[:-1] @ params["head"]
    target = tokens[1:]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, target[:, None], axis=-1)[:, 0]
    return jnp.mean(nll), jnp.sum(jnp.argmax(logits, -1) == target)


def tree_norms(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(k): float(jnp.sqrt(jnp.sum(jnp.square(
        jnp.asarray(v, jnp.float32))))) for k, v in flat}


def follow(config, *, seed, weights_seed, world, per_chip_batch, train,
           heldout, steps=3, drop_half=False, freeze=False, no_decay=False,
           no_shared_gate=False, dtype="float32"):
    """Train `steps` steps from the seeds and evaluate; return every number
    benchmark/correct.py reads.  `train` [N, L]: the stream's first
    sequences, step k taking rows [k*world*b, (k+1)*world*b), shard r its
    b = per_chip_batch rows of those; `heldout` [M, L].  `seed` drew the
    tokens; the objective draws nothing."""
    del seed
    z = sizes(config)
    opt = config["optimizer"]
    lr, mu, wd = config["lr"], opt["momentum"], opt["weight_decay"]
    faults = dict(no_decay=no_decay, no_shared_gate=no_shared_gate)
    params = jax.tree.map(lambda a: a.astype(dtype),
                          init(config, jax.random.PRNGKey(weights_seed)))
    vel = jax.tree.map(jnp.zeros_like, params)
    # weights, velocity and two gradients are 10 GB at the real sizes: the
    # initial weights wait on the host, and the updates reuse their buffers
    p0 = jax.device_get(params)

    @jax.jit
    def seq_grad(params, tokens):
        return jax.value_and_grad(
            lambda p: sequence_loss(p, tokens, z, **faults)[0])(params)

    def sgd(params, vel, g):
        d = jax.tree.map(lambda p, gg: gg + wd * p, params, g)
        vel = jax.tree.map(lambda v, dd: mu * v + dd, vel, d)
        return jax.tree.map(lambda p, v: p - lr * v, params, vel), vel
    sgd = jax.jit(sgd, donate_argnums=(0, 1))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=(0,))
    scale = jax.jit(lambda a, c: jax.tree.map(lambda x: x * c, a),
                    donate_argnums=(0,))
    out = {"loss": [], "grad_norms": []}
    b = per_chip_batch
    for k in range(steps):
        losses, g = [], None
        for r in range(world):
            rows = jnp.asarray(train[(k * world + r) * b:
                                     (k * world + r + 1) * b])
            use = range(b // 2) if drop_half else range(b)
            total, g_sum = 0.0, None
            for s in use:
                loss, gs = seq_grad(params, rows[s])
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
            params, vel = sgd(params, vel, g)
        if k == 0:
            out["momentum1_norms"] = tree_norms(vel)
        del g
    out["dparam_norms"] = tree_norms(jax.tree.map(
        lambda a, c: a - jnp.asarray(c), params, p0))

    seq_eval = jax.jit(lambda params, tokens: sequence_loss(
        params, tokens, z, **faults))
    loss_sum, correct = 0.0, 0
    for row in heldout:
        l, c = seq_eval(params, jnp.asarray(row))
        loss_sum += float(l)
        correct += int(c)
    out["eval_loss"] = loss_sum / len(heldout)
    out["eval_correct"] = correct
    out["eval_n"] = len(heldout)
    return out
