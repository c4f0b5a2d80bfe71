"""The dense hybrid decoder with negative eigenvalues (models/olmo_hybrid.py,
ops/gdn.py `mixer` with beta = 2 sigmoid(b) and its kernels at heads that
are not whole lanes, ops/attention.py `causal_attention` without rotary) at
a tiny size on the CPU: hidden 64, one period of three linear layers (2
heads, keys of 8, values of 16) and one full layer (4 heads of 16), dense
MLPs of 96, post-norm, L = 32, vocabulary 64.  The program against the
benchmark's plain reference (benchmark/reference/dense_hybrid_causal.py,
which imports nothing of the program and computes the recurrence token by
token) on seeded weights; the delta rule's and the front's Pallas kernels in
Pallas' interpreter at the published head sizes, keys of 96 and values of
192, with betas over (0, 2).
"""

import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import dense_hybrid_causal as ref  # noqa: E402
from benchmark.reference import hybrid_causal               # noqa: E402
from cs744_ddp_tpu import cli, models                       # noqa: E402
from cs744_ddp_tpu.models import olmo_hybrid, qwen3next     # noqa: E402
from cs744_ddp_tpu.obs import Telemetry                     # noqa: E402
from cs744_ddp_tpu.ops import gdn, sgd                      # noqa: E402
from cs744_ddp_tpu.train.loop import Trainer                # noqa: E402

CONFIG = json.load(open(os.path.join(
    ROOT, "benchmark", "tests", "tiny-olmo-hybrid-f32.json")))
REAL = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "olmo-hybrid-7b-pp8-f32.json")))
TINY = olmo_hybrid.TINY


def close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= tol * max(1.0, np.max(np.abs(b)))


def near(a, b, tol):
    """Within `tol` of b's largest entry, however small: a gradient leaf."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= tol * np.max(np.abs(b))


# -- the recurrence at keys of 96 and values of 192 ---------------------------

def negative_inputs(heads, length, dk=96, dv=192, seed=0):
    """Positions first, a key head a value head, as the mixer hands them
    over, with beta = 2 sigmoid(b) over (0, 2): about half above 1."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (length, heads, dk))) / np.sqrt(dk)
    k = unit(jax.random.normal(ks[1], (length, heads, dk)))
    v = jax.random.normal(ks[2], (length, heads, dv))
    g = -0.5 * jax.random.uniform(ks[3], (length, heads))
    b = 1.5 * jax.random.normal(ks[4], (length, heads))
    beta = 2.0 * jax.nn.sigmoid(b)
    return q, k, v, g, beta


@pytest.mark.parametrize("heads", [2, 6], ids=["whole-width", "ragged"])
def test_kernels_at_96_and_192_are_the_token_by_token_recurrence(
        heads, monkeypatch):
    """`delta_rule`'s two kernels in the interpreter at keys of 96 and
    values of 192 over 256 positions (2 chunks of 128), forward and the
    five gradients, against the reference's token-by-token loop and the
    chunked jax.numpy form, betas over (0, 2) and some above 1: two heads
    (192 lanes of keys, one grid step over the whole width), six (blocks of
    four heads, 384 lanes, the last block ragged)."""
    monkeypatch.setattr(gdn, "KERNEL_HEADS", 4)
    x = negative_inputs(heads, 256)
    beta = np.asarray(x[4])
    assert 0.3 < np.mean(beta > 1.0) < 0.7 and beta.max() < 2.0
    fused = lambda *a: gdn.delta_rule(*a, kernels=True, interpret=True)
    chunked = lambda *a: gdn.delta_rule(*a, 64, 4)
    want = hybrid_causal.recurrence(*x)
    assert str(jax.make_jaxpr(fused)(*x)).count("pallas_call") == 1
    got = fused(*x)
    assert got.shape == (256, heads, 192)
    assert close(got, want, 1e-5) and close(chunked(*x), want, 1e-5)
    grad = lambda f: jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))),
                              argnums=(0, 1, 2, 3, 4))(*x)
    for a, b, c in zip(grad(fused), grad(hybrid_causal.recurrence),
                       grad(chunked)):
        assert a.shape == b.shape and close(a, b, 1e-4) and close(c, b, 1e-4)


def test_a_beta_of_two_reflects_the_state_along_the_key():
    """beta = 2 with no decay: the transition I - 2 k k^T is a reflection
    (eigenvalue -1 along k), so a second write of the same key and value
    cancels the first (2 v read back, then 0; at beta 1 it would read v
    twice), on the kernels as on the chunked form and the loop."""
    k = jnp.zeros((2, 1, 96)).at[:, 0, 5].set(1.0)
    v = jnp.broadcast_to(jnp.arange(192.0)[None, None], (2, 1, 192))
    q, g, beta = k, jnp.zeros((2, 1)), jnp.full((2, 1), 2.0)
    pad = lambda a: jnp.pad(a, ((0, 126),) + ((0, 0),) * (a.ndim - 1))
    x = tuple(map(pad, (q, k, v, g, beta)))
    for o in (hybrid_causal.recurrence(*x),
              gdn.delta_rule(*x, kernels=True, interpret=True),
              gdn.delta_rule(*x, 64, 4)):
        # first write: S = 2 v k^T, read 2 v; second: S (I - 2kk^T) + 2vk^T
        assert close(o[0, 0], 2 * v[0, 0]) and close(o[1, 0], 0 * v[0, 0])


def shared_direction_inputs(heads, length, dk, dv, beta0, seed=0):
    """Keys that share a direction (noise of norm 0.3 about a common unit
    key), betas within 0.01 of `beta0`, a decay of at most 0.01 a
    position: the powers of a chunk's system grow like binomials here."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    common = unit(jax.random.normal(ks[0], (1, heads, dk)))
    k = unit(common + 0.3 * unit(jax.random.normal(ks[1],
                                                   (length, heads, dk))))
    q = unit(jax.random.normal(ks[2], (length, heads, dk))) / np.sqrt(dk)
    v = jax.random.normal(ks[3], (length, heads, dv))
    g = -0.01 * jax.random.uniform(ks[4], (length, heads))
    beta = beta0 + 0.02 * jax.random.uniform(ks[5], (length, heads)) - 0.01
    return q, k, v, g, beta


def chunk_system(c, keys):
    """A chunk's strictly lower-triangular system beta D (k k^T), as
    `_chunk` builds it, for one head of keys of 128: random unit keys and
    betas over (0, 2), or keys that share a direction with betas near 1 or
    near 2."""
    if keys == "random":
        q, k, v, g, beta = negative_inputs(1, c, dk=128, dv=128)
    else:
        q, k, v, g, beta = shared_direction_inputs(
            1, c, 128, 128, {"shared-1": 1.0, "shared-2": 1.99}[keys])
    k, G = k[:, 0], jnp.cumsum(g[:, 0])
    D = jnp.exp(jnp.tril(G[:, None] - G[None, :]))
    return jnp.tril(beta[:, 0, None] * D * (k @ k.T), -1)


@pytest.mark.parametrize("keys", ["random", "shared-1", "shared-2"])
@pytest.mark.parametrize("c", [64, 128])
def test_the_chunk_inversion_is_the_float64_triangular_solve(c, keys):
    """`_unit_lower_inverse` (diagonal blocks by substitution, then
    doubling), which both forms call, against a float64 triangular solve
    at the chunks of 64 and 128: finite and within 2e-5 of the solve's
    largest entry on random keys and on keys that share a direction, with
    beta near 1 and near 2, where the squarings' powers left float32."""
    from scipy.linalg import solve_triangular
    a = chunk_system(c, keys)
    want = solve_triangular(np.eye(c) + np.asarray(a, np.float64),
                            np.eye(c), lower=True, unit_diagonal=True)
    got = np.asarray(jax.jit(gdn._unit_lower_inverse)(a), np.float64)
    assert got.shape == (c, c) and np.all(np.isfinite(got))
    assert np.max(np.abs(got - want)) <= 2e-5 * np.max(np.abs(want))


@pytest.mark.parametrize("dk,dv,beta0", [(128, 128, 1.0), (96, 192, 1.99)],
                         ids=["128-beta-near-1", "96-192-beta-near-2"])
def test_kernels_hold_on_keys_that_share_a_direction(dk, dv, beta0):
    """Two chunks of 128 of keys that share a direction: the interpreted
    kernels and the chunked form, forward and the five gradients, against
    the token loop (the squarings left float32 on these: nan from both
    forms)."""
    x = shared_direction_inputs(2, 256, dk, dv, beta0)
    fused = lambda *a: gdn.delta_rule(*a, kernels=True, interpret=True)
    chunked = lambda *a: gdn.delta_rule(*a, 64, 4)
    want = hybrid_causal.recurrence(*x)
    got = fused(*x)
    assert np.all(np.isfinite(np.asarray(got)))
    assert close(got, want) and close(chunked(*x), want)
    grad = lambda f: jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))),
                              argnums=(0, 1, 2, 3, 4))(*x)
    for a, b, c in zip(grad(fused), grad(hybrid_causal.recurrence),
                       grad(chunked)):
        assert near(a, b, 1e-4) and near(c, b, 1e-4)


def test_the_narrow_rule_and_the_whole_lane_tiling():
    """Keys of 96 and values of 192 take the kernels (four heads fill whole
    lanes: 30 heads in blocks of 4, the last of 2); heads in whole lanes
    keep the tiling they had; keys of 8 still take jax.numpy."""
    assert gdn.narrow_fits(96, 192) and not gdn.kernel_fits(96, 192)
    assert gdn.plan(8192, 96, 192, True) == (True, gdn.KERNEL_CHUNK)
    assert gdn.plan(8192, 96, 192, False)[0] is False
    assert gdn.plan(32, 8, 16, True)[0] is False
    assert gdn._heads_a_step(30, 1, 96, 192) == 4
    assert gdn._heads_a_step(2, 1, 96, 192) == 2
    assert gdn._heads_a_step(32, 2, 128, 128) == 4        # qwen3next's
    assert gdn.key_stride(2880) == 2944 and gdn.key_stride(2048) == 2048
    assert gdn.front_plan(8192, 96, 5760, jnp.float32, True) == \
        gdn.FRONT_TILE
    assert gdn.front_plan(8192, 8, 5760, jnp.float32, True) == 0


# -- the front at heads of 96 lanes -------------------------------------------

@pytest.mark.parametrize("heads,value_width", [(2, 384), (10, 1920)],
                         ids=["one-group", "groups-and-a-rest"])
def test_front_kernels_at_96_lane_heads_are_the_jax_numpy_front(
        heads, value_width, monkeypatch):
    """The front's kernels in the interpreter on a projection laid out [q |
    zeros | k | zeros | v | z] (q and k filled to whole lanes): q, k, v, z
    and the gradients against the jax.numpy form on the same layout; ten
    heads of 96 are two groups of four in whole lanes and a last group of
    two; the zeros' channels get a zero cotangent."""
    monkeypatch.setattr(gdn, "FRONT_TILE", 16)
    kd = heads * 96
    stride = gdn.key_stride(kd)
    width = 2 * stride + 2 * value_width
    live = np.ones(width, np.float32)
    live[kd:stride] = live[stride + kd:2 * stride] = 0.0
    kx, kw = jax.random.split(jax.random.PRNGKey(4))
    x = jax.random.normal(kx, (48, width)) * live
    w = 0.5 * jax.random.normal(kw, (4, width - value_width)) \
        * live[:width - value_width]
    kernel = lambda x, w: gdn.front(x, w, kd, 96, stride=stride,
                                    kernels=True, interpret=True)
    plain = lambda x, w: gdn.front(x, w, kd, 96, stride=stride)
    got, want = kernel(x, w), plain(x, w)
    assert [a.shape for a in got] == [(48, kd), (48, kd), (48, value_width),
                                      (48, value_width)]
    for a, b in zip(got, want):
        assert close(a, b, 2e-6)
    cots = [jax.random.normal(jax.random.PRNGKey(7 + i), a.shape)
            for i, a in enumerate(want)]
    loss = lambda f: lambda x, w: sum(
        jnp.sum(jnp.sin(o) * c) for o, c in zip(f(x, w), cots))
    gk = jax.grad(loss(kernel), argnums=(0, 1))(x, w)
    gp = jax.grad(loss(plain), argnums=(0, 1))(x, w)
    for a, b in zip(gk, gp):
        assert close(a, b, 1e-5)
    assert not np.any(np.asarray(gk[0])[:, kd:stride])
    assert not np.any(np.asarray(gk[0])[:, stride + kd:2 * stride])


# -- the shared mixer ---------------------------------------------------------

def test_both_decoders_call_one_mixer():
    """qwen3next's linear mixer is `gdn.mixer` at beta's scale 1; at scale
    2 the same weights give twice the betas and another output."""
    s = qwen3next.TINY
    p = jax.tree.map(lambda a: a[0, 0], qwen3next.init_params(
        jax.random.PRNGKey(0), s)["periods"]["linear"])
    h = jax.random.normal(jax.random.PRNGKey(1), (32, s.hidden))
    kw = dict(key_heads=s.lin_key_heads, value_heads=s.lin_value_heads,
              dk=s.lin_key_dim, dv=s.lin_value_dim, eps=s.eps)
    y1, b1 = gdn.mixer(h, p["w_qkvz"], p["w_ba"], p, **kw)
    y2, b2 = gdn.mixer(h, p["w_qkvz"], p["w_ba"], p, beta_scale=2.0, **kw)
    assert np.array_equal(qwen3next._gdn_mixer(s, False, h, p), y1)
    assert close(b2, 2 * b1, 1e-7) and float(jnp.max(b1)) < 1.0
    assert float(jnp.max(b2)) > 1.0 and not close(y1, y2, 1e-3)


# -- the model against the reference ------------------------------------------

def config_for(layers):
    return dict(CONFIG, num_hidden_layers=layers)


def test_init_is_the_configurations_recipe():
    params, _ = olmo_hybrid.make(TINY)[0](jax.random.PRNGKey(7))
    rparams = ref.init(CONFIG, jax.random.PRNGKey(7))
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    rflat = dict(jax.tree_util.tree_flatten_with_path(rparams)[0])
    assert set(k for k, _ in flat) == set(rflat)
    assert all(np.array_equal(v, rflat[k]) for k, v in flat)
    linear, full = params["periods"]["linear"], params["periods"]["full"]
    assert linear["w_q"].shape == (1, 3, 64, 16)
    assert linear["w_v"].shape == linear["w_g"].shape == (1, 3, 64, 32)
    assert linear["conv"].shape == (1, 3, 4, 64)
    assert full["wq"].shape == (1, 64, 64) and full["q_norm"].shape == (1, 64)
    assert "router" not in linear and "router" not in full
    assert np.all(np.asarray(full["ln_mix"]) == 1.0)
    assert abs(float(jnp.std(params["embed"])) - 1.0) < 0.05


@pytest.mark.parametrize("layers", [4, 8], ids=["one-period", "two-periods"])
def test_loss_and_every_gradient_leaf_match_the_reference(layers):
    """The published order (three linear layers, then a full one), once
    and twice; gains moved off 1 so that they count.  The program's chunked
    recurrence, blocked causal tiles and scans against the reference's
    token-by-token loop, dense causal mask and loop over layers: float32 on
    both sides, to 1e-5 on the loss and, on its gradient, 1e-4 of a leaf's
    largest entry at one period and 3e-4 at two (read: 3.3e-05 and 1.5e-04;
    at betas under 1, 2.0e-05 and 3.3e-05: post-norm and reflections carry
    the two forms' rounding through eight layers further); each planted
    fault moves some leaf by a tenth or more of it; about half the betas
    are over 1."""
    config = config_for(layers)
    init_fn, apply_fn = olmo_hybrid.make(TINY._replace(layers=layers))
    params, _ = init_fn(jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.1 if path[-1].key in (
            "ln_mix", "ln_mlp", "q_norm", "k_norm", "gdn_norm",
            "final_norm") else a, params)
    toks = jax.random.randint(jax.random.PRNGKey(9), (2, 32), 0, 63)
    obj = apply_fn.objective

    def prog(p):
        loss, (_, extras) = obj.loss(apply_fn, p, {}, obj.prepare(None, toks))
        return loss, extras
    (loss, extras), grads = jax.jit(
        jax.value_and_grad(prog, has_aux=True))(params)
    z = ref.sizes(config)

    def plain(p):
        return sum(ref.sequence_loss(p, toks[s], z)[0] for s in range(2)) / 2
    rloss, rgrads = jax.jit(jax.value_and_grad(plain))(params)
    assert abs(float(loss) - float(rloss)) < 1e-5 * float(rloss)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    rflat = dict(jax.tree_util.tree_flatten_with_path(rgrads)[0])
    assert len(flat) == 3 + 16 + 11
    tol = 1e-4 if layers == 4 else 3e-4
    for k, g in flat:
        assert near(g, rflat[k], tol), jax.tree_util.keystr(k)
        assert np.any(np.asarray(g)), jax.tree_util.keystr(k)
    count, over, entries = (float(e) for e in extras)
    assert count == 2 * 31
    assert entries == 2 * 32 * 2 * 3 * layers // 4
    assert 0.3 < over / entries < 0.7
    # the faults are traced flags: one compiled gradient serves all four
    faulty = jax.jit(jax.grad(lambda p, flags: sum(ref.sequence_loss(
        p, toks[s], z, **flags)[0] for s in range(2)) / 2))
    for fault in ref.FAULTS:
        bad = faulty(params, {f: jnp.asarray(f == fault) for f in ref.FAULTS})
        assert not all(near(a, b, 0.1) for a, b in zip(
            jax.tree.leaves(bad), jax.tree.leaves(rgrads))), fault


def test_evaluation_counts_match_the_reference():
    init_fn, apply_fn = olmo_hybrid.make(TINY)
    params, _ = init_fn(jax.random.PRNGKey(1))
    toks = jax.random.randint(jax.random.PRNGKey(2), (3, 32), 0, 63)
    labels = jnp.asarray([0, 0, -1])            # the last row is padding
    loss, hit, count = apply_fn.objective.eval_counts(
        apply_fn, params, {}, None, toks, labels)
    z = ref.sizes(CONFIG)
    want = [ref.sequence_loss(params, toks[s], z) for s in range(2)]
    assert float(loss) == pytest.approx(sum(float(l) for l, _ in want),
                                        rel=1e-5)
    assert int(hit) == sum(int(c) for _, c in want) and int(count) == 62


SCOPES = ("attn_gdn", "gdn_conv", "gdn_recurrence", "attn_causal",
          "mlp_dense", "lm_head")


def test_every_scope_is_in_the_compiled_train_program():
    """Forward and backward: the scopes the benchmark's readers class the
    device time by, in the `op_name` of the compiled train step's
    instructions (what `benchmark/readers/lm.py` reads)."""
    init_fn, apply_fn = olmo_hybrid.make(TINY)
    params, _ = init_fn(jax.random.PRNGKey(0))
    toks = jnp.zeros((2, 32), jnp.int32)
    obj = apply_fn.objective
    step = jax.jit(jax.grad(lambda p: obj.loss(apply_fn, p, {}, toks)[0]))
    names = set(re.findall(r'op_name="([^"]*)"',
                           step.lower(params).compile().as_text()))
    for scope in SCOPES:
        held = [n for n in names if f"/{scope}/" in n]
        assert any("transpose(jvp" in n for n in held), scope
        assert any("transpose(jvp" not in n for n in held), scope
    assert any("/attn_gdn/gdn_recurrence/" in n for n in names)
    assert not any("rope" in n or "sin" in n.split("/")[-1] for n in names
                   if "/attn_causal/" in n)


def write_tokens(root, train, heldout):
    os.makedirs(os.path.join(root, "tokens"))
    np.save(os.path.join(root, "tokens", "train.npy"), train)
    np.save(os.path.join(root, "tokens", "heldout.npy"), heldout)


@pytest.mark.parametrize("devices", [1, 2])
def test_sgd_steps_and_test_model_through_trainer_match_the_reference(
        tmp_path, devices):
    """`Trainer.train_model` (staged epoch, scanned window, ring drain) and
    `test_model` against the reference followed step by step: three
    losses, the parameters' change, the evaluation; on two devices under
    `ddp`; the betas' counters beside `tokens_predicted`."""
    rng = np.random.default_rng(5)
    b = 4 * devices
    train = rng.integers(0, 63, (4 * b, 32), dtype=np.int32)
    heldout = rng.integers(0, 63, (6, 32), dtype=np.int32)     # ragged eval
    write_tokens(str(tmp_path), train, heldout)
    tel = Telemetry()
    tr = Trainer(model="olmo-hybrid-tiny", strategy="ddp",
                 num_devices=devices, global_batch=b, data_dir=str(tmp_path),
                 seed=11, init_seed=3, sgd_cfg=sgd.SGDConfig(lr=0.01),
                 limit_train_batches=3, telemetry=tel, log=lambda s: None)
    assert tr.real_data
    p0 = jax.device_get(tr.state.params)
    timers = tr.train_model(0)
    eval_loss, correct, acc = tr.test_model()
    want = ref.follow(CONFIG, seed=11, weights_seed=3, world=devices,
                      per_chip_batch=4, train=train, heldout=heldout, steps=3)
    assert np.allclose(timers.losses, want["loss"], rtol=2e-5)
    assert abs(eval_loss - want["eval_loss"]) < 2e-5 * want["eval_loss"]
    assert correct == want["eval_correct"]
    moved = ref.tree_norms(jax.tree.map(
        lambda a, c: np.asarray(a) - c, jax.device_get(tr.state.params), p0))
    assert set(moved) == set(want["dparam_norms"])
    # a leaf's gap over the larger of its norm and the median leaf's, as
    # benchmark/correct.py reads it: A_log's change is 1e-4 of the median
    median = float(np.median(list(want["dparam_norms"].values())))
    for leaf, norm in want["dparam_norms"].items():
        assert abs(moved[leaf] - norm) <= 1e-3 * max(norm, median), leaf
    totals = tel.counter_totals()
    assert totals["tokens_predicted"] == 3 * b * 31
    assert totals["gdn_beta_entries"] == 3 * b * 32 * 2 * 3
    assert 0.3 < totals["gdn_beta_over_one"] / totals["gdn_beta_entries"] \
        < 0.7
    assert "moe_rows_local" not in totals
    gauges = {(r["name"], r.get("kernel")): r["value"] for r in tel.records
              if r["kind"] == "gauge"}
    assert gauges["gdn_kernel", None] == 0          # the CPU: jax.numpy
    assert gauges["gdn_front_tile", None] == 0


def test_the_gauges_say_which_form_runs_and_its_front_tile():
    """At the published shape with the kernels: the delta rule's kernels in
    chunks of 128, each chunk's inversion from diagonal blocks of
    `INVERSE_BLOCK`, and the front's on tiles of `FRONT_TILE` positions;
    off the TPU neither kernel."""
    def gauges(kernels):
        _, apply_fn = olmo_hybrid.make(olmo_hybrid.Shape(), kernels=kernels)
        return {name: value for name, value, _ in apply_fn.objective.gauges
                if name.startswith("gdn_")}
    assert gauges(True) == {
        "gdn_kernel": 1, "gdn_chunk": gdn.KERNEL_CHUNK,
        "gdn_chunks_per_sequence": 8192 // gdn.KERNEL_CHUNK,
        "gdn_inverse_block": gdn.INVERSE_BLOCK,
        "gdn_front_kernel": 1, "gdn_front_tile": gdn.FRONT_TILE}
    assert gauges(False)["gdn_kernel"] == gauges(None)["gdn_kernel"] == 0
    assert gauges(False)["gdn_front_tile"] == 0
    assert gauges(False)["gdn_inverse_block"] == gdn.INVERSE_BLOCK


def test_a_share_of_whole_periods_only_and_of_its_own_fields():
    with pytest.raises(ValueError, match="whole"):
        olmo_hybrid.make(TINY._replace(layers=6))
    with pytest.raises(ValueError, match="held"):
        models.get_model("olmo-hybrid-tiny", held=(0, 1))
    init_fn, apply_fn = models.get_model("olmo-hybrid-tiny", layers=8,
                                         seq_len=16)
    params = jax.eval_shape(lambda k: init_fn(k)[0], jax.random.PRNGKey(0))
    assert params["periods"]["linear"]["w_gate"].shape[:2] == (2, 3)
    assert params["periods"]["full"]["wq"].shape == (2, 64, 64)


def test_the_published_share_is_the_configurations_count():
    """`--model olmo-hybrid-7b` resolves at the share the benchmark's
    configuration states, whose `counted` is the program's leaf shapes, at
    every published width under the catalog's keys."""
    init_fn, apply_fn = models.get_model("olmo-hybrid-7b")
    s = apply_fn.objective.shape
    params = jax.eval_shape(lambda k: init_fn(k)[0], jax.random.PRNGKey(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    assert n == REAL["counted"]["all"] == 928_862_196
    assert REAL["counted"]["bytes_weight_gradient_momentum_float32"] \
        == 12 * n
    assert (s.layers, s.vocab, s.seq_len) == (
        REAL["num_hidden_layers"], REAL["vocab_size"], REAL["seq_len"])
    widths = {"hidden_size": s.hidden, "num_attention_heads": s.heads,
              "num_key_value_heads": s.heads, "intermediate_size":
              s.mlp_width, "linear_num_key_heads": s.lin_key_heads,
              "linear_num_value_heads": s.lin_value_heads,
              "linear_key_head_dim": s.lin_key_dim, "linear_value_head_dim":
              s.lin_value_dim, "linear_conv_kernel_dim": s.conv_taps,
              "rms_norm_eps": s.eps}
    assert widths == {k: REAL[k] for k in widths}
    assert s.head_dim == 128 and REAL["linear_allow_neg_eigval"] \
        and s.beta_scale == 2.0
    assert REAL["rope_parameters"] == {"rope_theta": None}
    assert REAL["layer_types"][:s.layers] == ["linear_attention"] * 3 \
        + ["full_attention"]


def test_cli_trains_and_evaluates_the_decoder_with_its_shares_flags(tmp_path,
                                                                    capsys):
    """`python -m cs744_ddp_tpu.cli --model olmo-hybrid-tiny` on the
    default path with the share's flags a dense decoder has; the experts'
    flag is refused by name."""
    argv = ["--model", "olmo-hybrid-tiny", "--strategy", "ddp",
            "--num-devices", "1", "--batch-size", "4", "--lr", "0.01",
            "--limit-train-batches", "2", "--data-dir", str(tmp_path),
            "--lm-layers", "4", "--lm-vocab", "48", "--lm-seq-len", "16"]
    tr = cli.main(argv)
    shape = tr.objective.shape
    assert (shape.seq_len, shape.vocab) == (16, 48)
    out = capsys.readouterr().out
    assert "Test set: Average loss:" in out and "/240 (" in out   # 16 x 15
    with pytest.raises(ValueError, match="held"):
        cli.main(argv + ["--lm-experts-held", "0-1"])
