"""The hybrid linear-attention mixture-of-experts decoder
(models/qwen3next.py, ops/gdn.py, ops/attention.py `causal_attention`,
ops/moe.py `shared_expert`, ops/loss.py `next_token_head_counts`) at a tiny
size on the CPU: hidden 64, 4 layers (linear, linear, linear, full), 2 key /
4 value linear heads of 8, 4 query / 2 key-value heads of 16 with rotary on
4, 8 experts of 32 of which 2 are held, top-2, a shared expert of 32, L =
32, vocabulary 64.  The program against the benchmark's plain reference
(benchmark/reference/hybrid_causal.py, which imports nothing of the
program and computes the recurrence token by token) on seeded weights.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import hybrid_causal as ref      # noqa: E402
from cs744_ddp_tpu import cli, models                      # noqa: E402
from cs744_ddp_tpu.models import qwen3next                 # noqa: E402
from cs744_ddp_tpu.obs import Telemetry                    # noqa: E402
from cs744_ddp_tpu.ops import attention, gdn, moe, sgd     # noqa: E402
from cs744_ddp_tpu.ops import loss as losslib              # noqa: E402
from cs744_ddp_tpu.train.loop import Trainer               # noqa: E402

CONFIG = json.load(open(os.path.join(
    ROOT, "benchmark", "tests", "tiny-qwen3next-f32.json")))
TINY = qwen3next.TINY


def close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= tol * max(1.0, np.max(np.abs(b)))


# -- the recurrence and the convolution ----------------------------------------

def recurrence_inputs(heads=3, length=40, dk=8, dv=6, seed=0, key_heads=None):
    """Positions first, as the mixer hands them over: q, k [P, key heads,
    dk], v [P, heads, dv], g, beta [P, heads]."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    hk = key_heads or heads
    q = jax.random.normal(ks[0], (length, hk, dk))
    k = jax.random.normal(ks[1], (length, hk, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (length, heads, dv))
    g = -4.0 * jax.random.uniform(ks[3], (length, heads))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (length, heads)))
    return q, k, v, g, beta


def by_token(q, k, v, g, beta):
    """The reference's token-by-token loop, a key head repeated for its
    value heads."""
    group = v.shape[1] // q.shape[1]
    return ref.recurrence(jnp.repeat(q, group, 1), jnp.repeat(k, group, 1),
                          v, g, beta)


@pytest.mark.parametrize("chunk,segment", [
    (8, 16), (10, 2), (40, 1), (7, 16), (16, 2), (8, 3), (64, 16), (1, 5)],
    ids=lambda c: str(c))
def test_chunked_recurrence_is_the_token_by_token_one(chunk, segment):
    """Forward and every gradient, at chunk sizes that divide the length
    of 40 (8, 10, 40), that do not (7, 16), that exceed it (64) and of one
    position, in one segment and in several (whole: 2 x 2 chunks of 10;
    padded: 2 x 3 chunks of 8, 2 x 2 of 16); against the loop as the
    description writes it, which is the reference's."""
    x = recurrence_inputs()
    want = by_token(*x)
    got = gdn.delta_rule(*x, chunk=chunk, segment=segment)
    assert got.shape == want.shape and close(got, want)
    grad = lambda f: jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))),
                              argnums=(0, 1, 2, 3, 4))(*x)
    for a, b in zip(grad(lambda *a: gdn.delta_rule(*a, chunk=chunk,
                                                   segment=segment)),
                    grad(by_token)):
        assert close(a, b, 1e-4)


def test_recurrence_forgets_by_its_decay_and_writes_by_beta():
    """beta = 0 writes nothing (the output is 0 from a zero state); a
    decay of exp(-40) a position forgets everything but the position's own
    write: o_t = beta_t (q_t . k_t) v_t."""
    q, k, v, g, beta = recurrence_inputs(heads=2, length=12)
    assert not np.any(gdn.delta_rule(q, k, v, g, jnp.zeros_like(beta), 4, 2))
    o = gdn.delta_rule(q, k, v, jnp.full_like(g, -40.0), beta, 4, 2)
    own = (beta * jnp.sum(q * k, -1))[..., None] * v
    assert close(o, own)


# The kernels (Pallas' interpreter): keys and values of 128, the MXU's width.
KERNEL_CASES = [(64, 128), (64, 100), (128, 256), (128, 200)]


def kernel_rule(chunk, monkeypatch, heads_a_step=2):
    monkeypatch.setattr(gdn, "KERNEL_CHUNK", chunk)
    monkeypatch.setattr(gdn, "KERNEL_HEADS", heads_a_step)
    return lambda *a: gdn.delta_rule(*a, kernels=True, interpret=True)


@pytest.mark.parametrize("chunk,length", KERNEL_CASES, ids=lambda c: str(c))
def test_kernels_are_the_token_by_token_recurrence(chunk, length,
                                                   monkeypatch):
    """The forward kernel's output and the backward kernel's five
    gradients against the loop, at a length of whole chunks (2 of either
    size) and at one that is padded (100 = 64 + 36; 200 = 128 + 72): three
    heads with a key head each, all three a grid step; then four heads on two
    key heads, a key head's cotangents summed over its two value heads."""
    x = recurrence_inputs(heads=3, length=length, dk=128, dv=128)
    want = by_token(*x)
    grad = lambda f: jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))),
                              argnums=(0, 1, 2, 3, 4))(*x)
    fused = kernel_rule(chunk, monkeypatch)
    got = fused(*x)
    assert got.shape == want.shape and close(got, want)
    for a, b in zip(grad(fused), grad(by_token)):
        assert a.shape == b.shape and close(a, b, 1e-4)
    # four heads of two key heads, four a grid step then two
    x = recurrence_inputs(heads=4, key_heads=2, length=length, dk=128, dv=128)
    want = by_token(*x)
    for heads_a_step in (4, 2):
        fused = kernel_rule(chunk, monkeypatch, heads_a_step)
        assert close(fused(*x), want)
    for a, b in zip(grad(fused), grad(by_token)):
        assert a.shape == b.shape and close(a, b, 1e-4)


@pytest.mark.parametrize("chunk", [64, 128])
def test_kernels_forget_by_their_decay_and_write_by_beta(chunk, monkeypatch):
    """`test_recurrence_forgets...` on the kernels' path: beta = 0 writes
    nothing; under a decay of exp(-40) a position only its own write is
    left, and no exponent of a positive difference is taken (no inf, no
    nan in the output or in a gradient)."""
    q, k, v, g, beta = recurrence_inputs(heads=2, length=chunk + 8, dk=128,
                                         dv=128)
    fused = kernel_rule(chunk, monkeypatch)
    assert not np.any(fused(q, k, v, g, jnp.zeros_like(beta)))
    hard = jnp.full_like(g, -40.0)
    o = fused(q, k, v, hard, beta)
    own = (beta * jnp.sum(q * k, -1))[..., None] * v
    assert close(o, own)
    grads = jax.grad(lambda *a: jnp.sum(fused(*a)), argnums=(0, 1, 2, 3, 4))(
        q, k, v, hard, beta)
    assert all(np.all(np.isfinite(np.asarray(a))) for a in grads)


def test_the_path_is_chosen_from_the_backend_and_the_shapes(monkeypatch):
    """`kernels=False` (off the TPU) and key / value sizes that are not
    whole lanes take the jax.numpy form: no `pallas_call` in the program;
    at a shape both take, the two forms give the same numbers."""
    calls = lambda f, x: str(jax.make_jaxpr(f)(*x)).count("pallas_call")
    wide = recurrence_inputs(heads=2, length=72, dk=128, dv=128)
    narrow = recurrence_inputs(heads=2, length=72)           # dk 8, dv 6
    fused = kernel_rule(64, monkeypatch)
    plain = lambda *a: gdn.delta_rule(*a, 16, 2)
    assert calls(fused, wide) == 1 and calls(jax.grad(
        lambda *a: jnp.sum(fused(*a))), wide) == 2
    assert calls(plain, wide) == 0 and calls(fused, narrow) == 0
    assert calls(lambda *a: gdn.delta_rule(*a, kernels=False,
                                           interpret=True), wide) == 0
    assert close(fused(*wide), plain(*wide))
    assert close(fused(*narrow), plain(*narrow))
    assert gdn.kernel_fits(128, 256) and not gdn.kernel_fits(128, 64)


def test_the_gauge_says_which_form_runs():
    """`gdn_kernel` 1 and the kernels' chunk where the model is built with
    the kernels and they fit its key and value sizes; 0 and the jax.numpy
    chunk where either fails (the tiny size: keys of 8); the inversion's
    diagonal block, the same on both forms; printed by
    tools/telemetry_report.py."""
    def gauges(shape, kernels):
        _, apply_fn = qwen3next.make(shape, kernels=kernels)
        return {name: value for name, value, _ in apply_fn.objective.gauges
                if name.startswith("gdn_")}
    wide = TINY._replace(lin_key_dim=128, lin_value_dim=128, seq_len=256)
    block = gdn.INVERSE_BLOCK
    assert gauges(wide, True) == {
        "gdn_kernel": 1, "gdn_chunk": gdn.KERNEL_CHUNK,
        "gdn_chunks_per_sequence": 256 // gdn.KERNEL_CHUNK,
        "gdn_inverse_block": block, "gdn_front_kernel": 1}
    assert gauges(wide, False) == gauges(wide, None) == {
        "gdn_kernel": 0, "gdn_chunk": 64, "gdn_chunks_per_sequence": 4,
        "gdn_inverse_block": block, "gdn_front_kernel": 0}
    assert gauges(TINY, True) == {
        "gdn_kernel": 0, "gdn_chunk": 8, "gdn_chunks_per_sequence": 4,
        "gdn_inverse_block": block, "gdn_front_kernel": 0}
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import telemetry_report
    events = [dict(kind="gauge", name=n, value=v)
              for n, v in gauges(wide, True).items()]
    text = "\n".join(telemetry_report._gdn_lines(events))
    assert f"(the Pallas kernels) in chunks of {gdn.KERNEL_CHUNK} " in text
    assert f"from diagonal blocks of {block} positions" in text
    assert "the front (convolution, silu, q / k norms): the Pallas kernels" \
        in text


def test_convolution_is_the_explicit_four_tap_sum():
    x = jax.random.normal(jax.random.PRNGKey(1), (9, 5))
    w = jax.random.normal(jax.random.PRNGKey(2), (4, 5))
    xs, ws = np.asarray(x), np.asarray(w)
    want = np.zeros_like(xs)
    for t in range(9):
        for j in range(4):
            if t - 3 + j >= 0:
                want[t] += ws[j] * xs[t - 3 + j]
    assert close(gdn.causal_conv(x, w), want)
    assert close(ref.conv4(x, w), want)
    # causal: position t does not see t + 1
    moved = gdn.causal_conv(x.at[5].add(1.0), w) - gdn.causal_conv(x, w)
    assert not np.any(np.asarray(moved)[:5]) and np.any(np.asarray(moved)[5])


# -- causal attention ----------------------------------------------------------

def dense_causal(q, k, v):
    n = q.shape[2]
    g = q.shape[1] // k.shape[1]
    kk, vv = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    seen = np.arange(n)[:, None] >= np.arange(n)[None, :]
    s = jnp.where(seen, jnp.einsum("shqd,shkd->shqk", q, kk), -jnp.inf)
    return jnp.einsum("shqk,shkd->shqd", jax.nn.softmax(s, -1), vv)


def qkv(n, d, hq=4, hkv=2, s=1):
    mk = lambda i, h: jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(1), i), (s, h, n, d))
    return mk(0, hq) * d ** -0.5, mk(1, hkv), mk(2, hkv)


@pytest.mark.parametrize("tile", [8, 32, 128])
def test_blocked_causal_attention_matches_the_dense_mask(tile):
    q, k, v = qkv(32, 16)
    f = lambda *a: attention.causal_attention(*a, kernels=False, tile=tile)
    assert close(f(q, k, v), dense_causal(q, k, v))
    grad = lambda fn: jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                               argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grad(f), grad(dense_causal)):
        assert close(a, b, 1e-4)


def test_pallas_causal_kernels_match_the_dense_mask_interpreted():
    """The splash kernels under the library's causal mask at head size 256
    (the published one), tiles of 128, in Pallas' interpreter: forward and
    the gradients; bfloat16 operands, so to 2^-6."""
    q, k, v = qkv(256, 256, hq=2, hkv=1)
    f = lambda *a: attention.causal_attention(*a, kernels=True,
                                              interpret=True, tile=128)
    assert close(f(q, k, v), dense_causal(q, k, v), 2.0 ** -6)
    grad = lambda fn: jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                               argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grad(f), grad(dense_causal)):
        assert close(a, b, 2.0 ** -5)


@pytest.mark.parametrize("n,bq,bkv", [(64, 8, 8), (64, 16, 8), (64, 8, 32),
                                      (8192, 1024, 1024), (8192, 512, 512)])
def test_causal_tile_tally_is_the_brute_force_count(n, bq, bkv):
    visited, partial, over = attention.causal_tile_tally(n, bq, bkv)
    if n <= 64:
        seen = np.arange(n)[:, None] >= np.arange(n)[None, :]
        per = seen.reshape(n // bq, bq, n // bkv, bkv)
        some, every = per.any((1, 3)), per.all((1, 3))
        assert (visited, partial) == (some.sum(), (some & ~every).sum())
        assert over == visited * bq * bkv / seen.sum()
    else:       # square tiles: the lower triangle of tiles, diagonal partial
        t = n // bq
        assert (visited, partial) == (t * (t + 1) // 2, t)
        assert 1.0 < over < 1.0 + 1.5 * bq / n


# -- the expert layer's share, with the shared expert --------------------------

def test_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """8 experts as 4 shares of 2: the routed parts the shares give plus
    the shared expert, which every chip computes alike, counted ONCE, are
    what the plain reference gives for the whole layer; the shares' rows
    add up to P * top_k."""
    key = jax.random.PRNGKey(0)
    shapes = dict(router=(64, 8), w_gate=(8, 64, 32), w_up=(8, 64, 32),
                  w_down=(8, 32, 64), shared_gate=(64, 32),
                  shared_up=(64, 32), shared_down=(32, 64),
                  shared_sig=(64, 1))
    p = {name: 0.3 * jax.random.normal(jax.random.fold_in(key, i), shp)
         for i, (name, shp) in enumerate(shapes.items())}
    h = jax.random.normal(jax.random.PRNGKey(1), (48, 64), jnp.float32)
    z = dict(K=2, E=8, held=list(range(8)))
    whole = ref.experts(h, p, z)
    total, rows = moe.shared_expert(h, p), 0
    for held in ((0, 1), (2, 3), (4, 5), (6, 7)):
        idx = np.asarray(held)
        part = dict(p, w_gate=p["w_gate"][idx], w_up=p["w_up"][idx],
                    w_down=p["w_down"][idx])
        out, n, _ = moe.expert_layer(h, part, held=held, num_experts=8,
                                     top_k=2, kernels=False)
        total, rows = total + out, rows + int(n)
    assert rows == 48 * 2
    assert close(total, whole)
    # the gate matters: without it the layer is another layer
    assert not close(total, ref.experts(h, p, z, no_shared_gate=True), 1e-3)


# -- the model and its objective against the plain reference -------------------

def config_for(interval, layers):
    return dict(CONFIG, full_attention_interval=interval,
                num_hidden_layers=layers)


def test_init_is_the_configurations_recipe():
    params, _ = qwen3next.make(TINY)[0](jax.random.PRNGKey(7))
    rparams = ref.init(CONFIG, jax.random.PRNGKey(7))
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    rflat = dict(jax.tree_util.tree_flatten_with_path(rparams)[0])
    assert set(k for k, _ in flat) == set(rflat)
    assert all(np.array_equal(v, rflat[k]) for k, v in flat)
    lin = params["periods"]["linear"]
    assert lin["w_qkvz"].shape == (1, 3, 64, 2 * 16 + 2 * 32)
    assert params["periods"]["full"]["wq"].shape == (1, 64, 2 * 64)
    assert not np.any(lin["ln1"]) and np.all(np.asarray(lin["gdn_norm"]) == 1)
    a = np.exp(np.asarray(lin["A_log"]))
    assert np.all((a >= 1e-3) & (a <= 16.0))
    assert abs(float(jnp.std(params["embed"])) - 1.0) < 0.05


@pytest.mark.parametrize("interval,layers", [(4, 4), (2, 4), (4, 8)],
                         ids=["lllf", "lflf", "two-periods"])
def test_loss_and_every_gradient_leaf_match_the_reference(interval, layers):
    """The pattern of layers comes from the configuration: one period of
    three linear layers and a full one (the published pattern), periods of
    two, and two whole periods; zero-centred gains moved off 0 so that they
    count."""
    config = config_for(interval, layers)
    shape = TINY._replace(interval=interval, layers=layers)
    init_fn, apply_fn = qwen3next.make(shape)
    params, _ = init_fn(jax.random.PRNGKey(0))
    bump = lambda path, a: a + 0.1 if path[-1].key in (
        "ln1", "ln2", "q_norm", "k_norm", "final_norm") else a
    params = jax.tree_util.tree_map_with_path(bump, params)
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
    assert len(flat) == 3 + 16 + 17
    for k, g in flat:
        assert close(g, rflat[k], 2e-4), jax.tree_util.keystr(k)
        assert np.any(np.asarray(g)), jax.tree_util.keystr(k)
    rows, fullest, count, touched = (float(e) for e in extras)
    assert count == 2 * 31 and 0 < fullest <= rows <= touched
    assert touched <= layers * 2 * 32 * 2


def test_next_token_loss_predicts_the_next_token_and_not_the_last():
    """A head that reads the next token off the hidden state scores every
    position but the last; the last position's target is never read."""
    toks = jnp.asarray([[3, 1, 4, 1, 5, 9, 2, 6]], jnp.int32)
    eye = 20.0 * jnp.eye(10, dtype=jnp.float32)
    hidden = eye[jnp.roll(toks, -1, axis=1)].at[0, -1].set(eye[0])
    loss, hit, count = losslib.next_token_head_counts(hidden, eye, toks)
    assert int(count[0]) == 7 and int(hit[0]) == 7 and float(loss[0]) < 1e-6
    wrong = eye[toks]                       # predicts the token itself
    loss, hit, _ = losslib.next_token_head_counts(wrong, eye, toks)
    assert int(hit[0]) == 0 and float(loss[0]) > 10.0


def write_tokens(root, train, heldout):
    os.makedirs(os.path.join(root, "tokens"))
    np.save(os.path.join(root, "tokens", "train.npy"), train)
    np.save(os.path.join(root, "tokens", "heldout.npy"), heldout)


@pytest.mark.parametrize("devices", [1, 2])
def test_three_sgd_steps_and_test_model_through_trainer_match_the_reference(
        tmp_path, devices):
    """`Trainer.train_model` (staged epoch, scanned window, ring drain) and
    `test_model` against the reference followed step by step: three
    losses, the first gradient as the optimizer gets it, the parameters'
    change, the evaluation; on two devices under `ddp`."""
    rng = np.random.default_rng(5)
    b = 4 * devices
    train = rng.integers(0, 63, (4 * b, 32), dtype=np.int32)
    heldout = rng.integers(0, 63, (6, 32), dtype=np.int32)     # ragged eval
    write_tokens(str(tmp_path), train, heldout)
    tel = Telemetry()
    tr = Trainer(model="qwen3-next-tiny", strategy="ddp",
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
    for leaf, norm in want["dparam_norms"].items():
        assert abs(moved[leaf] - norm) <= 1e-3 * norm + 1e-9, leaf
    # counters of the epoch, beside dispatches and host_round_trips
    totals = tel.counter_totals()
    assert totals["dispatches"] == totals["host_round_trips"] == 2
    assert totals["moe_rows_local"] == tr.last_epoch_extras["moe_rows_local"]
    assert totals["moe_rows_expected"] == 3 * b * 32 * 2 * 2 * 4 / 8
    assert totals["tokens_predicted"] == 3 * b * 31
    touched = tr.last_epoch_extras["moe_rows_touched"]
    assert totals["moe_rows_touched"] == touched
    assert totals["moe_rows_local"] <= touched <= 3 * b * 32 * 2 * 4
    steps = [r for r in tel.records if r["kind"] == "step"]
    assert len(steps) == 3 and all("moe_rows_max_expert" in s for s in steps)
    # gauges, once: the recurrence's chunking, the causal kernels' tiles
    gauges = {(r["name"], r.get("kernel")): r["value"] for r in tel.records
              if r["kind"] == "gauge"}
    assert gauges["gdn_kernel", None] == 0          # the CPU: jax.numpy
    assert gauges["gdn_chunk", None] == 8
    assert gauges["gdn_chunks_per_sequence", None] == 4
    assert all(gauges["attn_tiles_visited", k] == 1
               for k in ("fwd", "dkv", "dq"))
    assert {r["rule"] for r in tel.records if r["kind"] == "gauge"
            and r["name"].startswith("attn_")} == {"causal"}
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import telemetry_report
    text = "\n".join(telemetry_report._gdn_lines(tel.records)
                     + telemetry_report._moe_lines(tel.records))
    assert "== linear attention ==" in text
    assert "(jax.numpy) in chunks of 8 positions, 4 a sequence" in text
    assert "the front (convolution, silu, q / k norms): jax.numpy" in text
    assert f"predicted tokens {3 * b * 31:,}" in text


def test_a_share_of_whole_periods_only_and_of_its_own_fields():
    with pytest.raises(ValueError, match="whole periods"):
        models.get_model("qwen3-next-tiny", layers=6)
    with pytest.raises(ValueError, match="block"):
        models.get_model("qwen3-next-tiny", block=4)
    with pytest.raises(ValueError, match="no share"):
        models.get_model("vgg11", layers=2)
    init_fn, apply_fn = models.get_model("qwen3-next-tiny", layers=8,
                                         held=(1, 5), seq_len=16)
    shape = apply_fn.objective.shape
    assert (shape.layers, shape.held, shape.seq_len) == (8, (1, 5), 16)
    params = jax.eval_shape(lambda k: init_fn(k)[0], jax.random.PRNGKey(0))
    assert params["periods"]["linear"]["w_gate"].shape[:3] == (2, 3, 2)


@pytest.mark.parametrize("name", sorted(models.DECODERS))
def test_every_decoder_of_the_table_resolves_at_its_published_share(name):
    """The table of decoders: a name gives a module's base shape; the
    published ones at the shares the benchmark's configurations state."""
    init_fn, apply_fn = models.get_model(name)
    shape = apply_fn.objective.shape
    params = jax.eval_shape(lambda k: init_fn(k)[0], jax.random.PRNGKey(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    want = {"sdar-30b-a3b": (645_623_296, 18992),
            "qwen3-next-80b-a3b": (625_667_136, 18992),
            "xing4.0-29b-a4b": (759_346_446, 16384),
            "mellum2-12b-a2.5b": (624_072_960, 12288),
            "olmo-hybrid-7b": (928_862_196, 12544)}
    if name in want:
        assert (n, shape.vocab) == want[name]
    else:
        assert n < 1_000_000 and shape.vocab == 64


def test_cli_trains_and_evaluates_the_decoder_with_its_shares_flags(tmp_path,
                                                                    capsys):
    """`python -m cs744_ddp_tpu.cli --model qwen3-next-tiny` on the default
    path, the share's flags the decoders have in common; the flag only the
    block-diffusion decoder has is refused by name."""
    argv = ["--model", "qwen3-next-tiny", "--strategy", "ddp",
            "--num-devices", "1", "--batch-size", "4", "--lr", "0.01",
            "--limit-train-batches", "2", "--data-dir", str(tmp_path),
            "--lm-layers", "4", "--lm-experts-held", "0-1",
            "--lm-seq-len", "16"]
    tr = cli.main(argv)
    assert tr.objective.shape.seq_len == 16 and len(tr.train_split) == 64
    out = capsys.readouterr().out
    assert "Test set: Average loss:" in out and "/240 (" in out   # 16 x 15
    with pytest.raises(ValueError, match="block"):
        cli.main(argv + ["--lm-block", "4"])
