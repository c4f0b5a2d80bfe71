"""Cross-entropy loss with torch.nn.CrossEntropyLoss semantics.

The reference uses ``torch.nn.CrossEntropyLoss()`` (mean reduction) as the
training and evaluation criterion (``/root/reference/src/Part 1/main.py:110``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean over batch of -log softmax(logits)[label].

    logits: [N, C] float; labels: [N] int.  Computed via log-sum-exp for
    stability (identical math to torch's CrossEntropyLoss mean reduction).
    Always reduced in f32 so bf16 compute mode keeps a full-precision loss.
    """
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - picked)


def accuracy_counts(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Number of correct argmax predictions (reference main.py:69-71)."""
    return jnp.sum(jnp.argmax(logits, axis=-1) == labels)


def blockdiff_noise(key: jax.Array, tokens: jax.Array, block: int,
                    mask_id: int):
    """The block-diffusion noising of a batch of sequences [S, L].

    Per block a ratio t ~ U[1/block, 1]; each token of the block becomes
    `mask_id` with probability t, independently; a block that drew no mask
    gets its first token masked.  Returns (xt [S, L], masked [S, L] bool,
    weight [S, L] = 1/t of the position's block)."""
    s, length = tokens.shape
    kt, km = jax.random.split(key)
    t = 1.0 / block + (1.0 - 1.0 / block) * jax.random.uniform(
        kt, (s, length // block), jnp.float32)
    u = jax.random.uniform(km, (s, length // block, block), jnp.float32)
    masked = u < t[..., None]
    none = ~jnp.any(masked, axis=-1)
    masked = masked.at[..., 0].set(masked[..., 0] | none).reshape(s, length)
    xt = jnp.where(masked, jnp.int32(mask_id), tokens)
    return xt, masked, jnp.repeat(1.0 / t, block, axis=-1)


def blockdiff_head_counts(hidden: jax.Array, w_head: jax.Array,
                          targets: jax.Array, masked: jax.Array,
                          weight: jax.Array):
    """Head + masked-diffusion cross-entropy, one sequence at a time.

    hidden [S, L, H] (the noisy half, after the final norm), w_head [H, V],
    targets / masked / weight [S, L].  The logit at a position predicts that
    position's own token (no shift).  Returns per sequence (loss [S]: the
    sum over masked positions of weight * -log softmax(logits)[target],
    over L; correct [S]: masked positions whose largest logit is right;
    count [S]: masked positions).  A sequence's [L, V] logits exist only
    while it is being worked on, forward and backward."""
    length = hidden.shape[1]

    @jax.checkpoint
    def one(args):
        h, y, m, w = args
        with jax.named_scope("lm_head"):
            logits = jnp.dot(h, w_head.astype(h.dtype),
                             preferred_element_type=jnp.float32)
            logz = jax.scipy.special.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
            loss = jnp.sum(jnp.where(m, w * (logz - picked), 0.0)) / length
            hit = jnp.sum(m & (jnp.argmax(logits, axis=-1) == y))
        return loss, hit.astype(jnp.int32), jnp.sum(m).astype(jnp.int32)

    return jax.lax.map(one, (hidden, targets, masked, weight))


def next_token_head_counts(hidden: jax.Array, w_head: jax.Array,
                           tokens: jax.Array):
    """Head + next-token cross-entropy, one sequence at a time: the logit
    at position t predicts token t + 1, every position but the last.
    hidden [S, L, H], tokens [S, L].  Returns per sequence (loss [S]: the
    mean of -log softmax(logits)[next token] over the L - 1 predicted
    positions; correct [S]; count [S] = L - 1): `blockdiff_head_counts` on
    shifted targets."""
    length = tokens.shape[1]
    predicted = jnp.broadcast_to(jnp.arange(length) < length - 1,
                                 tokens.shape)
    weight = jnp.full(tokens.shape, length / (length - 1.0), jnp.float32)
    return blockdiff_head_counts(hidden, w_head, jnp.roll(tokens, -1, axis=1),
                                 predicted, weight)
