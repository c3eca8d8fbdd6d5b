"""Next-token cross-entropy over an output head (the tied embedding, or
a matrix of the model's own), never holding the whole logit matrix.

At 32,768 tokens over a 16,384-row vocabulary slice the float32 logits
are 2 GiB (and as much again for their cotangent).  The hidden states
are cut along the token axis into chunks; each chunk's logits are made,
reduced to ``logsumexp - logit[target]`` and dropped, and the backward
recomputes them chunk by chunk (``jax.checkpoint`` inside a
``lax.scan``): one chunk's logits are live at a time, in either pass.

Device scopes: the head product is ``dsod.heads``, the reduction
``dsod.loss`` — siblings, so the stage table books each to its own.

A looped model (``models/ouro.py``) reads its head after every pass and
weights each token's cross-entropies by a learned exit distribution:
:func:`exit_weighted_cross_entropy` is the same chunked, recomputed
form kept PER TOKEN AND PER PASS.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

CHUNK = 4096


def _chunk(n_tokens: int, chunk: int) -> int:
    """The largest divisor of ``n_tokens`` not above ``chunk``."""
    return next(c for c in range(min(chunk, n_tokens), 0, -1)
                if n_tokens % c == 0)


def tied_cross_entropy(hidden, embedding, targets, *, chunk: int = CHUNK):
    """Mean over all tokens of ``logsumexp(h E^T) - (h E^T)[target]``.

    hidden: [..., D] in the compute dtype (after the final norm);
    embedding: [V, D], the head's rows of the vocabulary held (lfm2:
    the embedding itself; kimi: ``head/embedding``);
    targets: [...] int, ids inside the slice.  The product runs in
    ``hidden.dtype`` with float32 accumulation; the reduction is float32.
    """
    d = hidden.shape[-1]
    h = hidden.reshape(-1, d)
    t = targets.reshape(-1)
    c = _chunk(h.shape[0], chunk)
    with jax.named_scope("dsod.heads"):
        e = embedding.astype(hidden.dtype)

    @jax.checkpoint
    def one(total, ht):
        hc, tc = ht
        with jax.named_scope("dsod.heads"):
            z = lax.dot_general(hc, e, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        with jax.named_scope("dsod.loss"):
            lse = jax.nn.logsumexp(z, axis=-1)
            hit = jnp.take_along_axis(z, tc[:, None], axis=-1)[:, 0]
            return total + jnp.sum(lse - hit), None

    total, _ = lax.scan(one, jnp.float32(0.0),
                        (h.reshape(-1, c, d), t.reshape(-1, c)))
    with jax.named_scope("dsod.loss"):
        return total / h.shape[0]


def exit_distribution(gate_logits):
    """gate_logits [R, ...] float32 -> (p, log p) [R, ...]: ``p_t =
    sigmoid(g_t) prod_{j<t} (1 - sigmoid(g_j))`` for t < R and ``p_R``
    the rest, from log-sigmoids.  The last logit is not read."""
    g = gate_logits[:-1].astype(jnp.float32)
    stay = jnp.cumsum(jax.nn.log_sigmoid(-g), axis=0)   # log prod (1 - l)
    before = jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]], 0)
    logp = jnp.concatenate([jax.nn.log_sigmoid(g) + before, stay[-1:]], 0)
    return jnp.exp(logp), logp


def exit_weighted_cross_entropy(states, gate_logits, embedding, targets, *,
                                beta: float, chunk: int = CHUNK):
    """``mean_i [sum_t p_ti CE(h_ti E^T, target_i) - beta H(p_.i)]`` and
    its counters.

    states: [R, ..., D], the R passes' normed hidden states in the
    compute dtype; gate_logits: [R, ...] float32; embedding: [V, D], the
    head; targets: [...] int.  Differentiable in the states, the head
    AND the gate (through ``p`` and through the entropy).  The R x T
    rows go through ONE scan a chunk of one pass's tokens at a time, so
    the head matrix is read once a (chunk, pass) and one chunk's
    [chunk, V] float32 logits are live at a time in either direction;
    the weights meet the per-row cross-entropies outside the scan.
    Returns ``(total, counters)``: ``loop_ce_t``, ``loop_exit_mass_t``
    (means over tokens, t = 1..R) and ``loop_exit_entropy`` (nats).
    """
    r, d = states.shape[0], states.shape[-1]
    h = states.reshape(-1, d)
    t = jnp.broadcast_to(targets.reshape(1, -1), (r, targets.size))
    c = _chunk(targets.size, chunk)
    with jax.named_scope("dsod.heads"):
        e = embedding.astype(states.dtype)

    @jax.checkpoint
    def one(_, ht):
        hc, tc = ht
        with jax.named_scope("dsod.heads"):
            z = lax.dot_general(hc, e, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        with jax.named_scope("dsod.loss"):
            hit = jnp.take_along_axis(z, tc[:, None], axis=-1)[:, 0]
            return None, jax.nn.logsumexp(z, axis=-1) - hit

    _, ce = lax.scan(one, None, (h.reshape(-1, c, d), t.reshape(-1, c)))
    with jax.named_scope("dsod.loss"):
        ce = ce.reshape(r, -1)
        with jax.named_scope("dsod.loop.exit"):
            p, logp = exit_distribution(gate_logits.reshape(r, -1))
            entropy = -jnp.sum(p * logp, axis=0)
        total = jnp.mean(jnp.sum(p * ce, axis=0) - beta * entropy)
        counters = {"loop_exit_entropy": jnp.mean(entropy)}
        for i in range(r):
            counters[f"loop_ce_{i + 1}"] = jnp.mean(ce[i])
            counters[f"loop_exit_mass_{i + 1}"] = jnp.mean(p[i])
        return total, lax.stop_gradient(counters)
