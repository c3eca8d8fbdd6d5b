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
