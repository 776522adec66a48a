"""Token sampling with the Goldschmidt softmax on the hot path.

``sample_tokens`` is pure and jittable — the engine fuses it with the
decode step so the per-token argmax/sampling runs on-device and only the
chosen token ids cross to the host (no per-token logits transfer).

Both paths route the probability normalization through
``policy.softmax`` — a Goldschmidt reciprocal of the denominator — so
division sits on the sampling hot path exactly like in the attention
epilogues.  Greedy takes argmax over those probabilities (the per-row
reciprocal is a single positive factor, so the ordering is the logits'
ordering); stochastic sampling inverts the CDF at a uniform draw.
``temperature`` may be a (b,) vector so greedy and sampling requests
share one fused tick.  ``top_k`` is either a static int (one k for the
whole batch — shapes the lowering) or a **(b,) vector of per-row k**
paired with a static ``max_top_k`` bound: the lowering takes the top
``max_top_k`` once and each row picks its own kth threshold, so requests
with different ``SamplingParams.top_k`` share one fused tick.  A row
with ``k == 0`` keeps the full vocab.  When every row carries the same
k, the vector path masks exactly the same logits as the static path
(same kth threshold), so the two are token-for-token interchangeable.

``key`` may be a single typed PRNG key (one draw broadcast over rows —
the legacy tick-stream shape) or a **(b,) vector of typed keys**, one
independent stream per row.  The engine uses the vector form with keys
folded from ``(request id, sequence position)`` so the draw for token t
of request r is a pure function of (seed, r, t) — invariant to slot
assignment, scheduler interleaving and pool width (see engine.py
"Scheduler-invariant sampling").
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import NumericsPolicy
from repro.layers.attention import NEG_INF  # the shared masking constant


@jax.named_scope("sampler")
def sample_tokens(
    logits: jnp.ndarray,  # (b, V) last-position logits
    *,
    policy: NumericsPolicy,
    temperature=0.0,  # python float or (b,) array; 0 -> greedy per row
    top_k=0,          # static int (0 = full vocab) or (b,) per-row array
    max_top_k: Optional[int] = None,  # static bound, required w/ array top_k
    key: Optional[jax.Array] = None,  # single key or (b,) per-row keys;
    # required when any row samples
) -> jnp.ndarray:
    """Returns (b,) int32 token ids."""
    lf = logits.astype(jnp.float32)
    if top_k is None or isinstance(top_k, (int, np.integer)):
        if top_k:
            kth = jax.lax.top_k(lf, int(top_k))[0][..., -1:]
            lf = jnp.where(lf >= kth, lf, NEG_INF)  # kth-value ties stay
    else:
        if not max_top_k:
            raise ValueError("array top_k needs a static max_top_k bound")
        kvec = jnp.asarray(top_k, jnp.int32)
        vals = jax.lax.top_k(lf, int(max_top_k))[0]  # (b, K) sorted desc
        kth = jnp.take_along_axis(
            vals, jnp.clip(kvec - 1, 0, int(max_top_k) - 1)[:, None], axis=1)
        # same mask as the static path per row; k == 0 rows stay unmasked
        lf = jnp.where((kvec[:, None] > 0) & (lf < kth), NEG_INF, lf)

    temp = jnp.asarray(temperature, jnp.float32)
    stochastic = key is not None
    scale = jnp.where(temp > 0, temp, 1.0) if stochastic else 1.0
    probs = policy.softmax(lf / jnp.reshape(scale, (-1, 1)), axis=-1) \
        if stochastic else policy.softmax(lf, axis=-1)
    greedy = jnp.argmax(probs, axis=-1).astype(jnp.int32)
    if not stochastic:
        return greedy

    # minval keeps u strictly positive: u == 0 would satisfy cdf >= u*total
    # at index 0 even when token 0 is top-k-masked (probability 0)
    tiny = jnp.finfo(jnp.float32).tiny
    if (jnp.ndim(key) == 1
            and jax.dtypes.issubdtype(key.dtype, jax.dtypes.prng_key)):
        u = jax.vmap(lambda k: jax.random.uniform(
            k, (1,), jnp.float32, minval=tiny))(key)
    else:
        u = jax.random.uniform(key, (lf.shape[0], 1), jnp.float32,
                               minval=tiny)
    cdf = jnp.cumsum(probs, axis=-1)
    drawn = jnp.argmax(cdf >= u * cdf[:, -1:], axis=-1).astype(jnp.int32)
    temp_rows = jnp.broadcast_to(jnp.atleast_1d(temp), (lf.shape[0],))
    return jnp.where(temp_rows > 0, drawn, greedy)
