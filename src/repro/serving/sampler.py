"""Token sampling: greedy / temperature / top-k / top-p.

Fully jittable, in two flavors:

- ``sample`` — engine-wide static :class:`SamplingConfig`; branches are
  resolved at trace time (the cheapest path when every row shares one
  config — e.g. a greedy benchmark).
- ``sample_batched`` — **per-row** (per-slot) traced parameters, so one
  continuous-batching decode dispatch can serve heterogeneous requests:
  slot 0 greedy, slot 1 at temperature 1.2/top-k 40, in the same
  ``(B, V)`` logits block. Greedy rows (``temperature <= 0``) are exact
  argmax and never consume randomness, so a request's greedy stream is
  bit-identical regardless of which sampling configs its batch
  neighbours use.

Contract: logits ``(B, V)`` → tokens ``(B,)`` everywhere (prefill and
decode use the same call; no reshape contortions at call sites).

Stochastic draws fold the batch-row index into the step key, so each
row draws from its own stream regardless of batch width or of which
other rows happen to be active that step. (In the decode megastep the
row IS the slot; in batched prefill it is the position within the
length bucket, so stochastic first tokens depend on bucket grouping.)
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class SamplingConfig:
    temperature: float = 0.0    # 0 → greedy
    top_k: int = 0              # 0 → off
    top_p: float = 1.0          # 1 → off


@jax.named_scope("sampler")
def sample_batched(logits: jax.Array, rng: jax.Array,
                   temperature: jax.Array, top_k: jax.Array,
                   top_p: jax.Array) -> jax.Array:
    """Per-row sampling: logits (B, V) + per-row params (B,) → (B,).

    Rows with ``temperature <= 0`` return ``argmax`` (no PRNG use);
    ``top_k == 0`` / ``top_p >= 1`` disable the respective filter for
    that row. Filters apply in the same order as the static path
    (top-k, then top-p over the filtered logits) so the two flavors
    draw identical tokens for identical parameters. Its device ops,
    like ``sample``'s, run in the ``sampler`` named scope.
    """
    B, V = logits.shape
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t = jnp.asarray(temperature, jnp.float32)
    k = jnp.asarray(top_k, jnp.int32)
    p = jnp.asarray(top_p, jnp.float32)
    lf = logits.astype(jnp.float32) / jnp.where(t > 0.0, t, 1.0)[:, None]

    # top-k: kth-largest per row via one ascending sort
    asc = jnp.sort(lf, axis=-1)
    kth = jnp.take_along_axis(
        asc, jnp.clip(V - k, 0, V - 1)[:, None], axis=-1)
    lf = jnp.where((k > 0)[:, None] & (lf < kth), -jnp.inf, lf)

    # top-p over the (top-k-filtered) logits
    desc = jnp.sort(lf, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cutoff_idx = jnp.sum(cum < p[:, None], axis=-1)
    cutoff = jnp.take_along_axis(
        desc, jnp.clip(cutoff_idx, 0, V - 1)[:, None], axis=-1)
    lf = jnp.where((p < 1.0)[:, None] & (lf < cutoff), -jnp.inf, lf)

    keys = jax.vmap(lambda i: jax.random.fold_in(rng, i))(jnp.arange(B))
    drawn = jax.vmap(
        lambda l, key: jax.random.categorical(key, l, axis=-1)
    )(lf, keys).astype(jnp.int32)
    return jnp.where(t > 0.0, drawn, greedy)


def sample(logits: jax.Array, rng: jax.Array,
           cfg: SamplingConfig) -> jax.Array:
    """logits: (B, V) → tokens (B,). Pure/jittable (cfg is static)."""
    if cfg.temperature <= 0.0:
        with jax.named_scope("sampler"):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    B = logits.shape[0]
    return sample_batched(
        logits, rng,
        jnp.full((B,), cfg.temperature, jnp.float32),
        jnp.full((B,), cfg.top_k, jnp.int32),
        jnp.full((B,), cfg.top_p, jnp.float32))
