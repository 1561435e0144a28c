"""Token sampling — jitted, batched, per-request parameters.

The reference's claimed serving stack (vLLM, ``README.md:10``) samples with
per-request temperature / top-k / top-p; this is the TPU-native equivalent.
One compiled function handles the whole decode batch: every request carries
its own knobs as array entries, so mixed greedy/sampling batches never
recompile.

How a row draws:

* **One Gumbel-max draw in token space.** Each row adds Gumbel noise from its
  own key to ``logits / T`` and takes the ``argmax``: a draw from
  ``softmax(logits / T)``. The noise lies along *token ids* (entry ``i`` of a
  row's noise belongs to token ``i``), never along ranks, so the vocabulary's
  order is not needed to draw.
* **The vocabulary is sorted only when some row of the batch sets top-k or
  top-p** (``lax.cond`` on ``any(top_k > 0) | any(top_p < 1)``, inside the
  same traced function: no flag, no second program). The sorted branch turns
  the rank and cumulative-probability masks into one *threshold logit* per
  row; ``keep = logits >= threshold`` is applied in token space. The other
  branch returns ``-inf`` thresholds without touching the vocabulary's order,
  as does the sorted branch for a row that restricts nothing.
* **A row's draw does not depend on the branch its batch took**: both feed
  the same noise and the same ``argmax``, so a seeded request without
  top-k/top-p yields the same tokens alone, among others like it, or beside a
  row with ``top_p = 0.9`` (what ``SamplingParams.seed`` promises).
* **Ties at the threshold are all kept**: tokens whose logit equals the
  k-th largest (or the nucleus's last) stay in the support, so a row can keep
  more than ``top_k`` tokens when logits tie there (the convention of HF's
  ``TopKLogitsWarper``). Ties elsewhere change nothing.
* ``temperature == 0`` selects ``argmax(logits)`` via ``jnp.where`` on the
  same path (no branch, no recompile).

What the sort costs on the chip (one TPU v5e, 32 rows, this function alone,
host clock over 40 calls; PERF.md section 6, PR 32): a batch without
top-k/top-p rows takes at most 0.40 ms whatever the head's size (the Gumbel
noise and two argmaxes, linear in rows; the same reading at every size, so it
is the host's dispatch floor); a batch with one such row takes 0.60 ms over
32,000 rows, 1.18 ms over 65,536 and 3.96 ms over 152,064 (values only, no
index operand, not stable). Sorting values and indices on every step, as this
function did before, took 0.97 / 1.92 / 6.69 ms: on the 152k-row head more
than half of what reading a 7B model's weights costs, 6.2 ms of every decode
step in the trace.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import jax.numpy as jnp


@dataclass
class SamplingParams:
    """Per-request sampling knobs (OpenAI API semantics)."""

    temperature: float = 1.0
    top_k: int = 0  # 0 = disabled (full vocab)
    top_p: float = 1.0
    max_tokens: int = 128
    stop_token_ids: Sequence[int] = field(default_factory=tuple)
    # Per-request seed: fixes the request's own draw stream regardless of
    # what else shares the decode batch (engine folds it per emitted token).
    seed: Optional[int] = None
    # Whether the server should return logprobs in the API response (they
    # are always computed device-side; this is a response-shaping flag).
    logprobs: bool = False

    def greedy(self) -> bool:
        return self.temperature == 0.0


def sample_tokens(
    logits: jnp.ndarray,
    rng: jax.Array,
    temperature: jnp.ndarray,
    top_k: jnp.ndarray,
    top_p: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Sample one token per row.

    Args:
      logits: (batch, vocab) float32.
      rng: a single PRNG key (split per-row internally) or a batch of
        per-row keys of shape (batch, 2) — the engine passes per-request
        keys so ``SamplingParams.seed`` reproduces a request's draw stream
        independent of what else is in the batch.
      temperature: (batch,) float32; 0 => greedy (argmax).
      top_k: (batch,) int32; 0 => disabled.
      top_p: (batch,) float32; 1.0 => disabled.

    Returns:
      (tokens (batch,) int32, logprob of each sampled token (batch,) float32).

    A row keeps the tokens whose logit is at least its threshold: the logit
    at the last rank that top-k and top-p (on the full, temperature-scaled
    distribution) both admit. Tokens that tie with the threshold are all
    kept. The sort that finds the thresholds runs only if some row sets
    top-k or top-p; a row that sets neither draws the same token whichever
    branch its batch took (module docstring).
    """
    b, v = logits.shape
    k_on = top_k > 0
    p_on = top_p < 1.0
    # Scale by temperature (guard 0 for the greedy rows).
    safe_t = jnp.where(temperature > 0, temperature, 1.0)[:, None]

    def sorted_thresholds():
        # Descending values; ranks and cumulative probabilities in sorted
        # space, as top-k / top-p are defined. Both masks are prefixes of the
        # sorted row, so the kept set is "the first n_keep ranks" and the
        # logit at rank n_keep - 1 is the row's threshold.
        sorted_logits = -jnp.sort(-logits, axis=-1, stable=False)
        probs = jax.nn.softmax(sorted_logits / safe_t, axis=-1)
        # Keep tokens while cumulative prob *before* this token < top_p
        # (always keeps the head token).
        cum_before = jnp.cumsum(probs, axis=-1) - probs
        ranks = jnp.arange(v, dtype=jnp.int32)[None, :]
        k = jnp.where(k_on, top_k, v).astype(jnp.int32)[:, None]
        keep = (ranks < k) & (cum_before < top_p[:, None])
        n_keep = jnp.maximum(jnp.sum(keep, axis=-1, dtype=jnp.int32), 1)
        thresh = jnp.take_along_axis(
            sorted_logits, (n_keep - 1)[:, None], axis=1)[:, 0]
        # A row that restricts nothing keeps everything, exactly (float32
        # cumulative sums may pass 1.0 before the row's end).
        return jnp.where(k_on | p_on, thresh, -jnp.inf)

    def no_thresholds():
        return jnp.full((b,), -jnp.inf, logits.dtype)

    thresh = jax.lax.cond(jnp.any(k_on) | jnp.any(p_on),
                          sorted_thresholds, no_thresholds)

    masked = jnp.where(logits >= thresh[:, None], logits / safe_t, -jnp.inf)
    rngs = rng if rng.ndim == 2 else jax.random.split(rng, b)
    gumbel = jax.vmap(lambda r: jax.random.gumbel(r, (v,), masked.dtype))(rngs)
    sampled = jnp.argmax(masked + gumbel, axis=-1)

    tokens = jnp.where(temperature > 0, sampled, jnp.argmax(logits, axis=-1))

    # Log-prob of the chosen token under the *unmasked, unscaled* distribution
    # (what the OpenAI API reports).
    logz = jax.nn.logsumexp(logits, axis=-1)
    chosen_logit = jnp.take_along_axis(logits, tokens[:, None], axis=1)[:, 0]
    return tokens.astype(jnp.int32), chosen_logit - logz
