"""Token sampling: greedy / temperature / top-k, jitted with the decode
step so sampled ids (not logits) cross the host boundary — [slots] int32
per step instead of [slots, vocab] fp32."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    temperature: float = 0.0     # 0 → greedy
    top_k: int = 0               # 0 → no truncation

    def __post_init__(self):
        if self.temperature < 0:
            raise ValueError('temperature must be >= 0')


@jax.named_scope('sample')
def speculative_accept(logits: jnp.ndarray, drafts: jnp.ndarray,
                       draft_len: jnp.ndarray, key: jax.Array,
                       temperature: jnp.ndarray, top_k: int = 0
                       ) -> "tuple[jnp.ndarray, jnp.ndarray]":
    """Exact-greedy draft acceptance, fused with the verify logits.

    logits: [slots, K+1, vocab] — position i is the model's output
    after verify input token i (input 0 = the slot's last sampled
    token, inputs 1..K = padded draft candidates). drafts: [slots, K]
    int32; draft_len: [slots] int32 valid-draft counts (the static-pad
    active mask); temperature/top_k as in :func:`sample`.

    Returns ``(emitted [slots, K+1] int32, accepted [slots] int32)``:
    ``emitted[:, i]`` is the model's own next token at each position —
    position 0 goes through :func:`sample` (so a temperature>0 slot
    riding the verify program with draft_len=0 samples EXACTLY like
    the decode program), later positions are pure argmax (speculation
    is greedy-only; the engine never drafts for sampled slots).
    ``accepted`` = length of the longest prefix where draft i equals
    the model's prediction at position i — the acceptance rule that
    makes spec-on outputs bit-identical to spec-off: every emitted
    token IS the model's next token; drafts only decide how many land
    per step. The caller emits ``emitted[:, :accepted+1]`` (accepted
    run plus one corrected/bonus token)."""
    slots, k1, _ = logits.shape
    k = k1 - 1
    first = sample(logits[:, 0], key, temperature, top_k=top_k)
    preds = jnp.argmax(logits[:, 1:], axis=-1).astype(jnp.int32)
    emitted = jnp.concatenate([first[:, None], preds], axis=1)
    match = ((drafts == emitted[:, :k])
             & (jnp.arange(k)[None, :] < draft_len[:, None]))
    accepted = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1),
                       axis=1).astype(jnp.int32)
    return emitted, accepted


@jax.named_scope('sample')
def sample(logits: jnp.ndarray, key: jax.Array,
           temperature: jnp.ndarray, top_k: int = 0) -> jnp.ndarray:
    """logits [slots, vocab], temperature [slots] → tokens [slots].

    Per-slot temperature is a traced array (mixed greedy/sampled batches
    in one compiled step); top_k is static (it changes the program).
    """
    if top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][:, -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    greedy = jnp.argmax(logits, axis=-1)
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    sampled = jax.random.categorical(key, logits / temp, axis=-1)
    return jnp.where(temperature > 0, sampled, greedy).astype(jnp.int32)
