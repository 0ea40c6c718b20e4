"""Token sampling on device: temperature / top-k / top-p / greedy.

One jit-traced function over the whole batch; per-request knobs arrive as
arrays so one compiled program serves any mix of greedy and sampled
sequences (no recompilation per sampling config).

Also hosts the speculative-decoding acceptance rule
(``accept_draft_tokens``): the host-side half of the verify step that
turns per-position target samples plus a draft into the emitted window.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SamplingInputs:
    temperature: jax.Array  # [B] f32; <=1e-5 means greedy
    top_k: jax.Array  # [B] i32; 0 disables
    top_p: jax.Array  # [B] f32; 1.0 disables
    # Per-row PRNG seed: rows with SamplingParams.seed get a deterministic
    # seed derived from (seed, output position); others get engine-RNG draws.
    seeds: jax.Array  # [B] u32


@jax.named_scope("llmd.sampler")
def sample_tokens(
    logits: jax.Array, s: SamplingInputs, all_greedy: bool = False
) -> tuple[jax.Array, jax.Array]:
    """Returns (token_ids [B] i32, logprobs [B] f32 of the chosen token).

    ``all_greedy`` is a trace-time flag (the host knows the batch's sampling
    mix): it elides the sort/top-k/top-p/gumbel pipeline entirely, which
    matters at TPU vocab sizes (two [B, 128k] sorts per decode step).
    """
    B, V = logits.shape
    logits = logits.astype(jnp.float32)
    greedy_tok = jnp.argmax(logits, axis=-1)
    if all_greedy:
        logp = jax.nn.log_softmax(logits, axis=-1)
        chosen = jnp.take_along_axis(logp, greedy_tok[:, None], axis=-1)[:, 0]
        return greedy_tok.astype(jnp.int32), chosen

    temp = jnp.maximum(s.temperature, 1e-5)[:, None]
    scaled = logits / temp

    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]  # [B, V]
    # top-k: keep values >= k-th largest (k=0 -> keep all).
    k = jnp.where(s.top_k > 0, s.top_k, V)
    kth = jnp.take_along_axis(sorted_desc, (k - 1)[:, None], axis=-1)
    scaled = jnp.where(scaled >= kth, scaled, -jnp.inf)

    # top-p (nucleus): smallest prefix of the sorted dist with mass >= top_p.
    probs_sorted = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs_sorted, axis=-1)
    keep_sorted = (cum - probs_sorted) < s.top_p[:, None]  # always keeps rank 0
    num_keep = jnp.maximum(jnp.sum(keep_sorted, axis=-1), 1)
    p_thresh = jnp.take_along_axis(sorted_desc, (num_keep - 1)[:, None], axis=-1)
    scaled = jnp.where(scaled >= p_thresh, scaled, -jnp.inf)

    keys = jax.vmap(jax.random.key)(s.seeds)
    gumbel = jax.vmap(lambda k: jax.random.gumbel(k, (V,), jnp.float32))(keys)
    sampled_tok = jnp.argmax(scaled + gumbel, axis=-1)

    tokens = jnp.where(s.temperature <= 1e-5, greedy_tok, sampled_tok)
    logp = jax.nn.log_softmax(logits, axis=-1)
    chosen_logp = jnp.take_along_axis(logp, tokens[:, None], axis=-1)[:, 0]
    return tokens.astype(jnp.int32), chosen_logp


def spec_seed(seed: int, out_index: int) -> int:
    """The per-(request seed, output index) sampling-seed derivation —
    THE one definition every dispatch path uses (prefill, fused decode
    windows and the one-shot verify step, all on host in
    ``ModelRunner._overwrite_seeded_rows``), which is what keeps seeded
    speculative streams byte-identical whichever path samples a given
    output index."""
    return (seed * 1000003 + out_index) & 0xFFFFFFFF


def accept_counts(draft, target, draft_len):
    """Vectorized Leviathan-style acceptance rule (numpy), behind
    ``accept_draft_tokens``.

    ``draft [..., k]`` vs ``target [..., >=k]`` (the target model's
    per-position samples), with ``draft_len [...]`` masking each row's
    real draft width. Returns ``(n_emit, n_acc)``: ``n_acc`` is the
    longest accepted prefix (leading run of draft[j] == target[j] with
    j < draft_len) and ``n_emit = n_acc + 1`` — the accepted drafts
    plus the correction/bonus sample that always lands.
    """
    k = draft.shape[-1]
    idx = np.arange(k)
    matches = (draft == target[..., :k]) & (idx < draft_len[..., None])
    n_acc = np.sum(np.cumprod(matches.astype(np.int32), axis=-1), axis=-1)
    return n_acc + 1, n_acc


def accept_draft_tokens(
    draft: list[int], sampled: list[int]
) -> tuple[list[int], int]:
    """Speculative-decoding acceptance: longest draft prefix consistent
    with the target distribution.

    ``sampled[j]`` is the token the TARGET model samples at drafted
    position j (greedy argmax, or the per-(seed, output-index) PRNG draw
    for seeded rows) — computed in one verify pass whose position-j
    context is ``draft[:j]``. That context is valid exactly while every
    prior draft token matched its target sample, so the emitted window is
    ``sampled[0 .. m]`` where m is the first mismatch (the target's
    correction token lands for free at the mismatch position, and the
    bonus sample at the end when the whole draft holds). Every emitted
    token IS a target sample under a correct context, which is why
    speculative streams are byte-identical to non-speculative ones for
    greedy and seeded rows (Leviathan et al. 2023 specialized to
    deterministic per-position sampling).

    Returns (emitted window, number of draft tokens accepted).
    """
    if not sampled:
        return [], 0
    k = min(len(draft), len(sampled))
    d = np.asarray(draft[:k], np.int64).reshape(1, k)
    t = np.asarray(sampled[:k], np.int64).reshape(1, k)
    _, n_acc = accept_counts(d, t, np.asarray([k]))
    n_acc = int(n_acc[0])
    n_emit = min(n_acc + 1, len(sampled))
    return [int(tok) for tok in sampled[:n_emit]], n_acc
