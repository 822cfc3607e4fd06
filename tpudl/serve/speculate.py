"""Speculative decoding: draft k tokens cheap, verify them in one
target dispatch (Leviathan et al., "Fast Inference from Transformers
via Speculative Decoding").

TPOT's floor is one target-model dispatch per output token — every
weight byte read per token. Speculation attacks exactly that: a cheap
DRAFT path (here a quantized self-draft built by ``tpudl.quant``, or
any companion model sharing the tokenizer) proposes ``k`` tokens per
slot with k single-token paged dispatches, then the target model
scores the whole window in ONE slot-batched chunk dispatch
(``tpudl.models.generate.paged_chunk_decode_fn``) and an acceptance
rule keeps the output distribution:

- **greedy** requests accept the longest prefix where the target's
  argmax agrees with the proposal; the first disagreement is REPLACED
  by the target's own choice — so the emitted stream is exactly what
  non-speculative greedy decoding would produce (modulo near-tie flips
  between the chunked and single-token programs, which is why the
  parity gate is ``assert_serving_parity``'s teacher-forced margin
  mode).
- **sampled** requests run acceptance sampling: proposal ``x ~ q`` is
  kept with probability ``min(1, p(x)/q(x))``; a rejection draws from
  the residual ``max(p - q, 0)`` and ends the window. The marginal
  distribution of every emitted token is exactly ``p`` — same
  distribution, different schedule. Randomness is per-request
  counter-keyed (Philox on ``(request.seed, token_index)``), so a
  sampled request reproduces its tokens across runs like the engine's
  ``fold_in`` stream (the two streams differ — speculation changes
  WHICH uniforms are consumed — so sampled outputs match themselves,
  not the non-speculative stream).

Rollback is pure per-slot bookkeeping on the paged substrate: the
verify dispatch wrote the whole window into the slot's reserved pages,
and a rejected tail is abandoned by simply not advancing ``lens`` past
the accepted count — the garbage rows are masked (attention stops at
``lens``) and overwritten by the next window. No shared write index
exists to unwind (PR 8), which is what makes per-slot rollback free.

Draft and target stay in LOCKSTEP by construction: both caches see the
same input tokens at the same positions — the window is
``[t_last, p_1 .. p_{k-1}]`` for both — and both advance ``lens`` by
the emitted count. A fully-accepted window therefore emits k tokens
(no separate bonus token: the bonus would desynchronize the draft,
whose cache never saw ``p_k``).

The engine drives this via ``Engine._spec_step``; ``Speculator`` owns
the draft programs + draft KV cache; the acceptance rules live here as
pure host functions so they unit-test without a model.
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import numpy as np

from tpudl.serve.api import left_pad


def _philox(seed: int, token_index: int, salt: int) -> np.random.Generator:
    """Counter-keyed per-(request, position) randomness: deterministic
    across runs and batch compositions, never reused across the
    (propose, accept, residual) roles (``salt``)."""
    return np.random.Generator(
        np.random.Philox(key=[
            ((seed & 0xFFFFFFFF) << 32) | (token_index & 0xFFFFFFFF),
            (salt << 16) | 0x5BEC,
        ])
    )


def softmax(logits: np.ndarray, temperature: float) -> np.ndarray:
    """Temperature-scaled softmax in f64 on the host (the acceptance
    ratio p/q is a ratio of tiny numbers; f32 underflow would bias
    it)."""
    x = np.asarray(logits, np.float64) / max(temperature, 1e-8)
    x = x - x.max()
    e = np.exp(x)
    return e / e.sum()


def sample_from(probs: np.ndarray, u: float) -> int:
    """Inverse-CDF draw: the single-uniform sampling primitive both the
    draft proposal and the residual draw use."""
    cdf = np.cumsum(probs)
    return int(np.searchsorted(cdf, u * cdf[-1], side="right").clip(
        0, len(probs) - 1
    ))


def greedy_accept(
    proposals: Sequence[int], target_choice: Sequence[int]
) -> Tuple[List[int], int]:
    """Greedy acceptance: emit the target's choice at every position,
    stopping after the first one that disagrees with the proposal.
    Returns ``(emitted_tokens, accepted_count)`` — emitted is the
    accepted prefix plus (on disagreement) the target's correction, so
    the stream equals non-speculative greedy decoding exactly."""
    emitted: List[int] = []
    accepted = 0
    for p, t in zip(proposals, target_choice):
        emitted.append(int(t))
        if int(p) == int(t):
            accepted += 1
        else:
            break
    return emitted, accepted


def sample_accept(
    proposals: Sequence[int],
    q_probs: Sequence[np.ndarray],
    p_probs: Sequence[np.ndarray],
    seed: int,
    token_index: int,
) -> Tuple[List[int], int]:
    """Leviathan acceptance sampling over one window: keep ``x ~ q``
    with probability ``min(1, p(x)/q(x))``; on rejection draw from the
    normalized residual ``max(p - q, 0)`` and end the window. Each
    emitted token is marginally distributed exactly as ``p`` — the
    output-distribution-preserving property speculation promises.
    ``token_index`` is the absolute index of the window's first token
    in the request's stream (keys the per-position Philox counters)."""
    emitted: List[int] = []
    accepted = 0
    for j, (x, q, p) in enumerate(zip(proposals, q_probs, p_probs)):
        x = int(x)
        u = float(_philox(seed, token_index + j, salt=2).random())
        qx, px = float(q[x]), float(p[x])
        if qx <= 0.0 or u * qx <= px:
            emitted.append(x)
            accepted += 1
            continue
        residual = np.maximum(np.asarray(p, np.float64) - q, 0.0)
        total = residual.sum()
        if total <= 0.0:
            # p <= q everywhere means p == q (both sum to 1): rejection
            # was a measure-zero numerical fluke — draw from p itself.
            residual, total = np.asarray(p, np.float64), 1.0
        r = float(_philox(seed, token_index + j, salt=3).random())
        emitted.append(sample_from(residual / total, r))
        break
    return emitted, accepted


class Speculator:
    """The draft half of speculative serving: a quantized self-draft
    (or companion) model with its OWN paged KV cache, kept in lockstep
    with the target engine's cache (same seat geometry, same fed
    tokens, same per-slot lens advance). The engine calls ``seat`` /
    ``propose`` / ``rollback`` / ``free``; everything device-side rides
    the same paged decode contract as the target."""

    def __init__(
        self,
        prefill_call: Callable,
        decode_call: Callable,
        params: Any,
        cache,
        k: int,
        weight_bytes: Optional[int] = None,
    ):
        if k < 1:
            raise ValueError(f"spec_k must be >= 1, got {k}")
        self.prefill_call = prefill_call
        self.decode_call = decode_call
        self.params = params
        self.cache = cache  # a PagedKVCache (plain, pad-aligned seating)
        self.k = int(k)
        #: Resident draft weight bytes (the bench's bytes/token model).
        self.weight_bytes = weight_bytes

    # -- slot lifecycle (mirrors the target cache) ----------------------

    def seat(self, slot: int, input_ids, prompt_len: int,
             reserve_tokens: int) -> None:
        """Draft-prefill the request (left-padded batch-1, exactly like
        the engine's own seat) and seat its draft KV row — the draft's
        own view of the prompt (its KV differs from the target's, so
        sharing a cache is impossible by construction)."""
        _, row_cache, *_ = self.prefill_call(
            self.params, *left_pad(input_ids, prompt_len)
        )
        self.cache.seat(
            row_cache, slot, prompt_len - len(input_ids), prompt_len,
            reserve_tokens,
        )

    def compile_seat(self, prompt_len: int) -> None:
        """The draft's prefill and seat at rows of ``prompt_len``,
        compiled and run once, dry (``Engine.compile_prefill_lengths``)."""
        _, row_cache, *_ = self.prefill_call(
            self.params, *left_pad([0], prompt_len)
        )
        self.cache.compile_seat(row_cache, prompt_len)

    def free(self, slot: int) -> None:
        self.cache.free(slot)

    def sync_len(self, slot: int, target_len_delta: int) -> None:
        """Advance the draft's lens by the emitted count (= the
        target's advance): the lockstep rollback — proposals past the
        accepted tail are simply never acknowledged."""
        self.cache.advance([slot], target_len_delta)

    # -- migration (the draft remainder of the PR 13 payload) -----------

    def export_slot(self, slot: int, input_ids,
                    reserve_tokens: int) -> bytes:
        """Serialize this slot's draft KV as its own nested migration
        payload (same pack/crc format as the target's — the draft cache
        IS a PagedKVCache). The draft's rows differ from the target's
        (different model), so they must ship as bytes; what makes the
        transfer small is that the draft model is the quantized
        self-draft. Non-destructive, like the cache export."""
        meta = {
            "request": {"input_ids": [int(t) for t in input_ids]},
            "reserve_tokens": int(reserve_tokens),
        }
        return self.cache.export_request(slot, meta)

    def import_slot(self, slot: int, draft_payload) -> None:
        """Seat a nested draft payload into this speculator's cache at
        ``slot`` — after this the draft is back in lens-lockstep with
        the target's imported KV, and the next ``propose`` window runs
        as if the request never moved. Raises the cache's
        MigrationCorrupt/CompatError on a payload this draft cannot
        seat (different draft geometry, quantization mismatch)."""
        from tpudl.serve.cache import parse_migration

        meta = (
            draft_payload
            if isinstance(draft_payload, dict) and "_arrays" in draft_payload
            else parse_migration(draft_payload)
        )
        self.cache.import_request(meta, slot)

    # -- the propose loop ----------------------------------------------

    def propose(
        self,
        tokens0: np.ndarray,
        positions0: np.ndarray,
        active: Sequence[int],
        temps: np.ndarray,
        seeds: np.ndarray,
        token_index: np.ndarray,
    ):
        """k single-token draft dispatches from each slot's last
        emitted token. Greedy slots propose by argmax; sampling slots
        draw from the draft distribution with the per-(request,
        position) Philox stream (and the q-distributions ride back for
        the acceptance test). Returns ``(proposals [B, k] int32,
        q_probs: {slot: [k arrays]} for sampling slots)``.

        The draft cache's lens advance here is PROVISIONAL (the k
        writes must land at successive positions); ``sync_len`` rolls
        it back to the accepted count afterwards."""
        b = tokens0.shape[0]
        k = self.k
        proposals = np.zeros((b, k), np.int32)
        sampling = [i for i in active if temps[i] > 0]
        q_probs = {i: [] for i in sampling}
        cur_tok = np.asarray(tokens0, np.int32).copy()
        cur_pos = np.asarray(positions0, np.int32).copy()
        lens_before = {i: int(self.cache.lens[i]) for i in active}
        for j in range(k):
            logits = self.cache.decode(
                self.decode_call, self.params, cur_tok, cur_pos
            )
            if sampling:
                host = np.asarray(jax.device_get(logits), np.float32)
                sel = np.argmax(host, axis=-1).astype(np.int32)
                for i in sampling:
                    q = softmax(host[i], float(temps[i]))
                    u = float(
                        _philox(
                            int(seeds[i]), int(token_index[i]) + j, salt=1
                        ).random()
                    )
                    sel[i] = sample_from(q, u)
                    q_probs[i].append(q)
            else:
                from tpudl.serve.engine import _select_greedy

                sel = jax.device_get(_select_greedy(logits))
            self.cache.advance(active)
            proposals[:, j] = sel
            cur_tok = sel
            cur_pos = cur_pos + 1
        # Roll the provisional advance back; sync_len re-applies the
        # accepted amount once the verdict is in.
        for i in active:
            self.cache.set_len(i, lens_before[i])
        return proposals, q_probs
