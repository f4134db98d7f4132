"""Speculative decoding drafters + acceptance governance (ISSUE 8).

Plain continuous-batching decode advances every slot ONE token per
target-model forward: what it gains over sequential calls is batching
and paging, not per-token speed. Draft-and-verify speculative
decoding (Leviathan et al. 2023) recovers several tokens per forward:
a cheap **drafter** proposes up to K continuation tokens per slot, the
engine scores all of them in ONE batched verify forward (the chunk
programs in :mod:`~elephas_tpu.serving.kv_cache` /
:mod:`~elephas_tpu.serving.paged_kv` — see ``verify_forward`` /
``paged_verify_forward``), and the longest draft prefix matching the
model's own (greedy) tokens is accepted, plus the model's one "bonus"
token from the first non-matching position. At temperature 0 the
accepted tokens are BY CONSTRUCTION the tokens plain decode would have
produced — speculation changes latency, never output.

This module is the host side of that loop:

- :class:`Drafter` — the drafting interface. ``propose(req, k)``
  returns up to ``k`` guessed continuation tokens for one request;
  ``propose_batch`` is the batched entry point the engine calls once
  per verify round (the default fans out to ``propose``; device-backed
  drafters override it to batch their own forwards).
- :class:`NgramDrafter` — prompt-lookup drafting (Saxena 2023):
  matches the request's recent token suffix against its OWN
  prompt+generated history and proposes whatever followed the most
  recent earlier occurrence. Pure host-side string matching — zero
  device cost, and nearly free accuracy on the shared-prefix /
  long-context workloads the prefix cache and paged arena already
  target (templated text keeps repeating itself).
- :class:`DraftModelDrafter` — a second, smaller model from the zoo
  drafts autoregressively in its OWN fixed KV slot arena (one slot per
  engine slot). Catch-up is chunked through one fixed-width program
  and drafting is one greedy multi-step program, so the drafter's
  compiled-shape set is closed like the engine's. The draft arena is
  deliberately fixed (not paged): draft models are small, and the
  drafter's rows are scratch state that is rebuilt from the true token
  stream whenever a slot changes occupants.
- :class:`AcceptanceThrottle` — per-request drafting governor: a
  request whose measured acceptance rate collapses stops drafting
  (falls back to plain decode) and re-probes periodically, so
  adversarial/unpredictable text can never make speculation a
  sustained net loss.

Determinism: drafters run identical host code from identical request
state on every gang process, and the draft model runs unmeshed but
greedy on identical weights — all processes propose identical drafts,
preserving the SPMD contract the engine already imposes.
"""

from __future__ import annotations

import numpy as np

__all__ = [
    "Drafter",
    "NgramDrafter",
    "DraftModelDrafter",
    "AcceptanceThrottle",
    "resolve_drafter",
]


class Drafter:
    """Interface: guess the next tokens of a request, cheaply.

    The engine calls :meth:`propose_batch` once per verify round with
    every slot eligible to draft; implementations return ``{slot:
    [token, ...]}`` with at most the per-item ``k`` tokens each. A
    wrong guess costs only wasted verify compute (the acceptance rule
    discards it); a missing guess costs nothing (the slot rides the
    verify round as a plain one-token decode)."""

    # weight generation this drafter's state was built under (ISSUE
    # 20): the engine's refresh_weights() cascade stamps it alongside
    # the re-upload, so a mixed-version fleet debug view can tell a
    # stale draft model from a refreshed one. 0 = unversioned.
    weight_version: int = 0

    def propose(self, req, k: int) -> list[int]:
        """Up to ``k`` guessed continuation tokens for ``req`` (which
        exposes ``prompt``, ``tokens`` and ``full_sequence``). Return
        ``[]`` to skip drafting this round."""
        raise NotImplementedError

    def propose_batch(self, items) -> dict[int, list[int]]:
        """``items`` is ``[(slot, req, k), ...]``; returns ``{slot:
        drafts}``. Default: per-item :meth:`propose` fan-out."""
        return {slot: self.propose(req, k) for slot, req, k in items}

    def refresh_weights(self) -> None:
        """Called by the engine's ``refresh_weights()``: drafters that
        hold model state re-upload it here (the draft model may have
        been retrained alongside the target). Stateless drafters
        no-op."""

    def release(self) -> None:
        """Drop any device/host resources. The engine does not call
        this — its drafter lives (and is garbage-collected) with the
        engine; owners constructing drafters directly may call it to
        free a draft arena early."""


class NgramDrafter(Drafter):
    """Prompt-lookup / n-gram drafting: propose the continuation of
    the most recent earlier occurrence of the request's current token
    suffix inside its own prompt+generated stream.

    Longest suffix first (``max_ngram`` down to ``min_ngram``), most
    recent match first within a suffix length — recency tracks the
    local pattern the sequence is currently in (templated text, code,
    long-context copy tasks). Matching runs over ``full_sequence``, so
    a match may span the prompt/generated boundary, sit entirely in
    the prompt (classic prompt lookup), or entirely in the generated
    tail. No match → no drafts → the slot decodes plainly this round.
    """

    def __init__(self, max_ngram: int = 4, min_ngram: int = 1):
        max_ngram, min_ngram = int(max_ngram), int(min_ngram)
        if not 1 <= min_ngram <= max_ngram:
            raise ValueError(
                f"need 1 <= min_ngram <= max_ngram, got "
                f"min_ngram={min_ngram}, max_ngram={max_ngram}"
            )
        self.max_ngram = max_ngram
        self.min_ngram = min_ngram

    def propose(self, req, k: int) -> list[int]:
        seq = req.full_sequence
        n_seq = len(seq)
        for n in range(self.max_ngram, self.min_ngram - 1, -1):
            # need the suffix PLUS at least one earlier position for a
            # non-trivial match (the terminal occurrence is the query)
            if n_seq < n + 1:
                continue
            suffix = seq[n_seq - n:]
            for i in range(n_seq - n - 1, -1, -1):
                if seq[i:i + n] == suffix:
                    # i + n <= n_seq - 1, so at least one continuation
                    # token always exists
                    return [
                        int(t) for t in seq[i + n: i + n + int(k)]
                    ]
        return []


class DraftModelDrafter(Drafter):
    """Draft with a second (small) causal LM in its own fixed KV slot
    arena — the classic two-model speculative setup.

    The drafter mirrors the engine's slot space: slot ``s`` of the
    draft arena shadows engine slot ``s``. Per :meth:`propose_batch`
    call it (1) **catches up** — feeds the true token stream the
    verify loop has committed since the drafter last saw this slot,
    through one fixed-width chunk program (full prompt on first call
    after an occupant change; the accepted tokens of the last round
    otherwise) — then (2) **drafts** ``k`` tokens greedily with the
    draft model's own single-token decode step, writing scratch K/V
    past the committed frontier. Scratch rows are rewritten by the
    next catch-up before any query can see them (the same
    rewrite-before-visible invariant the engine's verify rollback
    relies on), so no state is ever unwound.

    Occupant changes are self-healing: the drafter keys its committed
    frontier by ``(slot, rid)`` and resets to a full re-ingest when
    the engine reassigns a slot (including preempt/resume moves) —
    no engine hooks required.

    The draft model must share the target's tokenizer space (equal
    vocab) and cover its positions (``draft maxlen >= target
    maxlen``); both are validated loudly. It runs UNMESHED and greedy:
    every gang process derives identical drafts from identical
    weights, keeping the SPMD contract."""

    #: catch-up chunk width — ONE compiled ingest program regardless of
    #: deficit (long prompts loop it); clipped to the draft maxlen
    CATCHUP_CHUNK = 32

    def __init__(self, model, num_slots: int,
                 target_maxlen: int | None = None,
                 target_vocab: int | None = None):
        from elephas_tpu.models.transformer import (
            validate_token_decode_model,
        )
        from elephas_tpu.serving.kv_cache import SlotKVCache

        flash_layers, _stock, _gqa = validate_token_decode_model(
            model,
            what="the draft-model drafter",
            hint="draft with NgramDrafter instead",
            allow_stock=False,
        )
        self.model = model
        self.maxlen = int(model.inputs[0].shape[1])
        self.vocab = int(model.outputs[0].shape[-1])
        self.num_slots = int(num_slots)
        if self.num_slots < 1:
            raise ValueError(f"num_slots={num_slots} < 1")
        self.validate_for(
            self.num_slots,
            self.maxlen if target_maxlen is None else target_maxlen,
            self.vocab if target_vocab is None else target_vocab,
        )
        self.arena = SlotKVCache(flash_layers, self.num_slots, self.maxlen)
        self._chunk = min(self.CATCHUP_CHUNK, self.maxlen)
        # committed frontier per slot: (rid, tokens of the TRUE stream
        # whose K/V is resident) — scratch draft rows never count
        self._frontier: dict[int, tuple[int, int]] = {}
        self._build()

    def _build(self):
        import jax
        import jax.numpy as jnp

        from elephas_tpu.serving.kv_cache import (
            chunked_prefill_forward,
            token_decode_step,
        )

        model, maxlen = self.model, self.maxlen

        def ingest(w, caches, tokens, offs, clens, act):
            _logits, caches = chunked_prefill_forward(
                model, w, tokens, caches, offs, clens, act, maxlen
            )
            return caches

        def draft(w, caches, last, positions, act, k):
            def body(i, carry):
                caches, last, positions, toks = carry
                pos = jnp.minimum(positions, maxlen - 1)
                logits, caches = token_decode_step(
                    model, w, last, pos, caches, maxlen, active=act
                )
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                toks = toks.at[i].set(nxt)
                return caches, nxt, positions + 1, toks

            toks0 = jnp.zeros((k, last.shape[0]), jnp.int32)
            caches, _last, _pos, toks = jax.lax.fori_loop(
                0, k, body, (caches, last, positions, toks0)
            )
            return caches, toks

        self._ingest_jit = jax.jit(ingest, donate_argnums=(1,))
        self._draft_jit = jax.jit(
            draft, static_argnums=(5,), donate_argnums=(1,)
        )
        self._weights = {
            v.path: jnp.asarray(v.value) for v in model.variables
        }
        self._caches = jax.jit(self.arena.init)()

    def refresh_weights(self) -> None:
        """Re-upload the draft model's weights (after further
        training) and invalidate every committed frontier — resident
        rows were computed under the old weights."""
        import jax.numpy as jnp

        self._weights = {
            v.path: jnp.asarray(v.value) for v in self.model.variables
        }
        self._frontier.clear()

    def propose_batch(self, items) -> dict[int, list[int]]:
        if not items:
            return {}
        seqs = {}
        for slot, req, _k in items:
            seq = req.full_sequence
            seqs[slot] = seq
            rid, seen = self._frontier.get(slot, (None, 0))
            if rid != req.rid:
                seen = 0  # new occupant: full re-ingest
            self._frontier[slot] = (req.rid, seen)
        # -- catch-up: commit the true stream up to (but excluding) the
        # last token — its K/V lands during drafting, exactly the
        # engine's own cursor convention
        while True:
            batch = []
            for slot, req, _k in items:
                rid, seen = self._frontier[slot]
                deficit = len(seqs[slot]) - 1 - seen
                if deficit > 0:
                    batch.append((slot, seen, min(self._chunk, deficit)))
            if not batch:
                break
            rows = np.zeros((self.num_slots, self._chunk), np.int32)
            offs = np.zeros((self.num_slots,), np.int32)
            clens = np.zeros((self.num_slots,), np.int32)
            act = np.zeros((self.num_slots,), bool)
            for slot, seen, take in batch:
                rows[slot, :take] = seqs[slot][seen:seen + take]
                offs[slot] = seen
                clens[slot] = take
                act[slot] = True
            import jax.numpy as jnp

            self._caches = self._ingest_jit(
                self._weights, self._caches, jnp.asarray(rows),
                jnp.asarray(offs), jnp.asarray(clens), jnp.asarray(act),
            )
            for slot, seen, take in batch:
                rid, _seen = self._frontier[slot]
                self._frontier[slot] = (rid, seen + take)
        # -- draft: k greedy tokens from the last true token; rows
        # written past the frontier are scratch (rewritten by the next
        # catch-up before visible)
        k_max = max(int(k) for _s, _r, k in items)
        if k_max < 1:
            return {slot: [] for slot, _r, _k in items}
        import jax.numpy as jnp

        last = np.zeros((self.num_slots,), np.int32)
        positions = np.zeros((self.num_slots,), np.int32)
        act = np.zeros((self.num_slots,), bool)
        for slot, req, k in items:
            if k < 1:
                continue
            last[slot] = seqs[slot][-1]
            positions[slot] = len(seqs[slot]) - 1
            act[slot] = True
        self._caches, toks = self._draft_jit(
            self._weights, self._caches, jnp.asarray(last),
            jnp.asarray(positions), jnp.asarray(act), int(k_max),
        )
        toks = np.asarray(toks)  # [k_max, num_slots]
        return {
            slot: [int(t) for t in toks[: int(k), slot]] if k >= 1 else []
            for slot, _req, k in items
        }

    def validate_for(self, num_slots: int, maxlen: int,
                     vocab: int) -> None:
        """Check this drafter fits a target engine — called by
        ``resolve_drafter`` for PRE-BUILT instances too, so a drafter
        sized for a different engine fails at construction, not with
        an IndexError mid-serve."""
        if self.num_slots < int(num_slots):
            raise ValueError(
                f"draft arena has {self.num_slots} slots but the "
                f"engine serves {num_slots} — the drafter shadows "
                f"engine slots one-to-one"
            )
        if self.maxlen < int(maxlen):
            raise ValueError(
                f"draft model maxlen {self.maxlen} < target maxlen "
                f"{maxlen} — the drafter could not represent "
                f"positions the target decodes at"
            )
        if self.vocab != int(vocab):
            raise ValueError(
                f"draft model vocab {self.vocab} != target vocab "
                f"{vocab} — drafted token ids would not mean the "
                f"same tokens"
            )

    def release(self) -> None:
        self._caches = None
        self._weights = None
        self._frontier.clear()


class AcceptanceThrottle:
    """Per-request drafting governor: measure acceptance over a probe
    window, stop drafting when it collapses, re-probe later.

    A request whose text the drafter cannot predict (adversarial or
    just unpredictable) would otherwise pay draft + K-wide verify
    compute every round for ~1 token — speculation as a net loss.
    The throttle turns that into: draft for ``probe_window`` proposed
    tokens; if the measured acceptance rate is below ``min_rate``,
    stop drafting for ``reprobe_rounds`` decode rounds (the engine
    falls back to plain decode for this request), then probe again —
    text often becomes predictable later (a list, a quote, a repeated
    template). Defaults probe SHORT and back off LONG (8-token window,
    16-round cooldown): a failed probe round costs a full-width verify
    for ~1 token, so the steady-state duty cycle under total collapse
    — ~2 probe rounds per 16 plain — is what bounds the worst-case
    tax. State is plain host bookkeeping keyed by request id;
    telemetry observes it, never drives it."""

    def __init__(self, probe_window: int = 8, min_rate: float = 0.25,
                 reprobe_rounds: int = 16):
        if probe_window < 1:
            raise ValueError(f"probe_window={probe_window} < 1")
        if not 0.0 <= min_rate <= 1.0:
            raise ValueError(f"min_rate={min_rate} outside [0, 1]")
        if reprobe_rounds < 1:
            raise ValueError(f"reprobe_rounds={reprobe_rounds} < 1")
        self.probe_window = int(probe_window)
        self.min_rate = float(min_rate)
        self.reprobe_rounds = int(reprobe_rounds)
        # rid -> [proposed_in_window, accepted_in_window, cooldown]
        self._state: dict[int, list] = {}

    def should_draft(self, rid: int) -> bool:
        """Consult (and advance) the governor for one decode round:
        True = draft this round; False = throttled (the cooldown ticks
        down; hitting zero re-arms a fresh probe window)."""
        st = self._state.setdefault(int(rid), [0, 0, 0])
        if st[2] > 0:
            st[2] -= 1
            if st[2] == 0:
                st[0] = st[1] = 0  # fresh probe window on re-entry
            return False
        return True

    def note(self, rid: int, proposed: int, accepted: int) -> bool:
        """Record one round's outcome; returns True when this round
        TRIPPED the throttle (the caller counts fallbacks)."""
        if proposed <= 0:
            return False
        st = self._state.setdefault(int(rid), [0, 0, 0])
        st[0] += int(proposed)
        st[1] += int(accepted)
        if st[0] >= self.probe_window:
            if st[1] / st[0] < self.min_rate:
                st[2] = self.reprobe_rounds
                return True
            st[0] = st[1] = 0  # healthy: slide the window
        return False

    def throttled(self, rid: int) -> bool:
        st = self._state.get(int(rid))
        return bool(st) and st[2] > 0

    def forget(self, rid: int) -> None:
        """Drop a finished request's state (bounded memory)."""
        self._state.pop(int(rid), None)


def resolve_drafter(spec, num_slots: int, maxlen: int, vocab: int):
    """Engine-side drafter resolution for the ``spec_drafter`` knob:
    ``None``/``"ngram"`` → :class:`NgramDrafter`; a :class:`Drafter`
    instance passes through; a causal-LM keras model wraps into a
    :class:`DraftModelDrafter` sized to the engine. Anything else is
    rejected loudly."""
    if spec is None or (isinstance(spec, str) and spec == "ngram"):
        return NgramDrafter()
    if isinstance(spec, DraftModelDrafter):
        # a pre-built instance may have been sized for a DIFFERENT
        # engine: fail here, not with an IndexError mid-serve
        spec.validate_for(num_slots, maxlen, vocab)
        return spec
    if isinstance(spec, Drafter):
        return spec
    if hasattr(spec, "inputs") and hasattr(spec, "outputs"):
        return DraftModelDrafter(
            spec, num_slots=num_slots,
            target_maxlen=maxlen, target_vocab=vocab,
        )
    raise ValueError(
        f"spec_drafter={spec!r} is not a drafter: pass 'ngram', a "
        f"serving.Drafter instance, or a causal-LM keras model to "
        f"draft with"
    )
