"""Speculative decoding: drafters + the closed-loop spec_len controller.

Classic speculative decoding (Leviathan et al. 2023, "Fast Inference from
Transformers via Speculative Decoding"; Chen et al. 2023, "Accelerating
Large Language Model Decoding with Speculative Sampling") converts decode
from one model pass per token to one pass per ACCEPTED RUN: a cheap
drafter proposes ``gamma`` tokens, one jitted verify dispatch
(engine.verify — the blocked decode program generalized to gamma+1 query
positions per slot) scores them all, and the distribution-preserving
acceptance rule (sampling.speculative_accept) keeps the matching prefix
plus one fresh token. Every dispatch emits between 1 and gamma+1 tokens,
so dispatches-per-token — the host syncs a token costs —
drops below 1 whenever anything accepts, and the output distribution is
untouched (bit-identical for greedy, distributionally identical for
sampled; both test-pinned).

This module holds the DRAFT side plus the policy loop that tunes it:

- ``NgramDrafter`` — prompt-lookup decoding (match the last k tokens
  against the history, propose what followed last time): free, and strong
  exactly where speculation pays — repetitive continuations, code,
  retrieval-grounded generation, and the token loops greedy decoding
  falls into. The suffix index is INCREMENTAL (append-only per slot, keyed
  by the batcher-provided ``ctx``) and the match scan is capped at
  ``window`` recent tokens, so a long-running slot's lookup stays O(1)
  per round instead of re-scanning its whole history.
- ``LearnedDrafter`` — the EAGLE-style learned draft model (Li et al.
  2024): a tiny head over the TARGET's own last hidden state that shares
  the target's embedding and lm_head weights, so no separate draft
  checkpoint exists; optional tiny-head params plug in when available.
  Drafts all slots' gamma tokens in one small jitted dispatch
  (engine.make_draft_program) from the hidden states the engine's
  ``return_hidden`` hook keeps on device.
- ``SpecController`` — the closed policy loop (ROADMAP item 4): reads the
  obs registry's LIVE per-slot draft-proposed/accepted counters and
  per-kind dispatch-latency histograms (the PR 10 instruments, consumed
  here as a CONTROL surface for the first time) and sets ``spec_len``
  per slot each round — ramping up while acceptance x draft cost beats
  plain blocked decode, ramping to 0 (speculation off; the batcher falls
  back to ``decode_block`` once every slot is off) when it does not, and
  switching drafters per slot — with windowed evaluation + consecutive-
  decision hysteresis so adversarial accept-rate flip-flop traffic
  cannot make it oscillate.

Acceptance accounting rides in the batcher (``draft_proposed`` /
``draft_accepted`` / ``accept_rate``): an accept-rate of r means the
average dispatch emitted ~1 + r*gamma tokens. Rates near 0 mean the
drafter is guessing blind (speculation costs nothing but the wider verify
dispatch); rates near 1 mean dispatches-per-token approaches
1/(gamma+1).

Overlapped scheduling staleness contract (``inference.overlap``): under
the zero-bubble pipeline the batcher drafts round N+1 WHILE round N still
executes, so every drafter input — slot histories, ``_last_tok``, the
device hidden rows, the controller's per-slot lens/kinds — is one round
stale. That is safe by construction: the slot-schedule verify program's
sample-and-match acceptance (sampling.speculative_match) makes the
EMITTED stream independent of the draft values, so a stale guess can only
lower the accept rate, never change a token. Controller decisions land at
round boundaries one round late for the same reason (its counters update
at sync). See docs/INFERENCE.md "Overlapped scheduling".
"""

from __future__ import annotations

from typing import Optional

import numpy as np


class Drafter:
    """Proposes draft tokens for one slot from its token history.

    Implementations must be DETERMINISTIC functions of ``history`` — the
    acceptance rule (sampling.speculative_accept) treats the proposal as a
    point-mass distribution, which is what makes rejection resampling
    exact. A stochastic drafter (e.g. a sampled draft model) would need
    its per-token proposal probabilities threaded into the accept rule.

    ``kind`` labels the drafter in telemetry and the controller's
    switching table; ``stateful`` drafters additionally take the
    batcher's per-request ``ctx`` key in ``propose`` and get
    ``begin``/``forget`` lifecycle calls; ``needs_hidden`` drafters
    (the learned family) draft per BATCH from device state instead —
    ``propose_batch`` — and the engine must run with ``return_hidden``.
    """

    kind = "custom"
    stateful = False
    needs_hidden = False

    def propose(self, history: np.ndarray, n: int) -> np.ndarray:
        """Return exactly ``n`` proposed continuation tokens (int32) for a
        slot whose tokens so far (prompt + generated, the yet-unwritten
        last token included) are ``history``. Proposals are speculative by
        definition — a bad guess costs nothing but the rejected verify
        columns — so there is no "no proposal" escape hatch; return a
        best-effort guess."""
        raise NotImplementedError

    def begin(self, ctx) -> None:
        """A request keyed ``ctx`` was admitted (stateful drafters reset
        any per-request index here)."""

    def forget(self, ctx) -> None:
        """The request keyed ``ctx`` finished — drop its state."""


class NgramDrafter(Drafter):
    """Prompt-lookup drafting: match the longest suffix n-gram (``ngram``
    down to 1 tokens) of the history against its earlier occurrences and
    propose the ``n`` tokens that followed the MOST RECENT match. A match
    near the end of the history cycles its continuation (the region from
    the match to the end is exactly the pattern being repeated), which is
    what catches greedy token loops and boilerplate. No match at any
    length falls back to repeating the last token.

    ``window`` > 0 caps the match scan at the most recent ``window``
    history tokens (a match whose continuation starts earlier is
    ignored); 0 scans everything.

    Two lookup paths, pinned equal in tests/test_speculative.py:

    - stateless (``ctx=None``): full suffix scan over the history each
      call — the reference semantics;
    - incremental (``ctx=<request key>``): an append-only per-request
      index maps every k-gram to its most recent indexed end position;
      each call extends the index by the tokens appended since the last
      call and answers with dict lookups — O(new tokens) per round
      instead of O(history). The final gram (the query suffix itself) is
      deliberately indexed one call LATE, which is exactly the "match
      must have a continuation" exclusion of the full scan.
    """

    kind = "ngram"
    stateful = True

    def __init__(self, ngram: int = 3, window: int = 0):
        if ngram < 1:
            raise ValueError("ngram must be >= 1")
        if window < 0:
            raise ValueError("window must be >= 0 (0 = unbounded)")
        self.ngram = int(ngram)
        self.window = int(window)
        self._idx: dict = {}  # ctx -> {"done": int, "maps": [dict] * ngram}

    def begin(self, ctx) -> None:
        self._idx.pop(ctx, None)

    def forget(self, ctx) -> None:
        self._idx.pop(ctx, None)

    def _continuation(self, h: np.ndarray, end: int, n: int) -> np.ndarray:
        """The ``n``-token proposal from a match whose gram ends at
        ``end``: cycle the continuation out to n tokens — after a match
        near the end, the tail IS the expected future of the loop."""
        return np.resize(h[end + 1:], n).astype(np.int32)

    def _min_end(self, L: int) -> int:
        """Earliest gram-end position the window admits as a match."""
        return 0 if self.window <= 0 else max(0, L - 1 - self.window)

    def propose(self, history: np.ndarray, n: int,
                ctx=None) -> np.ndarray:
        h = np.asarray(history, np.int32).reshape(-1)
        if n < 1:
            return np.zeros(0, np.int32)
        if h.size < 2:
            fill = h[-1] if h.size else 0
            return np.full(n, fill, np.int32)
        if ctx is not None:
            return self._propose_indexed(h, n, ctx)
        return self._propose_scan(h, n)

    def _propose_scan(self, h: np.ndarray, n: int) -> np.ndarray:
        """The full-rebuild reference: scan every candidate each call."""
        lo = self._min_end(h.size)
        for k in range(min(self.ngram, h.size - 1), 0, -1):
            suffix = h[-k:]
            # candidate starts i with i + k <= len - 1: the match must have
            # at least one continuation token (the final occurrence — the
            # suffix itself — is excluded by construction)
            windows = np.lib.stride_tricks.sliding_window_view(
                h[: h.size - 1], k)
            hits = np.flatnonzero((windows == suffix).all(axis=1))
            # window cap on the match's END position (hit start + k - 1)
            hits = hits[hits + k - 1 >= lo]
            if hits.size:
                return self._continuation(h, int(hits[-1]) + k - 1, n)
        return np.full(n, h[-1], np.int32)

    def _propose_indexed(self, h: np.ndarray, n: int, ctx) -> np.ndarray:
        """Incremental path: extend the per-request index by the newly
        appended tokens, then answer the suffix lookup from the maps."""
        st = self._idx.get(ctx)
        if st is None or st["done"] > h.size - 1:
            # unknown request, or a history that shrank (slot recycled
            # without begin()) — start a fresh index
            st = {"done": 0, "maps": [dict() for _ in range(self.ngram)]}
            self._idx[ctx] = st
        maps = st["maps"]
        # index gram ENDS e in [done, len-2]: ends at len-1 would be the
        # query suffix itself — no continuation yet, indexed next call.
        # Only the tokens the new grams can touch are materialized, so a
        # round's host cost tracks the APPENDED tokens, not the history
        # (every gram end e >= done reaches back at most ngram - 1).
        base = max(0, st["done"] - self.ngram + 1)
        tail = h[base:].tolist()
        for e in range(st["done"], h.size - 1):
            for k in range(1, min(self.ngram, e + 1) + 1):
                maps[k - 1][tuple(tail[e - k + 1 - base: e + 1 - base])] = e
        st["done"] = h.size - 1
        lo = self._min_end(h.size)
        for k in range(min(self.ngram, h.size - 1), 0, -1):
            e = maps[k - 1].get(tuple(tail[h.size - k - base:]))
            if e is not None and e >= lo:
                return self._continuation(h, e, n)
        return np.full(n, h[-1], np.int32)


class LearnedDrafter(Drafter):
    """EAGLE-style learned drafting from the target's own last hidden
    state. The engine's ``return_hidden`` hook keeps each slot's
    pre-final-norm hidden state (at the position whose logits produced
    the slot's current last token) ON DEVICE; one small jitted dispatch
    (engine.make_draft_program) then autoregresses a pseudo-hidden state
    through the SHARED embedding + lm_head for ``spec_len`` greedy steps
    — no separate draft checkpoint, no KV traffic, no [B, vocab] logits
    crossing to the host (the dispatch ships [B, spec_len] token ids).

    ``head`` (optional) is a tiny-head parameter tree ``{"w": [2H, H]}``
    — load one with ``checkpoint.load_params`` next to the target's
    weights, or pass None for the parameter-free residual merge
    (``hidden + embed(token)``), which needs nothing beyond the target
    checkpoint. Either way the proposal is a deterministic function of
    (hidden, token), so the acceptance rule's point-mass assumption
    holds and greedy output stays bit-identical to spec-off."""

    kind = "learned"
    needs_hidden = True

    def __init__(self, engine, params, head: Optional[dict] = None):
        if engine.spec_len < 1:
            raise ValueError(
                "LearnedDrafter needs a speculative engine (spec_len > 0)")
        if not engine.return_hidden:
            raise ValueError(
                "LearnedDrafter needs the engine's last-hidden-state hook"
                " — build the engine with inference.drafter: 'learned' "
                "(or return_hidden=True)")
        self.engine = engine
        self.params = params
        self.head = head
        self._jit = engine.make_draft_program(with_head=head is not None)

    def propose_batch(self, tokens, hidden, n: int) -> np.ndarray:
        """Draft ``n`` tokens for EVERY slot in one dispatch: ``tokens``
        [B] (each slot's current last token, host or device), ``hidden``
        [B, H] (the engine-returned device hidden states). ``n`` must be
        the engine's ``spec_len`` — the program's compiled length; ragged
        per-slot lengths are the verify mask's job, so callers slice the
        prefix they need. Returns host int32 [B, n].

        The overlap pipeline passes the HOST ``_last_tok`` view here even
        though it is one round stale (passing the device-carried token
        row would host-sync on the in-flight round — the bubble the
        pipeline exists to remove); a stale conditioning token only costs
        acceptance, never correctness (module docstring)."""
        import jax.numpy as jnp

        if n != self.engine.spec_len:
            raise ValueError(
                f"the draft program proposes exactly spec_len = "
                f"{self.engine.spec_len} tokens per slot, got n = {n} "
                f"(slice the per-slot prefix you need)")
        toks = jnp.asarray(np.asarray(tokens, np.int32))
        head = (self.head,) if self.head is not None else ()
        return np.asarray(self._jit(self.params, *head, hidden, toks))

    def propose(self, history, n, ctx=None):
        raise TypeError(
            "LearnedDrafter drafts per batch from device hidden states "
            "(propose_batch); per-slot host proposal is the n-gram "
            "drafter's path")


def init_draft_head(key, hidden_size: int, dtype=np.float32) -> dict:
    """A randomly initialized tiny-head parameter tree for
    ``LearnedDrafter`` (the shape ``checkpoint.load_params`` would
    restore): one [2H, H] merge matrix, U(-1/sqrt(2H), 1/sqrt(2H))."""
    import jax

    bound = 1.0 / np.sqrt(2.0 * hidden_size)
    w = jax.random.uniform(key, (2 * hidden_size, hidden_size),
                           np.float32, -bound, bound)
    return {"w": w.astype(dtype)}


class SpecController:
    """The per-slot speculation policy loop (docs/INFERENCE.md
    "Self-tuning speculation").

    Telemetry as a control surface: the batcher mirrors every round's
    per-slot draft counts into the obs registry
    (``picotron_slot_draft_proposed_total{slot=...}`` / ``..accepted..``)
    and every dispatch's wall time into
    ``picotron_dispatch_seconds{kind}``; the controller reads BOTH live
    and decides, per slot, the next round's draft length and drafter:

    - each slot re-evaluates only after proposing ``window`` draft tokens
      since its last decision (one bad round cannot flip policy);
    - the windowed accept rate r picks a direction: r >= ``target`` ramps
      UP (spec_len doubles toward the engine ceiling), r < ``low`` ramps
      DOWN (halves toward 0); the [low, target) band holds;
    - the measured cost ratio joins once the latency histograms hold
      ``latency_min_samples`` per kind: speculation must also PAY —
      (1 + r*g) tokens per (verify + draft) dispatch must beat the
      blocked-decode alternative's block_len tokens per decode dispatch
      — or the direction is forced down / the ramp-up vetoed;
    - a ramp applies only after ``hysteresis`` CONSECUTIVE evaluations
      agree on the direction (flip-flopping traffic alternates the
      direction, the streak never completes, spec_len holds — pinned in
      tests);
    - ramping down past spec_len 1 first SWITCHES drafters (when the
      batcher registered more than one kind and the other is untried
      since the slot's last reset), then turns speculation OFF (spec_len
      0). An off slot re-probes with a 1-token draft after ``cooloff``
      rounds, so traffic that turns easy is rediscovered;
    - every decision lands in
      ``picotron_spec_controller_decisions_total{action}``.

    When EVERY occupied slot is off the batcher skips the verify dispatch
    entirely and falls back to ``engine.decode_block`` — speculation
    "gets out of the way" instead of paying verify width for nothing.
    """

    def __init__(self, cfg, registry, *, slots: int, max_spec_len: int,
                 block_len: int, kinds=("ngram",)):
        if max_spec_len < 1:
            raise ValueError("SpecController needs max_spec_len >= 1")
        if not kinds:
            raise ValueError("SpecController needs at least one drafter")
        self.cfg = cfg
        self.registry = registry
        self.slots = int(slots)
        self.gmax = int(max_spec_len)
        self.block_len = int(block_len)
        self.kinds = tuple(kinds)
        self._decisions = {}
        self._g = [self.gmax] * self.slots  # optimistic start: full draft
        self._kind = [self.kinds[0]] * self.slots
        self._streak = [0] * self.slots
        self._idle = [0] * self.slots
        self._tried: list = [{self.kinds[0]} for _ in range(self.slots)]
        self._snap = [(0.0, 0.0)] * self.slots  # counter values at last eval
        # per-slot TPOT SLO in SECONDS (None = best-effort): tokens
        # arrive in per-dispatch bursts, so the inter-token gap a client
        # sees is the dispatch wall time — a slot whose measured
        # verify+draft latency exceeds its SLO gets its draft length
        # halved regardless of accept rate (multi-tenant serving's SLO
        # input; the batcher sets it at admission via reset())
        self._slo: list = [None] * self.slots
        # shadow tallies so the loop still closes under obs.enabled:
        # false (the NullRegistry's counters read 0 forever)
        self._local = [(0.0, 0.0)] * self.slots

    # ---- registry reads (the control surface) -----------------------------

    def record(self, slot: int, proposed: int, accepted: int) -> None:
        """Mirror one round's draft counts (the batcher also writes the
        registry's labeled counters — the authoritative source the reads
        below prefer; this shadow only carries an obs-disabled server)."""
        p, a = self._local[slot]
        self._local[slot] = (p + proposed, a + accepted)

    def _counts(self, slot: int) -> tuple:
        from picotron_tpu.obs.metrics import NULL_INSTRUMENT

        reg = self.registry
        c = reg.counter("picotron_slot_draft_proposed_total",
                        slot=str(slot))
        if c is NULL_INSTRUMENT:
            return self._local[slot]
        return (c.value,
                reg.counter("picotron_slot_draft_accepted_total",
                            slot=str(slot)).value)

    def _mean_latency(self, kind: str) -> Optional[float]:
        h = self.registry.histogram(
            "picotron_dispatch_seconds",
            "dispatch wall time incl. host sync, by kind", kind=kind)
        r = h.read()
        if r["count"] < self.cfg.latency_min_samples:
            return None
        return r["sum"] / r["count"]

    def _pays(self, g: int, r: float) -> Optional[bool]:
        """Whether speculating at ``g`` with accept rate ``r`` beats the
        blocked-decode alternative on MEASURED dispatch latencies:
        (1 + r*g) tokens per (verify + draft) dispatch vs ``block_len``
        tokens per decode dispatch. None while either histogram is under
        ``latency_min_samples`` — the accept thresholds then decide
        alone (a mixed controller batch never runs decode_block, so
        fresh servers start threshold-only and gain the cost term as
        evidence accumulates)."""
        c_v = self._mean_latency("verify")
        c_d = self._mean_latency("decode")
        if c_v is None or c_d is None:
            return None
        c_draft = self._mean_latency("draft") or 0.0
        return (1.0 + r * g) * c_d > self.block_len * (c_v + c_draft)

    # ---- decision recording ------------------------------------------------

    def _decide(self, action: str) -> None:
        self._decisions[action] = self._decisions.get(action, 0) + 1
        self.registry.counter(
            "picotron_spec_controller_decisions_total",
            "spec controller policy decisions by action",
            action=action).inc()

    @property
    def decisions(self) -> dict:
        """{action: count} over the controller's lifetime (the bench's
        controller-decision counts)."""
        return dict(self._decisions)

    # ---- batcher surface ---------------------------------------------------

    def reset(self, slot: int, tpot_slo_s: Optional[float] = None) -> None:
        """A fresh request took ``slot``: restart it at the optimistic
        full draft with the primary drafter and clean stats.
        ``tpot_slo_s`` (multi-tenant serving) is the request's token-gap
        budget in seconds — a slot whose measured dispatch latency
        cannot afford the full draft width starts at 1 instead of
        ``gmax`` and is capped down each round it overshoots."""
        self._slo[slot] = tpot_slo_s
        self._g[slot] = self.gmax
        if tpot_slo_s is not None and self._over_slo(slot):
            # the measured verify cadence already misses this budget:
            # start at the narrowest useful draft, not the optimistic max
            self._g[slot] = 1
        self._kind[slot] = self.kinds[0]
        self._streak[slot] = 0
        self._idle[slot] = 0
        self._tried[slot] = {self.kinds[0]}
        self._snap[slot] = self._counts(slot)

    def _over_slo(self, slot: int) -> bool:
        """Whether the slot's measured per-dispatch latency (verify +
        draft — the burst gap its client observes) exceeds its TPOT SLO.
        False without an SLO or before the latency histograms hold
        ``latency_min_samples`` — the SLO input engages on EVIDENCE,
        like the controller's cost term."""
        slo = self._slo[slot]
        if slo is None:
            return False
        c_v = self._mean_latency("verify")
        if c_v is None:
            return False
        return c_v + (self._mean_latency("draft") or 0.0) > slo

    def lens(self) -> np.ndarray:
        """Per-slot draft length for the NEXT round [slots] int32."""
        return np.asarray(self._g, np.int32)

    def drafter_kinds(self) -> list:
        """Per-slot drafter kind for the NEXT round."""
        return list(self._kind)

    def spec_len_mean(self, occupied) -> float:
        """Mean effective spec_len over ``occupied`` slot indices (the
        ``picotron_spec_len`` gauge / bench ``spec_len_effective``)."""
        occ = list(occupied)
        if not occ:
            return 0.0
        return float(np.mean([self._g[i] for i in occ]))

    def after_round(self, slot: int) -> None:
        """One occupied slot finished one scheduler round (verify or the
        decode_block fallback): advance its cooloff clock and, once its
        proposal window has filled, evaluate."""
        g = self._g[slot]
        if g == 0:
            self._idle[slot] += 1
            if self.cfg.cooloff and self._idle[slot] >= self.cfg.cooloff:
                # re-probe: traffic may have turned easy; a 1-token draft
                # is the cheapest possible question
                self._g[slot] = 1
                self._idle[slot] = 0
                self._streak[slot] = 0
                self._tried[slot] = {self._kind[slot]}
                self._snap[slot] = self._counts(slot)
                self._decide("probe")
            return
        if g > 1 and self._over_slo(slot):
            # SLO input (multi-tenant serving): the dispatch burst gap
            # misses this slot's token-cadence budget — halve the width
            # now, without waiting for the accept-rate window; ramp-ups
            # re-earn width only once the cadence fits again
            self._g[slot] = g // 2
            self._streak[slot] = 0
            self._decide("slo_cap")
            return
        prop, acc = self._counts(slot)
        sprop, sacc = self._snap[slot]
        if prop - sprop < self.cfg.window:
            return
        r = (acc - sacc) / max(prop - sprop, 1.0)
        self._snap[slot] = (prop, acc)
        direction = (1 if r >= self.cfg.target
                     else -1 if r < self.cfg.low else 0)
        pays = self._pays(g, r)
        if pays is not None:
            if direction > 0 and not self._pays(min(2 * g, self.gmax), r):
                direction = 0  # don't ramp up into a measured loss
            if not pays:
                direction = -1  # measured loss forces down regardless
        if direction == 0:
            self._streak[slot] = 0
            return
        streak = self._streak[slot]
        streak = streak + direction if streak * direction > 0 else direction
        self._streak[slot] = streak
        if abs(streak) < self.cfg.hysteresis:
            return
        self._streak[slot] = 0
        if direction > 0:
            new_g = min(max(1, 2 * g), self.gmax)
            if new_g != g:
                self._g[slot] = new_g
                self._decide("ramp_up")
            return
        if g > 1:
            self._g[slot] = g // 2
            self._decide("ramp_down")
            return
        # at spec_len 1 and still losing: try the other drafter before
        # giving up on speculation for this slot
        untried = [k for k in self.kinds if k not in self._tried[slot]]
        if untried:
            self._kind[slot] = untried[0]
            self._tried[slot].add(untried[0])
            self._snap[slot] = self._counts(slot)
            self._decide("switch_drafter")
            return
        self._g[slot] = 0
        self._idle[slot] = 0
        self._decide("spec_off")
