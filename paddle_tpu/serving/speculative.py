"""Speculative decoding through the paged, quantized serving stack:
draft-model propose + single-forward verify against block tables.

The decode tick is memory-bound: every emitted token pays one full read
of the target weights + KV cache. Speculative decoding amortizes that
read — a cheap DRAFT model proposes γ tokens autoregressively, then the
TARGET model scores all γ+1 window positions in ONE forward (the same
fused decode-attention chain, widened along the query axis), and the
longest accepted prefix commits. Greedy mode is TOKEN-IDENTICAL to
target-only decode by construction: a drafted token is accepted iff it
equals the target's argmax at that position, and the first mismatch is
replaced by the target's own output — exactly the token the plain tick
would have emitted. Sampling mode preserves the target distribution via
rejection sampling (`rejection_sample`).

How it rides the existing stack:

- The DRAFT is the same transformer architecture built by
  `transformer_lm_decode_tick(param_prefix="draft_")`: every weight
  lives under the reserved `draft_` name prefix (its own census
  category, `params_draft`), initialized by COPYING the target's
  weights (optionally truncated to `draft_layers` layers) before the
  target's quantize pass erases the f32 payloads, then quantized to
  `SpecConfig.draft` bits (int4 default halves the draft's weight
  reads). Draft KV is slot-resident on BOTH engines — the draft never
  pages.
- The VERIFY forward is a dedicated tick program per engine
  (`transformer_lm_spec_verify_tick` / `transformer_lm_paged_spec_
  verify_tick`) sharing the TARGET's caches and weights by name: γ+1
  query positions ride the query-row axis of the same fused
  decode-attention kernel (bit-identical to γ+1 sequential plain ticks
  — pinned by tests/test_speculative.py), writes land through the same
  `cache_write`/`paged_cache_write` ops, and the quantize pass's
  twin-program path rewrites it onto the SAME resident @qparam/@qscale
  payloads as the main tick.
- Both draft and verify are BOUND prepared steps (PreparedStep.bind):
  the pure-spec steady state dispatches zero per-call setup. The verify
  step and the plain tick share the target caches, so whichever ran
  last owns the donated buffers — `PreparedStep.refresh_state()`
  re-points the other before it runs (tracked by the engine's
  `_target_state_owner`; pure spec rounds never refresh).
- On the paged engine, a rejected tail's fully-dead blocks roll back
  through `KVPager.rollback` (release + fresh alloc; pool invariants
  `used + free == n_blocks - 1` and refcounts hold after every round —
  `BlockPool.check()` runs per round under PTPU_SPEC_POOL_CHECK=1 and
  always in the tests/bench).
- Prompt positions inside the verify window are teacher-forced (the
  "draft" is the prompt itself, always accepted): prefill advances γ+1
  positions per round — chunked prefill for free.

Observability: each round emits a `speculate` span (the γ+1 draft
ticks) and a `verify` span (the single target forward); acceptance-rate
/ draft-overhead / rolled-back-blocks gauges land in the engine
registry AND the process default registry (labeled by engine), and
`engine.stats()["speculative"]` — hence /healthz — carries the counters.
`GenRequest.phases(subphases=True)` splits the decode window into
spec_draft / spec_verify sub-phases.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

from ..core.enforce import InvalidArgumentError, enforce
from ..observability import tracing as _tracing
from .engine import _feed_arrays

#: reserved name prefix for draft-model state: the census classifier
#: (framework/costs.state_category) maps `draft_*` weights — including
#: quantized `draft_*@qparam` payloads — to the `params_draft` category
DRAFT_PREFIX = "draft_"


@dataclass
class SpecConfig:
    """Speculative-decoding knobs (`ContinuousBatchingEngine(...,
    speculative=SpecConfig(...))`).

    gamma         draft tokens proposed per round; the verify window is
                  γ+1 positions wide
    draft         draft weight precision: "f32" | "int8" | "int4" —
                  int4/int8 quantize the draft's own weight copies
                  (PTPU_QUANT_PARAMS=0 kill switch serves f32 regardless)
    draft_layers  truncate the draft to its first N layers (None = full
                  depth — the honest-high-acceptance default)
    sampling      False = greedy (token-identical to target-only
                  decode); True = rejection sampling preserving the
                  target distribution (seeded, host-side)
    seed          the host RNG seed for sampling mode
    """

    gamma: int = 4
    draft: str = "int8"
    draft_layers: Optional[int] = None
    sampling: bool = False
    seed: int = 0

    def __post_init__(self):
        enforce(int(self.gamma) >= 1, "gamma must be >= 1",
                exc=InvalidArgumentError)
        enforce(self.draft in ("f32", "int8", "int4"),
                f"draft must be 'f32', 'int8' or 'int4', "
                f"got {self.draft!r}", exc=InvalidArgumentError)
        self.gamma = int(self.gamma)


def rejection_sample(p: np.ndarray, q: np.ndarray, draft_token: int,
                     rng: np.random.RandomState):
    """One speculative rejection-sampling step: accept `draft_token`
    (drawn from draft distribution q) with probability min(1,
    p[d]/q[d]); on rejection draw from the residual norm(max(0, p-q)).
    Returns (token, accepted). The emitted token is distributed EXACTLY
    as p regardless of q (Leviathan et al.'s lemma) — pinned by the
    fixed-seed distribution test in tests/test_speculative.py."""
    p = np.asarray(p, np.float64)
    q = np.asarray(q, np.float64)
    d = int(draft_token)
    if rng.random_sample() < min(1.0, p[d] / max(float(q[d]), 1e-30)):
        return d, True
    resid = np.maximum(p - q, 0.0)
    z = float(resid.sum())
    if z <= 0.0:
        # numerically p <= q everywhere yet the accept draw failed
        # (p ~= q with float rounding): the residual is empty — the
        # target distribution itself is the correct fallback
        resid, z = p, float(p.sum())
    return int(rng.choice(len(p), p=resid / z)), False


class SpeculativeDecoder:
    """The per-engine speculative-decoding driver: owns the draft and
    verify programs/steps and runs the propose → verify → commit →
    rollback round. Built in two phases bracketing the engine's own
    setup: `build_draft()` BEFORE the target quantize pass (it must copy
    f32 weights), `finalize()` after the main step is prepared+bound."""

    def __init__(self, engine, config):
        if config is True:
            config = SpecConfig()
        elif isinstance(config, dict):
            config = SpecConfig(**config)
        enforce(isinstance(config, SpecConfig),
                f"speculative must be a SpecConfig (or True / kwargs "
                f"dict), got {type(config).__name__}",
                exc=InvalidArgumentError)
        self.engine = engine
        self.cfg = config
        self.draft_layers = (int(config.draft_layers)
                            if config.draft_layers is not None
                            else int(engine._builder_dims["num_layers"]))
        enforce(1 <= self.draft_layers
                <= engine._builder_dims["num_layers"],
                f"draft_layers {self.draft_layers} out of range "
                f"[1, {engine._builder_dims['num_layers']}]",
                exc=InvalidArgumentError)
        # -- counters (stats() / gauges) --
        self.rounds = 0
        self.draft_ticks = 0
        self.verify_forwards = 0
        self.draft_proposed = 0      # drafted window tokens evaluated
        self.draft_accepted = 0      # ... of those, accepted
        self.draft_s = 0.0
        self.verify_s = 0.0
        self.rolled_back = 0         # paged: block-table entries redone
        self._pool_check = os.environ.get(
            "PTPU_SPEC_POOL_CHECK", "0") not in ("0", "")

    # -- construction -----------------------------------------------------
    def build_draft(self):
        """Build the draft tick program (weights under `draft_`), copy
        the target's f32 weights into the draft names, and quantize the
        draft program at `cfg.draft`. MUST run before the target
        program's own quantize pass — afterwards the f32 payloads are
        gone from the scope."""
        from ..core import flags as _flags
        from ..core import unique_name
        from ..framework.passes import get_pass
        from ..framework.program import Program, program_guard
        from ..models import transformer

        eng = self.engine
        dims = {**eng._builder_dims, "num_layers": self.draft_layers}
        self._draft_program, self._draft_startup = Program(), Program()
        with program_guard(self._draft_program, self._draft_startup), \
                unique_name.guard():
            outs = transformer.transformer_lm_decode_tick(
                n_slots=eng.n_slots, max_len=eng.max_len,
                cache_prefix=eng._cache_prefix + "dr",
                param_prefix=DRAFT_PREFIX, emit_logp=True, **dims)
        self._draft_ids, self.draft_cache_names, self._draft_logp = outs
        # weight copy: draft_<w> <- <w> for every draft parameter whose
        # target twin is resident (trained or engine-initialized); the
        # rest (the draft's own slot caches) take the startup init. The
        # copy is BY REFERENCE — with an f32 draft over an f32 target
        # the two names share one device buffer until either side's
        # quantize pass erases its f32 name.
        cut = len(DRAFT_PREFIX)
        eng._init_missing_vars(self._draft_startup, aliases={
            n: n[cut:] for op in self._draft_startup.global_block().ops
            for n in op.output_names()
            if n.startswith(DRAFT_PREFIX) and eng.scope.has_var(n[cut:])})
        if self.cfg.draft in ("int8", "int4") \
                and _flags.get_flag("quant_params"):
            get_pass("quantize_params_pass",
                     bits=8 if self.cfg.draft == "int8" else 4)(
                self._draft_program, eng.scope)

    def finalize(self):
        """Build + quantize the verify program (target weights/caches by
        name — the quantize pass's twin path reuses the resident
        payloads), then prepare and BIND both steps. Runs after the
        engine's main step is prepared+bound."""
        from ..core import unique_name
        from ..framework.passes import get_pass
        from ..framework.program import Program, program_guard

        eng = self.engine
        g = self.cfg.gamma + 1
        self._verify_program, self._verify_startup = Program(), Program()
        with program_guard(self._verify_program, self._verify_startup), \
                unique_name.guard():
            (self._verify_ids, self._verify_logp,
             self.verify_cache_names) = eng._build_verify_tick(
                self.cfg.gamma)
        # target caches/weights are already resident; initialize only what
        # the verify startup would mint beyond them (none today — belt and
        # braces against future builder state). A name the target quantize
        # pass ERASED counts as resident (its payload lives on as
        # @qparam/@qscale): a fresh random init under it would make the
        # verify quantize pass below re-quantize garbage OVER the resident
        # payloads, which the main tick shares
        eng._init_missing_vars(self._verify_startup)
        if eng.quant is not None:
            get_pass("quantize_params_pass",
                     bits=8 if eng.quant == "int8" else 4)(
                self._verify_program, eng.scope)
        self._draft_feeds = _feed_arrays(self._draft_program)
        self._verify_feeds = _feed_arrays(self._verify_program)
        self._draft_step = eng._exe.prepare(
            self._draft_program, dict(self._draft_feeds),
            [self._draft_ids, self._draft_logp],
            eng.scope, name="draft_tick").bind(self._draft_feeds)
        self._verify_step = eng._exe.prepare(
            self._verify_program, dict(self._verify_feeds),
            [self._verify_ids, self._verify_logp],
            eng.scope, name="verify_tick").bind(self._verify_feeds)
        eng._bound_steps.update(draft=self._draft_step,
                                verify=self._verify_step)
        self._rng = np.random.RandomState(self.cfg.seed)
        self._windows = np.zeros((eng.n_slots, g), np.int64)
        self._from_draft = np.zeros((eng.n_slots, g), bool)
        self._register_metrics()

    def _register_metrics(self):
        from ..observability.metrics import default_registry, get_or_create
        eng = self.engine
        specs = (
            ("ptpu_engine_spec_acceptance_rate",
             "Accepted draft tokens over evaluated draft proposals.",
             self.acceptance_rate),
            ("ptpu_engine_spec_draft_overhead",
             "Draft-phase share of speculative round wall time.",
             self.draft_overhead),
            ("ptpu_engine_spec_tokens_per_target_forward",
             "Tokens emitted per target forward (verify + plain ticks) "
             "— the speculative amortization headline.",
             lambda: (eng.tokens_out / max(eng.target_forwards, 1))),
            ("ptpu_engine_spec_rolled_back_blocks",
             "Paged-KV block-table entries rolled back after verify "
             "rejected their whole span (0 on the slot engine).",
             lambda: self.rolled_back),
        )
        for name, help_, fn in specs:
            get_or_create(eng.metrics_registry, "gauge", name, help_,
                          fn=fn)
            # the process default registry carries the same gauges
            # labeled per engine, so /metrics scrapes and /healthz see
            # them without reaching into the engine registry
            get_or_create(default_registry(), "gauge", name, help_,
                          labels={"engine": eng._cache_prefix}, fn=fn)

    # -- telemetry --------------------------------------------------------
    def acceptance_rate(self) -> float:
        return (self.draft_accepted / self.draft_proposed
                if self.draft_proposed else 0.0)

    def draft_overhead(self) -> float:
        total = self.draft_s + self.verify_s
        return self.draft_s / total if total else 0.0

    def draft_param_bytes(self) -> int:
        """Resident bytes of the draft model's weight state — the
        `params_draft` census category, measured from the actual scope
        arrays (the figure the r17 ledger identity reconciles)."""
        from ..framework.costs import state_category
        from ..observability.memory import per_device_bytes
        eng = self.engine
        seen, total = set(), 0
        for b in self._draft_program.blocks:
            for name, v in b.vars.items():
                if name in seen or not v.persistable \
                        or not eng.scope.has_var(name):
                    continue
                seen.add(name)
                if state_category(v, name) == "params_draft":
                    total += int(per_device_bytes(eng.scope.get(name)))
        return total

    def stats(self) -> Dict:
        return {
            "gamma": self.cfg.gamma,
            "draft": self.cfg.draft,
            "draft_layers": self.draft_layers,
            "sampling": self.cfg.sampling,
            "rounds": self.rounds,
            "draft_ticks": self.draft_ticks,
            "verify_forwards": self.verify_forwards,
            "draft_proposed": self.draft_proposed,
            "draft_accepted": self.draft_accepted,
            "acceptance_rate": self.acceptance_rate(),
            "draft_overhead": self.draft_overhead(),
            "rolled_back_blocks": self.rolled_back,
            "draft_param_bytes": self.draft_param_bytes(),
        }

    # -- the round --------------------------------------------------------
    def round(self, active: Dict[int, "GenRequest"]) -> List:
        """One speculative round over `active` (slot → request, every
        one spec-capable): γ+1 draft ticks build the token window, one
        verify forward scores it, the commit walk advances each request
        through its accepted prefix (sharing `_advance_slot` with the
        plain tick — identical phase/finish semantics), and the paged
        engine rolls back fully-rejected blocks. Returns the requests
        that finished."""
        eng = self.engine
        cfg = self.cfg
        gamma = cfg.gamma
        g = gamma + 1
        windows, from_draft = self._windows, self._from_draft
        windows[:] = 0
        from_draft[:] = False
        draft_logp = [None] * g if cfg.sampling else None
        dtok = self._draft_feeds["tick_tok"]
        dpos = self._draft_feeds["tick_pos"]

        t0 = time.perf_counter()
        with _tracing.span("speculate", "engine/speculate",
                           active=len(active), gamma=gamma):
            for slot, req in active.items():
                windows[slot, 0] = req.next_tok
            for j in range(g):
                dtok[:] = 0
                dpos[:] = 0.0
                for slot, req in active.items():
                    dtok[slot, 0] = windows[slot, j]
                    dpos[slot, 0, 0] = float(req.fed + j)
                fetches = self._draft_step.run_bound()
                self.draft_ticks += 1
                if j == gamma:
                    # the last tick exists to write the draft cache at
                    # position fed+γ (a full acceptance starts the next
                    # round one past it); its proposal is unused
                    break
                ids = np.asarray(fetches[0])
                logp = np.asarray(fetches[1]) if cfg.sampling else None
                for slot, req in active.items():
                    nxt = req.fed + j + 1
                    if nxt < len(req.prompt):
                        # teacher-forced: the window token IS the prompt
                        windows[slot, j + 1] = req.prompt[nxt]
                        continue
                    if cfg.sampling:
                        q = np.exp(logp[slot, 0].astype(np.float64))
                        q /= q.sum()
                        tok = int(self._rng.choice(len(q), p=q))
                    else:
                        tok = int(ids[slot, 0])
                    windows[slot, j + 1] = tok
                    from_draft[slot, j + 1] = True
                if cfg.sampling:
                    draft_logp[j + 1] = logp
        td = time.perf_counter()
        self.draft_s += td - t0

        with _tracing.span("verify", "engine/verify",
                           active=len(active), width=g):
            vf = self._verify_feeds
            for a in vf.values():
                a[:] = 0
            vf["spec_tok"][:] = windows
            for slot, req in active.items():
                eng._fill_verify_row(vf, slot, req, g)
            if eng._target_state_owner != "verify":
                self._verify_step.refresh_state()
                eng._target_state_owner = "verify"
            fetches = self._verify_step.run_bound()
            self.verify_forwards += 1
            eng.target_forwards += 1
            ids = np.asarray(fetches[0])                    # [S, G]
            vlogp = (np.asarray(fetches[1])                 # [S, G, V]
                     if cfg.sampling else None)
        tv = time.perf_counter()
        self.verify_s += tv - td
        self.rounds += 1

        # -- commit walk per slot -----------------------------------------
        finished = []
        for slot, req in active.items():
            k0 = req.fed
            req.spec_draft_s += td - t0
            req.spec_verify_s += tv - td
            fin = False
            for i in range(g):
                if req.fed < len(req.prompt) - 1:
                    # prompt position: teacher-forced, always advances
                    # (the plain tick ignores the model output here too)
                    fin = eng._advance_slot(req, int(ids[slot, i]))
                    if fin:
                        break
                    continue
                # generated position: emit + decide continuation
                accept_next = False
                if not cfg.sampling:
                    emitted = int(ids[slot, i])
                    if i < gamma:
                        accept_next = int(windows[slot, i + 1]) == emitted
                elif i < gamma:
                    p = np.exp(vlogp[slot, i].astype(np.float64))
                    p /= p.sum()
                    q = np.exp(draft_logp[i + 1][slot, 0]
                               .astype(np.float64))
                    q /= q.sum()
                    emitted, accept_next = rejection_sample(
                        p, q, int(windows[slot, i + 1]), self._rng)
                else:
                    p = np.exp(vlogp[slot, gamma].astype(np.float64))
                    p /= p.sum()
                    emitted = int(self._rng.choice(len(p), p=p))
                if i < gamma and from_draft[slot, i + 1]:
                    self.draft_proposed += 1
                    if accept_next:
                        self.draft_accepted += 1
                fin = eng._advance_slot(req, emitted)
                if fin or not accept_next:
                    break
            if fin:
                finished.append(req)
            elif req.fed < k0 + g:
                # rejected tail [fed, k0+g): fully-dead blocks roll back
                # (paged; the slot engine's stale rows are masked and
                # overwritten before exposure — rollback is a no-op)
                self.rolled_back += eng._spec_rollback(req, req.fed,
                                                       k0 + g)
        if self._pool_check and hasattr(eng, "pager"):
            eng.pager.pool.check()
        san = getattr(getattr(eng, "pager", None), "sanitizer", None)
        if san is not None:
            # shadow-state census after every round: rollback remapped
            # blocks and the accept path advanced write frontiers — the
            # full ownership invariants must hold at the boundary
            san.verify_full("speculative-round")
        return finished
