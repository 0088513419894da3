"""EngineCore: the step-shaped device-dispatch layer of the serve stack
(DESIGN.md §13).

Everything in ``repro.serve`` that touches the device lives in this file:

* :class:`ServeEngine` — the static-batching dense-cache engine (one
  shared prefill, lock-step decode). Re-exported from
  ``repro.serve.engine`` for back-compat.
* :class:`EngineCore` — the continuous-batching core. One public
  :meth:`EngineCore.step` performs exactly **one** scheduling decision +
  at most one device dispatch (admit/adopt, one prefill chunk, or one
  batched decode step) and returns structured :class:`TokenEvent`\\ s.
  Requests move through an explicit state machine::

      WAITING -> PREFILLING -> DECODING -> FINISHED
                      \\_________/     \\-> PREEMPTED (-> WAITING)
                any live state -> CANCELLED

  The batch adapter (``engine.ContinuousBatchingEngine.run``) and the
  streaming front door (``api.StreamingEngine``) are both thin host-side
  drivers over this class — the layering lint
  (``scripts/check_engine_layering.sh``) keeps it that way.

Bit-identical replay invariant: driving :meth:`step` to quiescence over a
fixed request list reproduces the pre-refactor monolithic ``run()`` loop
exactly — same greedy tokens, same page-adoption decisions, same
scheduler metrics (asserted against the frozen oracle in
``tests/cb_reference.py``). The step machine therefore mirrors the
monolith's *cycle* structure: arrivals are pumped and the chunk-prefill
budget reset once per cycle (admit phase), not once per step.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cache_layout import PagedLayout, PrefixIndex
from repro.distributed import ctx
from repro.distributed import serving as dsrv
from repro.distributed.sharding import serving_rules
from repro.models.registry import Model
from repro.serve.qos import (
    DegradeController, QosConfig, QosState, RateEstimator,
)
from repro.serve.scheduler import Request, Scheduler
from repro.spec import SpecConfig, make_proposer, make_verifier
from repro.utils import (
    cdiv, nearest_rank_pct, pow2_bucket, tree_bytes as _tree_bytes,
)


@dataclasses.dataclass(frozen=True)
class GenerationConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0      # 0 => greedy
    top_k: int = 0
    eos_id: int = -1              # -1 => never stop early
    seed: int = 0


def _sample(logits, key, gen: GenerationConfig):
    if gen.temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / gen.temperature
    if gen.top_k > 0:
        vals, _ = jax.lax.top_k(logits, gen.top_k)
        logits = jnp.where(logits < vals[..., -1:], -1e30, logits)
    return jax.random.categorical(key, logits).astype(jnp.int32)


#: A host span on the profiler's clock: ``engine.*`` spans nest inside
#: ``engine.step`` and lie in one trace with the device's programs, so a
#: device idle gap can be put down to the engine code the host was in.
#: Off the profiler one costs about a microsecond.
_span = jax.profiler.TraceAnnotation


# ---------------------------------------------------------------------------
# Request lifecycle states and the event taxonomy
# ---------------------------------------------------------------------------

WAITING = "waiting"          # submitted, not yet holding a slot
PREFILLING = "prefilling"    # slot assigned, context not fully encoded
DECODING = "decoding"        # prefill done, producing tokens
FINISHED = "finished"        # EOS / length limit; slot + pages released
PREEMPTED = "preempted"      # pages reclaimed under pressure; requeued
CANCELLED = "cancelled"      # caller cancelled; slot + pages released
REJECTED = "rejected"        # QoS bounded-queue backpressure at intake
SHED = "shed"                # QoS deadline shed before admission

#: Every kind a :class:`TokenEvent` can carry, in lifecycle order.
#: ``reject`` and ``shed`` are terminal: a rejected/shed rid never emits
#: another event (the event-stream invariant bench arms assert).
EVENT_KINDS = ("admit", "first_token", "token", "finish", "preempt",
               "cancel", "reject", "shed")


@dataclasses.dataclass(frozen=True)
class TokenEvent:
    """One observable engine transition, stamped with the device-time
    clock (queueing + measured compute seconds, same clock the latency
    percentiles are computed on).

    ``token`` is the sampled token id for ``first_token``/``token``
    events; for a ``preempt`` event it is the **retracted** token — the
    victim's most recent token is withdrawn (it was never fed back to
    the model) and re-sampled on resume, so a consumer accumulating
    streamed tokens must drop its last token for that rid when a
    ``preempt`` arrives. None otherwise. ``slot`` is the cache slot
    involved (-1 when the request never held one, e.g. a queued
    cancel).

    ``ordinal`` is the token's 0-based index in the request's output
    stream (monotone per rid; a preempt retraction rewinds it by one,
    matching the drop-last-token rule above). Speculative decode
    (DESIGN.md §15) can retire several tokens from one dispatch: each
    gets its own event sharing one clock stamp, with ``span`` the total
    tokens that dispatch retired for the rid and ``span_ix`` the event's
    position inside the span — plain decode is the degenerate
    ``span=1, span_ix=0``."""

    kind: str
    rid: int
    t: float
    token: Optional[int] = None
    slot: int = -1
    ordinal: int = -1
    span: int = 1
    span_ix: int = 0
    #: why a ``reject``/``shed`` event happened (``"queue_full"``,
    #: ``"deadline_blown"``, ``"deadline_unmeetable"``); None otherwise —
    #: clients see the reason instead of a silent hang.
    reason: Optional[str] = None


class ServeEngine:
    """Static batching: one shared prefill, lock-step decode, the whole
    batch stalls until its slowest request finishes. Kept as the baseline
    (and for single-batch offline use)."""

    def __init__(self, model: Model, params, max_len: int,
                 mesh=None, rules: Optional[dict] = None):
        self.model = model
        self.params = params
        self.max_len = max_len
        self.mesh = mesh
        self.rules = rules
        self._prefill = jax.jit(model.prefill)
        # donate the decode state: cache buffers update in place instead of
        # being copied every step (the state is rebound to the result)
        self._decode = jax.jit(model.decode, donate_argnums=(1,))
        self._sample = jax.jit(_sample, static_argnames=("gen",))

    def _ctx(self):
        if self.mesh is not None and self.rules is not None:
            return ctx.use_sharding(self.mesh, self.rules)
        return contextlib.nullcontext()

    def generate(self, batch: dict,
                 gen: Optional[GenerationConfig] = None):
        """batch: prompt inputs (tokens (B, Tp) [+ frames/patches]).

        Returns dict with generated tokens (B, max_new_tokens) and timings.
        """
        gen = gen if gen is not None else GenerationConfig()
        b = batch["tokens"].shape[0]
        cfg = self.model.cfg
        if cfg.family in ("dense", "moe", "vlm") and cfg.window == 0:
            # linear cache: prompt + appended tokens must fit (the last
            # sampled token is never appended, hence the -1)
            tp = batch["tokens"].shape[1] + (
                cfg.frontend_tokens if cfg.family == "vlm" else 0)
            if tp + gen.max_new_tokens - 1 > self.max_len:
                raise ValueError(
                    f"prompt {tp} + max_new_tokens {gen.max_new_tokens} "
                    f"exceeds cache capacity {self.max_len}")
        key = jax.random.PRNGKey(gen.seed)
        with self._ctx():
            state = self.model.init_decode_state(b, self.max_len)
            t0 = time.monotonic()
            logits, state = self._prefill(self.params, batch, state)
            logits.block_until_ready()  # sync: static-engine prefill timing
            t_prefill = time.monotonic() - t0

            toks = []
            tok = self._sample(logits, key, gen)
            toks.append(tok)
            t0 = time.monotonic()
            done = jnp.zeros((b,), bool)
            for i in range(gen.max_new_tokens - 1):
                logits, state = self._decode(self.params, state, tok)
                key, sub = jax.random.split(key)
                tok = self._sample(logits, sub, gen)
                if gen.eos_id >= 0:
                    done = done | (tok == gen.eos_id)
                    tok = jnp.where(done, gen.eos_id, tok)
                toks.append(tok)
            jax.block_until_ready(tok)  # sync: static-engine decode timing
            t_decode = time.monotonic() - t0
        out = jnp.stack(toks, axis=1)
        n_dec = max(gen.max_new_tokens - 1, 1)
        return {
            "tokens": np.asarray(out),
            "prefill_s": t_prefill,
            "decode_s": t_decode,
            "tokens_per_s": b * n_dec / max(t_decode, 1e-9),
            "cache_bytes": _tree_bytes(state),
            "cache_bytes_per_layer": (
                self.model.cache_layer_bytes(state)
                if self.model.cache_layer_bytes else None),
        }


# ---------------------------------------------------------------------------
# EngineCore: the continuous-batching step loop
# ---------------------------------------------------------------------------


class EngineCore:
    """Step-shaped continuous-batching core over per-layer paged KV caches.

    Construction compiles the device functions and fixes the pool layout
    (``max_slots`` concurrent requests over ``num_pages`` pages of
    ``group_size`` tokens; ``prefill_chunk``/``prefix_cache``/
    ``table_slicing`` as on the old monolithic engine — see
    ``engine.ContinuousBatchingEngine`` for the knob docs). Compiled
    functions persist across sessions; :meth:`reset` starts a fresh
    session (new device state, scheduler, prefix index, clock, RNG).

    Drive it with :meth:`add_request` / :meth:`cancel` / :meth:`step`:
    each ``step()`` makes one scheduling decision, performs at most one
    device dispatch, advances the device-time clock, and returns the
    :class:`TokenEvent`\\ s it caused. ``step()`` with no work is a no-op
    returning ``[]`` — an open-loop driver can keep calling it as
    requests arrive. The clock is *simulated*: while the engine is idle,
    it jumps to the next scheduled arrival instead of sleeping, so batch
    replays compose queueing + compute without wall-clock waits.
    """

    def __init__(self, model: Model, params, *, max_slots: int = 4,
                 max_len: int = 256, num_pages: Optional[int] = None,
                 mesh=None, rules: Optional[dict] = None,
                 table_slicing: bool = True, prefix_cache: bool = False,
                 prefill_chunk: int = 0, prefill_budget: int = 0,
                 spec: Optional[SpecConfig] = None,
                 qos: Optional[QosConfig] = None, chaos=None,
                 runahead: int = 0):
        if model.decode_paged is None:
            raise ValueError(
                f"family {model.cfg.family!r} has no paged decode path")
        self.model = model
        self.params = params
        self.mesh = mesh
        # a mesh without explicit rules gets the serving rule set: heads
        # (and the KV page pools, via distributed/serving.py) over the
        # "model" axis where divisible, batch over the data axes —
        # DESIGN.md §17. Rules without a mesh stay inert (matching _ctx).
        if mesh is not None and rules is None:
            rules = serving_rules(model.cfg, mesh, max_slots)
        self.rules = rules
        if mesh is not None:
            # params are replicated: head-sharded TP partitions cache
            # *pools*; weight TP is a separate (training-side) concern
            self.params = jax.device_put(
                params, jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec()))
        # table_slicing=False ships the full (S, pages_per_slot) table every
        # step — the pre-width-bucketing behavior, kept as a benchmark
        # baseline (decode cost then scales with pool capacity)
        self.table_slicing = table_slicing
        # chunk prefill width-slices the slot's table row only on the
        # page-native backends: the kernel's read volume then tracks the
        # live prefix instead of the pool capacity. The jnp reference
        # keeps the full row (it gathers the whole pool regardless — the
        # benchmark contrast), and decode slicing stays independent, so
        # A/B arms share bit-identical decode steps.
        self._prefill_slicing = (table_slicing
                                 and model.cfg.prefill_backend != "jnp")
        # page == quantization group: every layer of the policy must agree
        # on the group size (bit-widths/methods may differ per layer)
        g = model.cfg.policy.page_group_size()
        pages_per_slot = cdiv(max_len, g)
        if num_pages is None:
            num_pages = max_slots * pages_per_slot
        self.layout = PagedLayout(page_size=g, num_pages=num_pages,
                                  slots=max_slots,
                                  pages_per_slot=pages_per_slot)
        self.prefix_cache = bool(prefix_cache)
        chunk = int(prefill_chunk)
        if chunk < 0:
            raise ValueError(f"prefill_chunk must be >= 0, got {chunk}")
        if self.prefix_cache and chunk == 0:
            chunk = 2 * g   # sharing requires the chunk-aligned path
        if chunk:
            chunk = cdiv(chunk, g) * g   # page-aligned chunks
            if model.prefill_paged_chunk is None:
                raise ValueError(
                    f"family {model.cfg.family!r} has no chunked prefill "
                    "path (prefill_paged_chunk)")
        self.prefill_chunk = chunk
        self.prefill_budget = int(prefill_budget) if prefill_budget else chunk
        # the registry names each function after its program (jit_prefill,
        # jit_prefill_chunk, jit_copy_pages, jit_decode_step,
        # jit_runahead_horizon; the verifier's is jit_verify)
        self._prefill = jax.jit(model.prefill_paged)
        if chunk:
            self._prefill_chunk = jax.jit(model.prefill_paged_chunk,
                                          donate_argnums=(2,))
        if model.copy_pages is not None:
            self._copy_pages = jax.jit(model.copy_pages, donate_argnums=(0,))
        # donate the paged state: page pools update in place each step
        self._decode = jax.jit(model.decode_paged, donate_argnums=(1,))
        self._sample = jax.jit(_sample, static_argnames=("gen",))
        # run-ahead decode (DESIGN.md §18): when the horizon planner
        # predicts no scheduling event for the next `runahead` steps, one
        # lax.scan dispatch covers all of them — on-device sampling +
        # EOS/budget masking, a single host sync per (H, slots) token
        # block, and the next horizon chained off device-resident carries
        # while the previous block is still in flight. 0/1 disables it.
        self.runahead = int(runahead)
        if self.runahead < 0:
            raise ValueError(f"runahead must be >= 0, got {runahead}")
        if self.runahead > 1:
            if model.decode_runahead is None:
                raise ValueError(
                    f"family {model.cfg.family!r} has no run-ahead decode "
                    "path (decode_runahead)")
            self._runahead_fn = jax.jit(
                model.decode_runahead, donate_argnums=(1,),
                static_argnames=("horizon", "temperature", "top_k",
                                 "eos_id"))
        # speculative decode (DESIGN.md §15): a host-side proposer guesses
        # up to spec.k tokens per slot; one verify dispatch scores the
        # whole span and commits only accepted tokens through the vanilla
        # append path. Greedy-only — reset() enforces temperature 0.
        self.spec = spec if spec is not None and spec.mode != "off" else None
        if self.spec is not None:
            self._verify = jax.jit(make_verifier(model), donate_argnums=(1,))
            self._proposer = make_proposer(
                self.spec, target_cfg=model.cfg, target_model=model,
                target_params=params, max_len=self.layout.tokens_per_slot)
        # QoS (DESIGN.md §16): None means the engine is byte-for-byte the
        # pre-QoS FCFS engine — every QoS branch is gated on it.
        self.qos_cfg = qos
        # chaos is a pre-built injector (duck-typed: core never imports
        # serve.chaos — enforced by scripts/check_engine_layering.sh);
        # None takes zero chaos branches.
        self.chaos = chaos
        self.reset()

    # --- session lifecycle ------------------------------------------------

    def reset(self, gen: Optional[GenerationConfig] = None) -> None:
        """Start a fresh serving session: new device state, scheduler,
        prefix index, clock, RNG, and metrics. ``gen`` fixes the session's
        sampling configuration (per-request budgets still come from
        ``Request.max_new_tokens``)."""
        self.gen = gen if gen is not None else GenerationConfig()
        if self.spec is not None:
            if self.gen.temperature > 0.0:
                raise ValueError(
                    "speculative decoding requires greedy sampling "
                    "(temperature 0): acceptance compares the target "
                    "model's argmax per position")
            self._proposer.reset()
        self.prefix = (PrefixIndex(self.layout, self.prefill_chunk)
                       if self.prefix_cache else None)
        self.qos = QosState(self.qos_cfg) if self.qos_cfg is not None \
            else None
        self.degrade = (DegradeController(self.qos_cfg)
                        if self.qos_cfg is not None and self.qos_cfg.degrade
                        else None)
        self._prefill_rate = RateEstimator() if self.qos is not None \
            else None
        self.sched = Scheduler(self.layout, prefix_index=self.prefix,
                               chunk_tokens=self.prefill_chunk,
                               qos=self.qos)
        self.state = self._place_state(
            self.model.init_paged_state(self.layout))
        s = self.layout.slots
        self.clock = 0.0
        self._key = jax.random.PRNGKey(self.gen.seed)
        self._next_tok = np.zeros((s,), np.int32)
        self._lengths = np.zeros((s,), np.int64)
        self._eff_max: dict[int, int] = {}
        self._admit_seq: dict[int, int] = {}   # slot -> admission order
        self._prefilling: dict[int, dict] = {}  # slot -> {"ctx", "off"}
        self._n_admitted = 0
        self._arrivals: list[Request] = []     # sorted by arrival_time
        self.completed: list[Request] = []
        self.cancelled: list[Request] = []
        self.shed: list[Request] = []          # QoS deadline sheds
        self.rejected: list[Request] = []      # QoS queue-full rejects
        self._intake_events: list[TokenEvent] = []
        self._cycle = 0                        # chaos schedule domain
        self._quarantine_release = -1
        self._preempted_cycle = False          # degrade pressure signal
        self.proposer_faults = 0
        if self.chaos is not None:
            self.chaos.reset()
        # cycle state: the step machine mirrors one monolith loop
        # iteration as the phase sequence begin -> admit* -> prefill* ->
        # decode, pumping arrivals and resetting the chunk budget once
        # per cycle (bit-identical-replay invariant)
        self._phase = "begin"
        self._progressed = False
        self._budget_left = 0
        # metrics
        self._util: list[float] = []
        self._active_hist: list[int] = []
        self._step_times: list[float] = []
        self.decode_steps = 0
        self.prefill_chunks = 0     # chunked-prefill dispatches
        self.prefill_computed = 0   # prefill tokens run through the model
        self.prefill_skipped = 0    # prefill tokens served from adoption
        self.cow_splits = 0
        self.spec_steps = 0         # decode steps that verified >=1 draft
        self.spec_drafted = 0       # draft tokens sent to verification
        self.spec_accepted = 0      # draft tokens accepted
        self.host_sync_s = 0.0      # host time blocked in engine.sync
        # run-ahead pipeline state (DESIGN.md §18): the in-flight horizon
        # record (which carries its own optimistic per-slot budgets)
        self._inflight: Optional[dict] = None
        self._land_t = 0.0          # wall time the last horizon landed
        self.runahead_horizons = 0
        self.runahead_tokens = 0

    # --- request intake ---------------------------------------------------

    def add_request(self, req: Request) -> int:
        """Enqueue ``req`` for admission at ``req.arrival_time`` on the
        engine clock (insertion-ordered among equal times, so a
        pre-sorted batch replays FCFS exactly). Returns the rid.

        Rejects (ValueError) a context that can never fit one slot —
        at intake, so an open-loop session is never poisoned by an
        oversized request reaching the queue head mid-stream.

        With QoS bounded-queue backpressure (``QosConfig.max_pending``),
        intake over a full queue marks the request ``REJECTED`` and
        queues an explicit ``reject`` TokenEvent (``reason=
        "queue_full"``) — never a silent hang; the rid is still
        returned so the caller can match the event."""
        need = self.layout.pages_for(req.context_len + 1)
        if need > self.layout.pages_per_slot:
            raise ValueError(
                f"request {req.rid}: context {req.context_len} needs "
                f"{need} pages > pages_per_slot "
                f"{self.layout.pages_per_slot}")
        if self.qos is not None and self.qos_cfg.max_pending > 0 and \
                len(self._arrivals) + len(self.sched.pending) >= \
                self.qos_cfg.max_pending:
            req.state = REJECTED
            req.t_done = self.clock
            self.rejected.append(req)
            self.qos.on_reject(req)
            self._intake_events.append(TokenEvent(
                "reject", req.rid, self.clock, reason="queue_full"))
            return req.rid
        req.state = WAITING
        i = len(self._arrivals)
        while i > 0 and self._arrivals[i - 1].arrival_time > \
                req.arrival_time:
            i -= 1
        self._arrivals.insert(i, req)
        return req.rid

    def take_intake_events(self) -> list[TokenEvent]:
        """Drain events produced at intake (QoS rejects). :meth:`step`
        prepends these automatically; streaming drivers that want the
        reject surfaced before the next step may drain them directly."""
        evs, self._intake_events = self._intake_events, []
        return evs

    def cancel(self, rid: int) -> list[TokenEvent]:
        """Cancel a request wherever it is in the lifecycle.

        * scheduled / pending: dropped from the queue, no pages involved.
        * mid-prefill or mid-decode: the slot is released through the
          scheduler, which *decrefs* the slot's pages — pages shared with
          other slots or pinned by the prefix index survive with their
          encoded bytes intact; exclusive pages return to the free list.
          The slot is immediately reusable by the next admission.

        Returns the ``cancel`` event ([] when ``rid`` is unknown or
        already finished — a documented no-op, never an error).
        Host-side only — no new device dispatch; an in-flight run-ahead
        horizon is landed first (its token events precede the cancel in
        the returned list), so a cancelled rid never emits tokens after
        its terminal event and its pages are only released once the
        device is done writing them."""
        pre = self._reconcile_horizon() if self._inflight is not None \
            else []
        for i, r in enumerate(self._arrivals):
            if r.rid == rid:
                del self._arrivals[i]
                return pre + self._cancelled(r)
        summary = self.sched.cancel(rid)
        if summary is None:
            return pre
        req, slot = summary.req, summary.slot
        if slot >= 0:
            self._prefilling.pop(slot, None)
            self._eff_max.pop(rid, None)
        if self.spec is not None:
            self._proposer.release(rid)
        return pre + self._cancelled(req, slot)

    def _cancelled(self, req: Request, slot: int = -1) -> list[TokenEvent]:
        req.state = CANCELLED
        req.t_done = self.clock
        self.cancelled.append(req)
        return [TokenEvent("cancel", req.rid, self.clock, slot=slot)]

    @property
    def has_work(self) -> bool:
        return bool(self._arrivals) or self.sched.has_work or \
            self._inflight is not None

    # --- compile helpers --------------------------------------------------

    def _decode_widths(self) -> list[int]:
        """Page-table width buckets the decode step compiles against:
        powers of two capped at ``pages_per_slot``."""
        n = self.layout.pages_per_slot
        if not self.table_slicing:
            return [n]
        widths, w = [], 1
        while w < n:
            widths.append(w)
            w *= 2
        widths.append(n)
        return widths

    def _spec_q_buckets(self) -> list[int]:
        """Span-width buckets (Q = 1 bonus + drafts) the verify dispatch
        compiles against: 1 + pow2 draft counts, capped at ``spec.k + 1``
        — and at the group size, since the span clamp
        (:meth:`_propose_drafts`) keeps every span inside its slot's
        current quantization group."""
        cap = min(self.spec.k + 1, self.layout.page_size)
        out, q = [], 2
        while q < cap:
            out.append(q)
            q = 2 * (q - 1) + 1
        out.append(cap)
        return out

    def _spec_q(self, q_needed: int) -> int:
        """Smallest span-width bucket covering ``q_needed`` positions."""
        for q in self._spec_q_buckets():
            if q >= q_needed:
                return q
        return self.spec.k + 1

    def _step_width(self, pages_needed: int) -> int:
        """Smallest width bucket covering ``pages_needed`` live pages.

        The decode step reads the page table only up to this width, so its
        per-step cost scales with the *live* context of the current batch
        — O(max live tokens) — instead of the pool capacity."""
        if not self.table_slicing:
            return self.layout.pages_per_slot
        for w in self._decode_widths():
            if w >= pages_needed:
                return w
        return self.layout.pages_per_slot

    def _prefill_widths(self, prompt_lens: list[int]) -> list[int]:
        """Table-row width buckets the chunk prefill compiles against:
        the pow2 decode buckets up to the largest prompt's page count when
        the page-native backends slice the row, the full width otherwise."""
        if not self._prefill_slicing:
            return [self.layout.pages_per_slot]
        maxw = self._step_width(
            self.layout.pages_for(max(prompt_lens))
            if prompt_lens else self.layout.pages_per_slot)
        return [w for w in self._decode_widths() if w <= maxw]

    def _ctx(self):
        if self.mesh is not None and self.rules is not None:
            return ctx.use_sharding(self.mesh, self.rules)
        return contextlib.nullcontext()

    @contextlib.contextmanager
    def _sync(self, what: str):
        """An ``engine.sync`` span around one blocking fetch of the
        serving path; its time counts to ``host_sync_s``."""
        t0 = time.monotonic()
        with _span("engine.sync", what=what):
            yield
        self.host_sync_s += time.monotonic() - t0

    def _place_state(self, state):
        """Place fresh paged state on the mesh: page pools partitioned
        over KV heads when the rule set maps ``kv_heads`` to a mesh axis,
        fully replicated otherwise (the GQA-indivisible fallback). Meshless
        engines pass through untouched. reset() and warmup() both route
        here so the donated decode signature sees one consistent
        placement."""
        if self.mesh is None:
            return state
        axis = (self.rules or {}).get("kv_heads")
        if isinstance(axis, str):
            return dsrv.shard_paged_state(state, self.mesh, axis)
        repl = jax.sharding.NamedSharding(self.mesh,
                                          jax.sharding.PartitionSpec())
        return jax.device_put(state, jax.tree_util.tree_map(
            lambda _: repl, state))

    def _bucket(self, prompt_len: int) -> int:
        return min(pow2_bucket(prompt_len, self.layout.page_size),
                   self.layout.tokens_per_slot)

    def warmup(self, prompt_lens: list[int],
               gen: Optional[GenerationConfig] = None) -> None:
        """Compile prefill buckets (or the single chunk shape) + the decode
        step against throwaway state."""
        gen = gen if gen is not None else GenerationConfig()
        state = self._place_state(self.model.init_paged_state(self.layout))
        sched = Scheduler(self.layout)
        key = jax.random.PRNGKey(0)
        s = self.layout.slots
        with self._ctx():
            if self.prefill_chunk:
                # one compile per table-row width covers every chunk of
                # every prompt (a single full-width compile unless the
                # page-native prefill backends slice the row)
                c = self.prefill_chunk
                for w in self._prefill_widths(prompt_lens):
                    logits, state = self._prefill_chunk(
                        self.params, jnp.zeros((1, c), jnp.int32), state,
                        jnp.zeros((), jnp.int32),
                        sched.alloc.table()[0][:w],
                        jnp.zeros((), jnp.int32), jnp.asarray(c, jnp.int32))
                    jax.block_until_ready(self._sample(logits, key, gen))  # sync: warmup compile barrier
            else:
                for tp in sorted({self._bucket(t) for t in prompt_lens}):
                    logits, state = self._prefill(
                        self.params, jnp.zeros((1, tp), jnp.int32), state,
                        jnp.zeros((), jnp.int32), sched.alloc.table()[0],
                        jnp.asarray(tp, jnp.int32))
                    jax.block_until_ready(self._sample(logits, key, gen))  # sync: warmup compile barrier
            for w in self._decode_widths():
                logits, state = self._decode(
                    self.params, state, jnp.zeros((s,), jnp.int32),
                    sched.alloc.table()[:, :w], jnp.zeros((s,), bool))
                jax.block_until_ready(self._sample(logits, key, gen))  # sync: warmup compile barrier
                if self.runahead > 1:
                    rtoks, state, _t, _k, _d, _r = self._runahead_fn(
                        self.params, state, jnp.zeros((s,), jnp.int32),
                        sched.alloc.table()[:, :w],
                        jnp.zeros((s,), bool), key,
                        jnp.zeros((s,), jnp.int32), jnp.zeros((s,), bool),
                        horizon=self.runahead,
                        temperature=gen.temperature, top_k=gen.top_k,
                        eos_id=gen.eos_id)
                    jax.block_until_ready(rtoks)  # sync: warmup compile barrier
                if self.spec is not None:
                    for q in self._spec_q_buckets():
                        preds, _, state = self._verify(
                            self.params, state,
                            jnp.zeros((s, q), jnp.int32),
                            jnp.zeros((s,), jnp.int32),
                            sched.alloc.table()[:, :w],
                            jnp.zeros((s,), bool))
                        jax.block_until_ready(preds)  # sync: warmup compile barrier

    # --- the step loop ----------------------------------------------------

    def step(self) -> list[TokenEvent]:
        """One scheduling decision + at most one device dispatch.

        Exactly one of, in cycle priority order:

        1. admit the next admissible request (adopting prefix pages;
           classic mode also runs its one-shot prefill here),
        2. run one prefill chunk (chunked mode, under the cycle budget),
        3. run one batched decode step over all decode-ready slots — or
           recompute-preempt the youngest admission when every slot
           stalls on a dry pool.

        Idle with scheduled arrivals jumps the clock; idle with no work
        at all returns ``[]`` immediately (streaming drivers poll)."""
        with _span("engine.step", cycle=self._cycle), self._ctx():
            intake = self.take_intake_events()
            return intake + self._step() if intake else self._step()

    def _step(self) -> list[TokenEvent]:
        if self._inflight is not None:
            # a run-ahead horizon is in flight: chain the next horizon
            # off its device-resident carries while it computes, then
            # land it and reconcile its events (DESIGN.md §18). The
            # phase machine only runs once the pipeline drains.
            return self._advance_runahead()
        if self._phase == "begin":
            self._pump_arrivals()
            if not self.sched.has_work:
                if not self._arrivals:
                    return []   # fully idle: wait for add_request()
                # idle engine: jump the clock to the next arrival
                self.clock = max(self.clock, self._arrivals[0].arrival_time)
                self._pump_arrivals()
            self._cycle += 1
            self._progressed = False
            self._budget_left = self.prefill_budget
            if self.qos is not None:
                self.qos.refill(self.clock)
            if self.degrade is not None:
                self.degrade.update(self.sched.utilization(),
                                    self._preempted_cycle)
                self._preempted_cycle = False
                self._budget_left = self.degrade.prefill_budget(
                    self.prefill_budget)
                if self.degrade.evict_ahead:
                    # proactively drop index-only prefix pages so live
                    # decode keeps ~1 page of headroom per active slot,
                    # ahead of the preemption path
                    want = (self.sched.num_active
                            - self.sched.alloc.free_pages)
                    if want > 0:
                        self.sched.reclaim(want)
            self._phase = "admit"
            if self.chaos is not None:
                events = self._apply_chaos()
                if events:
                    return events   # faults end the begin phase

        if self._phase == "admit":
            if self.qos is not None:
                shed_evs = self._shed_unmeetable()
                if shed_evs:
                    return shed_evs
            req = self.sched.admissible()
            if req is not None:
                return self._admit(req)
            if not self.sched.active:
                # nothing running and the queue head can't fit: future
                # arrivals can't free pages, so either wait them out
                # (clock jump) or fail loudly
                self._phase = "begin"
                if self.sched.pending and self._arrivals:
                    self.clock = max(self.clock,
                                     self._arrivals[0].arrival_time)
                    return []
                if self.sched.pending:
                    if self._quarantine_release >= 0:
                        # a chaos quarantine (not pool size) is holding
                        # the pages; it lifts at a known cycle — spin
                        return []
                    if self.qos is not None:
                        # an idle engine's clock freezes, so a tenant
                        # bucket blocking the whole queue would never
                        # refill — jump to the earliest affordable time
                        # (deadlines blown by the wait shed next cycle)
                        t = self.qos.next_affordable_time(
                            self.sched.pending, self.clock)
                        if t is not None:
                            self.clock = max(self.clock, t)
                            return []
                    raise RuntimeError(
                        "pool cannot fit a single pending request "
                        "(num_pages too small)")
                return []
            self._phase = "prefill"

        if self._phase == "prefill":
            if self._budget_left > 0 and self._prefilling:
                return self._prefill_one_chunk()
            self._phase = "decode"

        self._phase = "begin"   # decode (or preempt) ends the cycle
        return self._decode_step()

    def _pump_arrivals(self) -> None:
        while self._arrivals and \
                self._arrivals[0].arrival_time <= self.clock:
            self.sched.submit(self._arrivals.pop(0))

    # --- QoS + chaos seams (DESIGN.md §16) --------------------------------

    def _apply_chaos(self) -> list[TokenEvent]:
        """The single chaos seam: once per cycle, apply the injector's
        declarative faults through production paths (allocator
        quarantine, engine clock, the real cancel path). Inert when the
        injector's schedule yields nothing this cycle."""
        if 0 <= self._quarantine_release <= self._cycle:
            self.sched.alloc.release_quarantine()
            self._quarantine_release = -1
        events: list[TokenEvent] = []
        for act in self.chaos.actions(self._cycle):
            if act[0] == "exhaust":
                self.sched.alloc.quarantine(self.sched.alloc.free_pages)
                self._quarantine_release = self._cycle + int(act[1])
            elif act[0] == "slow":
                self.clock += float(act[1])
            elif act[0] == "cancel_storm":
                live = [r.rid for r in self.sched.pending] + \
                    [r.rid for r in self.sched.active.values()]
                for rid in self.chaos.pick_victims(live, float(act[1])):
                    events += self.cancel(rid)
        return events

    def _shed_unmeetable(self) -> list[TokenEvent]:
        """Deadline-aware admission control: drop pending requests whose
        TTFT deadline is already blown or provably unmeetable given the
        queue ahead of them and the measured prefill rate, emitting
        explicit ``shed`` events (QosState.unmeetable documents the
        projection)."""
        inflight = sum(len(cur["ctx"]) - cur["off"]
                       for cur in self._prefilling.values())
        rate = self._prefill_rate.rate if self._prefill_rate else None
        doomed = self.qos.unmeetable(self.sched.pending, self.clock,
                                     rate, inflight)
        events: list[TokenEvent] = []
        for req, reason in doomed:
            self.sched.cancel(req.rid)
            req.state = SHED
            req.t_done = self.clock
            self.shed.append(req)
            self.qos.on_shed(req)
            events.append(TokenEvent("shed", req.rid, self.clock,
                                     reason=reason))
        return events

    def _admit(self, req: Request) -> list[TokenEvent]:
        """Admission: assign a slot, adopt prefix hits, reserve pages.
        Chunked mode queues the prompt for interleaved chunk prefill;
        classic mode prefills the whole context in one shot (a preempted
        request resumes by prefilling its full context either way)."""
        with _span("engine.admit", rid=req.rid):
            return self._admit_slot(req)

    def _admit_slot(self, req: Request) -> list[TokenEvent]:
        slot = self.sched.admit(req)
        req.state = PREFILLING
        self._admit_seq[slot] = self._n_admitted
        self._n_admitted += 1
        ctx_toks = req.context_tokens()
        tl = len(ctx_toks)
        self._eff_max[req.rid] = req.done_tokens + min(
            req.max_new_tokens - req.done_tokens,
            self.layout.tokens_per_slot - tl + 1)
        events = [TokenEvent("admit", req.rid, self.clock, slot=slot)]
        if self.prefill_chunk:
            # adopted prefix pages skip their prefill compute; chunks
            # cover [prefix_hit_tokens, tl)
            self._prefilling[slot] = {"ctx": ctx_toks,
                                      "off": req.prefix_hit_tokens}
            self._lengths[slot] = req.prefix_hit_tokens
            self.prefill_skipped += req.prefix_hit_tokens
            return events
        with _span("engine.dispatch", program="prefill"):
            toks = np.zeros((1, self._bucket(tl)), np.int32)
            toks[0, :tl] = ctx_toks
            t0 = time.monotonic()
            logits, self.state = self._prefill(
                self.params, jnp.asarray(toks), self.state,
                jnp.asarray(slot, jnp.int32),
                self.sched.alloc.table()[slot],
                jnp.asarray(tl, jnp.int32))
        tok = self._sample_dispatch(logits)
        # one numpy fetch for the whole dispatch (np.asarray blocks until
        # the device is done), not a ready-barrier plus a scalar D2H
        with self._sync("first_token"):
            tok0 = int(np.asarray(tok)[0])  # sync: classic prefill first token
        dt = time.monotonic() - t0
        self.clock += dt
        if self._prefill_rate is not None:
            self._prefill_rate.observe(tl, dt)
        self.prefill_computed += tl
        return events + self._take_first_token(slot, tok0, tl)

    def _prefill_one_chunk(self) -> list[TokenEvent]:
        """One prefill chunk for the oldest mid-prefill admission (FCFS);
        the slot joins the decode batch the step after its final chunk."""
        slot = min(self._prefilling, key=self._admit_seq.__getitem__)
        cur = self._prefilling[slot]
        ctx_toks, off = cur["ctx"], cur["off"]
        tl = len(ctx_toks)
        c = self.prefill_chunk
        clen = min(c, tl - off)
        with _span("engine.prefill_chunk", rid=self.sched.active[slot].rid,
                   tokens=clen):
            with _span("engine.dispatch", program="prefill_chunk"):
                toks = np.zeros((1, c), np.int32)
                toks[0, :clen] = ctx_toks[off:off + clen]
                row = self.sched.alloc.table()[slot]
                if self._prefill_slicing:
                    # width-slice the row to the pages this chunk touches:
                    # the page-native kernel then reads O(live prefix), not
                    # O(capacity) (one compile per pow2 bucket, as in
                    # decode)
                    row = row[:self._step_width(
                        cdiv(off + clen, self.layout.page_size))]
                t0 = time.monotonic()
                logits, self.state = self._prefill_chunk(
                    self.params, jnp.asarray(toks), self.state,
                    jnp.asarray(slot, jnp.int32), row,
                    jnp.asarray(off, jnp.int32),
                    jnp.asarray(clen, jnp.int32))
            self._progressed = True
            self._budget_left -= clen
            self.prefill_computed += clen
            self.prefill_chunks += 1
            cur["off"] = off + clen
            self._lengths[slot] = off + clen
            if cur["off"] >= tl:
                # final chunk: its last-token logits seed decode
                tok = self._sample_dispatch(logits)
                # single numpy fetch per dispatch (see _admit)
                with self._sync("first_token"):
                    tok0 = int(np.asarray(tok)[0])  # sync: final-chunk first token
                dt = time.monotonic() - t0
                self.clock += dt
                if self._prefill_rate is not None:
                    self._prefill_rate.observe(clen, dt)
                del self._prefilling[slot]
                self.sched.register_prefix(slot)
                return self._take_first_token(slot, tok0, tl)
            with self._sync("chunk_barrier"):
                jax.block_until_ready(logits)  # sync: chunk completion barrier (honest clock)
            dt = time.monotonic() - t0
            self.clock += dt
            if self._prefill_rate is not None:
                self._prefill_rate.observe(clen, dt)
            return []

    def _sample_dispatch(self, logits):
        """Split the session key and sample one token per row of
        ``logits`` on the device (no fetch)."""
        with _span("engine.dispatch", program="sample"):
            self._key, sub = jax.random.split(self._key)
            return self._sample(logits, sub, self.gen)

    def _take_first_token(self, slot: int, tok0: int,
                          tl: int) -> list[TokenEvent]:
        """Record a request's first sampled token after its prefill."""
        req = self.sched.active[slot]
        req.state = DECODING
        first = req.t_first_token is None
        if req.t_admitted is None:
            req.t_admitted = req.t_first_token = self.clock
        req.out_tokens.append(tok0)
        self._next_tok[slot] = tok0
        self._lengths[slot] = tl
        if self.qos is not None:
            self.qos.on_tokens(req.tenant, 1)
        # a preemption-resume re-prefill is not the stream's first token
        events = [TokenEvent("first_token" if first else "token",
                             req.rid, self.clock, token=tok0, slot=slot,
                             ordinal=req.done_tokens - 1)]
        if (self.gen.eos_id >= 0 and tok0 == self.gen.eos_id) or \
                req.done_tokens >= self._eff_max[req.rid]:
            events += self._finish(slot)
        return events

    def _finish(self, slot: int) -> list[TokenEvent]:
        req = self.sched.active[slot]
        req.state = FINISHED
        req.t_done = self.clock
        self._eff_max.pop(req.rid, None)
        if self.spec is not None:
            self._proposer.release(req.rid)
        self.completed.append(self.sched.finish(slot))
        return [TokenEvent("finish", req.rid, self.clock, slot=slot)]

    def _decode_step(self) -> list[TokenEvent]:
        """Batched decode over non-stalled, fully-prefilled slots; falls
        back to recompute-preemption when nothing can run and no chunk
        progressed this cycle."""
        if not self.sched.active:
            return []   # cancellation emptied the cycle mid-flight
        with _span("engine.decode", active=len(self.sched.active)):
            return self._decode_slots()

    def _decode_slots(self) -> list[TokenEvent]:
        sched, g = self.sched, self.layout.page_size
        drafts: dict[int, list[int]] = {}
        spans = None
        want_runahead = self._runahead_want()
        if want_runahead:
            # reserve the whole horizon's pages up front; a shortfall
            # (pool pressure) drops this step back to the H=1 dispatch,
            # which knows how to shed and preempt
            spans = {sl: self.runahead for sl in sched.active}
        if self.spec is not None:
            # proposer work bills to the session clock: for ngram it is
            # microseconds of suffix matching, but a draft-model proposer
            # runs real forwards and must not get them for free in tok/s
            t0 = time.monotonic()
            drafts = self._propose_drafts()
            self.clock += time.monotonic() - t0
            spans = {sl: 1 + len(d) for sl, d in drafts.items()}
        stalled = set(sched.ensure_pages(self._lengths,
                                         skip=self._prefilling.keys(),
                                         spans=spans))
        if self.spec is not None:
            # shed drafts the pool couldn't back: the accepted span must
            # stay inside the slot's allocated pages (only the *verify*
            # copy may spill to scratch, never the committed state)
            for sl, d in drafts.items():
                cap = (sched.alloc.slot_pages(sl) * g
                       - int(self._lengths[sl]) - 1)
                if len(d) > max(cap, 0):
                    del d[max(cap, 0):]
        step_slots = [sl for sl in sched.active
                      if sl not in stalled and sl not in self._prefilling]

        # copy-on-write guard: never append into a shared page.
        # Chunk-aligned adoption makes this a no-op in steady state
        # (adopted pages all precede the write frontier), but it is the
        # invariant that keeps sharing safe under any adoption policy
        # (DESIGN.md §12). A speculative span may cross into further
        # pages, so every page the commit could touch is checked.
        if step_slots and (self.prefix_cache or self.cow_splits):
            safe = []
            for sl in step_slots:
                lo = int(self._lengths[sl]) // g
                hi = (int(self._lengths[sl])
                      + len(drafts.get(sl, ()))) // g
                ok = True
                for pidx in range(lo, hi + 1):
                    if not (pidx < sched.alloc.slot_pages(sl) and
                            sched.alloc.refcount(
                                sched.alloc.page_at(sl, pidx)) > 1):
                        continue
                    if not sched.alloc.can_alloc(1):
                        sched.reclaim(1)
                    if not sched.alloc.can_alloc(1):
                        stalled.add(sl)
                        ok = False
                        break
                    src, dst = sched.alloc.cow(sl, pidx)
                    with _span("engine.dispatch", program="copy_pages"):
                        self.state = self._copy_pages(
                            self.state, jnp.asarray(src, jnp.int32),
                            jnp.asarray(dst, jnp.int32))
                    self.cow_splits += 1
                if ok:
                    safe.append(sl)
            step_slots = safe

        if not step_slots:
            if self._progressed:
                return []   # chunk prefill advanced; next cycle retries
            # every slot needs a page and the pool is dry: recompute-
            # preempt the most recent admission so the rest make progress
            victim = max(sched.active, key=self._admit_seq.__getitem__)
            vreq = sched.active[victim]
            if vreq.preemptions >= 64:
                raise RuntimeError(
                    "request thrashing on preemption — pool too small to "
                    "finish any request")
            # mid-prefill slots can't be victims: chunk work always
            # progresses when any exist, and progress skips this branch
            assert victim not in self._prefilling
            retracted = None
            if vreq.out_tokens:
                retracted = vreq.out_tokens.pop()   # un-fed; re-sampled
            self._eff_max.pop(vreq.rid, None)
            if self.spec is not None:
                self._proposer.release(vreq.rid)
            sched.preempt(victim)
            vreq.state = PREEMPTED
            self._preempted_cycle = True
            # the preempt event carries the retracted token: streaming
            # consumers must drop their last token for this rid
            return [TokenEvent("preempt", vreq.rid, self.clock,
                               token=retracted, slot=victim)]

        if self.spec is not None and any(drafts.get(sl) for sl in
                                         step_slots):
            return self._spec_dispatch(step_slots, drafts)
        if want_runahead and self._runahead_ready(step_slots):
            return self._runahead_dispatch(step_slots)
        return self._decode_dispatch(step_slots)

    def _decode_dispatch(self, step_slots: list[int]) -> list[TokenEvent]:
        """The vanilla one-token decode dispatch (also the fast path of a
        spec session when no slot has drafts this step)."""
        sched, g = self.sched, self.layout.page_size
        s = self.layout.slots
        with _span("engine.dispatch", program="decode_step"):
            mask = np.zeros((s,), bool)
            mask[step_slots] = True
            # width-slice the page table to the live pages of this step's
            # batch: the decode step then reads O(live tokens) instead of
            # O(pool capacity) (one compile per pow2 bucket)
            w = self._step_width(
                max(int(self._lengths[sl]) // g + 1 for sl in step_slots))
            t0 = time.monotonic()
            logits, self.state = self._decode(
                self.params, self.state, jnp.asarray(self._next_tok),
                sched.alloc.table()[:, :w], jnp.asarray(mask))
        tok = self._sample_dispatch(logits)
        with self._sync("decode_tokens"):
            toks = np.asarray(tok)  # sync: H=1 decode token fetch
        step_s = time.monotonic() - t0
        self.clock += step_s
        self.decode_steps += 1
        self._step_times.append(step_s)
        self._util.append(sched.utilization())
        self._active_hist.append(len(step_slots))

        events = []
        for sl in step_slots:
            self._lengths[sl] += 1
            req = sched.active[sl]
            t = int(toks[sl])
            req.out_tokens.append(t)
            self._next_tok[sl] = t
            if self.qos is not None:
                self.qos.on_tokens(req.tenant, 1)
            events.append(TokenEvent("token", req.rid, self.clock,
                                     token=t, slot=sl,
                                     ordinal=req.done_tokens - 1))
            if (self.gen.eos_id >= 0 and t == self.gen.eos_id) or \
                    req.done_tokens >= self._eff_max[req.rid]:
                events += self._finish(sl)
        return events

    # --- run-ahead fused decode (DESIGN.md §18) ---------------------------

    def _runahead_want(self) -> bool:
        """Horizon-planner gate: run-ahead engages only when the next H
        step boundaries are provably event-free — nothing queued to
        admit, no prefill chunk due, and none of the subsystems that
        make per-step scheduling decisions (spec, QoS, chaos, prefix
        sharing, mesh placement) in play. Every other configuration
        takes the H=1 dispatch unchanged, which is why all prior
        bit-identity gates (spec, QoS, mesh, prefix A/Bs) are preserved
        by construction."""
        return (self.runahead > 1 and self.spec is None
                and self.qos is None and self.chaos is None
                and self.mesh is None and not self.prefix_cache
                and not self._prefilling and not self._arrivals
                and not self.sched.pending)

    def _runahead_ready(self, step_slots: list[int]) -> bool:
        """Per-slot hazard check after page reservation: every active
        slot decodes this step and holds pages covering the tokens its
        horizon can append (EOS and budget hazards are masked on
        device; a page shortfall means pool pressure, so fall back to
        the H=1 path, which can shed and preempt)."""
        sched, g = self.sched, self.layout.page_size
        if len(step_slots) != len(sched.active):
            return False
        for sl in step_slots:
            req = sched.active[sl]
            need = int(self._lengths[sl]) + min(
                self.runahead, self._eff_max[req.rid] - req.done_tokens)
            if sched.alloc.slot_pages(sl) * g < need:
                return False
        return True

    def _runahead_dispatch(self, step_slots: list[int]) -> list[TokenEvent]:
        """Dispatch one fresh run-ahead horizon from host-known carries
        (next tokens, session key, per-slot budgets). Returns no events:
        the host does not block on the block — tokens reconcile when it
        lands (the next step, or a cancel arriving mid-flight)."""
        s = self.layout.slots
        with _span("engine.dispatch", program="runahead_horizon"):
            mask = np.zeros((s,), bool)
            mask[step_slots] = True
            rem = np.zeros((s,), np.int32)
            for sl in step_slots:
                req = self.sched.active[sl]
                rem[sl] = self._eff_max[req.rid] - req.done_tokens
            self._dispatch_scan(
                step_slots, jnp.asarray(self._next_tok), self._key,
                jnp.zeros((s,), bool), jnp.asarray(rem), jnp.asarray(mask),
                {sl: int(rem[sl]) for sl in step_slots},
                {sl: int(self._lengths[sl]) for sl in step_slots})
        return []

    def _chain_dispatch(self, live: list[int]) -> None:
        """Dispatch the next horizon directly off the in-flight one's
        device-resident carries (final token, key, done mask, budgets)
        — no host sync in between, so the device stays busy while the
        host reconciles the previous block's events."""
        blk = self._inflight
        tok, key, done, rem = blk["carry"]
        with _span("engine.dispatch", program="runahead_horizon"):
            self._dispatch_scan(live, tok, key, done, rem, blk["mask"],
                                {sl: blk["ahead_rem"][sl] for sl in live},
                                {sl: blk["opt_len"][sl] for sl in live})

    def _dispatch_scan(self, slots, tok, key, done, rem, mask,
                       host_rem: dict, host_len: dict) -> None:
        g, h = self.layout.page_size, self.runahead
        cap = self.layout.tokens_per_slot
        # width covers every page the horizon can touch (appends and
        # attention reads up to len + H, clamped to slot capacity)
        w = self._step_width(max(
            (min(host_len[sl] + h, cap) - 1) // g + 1 for sl in slots))
        t0 = time.monotonic()
        toks, self.state, tok, key, done, rem = self._runahead_fn(
            self.params, self.state, tok,
            self.sched.alloc.table()[:, :w], mask, key, rem, done,
            horizon=h, temperature=self.gen.temperature,
            top_k=self.gen.top_k, eos_id=self.gen.eos_id)
        # adopt the post-scan key without a sync: the scan split it
        # exactly as H sequential host steps would have, so any later
        # H=1 dispatch continues the same key stream
        self._key = key
        self._inflight = {
            "toks": toks, "slots": list(slots),
            "rem": host_rem, "len": host_len,
            # optimistic carries for chaining: a slot that survives its
            # horizon advanced exactly min(h, rem) tokens; one that
            # finished is excluded from the next chain (budget) or
            # skipped at reconcile (EOS — its device lane froze)
            "ahead_rem": {sl: max(host_rem[sl] - h, 0) for sl in slots},
            "opt_len": {sl: host_len[sl] + min(h, host_rem[sl])
                        for sl in slots},
            "carry": (tok, key, done, rem), "mask": mask, "t0": t0,
        }

    def _advance_runahead(self) -> list[TokenEvent]:
        """The async pipeline's per-step beat: optionally chain the next
        horizon off the in-flight one's device carries, then land the
        in-flight block and reconcile its TokenEvents. Chaining happens
        *before* the landing sync, so host reconciliation overlaps the
        next horizon's device compute."""
        old = self._inflight
        chained = False
        if self._runahead_want():
            live = [sl for sl in old["slots"]
                    if old["ahead_rem"][sl] > 0
                    and sl in self.sched.active]
            if live:
                with _span("engine.decode", active=len(live)):
                    chained = self._chain(old, live)
        if not chained:
            self._inflight = None
        return self._reconcile_block(old)

    def _chain(self, old: dict, live: list[int]) -> bool:
        """Reserve the pages of another horizon for the ``live`` slots
        and, when every one has them, chain it; False leaves the
        pipeline to drain."""
        g = self.layout.page_size
        opt = self._lengths.copy()
        for sl in live:
            opt[sl] = old["opt_len"][sl]
        dead = [sl for sl in self.sched.active if sl not in live]
        stalled = set(self.sched.ensure_pages(
            opt, skip=dead, spans={sl: self.runahead for sl in live}))
        ok = not stalled and all(
            self.sched.alloc.slot_pages(sl) * g >=
            old["opt_len"][sl] + min(self.runahead, old["ahead_rem"][sl])
            for sl in live)
        if ok:
            self._chain_dispatch(live)
        return ok

    def _reconcile_horizon(self) -> list[TokenEvent]:
        """Forcibly land the in-flight horizon (cancel or any other
        host-side mutation arriving mid-flight): sync, emit its events,
        drain the pipeline."""
        blk, self._inflight = self._inflight, None
        return self._reconcile_block(blk)

    def _reconcile_block(self, blk: dict) -> list[TokenEvent]:
        """Land one horizon's (H, S) token block — the single host sync
        for its H micro-steps — and replay the host-side bookkeeping the
        H=1 loop does per step: per-token ordinals, horizon-shared clock
        stamps with (span, span_ix) metadata, post-hoc truncation at EOS
        or budget, finish + page reclamation on the first host step
        after the block lands."""
        with _span("engine.runahead.land", horizon=self.runahead_horizons,
                   slots=len(blk["slots"])):
            return self._land(blk)

    def _land(self, blk: dict) -> list[TokenEvent]:
        sched, h = self.sched, self.runahead
        with self._sync("horizon"):
            toks = np.asarray(blk["toks"])  # sync: horizon block lands — one fetch per H tokens
        now = time.monotonic()
        # wall time is partitioned across pipelined horizons: each bills
        # from the later of its dispatch and the previous landing
        step_s = now - max(blk["t0"], self._land_t)
        self._land_t = now
        self.clock += step_s
        events: list[TokenEvent] = []
        live = 0
        for sl in blk["slots"]:
            req = sched.active.get(sl)
            if req is None or req.state != DECODING:
                continue   # finished/cancelled before this block landed
            live += 1
            rem = blk["rem"][sl]
            emit: list[int] = []
            finished = False
            for j in range(min(h, rem)):
                t = int(toks[j, sl])
                emit.append(t)
                if self.gen.eos_id >= 0 and t == self.gen.eos_id:
                    finished = True
                    break
            if len(emit) >= rem:
                finished = True   # budget bound inside the horizon
            span = len(emit)
            # device lengths advanced once per live micro-step (the fed
            # token's append) — EOS froze the lane right after its
            # sample — so host and device lengths agree for every
            # surviving slot; a finishing slot's pages are reclaimed
            # here, the first host step after the block lands
            self._lengths[sl] += span
            for j, t in enumerate(emit):
                req.out_tokens.append(t)
                events.append(TokenEvent(
                    "token", req.rid, self.clock, token=t, slot=sl,
                    ordinal=req.done_tokens - 1, span=span, span_ix=j))
            self._next_tok[sl] = emit[-1]
            self.runahead_tokens += span
            if finished:
                events += self._finish(sl)
        self.runahead_horizons += 1
        self.decode_steps += h
        self._step_times.append(step_s / h)
        self._util.append(sched.utilization())
        self._active_hist.append(live)
        return events

    # --- speculative decode (DESIGN.md §15) -------------------------------

    def _propose_drafts(self) -> dict[int, list[int]]:
        """Up to ``spec.k`` draft tokens per decode-ready slot, clamped so
        (a) a fully-accepted span can never overshoot the request's
        effective budget (the bonus token is always emitted on top of the
        drafts), and (b) the span never extends past the slot's current
        quantization group (``span <= g - length % g``) — the invariant
        the batched span verifier and the fused span commit rely on: at
        most the LAST span position can trigger a group flush, so one
        residual buffer represents every per-position view bit-exactly
        (``paged_cache.span_verify_attention``). At worst — a slot one
        token shy of a boundary — the step degrades to plain decode."""
        g = self.layout.page_size
        # graceful degradation halves k per level (0 at level 3): under
        # sustained pool pressure speculative spans are the first cost
        # to drop before live requests get preempted
        k = (self.degrade.spec_k(self.spec.k) if self.degrade is not None
             else self.spec.k)
        drafts: dict[int, list[int]] = {}
        for sl, req in self.sched.active.items():
            if sl in self._prefilling:
                continue
            want = min(k,
                       self._eff_max[req.rid] - req.done_tokens - 1,
                       g - int(self._lengths[sl]) % g - 1)
            d: list = []
            if want > 0:
                # a proposer exception (real bug or injected fault) must
                # never take the engine down — the step degrades to plain
                # decode for this slot and the fault is counted
                try:
                    if self.chaos is not None:
                        self.chaos.maybe_fail_proposer()
                    d = self._proposer.propose(req, want)
                except Exception:
                    self.proposer_faults += 1
                    d = []
            drafts[sl] = [int(t) for t in d[:max(want, 0)]]
        return drafts

    def _spec_dispatch(self, step_slots: list[int],
                       drafts: dict[int, list[int]]) -> list[TokenEvent]:
        """One verify dispatch retiring 1..k+1 tokens per stepped slot.

        Column 0 of the span is the step's real next token (vanilla would
        have fed exactly it), columns 1..k the zero-padded drafts. The
        verifier returns the target argmax per position and the accepted
        count; emitted tokens are the argmaxes of column 0 plus the
        accepted drafts — precisely what vanilla greedy decode would have
        emitted over the next ``n_acc + 1`` steps — and the committed
        cache equals the vanilla one bitwise (spec/verify.py)."""
        sched, g = self.sched, self.layout.page_size
        s = self.layout.slots
        with _span("engine.dispatch", program="verify"):
            # bucket the span width to this step's longest draft
            # (pow2-ish, one compile per bucket): a step where every
            # proposer came back short doesn't pay for k+1 verify positions
            q = self._spec_q(1 + max(len(drafts.get(sl, ()))
                                     for sl in step_slots))
            mask = np.zeros((s,), bool)
            mask[step_slots] = True
            toks = np.zeros((s, q), np.int32)
            toks[:, 0] = self._next_tok
            dlen = np.zeros((s,), np.int32)
            for sl in step_slots:
                d = drafts.get(sl, [])
                toks[sl, 1:1 + len(d)] = d
                dlen[sl] = len(d)
            # width must cover every span position; span pages the
            # scheduler couldn't (or didn't need to) allocate resolve to
            # the scratch page, touched only by the discarded verify copy
            w = self._step_width(
                max((int(self._lengths[sl]) + q - 1) // g + 1
                    for sl in step_slots))
            t0 = time.monotonic()
            preds, n_acc, self.state = self._verify(
                self.params, self.state, jnp.asarray(toks),
                jnp.asarray(dlen), sched.alloc.table()[:, :w],
                jnp.asarray(mask))
        with self._sync("verify"):
            preds = np.asarray(preds)   # sync: verify-span argmax block
            n_acc = np.asarray(n_acc)   # sync: verify-span accept counts
        step_s = time.monotonic() - t0
        self.clock += step_s
        self.decode_steps += 1
        self.spec_steps += 1
        self._step_times.append(step_s)
        self._util.append(sched.utilization())
        self._active_hist.append(len(step_slots))

        events = []
        for sl in step_slots:
            req = sched.active[sl]
            n = int(n_acc[sl])
            self.spec_drafted += int(dlen[sl])
            self.spec_accepted += n
            self._proposer.feedback(req.rid, int(dlen[sl]), n)
            # emit the argmax chain, truncating at EOS (the budget clamp
            # in _propose_drafts means eff_max can only bind at the last
            # span position, exactly like vanilla)
            emit: list[int] = []
            finished = False
            for j in range(n + 1):
                t = int(preds[sl, j])
                emit.append(t)
                if (self.gen.eos_id >= 0 and t == self.gen.eos_id) or \
                        req.done_tokens + len(emit) >= \
                        self._eff_max[req.rid]:
                    finished = True
                    break
            span = len(emit)
            if self.qos is not None:
                self.qos.on_tokens(req.tenant, span)
            for j, t in enumerate(emit):
                req.out_tokens.append(t)
                events.append(TokenEvent(
                    "token", req.rid, self.clock, token=t, slot=sl,
                    ordinal=req.done_tokens - 1, span=span, span_ix=j))
            # device lengths advanced by n+1 (the full accepted span);
            # when EOS truncates the emission the slot finishes and its
            # pages are reclaimed, so the host length is moot
            self._lengths[sl] += n + 1
            self._next_tok[sl] = emit[-1]
            if finished:
                events += self._finish(sl)
        return events

    # --- session results --------------------------------------------------

    def events(self) -> Iterator[TokenEvent]:
        """Drive :meth:`step` until the engine has no work, yielding each
        event as it happens (the batch-replay convenience; open-loop
        drivers call :meth:`step` themselves)."""
        while self.has_work:
            yield from self.step()

    def result(self) -> dict:
        """Aggregate session metrics plus the completed request objects
        (tokens + timestamps filled in) — the same dict the monolithic
        ``run()`` returned."""
        completed = self.completed
        total_tokens = sum(r.done_tokens for r in completed)
        lats = sorted(r.latency() for r in completed)

        def pct(p):
            return nearest_rank_pct(lats, p)

        step_times = self._step_times
        res = {
            "requests": completed,
            "total_tokens": total_tokens,
            "wall_s": self.clock,
            "tokens_per_s": total_tokens / max(self.clock, 1e-9),
            "p50_latency_s": pct(50),
            "p99_latency_s": pct(99),
            "decode_steps": self.decode_steps,
            "prefill_chunks": self.prefill_chunks,
            # host time blocked on the device, every serving-path sync
            "host_sync_s": self.host_sync_s,
            "decode_step_s_mean": float(np.mean(step_times)) if step_times
            else 0.0,
            "decode_step_s_p50": float(np.median(step_times)) if step_times
            else 0.0,
            "decode_backend": self.model.cfg.decode_backend,
            "prefill_backend": self.model.cfg.prefill_backend,
            "mean_active_slots": float(np.mean(self._active_hist))
            if self._active_hist else 0.0,
            "mean_page_utilization": float(np.mean(self._util))
            if self._util else 0.0,
            "cache_bytes": _tree_bytes(self.state),
            "cache_bytes_per_layer": (
                self.model.cache_layer_bytes(self.state)
                if self.model.cache_layer_bytes else None),
            "prefill_chunk": self.prefill_chunk,
            "prefix_cache": self.prefix_cache,
            "prefill_tokens_computed": self.prefill_computed,
            "prefill_tokens_skipped": self.prefill_skipped,
            "prefix_hit_rate": self.prefill_skipped / max(
                self.prefill_skipped + self.prefill_computed, 1),
            "adopted_pages": self.sched.adopted_pages,
            "fresh_pages": self.sched.fresh_pages,
            "cow_splits": self.cow_splits,
            "cancelled_requests": self.cancelled,
            "n_cancelled": len(self.cancelled),
            "shed_requests": self.shed,
            "n_shed": len(self.shed),
            "rejected_requests": self.rejected,
            "n_rejected": len(self.rejected),
            "proposer_faults": self.proposer_faults,
        }
        if self.qos is not None:
            res["qos"] = {
                **self.qos.stats(),
                "prefill_rate_est": self._prefill_rate.rate,
                "degrade": (self.degrade.stats()
                            if self.degrade is not None else None),
            }
        if self.chaos is not None:
            res["chaos"] = self.chaos.stats()
        if self.runahead > 1:
            res["runahead"] = {
                "h": self.runahead,
                "horizons": self.runahead_horizons,
                "tokens": self.runahead_tokens,
            }
        if self.spec is not None:
            res["spec"] = {
                "mode": self.spec.mode,
                "k": self.spec.k,
                "steps": self.spec_steps,
                "drafted_tokens": self.spec_drafted,
                "accepted_tokens": self.spec_accepted,
                "acceptance_rate": self.spec_accepted / max(
                    self.spec_drafted, 1),
                "mean_accepted_per_step": self.spec_accepted / max(
                    self.spec_steps, 1),
            }
        if self.prefix is not None:
            from repro.core import paged_cache as pgc
            page_bytes = sum(pgc.pool_page_bytes(c) for c in self.state)
            res["pool_page_bytes"] = page_bytes
            res["prefix_pool_bytes_saved"] = \
                self.sched.adopted_pages * page_bytes
            res["prefix_index"] = {
                "entries": len(self.prefix), "queries": self.prefix.queries,
                "evictions": self.prefix.evictions,
            }
        return res
