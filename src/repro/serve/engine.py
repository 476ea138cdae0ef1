"""Batched serving engine over the models' prefill/decode interface.

The paper is an inference-accelerator paper, so serving is the primary
end-to-end driver (examples/serve_cim.py): weights can be served from
packed-ternary HBM storage (the paper's density claim) by converting
params with core.cim_linear.ternarize_params — every dense() inside
prefill/decode then routes through the ternary_matmul kernel.

Engine model: requests are queued, bucketed by prompt length (identical
lengths batch exactly — no padding approximations in scoring), prefilled
as a batch, then decoded with per-row EOS/max-token termination.  The
decode batch keeps running while any row is live; finished rows keep
decoding into a scratch token that is discarded (standard fixed-batch
serving).

Two decode drivers:
  on-device (default) — ``make_decode_loop``: a single jitted
      ``lax.while_loop`` carries (token, cache, live-mask, token buffer)
      on device, checks EOS + per-row max-new in-graph, and transfers
      tokens to the host exactly ONCE per bucket.  The legacy driver
      blocked on a ``jax.device_get`` after every decode step,
      serializing host and device.
  legacy step loop (``on_device_loop=False``) — one jitted step per
      token with a host-side sync; kept for tests that pin per-step
      behavior and for debugging.

Both drivers produce identical greedy tokens; ``host_transfers`` counts
device->host syncs so the one-transfer-per-bucket contract is testable.

``make_decode_step`` is the jitted `serve_step` the multi-pod dry-run
lowers for the decode_32k / long_500k cells.

Continuous batching (``Scheduler``): the bucket engine drains one static
batch at a time, so decode slots sit empty while long requests finish
and new arrivals queue behind the whole bucket.  The Scheduler instead
keeps a persistent pool of ``slots`` decode lanes whose on-device state
(KV/carry, live-mask, per-slot max-new/EOS budgets) survives across
scheduling rounds:

  * each slot carries an independent batch-1 decode state stacked on a
    leading slot axis; ``make_chunked_decode_loop`` advances every slot
    with a vmapped single-row decode, so slots at DIFFERENT sequence
    positions coexist in one jitted ``lax.while_loop`` (the batched
    drivers share one scalar cache position and cannot do this);
  * the loop runs up to ``chunk`` decode steps, then yields to the host
    for admission with ONE device->host transfer (the PR 2 invariant,
    now per chunk instead of per bucket);
  * admission prefills newly arrived requests and scatters their state
    into freed slots in-graph (``make_admit_fn``) — compaction is the
    overwrite, no pool reshape, no extra transfer (the prefill token
    stays on device and is emitted by the next chunk's prologue);
  * finished rows are retired host-side from the per-chunk transfer and
    their slots returned to the free list.

Per-request tokens are bitwise identical to both PR 2 drivers (pinned in
tests/test_continuous.py): a slot's computation is exactly the batch-1
decode of that request, and greedy tokens are batch-shape independent.
"""
from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

_LOG = logging.getLogger("repro.serve.engine")


def _span(name: str, **args):
    """A host span (``serve.*``) on the profiler trace's clock, beside the
    device's ops; about a microsecond when no profiler runs.  The spans
    and their nesting are listed in serve/README.md, "Spans"."""
    return jax.profiler.TraceAnnotation(name, **args)


def greedy_sample(logits: jax.Array) -> jax.Array:
    return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)


def make_prefill_step(model, capacity: int, cim=None) -> Callable:
    def prefill_step(params, batch):
        logits, state = model.prefill(params, batch, capacity, cim=cim)
        return greedy_sample(logits), state
    return jax.jit(prefill_step)


def make_decode_step(model, cim=None) -> Callable:
    def decode_step(params, token, state):
        logits, state = model.decode(params, token[:, None], state, cim=cim)
        return greedy_sample(logits), state
    return jax.jit(decode_step, donate_argnums=(2,))


def make_decode_loop(model, max_new: int, cim=None) -> Callable:
    """Jitted whole-bucket decode: ``lax.while_loop`` over decode steps
    with the live-mask, per-row budgets and the token buffer all carried
    on device.

    fn(params, tok0, state, max_new_row, eos_row) ->
        (buf (B, max_new) int32, counts (B,) int32, steps () int32)

    tok0 is the prefill-sampled token (recorded at buf[:, 0], exactly
    like the legacy driver records it before its first decode step);
    counts[b] is how many of row b's buffer slots are real output
    (min(EOS position + 1, max_new_row[b])); steps is the number of
    decode steps executed (for steps_run accounting).  Rows append in
    lockstep while live, so a row's tokens always occupy buf[b, :counts].
    """
    def decode_loop(params, tok, state, max_new_row, eos_row):
        b = tok.shape[0]
        buf = jnp.zeros((b, max_new), jnp.int32).at[:, 0].set(tok)
        counts = jnp.ones((b,), jnp.int32)
        live = (counts < max_new_row) & (tok != eos_row)

        def cond(carry):
            step, tok, state, live, buf, counts = carry
            return jnp.any(live) & (step < max_new - 1)

        def body(carry):
            step, tok, state, live, buf, counts = carry
            logits, state = model.decode(params, tok[:, None], state,
                                         cim=cim)
            tok = greedy_sample(logits)
            buf = buf.at[:, step + 1].set(
                jnp.where(live, tok, buf[:, step + 1]))
            counts = counts + live.astype(jnp.int32)
            live = live & (counts < max_new_row) & (tok != eos_row)
            return step + 1, tok, state, live, buf, counts

        steps, _, _, _, buf, counts = jax.lax.while_loop(
            cond, body, (jnp.zeros((), jnp.int32), tok, state, live, buf,
                         counts))
        return buf, counts, steps

    # no donate_argnums: the while_loop carries the cache internally and
    # XLA cannot alias the donated input into the loop state (it would
    # only warn on every bucket).
    return jax.jit(decode_loop)


# =====================================================================
# continuous batching: slot pool + chunked decode loop
# =====================================================================

def init_slot_pool(model, slots: int, capacity: int):
    """Pooled decode state: one batch-1 cache per slot, stacked on a new
    leading slot axis (logical axis 'slot' in repro.dist — folds over
    the data-parallel mesh axes like 'batch')."""
    one = model.init_cache(1, capacity)
    return jax.tree.map(lambda a: jnp.stack([a] * slots), one)


def make_chunked_decode_loop(model, chunk: int, cim=None, spmd_axes=None):
    """Chunked variant of ``make_decode_loop`` over a slot pool: run up
    to ``chunk`` decode steps in one jitted ``lax.while_loop``, then
    yield to the host for admission.

    fn(params, tok (P,), state_pool, live (P,), made (P,), fresh (P,),
       max_new_row (P,), eos_row (P,)) ->
        (tok, state_pool, live, made,
         buf (P, chunk+1) int32, cnt (P,) int32, steps (), occ ())

    Every slot advances with a vmapped batch-1 ``model.decode`` so slots
    at different positions coexist (each slot state carries its own
    scalar cache position).  `spmd_axes` threads the physical mesh axes
    of the slot dim into ``jax.vmap(spmd_axis_name=...)`` so activation
    constraints inside the model shard the pool over data parallelism
    (see dist.sharding.slot_spmd_axes).

    Semantics per slot are exactly ``make_decode_loop``'s per row:
    freshly admitted slots emit their prefill-sampled token at buf[:, 0]
    (already counted in ``made`` by the admit scatter), live rows append
    in per-row order at buf[row, cnt[row]], ``made`` tracks the per-slot
    budget and EOS flips ``live`` in-graph.  ``steps`` is the number of
    decode steps executed, ``occ`` the live-slot-steps (occupancy
    accounting); dead/empty slots keep decoding into scratch state, like
    finished rows in the fixed-batch drivers.
    """
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")

    def decode_one(params, tok, st):
        logits, st = model.decode(params, tok[None, None], st, cim=cim)
        return greedy_sample(logits)[0], st

    vdec = jax.vmap(decode_one, in_axes=(None, 0, 0),
                    spmd_axis_name=spmd_axes)

    def chunk_step(params, tok, state, live, made, fresh, max_new_row,
                   eos_row):
        p = tok.shape[0]
        rows = jnp.arange(p)
        # prologue: emit the admission tokens of freshly prefilled slots
        buf = jnp.zeros((p, chunk + 1), jnp.int32)
        buf = buf.at[:, 0].set(jnp.where(fresh, tok, 0))
        cnt = fresh.astype(jnp.int32)

        def cond(carry):
            step, live = carry[0], carry[3]
            return jnp.any(live) & (step < chunk)

        def body(carry):
            step, tok, state, live, buf, cnt, made, occ = carry
            occ = occ + jnp.sum(live.astype(jnp.int32))
            tok, state = vdec(params, tok, state)
            buf = buf.at[rows, cnt].set(
                jnp.where(live, tok, buf[rows, cnt]))
            cnt = cnt + live.astype(jnp.int32)
            made = made + live.astype(jnp.int32)
            live = live & (made < max_new_row) & (tok != eos_row)
            return step + 1, tok, state, live, buf, cnt, made, occ

        zero = jnp.zeros((), jnp.int32)
        steps, tok, state, live, buf, cnt, made, occ = jax.lax.while_loop(
            cond, body, (zero, tok, state, live, buf, cnt, made, zero))
        return tok, state, live, made, buf, cnt, steps, occ

    # no donation: the while_loop carries the pool state internally, so
    # XLA cannot alias a donated input into it (same as make_decode_loop)
    return jax.jit(chunk_step)


def make_admit_fn() -> Callable:
    """Jitted admission scatter: overwrite slot `slot` of the pool with a
    freshly prefilled batch-1 state and arm its control lanes.  This IS
    the compaction step — a freed slot is reclaimed by overwriting every
    state leaf in place; nothing is transferred to the host (tok0 stays
    on device and the next chunk's prologue emits it)."""
    def admit(state, tok, live, made, fresh, max_new_row, eos_row,
              slot, new_state, tok0, max_new, eos_id):
        state = jax.tree.map(
            lambda pool, new: pool.at[slot].set(new.astype(pool.dtype)),
            state, new_state)
        t0 = tok0[0]
        tok = tok.at[slot].set(t0)
        # same initial-liveness rule as the bucket loop: tok0 is token 1
        made = made.at[slot].set(1)
        live = live.at[slot].set((1 < max_new) & (t0 != eos_id))
        fresh = fresh.at[slot].set(True)
        max_new_row = max_new_row.at[slot].set(max_new)
        eos_row = eos_row.at[slot].set(eos_id)
        return state, tok, live, made, fresh, max_new_row, eos_row
    # donate the pool: admission is a pure scatter, aliased in place
    return jax.jit(admit, donate_argnums=(0, 1, 2, 3, 4, 5, 6))


# =====================================================================
# paged KV: chunked decode over the page pool
# =====================================================================

def make_paged_decode_loop(model, chunk: int, cim=None, spmd_axes=None,
                           attn_plan=None):
    """``make_chunked_decode_loop`` over the paged KV block pool
    (models/paged_kv.py): same chunk semantics, live-mask, budgets and
    ONE device->host transfer per chunk, but the per-slot cache is a
    page-table gather over a SHARED page pool instead of a private
    dense ``(1, capacity)`` buffer.

    fn(params, tok (P,), pool: PagedKVCache, page_table (P, W) int32,
       pos (P,), live, made, fresh, max_new_row, eos_row) ->
        (tok, pool, pos, live, made,
         buf (P, chunk+1) int32, cnt (P,) int32, steps (), occ ())

    Per decode step every slot runs the READ-only ``model.decode_paged``
    (vmapped; the pool itself is broadcast, only the page-table row and
    position map per slot), then ONE scatter appends all live slots'
    new K/V tokens into their current pages
    (``paged_kv.append_tokens``) — dead slots are routed to the null
    page so a freed-and-reused page is never clobbered by a scratch
    decode.  ``page_table`` is chunk-invariant (admission reserves every
    page a request can touch up front), so it rides as an operand, not
    loop state.  Tokens are bitwise identical to the dense pool: the
    gathered view feeds the same read graph, and masked page garbage
    contributes exactly zero (see models/paged_kv.py).

    With ``attn_plan`` (a resolved ``op='attention'`` ExecutionPlan,
    PagedScheduler resolves one per pool geometry) the read path is
    ``model.decode_paged_fused`` instead: one batched call whose
    planned executor consumes the page table in-kernel — the gathered
    dense KV copy the vmapped ``slot_view`` path materializes per slot
    per step never exists.  Token outputs stay bitwise identical at the
    argmax (tests/test_paged.py pins fused == gather == dense).  Dead
    slots read with length 0 there, so the kernel visits none of their
    pages (kernels/paged_attention.py).

    A mixture-of-experts model on the fused read routes only its live
    slots, and the loop appends ``moe (2,) int32`` to its outputs: the
    chunk's routed (row, expert) pairs and experts-with-a-row, summed
    over layers and steps (``PagedScheduler.moe_rows_routed`` /
    ``moe_experts_read``), carried on the chunk's one transfer.
    """
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    from repro.models import paged_kv
    moe = attn_plan is not None and bool(model.cfg.num_experts)

    if attn_plan is not None:
        def vread(params, pool, tok, page_table, pos, live):
            logits, kts, vts, st = model.decode_paged_fused(
                params, tok, pool, page_table, pos, cim=cim,
                attn_plan=attn_plan, live=live)
            # logits (S, 1, V) -> (S,); kts (L, S, KV, hd) ->
            # (S, L, KV, hd), the append_tokens scatter layout
            return (greedy_sample(logits), jnp.moveaxis(kts, 0, 1),
                    jnp.moveaxis(vts, 0, 1), st)
    else:
        def read_one(params, pool, tok, pt_row, pos):
            logits, kt, vt = model.decode_paged(params, tok[None, None],
                                                pool, pt_row, pos,
                                                cim=cim)
            return greedy_sample(logits)[0], kt[:, 0, 0], vt[:, 0, 0]

        vmapped = jax.vmap(read_one, in_axes=(None, None, 0, 0, 0),
                           spmd_axis_name=spmd_axes)

        def vread(params, pool, tok, page_table, pos, live):
            return vmapped(params, pool, tok, page_table, pos) + (
                jnp.zeros((2,), jnp.int32),)

    def chunk_step(params, tok, pool, page_table, pos, live, made, fresh,
                   max_new_row, eos_row):
        p = tok.shape[0]
        rows = jnp.arange(p)
        buf = jnp.zeros((p, chunk + 1), jnp.int32)
        buf = buf.at[:, 0].set(jnp.where(fresh, tok, 0))
        cnt = fresh.astype(jnp.int32)

        def cond(carry):
            step, live = carry[0], carry[4]
            return jnp.any(live) & (step < chunk)

        def body(carry):
            step, tok, pool, pos, live, buf, cnt, made, occ, st = carry
            occ = occ + jnp.sum(live.astype(jnp.int32))
            # the fused read skips every cell of a dead slot, given as
            # no context; live slots read (and RoPE) their own position
            read_pos = pos if attn_plan is None else jnp.where(live, pos, 0)
            tok_new, kts, vts, st_step = vread(params, pool, tok,
                                               page_table, read_pos, live)
            st = st + st_step
            pool = paged_kv.append_tokens(pool, kts, vts, page_table,
                                          pos, live)
            tok = tok_new
            pos = pos + 1
            buf = buf.at[rows, cnt].set(
                jnp.where(live, tok, buf[rows, cnt]))
            cnt = cnt + live.astype(jnp.int32)
            made = made + live.astype(jnp.int32)
            live = live & (made < max_new_row) & (tok != eos_row)
            return step + 1, tok, pool, pos, live, buf, cnt, made, occ, st

        zero = jnp.zeros((), jnp.int32)
        (steps, tok, pool, pos, live, buf, cnt, made, occ,
         st) = jax.lax.while_loop(
            cond, body, (zero, tok, pool, pos, live, buf, cnt, made,
                         zero, jnp.zeros((2,), jnp.int32)))
        out = (tok, pool, pos, live, made, buf, cnt, steps, occ)
        return out + (st,) if moe else out

    # no donation: the while_loop carries the pool internally (same as
    # the dense chunked loop)
    return jax.jit(chunk_step)


def make_paged_admit_fn() -> Callable:
    """Lane-only admission scatter for the paged scheduler: the KV state
    lands in the page pool via ``paged_kv.write_prompt_pages``; here we
    arm the control lanes and the slot's write position (= prompt
    length).  Same initial-liveness rule as the dense pools."""
    def admit(tok, live, made, fresh, max_new_row, eos_row, pos,
              slot, tok0, max_new, eos_id, prompt_len):
        t0 = tok0[0]
        tok = tok.at[slot].set(t0)
        made = made.at[slot].set(1)
        live = live.at[slot].set((1 < max_new) & (t0 != eos_id))
        fresh = fresh.at[slot].set(True)
        max_new_row = max_new_row.at[slot].set(max_new)
        eos_row = eos_row.at[slot].set(eos_id)
        pos = pos.at[slot].set(prompt_len)
        return tok, live, made, fresh, max_new_row, eos_row, pos
    return jax.jit(admit, donate_argnums=(0, 1, 2, 3, 4, 5, 6))


@dataclasses.dataclass
class Request:
    uid: int
    prompt: Any                      # (S,) int32
    max_new: int = 16
    eos_id: int = -1                 # -1: never
    arrival_s: float = 0.0           # offset from serve start (traces)
    priority: int = 0                # SLO class: lower is more urgent
    deadline_s: Optional[float] = None   # RELATIVE completion budget
    out_tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    latency_s: float = 0.0           # trace runs: completion - arrival
    admit_s: float = 0.0             # trace runs: admission - serve start

    @property
    def deadline_met(self) -> bool:
        """True when the request carries no deadline or completed
        within its relative budget (latency_s <= deadline_s)."""
        return self.deadline_s is None or self.latency_s <= self.deadline_s


def _batch_inputs(reqs: list, extra_inputs: dict) -> dict:
    toks = jnp.stack([jnp.asarray(r.prompt, jnp.int32) for r in reqs])
    batch = {"tokens": toks}
    for k, fn in extra_inputs.items():
        batch[k] = fn(len(reqs))
    return batch


def percentile(vals: list, q: float) -> float:
    """Linear interpolation between order statistics (numpy's default
    method).  The previous nearest-rank rounding (``int(q*(n-1)+0.5)``)
    made small-sample p99 degenerate to the sample max — for n <= 50
    every q > ~0.5 + 1/(2(n-1)) picked the last element — which biased
    the continuous-vs-bucket p99 bench gate toward whichever driver's
    single worst request was smaller."""
    return float(np.percentile(vals, 100.0 * q))


def latency_stats(reqs: list) -> dict:
    """p50/p99/p999/mean request latency (trace runs: completion -
    arrival; percentiles interpolate between order statistics,
    ``percentile``) plus the queue-wait vs service-time breakdown:
    ``queue_wait_*`` is arrival -> admission (``admit_s - arrival_s``,
    clamped into [0, latency] — engines that admit instantly report 0)
    and ``service_*`` is admission -> completion (the remainder), so
    an overloaded trace shows WHERE latency went — waiting for a slot
    or decoding."""
    zero = {"p50_s": 0.0, "p99_s": 0.0, "p999_s": 0.0, "mean_s": 0.0,
            "queue_wait_mean_s": 0.0, "queue_wait_p99_s": 0.0,
            "service_mean_s": 0.0, "service_p99_s": 0.0}
    if not reqs:
        return zero
    lat = sorted(r.latency_s for r in reqs)
    waits = sorted(min(max(r.admit_s - r.arrival_s, 0.0), r.latency_s)
                   for r in reqs)
    service = sorted(max(r.latency_s
                         - min(max(r.admit_s - r.arrival_s, 0.0),
                               r.latency_s), 0.0) for r in reqs)
    return {"p50_s": round(percentile(lat, 0.50), 4),
            "p99_s": round(percentile(lat, 0.99), 4),
            "p999_s": round(percentile(lat, 0.999), 4),
            "mean_s": round(sum(lat) / len(lat), 4),
            "queue_wait_mean_s": round(sum(waits) / len(waits), 4),
            "queue_wait_p99_s": round(percentile(waits, 0.99), 4),
            "service_mean_s": round(sum(service) / len(service), 4),
            "service_p99_s": round(percentile(service, 0.99), 4)}


class _EngineBase:
    """Request bookkeeping shared by the bucket and continuous engines:
    the queue, the completion list, and the host-transfer counter that
    both transfer contracts (one per bucket / one per chunk) are tested
    through."""

    def __init__(self, model, params, capacity: int, cim, extra_inputs):
        self.model = model
        self.params = params
        self.capacity = capacity
        # resolve the plan request ONCE at engine construction: 'auto'
        # backend/interpret pin against the kernel registry here, so an
        # incapable backend fails loudly now instead of mid-decode, and
        # every dense() under this engine hits the plan cache with a
        # fully concrete request.  Resolution is PER PHASE (noise-aware
        # routing): a `fidelity='device'` request runs the fault-
        # injected path only for decode — prefill routes back to an
        # exact backend (a prefill upset corrupts the whole KV prefix;
        # a decode upset perturbs one sampled token).  For exact
        # requests both resolutions are identical, so the exact serving
        # path is bitwise-unchanged.
        if cim is not None:
            self.cim = cim.resolve()
            self.cim_prefill = cim.resolve(phase="prefill")
        else:
            self.cim = self.cim_prefill = None
        self.extra_inputs = extra_inputs or {}
        self._prefill = make_prefill_step(model, capacity, self.cim_prefill)
        self.queue: list[Request] = []
        self.completed: list[Request] = []
        self.steps_run = 0
        self.host_transfers = 0

    def submit(self, req: Request):
        self.queue.append(req)

    def _device_get(self, x):
        """All device->host syncs route through here (transfer
        counting)."""
        self.host_transfers += 1
        return jax.device_get(x)

    @property
    def generated_tokens(self) -> int:
        return sum(len(r.out_tokens) for r in self.completed)

    def _arrival_pump(self, clock, sleep, try_admit, busy, serve_round):
        """Shared arrival loop for trace serving — the ONE place whose
        clock semantics both drivers inherit (the serve_continuous
        bench compares their latencies, so they must not drift):
        FIFO-sort the queue by (arrival_s, uid), offer arrived requests
        to `try_admit(req, now)` (return False to defer — e.g. no free
        slot; on success the admitter stamps `admit_s` so latency_stats
        can split queue wait from service time), sleep to the next
        arrival when nothing is `busy`, otherwise run one
        `serve_round(elapsed)`.  `serve_round` stamps `latency_s` as
        elapsed() - arrival_s (queue wait included)."""
        pending = sorted(self.queue, key=lambda r: (r.arrival_s, r.uid))
        self.queue = []
        t0 = clock()
        elapsed = lambda: clock() - t0
        while pending or busy():
            now = elapsed()
            while pending and pending[0].arrival_s <= now:
                if not try_admit(pending[0], now):
                    break
                pending.pop(0)
            if not busy():
                delay = pending[0].arrival_s - elapsed()
                if delay > 0:
                    sleep(delay)
                continue
            serve_round(elapsed)
        return self.completed


class ServeEngine(_EngineBase):
    def __init__(self, model, params, capacity: int = 512,
                 max_batch: int = 8, cim=None, extra_inputs=None,
                 on_device_loop: bool = True):
        super().__init__(model, params, capacity, cim, extra_inputs)
        self.max_batch = max_batch
        self.on_device_loop = on_device_loop
        self._decode = make_decode_step(model, self.cim)
        self._loops: dict[int, Callable] = {}   # max_new cap -> jitted loop

    def _next_bucket(self) -> list[Request]:
        """Pop up to max_batch queued requests sharing one prompt length
        (single pass: partition the queue instead of list.remove per hit)."""
        if not self.queue:
            return []
        length = len(self.queue[0].prompt)
        batch, rest = [], []
        for r in self.queue:
            if len(batch) < self.max_batch and len(r.prompt) == length:
                batch.append(r)
            else:
                rest.append(r)
        self.queue = rest
        return batch

    def _batch_inputs(self, reqs: list[Request]) -> dict:
        return _batch_inputs(reqs, self.extra_inputs)

    def _decode_loop_for(self, max_new: int) -> Callable:
        # bucket the static loop width up to a power of two: max_new is
        # request-controlled, and compiling (and retaining) one jitted
        # while_loop per distinct value would grow without bound.  The
        # live-mask still exits at the true per-row budgets; only the
        # token buffer is wider.
        cap = 1 << max(max_new - 1, 0).bit_length()
        if cap not in self._loops:
            self._loops[cap] = make_decode_loop(self.model, cap, self.cim)
        return self._loops[cap]

    # ------------------------------------------------------------------
    def _run_bucket_device(self, reqs: list[Request]):
        """Fast lane: prefill, then one on-device decode loop and ONE
        host transfer for the whole bucket."""
        tok, state = self._prefill(self.params, self._batch_inputs(reqs))
        self.steps_run += 1
        max_new = max(r.max_new for r in reqs)
        loop = self._decode_loop_for(max_new)
        max_new_row = jnp.asarray([r.max_new for r in reqs], jnp.int32)
        eos_row = jnp.asarray([r.eos_id for r in reqs], jnp.int32)
        buf, counts, steps = loop(self.params, tok, state, max_new_row,
                                  eos_row)
        buf, counts, steps = self._device_get((buf, counts, steps))
        self.steps_run += int(steps)
        for r, row, cnt in zip(reqs, buf, counts):
            r.out_tokens.extend(int(t) for t in row[: int(cnt)])

    def _run_bucket_legacy(self, reqs: list[Request]):
        """Original step-by-step driver: one host sync per decode step."""
        tok, state = self._prefill(self.params, self._batch_inputs(reqs))
        self.steps_run += 1
        live = [True] * len(reqs)
        for i, (r, t) in enumerate(zip(reqs, self._device_get(tok))):
            r.out_tokens.append(int(t))
            if len(r.out_tokens) >= r.max_new or int(t) == r.eos_id:
                live[i] = False
        max_new = max(r.max_new for r in reqs)
        for _ in range(max_new - 1):
            if not any(live):
                break
            tok, state = self._decode(self.params, tok, state)
            self.steps_run += 1
            for i, (r, t) in enumerate(zip(reqs, self._device_get(tok))):
                if not live[i]:
                    continue
                r.out_tokens.append(int(t))
                if len(r.out_tokens) >= r.max_new or int(t) == r.eos_id:
                    live[i] = False

    def run(self) -> list[Request]:
        """Serve the whole queue; returns completed requests."""
        run_bucket = (self._run_bucket_device if self.on_device_loop
                      else self._run_bucket_legacy)
        while self.queue:
            reqs = self._next_bucket()
            t0 = time.monotonic()
            run_bucket(reqs)
            dt = time.monotonic() - t0
            for r in reqs:
                r.done = True
                r.latency_s = dt
                self.completed.append(r)
        return self.completed

    def run_trace(self, clock=time.monotonic, sleep=time.sleep
                  ) -> list[Request]:
        """Replay arrival-stamped requests through the bucket driver
        (the shared ``_arrival_pump``): a request becomes visible at
        its ``arrival_s``; each round serves ONE bucket of whatever has
        arrived, so new arrivals can only be admitted at bucket
        boundaries — the baseline the continuous Scheduler is
        benchmarked against."""
        run_bucket = (self._run_bucket_device if self.on_device_loop
                      else self._run_bucket_legacy)

        def admit(req, now):
            self.queue.append(req)
            return True

        def serve_round(elapsed):
            reqs = self._next_bucket()
            # the bucket driver's real admission is the bucket pop —
            # a request "waits" until its bucket starts serving
            admit_t = elapsed()
            for r in reqs:
                r.admit_s = admit_t
            run_bucket(reqs)
            done_t = elapsed()
            for r in reqs:
                r.done = True
                r.latency_s = done_t - r.arrival_s
                self.completed.append(r)

        return self._arrival_pump(clock, sleep, admit,
                                  lambda: bool(self.queue), serve_round)


class Scheduler(_EngineBase):
    """Continuous-batching serve scheduler over a persistent slot pool.

    ``slots`` decode lanes live on device across scheduling rounds; each
    round runs one chunked decode loop (up to ``chunk`` steps, ONE
    device->host transfer), retires finished slots host-side from that
    transfer, and prefills newly arrived requests into the freed slots
    before the next round (interleaved prefill/decode).  Requests are
    admitted FIFO by ``arrival_s`` (then uid), so no request starves:
    every free slot is offered to the oldest arrived request first.

    Transfer accounting: ``host_transfers == chunks_run`` — admission
    and compaction stay on device, and a saturated uniform workload runs
    exactly ceil(decode_steps / chunk) chunks (pinned in
    tests/test_continuous.py).

    `spmd_axes` (from dist.sharding.slot_spmd_axes) shards the slot axis
    over the data-parallel mesh axes inside the chunked loop; off-mesh
    (the default) it is None and the pool is a plain leading axis.
    """

    def __init__(self, model, params, capacity: int = 512, slots: int = 8,
                 chunk: int = 8, cim=None, extra_inputs=None,
                 spmd_axes=None, clock=time.monotonic,
                 sleep=time.sleep, scrub_every: Optional[int] = 8):
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        super().__init__(model, params, capacity, cim, extra_inputs)
        self.slots = slots
        self.chunk = chunk
        self._clock = clock
        self._sleep = sleep
        self._init_fidelity(scrub_every)
        # control lanes shared by the dense and paged pools
        self.tok = jnp.zeros((slots,), jnp.int32)
        self.live = jnp.zeros((slots,), jnp.bool_)
        self.made = jnp.zeros((slots,), jnp.int32)
        self.fresh = jnp.zeros((slots,), jnp.bool_)
        self.max_new_row = jnp.ones((slots,), jnp.int32)
        self.eos_row = jnp.full((slots,), -1, jnp.int32)
        # host-side bookkeeping
        self._slot_req: list[Optional[Request]] = [None] * slots
        self.chunks_run = 0
        self.decode_steps = 0
        self.occupied_slot_steps = 0
        self._init_pool(model, spmd_axes)

    # subclass hook: allocate the device pool + compile the chunk loop
    def _init_pool(self, model, spmd_axes):
        self._chunk_fn = make_chunked_decode_loop(model, self.chunk,
                                                  self.cim, spmd_axes)
        self._admit_fn = make_admit_fn()
        # device-side pool: per-slot dense batch-1 states
        self.pool = init_slot_pool(model, self.slots, self.capacity)

    # ------------------------------------------------- device fidelity
    def _init_fidelity(self, scrub_every: Optional[int]) -> None:
        """Graceful-degradation state for ``fidelity='device'`` serving:
        pristine (TL-ReRAM) weights vs the SERVED weights, which drift
        by the fault model's per-chunk disturb channel and are repaired
        every ``scrub_every`` chunks by a restore-scrub — the paper's
        DC-power-free restore as an online repair, bounding accumulated
        error at the per-scrub restore yield instead of letting it
        compound.  Exact-fidelity engines: all hooks are no-ops and the
        serving path is bitwise-unchanged."""
        self.scrub_every = scrub_every
        self.scrubs_run = 0
        self.adc_clip_lo = 0          # per-chunk ADC clip/saturation
        self.adc_clip_hi = 0          # counters (device fidelity only)
        self._fault_serving = (self.cim is not None
                               and self.cim.mode == "ternary"
                               and self.cim.fidelity == "device")
        if not self._fault_serving:
            return
        from repro import faults
        nt = self.cim.num_trits
        fm = faults.get_fault_model()
        self._fault_model = fm
        self._params_pristine = self.params
        self._drift_key = fm.key_for("serve-drift")
        self._scrub_key = fm.key_for("serve-scrub")
        self._probe_fn = jax.jit(lambda p: faults.adc_probe(
            p, adc_bits=self.cim.adc_bits, num_trits=nt))
        self._disturb_fn = jax.jit(lambda p, k: faults.disturb_packed_params(
            p, fm.drift_rate, k, num_trits=nt))
        # pristine tree passed as an ARGUMENT, not closed over: a jit
        # constant would be constant-folded through the whole restore
        # channel at compile time (minutes per weight leaf on CPU)
        self._scrub_fn = jax.jit(lambda p, k: faults.scrub_packed_params(
            p, fm.restore_yield, k, num_trits=nt))
        # power-on restore: the served weights come up through ONE
        # restore pass from the pristine ReRAM contents
        self.params = self._scrub_fn(
            self._params_pristine,
            jax.random.fold_in(self._scrub_key, self.scrubs_run))

    def _pre_chunk(self) -> None:
        """Between-chunk drift: the disturb channel compounds on the
        served weights (chunk-indexed key — deterministic campaign)."""
        if self._fault_serving and self._fault_model.drift_rate > 0.0:
            self.params = self._disturb_fn(
                self.params,
                jax.random.fold_in(self._drift_key, self.chunks_run))

    def _round_extras(self) -> tuple:
        """Device scalars appended to the round's SINGLE transfer (the
        one-transfer-per-chunk contract must hold in device mode too):
        the ADC clip/saturation probe over the served weights."""
        if self._fault_serving:
            return self._probe_fn(self.params)
        return ()

    def _absorb_round_extras(self, extras: tuple) -> None:
        if extras:
            lo, hi = extras
            self.adc_clip_lo += int(lo)
            self.adc_clip_hi += int(hi)

    def _maybe_scrub(self) -> None:
        """Periodic restore-scrub: every ``scrub_every`` chunks the
        served weights are re-restored from the pristine tree (drift
        discarded; residual error bounded by the restore yield).
        ``scrub_every=None``/0 disables repair — the degradation
        baseline the serve_fidelity bench measures against."""
        if (self._fault_serving and self.scrub_every
                and self.chunks_run % self.scrub_every == 0):
            self.scrubs_run += 1
            self.params = self._scrub_fn(
                self._params_pristine,
                jax.random.fold_in(self._scrub_key, self.scrubs_run))

    def kv_bytes(self) -> int:
        """Device bytes of the pool's KV leaves (codes + scales) — the
        resident-memory quantity the paged pool competes on.  The dense
        pool is always fully resident: every slot holds its full
        ``capacity`` whether or not a request occupies it."""
        keys = ("k", "v", "k_scale", "v_scale")
        return sum(int(v.nbytes) for k, v in self.pool.items()
                   if k in keys and hasattr(v, "nbytes"))

    def kv_bytes_resident(self) -> int:
        return self.kv_bytes()

    def _admit(self, req: Request, slot: int) -> bool:
        """Prefill one request and scatter its state into `slot` —
        entirely on device (tok0 is emitted by the next chunk)."""
        with _span("serve.prefill", uid=req.uid, prompt_len=len(req.prompt)):
            tok0, st = self._prefill(self.params,
                                     _batch_inputs([req], self.extra_inputs))
            self.steps_run += 1
            (self.pool, self.tok, self.live, self.made, self.fresh,
             self.max_new_row, self.eos_row) = self._admit_fn(
                self.pool, self.tok, self.live, self.made, self.fresh,
                self.max_new_row, self.eos_row,
                jnp.asarray(slot, jnp.int32), st, tok0,
                jnp.asarray(req.max_new, jnp.int32),
                jnp.asarray(req.eos_id, jnp.int32))
        self._slot_req[slot] = req
        return True

    def _run_chunk(self):
        """Advance the pool one chunk; returns (buf, cnt, steps, occ)
        device handles (the round's single transfer happens in
        ``_serve_round``)."""
        (self.tok, self.pool, self.live, self.made, buf, cnt, steps,
         occ) = self._chunk_fn(
            self.params, self.tok, self.pool, self.live, self.made,
            self.fresh, self.max_new_row, self.eos_row)
        return buf, cnt, steps, occ

    def _retire_slot(self, slot: int) -> None:
        """Host bookkeeping when a slot's request completes (the paged
        scheduler additionally returns the slot's pages here)."""
        self._slot_req[slot] = None

    def _serve_round(self, elapsed) -> None:
        # one scheduling round: <= chunk decode steps on device, then
        # ONE transfer carrying everything the host needs — fidelity
        # extras (ADC clip counters) ride the same transfer
        with _span("serve.round") as round_span:
            with _span("serve.dispatch"):
                occupied = [i for i, r in enumerate(self._slot_req)
                            if r is not None]
                self._pre_chunk()
                buf, cnt, steps, occ = self._run_chunk()
                self.fresh = jnp.zeros((self.slots,), jnp.bool_)
                extras = self._round_extras()
            with _span("serve.sync"):
                out = self._device_get(
                    (buf, cnt, self.live, steps, occ) + extras)
            with _span("serve.absorb"):
                args = self._absorb_round(out, occupied, elapsed)
            if args:
                round_span.set_metadata(**args)

    def _absorb_round(self, out, occupied, elapsed) -> Optional[dict]:
        """Host bookkeeping on the round's transfer: counters, each
        slot's new tokens, retirement, the periodic scrub.  Returns the
        round's args for its ``serve.round`` span, if any."""
        buf_h, cnt_h, live_h, steps_h, occ_h = out[:5]
        self._absorb_round_extras(out[5:])
        self.chunks_run += 1
        self.decode_steps += int(steps_h)
        self.steps_run += int(steps_h)
        self.occupied_slot_steps += int(occ_h)
        done_t = elapsed()
        for s in occupied:
            req = self._slot_req[s]
            req.out_tokens.extend(
                int(t) for t in buf_h[s, : int(cnt_h[s])])
            if not bool(live_h[s]):            # retire: slot freed for
                req.done = True                # the next admission round
                req.latency_s = done_t - req.arrival_s
                self.completed.append(req)
                self._retire_slot(s)
        self._maybe_scrub()

    # ------------------------------------------------- external pump
    # The front-end (repro.frontend.server) drives the scheduler
    # through these three instead of run(): it owns the arrival loop
    # (bounded queue, SLO admission order) but MUST reuse the same
    # admission/round machinery so tokens and the one-transfer-per-
    # chunk contract are identical to a direct run().

    def free_slots(self) -> list[int]:
        return [i for i, r in enumerate(self._slot_req) if r is None]

    def is_busy(self) -> bool:
        return any(r is not None for r in self._slot_req)

    def try_admit(self, req: Request, now: float = 0.0) -> bool:
        """Offer one request to the first free slot; False defers it
        (pool full — or, paged, page reservation not coverable yet).
        Stamps ``admit_s`` on success."""
        free = self.free_slots()
        with _span("serve.admit", uid=req.uid,
                   slot=free[0] if free else -1):
            if not (free and self._admit(req, free[0])):
                return False
        req.admit_s = now
        return True

    def step_round(self, elapsed) -> None:
        """Run ONE scheduling round (<= chunk decode steps, exactly one
        device->host transfer); ``elapsed()`` is the caller's serve
        clock, used to stamp completion latencies."""
        self._serve_round(elapsed)

    def run(self) -> list[Request]:
        """Serve the whole queue continuously (the shared
        ``_arrival_pump``); returns completed requests."""
        # oldest arrived request into the first free slot, FIFO; defer
        # admission (False) when the pool is full — or, paged, when the
        # page pool cannot cover the request yet
        return self._arrival_pump(self._clock, self._sleep,
                                  self.try_admit, self.is_busy,
                                  self._serve_round)

    @property
    def slot_occupancy(self) -> float:
        """Fraction of (slot x decode-step) cells that held a live
        request — the utilization the continuous scheduler exists to
        maximize."""
        total = self.slots * self.decode_steps
        return self.occupied_slot_steps / total if total else 0.0


def _ceil_sum(n: int, ps: int) -> int:
    """``sum(ceil(x / ps) for x in 1..n)``: ``ps`` terms of each whole
    block ``k = 1..q``, then ``r`` terms of ``q + 1``."""
    q, r = divmod(n, ps)
    return ps * q * (q + 1) // 2 + r * (q + 1)


class PagedScheduler(Scheduler):
    """Continuous-batching scheduler over a paged, prefix-shared KV
    block pool (models/paged_kv.py) instead of per-slot dense caches.

    Identical scheduling semantics and transfer contract to
    :class:`Scheduler` (bitwise-identical tokens — tests/test_paged.py),
    but resident KV scales with the tokens actually held, not
    ``slots x capacity``:

      * the device pool is ``num_pages`` fixed-size pages shared by all
        slots; per-slot page tables map a slot's positions onto pages;
      * admission reserves every page the request can touch up front
        (prompt + worst-case decode budget) — all-or-nothing, so a
        request whose pages don't fit is DEFERRED (FIFO) rather than
        OOM-ing mid-decode — runs the batch-1 prefill, and scatters its
        KV into the fresh pages on device;
      * full prompt pages whose hashed token prefix already resides in
        the pool are mapped SHARED (refcounted, read-only — decode
        never writes a page holding positions below the slot's write
        point) instead of being written again: identical prefixes in a
        trace cost one copy;
      * retiring a slot releases its references; pages return to the
        free list when the last reference drops.

    When a ternary CIM config is supplied, it is re-resolved with
    ``kv_layout='paged'`` so only kernel backends that declare the
    paged capability are planned (src/repro/kernels/README.md).

    ``capacity`` bounds one request's prompt + decode budget (rounded
    up to a page multiple); ``num_pages`` defaults to the dense-pool
    equivalent (``slots * capacity / page_size``) — pass a smaller pool
    to cap resident KV below the dense baseline (admission then defers
    under overload instead of over-allocating).

    ``fused_attn`` selects the decode read path: ``'auto'`` (default)
    resolves a fused ``op='attention'`` plan — the Pallas executor that
    consumes the page table in-kernel, no gathered dense copy — and
    falls back to the ``slot_view`` gather path (logged, never silent)
    when the fused read would not help or hold: no capable backend for
    this pool (int8-KV scale pages, spmd-sharded pools), an
    interpret-mode-only platform (the emulation is slower than the
    gather path's native lowering), or a MoE config (top-k routing
    amplifies the kernel's f32 reassociation into token divergence —
    the bitwise contract needs the gather graph).  ``True`` requires
    the fused path (raises when no backend is capable; overrides the
    interpret/MoE preferences); ``False`` pins the gather path.  Token
    outputs are bitwise identical on every path 'auto' selects.
    """

    def __init__(self, model, params, capacity: int = 512,
                 slots: int = 8, chunk: int = 8, page_size: int = 16,
                 num_pages: Optional[int] = None,
                 share_prefix: bool = True, cim=None, extra_inputs=None,
                 spmd_axes=None, clock=time.monotonic, sleep=time.sleep,
                 scrub_every: Optional[int] = 8, fused_attn="auto"):
        if not model.supports_paged_kv:
            raise ValueError(
                f"{type(model).__name__} (family "
                f"{model.cfg.family!r}) does not support paged KV; "
                f"use the dense-pool Scheduler")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        capacity = -(-capacity // page_size) * page_size
        self.page_size = page_size
        self.pages_per_slot = capacity // page_size
        self.num_pages = (1 + slots * self.pages_per_slot
                          if num_pages is None else num_pages)
        self.share_prefix = share_prefix
        self.fused_attn = fused_attn
        if cim is not None:
            cim = dataclasses.replace(cim, kv_layout="paged")
        super().__init__(model, params, capacity=capacity, slots=slots,
                         chunk=chunk, cim=cim, extra_inputs=extra_inputs,
                         spmd_axes=spmd_axes, clock=clock, sleep=sleep,
                         scrub_every=scrub_every)

    def _resolve_attn_plan(self, model, spmd_axes):
        """Resolve the fused-attention ExecutionPlan for this pool
        geometry through the capability registry (never kwargs), or
        None for the gather path.  The plan shape is the attention
        problem the chunk loop runs every step: all slots' grouped
        queries (``S*KV*rep`` rows) of head dim ``hd`` against the
        per-slot page capacity ``W*page_size``."""
        if not self.fused_attn:
            return None
        from repro.kernels import plan_matmul
        cfg = model.cfg
        why = None
        if spmd_axes is not None:
            # the fused kernel carries no sharding annotations yet; the
            # vmapped gather path keeps its spmd_axis_name contract
            why = "spmd-sharded slot pool"
        elif cfg.kv_cache_dtype == "int8":
            why = "int8 KV pool (scale pages the fused read does not " \
                  "consume)"
        elif cfg.num_experts and self.fused_attn != True:  # noqa: E712
            # MoE top-k expert routing is discontinuous: the fused
            # read's per-page summation order differs from the gather
            # graph by f32 round-off, and a router near-tie amplifies
            # that into different experts — different tokens.  The
            # scheduler's contract is bitwise parity with the dense
            # pool, so 'auto' keeps the identical gather graph here;
            # fused_attn=True overrides (correct, but only
            # round-off-equal).
            why = "MoE routing (top-k amplifies f32 round-off; " \
                  "bitwise token parity needs the gather graph)"
        else:
            shape = (self.slots * cfg.num_heads, cfg.hd,
                     self.pages_per_slot * self.page_size)
            try:
                plan = plan_matmul(shape, "decode", op="attention",
                                   domain="float", kv_layout="paged")
            except ValueError as e:
                plan, why = None, str(e)
            if plan is not None:
                if not plan.interpret or self.fused_attn is True:
                    return plan
                # interpret mode is a correctness emulation, not the
                # kernel: it is slower than the gather path's native
                # XLA lowering, so 'auto' serves wallclock through the
                # gather graph on hosts without a real lowering.  The
                # parity tests and the bench force fused_attn=True.
                why = "interpret-mode emulation on this platform " \
                      "(slower than the gather path's native lowering)"
        if self.fused_attn is True:
            raise ValueError(
                f"fused_attn=True but the fused paged-attention read "
                f"is unavailable: {why}")
        _LOG.info("PagedScheduler: fused paged-attention read "
                  "unavailable (%s); serving through the slot_view "
                  "gather path", why)
        return None

    def _init_pool(self, model, spmd_axes):
        from repro.models import paged_kv
        self._paged_kv = paged_kv
        self.attn_plan = self._resolve_attn_plan(model, spmd_axes)
        self._chunk_fn = make_paged_decode_loop(model, self.chunk,
                                                self.cim, spmd_axes,
                                                attn_plan=self.attn_plan)
        self._admit_fn = make_paged_admit_fn()
        self._write_pages = jax.jit(paged_kv.write_prompt_pages,
                                    donate_argnums=(0,))
        self.pool = paged_kv.init_page_pool(model.cfg, self.num_pages,
                                            self.page_size)
        self.allocator = paged_kv.PageAllocator(self.num_pages,
                                                self.page_size)
        self.pos = jnp.zeros((self.slots,), jnp.int32)
        # host-side page tables: uploaded per chunk (a host->device
        # copy, not a device->host sync — the transfer contract counts
        # the latter); row entries beyond a slot's reservation stay 0
        # (the null page, masked by `pos` in the gather)
        self._page_table = np.zeros((self.slots, self.pages_per_slot),
                                    np.int32)
        # device copy of the table, re-uploaded only after admission or
        # retire edits it (not on every steady-state chunk)
        self._page_table_dev = None
        self._slot_pages: list[list] = [[] for _ in range(self.slots)]
        # fused read only: (slot, page) cells of the kernel's grid, per
        # layer, and those it computed (the rest it skips)
        self.attn_cells_computed = 0
        self.attn_cells_grid = 0
        # fused read of a MoE model only: routed (row, expert) pairs of
        # the decode steps, and experts with a row summed over layers
        # and steps (what the grouped kernel reads), from the device
        self._moe_stats = (self.attn_plan is not None
                           and bool(model.cfg.num_experts))
        self._moe_dev = None
        self.moe_rows_routed = 0
        self.moe_experts_read = 0

    # ------------------------------------------------------ accounting
    def kv_bytes(self) -> int:
        """Allocated device bytes of the page pool."""
        return sum(int(leaf.nbytes) for leaf in self.pool
                   if leaf is not None)

    def kv_bytes_resident(self) -> int:
        """Bytes of pages currently holding live KV."""
        return self.allocator.pages_in_use * self.pool.page_bytes

    @property
    def kv_bytes_resident_peak(self) -> int:
        return self.allocator.peak_in_use * self.pool.page_bytes

    @property
    def pages_in_use(self) -> int:
        return self.allocator.pages_in_use

    @property
    def prefix_hit_rate(self) -> float:
        return self.allocator.prefix_hit_rate

    # -------------------------------------------------------- admission
    def _admit(self, req: Request, slot: int) -> bool:
        ps = self.page_size
        s_len = len(req.prompt)
        # positions written: 0..S-1 (prefill) and S..S+max_new-2
        # (decode feeds tok0 first; the last sampled token is never fed)
        last_pos = s_len + req.max_new - 2 if req.max_new >= 2 else \
            s_len - 1
        n_total = last_pos // ps + 1
        if n_total > self.pages_per_slot:
            raise ValueError(
                f"request uid={req.uid} needs {n_total} pages "
                f"(prompt {s_len} + max_new {req.max_new}) but capacity "
                f"{self.capacity} holds {self.pages_per_slot} per slot")
        if n_total > self.num_pages - 1:
            # deferring would busy-spin forever: even an empty pool can
            # never privately satisfy this reservation
            raise ValueError(
                f"request uid={req.uid} needs {n_total} pages but the "
                f"pool holds {self.num_pages - 1} usable pages "
                f"(num_pages={self.num_pages}, page 0 reserved); size "
                f"num_pages to cover one worst-case request")
        with _span("serve.reserve", uid=req.uid):
            pages, shared = self._reserve(req, n_total)
        if pages is None:
            return False
        # device: batch-1 prefill, then scatter its KV into the fresh
        # pages (shared hits already hold the identical bits)
        with _span("serve.prefill", uid=req.uid, prompt_len=s_len):
            tok0, st = self._prefill(self.params,
                                     _batch_inputs([req], self.extra_inputs))
            self.steps_run += 1
            n_prompt = -(-s_len // ps)
            hit = set(shared)
            write_src = [j for j in range(n_prompt) if j not in hit]
            if write_src:
                self.pool = self._write_pages(
                    self.pool, st,
                    jnp.asarray([pages[j] for j in write_src], jnp.int32),
                    jnp.asarray(write_src, jnp.int32))
            (self.tok, self.live, self.made, self.fresh, self.max_new_row,
             self.eos_row, self.pos) = self._admit_fn(
                self.tok, self.live, self.made, self.fresh,
                self.max_new_row, self.eos_row, self.pos,
                jnp.asarray(slot, jnp.int32), tok0,
                jnp.asarray(req.max_new, jnp.int32),
                jnp.asarray(req.eos_id, jnp.int32),
                jnp.asarray(s_len, jnp.int32))
        row = np.zeros((self.pages_per_slot,), np.int32)
        row[:n_total] = pages
        self._page_table[slot] = row
        self._page_table_dev = None
        self._slot_pages[slot] = pages
        self._slot_req[slot] = req
        return True

    def _reserve(self, req: Request, n_total: int) -> tuple:
        """Host page reservation for ``req``: prefix-shared pages looked
        up, the rest allocated all-or-nothing.  Returns ``(pages,
        shared)``, or ``(None, [])`` with every reference rolled back
        when the pool cannot cover it."""
        from repro.models.paged_kv import prefix_key
        ps = self.page_size
        prompt_np = np.asarray(req.prompt)
        n_share = len(prompt_np) // ps if self.share_prefix else 0
        pages: list = [None] * n_total
        keys = [prefix_key(prompt_np, j, ps) for j in range(n_share)]
        shared = []
        for j, key in enumerate(keys):
            pid = self.allocator.lookup_prefix(key)
            if pid is not None:
                pages[j] = pid
                shared.append(j)
        missing = [j for j in range(n_total) if pages[j] is None]
        fresh_ids = self.allocator.alloc(len(missing))
        if fresh_ids is None:
            # pool exhausted: roll back the prefix references (and
            # their stats — the deferred retry will look them up again)
            self.allocator.release([pages[j] for j in shared])
            self.allocator.prefix_hits -= len(shared)
            self.allocator.prefix_lookups -= n_share
            return None, []
        for j, pid in zip(missing, fresh_ids):
            pages[j] = pid
            if j < n_share:
                self.allocator.register_prefix(keys[j], pid)
        return pages, shared

    # ------------------------------------------------------ chunk round
    def _run_chunk(self):
        if self._page_table_dev is None:
            self._page_table_dev = jnp.asarray(self._page_table)
        out = self._chunk_fn(
            self.params, self.tok, self.pool, self._page_table_dev,
            self.pos, self.live, self.made, self.fresh,
            self.max_new_row, self.eos_row)
        if self._moe_stats:
            out, self._moe_dev = out[:-1], out[-1]
        (self.tok, self.pool, self.pos, self.live, self.made, buf, cnt,
         steps, occ) = out
        return buf, cnt, steps, occ

    def _round_extras(self) -> tuple:
        """The MoE counts ride the round's one transfer, after the
        fidelity extras."""
        extras = super()._round_extras()
        return extras + (self._moe_dev,) if self._moe_stats else extras

    def _absorb_round_extras(self, extras: tuple) -> None:
        if self._moe_stats:
            routed, read = (int(v) for v in extras[-1])
            self.moe_rows_routed += routed
            self.moe_experts_read += read
            extras = extras[:-1]
        super()._absorb_round_extras(extras)

    def _absorb_round(self, out, occupied, elapsed) -> Optional[dict]:
        """Counts the fused read's cells before the base bookkeeping
        extends each request's tokens: the decode step that made token
        ``i`` (``i >= 1``; token 0 is the prefill's) read ``len = prompt
        + i - 1`` positions, ``ceil(len / page_size)`` live cells."""
        if self.attn_plan is None:
            return super()._absorb_round(out, occupied, elapsed)
        cnt_h, steps_h = out[1], int(out[3])
        computed = 0
        for s in occupied:
            req = self._slot_req[s]
            n0 = len(req.out_tokens)
            # this round's tokens i in [max(n0, 1), n0 + cnt) read
            # lens in [lo, hi), lo >= 1
            lo = len(req.prompt) + max(n0, 1) - 1
            hi = len(req.prompt) + n0 + int(cnt_h[s]) - 1
            computed += (_ceil_sum(hi - 1, self.page_size)
                         - _ceil_sum(lo - 1, self.page_size))
        grid = steps_h * self.slots * self.pages_per_slot
        self.attn_cells_computed += computed
        self.attn_cells_grid += grid
        super()._absorb_round(out, occupied, elapsed)
        return {"computed": computed, "grid": grid}

    def _retire_slot(self, slot: int) -> None:
        self.allocator.release(self._slot_pages[slot])
        self._slot_pages[slot] = []
        self._page_table[slot] = 0
        self._page_table_dev = None
        self._slot_req[slot] = None
