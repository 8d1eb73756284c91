"""Jitted, vmappable twin of the discrete-event serving runtime.

``serving.runtime.ServingRuntime`` steps a Python ``heapq`` one event at a
time — exact, but single-env and far slower than training wants. This module
re-expresses the same dynamics as a pure-JAX event loop so a full
closed-loop adaptation episode (policy decision every
``ADAPTATION_INTERVAL``, measured-telemetry reward per Eq. (3)/(7)) is one
``lax.scan`` over intervals, vmappable across environments — the runtime
counterpart of ``core.vecenv``'s analytic twin.

Instead of heaped timer events, the twin *derives* each stage's next
dispatch instant from its queue state (timeout-or-full continuous batching,
cold-start gate, free-replica gate) and advances an inner ``lax.while_loop``
one event at a time, always processing the earliest of

  dispatch < completion

(the priority order mirrors the Python loop's FIFO tie-breaking; with
continuous arrival times, exact ties are measure zero). Neither arrivals
nor transfer deliveries are events. The pre-generated arrival array is
sorted and immutable, so stage 0's queue is *virtual* — a head pointer into
the arrival array, which ``init_state`` lays into queue-buffer row 0 so
every stage reads through one uniform window; a dispatch counts how many
arrivals have landed within its 2B-wide head window.
Cross-node transfers get the same treatment: a forwarded completion writes
its batch into the next stage's queue immediately, stamped with its
*delivery* time (``now + hop``), and every dispatch-timer quantity — the
timeout anchor, the batch-full instant, the poppable count — is derived
from those stamps, so a separate delivery event would change nothing the
loop can observe. Downstream per-stage queues are append-only buffers
sized to the episode's arrival count; per-replica slots pin (variant,
batch, node speed) at dispatch exactly like the event loop, so mid-flight
reconfigurations never change an already-running batch.
Placement reuses ``vecenv._placement`` — the float32 scheduler twin whose
discrete decisions are bit-identical to the Python first-fit scheduler — so
replica slot speeds, primary nodes, and cross-node hop penalties match
``ServingRuntime`` exactly.

Performance shape: the env axis is threaded *explicitly* through the event
loop rather than via ``vmap`` — ``while_loop``'s batching rule wraps every
carry array in a per-iteration ``select(done, old, new)``, which copies
the multi-MB queue buffer once per event; with a scalar ``jnp.any``
condition and self-masking envs the buffer keeps a single consumer (its
enqueue scatter) and XLA mutates it in place. Three things keep the loop
body lean on CPU, where it is kernel-launch bound (~35 fused kernels per
event at a microsecond each):

- the queue buffer sees exactly one scatter per event (the forward
  enqueue); everything else is gathers and one-hot masked vector math.
  In-flight batches pin their *head index* (``fl_head``), not their
  contents — the buffer is append-only, so one gather at completion
  recovers the batch's arrival times, where pinning the times themselves
  would cost a second scatter (vmapped ``dynamic_update_slice`` lowers to
  a sequential per-env loop on CPU XLA — gathers don't);
- ``select`` runs on carried per-stage head / batch-full delivery stamps
  (``r_head`` / ``r_full``, refreshed from the buffer once per interval,
  maintained incrementally per event), so picking the next event never
  touches the big buffer; the loop body patches the one stage a dispatch
  changed and re-runs the argmin instead of recomputing ``select``;
- a completion replays the dispatch timers on the post-completion state
  and, when some stage is due at that same instant (the freed replica's
  stage, or the one its forwarded batch just filled), processes that
  dispatch in the same iteration — provably the globally-next event, and
  under load it nearly halves the iteration count.

Exact vs approximate w.r.t. the event loop:

- *exact*: event ordering, batch formation, replica claiming (fastest free,
  ties lowest slot), service times, cold-start gating, placement decisions,
  transfer delivery times (including transfers in flight across a
  reconfiguration — their stamps keep the hop they departed with),
  interval scoring formulas, arrival streams (shared
  ``ArrivalProcess.times``);
- *approximate*: times are float32, so completions landing within ~1e-4 s
  of an interval boundary may be counted one interval over — served counts
  match within a request or two and episode rewards within float tolerance
  (``tests/test_runtime_vec.py`` pins both against ``ServingRuntime``).
  Queues pop strictly FIFO in *enqueue* order; if a re-placement changes a
  hop while transfers are in flight, delivery stamps across the boundary
  can be momentarily non-monotone and a pop may wait out the older stamp
  (at most one hop, ~tens of ms).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np  # reprolint: ignore[RPL002] host-side arrival-array prep only (episode_arrivals/stack_episodes)

from repro.analysis import sanitize
from repro.core.mdp import (ADAPTATION_INTERVAL, COLD_START_FRACTION,
                            QoSWeights)
from repro.core.policy import apply_policy, sample_action
from repro.core.vecenv import (PipelineTables, _gather, _placement,
                               decode_action, observe_cfg)

INF = jnp.float32(jnp.inf)
COLD_START_SECONDS = COLD_START_FRACTION * ADAPTATION_INTERVAL
DEFAULT_MAX_WAIT = 0.25          # mirrors serving.runtime.DEFAULT_MAX_WAIT
_ARRIVAL_BUCKET = 512            # arrival arrays pad to multiples of this
# guaranteed inf-padding at the tail of every arrival array, so the event
# loop's 2B-wide head window is always a plain in-bounds dynamic_slice
# (requires 2 * b_max <= _ARRIVAL_PAD — checked in init_state)
_ARRIVAL_PAD = 64


class EpisodeArrivals(NamedTuple):
    """One episode's pre-generated arrival stream plus the host-precomputed
    per-interval statistics the reward/observation need (computed in float64
    from the exact times, so demand and measured load match the Python
    telemetry bit-for-bit)."""
    times: jax.Array         # [N_cap] f32 arrival instants, padded with inf
    arrived: jax.Array       # [T] f32  arrivals in [10k, 10k+10)
    load_obs: jax.Array      # [T] f32  measured load at decision k (req/s)


class RuntimeState(NamedTuple):
    """The twin's full event-loop state (one environment)."""
    now: jax.Array           # f32 virtual clock
    arr_idx: jax.Array       # i32 arrivals landed by the last boundary
    q_buf: jax.Array         # [S, Q, 2] f32 append-only queue:
                             #   [..., 0] original arrival time
                             #   [..., 1] delivery time at this stage
                             #     (completion time + hop; a stamp in the
                             #      future means the batch is still in
                             #      cross-node transfer)
                             #   (row 0 holds the episode's arrival array
                             #    in both columns — stage 0's "queue" —
                             #    so every read is uniform across stages)
    q_head: jax.Array        # [S] i32 (monotone, no wraparound; head 0
                             #   indexes the episode's arrival array)
    q_len: jax.Array         # [S] i32 enqueued requests (head..head+len)
    r_head: jax.Array        # [S] f32 head delivery stamp (valid while
                             #   q_len > 0; stage 0: times[head], inf past
                             #   the last arrival) — carried so ``select``
                             #   never gathers from the big queue buffer
    r_full: jax.Array        # [S] f32 delivery stamp of the b-th queued
                             #   request (valid while q_len >= b)
    fl_finish: jax.Array     # [S, R] f32 in-flight finish time (inf = free)
    fl_size: jax.Array       # [S, R] i32 pinned batch size
    fl_head: jax.Array       # [S, R] i32 queue index of the batch's first
                             #   request at dispatch — the buffer is
                             #   append-only, so the batch's arrival times
                             #   are still there at completion (pinning an
                             #   index instead of copying the times keeps
                             #   the dispatch path free of batched scatters)
    blocked: jax.Array       # [S] f32 cold-start gate
    z: jax.Array             # [S] i32 live variant
    f: jax.Array             # [S] i32 live replicas
    b: jax.Array             # [S] i32 live batch size
    slot_speed: jax.Array    # [S, R] f32 node speed of each replica slot
    hop_next: jax.Array      # [S] f32 transfer delay stage s -> s+1 (last 0)
    completed: jax.Array     # f32 completions this interval
    lat_sum: jax.Array       # f32 Σ end-to-end latency this interval


# ---------------------------------------------------------------- episode --

def episode_arrivals(process, horizon: int, *,
                     n_cap: int | None = None) -> EpisodeArrivals:
    """Host-side precomputation of one episode's arrivals: the shared
    ``process.times(horizon)`` array (identical to what ``ServingRuntime.
    load`` consumes) padded to a static bucketed capacity, plus exact
    float64 per-interval arrival counts and the per-second measured load the
    predictor-free observation reads (``RuntimeEnv`` prefills its monitor
    with the t=0 expected rate; afterwards the newest monitor slot is the
    arrival count of the second before each decision)."""
    t = np.asarray(process.times(horizon), np.float64)
    n_steps = max(1, int(horizon) // ADAPTATION_INTERVAL)
    edges = np.arange(n_steps + 1, dtype=np.float64) * ADAPTATION_INTERVAL
    arrived = np.histogram(t, bins=edges)[0].astype(np.float64)
    load_obs = np.empty(n_steps, np.float64)
    load_obs[0] = float(process.rates(1)[0])
    for k in range(1, n_steps):
        s = k * ADAPTATION_INTERVAL - 1
        load_obs[k] = np.count_nonzero((t >= s) & (t < s + 1))
    if n_cap is None:
        n_cap = (int(np.ceil((len(t) + _ARRIVAL_PAD) / _ARRIVAL_BUCKET))
                 * _ARRIVAL_BUCKET)
    if len(t) > n_cap - _ARRIVAL_PAD:
        raise ValueError(f"n_cap={n_cap} < {len(t)} arrivals + pad")
    padded = np.full(n_cap, np.inf, np.float32)
    padded[:len(t)] = t.astype(np.float32)
    return EpisodeArrivals(times=jnp.asarray(padded),
                           arrived=jnp.asarray(arrived, jnp.float32),
                           load_obs=jnp.asarray(load_obs, jnp.float32))


def stack_episodes(eps: list[EpisodeArrivals]) -> EpisodeArrivals:
    """Batch per-env episodes along a leading axis (re-padding arrival
    arrays to the widest bucket) for ``vec_rollout``."""
    n_cap = max(e.times.shape[0] for e in eps)
    times = np.full((len(eps), n_cap), np.inf, np.float32)
    for i, e in enumerate(eps):
        times[i, :e.times.shape[0]] = np.asarray(e.times)
    return EpisodeArrivals(
        times=jnp.asarray(times),
        arrived=jnp.stack([e.arrived for e in eps]),
        load_obs=jnp.stack([e.load_obs for e in eps]))


# ------------------------------------------------------------------ state --

def init_state(tables: PipelineTables, ep: EpisodeArrivals) -> RuntimeState:
    """Episode start: default configuration (z=0, f=1, b=1) already placed,
    empty queues, idle replicas — mirroring ``RuntimeEnv.reset``."""
    S = tables.n_tasks
    R = tables.replica_slots.shape[0]
    B = tables.batch_slots.shape[0]
    if 2 * B > _ARRIVAL_PAD:
        raise ValueError(
            f"2*b_max={2 * B} exceeds arrival padding {_ARRIVAL_PAD}")
    # every request enqueues at each stage exactly once, so the append-only
    # buffer needs arrival capacity + one batch of write headroom
    Q = ep.times.shape[0] + B
    z0 = jnp.zeros(S, jnp.int32)
    f0 = jnp.ones(S, jnp.int32)
    slot_speed, hop_next = _install_placement(tables, z0, f0)
    # stage 0's queue row holds the episode's (inf-padded) arrival array in
    # both columns: a request's stage-0 "delivery" is its arrival. The last
    # B lanes stay inf — that's where masked-off enqueue writes land, and
    # no read reaches past times' own _ARRIVAL_PAD inf tail before it
    row0 = jnp.full(Q, jnp.inf, jnp.float32).at[:ep.times.shape[0]].set(
        ep.times)
    q_buf = jnp.zeros((S, Q, 2), jnp.float32)
    q_buf = q_buf.at[0, :, 0].set(row0).at[0, :, 1].set(row0)
    return RuntimeState(
        now=jnp.float32(0.0), arr_idx=jnp.int32(0),
        q_buf=q_buf,
        q_head=jnp.zeros(S, jnp.int32), q_len=jnp.zeros(S, jnp.int32),
        r_head=jnp.full(S, jnp.inf, jnp.float32),
        r_full=jnp.full(S, jnp.inf, jnp.float32),
        fl_finish=jnp.full((S, R), jnp.inf, jnp.float32),
        fl_size=jnp.zeros((S, R), jnp.int32),
        fl_head=jnp.zeros((S, R), jnp.int32),
        blocked=jnp.zeros(S, jnp.float32),
        z=z0, f=f0, b=jnp.ones(S, jnp.int32),
        slot_speed=slot_speed, hop_next=hop_next,
        completed=jnp.float32(0.0), lat_sum=jnp.float32(0.0))


def _install_placement(tables: PipelineTables, z: jax.Array, f: jax.Array):
    """(slot_speed [S, R], hop_next [S]) of configuration (z, f) — the twin
    of ``ServingRuntime._install_placement``."""
    S = tables.n_tasks
    R = tables.replica_slots.shape[0]
    if tables.n_nodes == 0:            # scalar pool: unit speed, no hops
        return jnp.ones((S, R), jnp.float32), jnp.zeros(S, jnp.float32)
    pl = _placement(tables, z, f)
    hop = jnp.where(pl.primary[:-1] != pl.primary[1:], tables.hop_latency,
                    0.0).astype(jnp.float32)
    return pl.slot_speed, jnp.concatenate([hop, jnp.zeros(1, jnp.float32)])


# -------------------------------------------------------------- event loop --

def _advance(tables: PipelineTables, state: RuntimeState,
             times: jax.Array, t_end: jax.Array,
             max_wait: jax.Array) -> RuntimeState:
    """Process every event with time <= t_end (one ``lax.while_loop``
    iteration per event), leaving every env's clock at t_end — the twin of
    ``ServingRuntime.run_until``.

    ``state`` carries an explicit leading env axis and the loop condition
    reduces over it. Putting the whole loop under ``vmap`` instead would
    invoke ``while_loop``'s batching rule, which wraps every carry array in
    a per-iteration ``select(done, old, new)`` — a full copy of the
    multi-MB queue buffer per event. With a scalar ``jnp.any`` condition
    the queue buffer keeps a single consumer (its enqueue scatter), XLA
    updates it in place, and envs that have drained their interval mask
    their own effects (~5x wall clock on CPU at 32 envs).
    """
    S = tables.n_tasks
    R = tables.replica_slots.shape[0]
    B = tables.batch_slots.shape[0]
    Q = state.q_buf.shape[2]
    iota_s = jnp.arange(S, dtype=jnp.int32)
    iota_r = jnp.arange(R, dtype=jnp.int32)
    iota_b = jnp.arange(B, dtype=jnp.int32)
    iota_b2 = jnp.arange(2 * B, dtype=jnp.int32)
    # per-interval constants: the live configuration is fixed between
    # reconfigurations, so service coefficients resolve once per _advance
    a_z = jax.vmap(lambda z: _gather(tables.alpha, z))(state.z)
    b_z = jax.vmap(lambda z: _gather(tables.beta, z))(state.z)

    def row_s(arr, s):
        """arr [S, ...] at dynamic stage s via one-hot sum (vector math in
        place of a batched gather; rows are mutually exclusive so the sum
        selects — inf entries survive as 0 + inf)."""
        mask = (iota_s == s).reshape((S,) + (1,) * (arr.ndim - 1))
        return jnp.sum(jnp.where(mask, arr, 0), axis=0)

    def refresh(st: RuntimeState):
        """Recompute the carried head / batch-full delivery stamps from the
        queue buffers — once per interval (a reconfiguration can change
        ``b``, moving the batch-full position). Inside the event loop the
        stamps are maintained incrementally from the dispatch window and
        enqueue writes, so ``select`` never touches the big buffer. The
        inf tails keep head + b - 1 in bounds and return inf when stage
        0's remaining arrivals can't fill a batch."""
        r_head = st.q_buf[iota_s, jnp.minimum(st.q_head, Q - 1), 1]
        r_full = st.q_buf[iota_s,
                          jnp.minimum(st.q_head + st.b - 1, Q - 1), 1]
        return st._replace(r_head=r_head, r_full=r_full)

    def select(st: RuntimeState):
        """One env's earliest pending event: (t_next, ev, s_disp, s_cmp,
        r_cmp). ev: 0=dispatch, 1=completion. Pure small-vector math over
        the carried per-stage stamps: batch-full and timeout instants
        derive from delivery stamps, so future arrivals and in-flight
        transfers schedule dispatches without ever being events
        themselves."""
        in_flight = jnp.sum(st.fl_finish < INF, axis=1)
        has_any = jnp.where(iota_s == 0, st.r_head < INF, st.q_len > 0)
        # max with the head stamp: stamps are monotone except momentarily
        # after a hop re-placement, and strict-FIFO popping can't start a
        # batch before its head delivers
        t_full = jnp.where(jnp.where(iota_s == 0, True, st.q_len >= st.b),
                           jnp.maximum(st.r_full, st.r_head), INF)
        t_ready = jnp.minimum(t_full,
                              jnp.where(has_any, st.r_head + max_wait, INF))
        t_disp_s = jnp.maximum(st.now, jnp.maximum(st.blocked, t_ready))
        t_disp_s = jnp.where(in_flight < st.f, t_disp_s, INF)
        # one shared argmin over [S + S*R] candidates; dispatch entries
        # come first, so the first-occurrence tie-break keeps the
        # dispatch-before-completion priority
        cand = jnp.concatenate([t_disp_s, st.fl_finish.reshape(-1)])
        idx = jnp.argmin(cand).astype(jnp.int32)
        cmp_flat = jnp.maximum(idx - S, 0)
        return (jnp.min(cand), (idx >= S).astype(jnp.int32),
                jnp.minimum(idx, S - 1), cmp_flat // R, cmp_flat % R)

    def body_env(st, sel, active, cpack_e):
        """One env, one event — every effect is masked by ``active`` so a
        drained env is a no-op while its siblings catch up."""
        now, ev, s_disp, s_cmp, r_cmp = sel
        is_cmp = active & (ev == 1)

        # -- completion: free the slot; final stage -> telemetry, else the
        #    batch enters the next stage's queue immediately, stamped with
        #    its delivery time (now + hop) — the dispatch timers derive
        #    everything from the stamps, so in-flight transfers need no
        #    event of their own -------------------------------------------
        oh_cmp = (iota_s[:, None] == s_cmp) & (iota_r[None, :] == r_cmp)
        hk = jnp.sum(jnp.where(oh_cmp[None], jnp.stack([st.fl_size,
                                                        st.fl_head]), 0),
                     axis=(1, 2))
        k_cmp, hd_cmp = hk[0], hk[1]
        # the batch's arrival times still sit where they were dispatched
        # from: the buffer is append-only (slab writes land at tails past
        # them), so the pinned head index recovers them with one gather
        cmp_orig = jax.lax.dynamic_slice(
            st.q_buf, (s_cmp, hd_cmp, 0), (1, B, 2))[0, :, 0]
        last = s_cmp == S - 1
        fl_finish = jnp.where(is_cmp & oh_cmp, INF, st.fl_finish)
        done = is_cmp & last
        completed = st.completed + jnp.where(done, k_cmp, 0)
        lat_sum = st.lat_sum + jnp.where(
            done,
            k_cmp * now - jnp.sum(jnp.where(iota_b < k_cmp, cmp_orig, 0.0)),
            0.0)
        hop_cmp = row_s(st.hop_next, s_cmp)
        forward = is_cmp & ~last
        s_next = jnp.minimum(s_cmp + 1, S - 1)

        # -- the one write on the big buffer: a forwarded completion puts
        #    its batch into s+1 (stamp = delivery time, now + hop) as one
        #    contiguous dynamic_update_slice. Lanes past the batch land
        #    beyond the new tail and are overwritten before any read;
        #    masked-off events write at (0, Q - B) — the inf headroom past
        #    stage 0's arrival array, which no window read ever reaches ----
        w_s, w_k = s_next, k_cmp
        tail = row_s(st.q_head + st.q_len, w_s)
        vals = jnp.stack([cmp_orig,
                          jnp.broadcast_to(now + hop_cmp, (B,))], axis=-1)
        q_buf = jax.lax.dynamic_update_slice(
            st.q_buf, vals[None],
            (jnp.where(forward, w_s, 0), jnp.where(forward, tail, Q - B),
             0))

        # -- completion -> dispatch fusion: replay ``select``'s dispatch
        #    timers on the post-completion state — pure vector math on the
        #    carried stamps, no gathers. If any stage is due at this very
        #    instant the globally-next event is provably that dispatch
        #    (dispatches outrank completions and nothing can precede
        #    ``now``), so it is processed in the same iteration. This
        #    catches both the freed replica's stage re-dispatching and the
        #    downstream stage the forwarded batch just filled — under load
        #    most completions trigger one, halving the event count --------
        enq = forward & (iota_s == w_s)
        deliver = now + hop_cmp
        q_len_mid = st.q_len + jnp.where(enq, w_k, 0)
        r_head_mid = jnp.where(enq & (st.q_len == 0), deliver, st.r_head)
        r_full_mid = jnp.where(enq & (st.q_len < st.b)
                               & (st.q_len + w_k >= st.b), deliver,
                               st.r_full)
        in_flight = jnp.sum(fl_finish < INF, axis=1)
        has_any = jnp.where(iota_s == 0, r_head_mid < INF, q_len_mid > 0)
        t_full = jnp.where(jnp.where(iota_s == 0, True, q_len_mid >= st.b),
                           jnp.maximum(r_full_mid, r_head_mid), INF)
        t_ready = jnp.minimum(t_full,
                              jnp.where(has_any, r_head_mid + max_wait, INF))
        t_disp = jnp.maximum(now, jnp.maximum(st.blocked, t_ready))
        t_disp = jnp.where(in_flight < st.f, t_disp, INF)
        fused = is_cmp & (jnp.min(t_disp) <= now)
        s_disp = jnp.where(ev == 0, s_disp,
                           jnp.argmin(t_disp).astype(jnp.int32))
        is_disp = (active & (ev == 0)) | fused

        # -- dispatch: pop the delivered FIFO prefix (clamped to b), claim
        #    the fastest free slot. Stage 0 pops straight out of the
        #    arrival-array head window; b <= B, so the B-wide window
        #    bounds the count exactly after the min() clamp ---------------
        # one masked sum selects every per-stage constant the dispatch
        # needs (b, f, blocked, alpha, beta — fixed for the interval, so
        # the [5, S] pack is built once outside the loop), and a second
        # the two mutable cursors — five reductions become two
        oh_d = iota_s == s_disp
        seld = jnp.sum(jnp.where(oh_d[None, :], cpack_e, 0.0), axis=1)
        b_d = seld[0].astype(jnp.int32)
        f_d = seld[1].astype(jnp.int32)
        hq = jnp.sum(jnp.where(oh_d[None, :],
                               jnp.stack([st.q_head, q_len_mid]), 0), axis=1)
        head_d = hq[0]
        q_slice = jax.lax.dynamic_slice(
            q_buf, (s_disp, head_d, 0), (1, 2 * B, 2)).reshape(2 * B, 2)
        orig_src = q_slice[:, 0]
        stamp = q_slice[:, 1]
        # stage 0's depth is virtual (its lanes past the arrivals are inf,
        # so the stamp check alone bounds the pop)
        in_q = (s_disp == 0) | (iota_b2 < hq[1])
        # delivered prefix: stamps are monotone except momentarily after a
        # hop re-placement, where strict-FIFO popping waits out the head
        # first undelivered lane bounds the poppable prefix — argmin on the
        # bool mask, not a cumprod-sum: XLA CPU lowers cumprod to a slow
        # O(window²) reduce-window, and this loop is kernel-launch bound
        ready = (stamp <= now) & in_q
        n_avail = jnp.where(jnp.all(ready), 2 * B,
                            jnp.argmin(ready).astype(jnp.int32))
        rows = jnp.sum(jnp.where(oh_d[None, :, None],
                                 jnp.stack([fl_finish, st.slot_speed]), 0.0),
                       axis=1)
        fl_row, speed_row = rows[0], rows[1]
        n_pop = jnp.where(is_disp, jnp.minimum(b_d, n_avail), 0)
        free = (iota_r < f_d) & (fl_row == INF)
        score = jnp.where(free, speed_row, -INF)
        r_claim = jnp.argmax(score)
        service = ((seld[3] + seld[4] * n_pop)
                   / jnp.maximum(jnp.max(score), 1e-9))
        oh_claim = oh_d[:, None] & (iota_r[None, :] == r_claim)
        fl_finish = jnp.where(is_disp & oh_claim, now + service, fl_finish)
        fl_size = jnp.where(is_disp & oh_claim, n_pop, st.fl_size)
        # pin where the batch came from, not what it contained: the buffer
        # is append-only, so the head index recovers the arrival times at
        # completion — a masked vector write instead of a scatter
        fl_head = jnp.where(is_disp & oh_claim, head_d, st.fl_head)

        # -- head/len bookkeeping (one-hot on [S]; stage 0's len is
        #    virtual and reconstructed after the loop) ---------------------
        q_head = st.q_head + jnp.where(is_disp & oh_d, n_pop, 0)
        q_len = (q_len_mid
                 - jnp.where(is_disp & (s_disp > 0) & oh_d, n_pop, 0))

        # -- maintain the carried stamps: the dispatching stage's new head
        #    and batch-full stamps come straight out of its 2B-wide window
        #    (n_pop <= b <= B keeps both in range); a forwarded batch
        #    stamps the destination's head when its queue was empty and
        #    its batch-full slot when the append crosses b. Off-range
        #    values are garbage, guarded by select's q_len checks ----------
        pos = jnp.stack([n_pop, n_pop + b_d - 1])
        rhf = jnp.take(stamp, pos)
        oh_disp = is_disp & oh_d
        r_head = jnp.where(oh_disp, rhf[0], r_head_mid)
        r_full = jnp.where(oh_disp, rhf[1], r_full_mid)

        st = st._replace(
            now=jnp.where(active, jnp.maximum(st.now, now), st.now),
            q_buf=q_buf, q_head=q_head, q_len=q_len,
            r_head=r_head, r_full=r_full,
            fl_finish=fl_finish, fl_size=fl_size, fl_head=fl_head,
            completed=completed, lat_sum=lat_sum)

        # -- incremental next-event pick: the dispatch timers were already
        #    replayed on the mid state above, and a dispatch only changes
        #    its own stage's entry — patch that one stage scalar-wise and
        #    redo the argmin instead of recomputing ``select`` in full.
        #    (For an idle env every entry is provably >= its pending event
        #    time, so the clamp at ``now`` is a no-op and the previous
        #    pick is reproduced exactly.) ----------------------------------
        q_len_d = hq[1] - jnp.where(s_disp > 0, n_pop, 0)
        has_any_d = jnp.where(s_disp == 0, rhf[0] < INF, q_len_d > 0)
        t_full_d = jnp.where((s_disp == 0) | (q_len_d >= b_d),
                             jnp.maximum(rhf[1], rhf[0]), INF)
        t_ready_d = jnp.minimum(
            t_full_d, jnp.where(has_any_d, rhf[0] + max_wait, INF))
        in_flight_d = jnp.sum(jnp.where(oh_d, in_flight, 0)) + 1
        t_disp_d = jnp.maximum(now, jnp.maximum(seld[2], t_ready_d))
        t_disp_d = jnp.where(in_flight_d < f_d, t_disp_d, INF)
        cand = jnp.concatenate([jnp.where(oh_disp, t_disp_d, t_disp),
                                fl_finish.reshape(-1)])
        idx = jnp.argmin(cand).astype(jnp.int32)
        cmp_flat = jnp.maximum(idx - S, 0)
        return st, (jnp.min(cand), (idx >= S).astype(jnp.int32),
                    jnp.minimum(idx, S - 1), cmp_flat // R, cmp_flat % R)

    def cond(carry):
        return jnp.any(carry[1][0] <= t_end)

    # per-stage constants the dispatch path selects with one masked sum:
    # batch size, replica count, cold-start gate, service coefficients
    cpack = jnp.stack([state.b.astype(jnp.float32),
                       state.f.astype(jnp.float32),
                       state.blocked, a_z, b_z], axis=1)

    def body(carry):
        st, sel = carry
        return jax.vmap(body_env)(st, sel, sel[0] <= t_end, cpack)

    state = jax.vmap(refresh)(state)
    sel0 = jax.vmap(select)(state)
    st, _ = jax.lax.while_loop(cond, body, (state, sel0))
    # materialise stage 0's virtual queue depth at the interval boundary
    n_seen = jax.vmap(
        lambda te: jnp.searchsorted(te, t_end, side="right"))(times)
    n_seen = n_seen.astype(jnp.int32)
    q_len = st.q_len.at[:, 0].set(n_seen - st.q_head[:, 0])
    return st._replace(now=jnp.maximum(st.now, t_end),
                       arr_idx=n_seen, q_len=q_len)


# ----------------------------------------------------------------- interval --

def _analytic_latency(tables: PipelineTables, z, f, b, demand):
    """jnp twin of ``mdp.analytic_pipeline_latency`` — the smooth latency
    fallback when an interval completes nothing."""
    bf = b.astype(jnp.float32)
    fb = f.astype(jnp.float32) * bf
    lat = _gather(tables.alpha, z) + _gather(tables.beta, z) * bf
    wait = jnp.minimum(fb / jnp.maximum(demand, 1e-6), 2.0)
    if tables.n_nodes == 0:
        thr = fb / lat
        lat_eff = lat
        hop_total = jnp.float32(0.0)
    else:
        pl = _placement(tables, z, f)
        thr = pl.speed_sum * bf / lat
        lat_eff = lat / pl.min_speed
        n_hops = jnp.sum((pl.primary[:-1] != pl.primary[1:])
                         .astype(jnp.float32))
        hop_total = tables.hop_latency * n_hops
    rho = demand / jnp.maximum(thr, 1e-9)
    congestion = 1.0 / jnp.maximum(1.0 - rho, 0.1)
    return jnp.sum(wait + lat_eff * congestion) + hop_total


def _apply_config(tables: PipelineTables, state: RuntimeState,
                  action: jax.Array) -> RuntimeState:
    """Decode + install one env's configuration at an interval boundary
    (cold start in virtual time, re-placement, telemetry reset) — the first
    half of ``RuntimeEnv.step``."""
    z, f, b = decode_action(tables, action)
    switched = z != state.z
    blocked = jnp.where(switched,
                        jnp.maximum(state.blocked,
                                    state.now + COLD_START_SECONDS),
                        state.blocked)
    slot_speed, hop_next = _install_placement(tables, z, f)
    # in-flight transfers keep the delivery stamps they departed with — a
    # hop re-placement only affects batches completed after it, exactly
    # like the Python runtime's already-heaped transfer events
    return state._replace(z=z, f=f, b=b, blocked=blocked,
                          slot_speed=slot_speed, hop_next=hop_next,
                          completed=jnp.float32(0.0),
                          lat_sum=jnp.float32(0.0))


def _score(tables: PipelineTables, state: RuntimeState, arrived: jax.Array,
           weights: QoSWeights):
    """Score one env's measured interval telemetry with Eq. (3)/(7) — the
    second half of ``RuntimeEnv.step``. Returns (reward, metrics)."""
    w = weights
    z, f, b = state.z, state.f, state.b
    demand = arrived / ADAPTATION_INTERVAL
    T = state.completed / ADAPTATION_INTERVAL
    L = jnp.where(state.completed > 0,
                  state.lat_sum / jnp.maximum(state.completed, 1.0),
                  _analytic_latency(tables, z, f, b,
                                    jnp.maximum(demand, 1.0)))
    E = demand - T
    V = jnp.sum(_gather(tables.accuracy, z))
    C = jnp.sum(_gather(tables.cost, z) * f.astype(jnp.float32))
    qos = (w.alpha * V + w.beta * T - L
           - jnp.where(E >= 0, w.gamma * E, w.delta * (-E)))
    reward = qos - w.beta_c * C - w.gamma_b * jnp.max(b)
    if tables.n_nodes == 0:
        res = _gather(tables.resource, z)
        infeasible = jnp.sum(res * f.astype(jnp.float32)) > tables.w_max
    else:
        infeasible = _placement(tables, z, f).overflow > 0
    reward = reward - 50.0 * infeasible
    metrics = {"qos": qos, "cost": C, "latency": L, "throughput": T,
               "excess": E, "demand": demand,
               "completed": state.completed, "infeasible": infeasible,
               "queue_depths": state.q_len, "backlog": _backlog(state)}
    return reward, metrics


def interval_step(tables: PipelineTables, state: RuntimeState,
                  action: jax.Array, k: jax.Array, ep: EpisodeArrivals,
                  weights: QoSWeights, max_wait: jax.Array):
    """One adaptation interval of the closed loop across the env axis — the
    twin of ``RuntimeEnv.step``: decode + apply each env's configuration,
    advance the shared event loop one interval, score each env's *measured*
    telemetry. ``state``, ``action`` [E, 3N] and ``ep`` carry a leading env
    axis; ``k`` is the shared interval index. Returns (state', rewards [E],
    metrics)."""
    state = jax.vmap(partial(_apply_config, tables))(state, action)
    t1 = (k + 1).astype(jnp.float32) * ADAPTATION_INTERVAL
    state = _advance(tables, state, ep.times, t1, max_wait)
    reward, metrics = jax.vmap(
        lambda st, a: _score(tables, st, a, weights))(state, ep.arrived[:, k])
    return state, reward, metrics


def _backlog(state: RuntimeState) -> jax.Array:
    """Requests admitted but not yet fully served (queued, in cross-node
    transfer, or in flight) — the twin of ``ServingRuntime.in_system``.
    In-transfer batches already sit in their destination queue (stamped
    with a future delivery time), so q_len covers them."""
    in_fl = jnp.sum(jnp.where(state.fl_finish < INF, state.fl_size, 0))
    return (jnp.sum(state.q_len) + in_fl).astype(jnp.float32)


# ------------------------------------------------------------------ rollout --

def rollout(params, tables: PipelineTables, ep: EpisodeArrivals,
            key: jax.Array, *, n_steps: int, weights: QoSWeights,
            max_wait: float = DEFAULT_MAX_WAIT, greedy: bool = False):
    """One on-policy closed-loop episode on the runtime twin — a
    ``vec_rollout`` batch of one. Mirrors ``vecenv.rollout`` so
    ``OPDTrainer`` can swap engines."""
    eps = jax.tree.map(lambda x: x[None], ep)
    traj = vec_rollout(params, tables, eps, key[None], n_steps=n_steps,
                       weights=weights, max_wait=max_wait, greedy=greedy)
    return jax.tree.map(lambda x: x[0], traj)


# NaN + div only: checkify's OOB rule can't transform the batched
# dynamic_update_slice in the vmapped event loop
@sanitize.checked(errors=sanitize.NAN_DIV_ERRORS)
@partial(jax.jit, static_argnames=("n_steps", "weights", "max_wait",
                                   "greedy"))
def vec_rollout(params, tables: PipelineTables, eps: EpisodeArrivals,
                keys: jax.Array, *, n_steps: int, weights: QoSWeights,
                max_wait: float = DEFAULT_MAX_WAIT, greedy: bool = False):
    """Parallel closed-loop episodes via one ``lax.scan`` over the shared
    interval clock: sample each env's action, advance the batched event
    loop, collect PPO trajectories [E, T, ...]. Each env consumes only its
    own arrivals and key, so outputs are permutation-invariant along the
    env axis (the env dimension is explicit rather than vmapped so the
    event loop's while condition stays scalar — see ``_advance``)."""
    mw = jnp.float32(max_wait)
    state0 = jax.vmap(partial(init_state, tables))(eps)

    def obs_of(state, load):
        return jax.vmap(
            lambda z, f, b, l: observe_cfg(tables, z, f, b, l))(
                state.z, state.f, state.b, load)

    obs0 = obs_of(state0, eps.load_obs[:, 0])

    def one_step(carry, k):
        state, obs, kkeys = carry
        split = jax.vmap(jax.random.split)(kkeys)
        kkeys, subs = split[:, 0], split[:, 1]
        action, logp, value = jax.vmap(
            lambda o, s: sample_action(params, o, s, greedy=greedy))(
                obs, subs)
        state, r, metrics = interval_step(tables, state, action, k, eps,
                                          weights, mw)
        load = eps.load_obs[:, jnp.minimum(k + 1, n_steps - 1)]
        obs_next = obs_of(state, load)
        out = {"states": obs, "actions": action, "logps": logp,
               "rewards": r, "values": value, "qos": metrics["qos"],
               "completed": metrics["completed"]}
        return (state, obs_next, kkeys), out

    (_, obs_last, _), traj = jax.lax.scan(
        one_step, (state0, obs0, keys),
        jnp.arange(n_steps, dtype=jnp.int32))
    traj = jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1), traj)
    _, last_value = apply_policy(params, obs_last)
    traj["last_value"] = last_value
    return traj


# NaN + div only — same OOB-rule limitation as vec_rollout above
@sanitize.checked(errors=sanitize.NAN_DIV_ERRORS)
@partial(jax.jit, static_argnames=("n_steps", "weights", "max_wait"))
def replay(tables: PipelineTables, ep: EpisodeArrivals, actions: jax.Array,
           *, n_steps: int, weights: QoSWeights,
           max_wait: float = DEFAULT_MAX_WAIT):
    """Drive the twin with a fixed action sequence [T, 3N] (policy head
    indices) and return per-interval rewards + measured metrics — the
    equivalence-pinning hook ``tests/test_runtime_vec.py`` compares against
    ``RuntimeEnv`` stepping the same decisions."""
    mw = jnp.float32(max_wait)
    eps = jax.tree.map(lambda x: x[None], ep)
    state0 = jax.vmap(partial(init_state, tables))(eps)

    def one_step(state, ka):
        k, action = ka
        state, r, metrics = interval_step(tables, state, action[None], k,
                                          eps, weights, mw)
        return state, {"rewards": r, **metrics}

    _, out = jax.lax.scan(one_step, state0,
                          (jnp.arange(n_steps, dtype=jnp.int32), actions))
    return jax.tree.map(lambda x: x[:, 0], out)
