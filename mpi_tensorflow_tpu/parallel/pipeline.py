"""Pipeline parallelism (PP): GPipe-style microbatched stage pipeline.

The layer stack is split into P stages whose parameters live sharded over a
``pipe`` mesh axis (one stage per shard).  A batch is cut into M microbatches
that flow stage-to-stage through ``ppermute`` neighbor hops: at tick t, stage
s processes microbatch t-s while its neighbors work on adjacent microbatches
— the classic pipeline schedule with (P-1) bubble ticks around M useful ones.
The whole schedule is a ``lax.scan``, so reverse-mode autodiff derives the
backward pipeline automatically (the transpose of ``ppermute`` is the
reverse hop).

No counterpart in the reference (SURVEY.md §2 checklist: PP absent); part of
the full parallelism-strategy coverage.  Use ``pipeline`` inside
``shard_map`` with the ``pipe`` axis in scope — see ``make_pipelined_fn`` for
the jit-ready wrapper.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def pipeline(stage_fn: Callable, stage_params: Any, microbatches,
             axis: str = "pipe", with_mb_index: bool = False):
    """Run ``stage_fn(params, x) -> y`` as a P-stage pipeline.

    Inside ``shard_map``: ``stage_params`` is this shard's stage parameters,
    ``microbatches`` has shape (M, mb, ...) and must hold the SAME full set
    of microbatches on every shard (replicated over ``axis``); the result is
    the final stage's outputs, (M, mb, ...), valid on every shard.

    ``with_mb_index=True`` calls ``stage_fn(params, x, mb_idx)`` where
    ``mb_idx`` is the index of the microbatch this stage is processing at
    the current tick (clipped to [0, M-1] during bubble ticks, whose outputs
    are discarded anyway) — the hook stateful-per-microbatch ops (dropout
    rng folding) need to decorrelate microbatches.
    """
    n_stages = lax.axis_size(axis)
    stage_idx = lax.axis_index(axis)
    m = microbatches.shape[0]
    ticks = m + n_stages - 1
    out_dtype = microbatches.dtype
    perm_fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick_fn(carry, t):
        state, outputs = carry
        # stage 0 ingests microbatch t (if any); other stages use the
        # activation handed to them by the previous stage last tick
        feed_idx = jnp.clip(t, 0, m - 1)
        fed = jnp.where(stage_idx == 0,
                        microbatches[feed_idx].astype(state.dtype), state)
        if with_mb_index:
            # at tick t, stage s works on microbatch t-s (pipeline skew)
            y = stage_fn(stage_params, fed,
                         jnp.clip(t - stage_idx, 0, m - 1))
        else:
            y = stage_fn(stage_params, fed)
        # last stage emits microbatch t-(P-1) when it is valid
        out_idx = t - (n_stages - 1)
        valid = (stage_idx == n_stages - 1) & (out_idx >= 0)
        outputs = lax.cond(
            valid,
            lambda o: lax.dynamic_update_index_in_dim(
                o, y.astype(out_dtype), jnp.clip(out_idx, 0, m - 1), 0),
            lambda o: o,
            outputs)
        # hand activations to the next stage
        state = lax.ppermute(y, axis, perm_fwd)
        return (state, outputs), None

    state0 = jnp.zeros(microbatches.shape[1:], microbatches.dtype)
    state0 = state0 + jnp.sum(microbatches[:1]) * 0   # inherit varying axes
    outputs0 = jnp.zeros_like(microbatches)
    (_, outputs), _ = lax.scan(tick_fn, (state0, outputs0),
                               jnp.arange(ticks))
    # every shard returns the outputs; only the last stage's copy is real —
    # broadcast it so the result is replicated over the pipe axis
    src = n_stages - 1
    outputs = lax.psum(
        jnp.where(stage_idx == src, outputs, jnp.zeros_like(outputs)), axis)
    return outputs


# ---------------------------------------------------------------------------
# interleaved 1F1B
# ---------------------------------------------------------------------------
#
# Slot algebra (P stages, M microbatches, one op per stage per tick):
#
#   forward  of microbatch i at stage s:  tick  s + 2i
#   backward of microbatch i at stage s:  tick  2P-1-s + 2i
#
# Checks: F and B land on disjoint tick parities per stage (never collide);
# a message produced at tick t is consumed by the neighbor at t+1 (one
# ppermute per tick each way); the last tick is 2M+2P-3, so the schedule is
# T = 2(M+P-1) ticks with exactly 2(P-1) idle ticks per stage — idle
# fraction (P-1)/(M+P-1), the 1F1B bubble (pinned by
# tests/test_pipeline_1f1b.py::TestOneFOneB::test_bubble_accounting).
# Microbatch i's input activation is stashed from its F tick to its B tick;
# at stage s that window holds at most P-s microbatches, so a P-slot ring
# buffer (indexed i mod P) suffices — O(P) activation memory, the whole
# point of 1F1B over end-to-end GPipe's O(M).
#
# The backward recomputes the stage forward from the stashed INPUT via
# jax.vjp at the B tick (activation recompute, the standard large-model
# setting) — VJP closures cannot live in a scan carry.  Gradients are
# accumulated in the carry and the function returns them directly
# (value-and-grad style); callers wrap it in jax.custom_vjp to splice the
# manual grads into an outer autodiff (models/bert_pipeline.py).

def schedule_table(n_stages: int, num_microbatches: int) -> list:
    """The 1F1B slot table as plain data — SAME predicate arithmetic as
    ``pipeline_1f1b``'s tick_fn, in python ints, so tests can pin the
    schedule's structural claims (bubble fraction, O(P) stash occupancy,
    neighbor-message timing) without tracing.  Returns
    ``table[t][s] = ("F"|"B", mb_index) | None``."""
    n, m = n_stages, num_microbatches
    ticks = 2 * (m + n - 1)
    table = []
    for t in range(ticks):
        row = []
        for s in range(n):
            f_num = t - s
            b_num = t - (2 * n - 1 - s)
            op = None
            if f_num >= 0 and f_num % 2 == 0 and f_num // 2 < m:
                op = ("F", f_num // 2)
            if b_num >= 0 and b_num % 2 == 0 and b_num // 2 < m:
                assert op is None, "F/B collision — parity argument broken"
                op = ("B", b_num // 2)
            row.append(op)
        table.append(row)
    return table


def interleaved_ring_depth(n_stages: int, num_microbatches: int) -> int:
    """Per-chunk ring-buffer depth for the interleaved schedule: 2P
    slots reach the Megatron-ideal bubble (P-deep rings throttle the
    warmup back to the plain-1F1B bubble); M slots suffice when the
    stream is shorter than that."""
    return max(1, min(2 * n_stages, num_microbatches))


def interleaved_table(n_stages: int, v: int, num_microbatches: int) -> list:
    """Interleaved-1F1B schedule: ``v`` virtual stage chunks per device.

    Chunk ``k`` (of ``V = v * n_stages``) lives on device ``k % P`` with
    local index ``j = k // P``; every forward message rides the +1 ring
    hop, every backward the -1 hop — same neighbor topology as plain
    1F1B, just more chunks.  Built by dependency-driven greedy list
    scheduling (backward-first, then earliest (mb, chunk)), honoring:

    - message latency 1 tick (consume at >= produce + 1);
    - one op per device per tick;
    - Q-slot ring buffers per chunk for the stash and the in-flight
      messages: F(k, i) needs B(k, i-Q) done (stash slot ``i % Q`` free)
      and F(k+1, i-Q) done (the consumer's input slot free); mirrored
      for backward cotangents.

    Returns ``table[t][d] = ("F"|"B", chunk_local_j, mb_index) | None``.
    Achieves the Megatron-ideal schedule length ``2vM + 2(P-1)`` ticks —
    bubble ``(P-1)/(vM+P-1)``, ~v-fold below plain 1F1B (pinned by
    tests).  The price is the deeper ring: ``Q = min(2P, M)`` slots per
    chunk (``interleaved_ring_depth``) instead of plain 1F1B's P —
    Megatron's warmup keeps up to ``2(P-1) + (v-1)P + 1`` chunk-ops in
    flight per device, more than P-deep rings can hold (a P-deep ring
    caps the schedule at the PLAIN bubble; measured while building
    this) — and v x the ring messages.
    """
    P_, M, V = n_stages, num_microbatches, v * n_stages
    Q = interleaved_ring_depth(n_stages, num_microbatches)
    tick_f: dict = {}
    tick_b: dict = {}

    def done_before(d_, key, t):
        """op done strictly before tick t (message latency)."""
        return key in d_ and d_[key] < t

    def done_by(d_, key, t):
        """op done at or before tick t (slot freed; same-tick is safe —
        reads happen during the owner's tick, overwrites at a later
        one, and two ops never share a device-tick)."""
        return key in d_ and d_[key] <= t

    def b_ready(k, i, t):
        if (k, i) in tick_b or not done_before(tick_f, (k, i), t):
            return False
        if k < V - 1 and not done_before(tick_b, (k + 1, i), t):
            return False
        # this B's cotangent message lands in chunk k-1's ring slot
        # (i % Q): the previous occupant must have been consumed
        if k > 0 and i >= Q and \
                not done_by(tick_b, (k - 1, i - Q), t):
            return False
        return True

    def f_ready(k, i, t):
        if (k, i) in tick_f:
            return False
        if k > 0 and not done_before(tick_f, (k - 1, i), t):
            return False
        # stash ring slot (i % Q) free: B of the slot's prior tenant done
        if i >= Q and not done_by(tick_b, (k, i - Q), t):
            return False
        # this F's output message lands in chunk k+1's ring slot (i % Q):
        # its previous occupant must have been consumed
        if k < V - 1 and i >= Q and \
                not done_by(tick_f, (k + 1, i - Q), t):
            return False
        return True

    # Megatron-style fixed op order per device: microbatches advance in
    # GROUPS of P per chunk (breadth-first over the group, then the next
    # chunk) — depth-first (push one mb through all chunks) stalls on the
    # cross-device round-trip and yields a WORSE bubble than plain 1F1B.
    # B order mirrors F with chunks reversed (B(k) depends on B(k+1)).
    def f_order(d):
        for g0 in range(0, M, P_):
            group = range(g0, min(g0 + P_, M))
            for j in range(v):
                for i in group:
                    yield (j * P_ + d, i)

    def b_order(d):
        for g0 in range(0, M, P_):
            group = range(g0, min(g0 + P_, M))
            for j in reversed(range(v)):
                for i in group:
                    yield (j * P_ + d, i)

    f_seq = [list(f_order(d)) for d in range(P_)]
    b_seq = [list(b_order(d)) for d in range(P_)]
    f_ptr = [0] * P_
    b_ptr = [0] * P_
    # Megatron's warmup depth: 2(P-d-1) + (v-1)P forward chunk-ops before
    # the first backward; steady state then holds in-flight constant
    # (strict one-F-one-B), cooldown drains.  Encoded as a preference on
    # in-flight count, work-conserving (falls back to the other op kind
    # rather than idling when the preferred one is not ready).
    target = [min(2 * (P_ - d - 1) + (v - 1) * P_ + 1, v * M)
              for d in range(P_)]
    table: list = []
    t = 0
    while len(tick_b) < V * M:
        row: list = [None] * P_
        for d in range(P_):
            f_ok = (f_ptr[d] < len(f_seq[d])
                    and f_ready(*f_seq[d][f_ptr[d]], t))
            b_ok = (b_ptr[d] < len(b_seq[d])
                    and b_ready(*b_seq[d][b_ptr[d]], t))
            in_flight = f_ptr[d] - b_ptr[d]
            pick_b = b_ok and (in_flight >= target[d] or not f_ok)
            if pick_b:
                k, i = b_seq[d][b_ptr[d]]
                b_ptr[d] += 1
                row[d] = ("B", k // P_, i)
                tick_b[(k, i)] = t
            elif f_ok:
                k, i = f_seq[d][f_ptr[d]]
                f_ptr[d] += 1
                row[d] = ("F", k // P_, i)
                tick_f[(k, i)] = t
        table.append(row)
        t += 1
        assert t <= 8 * V * (M + P_), "interleaved scheduler wedged"
    return table


def schedule_cost(n_stages: int, num_microbatches: int,
                  uniform_stages: bool) -> dict:
    """Tick-level stage-body accounting for one ``pipeline_1f1b`` pass —
    the measured truth of what ``uniform_stages`` costs (VERDICT r4 #4).

    Counts per device, in stage-body runs (the backward's recompute
    replay counts as one forward body; its vjp backward as two — the
    standard 1:3 fwd:bwd flop ratio):

    - gated (``uniform_stages=False``, collective-free meshes only):
      exactly M forward ops and M backward ops execute — the lax.cond
      skips bubble ticks.  Useful work only.
    - uniform (required whenever stage bodies or the head carry
      collectives): the forward body AND the backward replay+vjp run
      every tick — ``2*(M+P-1)`` times each — because collectives may
      not sit under a slot-gated cond.  Total body-equivalents are
      ``2*(M+P-1)/M`` times the useful work: ~2x GPipe's unconditional
      scan even at P=1, shrinking toward 2x as M >> P.

    The uniform schedule buys the O(P) activation stash (vs GPipe's
    O(M)) at that compute price; ``schedule="1f1b"`` on a
    collective-free mesh keeps the gated fast path and pays nothing.
    """
    m, p = num_microbatches, n_stages
    ticks = 2 * (m + p - 1)
    if uniform_stages:
        f_runs = b_runs = ticks
    else:
        f_runs = b_runs = m
    useful = 4 * m               # M forward (1) + M backward (3)
    total = f_runs + 3 * b_runs
    return {"ticks": ticks, "fwd_body_runs": f_runs,
            "bwd_body_runs": b_runs, "useful_body_equiv": useful,
            "total_body_equiv": total,
            "overhead_ratio": total / useful,
            "bubble_fraction": (p - 1) / (m + p - 1)}


def _bwd_core(stage_call: Callable, stage_p: Any, last_fn: Callable,
              last_params: Any, aux_i: Any, x, incoming_dy, is_last,
              gate, uniform: bool):
    """The backward op shared by both 1F1B executors: replay the stage
    from its stashed input, seed the output cotangent from the head
    (last stage/chunk) or the incoming message, and differentiate.

    ``stage_call(params, x) -> y`` is the stage body closed over
    everything but its differentiable inputs.  Under ``uniform`` the
    head math runs unconditionally and is masked by ``gate & is_last``
    (collectives may not sit under the rank-varying cond — see
    ``pipeline_1f1b``); the gated path keeps the ``lax.cond`` and is
    valid for collective-free stages/heads only.

    Returns ``(dsp, dx, dlp_add, li_add)``: raw stage-param and input
    cotangents (caller masks/accumulates — the two executors index
    their grads differently) plus ready-masked head-grad and loss
    addends."""
    yb, vjp_fn = jax.vjp(stage_call, stage_p, x)

    def head_math(yb):
        li, last_vjp = jax.vjp(
            lambda lp, yy: last_fn(lp, yy, aux_i), last_params, yb)
        dlp, dy = last_vjp(jnp.ones((), li.dtype))
        return li, dlp, dy

    if uniform:
        li, dlp, dy_head = head_math(yb)
        on_last = gate & is_last
        dlp_add = jax.tree.map(
            lambda d: jnp.where(on_last, d, jnp.zeros_like(d)), dlp)
        li_add = jnp.where(on_last, li, 0.0).astype(jnp.float32)
        dy = jnp.where(is_last, dy_head,
                       incoming_dy.astype(dy_head.dtype))
    else:
        def last_stage(yb):
            li, dlp, dy = head_math(yb)
            # f32 to match mid_stage's zero (cond branch types must
            # agree even for a low-precision last_fn)
            return (dy,
                    jax.tree.map(
                        lambda d: jnp.where(gate, d, jnp.zeros_like(d)),
                        dlp),
                    jnp.where(gate, li, 0.0).astype(jnp.float32))

        def mid_stage(yb):
            return (incoming_dy.astype(yb.dtype),
                    jax.tree.map(jnp.zeros_like, last_params),
                    jnp.zeros((), jnp.float32))

        dy, dlp_add, li_add = lax.cond(is_last, last_stage, mid_stage, yb)
    dsp, dx = vjp_fn(dy)
    return dsp, dx, dlp_add, li_add


def pipeline_1f1b(stage_fn: Callable, last_fn: Callable, stage_params: Any,
                  last_params: Any, microbatches, mb_aux: Any,
                  axis: str = "pipe", *, uniform_stages: bool = True):
    """Interleaved one-forward-one-backward pipeline schedule.

    Inside ``shard_map`` with ``axis`` in scope.  Per pipe shard:

    - ``stage_fn(sp, x, mb_idx) -> y``: this shard's stage.
    - ``last_fn(lp, y, aux_i) -> scalar``: microbatch i's loss contribution
      (already globally normalized so contributions SUM to the loss);
      evaluated only on the last stage's shard.
    - ``stage_params``: this shard's stage parameters.
    - ``last_params``: head/loss parameters — replicated over ``axis``;
      they MAY be sharded over other mesh axes (e.g. a vocab-parallel
      decoder over ``model``), in which case ``last_fn`` owns the
      cross-shard collectives and the caller owns the partial-cotangent
      reductions on the returned grads (see bert_pipeline's
      ``_reduce_partials``).
    - ``microbatches``: (M, mb, ...) — the SAME full stream on every pipe
      shard.  ``mb_aux``: pytree with leading M axis (labels/masks/...).
    - ``uniform_stages``: MUST be True whenever ``stage_fn`` contains
      collectives over mesh axes other than ``axis`` (ring attention's
      ppermute over 'seq', TP psums over 'model'): those collectives'
      groups span devices whose slot predicates agree, but placing them
      under a pipe-rank-dependent ``lax.cond`` is unsound regardless — a
      minimal repro crashes XLA:CPU's thunk executor, and the full model
      silently computed a wrong seq-sharded forward.  True runs the
      stage body and its vjp unconditionally every tick and masks the
      results (GPipe's scan always worked this way).  False keeps the
      slot-gated ``lax.cond`` fast path — valid ONLY for collective-free
      stages (plain pipe x data), where it skips the bubble-tick
      compute.

    Returns ``(loss, d_stage_params, d_last_params, d_microbatches)`` —
    loss/d_last/d_micro are summed over ``axis`` (zeros contributed by
    non-owning stages), d_stage_params is this shard's own stage grads.
    """
    n = lax.axis_size(axis)
    s_idx = lax.axis_index(axis)
    m = microbatches.shape[0]
    ticks = 2 * (m + n - 1)
    x_shape = microbatches.shape[1:]
    f32 = jnp.float32

    def tick_fn(carry, t):
        fwd_msg, bwd_msg, stash, gs, gl, loss, dx_out = carry
        # forward: stage s OWNS microbatch (t-s)/2 when parity/range fit.
        # Under ``uniform_stages`` the stage body runs UNCONDITIONALLY
        # every tick and its result is masked by f_on: the stage may
        # contain collectives (ring attention's ppermute over 'seq', TP
        # psums over 'model') and a lax.cond on the pipe-dependent slot
        # predicate would put them under control flow — UNSOUND (the
        # minimal repro crashes XLA:CPU's thunk executor; the full model
        # silently corrupted the seq-sharded forward).  GPipe's
        # pipeline() already runs stages unconditionally; the gated
        # fast path below remains for collective-free stages only.
        f_num = t - s_idx
        i_f = jnp.clip(f_num // 2, 0, m - 1)
        f_on = (f_num >= 0) & (f_num % 2 == 0) & (f_num // 2 < m)
        x_in = jnp.where(s_idx == 0,
                         microbatches[i_f].astype(fwd_msg.dtype), fwd_msg)
        if uniform_stages:
            y_all = stage_fn(stage_params, x_in, i_f)
            y = jnp.where(f_on, y_all, jnp.zeros(x_shape, y_all.dtype))
        else:
            y = lax.cond(
                f_on,
                lambda xx: stage_fn(stage_params, xx, i_f),
                lambda xx: jnp.zeros(x_shape, fwd_msg.dtype), x_in)
        # carry updates hold NO collectives — always safely slot-gated
        stash = lax.cond(
            f_on,
            lambda s: lax.dynamic_update_index_in_dim(s, x_in, i_f % n, 0),
            lambda s: s, stash)

        # backward: stage s owns microbatch (t-(2n-1-s))/2.  Same rule:
        # under uniform_stages the stage replay (and its vjp — reverse
        # ppermute hops) runs unconditionally; only the ACCUMULATIONS
        # are masked by b_on.
        b_num = t - (2 * n - 1 - s_idx)
        i_b = jnp.clip(b_num // 2, 0, m - 1)
        b_on = (b_num >= 0) & (b_num % 2 == 0) & (b_num // 2 < m)

        def bwd_math(c):
            """The shared backward body (``_bwd_core``): stage replay +
            head-or-message cotangent + vjp.  The head math runs
            unconditionally on the uniform path — ``last_fn`` may carry
            collectives over OTHER mesh axes (vocab-parallel CE's psum
            over 'model') and the ``s_idx == n-1`` predicate varies
            across pipe ranks, the same unsound pattern the uniform path
            exists to avoid.  Accumulations masked by ``gate`` (constant
            True on the gated path — the cond already gates)."""
            bwd_msg, stash, gs, gl, loss, dx_out, gate = c
            x = stash[i_b % n]
            aux_i = jax.tree.map(lambda a: a[i_b], mb_aux)
            dsp, dx, dlp_add, li_add = _bwd_core(
                lambda sp, xx: stage_fn(sp, xx, i_b), stage_params,
                last_fn, last_params, aux_i, x, bwd_msg,
                s_idx == n - 1, gate, uniform_stages)
            gl = jax.tree.map(jnp.add, gl, dlp_add)
            loss = loss + li_add
            gs = jax.tree.map(
                lambda g, d: g + jnp.where(gate, d, jnp.zeros_like(d)),
                gs, dsp)
            # only stage 0's input cotangents are the embedding stream's
            dx_out = lax.cond(
                gate & (s_idx == 0),
                lambda d: lax.dynamic_update_index_in_dim(
                    d, dx.astype(f32), i_b, 0),
                lambda d: d, dx_out)
            dx_send = jnp.where(gate, dx.astype(fwd_msg.dtype),
                                jnp.zeros(x_shape, fwd_msg.dtype))
            return dx_send, stash, gs, gl, loss, dx_out

        if uniform_stages:
            dx_send, stash, gs, gl, loss, dx_out = bwd_math(
                (bwd_msg, stash, gs, gl, loss, dx_out, b_on))
        else:
            dx_send, stash, gs, gl, loss, dx_out = lax.cond(
                b_on,
                lambda c: bwd_math(c),
                lambda c: (jnp.zeros(x_shape, fwd_msg.dtype),) + c[1:6],
                (bwd_msg, stash, gs, gl, loss, dx_out, jnp.bool_(True)))

        perm_f = [(j, (j + 1) % n) for j in range(n)]
        perm_b = [(j, (j - 1) % n) for j in range(n)]
        fwd_msg = lax.ppermute(y, axis, perm_f)
        bwd_msg = lax.ppermute(dx_send, axis, perm_b)
        return (fwd_msg, bwd_msg, stash, gs, gl, loss, dx_out), None

    zero_like_local = lambda tree: jax.tree.map(
        lambda x: jnp.zeros(jnp.shape(x), f32), tree)
    # seed the messages/stash from the stream so they inherit its
    # varying-axes type under shard_map's type checks
    seed = jnp.sum(microbatches[:1]) * 0
    init = (
        jnp.zeros(x_shape, microbatches.dtype) + seed,
        jnp.zeros(x_shape, microbatches.dtype) + seed,
        jnp.zeros((n,) + x_shape, microbatches.dtype) + seed,
        zero_like_local(stage_params),
        zero_like_local(last_params),
        jnp.zeros((), f32),
        jnp.zeros((m,) + x_shape, f32) + seed,
    )
    (_, _, _, gs, gl, loss, dx_out), _ = lax.scan(
        tick_fn, init, jnp.arange(ticks))
    # loss/gl/dx_out live on one stage each (zeros elsewhere): sum the ring
    loss = lax.psum(loss, axis)
    gl = jax.tree.map(lambda x: lax.psum(x, axis), gl)
    dx_out = lax.psum(dx_out, axis)
    return loss, gs, gl, dx_out


def pipeline_1f1b_interleaved(stage_fn: Callable, last_fn: Callable,
                              chunk_params: Any, last_params: Any,
                              microbatches, mb_aux: Any,
                              axis: str = "pipe", *, v: int,
                              n_stages: int,
                              uniform_stages: bool = True):
    """Interleaved 1F1B: ``v`` virtual stage chunks per device.

    Same contract as ``pipeline_1f1b`` except ``chunk_params`` carries a
    leading ``(v, ...)`` axis — this device's chunks, where local chunk
    ``j`` is GLOBAL chunk ``k = j * P + device`` (chunks ascend round-
    robin so every hop is the +1 ring neighbor) — and ``stage_fn(cp, x,
    mb_idx, chunk_k)`` receives the global chunk index for layer-offset
    bookkeeping (dropout fold-ins).

    Executes the static ``interleaved_table`` schedule inside one
    ``lax.scan``: per tick each device runs its scheduled op (F body, or
    B replay+vjp, or idle), reads/writes Q-slot ring buffers
    (``interleaved_ring_depth``) for the stash and the in-flight
    messages, and exchanges one fwd (+1) and one bwd (-1) ppermute.
    Bubble = (P-1)/(vM+P-1), ~v-fold below plain 1F1B; activation
    memory is 3*v*Q microbatch slots (stash + two message rings) vs
    plain's ~P — the classic interleaving trade plus this executor's
    separate-buffer simplicity.

    ``uniform_stages`` as in ``pipeline_1f1b``: True runs both bodies
    every tick and masks (required for collectives inside stages /
    head); False slot-gates with ``lax.cond`` (collective-free only).

    Returns ``(loss, d_chunk_params, d_last_params, d_microbatches)``.
    """
    import numpy as np

    P_ = n_stages
    s_idx = lax.axis_index(axis)
    M = microbatches.shape[0]
    V = v * P_
    Q = interleaved_ring_depth(P_, M)
    x_shape = microbatches.shape[1:]
    f32 = jnp.float32

    # ---- bake the static schedule as per-(tick, device) index tables
    table = interleaved_table(P_, v, M)
    T = len(table)
    kind = np.zeros((T, P_), np.int32)          # 0 idle / 1 F / 2 B
    jj = np.zeros((T, P_), np.int32)
    ii = np.zeros((T, P_), np.int32)
    for t, row in enumerate(table):
        for d, op in enumerate(row):
            if op is None:
                continue
            kind[t, d] = 1 if op[0] == "F" else 2
            jj[t, d] = op[1]
            ii[t, d] = op[2]
    # arrival routing: a message in the carry at tick t was produced at
    # t-1.  fwd from device d-1 (k -> k+1), bwd from device d+1 (k -> k-1).
    fs_on = np.zeros((T, P_), bool)
    fs_j = np.zeros((T, P_), np.int32)
    fs_slot = np.zeros((T, P_), np.int32)
    bs_on = np.zeros((T, P_), bool)
    bs_j = np.zeros((T, P_), np.int32)
    bs_slot = np.zeros((T, P_), np.int32)
    for t in range(1, T):
        for d in range(P_):
            src = table[t - 1][(d - 1) % P_]
            if src is not None and src[0] == "F":
                k = src[1] * P_ + (d - 1) % P_
                if k < V - 1:
                    fs_on[t, d] = True
                    fs_j[t, d] = (k + 1) // P_
                    fs_slot[t, d] = src[2] % Q
            src = table[t - 1][(d + 1) % P_]
            if src is not None and src[0] == "B":
                k = src[1] * P_ + (d + 1) % P_
                if k > 0:
                    bs_on[t, d] = True
                    bs_j[t, d] = (k - 1) // P_
                    bs_slot[t, d] = src[2] % Q
    as_const = jnp.asarray
    KIND, JJ, II = as_const(kind), as_const(jj), as_const(ii)
    FS_ON, FS_J, FS_SLOT = as_const(fs_on), as_const(fs_j), as_const(fs_slot)
    BS_ON, BS_J, BS_SLOT = as_const(bs_on), as_const(bs_j), as_const(bs_slot)

    sel_chunk = lambda tree, j: jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, j, 0, keepdims=False), tree)

    def tick_fn(carry, t):
        (fwd_msg, bwd_msg, fwd_buf, bwd_buf, stash,
         gs, gl, loss, dx_out) = carry
        knd = KIND[t, s_idx]
        j = JJ[t, s_idx]
        i = II[t, s_idx]
        k_glob = j * P_ + s_idx
        slot = i % Q

        # ---- store arrivals (carry messages were produced last tick)
        fwd_buf = lax.cond(
            FS_ON[t, s_idx],
            lambda b: b.at[FS_J[t, s_idx], FS_SLOT[t, s_idx]].set(fwd_msg),
            lambda b: b, fwd_buf)
        bwd_buf = lax.cond(
            BS_ON[t, s_idx],
            lambda b: b.at[BS_J[t, s_idx], BS_SLOT[t, s_idx]].set(bwd_msg),
            lambda b: b, bwd_buf)

        f_on = knd == 1
        b_on = knd == 2
        from_stream = (k_glob == 0) & f_on
        x_in = jnp.where(from_stream,
                         microbatches[jnp.clip(i, 0, M - 1)]
                         .astype(fwd_buf.dtype),
                         fwd_buf[j, slot])
        cp_f = sel_chunk(chunk_params, j)
        if uniform_stages:
            y_all = stage_fn(cp_f, x_in, i, k_glob)
            y = jnp.where(f_on, y_all, jnp.zeros(x_shape, y_all.dtype))
        else:
            y = lax.cond(
                f_on,
                lambda xx: stage_fn(cp_f, xx, i, k_glob),
                lambda xx: jnp.zeros(x_shape, fwd_buf.dtype), x_in)
        stash = lax.cond(
            f_on,
            lambda s: s.at[j, slot].set(x_in),
            lambda s: s, stash)

        def bwd_math(c):
            bwd_buf, stash, gs, gl, loss, dx_out, gate = c
            x = stash[j, slot]
            cp_b = sel_chunk(chunk_params, j)
            aux_i = jax.tree.map(lambda a: a[jnp.clip(i, 0, M - 1)],
                                 mb_aux)
            dcp, dx, dlp_add, li_add = _bwd_core(
                lambda cp, xx: stage_fn(cp, xx, i, k_glob), cp_b,
                last_fn, last_params, aux_i, x, bwd_buf[j, slot],
                k_glob == V - 1, gate, uniform_stages)
            gl = jax.tree.map(jnp.add, gl, dlp_add)
            loss = loss + li_add
            gs = jax.tree.map(
                lambda g, d: g.at[j].add(
                    jnp.where(gate, d, jnp.zeros_like(d))), gs, dcp)
            dx_out = lax.cond(
                gate & (k_glob == 0),
                lambda o: lax.dynamic_update_index_in_dim(
                    o, dx.astype(f32), jnp.clip(i, 0, M - 1), 0),
                lambda o: o, dx_out)
            dx_send = jnp.where(gate, dx.astype(fwd_msg.dtype),
                                jnp.zeros(x_shape, fwd_msg.dtype))
            return dx_send, stash, gs, gl, loss, dx_out

        if uniform_stages:
            dx_send, stash, gs, gl, loss, dx_out = bwd_math(
                (bwd_buf, stash, gs, gl, loss, dx_out, b_on))
        else:
            dx_send, stash, gs, gl, loss, dx_out = lax.cond(
                b_on,
                lambda c: bwd_math(c),
                lambda c: (jnp.zeros(x_shape, fwd_msg.dtype),) + c[1:6],
                (bwd_buf, stash, gs, gl, loss, dx_out, jnp.bool_(True)))

        perm_f = [(q, (q + 1) % P_) for q in range(P_)]
        perm_b = [(q, (q - 1) % P_) for q in range(P_)]
        fwd_msg = lax.ppermute(
            jnp.where(f_on, y, jnp.zeros(x_shape, y.dtype)), axis, perm_f)
        bwd_msg = lax.ppermute(dx_send, axis, perm_b)
        return (fwd_msg, bwd_msg, fwd_buf, bwd_buf, stash,
                gs, gl, loss, dx_out), None

    zero_like_local = lambda tree: jax.tree.map(
        lambda x: jnp.zeros(jnp.shape(x), f32), tree)
    seed = jnp.sum(microbatches[:1]) * 0
    mdt = microbatches.dtype
    init = (
        jnp.zeros(x_shape, mdt) + seed,
        jnp.zeros(x_shape, mdt) + seed,
        jnp.zeros((v, Q) + x_shape, mdt) + seed,
        jnp.zeros((v, Q) + x_shape, mdt) + seed,
        jnp.zeros((v, Q) + x_shape, mdt) + seed,
        zero_like_local(chunk_params),
        zero_like_local(last_params),
        jnp.zeros((), f32),
        jnp.zeros((M,) + x_shape, f32) + seed,
    )
    (_, _, _, _, _, gs, gl, loss, dx_out), _ = lax.scan(
        tick_fn, init, jnp.arange(T))
    loss = lax.psum(loss, axis)
    gl = jax.tree.map(lambda x: lax.psum(x, axis), gl)
    dx_out = lax.psum(dx_out, axis)
    return loss, gs, gl, dx_out


def make_pipelined_fn(stage_fn: Callable, mesh: Mesh,
                      num_microbatches: int, axis: str = "pipe"):
    """jit-ready wrapper: ``f(stacked_params, batch) -> out``.

    ``stacked_params``: pytree with a leading stage dimension (length = pipe
    axis size), placed sharded over ``axis``.  ``batch``: (N, ...) global
    batch, replicated; it is cut into ``num_microbatches`` equal slices.
    """
    def fn(stacked_params, batch):
        def inner(stacked_params, batch):
            params = jax.tree.map(lambda x: x[0], stacked_params)
            mb = batch.reshape((num_microbatches,
                                batch.shape[0] // num_microbatches)
                               + batch.shape[1:])
            out = pipeline(stage_fn, params, mb, axis)
            return out.reshape(batch.shape[0], *out.shape[2:])

        return jax.shard_map(
            inner, mesh=mesh,
            in_specs=(P(axis), P()),
            out_specs=P(),
            check_vma=False,
        )(stacked_params, batch)

    return fn
