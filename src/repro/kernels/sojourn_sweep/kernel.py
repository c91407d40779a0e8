"""jnp cell recursion shared by the vmap and Pallas sweep backends.

:func:`cell_recursion` is the scan formulation documented in
:mod:`repro.kernels.sojourn_sweep.ref`, written against ``jax.numpy`` so
the *same* function body runs (a) jit+vmap'd over the cell/policy axes —
the ``jax`` backend, which is also the ``shard_map`` unit — and (b) as
the body of a ``pl.pallas_call`` over a ``(cells, policies)`` grid — the
``pallas`` backend.  Sharing the body is what makes jax↔pallas parity
structural rather than coincidental.

The body is written in the form the TPU's Mosaic compiler lowers: group
state lives in ``(1, G)`` lane vectors, every "element ``g`` of a vector"
read is a masked reduction over a 2-D iota and every update a masked
select, and the only dynamic indexing is a row read through the caller's
accessors (a ``pl.ds`` ref slice in the kernel, a dynamic slice in the
vmap path).  Per-program scalars (policy kind, threshold, group count,
arrival times, hedge bits) sit in SMEM.  The kernels compile for TPU;
``interpret`` defaults to the platform (interpreter on CPU only, see
:func:`repro.kernels.platform.resolve_interpret`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform import resolve_interpret
from .ref import KIND_CLONE, KIND_HEDGED, KIND_NONE, KIND_RELAUNCH  # noqa: F401

_INT_MAX = 2**31 - 1
_INT_MIN = -(2**31)


def cell_recursion(arrival, svc_row, alt_row, hedge, n_jobs, n_g, dtype,
                   kind, threshold, n_groups, resolve=True):
    """Sojourn recursion for one (dist, B, policy) cell, scan-formulated.

    Same contract as :func:`repro.kernels.sojourn_sweep.ref.sojourn_cell_reference`,
    with the cell's inputs read through accessors so one body serves both
    backends: ``arrival(i)`` the arrival time of job ``i``, ``svc_row(i)``
    / ``alt_row(i)`` its ``(1, G)`` primary / redundant draw rows,
    ``hedge(i)`` its hedge bit.  ``kind``/``threshold``/``n_groups`` are
    traced scalars.  Returns ``(out (1, J), extra int32)``.

    ``resolve`` is a STATIC flag: pass ``False`` only when no lane in the
    dispatch can ever arm a trigger (every policy is none/hedged, or every
    threshold is inf).  In that case the event-resolution pass is an
    identity — ``trig`` stays inf so ``_resolve_body`` computes ``do ==
    False`` on its first evaluation and mutates nothing — and skipping it
    at trace time halves the per-job work without changing a single bit.
    """
    inf = jnp.asarray(jnp.inf, dtype)
    gidx = lax.broadcasted_iota(jnp.int32, (1, n_g), 1)
    jidx = lax.broadcasted_iota(jnp.int32, (1, n_jobs), 1)
    valid = gidx < n_groups
    threshold = jnp.asarray(threshold, dtype)
    is_clone = kind == KIND_CLONE

    def at(vec, g):
        # vec[g] as a masked reduction: exact, since one lane survives
        return jnp.max(jnp.where(gidx == g, vec, -inf))

    def at_int(vec, g):
        return jnp.max(jnp.where(gidx == g, vec, _INT_MIN))

    def argmin(vec):
        # lowest index among ties, as jnp.argmin
        return jnp.min(jnp.where(vec == jnp.min(vec), gidx, n_g))

    def row_of(jid):
        # jid is INT_MAX for a group that never held a job; such reads are
        # discarded (``do`` is False) but must stay inside the block
        return jnp.clip(jid, 0, n_jobs - 1)

    def _effs(free, doneg, trig):
        m = jnp.min(jnp.where(valid, free, inf))
        armed = trig < inf

        def jcond(t):
            return jnp.any(armed & (t < doneg) & (t < m))

        def jbody(t):
            return jnp.where(armed & (t < doneg) & (t < m), t + threshold, t)

        jumped = lax.while_loop(jcond, jbody, trig)
        # A primary departing before its trigger caps the group's next
        # event at the depart (finalize + disarm), mirroring heap order.
        eff = jnp.minimum(jnp.where(is_clone, jumped, trig), doneg)
        eff = jnp.where(armed, eff, inf)
        return eff, m

    def _resolve_body(state):
        free, doneg, trig, jobid, out, extra, _, limit = state
        eff, m = _effs(free, doneg, trig)
        t_min = jnp.min(eff)
        g = argmin(jnp.where(eff == t_min, jobid, _INT_MAX))
        t = at(eff, g)
        jid = at_int(jobid, g)
        d = at(doneg, g)
        disarm = t >= d
        start = jnp.maximum(limit, m)
        # t_min == inf means nothing is armed (guards the drain, where
        # limit == inf would otherwise satisfy the disarm clause forever).
        do = (t_min < start) | ((t_min <= start) & disarm & (t_min < inf))
        idle = valid & (free <= t)
        h = argmin(jnp.where(idle, free, inf))
        alt_j = alt_row(row_of(jid))
        done_fire = jnp.where(is_clone,
                              jnp.minimum(d, t + at(alt_j, h)),
                              t + at(alt_j, g))
        done_new = jnp.where(disarm, d, done_fire)
        clone_set = do & ~disarm & is_clone
        at_g = gidx == g
        free_n = jnp.where(at_g, done_new, free)
        free_n = jnp.where(clone_set & (gidx == h), done_new, free_n)
        free_n = jnp.where(do, free_n, free)
        doneg_n = jnp.where(do & at_g, done_new, doneg)
        trig_n = jnp.where(do & at_g, inf, trig)
        sojourn = done_new - arrival(row_of(jid))
        out_n = jnp.where(do & (jidx == jid), sojourn, out)
        extra_n = extra + jnp.where(do & ~disarm, 1, 0).astype(jnp.int32)
        return free_n, doneg_n, trig_n, jobid, out_n, extra_n, do, limit

    def _resolve(carry, limit):
        if not resolve:
            return carry
        state = carry + (jnp.asarray(True), limit)
        state = lax.while_loop(lambda s: s[6], _resolve_body, state)
        return state[:6]

    armed_policy = ((kind == KIND_CLONE) | (kind == KIND_RELAUNCH)) & (
        threshold < inf)

    def _step(i, carry):
        a = arrival(i)
        carry = _resolve(carry, a)
        free, doneg, trig, jobid, out, extra = carry
        m = jnp.min(jnp.where(valid, free, inf))
        start = jnp.maximum(a, m)
        g = argmin(jnp.where(valid, free, inf))
        d0 = start + at(svc_row(i), g)
        idle = valid & (free <= start) & (gidx != g)
        h = argmin(jnp.where(idle, free, inf))
        do_hedge = (kind == KIND_HEDGED) & hedge(i) & jnp.any(idle)
        d_final = jnp.where(do_hedge, jnp.minimum(d0, start + at(alt_row(i), h)),
                            d0)
        d_primary = jnp.where(armed_policy, d0, d_final)
        at_g = gidx == g
        free_n = jnp.where(at_g, d_primary, free)
        free_n = jnp.where(do_hedge & (gidx == h), d_final, free_n)
        doneg_n = jnp.where(at_g, d_primary, doneg)
        trig_n = jnp.where(at_g, jnp.where(armed_policy, start + threshold, inf),
                           trig)
        jobid_n = jnp.where(at_g, i, jobid)
        out_n = jnp.where(~armed_policy & (jidx == i), d_final - a, out)
        extra_n = extra + jnp.where(do_hedge, 1, 0).astype(jnp.int32)
        return free_n, doneg_n, trig_n, jobid_n, out_n, extra_n

    carry = (
        jnp.where(valid, jnp.zeros((1, n_g), dtype), inf),
        jnp.zeros((1, n_g), dtype),
        jnp.full((1, n_g), inf, dtype),
        jnp.full((1, n_g), _INT_MAX, dtype=jnp.int32),
        jnp.zeros((1, n_jobs), dtype),
        jnp.asarray(0, jnp.int32),
    )
    carry = lax.fori_loop(0, n_jobs, _step, carry)
    carry = _resolve(carry, inf)
    return carry[4], carry[5]


def _cells_fn(arrivals, svc, alt, kinds, thresholds, hedge_masks, n_groups,
              resolve=True):
    """vmap the cell recursion over (cells, policies); svc shared across P."""
    _, n_jobs, n_g = svc.shape

    def per_cell(svc_c, alt_c, thr_c, ng_c):
        def per_policy(kind, thr, hmask):
            out, extra = cell_recursion(
                lambda i: arrivals[i],
                lambda i: lax.dynamic_slice_in_dim(svc_c, i, 1, axis=0),
                lambda i: lax.dynamic_slice_in_dim(alt_c, i, 1, axis=0),
                lambda i: hmask[i],
                n_jobs, n_g, svc.dtype, kind, thr, ng_c, resolve=resolve)
            return out[0], extra

        return jax.vmap(per_policy)(kinds, thr_c, hedge_masks)

    return jax.vmap(per_cell, in_axes=(0, 0, 0, 0))(svc, alt, thresholds,
                                                    n_groups)


sojourn_cells_vmap = jax.jit(_cells_fn, static_argnames=("resolve",))


def coded_cell(times, k):
    """k-th order statistic per trial of one coded cell (the jit+vmap
    backend's body; ``k`` is a traced scalar)."""
    srt = jnp.sort(times, axis=1)
    return lax.dynamic_slice_in_dim(srt, k - 1, 1, axis=1)[:, 0]


def _coded_cells_fn(times, ks):
    return jax.vmap(coded_cell)(times, ks)


coded_cells_vmap = jax.jit(_coded_cells_fn)


def _coded_kernel(k_ref, times_ref, out_ref):
    """k-th smallest of each trial column by rank counting (Mosaic has no
    sort): worker n holds rank ``#{m: x_m < x_n} + #{m < n: x_m == x_n}``,
    a permutation of 0..N-1, and the answer is the value of rank k-1 —
    the same value a sort would select, so the result is exact."""
    n_workers = times_ref.shape[1]
    x = times_ref[0]  # (N, T): workers on sublanes, trials on lanes
    widx = lax.broadcasted_iota(jnp.int32, x.shape, 0)

    def count(m, rank):
        xm = times_ref[0, pl.ds(m, 1), :]
        before = (xm < x) | ((xm == x) & (m < widx))
        return rank + before.astype(jnp.int32)

    rank = lax.fori_loop(0, n_workers, count, jnp.zeros(x.shape, jnp.int32))
    k = k_ref[pl.program_id(0)]
    out_ref[0] = jnp.max(jnp.where(rank == k - 1, x, -jnp.inf), axis=0,
                         keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def coded_cells_pallas(times, ks, interpret=None):
    """Pallas grid over coded cells; one order-statistic pass per program."""
    n_cells, n_trials, n_workers = times.shape
    out = pl.pallas_call(
        _coded_kernel,
        grid=(n_cells,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, n_workers, n_trials), lambda c: (c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, n_trials), lambda c: (c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_cells, 1, n_trials), times.dtype),
        interpret=resolve_interpret(interpret),
        name="coded_cells",
    )(ks, jnp.swapaxes(times, 1, 2))
    return out[:, 0, :]


def _sojourn_kernel(kind_ref, thr_ref, ng_ref, arr_ref, hmask_ref, svc_ref,
                    alt_ref, out_ref, extra_ref, *, resolve=True):
    c, p = pl.program_id(0), pl.program_id(1)
    n_pol = pl.num_programs(1)
    _, n_jobs, n_g = svc_ref.shape
    out, extra = cell_recursion(
        lambda i: arr_ref[i],
        lambda i: svc_ref[0, pl.ds(i, 1), :],
        lambda i: alt_ref[0, pl.ds(i, 1), :],
        lambda i: hmask_ref[p * n_jobs + i] != 0,
        n_jobs, n_g, svc_ref.dtype,
        kind_ref[p], thr_ref[c * n_pol + p], ng_ref[c], resolve=resolve,
    )
    out_ref[0, 0] = out
    # a (1, 128) lane row per program keeps the block tiling-aligned; the
    # wrapper reads lane 0
    extra_ref[0, 0] = jnp.broadcast_to(extra, extra_ref.shape[2:])


@functools.partial(jax.jit, static_argnames=("interpret", "resolve"))
def sojourn_cells_pallas(arrivals, svc, alt, kinds, thresholds, hedge_masks,
                         n_groups, interpret=None, resolve=True):
    """Pallas grid over (cells, policies); one cell recursion per program.

    The per-program scalars (``kinds``, ``thresholds``, ``n_groups``), the
    shared arrival times and the hedge bits are whole arrays in SMEM; a
    cell's ``(J, G)`` draw blocks sit in VMEM and are read a row at a time.
    """
    n_cells, n_jobs, n_g = svc.shape
    n_pol = kinds.shape[0]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out, extra = pl.pallas_call(
        functools.partial(_sojourn_kernel, resolve=resolve),
        grid=(n_cells, n_pol),
        in_specs=[
            smem, smem, smem, smem, smem,
            pl.BlockSpec((1, n_jobs, n_g), lambda c, p: (c, 0, 0)),
            pl.BlockSpec((1, n_jobs, n_g), lambda c, p: (c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, n_jobs), lambda c, p: (c, p, 0, 0)),
            pl.BlockSpec((1, 1, 1, 128), lambda c, p: (c, p, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_cells, n_pol, 1, n_jobs), svc.dtype),
            jax.ShapeDtypeStruct((n_cells, n_pol, 1, 128), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
        name="sojourn_cells",
    )(kinds, thresholds.reshape(-1), n_groups, arrivals,
      hedge_masks.astype(jnp.int32).reshape(-1), svc, alt)
    return out[:, :, 0, :], extra[:, :, 0, 0]
