"""Lockstep batched bounded-variable simplex as jitted jax device code.

``solve_lp_batch_jax`` is the device twin of
``repro.solver.batch.solve_lp_batch``: S same-layout instances (shared
``c``/``A``, per-instance ``b`` and bounds — the Eq.-14 (rho, t_bar)
grid shape) advance in lockstep, but here the whole two-phase simplex is
one jitted program: a ``lax.while_loop`` whose body prices every
instance with a stacked GEMM, runs every ratio test as a stacked
reduction, and applies every basis update as a batched rank-1 — with
**masked per-instance termination** (finished instances keep iterating
as no-ops under a ``run`` mask instead of leaving the dispatch) and
FTRAN/BTRAN as batched einsums over the (S, m, m) inverse stack.

The pivot rules mirror ``solver.batch`` exactly — Dantzig pricing with
per-instance Bland fallback, bound flips, largest-|pivot| ratio-test
tie-breaking, periodic batched refactorization (``jnp.linalg.inv`` over
the basis stack, selected per instance) — so the two backends follow
the same pivot path up to floating-point reduction order.  Like the
numpy path it is cold-start by design (no warm bases in or out), and it
agrees with the serial solver to solver tolerance, not bit-for-bit:
callers that need bit-stable policies keep the serial path.

Everything runs in float64 under a local ``jax.enable_x64`` scope — the
simplex is not a float32 algorithm — so importing this module never
flips global jax precision for the rest of the process.
"""

from __future__ import annotations

import numpy as np

from repro.solver.batch import (
    _EPS,
    _PIV_MIN,
    _STATUS,
    AT_LB,
    AT_UB,
    BASIC,
    INFEAS,
    LIMIT,
    OPT,
    RUN,
    UNB,
)
from repro.solver.result import LPResult

_SOLVE_CACHE: dict = {}


def _get_solver(max_iter: int, refactor_every: int):
    """Build (and cache) the jitted two-phase driver for the given caps."""
    key = (max_iter, refactor_every)
    if key in _SOLVE_CACHE:
        return _SOLVE_CACHE[key]

    import jax
    import jax.numpy as jnp
    from jax import lax

    def compute_xB(Binv, b, xN, art_sign, A):
        """Basic values B^-1 (b - N xN) for the whole stack."""
        n = A.shape[1]
        rhs = b - xN[:, :n] @ A.T - art_sign * xN[:, n:]
        return jnp.einsum("kmn,kn->km", Binv, rhs)

    def basis_mats(basis, art_sign, A):
        """Stacked (S, m, m) basis matrices rebuilt from column indices."""
        m, n = A.shape
        struct = basis < n
        gath = A.T[jnp.clip(basis, 0, n - 1)]  # (S, m_col, m_row)
        rows = jnp.clip(basis - n, 0, m - 1)
        sign = jnp.take_along_axis(art_sign, rows, axis=1)
        art = (jnp.arange(m)[None, None, :] == rows[:, :, None]) * sign[
            :, :, None
        ]
        cols = jnp.where(struct[:, :, None], gath, art)
        return jnp.swapaxes(cols, 1, 2)  # (S, row, col)

    def work_cols(j, art_sign, A):
        """(S, m) dense working column j per instance (masked gather)."""
        m, n = A.shape
        struct = (j < n)[:, None]
        wc_struct = A.T[jnp.clip(j, 0, n - 1)]
        rows = jnp.clip(j - n, 0, m - 1)
        S = j.shape[0]
        sign = art_sign[jnp.arange(S), rows]
        wc_art = (jnp.arange(m)[None, :] == rows[:, None]) * sign[:, None]
        return jnp.where(struct, wc_struct, wc_art)

    def masked_pivot(state, mask, r, j, leave_to, w, xj_new, io):
        """Apply one batched basis swap where ``mask`` holds.

        Mirrors ``_BatchSimplex._do_pivot``: bookkeeping scatter updates,
        a batched rank-1 product-form inverse update, and — where the
        pivot count hits the refactor schedule or the pivot element is
        tiny — a full stacked refactorization with per-instance
        breakdown detection (singular inverse => LIMIT).
        """
        vstat, basis, Binv, xB, xN, status, pivots, run = state
        A, b, art_sign, lbw, ubw = io
        S = vstat.shape[0]
        sidx = jnp.arange(S)
        leaving = basis[sidx, r]
        vstat = vstat.at[sidx, leaving].set(
            jnp.where(mask, leave_to, vstat[sidx, leaving])
        )
        vstat = vstat.at[sidx, j].set(
            jnp.where(mask, BASIC, vstat[sidx, j])
        )
        basis = basis.at[sidx, r].set(jnp.where(mask, j, basis[sidx, r]))
        leave_x = jnp.where(
            leave_to == AT_UB, ubw[sidx, leaving], lbw[sidx, leaving]
        )
        xN = xN.at[sidx, leaving].set(
            jnp.where(mask, leave_x, xN[sidx, leaving])
        )
        xN = xN.at[sidx, j].set(jnp.where(mask, 0.0, xN[sidx, j]))
        pivots = pivots + mask.astype(pivots.dtype)
        wr = w[sidx, r]
        need_rf = mask & (
            (pivots % refactor_every == 0) | (jnp.abs(wr) < _PIV_MIN)
        )
        upd = mask & ~need_rf
        # Product-form rank-1 update (guard the divide; masked out anyway).
        safe_wr = jnp.where(jnp.abs(wr) > 0.0, wr, 1.0)
        prow = Binv[sidx, r] / safe_wr[:, None]
        Binv_upd = Binv - w[:, :, None] * prow[:, None, :]
        Binv_upd = Binv_upd.at[sidx, r].set(prow)
        Binv = jnp.where(upd[:, None, None], Binv_upd, Binv)
        xB = xB.at[sidx, r].set(jnp.where(upd, xj_new, xB[sidx, r]))

        def refactor(ops):
            """Rebuild B^-1 from scratch for instances whose eta drift is due."""
            Binv, status, run, xB = ops
            B = basis_mats(basis, art_sign, A)
            Binv_new = jnp.linalg.inv(B)
            okm = jnp.isfinite(Binv_new).all(axis=(1, 2))
            use = need_rf & okm
            dead = need_rf & ~okm  # numerical breakdown: give up on those
            Binv = jnp.where(use[:, None, None], Binv_new, Binv)
            status = jnp.where(dead, LIMIT, status)
            run = run & ~dead
            xB_new = compute_xB(Binv, b, xN, art_sign, A)
            xB = jnp.where((use & ~dead)[:, None], xB_new, xB)
            return Binv, status, run, xB

        Binv, status, run, xB = lax.cond(
            need_rf.any(), refactor, lambda ops: ops, (Binv, status, run, xB)
        )
        return (vstat, basis, Binv, xB, xN, status, pivots, run)

    def phase(state, cost, io):
        """Advance every running instance to phase optimality (masked)."""
        A, b, art_sign, lbw, ubw = io
        S, nm = state[0].shape
        m = b.shape[1]
        n = nm - m
        sidx = jnp.arange(S)
        movable = (ubw - lbw) > _EPS

        vstat, basis, Binv, xB, xN, status, pivots, run = state
        xB0 = compute_xB(Binv, b, xN, art_sign, A)
        xB = jnp.where(run[:, None], xB0, xB)
        bland = jnp.zeros(S, dtype=bool)
        stall = jnp.zeros(S, dtype=jnp.int32)
        best = jnp.full(S, jnp.inf)
        carry = (
            (vstat, basis, Binv, xB, xN, status, pivots, run),
            bland,
            stall,
            best,
            jnp.int32(0),
        )

        def cond(carry):
            """Keep iterating while any instance runs and the cap isn't hit."""
            state, _, _, _, it = carry
            return state[7].any() & (it < max_iter)

        def body(carry):
            """One masked simplex pivot (or bound flip) across the stack."""
            state, bland, stall, best, it = carry
            vstat, basis, Binv, xB, xN, status, pivots, run = state
            costB = cost[basis]
            obj = (costB * xB).sum(axis=1) + xN @ cost
            better = obj < best - 1e-12
            best = jnp.where(run & better, obj, best)
            stall_new = jnp.where(better, 0, stall + 1)
            stall = jnp.where(run, stall_new, stall)
            bland = jnp.where(
                run,
                jnp.where(better, False, bland | (stall_new > 2 * m + 16)),
                bland,
            )
            # Pricing: one stacked GEMM covers every running instance.
            y = jnp.einsum("km,kmn->kn", costB, Binv)
            dred = jnp.concatenate(
                [cost[:n] - y @ A, cost[n:] - y * art_sign], axis=1
            )
            elig = movable & (
                ((vstat == AT_LB) & (dred < -_EPS))
                | ((vstat == AT_UB) & (dred > _EPS))
            )
            elig = elig & run[:, None]
            has = elig.any(axis=1)
            run = run & has  # phase-optimal instances retire in place
            act = run
            j_dz = jnp.argmax(jnp.where(elig, jnp.abs(dred), -1.0), axis=1)
            j = jnp.where(bland, jnp.argmax(elig, axis=1), j_dz)
            sdir = jnp.where(vstat[sidx, j] == AT_LB, 1.0, -1.0)
            w = jnp.einsum("kmn,kn->km", Binv, work_cols(j, art_sign, A))
            dxB = -sdir[:, None] * w
            lbB = jnp.take_along_axis(lbw, basis, axis=1)
            ubB = jnp.take_along_axis(ubw, basis, axis=1)
            inc = dxB > _EPS
            dec = dxB < -_EPS
            t_up = jnp.where(inc, (ubB - xB) / jnp.where(inc, dxB, 1.0),
                             jnp.inf)
            t_lo = jnp.where(dec, (lbB - xB) / jnp.where(dec, dxB, 1.0),
                             jnp.inf)
            t_up = jnp.where(jnp.isnan(t_up), jnp.inf, jnp.maximum(t_up, 0.0))
            t_lo = jnp.where(jnp.isnan(t_lo), jnp.inf, jnp.maximum(t_lo, 0.0))
            t_row = jnp.minimum(t_up, t_lo)
            rmin = t_row.min(axis=1)
            t_flip = ubw[sidx, j] - lbw[sidx, j]
            unb = act & ~jnp.isfinite(jnp.minimum(rmin, t_flip))
            status = jnp.where(unb, UNB, status)
            run = run & ~unb
            flip = act & ~unb & (t_flip < rmin - 1e-12)
            xB = jnp.where(flip[:, None], xB + dxB * t_flip[:, None], xB)
            newst = jnp.where(vstat[sidx, j] == AT_LB, AT_UB, AT_LB)
            vstat = vstat.at[sidx, j].set(
                jnp.where(flip, newst, vstat[sidx, j])
            )
            flip_x = jnp.where(newst == AT_UB, ubw[sidx, j], lbw[sidx, j])
            xN = xN.at[sidx, j].set(jnp.where(flip, flip_x, xN[sidx, j]))
            piv = act & ~unb & ~flip
            cand = t_row <= (rmin + _EPS)[:, None]
            r_dz = jnp.argmax(jnp.where(cand, jnp.abs(dxB), -1.0), axis=1)
            r_bl = jnp.argmax(
                jnp.where(cand, -basis.astype(jnp.float64), -jnp.inf), axis=1
            )
            r = jnp.where(bland, r_bl, r_dz)
            leave_to = jnp.where(
                t_up[sidx, r] <= t_lo[sidx, r], AT_UB, AT_LB
            )
            xj_new = xN[sidx, j] + sdir * rmin
            xB = jnp.where(piv[:, None], xB + dxB * rmin[:, None], xB)
            state = masked_pivot(
                (vstat, basis, Binv, xB, xN, status, pivots, run),
                piv, r, j, leave_to, w, xj_new,
                (A, b, art_sign, lbw, ubw),
            )
            return (state, bland, stall, best, it + 1)

        carry = lax.while_loop(cond, body, carry)
        state, _, _, _, _ = carry
        vstat, basis, Binv, xB, xN, status, pivots, run = state
        status = jnp.where(run, LIMIT, status)  # iteration cap
        run = jnp.zeros_like(run)
        return (vstat, basis, Binv, xB, xN, status, pivots, run)

    def solve(c, A, b, lb, ub, live):
        """Two-phase bounded-variable simplex over the stacked instances."""
        S, m = b.shape
        n = c.shape[0]
        sidx = jnp.arange(S)
        cost2 = jnp.concatenate([c, jnp.zeros(m)])
        cost1 = jnp.concatenate([jnp.zeros(n), jnp.ones(m)])
        lbw = jnp.concatenate([lb, jnp.zeros((S, m))], axis=1)
        ubw0 = jnp.concatenate([ub, jnp.zeros((S, m))], axis=1)
        vstat = jnp.full((S, n + m), AT_LB, dtype=jnp.int32)
        no_lb = ~jnp.isfinite(lbw[:, :n])
        vstat = vstat.at[:, :n].set(
            jnp.where(no_lb, AT_UB, vstat[:, :n])
        )
        xN = jnp.where(vstat == AT_UB, ubw0, lbw)
        xN = jnp.where(vstat == BASIC, 0.0, xN)
        r0 = b - xN[:, :n] @ A.T
        art_sign = jnp.where(r0 >= 0.0, 1.0, -1.0)
        basis = jnp.tile(jnp.arange(n, n + m), (S, 1))
        vstat = vstat.at[:, n:].set(BASIC)
        xN = xN.at[:, n:].set(0.0)
        Binv = jnp.eye(m)[None, :, :] * art_sign[:, :, None]
        ubw1 = ubw0.at[:, n:].set(jnp.inf)  # artificials live in phase 1
        status = jnp.where(live, RUN, INFEAS).astype(jnp.int32)
        pivots = jnp.zeros(S, dtype=jnp.int32)
        xB = jnp.zeros((S, m))
        run = live
        state = (vstat, basis, Binv, xB, xN, status, pivots, run)
        io1 = (A, b, art_sign, lbw, ubw1)
        state = phase(state, cost1, io1)
        vstat, basis, Binv, xB, xN, status, pivots, run = state
        still = status == RUN
        xB_new = compute_xB(Binv, b, xN, art_sign, A)
        xB = jnp.where(still[:, None], xB_new, xB)
        art_obj = jnp.where(basis >= n, xB, 0.0).sum(axis=1)
        status = jnp.where(still & (art_obj > 1e-7), INFEAS, status)

        def drive_row(r, state):
            """Pivot a leftover degenerate artificial out of row ``r``."""
            vstat, basis, Binv, xB, xN, status, pivots, run = state
            isart = (status == RUN) & (basis[:, r] >= n)
            row = jnp.einsum("km,mn->kn", Binv[:, r, :], A)
            free = (vstat[:, :n] != BASIC) & (jnp.abs(row) > 1e-7)
            mask = isart & free.any(axis=1)
            jj = jnp.argmax(free, axis=1)  # first eligible column
            w = jnp.einsum(
                "kmn,kn->km", Binv, work_cols(jj, art_sign, A)
            )
            rvec = jnp.full((S,), r, dtype=basis.dtype)
            leave = jnp.full((S,), AT_LB, dtype=vstat.dtype)
            xj_new = xN[sidx, jj]
            run = jnp.where(mask, True, run)  # refactor path needs liveness
            state = masked_pivot(
                (vstat, basis, Binv, xB, xN, status, pivots, run),
                mask, rvec, jj, leave, w, xj_new, io1,
            )
            vstat, basis, Binv, xB, xN, status, pivots, run = state
            run = jnp.where(mask, False, run)
            return (vstat, basis, Binv, xB, xN, status, pivots, run)

        state = lax.fori_loop(
            0, m, drive_row,
            (vstat, basis, Binv, xB, xN, status, pivots, run),
        )
        vstat, basis, Binv, xB, xN, status, pivots, run = state
        run = status == RUN
        io2 = (A, b, art_sign, lbw, ubw0)  # artificials pinned for phase 2
        state = phase(
            (vstat, basis, Binv, xB, xN, status, pivots, run), cost2, io2
        )
        vstat, basis, Binv, xB, xN, status, pivots, run = state
        status = jnp.where(status == RUN, OPT, status)
        x_full = xN.at[sidx[:, None], basis].set(xB)
        return x_full[:, :n], status, pivots

    fn = jax.jit(solve)
    _SOLVE_CACHE[key] = fn
    return fn


def solve_lp_batch_jax(
    c,
    A,
    b_stack,
    lb_stack=None,
    ub_stack=None,
    max_iter: int = 20000,
    refactor_every: int = 64,
) -> list[LPResult]:
    """Solve S instances min c@x s.t. A@x=b_s, lb_s<=x<=ub_s on device.

    Drop-in for ``repro.solver.batch.solve_lp_batch`` with identical
    call/return conventions (one ``LPResult`` per instance, cold-start,
    sparse ``A`` densified), executed as one jitted two-phase lockstep
    simplex in float64 under a local ``jax.enable_x64`` scope.  Compilation
    is cached per (shape, caps); repeat sweeps over the same layout —
    the Eq.-14 grid shape — pay tracing once.
    """
    import jax

    c = np.asarray(c, dtype=np.float64)
    if hasattr(A, "toarray") and not isinstance(A, np.ndarray):
        A = A.toarray()
    A = np.asarray(A, dtype=np.float64)
    b = np.atleast_2d(np.asarray(b_stack, dtype=np.float64))
    S = b.shape[0]
    n = c.shape[0]
    lb = np.zeros(n) if lb_stack is None else np.asarray(lb_stack, np.float64)
    ub = (
        np.full(n, np.inf) if ub_stack is None
        else np.asarray(ub_stack, np.float64)
    )
    lb = np.broadcast_to(lb, (S, n)).copy()
    ub = np.broadcast_to(ub, (S, n)).copy()
    if np.any(~np.isfinite(lb) & ~np.isfinite(ub)):
        raise ValueError("free variables (lb and ub infinite) unsupported")
    live = ~(lb > ub + _EPS).any(axis=1)

    # Pad the stack axis to the next power of two so sweeps whose
    # feasibility pre-filter keeps a varying number of grid points share
    # one compiled program per (m, n) layout.  Padded instances enter
    # dead (live=False -> INFEAS, never iterated) and are sliced off.
    S_pad = 1 << max(0, S - 1).bit_length()
    if S_pad > S:
        pad = S_pad - S
        b = np.concatenate([b, np.zeros((pad, b.shape[1]))])
        lb = np.concatenate([lb, np.zeros((pad, n))])
        ub = np.concatenate([ub, np.ones((pad, n))])
        live = np.concatenate([live, np.zeros(pad, dtype=bool)])

    with jax.enable_x64(True):
        fn = _get_solver(int(max_iter), int(refactor_every))
        x, status, pivots = fn(c, A, b, lb, ub, live)
        x = np.asarray(x)[:S]
        status = np.asarray(status)[:S]
        pivots = np.asarray(pivots)[:S]

    out = []
    for s in range(S):
        st = _STATUS[int(status[s])]
        piv = int(pivots[s])
        if st != "optimal":
            fun = -np.inf if st == "unbounded" else np.inf
            out.append(LPResult(None, fun, st, pivots=piv))
            continue
        xs = x[s]
        out.append(LPResult(xs, float(c @ xs), "optimal", pivots=piv))
    return out
