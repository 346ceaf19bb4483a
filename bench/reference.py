"""Plain reference of the CC-LP Dykstra solve, independent of the program.

It follows the paper (Ruggles, Veldt, Gleich, arXiv:1901.10084) directly:

  * one pass visits every triangle constraint in the paper's conflict-free
    order (Fig. 1: sets S_{i,k} = {(i, j, k)} along anti-diagonals of the
    (i, k) grid, family 1 with x = 0 and z = n-1 .. 2, then family 2 with
    z = n-1 and x = 1 .. n-3; within a set j rises), then the pair
    constraints +-(x - d) <= f, then the box 0 <= x <= 1;
  * per triplet the three constraints (long (i,j), apex k), (long (i,k),
    apex j), (long (j,k), apex i), each one Dykstra step of the
    eps-regularised QP with W = diag(w, w) (paper eq. (5), Algorithm 1);
  * sets of one diagonal share at most one index, so they are swept side by
    side as lanes; j is the sequential axis.

The state is plain arrays: X and F (n, n) upper triangles, the triangle
duals by diagonal ``y[d, r, t, c]`` (diagonal d, set c on it, step t = j -
i - 1, and r = 0, 1, 2 for the constraints with long side (i,j), (i,k),
(j,k)), and (2, n, n) pair and box duals. ``dtype`` is the arithmetic
precision; the check runs it in float32, and the control in bfloat16. It
runs on the host's CPU: a sequential sweep of small vectors is what a CPU
does well, and it leaves the chip to the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BOX = (0.0, 1.0)
UNROLL = 8  # j-steps per block of the sequential sweep


def diagonals(n: int) -> np.ndarray:
    """(D, 2) int32 rows (x, z) of the paper's diagonals, in order."""
    rows = [(0, z) for z in range(n - 1, 1, -1)]
    rows += [(x, n - 1) for x in range(1, n - 2)]
    return np.asarray(rows, np.int32).reshape(-1, 2)


def live_mask(n: int) -> jax.Array:
    r = jnp.arange(n)
    return r[:, None] < r[None, :]


def dims(n: int) -> tuple[int, int, int]:
    """(diagonals, padded j-steps, sets per diagonal) of the dual layout."""
    T = max(n - 2, 1)  # most j-steps of one set
    return len(diagonals(n)), -(-T // UNROLL) * UNROLL, max((n - 1) // 2, 1)


def start(n: int, eps: float, dtype) -> dict:
    """Algorithm 1 line 3 for CC-LP: x0 = 0, f0 = -1/eps on live pairs
    (c_x = 0, c_f = w_f = w); every dual 0."""
    D, Tp, C = dims(n)
    return {
        "x": jnp.zeros((n, n), dtype),
        "f": jnp.where(live_mask(n), -1.0 / eps, 0.0).astype(dtype),
        "y": jnp.zeros((D, 3, Tp, C), dtype),
        "ypair": jnp.zeros((2, n, n), dtype),
        "ybox": jnp.zeros((2, n, n), dtype),
    }


def _visit(xab, xac, xbc, y, iab, iac, ibc, eps, m):
    """One Dykstra step on x_ab - x_ac - x_bc <= 0 (serial oracle form)."""
    xab1 = xab + y * iab / eps
    xac1 = xac - y * iac / eps
    xbc1 = xbc - y * ibc / eps
    delta = xab1 - xac1 - xbc1
    theta = eps * jnp.maximum(delta, 0) / (iab + iac + ibc)
    xab1 = xab1 - theta * iab / eps
    xac1 = xac1 + theta * iac / eps
    xbc1 = xbc1 + theta * ibc / eps
    keep = lambda new, old: jnp.where(m, new, old)
    return keep(xab1, xab), keep(xac1, xac), keep(xbc1, xbc), keep(theta, y)


def _triangles(x, y, w, eps, n):
    """All triangle constraints of one pass, in the paper's order."""
    dt = x.dtype
    diag = jnp.asarray(diagonals(n))
    _, Tp, C = dims(n)
    c = jnp.arange(C, dtype=jnp.int32)
    t = jnp.arange(Tp, dtype=jnp.int32)
    iw = (1.0 / w).astype(dt)

    def one_diagonal(d, carry):
        x, y = carry
        x0, z = diag[d, 0], diag[d, 1]
        i, k = x0 + c, z - c
        s = k - i - 1
        live = s > 0
        act = live[None, :] & (t[:, None] < s[None, :])  # (Tp, C)
        j = i[None, :] + 1 + t[:, None]
        drop = n  # out-of-range row: dropped by every scatter below
        ia = jnp.where(act, jnp.broadcast_to(i, (Tp, C)), drop)
        ja = jnp.where(act, j, drop)
        ka = jnp.where(act, jnp.broadcast_to(k, (Tp, C)), drop)
        il, kl = jnp.where(live, i, drop), jnp.where(live, k, drop)
        get = lambda a, *ix: a.at[ix].get(mode="fill", fill_value=0)
        yd = jax.lax.dynamic_index_in_dim(y, d, keepdims=False)
        bufs = (get(x, ia, ja), get(x, ja, ka), yd[0], yd[1], yd[2])
        gR = get(iw, ia, ja)
        gK = get(iw, ja, ka)
        gP = get(iw, il, kl)
        xik = get(x, il, kl)

        def block(b, inner):
            xik, bufs = inner
            rows = [jax.lax.dynamic_slice_in_dim(a, b * UNROLL, UNROLL)
                    for a in bufs]
            gr = jax.lax.dynamic_slice_in_dim(gR, b * UNROLL, UNROLL)
            gk = jax.lax.dynamic_slice_in_dim(gK, b * UNROLL, UNROLL)
            m = jax.lax.dynamic_slice_in_dim(act, b * UNROLL, UNROLL)
            R, K, Y0, Y1, Y2 = rows
            outs = [[], [], [], [], []]
            for u in range(UNROLL):
                r, kk, mu = R[u], K[u], m[u]
                r, xik, kk, y0 = _visit(r, xik, kk, Y0[u], gr[u], gP, gk[u],
                                        eps, mu)
                xik, r, kk, y1 = _visit(xik, r, kk, Y1[u], gP, gr[u], gk[u],
                                        eps, mu)
                kk, r, xik, y2 = _visit(kk, r, xik, Y2[u], gk[u], gr[u], gP,
                                        eps, mu)
                for lst, v in zip(outs, (r, kk, y0, y1, y2)):
                    lst.append(v)
            bufs = tuple(
                jax.lax.dynamic_update_slice_in_dim(a, jnp.stack(o), b * UNROLL,
                                                    0)
                for a, o in zip(bufs, outs)
            )
            return xik, bufs

        nblocks = -(-(z - x0 - 1) // UNROLL)  # the c = 0 set is the longest
        xik, bufs = jax.lax.fori_loop(0, nblocks, block, (xik, bufs))
        R, K, Y0, Y1, Y2 = bufs
        put = lambda a, v, *ix: a.at[ix].set(v, mode="drop")
        x = put(x, R, ia, ja)
        x = put(x, K, ja, ka)
        x = put(x, xik, il, kl)
        y = jax.lax.dynamic_update_index_in_dim(
            y, jnp.stack([Y0, Y1, Y2]), d, 0)
        return x, y

    return jax.lax.fori_loop(0, diag.shape[0], one_diagonal, (x, y))


def _pair_box(x, f, ypair, ybox, w, d, live, eps):
    iw = 1.0 / w
    den = iw + iw
    m = lambda new, old: jnp.where(live, new, old)
    # x - f <= d
    xv = x + ypair[0] * iw / eps
    fv = f - ypair[0] * iw / eps
    th0 = eps * jnp.maximum(xv - fv - d, 0) / den
    x1, f1 = xv - th0 * iw / eps, fv + th0 * iw / eps
    # -x - f <= -d
    xv = x1 - ypair[1] * iw / eps
    fv = f1 - ypair[1] * iw / eps
    th1 = eps * jnp.maximum(d - xv - fv, 0) / den
    x1, f1 = xv + th1 * iw / eps, fv + th1 * iw / eps
    x, f = m(x1, x), m(f1, f)
    ypair = jnp.stack([m(th0, 0), m(th1, 0)]).astype(x.dtype)
    lo, hi = BOX
    xv = x + ybox[0] * iw / eps
    tb0 = eps * jnp.maximum(xv - hi, 0) / iw
    x1 = xv - tb0 * iw / eps
    xv = x1 - ybox[1] * iw / eps
    tb1 = eps * jnp.maximum(lo - xv, 0) / iw
    x1 = xv + tb1 * iw / eps
    x = m(x1, x)
    ybox = jnp.stack([m(tb0, 0), m(tb1, 0)]).astype(x.dtype)
    return x, f, ypair, ybox


@functools.partial(jax.jit, static_argnames=("n", "eps", "passes"),
                   donate_argnames=("st",))
def run_passes(st, w, d, *, n: int, eps: float, passes: int):
    """``passes`` full passes from ``st``. w, d are (n, n) in the state's
    dtype."""
    live = live_mask(n)
    dt = st["x"].dtype
    e = jnp.asarray(eps, dt)

    def one(_, s):
        x, y = _triangles(s["x"], s["y"], w, e, n)
        x, f, yp, yb = _pair_box(x, s["f"], s["ypair"], s["ybox"], w, d,
                                 live, e)
        return {"x": x, "f": f, "y": y, "ypair": yp, "ybox": yb}

    return jax.lax.fori_loop(0, passes, one, st)


@functools.partial(jax.jit, static_argnames=("n", "block"))
def max_triangle_violation(x, *, n: int, block: int = 32):
    """max over live a < b, apex c of x_ab - x_ac - x_bc (0 if none)."""
    xf = x.astype(jnp.float32)
    live = live_mask(n)
    xs = jnp.where(live, xf, 0)
    xs = xs + xs.T
    r = jnp.arange(n)
    pair_ok = live  # a < b, both live

    def body(b0, best):
        cs = b0 * block + jnp.arange(block)
        xc = xs[jnp.minimum(cs, n - 1)]  # (B, n)
        slack = xs[None] - xc[:, :, None] - xc[:, None, :]
        ok = (pair_ok[None]
              & (cs[:, None, None] < n)
              & (r[None, :, None] != cs[:, None, None])
              & (r[None, None, :] != cs[:, None, None]))
        return jnp.maximum(best, jnp.max(jnp.where(ok, slack, -jnp.inf)))

    nb = -(-n // block)
    best = jax.lax.fori_loop(0, nb, body, jnp.float32(-jnp.inf))
    return jnp.maximum(best, 0.0)


def stopping_pair(st: dict, w: np.ndarray, d: np.ndarray,
                  eps: float) -> tuple[float, float]:
    """(max violation, duality gap) of a CC-LP state: triangle violation on
    the device, the rest in float64 on the host (core of [37]'s pair)."""
    n = w.shape[0]
    with jax.default_device(host_device()):
        tri = float(max_triangle_violation(np.asarray(st["x"]), n=n))
    live = np.triu(np.ones((n, n), bool), 1)
    x = np.asarray(st["x"], np.float64)[live]
    f = np.asarray(st["f"], np.float64)[live]
    yp = np.asarray(st["ypair"], np.float64)[:, live]
    yb = np.asarray(st["ybox"], np.float64)[:, live]
    wl, dl = np.asarray(w, np.float64)[live], np.asarray(d, np.float64)[live]
    lo, hi = BOX
    viol = max(tri, float(np.max(np.abs(x - dl) - f, initial=0.0)),
               float(np.max(x - hi, initial=0.0)),
               float(np.max(lo - x, initial=0.0)), 0.0)
    gap = float(np.sum(wl * f + eps * wl * x * x + eps * wl * f * f))
    gap += float(np.sum(dl * yp[0]) - np.sum(dl * yp[1]))
    gap += float(hi * np.sum(yb[0]) - lo * np.sum(yb[1]))
    return viol, gap


def host_device():
    return jax.devices("cpu")[0]


def solve(w: np.ndarray, d: np.ndarray, eps: float, passes: int,
          dtype) -> dict:
    """The state after ``passes`` passes from the start, on the host CPU."""
    n = w.shape[0]
    with jax.default_device(host_device()):
        st = start(n, eps, dtype)
        return run_passes(st, jnp.asarray(w, dtype), jnp.asarray(d, dtype),
                          n=n, eps=eps, passes=passes)


def triangle_cells(n: int, d: int):
    """The live cells of diagonal ``d`` of the dual layout: (t, c) and
    the triplet (i, j, k) of each. ``y[d, r, t, c]`` is the dual of the
    constraint with long side (i, j), (i, k), (j, k) for r = 0, 1, 2."""
    x0, z = (int(v) for v in diagonals(n)[d])
    _, Tp, C = dims(n)
    c = np.arange(C)
    i, k = x0 + c, z - c
    t, c = np.nonzero(np.arange(Tp)[:, None] < (k - i - 1)[None, :])
    i, k = i[c], k[c]
    return t, c, i, i + 1 + t, k
