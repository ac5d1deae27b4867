"""Plain numpy reference of one Astaroth iteration (reference:
astaroth/user_kernels.h, integration.cuh, astaroth.cu:551-663): compressible
MHD in log-density / velocity / vector potential / entropy form,
6th-order centred differences, Williamson RK3. Imports nothing of
``stencil_tpu``; the parameters below are the values of
``astaroth/astaroth.conf``.

As in the reference driver, the in/out buffers swap once per ITERATION, so
all three substeps take their rates from the same ``in`` state; the stages
differ in how they combine it with the previous stage's output.

It works on a box: the core plus a margin of 3 cells per iteration.
``dtype`` is float64 for the reference. The control keeps the state in the
configuration's float32 and takes the right-hand sides in ``rate_dtype``
bfloat16: the arithmetic a later PR would be tempted to lower.
"""

from __future__ import annotations

import numpy as np

from benchmark import fields

FIELDS = ("lnrho", "uux", "uuy", "uuz", "ax", "ay", "az", "entropy")
R = 3
DT = 1e-8

DS = 0.04908738521
NU_VISC, CS_SOUND, ZETA = 5e-3, 1.0, 0.01
ETA, MU0 = 5e-3, 1.4
CP_SOUND, GAMMA, LNT0, LNRHO0 = 1.0, 0.5, 1.2, 1.3
CHI = 0.001                     # heat_conduction's constant, user_kernels.h:414

ALPHA = (0.0, -5.0 / 9.0, -153.0 / 128.0)
BETA = (1.0 / 3.0, 15.0 / 16.0, 8.0 / 15.0)

D1 = (3.0 / 4.0, -3.0 / 20.0, 1.0 / 60.0)
D2C, D2 = -49.0 / 18.0, (3.0 / 2.0, -3.0 / 20.0, 1.0 / 90.0)
DX = (270.0 / 720.0, -27.0 / 720.0, 2.0 / 720.0)


class Diff:
    """Derivatives of one padded field over its interior (margin R)."""

    def __init__(self, a):
        self.a = a
        self.t = a.dtype.type
        self.inv = self.t(1.0 / DS)

    def at(self, dz=0, dy=0, dx=0):
        a = self.a
        n = [s - 2 * R for s in a.shape]
        return a[R + dz:R + dz + n[0], R + dy:R + dy + n[1],
                 R + dx:R + dx + n[2]]

    def _sh(self, axis, i):
        return self.at(**{("dz", "dy", "dx")[axis]: i})

    def d1(self, axis):
        t = self.t
        acc = t(0)
        for i, c in enumerate(D1, 1):
            acc = acc + t(c) * (self._sh(axis, i) - self._sh(axis, -i))
        return acc * self.inv

    def d2(self, axis):
        t = self.t
        acc = t(D2C) * self.at()
        for i, c in enumerate(D2, 1):
            acc = acc + t(c) * (self._sh(axis, i) + self._sh(axis, -i))
        return acc * self.inv * self.inv

    def dx2(self, ax_a, ax_b):
        """Cross derivative from the two diagonals (user_kernels.h:62-75)."""
        t = self.t
        names = ("dz", "dy", "dx")
        acc = t(0)
        for i, c in enumerate(DX, 1):
            pp = self.at(**{names[ax_a]: i, names[ax_b]: i})
            mm = self.at(**{names[ax_a]: -i, names[ax_b]: -i})
            pm = self.at(**{names[ax_a]: i, names[ax_b]: -i})
            mp = self.at(**{names[ax_a]: -i, names[ax_b]: i})
            acc = acc + t(c) * (pp + mm - pm - mp)
        return acc * self.inv * self.inv

    # axes: z = 0, y = 1, x = 2; vectors and gradients are (x, y, z)
    def grad(self):
        return (self.d1(2), self.d1(1), self.d1(0))

    def lap(self):
        return self.d2(2) + self.d2(1) + self.d2(0)

    def hess(self):
        return {"xx": self.d2(2), "yy": self.d2(1), "zz": self.d2(0),
                "xy": self.dx2(2, 1), "xz": self.dx2(2, 0),
                "yz": self.dx2(1, 0)}


def _dot(a, b):
    return a[0] * b[0] + a[1] * b[1] + a[2] * b[2]


def _cross(a, b):
    return (a[1] * b[2] - a[2] * b[1], a[2] * b[0] - a[0] * b[2],
            a[0] * b[1] - a[1] * b[0])


def rates(state: dict) -> dict:
    """Right-hand sides over the interior of a padded 8-field box."""
    t = state["lnrho"].dtype.type
    d = {k: Diff(v) for k, v in state.items()}
    lnrho, ss = d["lnrho"], d["entropy"]
    u = (d["uux"], d["uuy"], d["uuz"])
    a = (d["ax"], d["ay"], d["az"])
    uv = tuple(c.at() for c in u)
    gu = [c.grad() for c in u]            # gu[i][j] = d u_i / d x_j
    ga = [c.grad() for c in a]
    hu = [c.hess() for c in u]
    ha = [c.hess() for c in a]
    g_lnrho, g_ss = lnrho.grad(), ss.grad()
    lap_lnrho, lap_ss = lnrho.lap(), ss.lap()

    def lap_of(h):
        return tuple(hh["xx"] + hh["yy"] + hh["zz"] for hh in h)

    def grad_div(h):
        return (h[0]["xx"] + h[1]["xy"] + h[2]["xz"],
                h[0]["xy"] + h[1]["yy"] + h[2]["yz"],
                h[0]["xz"] + h[1]["yz"] + h[2]["zz"])

    div_u = gu[0][0] + gu[1][1] + gu[2][2]
    curl_a = (ga[2][1] - ga[1][2], ga[0][2] - ga[2][0], ga[1][0] - ga[0][1])
    lap_a, lap_u = lap_of(ha), lap_of(hu)
    god_a, god_u = grad_div(ha), grad_div(hu)
    j = tuple((god_a[i] - lap_a[i]) / t(MU0) for i in range(3))
    third, half, two = t(1.0 / 3.0), t(0.5), t(2.0)
    s = {"xx": two * third * gu[0][0] - third * (gu[1][1] + gu[2][2]),
         "yy": two * third * gu[1][1] - third * (gu[0][0] + gu[2][2]),
         "zz": two * third * gu[2][2] - third * (gu[0][0] + gu[1][1]),
         "xy": half * (gu[0][1] + gu[1][0]),
         "xz": half * (gu[0][2] + gu[2][0]),
         "yz": half * (gu[1][2] + gu[2][1])}
    rho_v, ss_v = lnrho.at(), ss.at()
    gam, cp = t(GAMMA), t(CP_SOUND)

    out = {"lnrho": -_dot(uv, g_lnrho) - div_u}
    uxb = _cross(uv, curl_a)
    for i, k in enumerate(("ax", "ay", "az")):
        out[k] = uxb[i] + t(ETA) * lap_a[i]

    cs2 = t(CS_SOUND ** 2) * np.exp(
        gam * ss_v / cp + (gam - t(1)) * (rho_v - t(LNRHO0)))
    inv_rho = np.exp(-rho_v)
    jxb = _cross(j, curl_a)
    s_g = (s["xx"] * g_lnrho[0] + s["xy"] * g_lnrho[1] + s["xz"] * g_lnrho[2],
           s["xy"] * g_lnrho[0] + s["yy"] * g_lnrho[1] + s["yz"] * g_lnrho[2],
           s["xz"] * g_lnrho[0] + s["yz"] * g_lnrho[1] + s["zz"] * g_lnrho[2])
    for i, k in enumerate(("uux", "uuy", "uuz")):
        adv = _dot(gu[i], uv)
        pressure = cs2 * (g_ss[i] / cp + g_lnrho[i])
        visc = t(NU_VISC) * (lap_u[i] + god_u[i] * third + two * s_g[i])
        out[k] = (-adv - pressure + inv_rho * jxb[i] + visc
                  + t(ZETA) * god_u[i])

    rho = np.exp(rho_v)
    ln_t = t(LNT0) + gam * ss_v / cp + (gam - t(1)) * (rho_v - t(LNRHO0))
    inv_pt = t(1) / (rho * np.exp(ln_t))
    contract = (s["xx"] ** 2 + s["yy"] ** 2 + s["zz"] ** 2
                + two * (s["xy"] ** 2 + s["xz"] ** 2 + s["yz"] ** 2))
    heating = (t(ETA * MU0) * _dot(j, j) + two * rho * t(NU_VISC) * contract
               + t(ZETA) * rho * div_u * div_u)
    first = gam / cp * lap_ss + (gam - t(1)) * lap_lnrho
    second = tuple(gam / cp * g_ss[i] + (gam - t(1)) * g_lnrho[i]
                   for i in range(3))
    third_v = tuple(gam * (g_ss[i] / cp + g_lnrho[i]) - g_lnrho[i]
                    for i in range(3))
    chi = t(CHI) * np.exp(-rho_v) / cp
    conduction = cp * chi * (first + _dot(second, third_v))
    out["entropy"] = -_dot(uv, g_ss) + inv_pt * heating + conduction
    return out


def iterate(state: dict, dt: float = DT, rate_dtype=None) -> dict:
    """One iteration (three substeps) of a padded box; returns the fields
    over the box's interior (margin R smaller on every side)."""
    t = state["lnrho"].dtype.type
    if rate_dtype is None:
        rate = rates(state)
    else:
        low = rates({k: v.astype(rate_dtype) for k, v in state.items()})
        rate = {k: v.astype(t) for k, v in low.items()}
    out = {}
    for k in FIELDS:
        c = Diff(state[k]).at()
        o = c + t(BETA[0]) * rate[k] * t(dt)
        for s in (1, 2):
            o = c + t(BETA[s]) * (t(ALPHA[s] / BETA[s - 1]) * (c - o)
                                  + rate[k] * t(dt))
        out[k] = o
    return out


def box_after(seed: int, origin, core, iters: int, global_zyx,
              dtype=np.float64, rate_dtype=None) -> dict:
    """The cores of the 8 fields of one sampled box after ``iters``
    iterations from the seeded state (``iters`` 0: the seeded cores)."""
    z, y, x = fields.box_coords(origin, core, R * iters, global_zyx)
    zz, yy, xx = z[:, None, None], y[None, :, None], x[None, None, :]
    state = {k: fields.uniform(np, seed, q, zz, yy, xx).astype(dtype)
             for q, k in enumerate(FIELDS)}
    for _ in range(iters):
        state = iterate(state, rate_dtype=rate_dtype)
    return state
