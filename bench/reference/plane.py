"""Plain reference of the fleet control plane's service period, replayed
for a sample of tenants from the heartbeats they were sent.

Independent of the program: numpy, vectorised over tenants, in any
floating dtype (float64 for the reference; the control takes float32 for
the heartbeat rates and bfloat16 for the control law). Every
intermediate is rounded to its dtype.

Per period ending at t_i and per tenant:

* Eq. 1 (paper section 4.2): the median of the instantaneous rates
  1 / (t_k - t_{k-1}) of the beats in [t_{i-1}, t_i); the window's first
  beat pairs with the newest earlier beat (the anchor). No rate -> 0.
* detector (PI tenants with one): the design model's Eq. 3 replay of the
  cap applied over the window, a two-sided Page-Hinkley test on the
  residual's deviation from its slow EWMA level, a refractory window
  after each alarm. An alarm is reported; the fixed-gain PI ignores it.
* policies: Eq. 4 PI (anti-windup on the linearised cap); PI whose gains
  an RLS estimate of the first-order model re-places every ``dwell``
  periods; a duty-cycle ladder of ``n_levels`` cap levels.
* the applied cap is the command clipped to [pcap_min, pcap_max].
"""
from __future__ import annotations

import numpy as np

PI, PI_RLS, DUTY = "pi", "pi_rls", "dutycycle"


def eq1_progress(times: np.ndarray, tenant: np.ndarray, n_tenants: int,
                 anchor: np.ndarray, dtype=np.float64):
    """Eq. 1 for one period. ``times``/``tenant``: this period's beats
    (tenant-grouped, time-ordered within a tenant); ``anchor``: each
    tenant's newest earlier beat (NaN: none). Returns (progress, new
    anchor)."""
    d = np.dtype(dtype)
    t = times.astype(d)
    order = np.lexsort((t, tenant))
    t, ids = t[order], tenant[order]
    first = np.ones(len(t), bool)
    first[1:] = ids[1:] != ids[:-1]
    prev = np.empty_like(t)
    prev[1:] = t[:-1]
    prev[first] = anchor.astype(d)[ids[first]]
    gap = (t - prev).astype(d)
    ok = np.isfinite(gap) & (gap > 0)
    rate = (np.ones((), d) / np.where(ok, gap, np.ones((), d))).astype(d)
    progress = np.zeros(n_tenants, d)
    new_anchor = anchor.copy()
    if len(t):
        last = np.ones(len(t), bool)
        last[:-1] = ids[1:] != ids[:-1]
        new_anchor[ids[last]] = times[order][last]
    rate, rid = rate[ok], ids[ok]
    if len(rate):
        o = np.lexsort((rate, rid))
        rate, rid = rate[o], rid[o]
        m = np.bincount(rid, minlength=n_tenants)
        start = np.concatenate(([0], np.cumsum(m)[:-1]))
        has = m > 0
        lo = start + np.maximum(m - 1, 0) // 2
        hi = start + m // 2
        med = ((rate[np.minimum(lo, len(rate) - 1)]
                + rate[np.minimum(hi, len(rate) - 1)]) * d.type(0.5)
               ).astype(d)
        progress = np.where(has, med, progress).astype(d)
    return progress, new_anchor


class Fleet:
    """Control-law state of the sampled tenants, advanced period by
    period. ``plants``/``kind`` are per-tenant arrays; ``cfg`` holds the
    deployment's controller settings (epsilon, tau_obj, rls, dutycycle,
    detector)."""

    def __init__(self, plants: dict, kind: np.ndarray, detect: np.ndarray,
                 cfg: dict, dtype=np.float64):
        self.d = d = np.dtype(dtype)
        c = self.c
        self.p = p = {k: c(v) for k, v in plants.items()}
        self.kind, self.detect = np.asarray(kind), np.asarray(detect, bool)
        eps, tau_obj = cfg["epsilon"], cfg["tau_obj"]
        self.k_p = c(p["tau"] / c(p["K_L"] * c(tau_obj)))
        self.k_i = c(c(1.0) / c(p["K_L"] * c(tau_obj)))
        self.setpoint = c(c(1.0 - eps) * self.static(p["pcap_max"]))
        n = len(self.kind)
        zero = c(np.zeros(n))
        self.applied = p["pcap_max"]
        self.prev_err, self.prev_l = zero, self.lin(p["pcap_max"])
        r = cfg["rls"]
        self.rls = {k: c(v) for k, v in r.items()}
        self.tau_obj = c(c(1.0) / c(p["K_L"] * self.k_i))
        self.theta = np.stack([c(p["K_L"] * c(0.5)), c(np.full(n, 0.5))], 1)
        self.P = np.broadcast_to(c(np.eye(2) * 100.0), (n, 2, 2)).copy()
        self.phi = c(np.zeros((n, 2)))
        self.has_prev = np.zeros(n, bool)
        self.since = zero
        self.kp_s, self.ki_s = self.k_p, self.k_i
        dc = cfg["dutycycle"]
        self.dc = {k: c(v) for k, v in dc.items()}
        self.level = c(np.full(n, dc["n_levels"]))
        det = cfg["detector"]
        self.det = {k: c(v) for k, v in det.items()}
        self.sig0 = c(p["noise_scale"] * c(np.sqrt(p["n_sockets"])))
        self.pred_l = c(p["K_L"] * self.lin(p["pcap_max"]))
        self.level_r = zero
        self.m_pos, self.m_neg = zero, zero
        self.cooldown = c(np.full(n, det["min_gap"]))

    def c(self, x):
        return np.asarray(x).astype(self.d)

    def lin(self, cap):
        p, c = self.p, self.c
        return c(-c(np.exp(c(-c(p["alpha"] * c(c(c(p["a"] * cap) + p["b"])
                                                 - p["beta"]))))))

    def static(self, cap):
        p, c = self.p, self.c
        return c(p["K_L"] * c(c(1.0) + self.lin(cap)))

    def _pi(self, progress, k_p, k_i, dt):
        p, c = self.p, self.c
        err = c(self.setpoint - progress)
        cmd_l = c(c(c(c(c(k_i * dt) + k_p) * err) - c(k_p * self.prev_err))
                  + self.prev_l)
        cmd_l = np.clip(cmd_l, self.lin(p["pcap_min"]),
                        self.lin(p["pcap_max"]))
        power = c(p["beta"] - c(c(np.log(c(-cmd_l))) / p["alpha"]))
        return err, cmd_l, c(c(power - p["b"]) / p["a"])

    def _rls(self, progress, dt):
        """One RLS update of theta = (theta1, theta2) in
        progress_L[i+1] = theta1 pcap_L[i] + theta2 progress_L[i], the
        trace clamp on P, and the dwell-gated gain re-placement."""
        c, r, p = self.c, self.rls, self.p
        y = c(progress - p["K_L"])
        phi, P, th = self.phi, self.P, self.theta
        err = c(y - c(c(phi[:, 0] * th[:, 0]) + c(phi[:, 1] * th[:, 1])))
        Pphi = np.stack([c(c(P[:, i, 0] * phi[:, 0])
                           + c(P[:, i, 1] * phi[:, 1])) for i in (0, 1)], 1)
        denom = c(r["lam"] + c(c(phi[:, 0] * Pphi[:, 0])
                               + c(phi[:, 1] * Pphi[:, 1])))
        k = c(Pphi / denom[:, None])
        phiP = np.stack([c(c(phi[:, 0] * P[:, 0, i])
                           + c(phi[:, 1] * P[:, 1, i])) for i in (0, 1)], 1)
        h = self.has_prev
        th = np.where(h[:, None], c(th + c(k * err[:, None])), th)
        P = np.where(h[:, None, None],
                     c(c(P - c(k[:, :, None] * phiP[:, None, :]))
                       / r["lam"]), P)
        tr = c(P[:, 0, 0] + P[:, 1, 1])
        P = np.where((tr > r["p_trace_max"])[:, None, None],
                     c(P * c(r["p_trace_max"] / tr)[:, None, None]), P)
        th2 = np.clip(th[:, 1], c(1e-3), c(1.0 - 1e-3))
        tau_hat = c(c(dt * th2) / c(c(1.0) - th2))
        kl_hat = np.clip(c(c(th[:, 0] * c(dt + tau_hat)) / dt),
                         c(p["K_L"] / r["kl_clamp"]),
                         c(p["K_L"] * r["kl_clamp"]))
        since = c(self.since + c(1.0))
        place = since >= r["dwell"]
        kp = np.where(place, c(tau_hat / c(kl_hat * self.tau_obj)), self.kp_s)
        ki = np.where(place, c(c(1.0) / c(kl_hat * self.tau_obj)), self.ki_s)
        self.since = np.where(place, c(0.0), since)
        self.theta, self.P = th, P
        self.phi = np.stack([self.prev_l, y], 1)
        self.has_prev = np.ones_like(h)
        self.kp_s, self.ki_s = kp, ki
        return kp, ki

    def _detector(self, progress, dt):
        c, q, p = self.c, self.det, self.p
        w = c(dt / c(dt + p["tau"]))
        pred_l = c(c(c(p["K_L"] * w) * self.lin(self.applied))
                   + c(c(c(1.0) - w) * self.pred_l))
        resid = c(progress - c(pred_l + p["K_L"]))
        sigma = c(np.sqrt(c(c(c(self.sig0 * self.sig0)
                              + c(np.maximum(c(pred_l + p["K_L"]), c(1.0))
                                  / dt))
                            + c(c(q["level_slack"] * self.level_r) ** 2))))
        z = c(c(resid - self.level_r) / np.maximum(sigma, c(1e-6)))
        armed = self.cooldown <= 0
        m_pos = np.where(armed, np.maximum(c(0.0), c(c(self.m_pos + z)
                                                     - q["drift"])), c(0.0))
        m_neg = np.where(armed, np.maximum(c(0.0), c(c(self.m_neg - z)
                                                     - q["drift"])), c(0.0))
        alarm = armed & ((m_pos > q["threshold"]) | (m_neg > q["threshold"]))
        alarm &= self.detect
        on = self.detect
        eta = q["level_eta"]
        level = np.where(alarm, resid, c(c(c(c(1.0) - eta) * self.level_r)
                                         + c(eta * resid)))
        self.pred_l = np.where(on, pred_l, self.pred_l)
        self.level_r = np.where(on, level, self.level_r)
        self.m_pos = np.where(on, np.where(alarm, c(0.0), m_pos), self.m_pos)
        self.m_neg = np.where(on, np.where(alarm, c(0.0), m_neg), self.m_neg)
        self.cooldown = np.where(on, np.where(
            alarm, q["min_gap"], np.maximum(c(self.cooldown - c(1.0)),
                                            c(0.0))), self.cooldown)
        return alarm

    def step(self, progress, dt: float = 1.0):
        """One period from Eq. 1 progress -> (applied cap, alarm)."""
        # every tenant runs every law and keeps its own; the others'
        # arithmetic may overflow harmlessly
        with np.errstate(all="ignore"):
            return self._step(progress, dt)

    def _step(self, progress, dt):
        c, p = self.c, self.p
        progress = c(progress)
        dt = c(dt)
        alarm = self._detector(progress, dt)
        rls = self.kind == PI_RLS
        kp, ki = self._rls(progress, dt)
        kp = np.where(rls, kp, self.k_p)
        ki = np.where(rls, ki, self.k_i)
        err, cmd_l, pi_cap = self._pi(progress, kp, ki, dt)
        d = self.dc
        rel = c(progress / np.maximum(self.setpoint, c(1e-9)))
        lv = np.where(rel > c(c(1.0) + d["deadband"]),
                      c(self.level - d["down_step"]),
                      np.where(rel < c(c(1.0) - d["deadband"]),
                               c(self.level + d["up_step"]), self.level))
        lv = np.clip(np.round(lv), d["min_level"], d["n_levels"]).astype(
            self.d)
        u = c(c(lv - d["min_level"])
              / np.maximum(c(d["n_levels"] - d["min_level"]), c(1.0)))
        dc_cap = c(p["pcap_min"] + c(u * c(p["pcap_max"] - p["pcap_min"])))
        duty = self.kind == DUTY
        self.prev_err = np.where(duty, self.prev_err, err)
        self.prev_l = np.where(duty, self.prev_l, cmd_l)
        self.level = np.where(duty, lv, self.level)
        cap = np.where(duty, dc_cap, pi_cap)
        self.applied = np.clip(cap, p["pcap_min"], p["pcap_max"])
        return (np.asarray(self.applied, np.float64),
                np.asarray(alarm, bool))
