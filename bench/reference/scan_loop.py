"""Plain reference of one closed-loop run of the scan engine: phased
plant, scripted telemetry faults, guarded mixed-policy control.

Written from the model and independent of the program: numpy, vectorised
over runs, in any floating dtype (float64 for the reference, bfloat16 for
the control that has to fail). Every intermediate is rounded to that
dtype. Per control period of ``dt`` seconds, for a live run at time t:

* phases: a cyclic script of plant variants, each the run's plant with
  some fields scaled; the variant active at t drives the plant.
* plant (paper section 4): power = a * pcap + b; Eq. 3 on the linearised
  cap; measured progress max(0, clean + sigma z) or the drop level inside
  an exogenous drop; measured power = power + power_noise z.
* heartbeats: n ~ Poisson(progress * dt), evenly spaced; a heartbeat
  blackout keeps floor(n (1 - share)) of them. Eq. 1's median of their
  rates has the closed form of `window_median`.
* a frozen meter repeats the last reading taken outside the freeze.
* guard: a progress signal that is not finite, not positive or above
  ``outlier_mult`` x setpoint is invalid; after ``hold_k`` invalid
  periods in a row the cap holds, after ``failsafe_k`` it is pcap_max;
  while held the policy and detector state stand still; the first valid
  signal after a fail-safe resets the policy (its phase-change reaction).
* detector: the design model's Eq. 3 replay of the applied cap, a
  two-sided Page-Hinkley test on the residual's deviation from its slow
  level, a refractory window after each alarm; an alarm resets the
  policy before it steps.
* policies: Eq. 4 PI; PI whose gains an RLS estimate of the first-order
  model re-places every ``dwell`` periods (reset: covariance back to
  100 I, regressor dropped, re-placement due at once); a duty-cycle
  ladder of cap levels.
* a run stops when work >= total_work or t >= max_time; the summary
  averages the observed progress and the measured power over its live
  periods.

Random draws are inputs: `noise.ScanDraws` gives each period's four
plant draws and the heartbeat count for a given mean, from the run's
seed by the engine's key contract.
"""
from __future__ import annotations

import numpy as np

FIELDS = ("exec_time", "energy", "work", "progress_mean", "power_mean",
          "detections", "invalid_signals", "failsafe_periods",
          "guard_resets")
# fields that count events, with the floor of their gap
COUNTS = {"detections": 1.0, "invalid_signals": 1.0,
          "failsafe_periods": 1.0, "guard_resets": 1.0}
PI, PI_RLS, DUTY = 0, 1, 2
POLICIES = {"pi": PI, "pi_rls": PI_RLS, "dutycycle": DUTY}


def window_median(n, anchor_gap, has_anchor, dt, c):
    """Eq. 1's median for ``n`` beats evenly spaced in one period: n - 1
    in-window rates of n/dt and one reaching back ``anchor_gap`` before
    the window to the previous beat (missing without an anchor)."""
    r = c(n / dt)
    r_first = c(c(1.0) / np.maximum(
        c(anchor_gap + c(c(c(0.5) * dt) / np.maximum(n, c(1.0)))), c(1e-9)))
    with_anchor = np.where(n >= 3, r, np.where(
        n == 2, c(c(0.5) * c(r + r_first)),
        np.where(n == 1, r_first, c(0.0))))
    no_anchor = np.where(n >= 2, r, c(0.0))
    return c(np.where(has_anchor, with_anchor, no_anchor))


def _sel(mask, new: dict, old: dict) -> dict:
    return {k: np.where(mask, new[k], old[k]) for k in old}


class Runs:
    """Closed-loop runs side by side.

    ``plants``: per-run plant parameters (S,) keyed by a, b, alpha, beta,
    K_L, tau, pcap_min, pcap_max, n_sockets, noise_scale, power_noise,
    drop_prob, drop_exit_prob, drop_level. ``setpoint`` (S,). ``kind``
    (S,) policy index. ``cfg``: tau_obj, rls, dutycycle, detector, guard
    settings; ``phases`` a list of (duration, {field: factor}), cyclic;
    ``faults`` {"period", "windows": [{kind, start, duration, p1}]} with
    kinds hb_dropout and meter_freeze.
    """

    def __init__(self, plants, setpoint, kind, cfg, phases, faults,
                 dtype=np.float64):
        self.d = np.dtype(dtype)
        c = self.c
        self.p = p = {k: c(v) for k, v in plants.items()}
        self.kind = np.asarray(kind)
        self.sp = c(setpoint)
        tau_obj = c(cfg["tau_obj"])
        self.k_p = c(p["tau"] / c(p["K_L"] * tau_obj))
        self.k_i = c(c(1.0) / c(p["K_L"] * tau_obj))
        self.rls = {k: c(v) for k, v in cfg["rls"].items()}
        self.rls_tau_obj = c(c(1.0) / c(p["K_L"] * self.k_i))
        self.dc = {k: c(v) for k, v in cfg["dutycycle"].items()}
        self.det = {k: c(v) for k, v in cfg["detector"].items()}
        self.guard = {k: c(v) for k, v in cfg["guard"].items()}
        self.phase_plants, ends = [], []
        for dur, scale in phases:
            self.phase_plants.append(
                {k: c(v * scale[k]) if k in scale else v
                 for k, v in p.items()})
            ends.append((ends[-1] if ends else 0.0) + dur)
        self.ends = np.asarray(ends, np.float64)
        self.faults = faults
        for w in faults["windows"]:
            if w["kind"] not in ("hb_dropout", "meter_freeze"):
                raise ValueError(f"no reference for fault {w['kind']!r}")

    def c(self, x):
        return np.asarray(x).astype(self.d)

    def lin(self, cap, p=None):
        p, c = p or self.p, self.c
        return c(-c(np.exp(c(-c(p["alpha"] * c(c(c(p["a"] * cap) + p["b"])
                                                 - p["beta"]))))))

    # ---- the policies ------------------------------------------------------
    def _init_policy(self):
        c, p, n = self.c, self.p, len(self.kind)
        zero = c(np.zeros(n))
        return {"prev_err": zero, "prev_l": self.lin(p["pcap_max"]),
                "th0": c(p["K_L"] * c(0.5)), "th1": c(np.full(n, 0.5)),
                "P00": c(np.full(n, 100.0)), "P01": zero, "P10": zero,
                "P11": c(np.full(n, 100.0)), "phi0": zero, "phi1": zero,
                "has_prev": np.zeros(n, bool), "since": zero,
                "kp": self.k_p, "ki": self.k_i,
                "level": c(np.full(n, self.dc["n_levels"]))}

    def _reset(self, s):
        """The phase-change reaction: adaptive PI restarts its estimator
        and re-places its gains at once; the others do not change."""
        rls = self.kind == PI_RLS
        c = self.c
        new = dict(s)
        for k, v in (("P00", 100.0), ("P01", 0.0), ("P10", 0.0),
                     ("P11", 100.0)):
            new[k] = np.where(rls, c(v), s[k])
        new["has_prev"] = np.where(rls, False, s["has_prev"])
        new["since"] = np.where(rls, self.rls["dwell"], s["since"])
        return new

    def _policy(self, s, progress, dt):
        c, p, r = self.c, self.p, self.rls
        new = dict(s)
        # RLS on progress_L[i+1] = th0 pcap_L[i] + th1 progress_L[i]
        y = c(progress - p["K_L"])
        phi0, phi1 = s["phi0"], s["phi1"]
        err = c(y - c(c(phi0 * s["th0"]) + c(phi1 * s["th1"])))
        Pphi0 = c(c(s["P00"] * phi0) + c(s["P01"] * phi1))
        Pphi1 = c(c(s["P10"] * phi0) + c(s["P11"] * phi1))
        phiP0 = c(c(phi0 * s["P00"]) + c(phi1 * s["P10"]))
        phiP1 = c(c(phi0 * s["P01"]) + c(phi1 * s["P11"]))
        denom = c(r["lam"] + c(c(phiP0 * phi0) + c(phiP1 * phi1)))
        k0, k1 = c(Pphi0 / denom), c(Pphi1 / denom)
        h = s["has_prev"]
        th0 = np.where(h, c(s["th0"] + c(k0 * err)), s["th0"])
        th1 = np.where(h, c(s["th1"] + c(k1 * err)), s["th1"])
        P = {"P00": c(c(s["P00"] - c(k0 * phiP0)) / r["lam"]),
             "P01": c(c(s["P01"] - c(k0 * phiP1)) / r["lam"]),
             "P10": c(c(s["P10"] - c(k1 * phiP0)) / r["lam"]),
             "P11": c(c(s["P11"] - c(k1 * phiP1)) / r["lam"])}
        P = {k: np.where(h, v, s[k]) for k, v in P.items()}
        tr = c(P["P00"] + P["P11"])
        big = tr > r["p_trace_max"]
        P = {k: np.where(big, c(v * c(r["p_trace_max"] / tr)), v)
             for k, v in P.items()}
        t2 = np.clip(th1, c(1e-3), c(1.0 - 1e-3))
        tau_hat = c(c(dt * t2) / c(c(1.0) - t2))
        kl_hat = np.clip(c(c(th0 * c(dt + tau_hat)) / dt),
                         c(p["K_L"] / r["kl_clamp"]),
                         c(p["K_L"] * r["kl_clamp"]))
        since = c(s["since"] + c(1.0))
        place = since >= r["dwell"]
        kp = np.where(place, c(tau_hat / c(kl_hat * self.rls_tau_obj)),
                      s["kp"])
        ki = np.where(place, c(c(1.0) / c(kl_hat * self.rls_tau_obj)),
                      s["ki"])
        rls = self.kind == PI_RLS
        for k, v in (("th0", th0), ("th1", th1), ("since",
                     np.where(place, c(0.0), since)), ("kp", kp),
                     ("ki", ki), ("phi0", s["prev_l"]), ("phi1", y),
                     *P.items()):
            new[k] = np.where(rls, v, s[k])
        new["has_prev"] = np.where(rls, True, s["has_prev"])
        # Eq. 4 PI on the linearised cap (scheduled gains for adaptive PI)
        k_p = np.where(rls, kp, self.k_p)
        k_i = np.where(rls, ki, self.k_i)
        e = c(self.sp - progress)
        cmd_l = c(c(c(c(c(k_i * dt) + k_p) * e) - c(k_p * s["prev_err"]))
                  + s["prev_l"])
        cmd_l = np.clip(cmd_l, self.lin(p["pcap_min"]),
                        self.lin(p["pcap_max"]))
        power = c(p["beta"] - c(c(np.log(c(-cmd_l))) / p["alpha"]))
        pi_cap = c(c(power - p["b"]) / p["a"])
        # duty-cycle ladder
        d = self.dc
        rel = c(progress / np.maximum(self.sp, c(1e-9)))
        lv = np.where(rel > c(c(1.0) + d["deadband"]),
                      c(s["level"] - d["down_step"]),
                      np.where(rel < c(c(1.0) - d["deadband"]),
                               c(s["level"] + d["up_step"]), s["level"]))
        lv = c(np.clip(np.round(lv), d["min_level"], d["n_levels"]))
        u = c(c(lv - d["min_level"])
              / np.maximum(c(d["n_levels"] - d["min_level"]), c(1.0)))
        dc_cap = c(p["pcap_min"] + c(u * c(p["pcap_max"] - p["pcap_min"])))
        duty = self.kind == DUTY
        new["prev_err"] = np.where(duty, s["prev_err"], e)
        new["prev_l"] = np.where(duty, s["prev_l"], cmd_l)
        new["level"] = np.where(duty, lv, s["level"])
        return new, np.where(duty, dc_cap, pi_cap)

    # ---- the detector ------------------------------------------------------
    def _init_detector(self):
        c, p = self.c, self.p
        zero = c(np.zeros(len(self.kind)))
        return {"pred_l": c(p["K_L"] * self.lin(p["pcap_max"])),
                "level": zero, "m_pos": zero, "m_neg": zero,
                "cooldown": c(zero + self.det["min_gap"]), "n": zero}

    def _detect(self, s, progress, cap, dt):
        c, q, p = self.c, self.det, self.p
        w = c(dt / c(dt + p["tau"]))
        pred_l = c(c(c(p["K_L"] * w) * self.lin(cap))
                   + c(c(c(1.0) - w) * s["pred_l"]))
        pred = c(pred_l + p["K_L"])
        resid = c(progress - pred)
        sig0 = c(p["noise_scale"] * c(np.sqrt(p["n_sockets"])))
        sigma = c(np.sqrt(c(c(c(sig0 * sig0) + c(np.maximum(pred, c(1.0))
                                                / dt))
                            + c(c(q["level_slack"] * s["level"]) ** 2))))
        z = c(c(resid - s["level"]) / np.maximum(sigma, c(1e-6)))
        armed = s["cooldown"] <= 0
        m_pos = np.where(armed, np.maximum(c(0.0), c(c(s["m_pos"] + z)
                                                     - q["drift"])), c(0.0))
        m_neg = np.where(armed, np.maximum(c(0.0), c(c(s["m_neg"] - z)
                                                     - q["drift"])), c(0.0))
        alarm = armed & ((m_pos > q["threshold"]) | (m_neg > q["threshold"]))
        eta = q["level_eta"]
        return {"pred_l": pred_l,
                "level": np.where(alarm, resid, c(c(c(c(1.0) - eta)
                                                    * s["level"])
                                                  + c(eta * resid))),
                "m_pos": np.where(alarm, c(0.0), m_pos),
                "m_neg": np.where(alarm, c(0.0), m_neg),
                "cooldown": np.where(alarm, q["min_gap"], np.maximum(
                    c(s["cooldown"] - c(1.0)), c(0.0))),
                "n": c(s["n"] + alarm)}, alarm

    # ---- the closed loop ---------------------------------------------------
    def _active(self, t, period, spans):
        """Index of the span holding ``t`` (cyclic over ``period``)."""
        t_eff = np.mod(t, period) if period > 0 else t
        return np.searchsorted(spans, t_eff, side="right")

    def run(self, draws, *, total_work, max_time, dt) -> dict:
        with np.errstate(all="ignore"):
            return self._run(draws, total_work, max_time, dt)

    def _run(self, draws, total_work, max_time, dt):
        c, p = self.c, self.p
        S = len(self.kind)
        dt_ = c(dt)
        zero = c(np.zeros(S))
        ph0 = self.phase_plants[0]
        pl = c(c(ph0["K_L"] * c(c(1.0) + self.lin(p["pcap_max"], ph0)))
               - ph0["K_L"])
        dropped = np.zeros(S, bool)
        energy = work = t = zero
        pcap = p["pcap_max"]
        anchor_gap, has_anchor = zero, np.zeros(S, bool)
        count = prog_sum = pow_sum = zero
        last_power = c(c(p["a"] * p["pcap_max"]) + p["b"])
        g = self.guard
        gs = {"stale": zero, "mode": zero, "last_pg": zero,
              "last_pw": zero, "invalid": zero, "failsafe": zero,
              "resets": zero}
        pol = self._init_policy()
        det = self._init_detector()
        done = np.zeros(S, bool)
        period = float(self.ends[-1])
        f_period = float(self.faults["period"])
        for step in range(draws.horizon):
            if done.all():
                break
            z_n, z_p, u_d, u_e = (c(x) for x in draws.plant(step))
            live = ~done
            t64 = np.asarray(t, np.float64)
            idx = np.minimum(self._active(t64, period, self.ends),
                             len(self.phase_plants) - 1)
            ph = {k: c(np.choose(idx, [q[k] for q in self.phase_plants]))
                  for k in p}
            # scripted faults active at t
            drop_share = np.zeros(S)
            freeze = np.zeros(S, bool)
            t_f = np.mod(t64, f_period) if f_period > 0 else t64
            for w in self.faults["windows"]:
                on = (t_f >= w["start"]) & (t_f < w["start"] + w["duration"])
                if w["kind"] == "hb_dropout":
                    drop_share = np.where(on, np.maximum(
                        drop_share, w.get("p1", 1.0)), drop_share)
                else:
                    freeze |= on
            # plant (Eq. 3) in the active phase
            cap = np.clip(pcap, ph["pcap_min"], ph["pcap_max"])
            w = c(dt_ / c(dt_ + ph["tau"]))
            new_pl = c(c(c(ph["K_L"] * w) * self.lin(cap, ph))
                       + c(c(c(1.0) - w) * pl))
            new_drop = np.where(dropped, ~(u_e < ph["drop_exit_prob"]),
                                u_d < ph["drop_prob"])
            clean = c(new_pl + ph["K_L"])
            sigma = c(ph["noise_scale"] * c(np.sqrt(ph["n_sockets"])))
            meas = c(np.maximum(c(0.0), c(np.where(new_drop, ph["drop_level"],
                                                   clean) + c(sigma * z_n))))
            power = c(c(ph["a"] * cap) + ph["b"])
            power_meas = c(power + c(ph["power_noise"] * z_p))
            new_energy = c(energy + c(power * dt_))
            new_work = c(work + c(meas * dt_))
            new_t = c(t + dt_)
            # heartbeats (a blackout keeps a share of them) and Eq. 1
            n = c(draws.counts(step, c(meas * dt_)))
            n = np.where(drop_share > 0, c(np.floor(c(n * c(
                1.0 - np.clip(drop_share, 0.0, 1.0))))), n)
            progress = window_median(n, anchor_gap, has_anchor, dt_, c)
            new_gap = np.where(n > 0, c(c(c(0.5) * dt_) / np.maximum(
                n, c(1.0))), c(anchor_gap + dt_))
            new_has = has_anchor | (n > 0)
            # the meter as the controller reads it
            power_obs = np.where(freeze, last_power, power_meas)
            new_last_power = power_obs
            # guard: signal sentinels and the stale-signal ladder
            mult = g["outlier_mult"]
            p_ok = (np.isfinite(progress) & (progress > 0)
                    & (progress <= c(mult * np.maximum(self.sp, c(1e-6)))))
            pg = np.where(p_ok, progress, gs["last_pg"])
            w_hi = c(mult * c(c(p["a"] * p["pcap_max"]) + p["b"]))
            pw_ok = (np.isfinite(power_obs) & (power_obs >= 0)
                     & (power_obs <= w_hi))
            stale = np.where(p_ok, c(0.0), c(gs["stale"] + c(1.0)))
            mode = c(np.where(stale > g["failsafe_k"], 2.0,
                              np.where(stale > g["hold_k"], 1.0, 0.0)))
            recov = (gs["mode"] >= 2) & p_ok & (g["recover_reset"] > 0.5)
            pol_in = _sel(recov, self._reset(pol), pol)
            new_det, alarm = self._detect(det, pg, pcap, dt_)
            pol_step, cmd = self._policy(_sel(alarm, self._reset(pol_in),
                                              pol_in), pg, dt_)
            diverged = ~np.all([np.isfinite(v) for v in pol_step.values()
                                if v.dtype != bool], axis=0)
            pol_step = _sel(diverged, self._reset(pol_in), pol_step)
            cmd = np.where(diverged, p["pcap_max"], cmd)
            engaged = mode >= 1
            new_pcap = c(np.where(mode >= 2, p["pcap_max"],
                                  np.where(engaged, pcap, cmd)))
            pol_step = _sel(engaged, pol, pol_step)
            new_det = _sel(engaged, det, new_det)
            new_gs = {"stale": stale, "mode": mode,
                      "last_pg": np.where(p_ok, progress, gs["last_pg"]),
                      "last_pw": np.where(pw_ok, power_obs, gs["last_pw"]),
                      "invalid": c(c(gs["invalid"] + c(~p_ok))
                                   + c(~pw_ok)),
                      "failsafe": c(gs["failsafe"] + c(mode >= 2)),
                      "resets": c(gs["resets"] + c(recov | diverged))}
            # live runs advance; finished ones stay frozen
            pl = np.where(live, new_pl, pl)
            dropped = np.where(live, new_drop, dropped)
            energy = np.where(live, new_energy, energy)
            work = np.where(live, new_work, work)
            t = np.where(live, new_t, t)
            pcap = np.where(live, new_pcap, pcap)
            anchor_gap = np.where(live, new_gap, anchor_gap)
            has_anchor = np.where(live, new_has, has_anchor)
            last_power = np.where(live, new_last_power, last_power)
            gs = _sel(live, new_gs, gs)
            pol = _sel(live, pol_step, pol)
            det = _sel(live, new_det, det)
            count = np.where(live, c(count + c(1.0)), count)
            prog_sum = np.where(live, c(prog_sum + progress), prog_sum)
            pow_sum = np.where(live, c(pow_sum + power_meas), pow_sum)
            done = done | (work >= c(total_work)) | (t >= c(max_time - 1e-6))
        m = np.maximum(count, c(1.0))
        out = {"exec_time": t, "energy": energy, "work": work,
               "progress_mean": c(prog_sum / m),
               "power_mean": c(pow_sum / m), "detections": det["n"],
               "invalid_signals": gs["invalid"],
               "failsafe_periods": gs["failsafe"],
               "guard_resets": gs["resets"]}
        return {k: np.asarray(v, np.float64) for k, v in out.items()}
