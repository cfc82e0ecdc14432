"""Plain reference of one fixed-gain PI closed-loop run (Cerf et al. 2021).

Written from the paper's model and independent of the program: numpy,
vectorised over runs, in any floating dtype (float64 for the reference,
bfloat16 for the control that has to fail). Every intermediate is
rounded to that dtype.

Model, per control period of ``dt`` seconds (paper section 4):

* actuator: power = a * pcap + b, pcap clipped to [pcap_min, pcap_max];
* Eq. 2: pcap_L = -exp(-alpha (a pcap + b - beta));
* Eq. 3: progress_L += dt / (dt + tau) * (K_L pcap_L - progress_L),
  measured progress = max(0, progress_L + K_L + noise) with the noise
  sigma = noise_scale * sqrt(n_sockets), or drop_level inside an
  exogenous drop (a two-state chain: enter with drop_prob, leave with
  drop_exit_prob);
* heartbeats: n = round(lam + sqrt(lam) z), lam = progress * dt, evenly
  spaced in the period; Eq. 1's median of their rates has the closed
  form of `window_median`;
* Eq. 4: velocity-form PI on the linearised cap, clamped to the image of
  the actuator range, inverted through Eq. 2;
* a run stops when work >= total_work or t >= max_time; the summary
  averages Eq. 1 progress and measured power (true power plus
  power_noise * z) over its live periods.

The per-run noise (five channels per period) is an input: `noise.py`
draws it from each run's seed.
"""
from __future__ import annotations

import numpy as np

FIELDS = ("exec_time", "energy", "work", "progress_mean", "power_mean")
COUNTS = {}  # fields that count events, with the floor of their gap


def run(plants: dict, gains: dict, noise: np.ndarray, *, total_work: float,
        max_time: float, dt: float, dtype=np.float64) -> dict:
    """Closed-loop runs side by side.

    ``plants``: per-run plant parameters, arrays of shape (S,) keyed by
    a, b, alpha, beta, K_L, tau, pcap_min, pcap_max, n_sockets,
    noise_scale, power_noise, drop_prob, drop_exit_prob, drop_level.
    ``gains``: per-run k_p, k_i, setpoint (S,). ``noise``: (T, 5, S),
    channels z_progress, z_power, u_enter, u_exit, z_heartbeat.
    Returns the `FIELDS` as float64 arrays of shape (S,).
    """
    d = np.dtype(dtype)

    def c(x):
        return np.asarray(x).astype(d)

    p = {k: c(v) for k, v in plants.items()}
    g = {k: c(v) for k, v in gains.items()}
    dt_ = c(dt)
    zero = c(np.zeros_like(p["a"]))

    def lin(cap):
        power = c(c(p["a"] * cap) + p["b"])
        return c(-c(np.exp(c(-c(p["alpha"] * c(power - p["beta"]))))))

    lo_l, hi_l = lin(p["pcap_min"]), lin(p["pcap_max"])
    w = c(dt_ / c(dt_ + p["tau"]))
    sigma = c(p["noise_scale"] * c(np.sqrt(p["n_sockets"])))
    pl = c(p["K_L"] * lin(p["pcap_max"]))
    dropped = np.zeros(pl.shape, bool)
    energy, work, t = zero, zero, zero
    prev_err, prev_l = zero, lin(p["pcap_max"])
    pcap = p["pcap_max"]
    anchor_gap, has_anchor = zero, np.zeros(pl.shape, bool)
    count, prog_sum, pow_sum = zero, zero, zero
    done = np.zeros(pl.shape, bool)
    for s in range(noise.shape[0]):
        if done.all():
            break
        z_prog, z_pow, u_in, u_out, z_hb = (c(noise[s, i]) for i in range(5))
        live = ~done
        # plant (Eq. 3) with noise and exogenous drops
        cap = np.clip(pcap, p["pcap_min"], p["pcap_max"])
        new_pl = c(c(c(p["K_L"] * w) * lin(cap)) + c(c(1 - w) * pl))
        new_drop = np.where(dropped, ~(u_out < p["drop_exit_prob"]),
                            u_in < p["drop_prob"])
        clean = c(new_pl + p["K_L"])
        meas = c(np.maximum(
            zero, c(np.where(new_drop, p["drop_level"], clean)
                    + c(sigma * z_prog))))
        power = c(c(p["a"] * cap) + p["b"])
        new_energy = c(energy + c(power * dt_))
        new_work = c(work + c(meas * dt_))
        new_t = c(t + dt_)
        # heartbeats and the Eq. 1 median
        lam = c(meas * dt_)
        n = c(np.maximum(zero, np.floor(c(c(lam + c(c(np.sqrt(lam)) * z_hb))
                                          + c(0.5)))))
        progress = window_median(n, anchor_gap, has_anchor, dt_, c)
        new_gap = np.where(n > 0, c(c(0.5 * dt_) / np.maximum(n, c(1.0))),
                           c(anchor_gap + dt_))
        new_has = has_anchor | (n > 0)
        # Eq. 4 PI on the linearised cap, anti-windup clamp, Eq. 2 inverse
        err = c(g["setpoint"] - progress)
        cmd_l = c(c(c(c(c(g["k_i"] * dt_) + g["k_p"]) * err)
                    - c(g["k_p"] * prev_err)) + prev_l)
        cmd_l = np.clip(cmd_l, lo_l, hi_l)
        cmd_power = c(p["beta"] - c(c(np.log(c(-cmd_l))) / p["alpha"]))
        cmd = c(c(cmd_power - p["b"]) / p["a"])
        # live runs advance; finished ones stay frozen
        pl = np.where(live, new_pl, pl)
        dropped = np.where(live, new_drop, dropped)
        energy = np.where(live, new_energy, energy)
        work = np.where(live, new_work, work)
        t = np.where(live, new_t, t)
        prev_err = np.where(live, err, prev_err)
        prev_l = np.where(live, cmd_l, prev_l)
        pcap = np.where(live, cmd, pcap)
        anchor_gap = np.where(live, new_gap, anchor_gap)
        has_anchor = np.where(live, new_has, has_anchor)
        count = np.where(live, c(count + c(1.0)), count)
        prog_sum = np.where(live, c(prog_sum + progress), prog_sum)
        pow_sum = np.where(live, c(pow_sum + c(power + c(
            p["power_noise"] * z_pow))), pow_sum)
        done = done | (work >= c(total_work)) | (t >= c(max_time - 1e-6))
    n = np.maximum(count, c(1.0))
    out = {"exec_time": t, "energy": energy, "work": work,
           "progress_mean": c(prog_sum / n), "power_mean": c(pow_sum / n)}
    return {k: np.asarray(v, np.float64) for k, v in out.items()}


def window_median(n, anchor_gap, has_anchor, dt, c):
    """Eq. 1's median for ``n`` beats evenly spaced in one period.

    The rates are n - 1 in-window intervals of n/dt and one interval
    reaching back to the previous period's last beat (``anchor_gap``
    before the window opens); without an anchor that one is missing."""
    r = c(n / dt)
    r_first = c(c(1.0) / np.maximum(
        c(anchor_gap + c(c(0.5 * dt) / np.maximum(n, c(1.0)))), c(1e-9)))
    with_anchor = np.where(n >= 3, r, np.where(
        n == 2, c(c(0.5) * c(r + r_first)), np.where(n == 1, r_first,
                                                      c(0.0))))
    no_anchor = np.where(n >= 2, r, c(0.0))
    return c(np.where(has_anchor, with_anchor, no_anchor))
