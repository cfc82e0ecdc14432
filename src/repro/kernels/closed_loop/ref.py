"""Pure-jnp oracle for the fused closed-loop kernel: `repro.core.sim.
engine_step`'s fixed-gain PI path transcribed as a `lax.scan`, with the
randomness EXTERNALIZED into a pre-drawn noise tensor.

The transcription covers exactly what the Pallas kernel fuses — the
static-plant, detector-free, single-branch ``("pi",)`` engine: plant
dynamics (Eq. 3 + heteroscedastic noise + exogenous drops), heartbeat
synthesis and the Eq. 1 window median, the Eq. 4 PI update with
anti-windup clamping, early-exit-by-mask freezing, and the online
summary reductions (count/moments/histograms). Every arithmetic op
appears in the same order as `engine_step`, so kernel-vs-ref agreement
is bit-level in interpret mode and the ref itself is validated against
`sim.sweep` statistically (same model, different RNG stream).

Two deliberate differences from the scan engine, shared with kernel.py:

* **Noise is an input.** The engine draws from a per-step key chain
  (`jax.random.split` inside the scan); the kernel path pre-draws one
  ``(T, 5, B)`` tensor of unit normals/uniforms per run key (see
  `ops.draw_noise`) — channels: progress noise z, power noise z, drop
  enter u, drop exit u, heartbeat z.

Layout: runs ride the LANE (last) axis everywhere. Packed parameters are
``(N_PROF, B)`` / ``(N_GAIN, B)``, so one field is a ``(1, B)`` row;
every carry leaf is a ``(1, B)`` row and the histograms are
``(BINS, B)``. The TPU kernel runs this same `step` on ``(., block_b)``
tiles, and Mosaic lays every per-run vector out the same way (one
sublane row of lanes) — no sublane/lane mixing, no in-kernel transpose.
* **Heartbeat counts use `heartbeat_count`** — a rounded-Gaussian
  approximation of the engine's Poisson draw (exact in distribution to
  O(1/sqrt(lam)); the paper-scale rates are 10-80 beats/period where
  the two are statistically indistinguishable). Reimplementing JAX's
  Poisson rejection sampler inside a kernel would buy nothing but the
  bit-pattern of a different RNG stream.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.plant import PROFILE_FIELDS

# Column indices into the packed rows (shared with kernel.py).
F = {name: i for i, name in enumerate(PROFILE_FIELDS)}
GAIN_FIELDS = ("k_p", "k_i", "setpoint", "pcap_min", "pcap_max",
               "a", "b", "alpha", "beta")
G = {name: i for i, name in enumerate(GAIN_FIELDS)}

# Noise channels (axis 1 of the (T, 5, B) noise tensor).
NZ_PROG, NZ_POW, NU_ENTER, NU_EXIT, NZ_HB = range(5)
N_NOISE = 5

# Online-summary histogram resolution — mirrors repro.core.sim.
PROG_BINS = 64
CAP_BINS = 32
PROG_HIST_SPAN = 1.5

TRACE_KEYS = ("t", "progress", "pcap", "power", "energy", "work", "valid")


def heartbeat_count(lam, z):
    """Heartbeat count from a unit normal: round(lam + sqrt(lam) z),
    floored at 0 — the kernel path's Poisson stand-in (matches mean and
    variance; exact for lam = 0)."""
    return jnp.maximum(0.0, jnp.floor(lam + jnp.sqrt(lam) * z + 0.5))


def window_median(n, anchor_gap, has_anchor, dt):
    """Closed-form Eq. 1 median — verbatim `sim._window_median`, with
    the count already float."""
    nf = jnp.maximum(n, 1.0)
    r = n / dt
    first_int = anchor_gap + 0.5 * dt / nf
    r_first = 1.0 / jnp.maximum(first_int, 1e-9)
    with_anchor = jnp.where(n >= 3, r,
                            jnp.where(n == 2, 0.5 * (r + r_first),
                                      jnp.where(n == 1, r_first, 0.0)))
    no_anchor = jnp.where(n >= 2, r, 0.0)
    return jnp.where(has_anchor, with_anchor, no_anchor)


def hist_index(x, lo, hi, nbins):
    """Bin index of x in [lo, hi) split into nbins — `sim._hist_add`'s
    index rule."""
    return jnp.clip(((x - lo) / (hi - lo) * nbins).astype(jnp.int32),
                    0, nbins - 1)


def _row(packed, index, name):
    """One packed field as a (1, B) row."""
    i = index[name]
    return packed[i:i + 1]


def _mask(cond):
    """Boolean -> 0/1 float."""
    return jnp.where(cond, 1.0, 0.0)


def _one_hot_add(hist, idx, w):
    """hist (BINS, B) + w at row idx of each lane (idx, w: (1, B))."""
    rows = jax.lax.broadcasted_iota(jnp.int32, hist.shape, 0)
    return hist + jnp.where(rows == idx, w, 0.0)


def init_state(prof, gains):
    """Fresh per-run carry from packed (N_PROF, B) profile and (N_GAIN, B)
    gain rows — `sim._default_init` for the PI branch, as a dict of
    (1, B) rows (plus the two (BINS, B) histograms)."""
    p = lambda name: _row(prof, F, name)
    g = lambda name: _row(gains, G, name)
    B = prof.shape[1]
    z = jnp.zeros((1, B), jnp.float32)
    pcap0 = p("pcap_max")
    # plant_init: progress_l0 = static_progress(pcap_max) - K_L
    #           = K_L * pcap_linearize(pcap_max)  (plant transform)
    pl0 = -jnp.exp(-p("alpha") * (p("a") * pcap0 + p("b") - p("beta")))
    # pi_init: prev_pcap_l anchored at the GAIN transform's pcap_max
    gl0 = -jnp.exp(-g("alpha") * (g("a") * g("pcap_max") + g("b")
                                  - g("beta")))
    return {
        "progress_l": p("K_L") * pl0,
        "dropped": z,
        "energy": z,
        "work": z,
        "prev_error": z,
        "prev_pcap_l": gl0,
        "pcap": pcap0,
        "anchor_gap": z,
        "has_anchor": z,
        "t": z,
        "steps": z,
        "done": z,
        "count": z,
        "progress_sum": z,
        "progress_sq_sum": z,
        "power_sum": z,
        "progress_hist": jnp.zeros((PROG_BINS, B), jnp.float32),
        "pcap_hist": jnp.zeros((CAP_BINS, B), jnp.float32),
    }


def step(prof, gains, c, noise_s, total_work, max_time, dt, summary_from):
    """One fused control period over a batch of runs — the engine_step
    transcription. ``noise_s`` is this step's (5, B) noise slab.
    Returns (new_carry, trace_row) with (1, B) leaves."""
    p = lambda name: _row(prof, F, name)
    g = lambda name: _row(gains, G, name)
    z_prog, z_pow, u_enter, u_exit, z_hb = (noise_s[i:i + 1] for i in
                                            range(N_NOISE))
    done = c["done"]
    live = 1.0 - done

    # ---- plant_step (Eq. 3 + noise + drops) -------------------------------
    pcap_app = jnp.clip(c["pcap"], p("pcap_min"), p("pcap_max"))
    pl = -jnp.exp(-p("alpha") * (p("a") * pcap_app + p("b") - p("beta")))
    w = dt / (dt + p("tau"))
    new_pl = p("K_L") * w * pl + (1.0 - w) * c["progress_l"]
    enter = _mask(u_enter < p("drop_prob"))
    exit_ = _mask(u_exit < p("drop_exit_prob"))
    dropped = jnp.where(c["dropped"] > 0, 1.0 - exit_, enter)
    clean = new_pl + p("K_L")
    meas_noise = (p("noise_scale") * jnp.sqrt(p("n_sockets")) * z_prog)
    progress_m = jnp.maximum(
        0.0, jnp.where(dropped > 0, p("drop_level"), clean) + meas_noise)
    power_true = p("a") * pcap_app + p("b")
    power_m = power_true + p("power_noise") * z_pow
    energy = c["energy"] + power_true * dt
    work = c["work"] + progress_m * dt
    t = c["t"] + dt

    # ---- heartbeat synthesis + Eq. 1 window median ------------------------
    n = heartbeat_count(jnp.maximum(progress_m, 0.0) * dt, z_hb)
    progress = window_median(n, c["anchor_gap"], c["has_anchor"] > 0, dt)
    anchor_gap = jnp.where(n > 0, 0.5 * dt / jnp.maximum(n, 1.0),
                           c["anchor_gap"] + dt)
    has_anchor = jnp.maximum(c["has_anchor"], _mask(n > 0))

    # ---- Eq. 4 PI with anti-windup clamp ----------------------------------
    error = g("setpoint") - progress
    pcap_l = ((g("k_i") * dt + g("k_p")) * error
              - g("k_p") * c["prev_error"] + c["prev_pcap_l"])
    glin = lambda cap: -jnp.exp(-g("alpha") * (g("a") * cap + g("b")
                                               - g("beta")))
    lo_l, hi_l = glin(g("pcap_min")), glin(g("pcap_max"))
    # Eq. 2 image is negative and increasing in pcap: lo_l < hi_l
    pcap_l = jnp.clip(pcap_l, lo_l, hi_l)
    power_cmd = g("beta") - jnp.log(-pcap_l) / g("alpha")
    pcap_cmd = (power_cmd - g("b")) / g("a")

    # ---- early-exit-by-mask freeze ----------------------------------------
    frz = lambda new, old: jnp.where(done > 0, old, new)
    new_pl = frz(new_pl, c["progress_l"])
    dropped = frz(dropped, c["dropped"])
    energy = frz(energy, c["energy"])
    work = frz(work, c["work"])
    prev_error = frz(error, c["prev_error"])
    prev_pcap_l = frz(pcap_l, c["prev_pcap_l"])
    pcap_cmd = frz(pcap_cmd, c["pcap"])
    anchor_gap = frz(anchor_gap, c["anchor_gap"])
    has_anchor = frz(has_anchor, c["has_anchor"])
    t = frz(t, c["t"])
    progress = jnp.where(done > 0, 0.0, progress)
    power_out = jnp.where(done > 0, 0.0, power_m)

    # ---- online summary reductions ----------------------------------------
    acc = live * _mask(c["steps"] >= summary_from)
    pidx = hist_index(progress, 0.0, PROG_HIST_SPAN * p("K_L"), PROG_BINS)
    cidx = hist_index(pcap_cmd, p("pcap_min"), p("pcap_max"), CAP_BINS)
    prog_hist = _one_hot_add(c["progress_hist"], pidx, acc)
    pcap_hist = _one_hot_add(c["pcap_hist"], cidx, acc)

    new_done = jnp.maximum(done, jnp.maximum(
        _mask(work >= total_work), _mask(t >= max_time - 1e-6)))
    out = {"t": t, "progress": progress, "pcap": pcap_cmd,
           "power": power_out, "energy": energy, "work": work,
           "valid": live}
    new = {"progress_l": new_pl, "dropped": dropped, "energy": energy,
           "work": work, "prev_error": prev_error,
           "prev_pcap_l": prev_pcap_l, "pcap": pcap_cmd,
           "anchor_gap": anchor_gap, "has_anchor": has_anchor, "t": t,
           "steps": c["steps"] + live, "done": new_done,
           "count": c["count"] + acc,
           "progress_sum": c["progress_sum"] + acc * progress,
           "progress_sq_sum": c["progress_sq_sum"]
           + acc * progress * progress,
           "power_sum": c["power_sum"] + acc * power_out,
           "progress_hist": prog_hist, "pcap_hist": pcap_hist}
    return new, out


def closed_loop_ref(prof, gains, noise, total_work, max_time,
                    dt=1.0, summary_from=0.0, collect: bool = True
                    ) -> Tuple[Optional[dict], dict]:
    """prof (N_PROF, B), gains (N_GAIN, B), noise (T, 5, B) ->
    (traces, final), in the kernel's lane-major layout.

    Traces (collect=True) are (T, B) per key in `TRACE_KEYS`; `final` is
    the full carry dict of (1, B) rows plus the (BINS, B) histograms —
    what `kernel.closed_loop_pallas` computes, so `ops.closed_loop_sim`
    turns either into the same per-run dict.
    """
    prof = jnp.asarray(prof, jnp.float32)
    gains = jnp.asarray(gains, jnp.float32)
    noise = jnp.asarray(noise, jnp.float32)
    tw = jnp.float32(total_work)
    mt = jnp.float32(max_time)
    dt = jnp.float32(dt)
    sf = jnp.float32(summary_from)

    def body(c, noise_s):
        new, out = step(prof, gains, c, noise_s, tw, mt, dt, sf)
        return new, (out if collect else None)

    final, traces = jax.lax.scan(body, init_state(prof, gains), noise)
    if traces is not None:
        traces = {k: v[:, 0] for k, v in traces.items()}
    return traces, final
