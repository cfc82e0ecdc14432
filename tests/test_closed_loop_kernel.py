"""Closed-loop Pallas mega-kernel vs its oracles.

Three rungs of equivalence:

1. kernel (interpret mode) == `ref.closed_loop_ref` — bit-for-bit, both
   trace and summary modes, across batch/blocking/horizon buckets and
   input dtypes (the kernel body IS the ref step, so this pins the
   blocking/residency plumbing: tile order, chunk carry, padding).
2. kernel summary-mode finals == its own trace-mode reductions.
3. `sweep(backend="pallas")` == `sweep(backend="scan")` statistically
   (same model, per-run noise externalized into a different RNG
   stream) — and exactly equal between chunkings of itself.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sim
from repro.core.controller import PIGains
from repro.core.plant import PROFILES
from repro.kernels.closed_loop.ops import closed_loop_sim
from repro.kernels.closed_loop import ref as R


def _rows(profile_names, epsilon=0.1, reps=1):
    """Packed (B, 14)/(B, 9) rows + keys for reps runs per profile."""
    profs = [PROFILES[n] for n in profile_names] * reps
    prof = jnp.stack([sim.profile_values(p) for p in profs])
    gains = jnp.stack([sim.gains_values(PIGains.from_model(p, epsilon))
                       for p in profs])
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(len(profs))])
    return prof, gains, keys


# (profiles, reps, max_time, total_work, block_b, chunk_t, collect)
CASES = [
    (("gros", "dahu"), 4, 96.0, 1e9, 8, 32, True),     # mixed plants
    (("yeti",), 16, 64.0, 1e9, 16, 16, True),          # drop events
    (("v5e-chip",), 4, 64.0, 1e9, 4, 64, False),       # high-rate, summary
    (("gros",), 8, 48.0, 150.0, 8, 16, True),          # early exit
    (("gros", "dahu", "yeti"), 2, 64.0, 1e9, 4, 32, False),  # pad B=6->8
]


@pytest.mark.parametrize(
    "profiles,reps,max_time,total_work,block_b,chunk_t,collect", CASES)
def test_kernel_matches_ref_bit_for_bit(profiles, reps, max_time,
                                        total_work, block_b, chunk_t,
                                        collect):
    prof, gains, keys = _rows(profiles, reps=reps)
    kw = dict(total_work=total_work, max_time=max_time,
              collect=collect, block_b=block_b, chunk_t=chunk_t)
    tr_k, fin_k = closed_loop_sim(prof, gains, keys, **kw)
    tr_r, fin_r = closed_loop_sim(prof, gains, keys, use_ref=True, **kw)
    if collect:
        for k in R.TRACE_KEYS:
            np.testing.assert_array_equal(np.asarray(tr_k[k]),
                                          np.asarray(tr_r[k]), err_msg=k)
    else:
        assert tr_k is None and tr_r is None
    for k in fin_r:
        np.testing.assert_array_equal(np.asarray(fin_k[k]),
                                      np.asarray(fin_r[k]), err_msg=k)
    assert float(np.asarray(fin_k["done"]).min()) == 1.0  # all finished


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_param_dtype_buckets(dtype):
    """Parameter rows arriving in lower precision are cast once on load;
    kernel and oracle must agree bit-for-bit either way."""
    prof, gains, keys = _rows(("gros", "dahu"), reps=2)
    prof, gains = prof.astype(dtype), gains.astype(dtype)
    kw = dict(total_work=1e9, max_time=64.0, block_b=4, chunk_t=32)
    tr_k, fin_k = closed_loop_sim(prof, gains, keys, **kw)
    tr_r, fin_r = closed_loop_sim(prof, gains, keys, use_ref=True, **kw)
    np.testing.assert_array_equal(np.asarray(tr_k["progress"]),
                                  np.asarray(tr_r["progress"]))
    np.testing.assert_array_equal(np.asarray(fin_k["energy"]),
                                  np.asarray(fin_r["energy"]))


def test_kernel_summary_matches_trace_reductions():
    prof, gains, keys = _rows(("gros",), reps=8)
    kw = dict(total_work=1e9, max_time=96.0, block_b=8, chunk_t=32)
    tr, fin_t = closed_loop_sim(prof, gains, keys, collect=True, **kw)
    _, fin_s = closed_loop_sim(prof, gains, keys, collect=False, **kw)
    for k in fin_t:
        np.testing.assert_array_equal(np.asarray(fin_t[k]),
                                      np.asarray(fin_s[k]), err_msg=k)
    valid = np.asarray(tr["valid"]) > 0
    prog = np.asarray(tr["progress"])
    np.testing.assert_allclose(
        np.asarray(fin_t["progress_sum"]),
        (prog * valid).sum(0), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(fin_t["count"]), valid.sum(0))
    # per-run histogram mass equals the live-step count
    np.testing.assert_allclose(
        np.asarray(fin_t["progress_hist"]).sum(-1), valid.sum(0))


def test_heartbeat_count_moments():
    """The rounded-Gaussian heartbeat stand-in matches the Poisson draw
    it replaces in mean and variance at paper-scale rates."""
    lam = 24.0
    z = jax.random.normal(jax.random.PRNGKey(0), (20000,))
    n = np.asarray(R.heartbeat_count(lam, z))
    assert n.min() >= 0
    assert n.mean() == pytest.approx(lam, rel=0.02)
    assert n.var() == pytest.approx(lam, rel=0.05)


def test_sweep_pallas_backend_matches_scan_statistically():
    """Same grid through both backends: per-run RNG streams differ, the
    closed-loop statistics must not (the controller regulates progress
    to the same setpoint at the same power)."""
    kw = dict(total_work=1e9, max_time=192.0, collect_traces=False,
              summary_warmup=30)
    seeds = range(8)
    ps = sim.sweep("gros", [0.1, 0.3], seeds, backend="pallas", **kw)
    ss = sim.sweep("gros", [0.1, 0.3], seeds, backend="scan", **kw)
    for k in ("progress_mean", "power_mean"):
        a = np.asarray(ps.summary[k]).mean(-1)   # average over seeds
        b = np.asarray(ss.summary[k]).mean(-1)
        np.testing.assert_allclose(a, b, rtol=0.05, err_msg=k)
    np.testing.assert_allclose(np.asarray(ps.energy).mean(-1),
                               np.asarray(ss.energy).mean(-1), rtol=0.05)


def test_sweep_pallas_chunked_equals_one_shot():
    """The kernel's per-run noise streams depend only on the run key, so
    chunked == one-shot is exact on the pallas backend too."""
    kw = dict(total_work=1e9, max_time=96.0, collect_traces=False)
    one = sim.sweep("gros", [0.1], range(6), backend="pallas", **kw)
    ch = sim.sweep("gros", [0.1], range(6), backend="pallas",
                   chunk_size=4, **kw)
    np.testing.assert_array_equal(np.asarray(one.exec_time),
                                  np.asarray(ch.exec_time))
    np.testing.assert_array_equal(np.asarray(one.summary["progress_hist"]),
                                  np.asarray(ch.summary["progress_hist"]))


def test_sweep_pallas_rejects_incapable_grids():
    from repro.core.adaptive import RLSConfig
    from repro.core.policies import PIPolicy
    with pytest.raises(ValueError, match="pallas"):
        sim.sweep("gros", [0.1], [0], total_work=100.0,
                  policies=PIPolicy(adaptive=RLSConfig()),
                  backend="pallas")
