"""Public closed-loop kernel op: noise pre-draw, padding, jit wrapper.

`closed_loop_sim` is the executor-facing entry: packed per-run profile /
gain rows and PRNG keys in, (traces, final-carry dict) out — the same
contract as `ref.closed_loop_ref`, with the noise tensor drawn here from
the per-run keys (one five-channel stream per run, independent of batch
layout, so chunked execution is bit-for-bit identical to one-shot).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.closed_loop import ref as R
from repro.kernels.closed_loop.kernel import (HIST_KEYS,
                                             closed_loop_pallas,
                                             unpack_final)
from repro.obs import metrics as obs_metrics


def draw_noise(keys: jax.Array, T: int) -> jax.Array:
    """Per-run noise streams: keys (B, 2) uint32 -> (T, 5, B) f32.

    Channels (`ref.NZ_*`): progress-noise z, power-noise z, drop-enter
    u, drop-exit u, heartbeat z. Each run's stream depends only on its
    own key, never on the batch it rides in.
    """

    def one(k):
        kz, kp, kd, ke, kh = jax.random.split(k, 5)
        return jnp.stack([
            jax.random.normal(kz, (T,)),
            jax.random.normal(kp, (T,)),
            jax.random.uniform(kd, (T,)),
            jax.random.uniform(ke, (T,)),
            jax.random.normal(kh, (T,)),
        ], axis=0)                                     # (5, T)

    return jax.vmap(one)(keys).transpose(2, 1, 0)      # (T, 5, B)


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def runs_counter():
    """``closed_loop_runs_total{path}``: runs of the closed loop by engine
    path — ``native`` (compiled kernel), ``interpret`` or ``ref``, each
    counted as `closed_loop_sim` takes the runs, and ``scan`` (the scan
    engine, counted by `repro.core.sim.sweep` once it has the result)."""
    return obs_metrics.get_registry().counter(
        "closed_loop_runs_total",
        "runs of the closed loop, by engine path",
        labelnames=("path",))


def _per_run(final):
    """Lane-major final carry ((1, B) rows, (BINS, B) histograms) -> the
    per-run dict of (B,) leaves and (B, BINS) histograms."""
    return {k: (v.T if k in HIST_KEYS else v[0]) for k, v in final.items()}


@functools.partial(jax.jit, static_argnames=("T", "collect", "block_b",
                                             "chunk_t", "interpret",
                                             "use_ref"))
def _run(prof, gains, keys, scalars, *, T: int, collect: bool,
         block_b: int, chunk_t: int, interpret: bool, use_ref: bool):
    noise = draw_noise(keys, T)
    prof, gains = prof.T, gains.T  # runs onto the lane axis
    if use_ref:
        traces, final = R.closed_loop_ref(
            prof, gains, noise, scalars[0], scalars[1], scalars[2],
            scalars[3], collect=collect)
    else:
        traces, blocks = closed_loop_pallas(
            prof, gains, noise, scalars, collect=collect, block_b=block_b,
            chunk_t=chunk_t, interpret=interpret)
        final = unpack_final(*blocks)
    return traces, _per_run(final)


def closed_loop_sim(prof, gains, keys, *, total_work, max_time,
                    dt: float = 1.0, summary_from: float = 0.0,
                    collect: bool = True, block_b: int = 128,
                    chunk_t: int = 64, use_ref: bool = False):
    """Fused closed-loop runs for a flat batch.

    prof (B, 14) / gains (B, 9) packed rows, keys (B, 2) PRNG keys ->
    (traces | None, final): traces are (T, B) f32 per `ref.TRACE_KEYS`
    with T = ceil(max_time / dt) (rounded up to the kernel's time
    chunk), final the per-run carry dict of (B,) leaves + (B, BINS)
    histograms. The kernel is compiled natively on a TPU and runs
    through the Pallas interpreter on any other platform (CPU CI runs
    the same kernel body); there is no way to interpret it on the chip.
    ``use_ref=True`` swaps in the jnp oracle — same contract, no Pallas
    — for A/B tests.

    The batch is padded with replicas of its first run up to whole run
    tiles: 128 lanes on the chip (8 elsewhere), a single tile when the
    batch is smaller than ``block_b``.
    """
    interpret = jax.default_backend() != "tpu"
    B = prof.shape[0]
    block_b = min(block_b, _round_up(B, 8 if interpret else 128))
    Bp = _round_up(B, block_b)
    T = _round_up(int(-(-max_time // dt)), chunk_t)
    pad = Bp - B
    if pad:
        rep = lambda x: jnp.concatenate(
            [x, jnp.broadcast_to(x[:1], (pad,) + x.shape[1:])])
        prof, gains, keys = rep(prof), rep(gains), rep(keys)
    scalars = jnp.asarray([total_work, max_time, dt, summary_from],
                          jnp.float32)
    runs_counter().inc(B, path="ref" if use_ref else
                       "interpret" if interpret else "native")
    traces, final = _run(jnp.asarray(prof, jnp.float32),
                         jnp.asarray(gains, jnp.float32),
                         jnp.asarray(keys), scalars, T=T,
                         collect=collect, block_b=block_b,
                         chunk_t=chunk_t, interpret=interpret,
                         use_ref=bool(use_ref))
    if pad:
        traces = None if traces is None else {k: v[:, :B]
                                              for k, v in traces.items()}
        final = {k: v[:B] for k, v in final.items()}
    return traces, final
