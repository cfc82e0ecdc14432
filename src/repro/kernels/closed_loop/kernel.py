"""Fused closed-loop simulation Pallas kernel (TPU target).

One `pallas_call` marches a TILE of runs through the whole horizon:
grid ``(B // block_b, T // chunk_t)`` with the batch dim parallel and
the time dim innermost/sequential, the full per-run carry (plant state,
PI state, heartbeat window, online summary moments and histograms)
resident in VMEM output blocks between time chunks. Plant step, Eq. 1
window median, Eq. 4 PI update, actuator clamp, progress/energy
accumulation and the summary-mode online reductions all fuse into the
per-step body — the (T, grid) trace tensors the `lax.scan` engine
materializes in HBM never exist in summary mode, and in trace mode they
stream out chunk-by-chunk.

The per-step body IS `ref.step` — the `sim.engine_step` transcription —
called on the tile's vectors, so kernel-vs-oracle agreement is bit-level
by construction (the kernel contributes only the blocking/residency
schedule, not the math). Like the selective-scan kernel next door, the
recurrence is serial over time (`fori_loop`) and the hardware
parallelism is across the run lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.closed_loop import ref as R

N_PROF = len(R.F)
N_GAIN = len(R.G)

# Carry rows of the persistent state block, in `ref.init_state` order
# (histograms live in their own blocks).
STATE_KEYS = ("progress_l", "dropped", "energy", "work", "prev_error",
              "prev_pcap_l", "pcap", "anchor_gap", "has_anchor", "t",
              "steps", "done", "count", "progress_sum",
              "progress_sq_sum", "power_sum")
N_STATE = len(STATE_KEYS)
HIST_KEYS = ("progress_hist", "pcap_hist")


def unpack_final(state, phist, chist):
    """(N_STATE, B) carry block + (BINS, B) histogram blocks -> the `ref`
    carry dict of (1, B) rows and (BINS, B) histograms. Row slices of a
    value or of a ref alike, so the kernel reloads its persistent carry
    through it each time chunk."""
    c = {k: state[i:i + 1] for i, k in enumerate(STATE_KEYS)}
    c["progress_hist"] = phist[...]
    c["pcap_hist"] = chist[...]
    return c


def _store(state_ref, phist_ref, chist_ref, c):
    for i, k in enumerate(STATE_KEYS):
        state_ref[i:i + 1, :] = c[k]
    phist_ref[...] = c["progress_hist"]
    chist_ref[...] = c["pcap_hist"]


def _cl_kernel(scal_ref, prof_ref, gains_ref, noise_ref, state_ref,
               phist_ref, chist_ref, *trace_refs, chunk_t: int,
               collect: bool):
    tc = pl.program_id(1)
    prof = prof_ref[...].astype(jnp.float32)    # (N_PROF, block_b)
    gains = gains_ref[...].astype(jnp.float32)  # (N_GAIN, block_b)

    @pl.when(tc == 0)
    def _init():
        _store(state_ref, phist_ref, chist_ref, R.init_state(prof, gains))

    tw, mt, dt, sf = (scal_ref[i] for i in range(4))
    carry0 = unpack_final(state_ref, phist_ref, chist_ref)

    def body(s, c):
        noise_s = noise_ref[s].astype(jnp.float32)  # (N_NOISE, block_b)
        new, out = R.step(prof, gains, c, noise_s, tw, mt, dt, sf)
        if collect:
            for r, k in zip(trace_refs, R.TRACE_KEYS):
                r[pl.ds(s, 1), :] = out[k].astype(r.dtype)
        return new

    c = jax.lax.fori_loop(0, chunk_t, body, carry0)
    _store(state_ref, phist_ref, chist_ref, c)


def closed_loop_pallas(prof: jax.Array, gains: jax.Array,
                       noise: jax.Array, scalars: jax.Array, *,
                       collect: bool = True, block_b: int = 128,
                       chunk_t: int = 64, interpret: bool = False):
    """prof [N_PROF, B], gains [N_GAIN, B], noise [T, 5, B], scalars
    [total_work, max_time, dt, summary_from] -> (traces | None, final).

    Runs ride the lane axis (see `ref`). ``B`` must divide by
    ``block_b`` and ``T`` by ``chunk_t`` (ops.py pads); compiled for the
    chip, ``block_b`` must also be a multiple of the 128-lane tile or
    all of ``B``. Traces are a dict of (T, B) f32 arrays keyed
    `ref.TRACE_KEYS`; ``final`` is the (N_STATE, B) carry block plus the
    two (BINS, B) histogram blocks (`unpack_final` reads them).
    """
    T, n_noise, B = noise.shape
    assert n_noise == R.N_NOISE
    assert prof.shape == (N_PROF, B) and gains.shape == (N_GAIN, B)
    block_b = min(block_b, B)
    if B % block_b or T % chunk_t:
        raise ValueError(f"B={B} must divide by block_b={block_b} and "
                         f"T={T} by chunk_t={chunk_t}")
    if not interpret and block_b % 128 and block_b != B:
        raise ValueError(f"block_b={block_b} is not a multiple of the "
                         f"128-lane tile (B={B})")

    kernel = functools.partial(_cl_kernel, chunk_t=chunk_t,
                               collect=collect)
    out_shape = [
        jax.ShapeDtypeStruct((N_STATE, B), jnp.float32),
        jax.ShapeDtypeStruct((R.PROG_BINS, B), jnp.float32),
        jax.ShapeDtypeStruct((R.CAP_BINS, B), jnp.float32),
    ]
    out_specs = [
        pl.BlockSpec((N_STATE, block_b), lambda b, tc: (0, b)),
        pl.BlockSpec((R.PROG_BINS, block_b), lambda b, tc: (0, b)),
        pl.BlockSpec((R.CAP_BINS, block_b), lambda b, tc: (0, b)),
    ]
    if collect:
        out_shape += [jax.ShapeDtypeStruct((T, B), jnp.float32)
                      for _ in R.TRACE_KEYS]
        out_specs += [pl.BlockSpec((chunk_t, block_b),
                                   lambda b, tc: (tc, b))
                      for _ in R.TRACE_KEYS]

    outs = pl.pallas_call(
        kernel,
        grid=(B // block_b, T // chunk_t),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # scalars (4,)
            pl.BlockSpec((N_PROF, block_b), lambda b, tc: (0, b)),
            pl.BlockSpec((N_GAIN, block_b), lambda b, tc: (0, b)),
            pl.BlockSpec((chunk_t, R.N_NOISE, block_b),
                         lambda b, tc: (tc, 0, b)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="closed_loop",
    )(scalars, prof, gains, noise)
    state, phist, chist = outs[:3]
    traces = (dict(zip(R.TRACE_KEYS, outs[3:])) if collect else None)
    return traces, (state, phist, chist)
