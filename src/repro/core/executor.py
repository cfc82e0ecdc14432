"""Chunked, sharded, donation-aware sweep execution (the scale layer).

The scan engine (`repro.core.sim`) vmaps one run over a grid's per-run
rows. In one call that needs O(grid) device memory in summary mode,
O(grid * T) in trace mode, on one device. This module is the execution
layer between a grid and the hardware; every `sim.sweep` grid runs
through it, an unchunked one as a single chunk of all runs:

* **Chunking** — an arbitrarily large flat run list is cut into
  fixed-size tiles (the last tile padded, pad rows discarded), so a
  million-run grid needs only O(chunk) device memory and ONE compiled
  engine serves every tile.
* **Donation** — each tile's input buffers are donated to the compiled
  call (`donate_argnums`), so XLA reuses them for outputs instead of
  holding both generations live between chunks.
* **Sharding** — with more than one device, tiles are split across
  devices via `pmap` (single-device fallback is a plain `jit`; an engine
  that jits itself, like the Pallas op, gets one slice per device);
  per-run results are identical either way because every run's
  parameters and RNG stream ride in its own row.
* **Streaming merge** — per-chunk outputs land in preallocated host
  buffers (or go straight to a ``consume`` callback, e.g. the
  offline-RL transition harvester, and are dropped), so summary
  reductions of huge grids never materialize device-side at grid size.
* **Resume** — `ExecState` checkpoints which chunks are done plus the
  partially-filled buffers; `run_grid(..., state=...)` picks up at the
  first unfinished chunk, and `stop_after=` bounds one call's work so
  campaigns can be split across processes.

`sim.sweep(backend=..., chunk_size=..., devices=...)`,
`hierarchy.fleet_sweep` and `policies.offline_rl.harvest_dataset` all
ride this one driver.
"""
from __future__ import annotations

import dataclasses
import logging
import warnings
from typing import Any, Callable, Optional, Sequence, Tuple, Union

import jax
import numpy as np

from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

logger = logging.getLogger("repro.core.executor")


def resolve_devices(devices: Union[None, int, str, Sequence]
                    ) -> Tuple[Any, ...]:
    """Normalize a devices= argument to a tuple of jax devices.

    ``None``/1 -> () (single-device jit path); ``"all"`` -> every local
    device; an int n -> the first n local devices; a sequence is taken
    as-is. A single-entry answer collapses to () — pmap over one device
    would only add dispatch overhead."""
    if devices is None:
        return ()
    if devices == "all":
        devs = tuple(jax.local_devices())
    elif isinstance(devices, int):
        avail = jax.local_devices()
        if devices > len(avail):
            raise ValueError(f"asked for {devices} devices, "
                             f"{len(avail)} available")
        devs = tuple(avail[:devices])
    else:
        devs = tuple(devices)
    return devs if len(devs) > 1 else ()


@dataclasses.dataclass
class ExecState:
    """Resumable progress of one chunked grid: which chunks are done and
    the partially-filled host output buffers. Everything is plain
    numpy, so the state round-trips through pickle/np.savez across
    processes; `fingerprint` guards against resuming with a different
    grid or chunking."""
    n_runs: int
    chunk: int
    done: np.ndarray                      # (n_chunks,) bool
    buffers: Any = None                   # output pytree of np arrays
    fingerprint: str = ""

    @property
    def n_chunks(self) -> int:
        return len(self.done)

    @property
    def complete(self) -> bool:
        return bool(self.done.all())


_COMPILED: dict = {}


def _compiled(fn: Callable, n_shared: int, devs: Tuple, donate: bool,
              wrap: str) -> Callable:
    """jit/pmap wrapper for the per-chunk engine, cached per (fn,
    device set, donation). ``wrap='none'`` leaves compilation to fn
    (engines that jit internally, e.g. the Pallas op's static-shape
    wrapper) and only splits the chunk over several devices."""
    key = (fn, devs, donate, wrap)
    if key in _COMPILED:
        return _COMPILED[key]
    if wrap == "none":
        wrapped = _per_device(fn, devs) if devs else fn
    elif devs:
        inner = jax.pmap(fn, in_axes=(0,) + (None,) * n_shared,
                         devices=devs,
                         donate_argnums=(0,) if donate else ())

        def wrapped(batched, *shared, _nd=len(devs)):
            c = jax.tree_util.tree_leaves(batched)[0].shape[0]
            shard = lambda x: x.reshape((_nd, c // _nd) + x.shape[1:])
            out = inner(jax.tree_util.tree_map(shard, batched), *shared)
            return jax.tree_util.tree_map(
                lambda x: x.reshape((c,) + x.shape[2:]), out)
    else:
        wrapped = jax.jit(fn, donate_argnums=(0,) if donate else ())
    _COMPILED[key] = wrapped
    return wrapped


def _per_device(fn: Callable, devs: Tuple) -> Callable:
    """Shard a chunk for an engine that jits itself (``wrap='none'``):
    one contiguous slice per device, committed there so the engine's
    own jit runs on it; the outputs merge on the host. The engine
    compiles once per device here, and no trace has shown the devices
    computing their slices concurrently."""

    def wrapped(batched, *shared):
        c = jax.tree_util.tree_leaves(batched)[0].shape[0]
        per = c // len(devs)
        outs = [fn(jax.tree_util.tree_map(
                    lambda x: jax.device_put(x[i * per:(i + 1) * per], d),
                    batched), *shared)
                for i, d in enumerate(devs)]
        return jax.tree_util.tree_map(lambda *xs: np.concatenate(xs),
                                      *jax.device_get(outs))

    return wrapped


def _digest(batched: Any, shared: Tuple) -> str:
    """Content hash of a grid (pytree structure + every leaf's shape,
    dtype and bytes) for the resumable-state guard."""
    import hashlib

    h = hashlib.blake2b(digest_size=8)
    for tree in (batched, shared):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        h.update(str(treedef).encode())
        for leaf in leaves:
            a = np.asarray(leaf)
            h.update(f"{a.shape}{a.dtype}".encode())
            h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def digest(batched: Any, shared: Tuple = ()) -> str:
    """Public content hash of a (batched, shared) grid — also the
    fingerprint `repro.core.plane` stamps on plane snapshots so a
    modified/corrupted snapshot is rejected instead of resumed."""
    return _digest(batched, shared)


def _pad_rows(x, pad: int):
    """Pad a chunk slice to full size — ALWAYS copying. The chunk input
    must own its memory: device transfer of a host array can be
    zero-copy, and a donated zero-copy buffer would let the executable
    write its outputs straight into the caller's grid arrays."""
    if pad:
        return np.concatenate(
            [x, np.broadcast_to(x[:1], (pad,) + x.shape[1:])])
    return np.array(x)


def run_grid(fn: Callable, batched: Any, shared: Tuple, n_runs: int, *,
             chunk_size: Optional[int] = None,
             devices: Union[None, int, str, Sequence] = None,
             donate: bool = True, wrap: str = "jit",
             consume: Optional[Callable] = None,
             state: Optional[ExecState] = None,
             stop_after: Optional[int] = None,
             grid_digest: Optional[str] = None
             ) -> Tuple[Any, ExecState]:
    """Drive ``fn(batched_chunk, *shared)`` over a flat run list.

    ``batched`` is a pytree whose leaves all have leading axis
    ``n_runs``; ``fn`` must return a pytree whose leaves all have the
    chunk's leading axis. Results are merged into host numpy buffers in
    run order — or handed to ``consume(lo, hi, chunk_out)`` per chunk
    and dropped. Returns ``(merged | None, ExecState)``; ``merged`` is
    None when a consume hook ran or the state is still incomplete
    (``stop_after=`` cut the call short — pass the state back in to
    continue across the chunk boundary).

    Observability: chunk/run/resume counters and live progress gauges
    (``executor_grid_chunks_done`` / ``_planned``) publish into the
    process metrics registry after EVERY chunk — a `repro.obs.serve`
    scrape endpoint watches a campaign advance mid-call — and when the
    span tracer is enabled (`repro.obs.trace.enable()`) every chunk
    emits prepare/compute/transfer/merge spans with device ids, nested
    under the caller's span (``sweep``, ``plane/tick``). The first chunk
    of a freshly wrapped engine is marked ``cold`` (its compute span
    includes XLA compilation). Only while tracing does ``compute`` block
    until the device is done, so a traced run books the device wait
    under ``executor/compute`` (untraced, dispatch is asynchronous and
    the wait falls in the transfer's ``device_get``)."""
    chunk = int(chunk_size) if chunk_size else n_runs
    chunk = max(1, min(chunk, n_runs))
    devs = resolve_devices(devices)
    if devs and chunk % len(devs):
        chunk += len(devs) - chunk % len(devs)  # pad rows fill the rest
    n_chunks = -(-n_runs // chunk)
    fingerprint = f"{n_runs}x{chunk}"
    if state is not None or stop_after is not None:
        # resumable flows guard CONTENT, not just shape: a same-shape
        # grid with different parameters must not merge into a
        # half-finished state's buffers. grid_digest= lets a caller that
        # already hashed the grid (the campaign supervisor drives this
        # loop one chunk per call) skip re-digesting it every call.
        fingerprint += ":" + (grid_digest or _digest(batched, shared))

    reg = obs_metrics.get_registry()
    tracer = obs_trace.get_tracer()
    if state is None:
        state = ExecState(n_runs=n_runs, chunk=chunk,
                          done=np.zeros((n_chunks,), bool),
                          fingerprint=fingerprint)
    elif state.fingerprint != fingerprint:
        raise ValueError(f"resume state was built for grid "
                         f"{state.fingerprint}, this call is "
                         f"{fingerprint}")
    elif state.done.any():
        reg.counter("executor_resumes_total",
                    "run_grid calls resumed from partial ExecState"
                    ).inc()

    cold = (fn, devs, donate, wrap) not in _COMPILED and wrap != "none"
    wrapped = _compiled(fn, len(shared), devs, donate, wrap)
    dev_ids = [d.id for d in (devs or jax.local_devices()[:1])]
    leaves, treedef = jax.tree_util.tree_flatten(batched)
    # metrics publish PER CHUNK (not once post-loop) so a scrape
    # endpoint sees live campaign progress; end-of-call counter totals
    # are identical to the old single publication
    c_chunks = reg.counter("executor_chunks_total", "grid chunks executed")
    c_runs = reg.counter("executor_runs_total", "grid runs executed")
    g_plan = reg.gauge("executor_grid_chunks_planned",
                       "chunk count of the current run_grid call")
    g_done = reg.gauge("executor_grid_chunks_done",
                       "chunks completed (incl. resumed) of the current "
                       "run_grid call")
    g_plan.set(n_chunks)
    g_done.set(int(state.done.sum()))
    ran = 0
    # ONE scoped filter installation around the whole chunk loop (and
    # restored on exit, early returns included): user warning filters
    # are never mutated module-wide, and the hot loop stops
    # saving/restoring global filter state once per chunk
    with warnings.catch_warnings():
        # small parameter rows rarely alias an output buffer; the
        # donation win is the big per-chunk key/trace buffers
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        stopped = False
        for ci in range(n_chunks):
            if state.done[ci]:
                continue
            if stop_after is not None and ran >= stop_after:
                stopped = True
                break
            lo, hi = ci * chunk, min((ci + 1) * chunk, n_runs)
            pad = chunk - (hi - lo)
            with tracer.span("executor/prepare", chunk=ci, lo=lo, hi=hi,
                             pad=pad, devices=dev_ids):
                chunk_in = jax.tree_util.tree_unflatten(
                    treedef, [_pad_rows(np.asarray(x[lo:hi]), pad)
                              for x in leaves])
            with tracer.span("executor/compute", chunk=ci, lo=lo, hi=hi,
                             devices=dev_ids, cold=cold and ran == 0):
                out = wrapped(chunk_in, *shared)
                if tracer.enabled:
                    # async dispatch would defer the wait to device_get
                    # and book compute time under the transfer span
                    out = jax.block_until_ready(out)
            with tracer.span("executor/transfer", chunk=ci,
                             devices=dev_ids):
                out = jax.device_get(out)
            out = jax.tree_util.tree_map(lambda x: x[:hi - lo], out)
            with tracer.span("executor/merge", chunk=ci, lo=lo, hi=hi,
                             consume=consume is not None):
                if consume is not None:
                    # device_get on CPU can return zero-copy VIEWS of
                    # device buffers; once this chunk's arrays are
                    # dropped the allocator reuses that memory (donation
                    # makes it certain), so anything handed outward must
                    # own its storage
                    consume(lo, hi, jax.tree_util.tree_map(
                        lambda x: np.array(x), out))
                else:
                    if state.buffers is None:
                        state.buffers = jax.tree_util.tree_map(
                            lambda x: np.empty((n_runs,) + x.shape[1:],
                                               x.dtype), out)

                    def fill(buf, x):
                        buf[lo:hi] = x
                        return buf

                    jax.tree_util.tree_map(fill, state.buffers, out)
            state.done[ci] = True
            ran += 1
            c_chunks.inc()
            c_runs.inc(hi - lo)
            g_done.set(int(state.done.sum()))
    if stopped:
        return None, state
    merged = state.buffers if (consume is None and state.complete) \
        else None
    return merged, state
