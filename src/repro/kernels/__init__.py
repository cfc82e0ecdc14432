"""Pallas TPU kernels for the framework's perf-critical compute.

The paper (Cerf et al. 2021) contributes a control layer, not kernels —
these serve the framework's model substrate (DESIGN.md §7) and, with
``closed_loop``, the control layer's own hot path:

* ``flash_attention``  — fwd flash attention (GQA/causal/SWA) for
  train/prefill; bwd via recompute against the jnp oracle.
* ``decode_attention`` — split-KV flash-decode (parallel partial softmax +
  combine) for serve_step.
* ``selective_scan``   — fused Mamba (S6) chunked scan.
* ``closed_loop``      — the entire closed-loop simulation (plant step,
  PI update, actuator clamp, progress/energy accumulation, summary-mode
  online reductions) fused into one kernel, blocked over the run batch
  with the carry resident in VMEM — the same shape of computation as the
  selective scan (serial over time, parallel over lanes), applied to the
  paper's sweep engine. `repro.core.sim.sweep` dispatches capable
  grids to it on a TPU (``backend="auto"``, the default) through the
  chunked executor.

Each kernel ships ``kernel.py`` (pl.pallas_call + BlockSpec), ``ops.py``
(jit'd public wrapper, interpret-mode switch) and ``ref.py`` (pure-jnp
oracle used by the allclose test sweeps; the closed-loop oracle is the
`sim.engine_step` scan transcribed onto an externalized noise tensor,
and the kernel matches it bit-for-bit in interpret mode).
"""
