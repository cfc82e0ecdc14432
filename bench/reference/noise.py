"""Per-run random draws of the two campaign engines, from each run's seed
by each engine's contract.

Kernel path (`draw`): the run's key is ``PRNGKey(seed)``, split five
ways into z_progress, z_power (normal), u_enter, u_exit (uniform) and
z_heartbeat (normal), each a stream of ``T`` periods, where ``T`` is the
horizon rounded up to the kernel's 64-period chunk.

Scan engine (`ScanDraws`): one key per period, see its docstring."""
from __future__ import annotations

import functools
import math

import numpy as np

CHUNK_T = 64


def horizon(max_time: float, dt: float) -> int:
    """Periods drawn per run: ceil(max_time / dt) rounded up to 64."""
    return CHUNK_T * math.ceil(math.ceil(max_time / dt) / CHUNK_T)


@functools.lru_cache(maxsize=None)
def _drawer(T: int):
    import jax
    import jax.numpy as jnp

    def one(seed):
        k = jax.random.PRNGKey(seed)
        kz, kp, kd, ke, kh = jax.random.split(k, 5)
        return jnp.stack([jax.random.normal(kz, (T,)),
                          jax.random.normal(kp, (T,)),
                          jax.random.uniform(kd, (T,)),
                          jax.random.uniform(ke, (T,)),
                          jax.random.normal(kh, (T,))])

    return jax.jit(jax.vmap(one))


def draw(seeds, T: int) -> np.ndarray:
    """(S,) seeds -> (T, 5, S) float32 noise on the host."""
    import jax.numpy as jnp

    seeds = np.asarray(seeds, np.int64)
    if seeds.min() < 0 or seeds.max() >= 2 ** 32:
        raise ValueError("run seeds must fit in 32 unsigned bits")
    out = _drawer(int(T))(jnp.asarray(seeds.astype(np.uint32)))
    return np.asarray(out).transpose(2, 1, 0)


# ---- the scan engine's key contract ------------------------------------
SCAN_CHUNK = 64  # periods drawn per device call


def scan_horizon(max_time: float, dt: float) -> int:
    """Periods in a scan run's key stream: ceil(max_time / dt) rounded
    up to a power of two, at least 256."""
    n, b = math.ceil(max_time / dt), 256
    while b < n:
        b *= 2
    return b


@functools.lru_cache(maxsize=None)
def _scan_fns(T: int):
    import jax
    import jax.numpy as jnp

    def block(seed, lo):
        keys = jax.random.split(jax.random.PRNGKey(seed), T)
        keys = jax.lax.dynamic_slice_in_dim(keys, lo, SCAN_CHUNK)

        def period(k):
            k_plant, k_beats = jax.random.split(k)
            kz, kp, kd, ke = jax.random.split(k_plant, 4)
            return jnp.stack([jax.random.normal(kz), jax.random.normal(kp),
                              jax.random.uniform(kd),
                              jax.random.uniform(ke)]), k_beats

        return jax.vmap(period)(keys)

    def counts(k_beats, j, lam):
        k = jax.lax.dynamic_index_in_dim(k_beats, j, axis=1, keepdims=False)
        return jax.vmap(jax.random.poisson)(k, lam)

    return (jax.jit(jax.vmap(block, in_axes=(0, None))), jax.jit(counts))


class ScanDraws:
    """Random draws of scan-engine runs, from each run's seed as the
    engine's contract states: the run's key is ``PRNGKey(seed)``, split
    into one key per period of `scan_horizon`; a period's key splits in
    two, a plant key and a heartbeat key, and the plant key four ways
    into z_progress, z_power (normal), u_enter, u_exit (uniform). The
    heartbeat count is Poisson of the period's mean (float32) under the
    heartbeat key, so it depends on the mean the caller gives."""

    def __init__(self, seeds, max_time: float, dt: float):
        import jax.numpy as jnp

        seeds = np.asarray(seeds, np.int64)
        if seeds.min() < 0 or seeds.max() >= 2 ** 32:
            raise ValueError("run seeds must fit in 32 unsigned bits")
        self.horizon = scan_horizon(max_time, dt)
        self._block, self._counts = _scan_fns(self.horizon)
        self._seeds = jnp.asarray(seeds.astype(np.uint32))
        self._lo = None

    def _load(self, step: int):
        lo = step - step % SCAN_CHUNK
        if lo != self._lo:
            draws, self._k_beats = self._block(self._seeds, lo)
            self._draws = np.asarray(draws)          # (S, CHUNK, 4)
            self._lo = lo
        return step - lo

    def plant(self, step: int):
        """(z_progress, z_power, u_enter, u_exit) of period ``step``."""
        j = self._load(step)
        return tuple(self._draws[:, j, i] for i in range(4))

    def counts(self, step: int, lam) -> np.ndarray:
        """Heartbeat counts of period ``step`` for the means ``lam``."""
        j = self._load(step)
        return np.asarray(self._counts(self._k_beats, j,
                                       np.asarray(lam, np.float32)),
                          np.float64)
