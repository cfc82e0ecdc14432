"""Chunked / sharded / resumable sweep execution (repro.core.executor).

The load-bearing property everywhere: every run's parameters and RNG
stream ride in its own row of the flattened grid, so ANY execution
layout — one shot, chunked, sharded across devices, stopped and resumed
— produces identical per-run results.
"""
import os
import pickle
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import executor
from repro.core.hierarchy import FleetConfig, fleet_sweep, simulate_fleet
from repro.core.plant import PROFILES
from repro.core.policies.offline_rl import build_dataset, harvest_dataset
from repro.core.sim import sweep, sweep_resumable

KW = dict(total_work=500.0, max_time=400.0)


def test_chunked_equals_one_shot_trace_mode():
    one = sweep(["gros", "dahu"], [0.1, 0.3], range(3), **KW)
    ch = sweep(["gros", "dahu"], [0.1, 0.3], range(3), chunk_size=5,
               **KW)
    for k in one.traces:
        np.testing.assert_array_equal(np.asarray(one.traces[k]),
                                      np.asarray(ch.traces[k]),
                                      err_msg=k)
    np.testing.assert_array_equal(np.asarray(one.exec_time),
                                  np.asarray(ch.exec_time))
    np.testing.assert_array_equal(np.asarray(one.n_steps),
                                  np.asarray(ch.n_steps))


def test_chunked_equals_one_shot_summary_mode():
    one = sweep("gros", [0.1, 0.3], range(4), collect_traces=False,
                **KW)
    ch = sweep("gros", [0.1, 0.3], range(4), collect_traces=False,
               chunk_size=3, **KW)
    for k in ("progress_mean", "power_mean", "progress_hist",
              "pcap_hist"):
        np.testing.assert_array_equal(np.asarray(one.summary[k]),
                                      np.asarray(ch.summary[k]),
                                      err_msg=k)
    np.testing.assert_array_equal(np.asarray(one.energy),
                                  np.asarray(ch.energy))


def test_chunked_adaptive_and_workload_axes():
    """Chunking slices the FLATTENED grid, so multi-axis grids (eps x
    rls-configs x seeds, workload axes) must reassemble exactly."""
    from repro.core.adaptive import RLSConfig
    from repro.core.policies import PIPolicy
    from repro.core.workloads import Phase, PhaseSchedule
    pls = [PIPolicy(adaptive=RLSConfig(lam=0.99)),
           PIPolicy(adaptive=RLSConfig(lam=0.999))]
    one = sweep("gros", [0.1, 0.2], range(2), policies=pls,
                collect_traces=False, **KW)
    ch = sweep("gros", [0.1, 0.2], range(2), policies=pls,
               collect_traces=False, chunk_size=3, **KW)
    np.testing.assert_array_equal(np.asarray(one.exec_time),
                                  np.asarray(ch.exec_time))
    wls = [PhaseSchedule((Phase(50.0, scale=(("K_L", 2.0),)),
                          Phase(50.0)), cyclic=True),
           PhaseSchedule((Phase(100.0),))]
    onw = sweep("gros", [0.1], range(2), workloads=wls,
                collect_traces=False, **KW)
    chw = sweep("gros", [0.1], range(2), workloads=wls,
                collect_traces=False, chunk_size=2, **KW)
    np.testing.assert_array_equal(np.asarray(onw.exec_time),
                                  np.asarray(chw.exec_time))


def test_resume_across_chunk_boundary_round_trips():
    """Stop after one chunk, pickle the state, resume in a 'new
    process' (fresh unpickle) — the completed grid equals one-shot."""
    one = sweep("gros", [0.1, 0.3], range(4), collect_traces=False,
                **KW)
    res, st = sweep_resumable("gros", [0.1, 0.3], range(4),
                              collect_traces=False, chunk_size=3,
                              stop_after=1, **KW)
    assert res is None and not st.complete
    assert st.done.sum() == 1 and st.n_chunks == 3
    st = pickle.loads(pickle.dumps(st))
    res, st = sweep_resumable("gros", [0.1, 0.3], range(4),
                              collect_traces=False, chunk_size=3,
                              state=st, **KW)
    assert st.complete
    np.testing.assert_array_equal(np.asarray(one.exec_time),
                                  np.asarray(res.exec_time))
    np.testing.assert_array_equal(np.asarray(one.summary["pcap_hist"]),
                                  np.asarray(res.summary["pcap_hist"]))
    # a state built for a different chunking is rejected, not misread
    with pytest.raises(ValueError, match="resume state"):
        sweep_resumable("gros", [0.1, 0.3], range(4),
                        collect_traces=False, chunk_size=5, state=st,
                        **KW)
    # ... and so is a DIFFERENT grid of the same shape (content guard):
    # finished chunks must never merge with another grid's runs
    _, st2 = sweep_resumable("gros", [0.1, 0.3], range(4),
                             collect_traces=False, chunk_size=3,
                             stop_after=1, **KW)
    with pytest.raises(ValueError, match="resume state"):
        sweep_resumable("gros", [0.5, 0.9], range(4),
                        collect_traces=False, chunk_size=3, state=st2,
                        **KW)


def _sharded_matches_single_device(backend):
    code = f"""
import numpy as np
from repro.core.sim import sweep
import jax
assert len(jax.local_devices()) == 2, jax.local_devices()
kw = dict(total_work=300.0, max_time=256.0, collect_traces=False,
          backend={backend!r})
one = sweep("gros", [0.1, 0.3], range(4), **kw)
sh = sweep("gros", [0.1, 0.3], range(4), chunk_size=4, devices="all", **kw)
np.testing.assert_array_equal(np.asarray(one.exec_time), np.asarray(sh.exec_time))
np.testing.assert_array_equal(np.asarray(one.summary["progress_hist"]),
                              np.asarray(sh.summary["progress_hist"]))
print("SHARDED_OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=2")
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "SHARDED_OK" in out.stdout, out.stdout + out.stderr


def test_sharded_equals_single_device():
    """Chunks shard across devices via pmap; per-run results must be
    identical. Runs in a subprocess with 2 forced host CPU devices
    (device count is fixed at jax init)."""
    _sharded_matches_single_device("scan")


def test_pallas_sharded_equals_single_device():
    """The Pallas op jits itself, so the executor commits one slice of
    each chunk to each device instead of pmapping it; per-run results
    must still be identical to one device."""
    _sharded_matches_single_device("pallas")


def test_run_grid_consume_and_stop_semantics():
    """Executor-level contract on a toy engine: consume streams chunks
    in order and nothing is retained; stop_after leaves a resumable
    state whose buffers fill incrementally."""
    import jax.numpy as jnp
    fn = lambda b, c: {"y": b["x"] * c}
    x = np.arange(10, dtype=np.float32)
    seen = []
    merged, st = executor.run_grid(
        fn, {"x": x}, (jnp.float32(2.0),), 10, chunk_size=4,
        consume=lambda lo, hi, out: seen.append((lo, hi, out["y"])))
    assert merged is None and st.complete and st.buffers is None
    assert [(lo, hi) for lo, hi, _ in seen] == [(0, 4), (4, 8), (8, 10)]
    np.testing.assert_array_equal(np.concatenate([y for _, _, y in seen]),
                                  2.0 * x)
    merged, st = executor.run_grid(fn, {"x": x}, (jnp.float32(3.0),),
                                   10, chunk_size=4, stop_after=2)
    assert merged is None and st.done.tolist() == [True, True, False]
    merged, st = executor.run_grid(fn, {"x": x}, (jnp.float32(3.0),),
                                   10, chunk_size=4, state=st)
    np.testing.assert_array_equal(merged["y"], 3.0 * x)


def test_fleet_sweep_rides_executor_and_matches_single_runs():
    prof = PROFILES["dahu"]
    peak = float(prof.power_of_pcap(prof.pcap_max)) * 8
    fc = FleetConfig(n_nodes=8, epsilon=0.1, power_budget=0.7 * peak)
    fs = fleet_sweep(prof, fc, steps=25, seeds=[0, 1, 2], chunk_size=2)
    assert fs["power"].shape == (3, 25)
    for s in (0, 2):
        one = simulate_fleet(prof, fc, steps=25, seed=s)
        np.testing.assert_allclose(fs["power"][s],
                                   np.asarray(one["power"]), rtol=1e-6)
        np.testing.assert_allclose(fs["energy_total"][s],
                                   float(one["energy_total"]), rtol=1e-6)


def test_harvest_dataset_streams_chunks_exactly():
    eps = [0.1, 0.2]
    hd = harvest_dataset(["gros", "dahu"], eps, range(2),
                         total_work=300.0, max_time=256.0, chunk_size=3)
    parts = []
    for p in ("gros", "dahu"):
        for e in eps:
            r = sweep(p, [e], range(2), total_work=300.0, max_time=256.0)
            parts.append(build_dataset(
                {k: np.asarray(v) for k, v in r.traces.items()},
                PROFILES[p], e))
    for k in ("s", "a", "r", "s2"):
        np.testing.assert_array_equal(
            hd[k], np.concatenate([d[k] for d in parts]), err_msg=k)
    assert len(hd["s"]) > 50


@pytest.mark.slow
def test_chunked_100k_run_summary_grid_bounded_memory():
    """The acceptance-scale grid: >= 100k summary-mode runs complete
    through bounded chunks (no single device batch beyond chunk_size
    ever exists — that is the executor's construction, asserted via the
    chunk accounting) and the statistics are sane."""
    n_seeds, eps = 20000, [0.0, 0.05, 0.1, 0.15, 0.3]
    chunk = 8192
    res, st = sweep_resumable(
        "gros", eps, range(n_seeds), total_work=1200.0, max_time=200.0,
        collect_traces=False, summary_warmup=20, chunk_size=chunk)
    assert st.complete
    assert st.n_chunks == -(-len(eps) * n_seeds // chunk)
    assert st.chunk == chunk <= 8192
    assert res.exec_time.shape == (len(eps), n_seeds)
    assert bool(np.asarray(res.completed).all())
    # deeper degradation -> less energy, longer runs (paper trade-off)
    e = np.asarray(res.energy).mean(-1)
    t = np.asarray(res.exec_time).mean(-1)
    assert e[-1] < e[0] and t[-1] > t[0]

def test_consume_raise_leaves_state_resumable_bit_identical():
    """Failure atomicity: a consume= callback that raises mid-grid must
    leave the ExecState exactly as a clean stop at the same boundary —
    the failed chunk is NOT marked done (its consume never completed),
    no partial buffers leak, and resuming with a working consume
    replays it plus the remainder."""
    import jax.numpy as jnp
    fn = lambda b, c: {"y": b["x"] * c}
    x = np.arange(10, dtype=np.float32)
    shared = (jnp.float32(2.0),)

    # oracle: a clean stop after the first chunk
    _, st_clean = executor.run_grid(fn, {"x": x}, shared, 10,
                                    chunk_size=4, consume=lambda *a: None,
                                    stop_after=1)

    def bomb(lo, hi, out):
        if lo >= 4:
            raise RuntimeError("downstream sink went away")

    st = executor.run_grid(fn, {"x": x}, shared, 10, chunk_size=4,
                           stop_after=0)[1]
    with pytest.raises(RuntimeError, match="sink went away"):
        executor.run_grid(fn, {"x": x}, shared, 10, chunk_size=4,
                          consume=bomb, state=st)

    # the surviving state is bit-identical to the clean stop
    assert st.done.tolist() == [True, False, False]
    assert st.n_runs == st_clean.n_runs
    assert st.chunk == st_clean.chunk
    assert st.done.tolist() == st_clean.done.tolist()
    assert st.buffers is None and st_clean.buffers is None
    assert st.fingerprint == st_clean.fingerprint

    # resume: only the failed chunk and the tail run, output completes
    seen = []
    merged, st2 = executor.run_grid(
        fn, {"x": x}, shared, 10, chunk_size=4,
        consume=lambda lo, hi, out: seen.append((lo, hi, out["y"])),
        state=st)
    assert merged is None and st2.complete
    assert [(lo, hi) for lo, hi, _ in seen] == [(4, 8), (8, 10)]
    np.testing.assert_array_equal(
        np.concatenate([y for _, _, y in seen]), 2.0 * x[4:])
