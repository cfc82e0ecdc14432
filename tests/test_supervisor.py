"""Durable campaign supervisor (repro.core.supervisor) + its retry
ladder.

The load-bearing property, inherited from the executor contract: every
run's parameters and RNG ride in its own row, so a campaign that was
retried, timed out, quarantined, killed -9 and resumed produces results
bit-for-bit identical to one uninterrupted `run_grid` call.
"""
import json
import os
import pickle
import signal
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import executor, supervisor
from repro.obs.retry import RetryPolicy, call_with_retries

N = 20
CHUNK = 4


def _toy(b, c):
    return {"y": b["x"] * c, "z": b["x"] + 1.0}


def _grid(n=N):
    import jax.numpy as jnp
    return {"x": np.arange(n, dtype=np.float32)}, (jnp.float32(2.0),)


def _reference(n=N, chunk=CHUNK):
    batched, shared = _grid(n)
    merged, _ = executor.run_grid(_toy, batched, shared, n,
                                  chunk_size=chunk)
    return merged


def _assert_identical(a, b):
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]),
                                      np.asarray(b[k]), err_msg=k)


# ---------------------------------------------------------------- retry
def test_retry_policy_backoff_ladder():
    p = RetryPolicy(max_retries=5, base_s=0.1, factor=2.0, max_s=0.5,
                    jitter=0.25)
    assert [p.backoff_s(a) for a in range(4)] == [0.1, 0.2, 0.4, 0.5]
    import random
    rng = random.Random(0)
    for a in range(4):
        d = p.backoff_s(a, rng)
        base = min(0.1 * 2.0 ** a, 0.5)
        assert 0.75 * base <= d <= 1.25 * base


def test_call_with_retries_budget_and_hook():
    calls, seen = [], []
    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"
    out = call_with_retries(flaky, RetryPolicy(max_retries=3, base_s=0.0),
                            on_retry=lambda a, d, e: seen.append(a),
                            sleep=lambda s: None)
    assert out == "ok" and len(calls) == 3 and seen == [0, 1]
    with pytest.raises(ValueError):
        call_with_retries(lambda: (_ for _ in ()).throw(ValueError("x")),
                          RetryPolicy(max_retries=2, base_s=0.0),
                          sleep=lambda s: None)


def test_classify_failure_rungs():
    cf = supervisor.classify_failure
    assert cf(supervisor.DeviceLost(1)) == "device"
    assert cf(supervisor.ChunkTimeout("t")) == "timeout"
    assert cf(supervisor.TransientFault("f")) == "transient"
    assert cf(MemoryError()) == "transient"
    assert cf(RuntimeError("RESOURCE_EXHAUSTED: out of memory")) \
        == "transient"
    assert cf(RuntimeError("device lost mid-collective")) == "device"
    assert cf(ValueError("shapes do not match")) == "permanent"


# -------------------------------------------------------------- journal
def test_journal_roundtrip_and_torn_tail(tmp_path):
    p = tmp_path / "j.jsonl"
    j = supervisor.Journal(p)
    recs = [{"k": "plan", "fp": "a"}, {"k": "commit", "ci": 0},
            {"k": "commit", "ci": 1}]
    for r in recs:
        j.append(r)
    j.close()
    got, torn = supervisor.read_journal(p)
    assert got == recs and torn == 0
    # torn tail: chop the last record mid-line — dropped, counted
    raw = p.read_bytes()
    p.write_bytes(raw[:-9])
    got, torn = supervisor.read_journal(p)
    assert got == recs[:2] and torn == 1
    # corruption that is NOT the tail refuses to resume
    lines = raw.decode().splitlines()
    lines[1] = lines[1][:-4] + 'xx"}'
    p.write_text("\n".join(lines) + "\n")
    with pytest.raises(ValueError, match="corrupt"):
        supervisor.read_journal(p)


def test_durable_matches_bare_run_grid(tmp_path):
    batched, shared = _grid()
    merged, report = supervisor.run_durable(
        _toy, batched, shared, N, dir=tmp_path, chunk_size=CHUNK)
    _assert_identical(merged, _reference())
    assert report.n_chunks == 5 and report.committed == 5
    assert not report.dead and not report.resumed and report.retries == 0
    recs, torn = supervisor.read_journal(tmp_path
                                         / supervisor.JOURNAL_NAME)
    kinds = [r["k"] for r in recs]
    assert kinds[0] == "plan" and kinds[-1] == "done" and torn == 0
    assert kinds.count("commit") == 5 and kinds.count("start") == 5
    assert (tmp_path / supervisor.CHECKPOINT_NAME).exists()


def test_transient_faults_retry_to_identical_completion(tmp_path):
    """10%-style chunk chaos: injected transient faults retry with
    backoff and the campaign completes with zero lost runs."""
    batched, shared = _grid()
    flaky = supervisor.FlakyGridFn(
        _toy, failures={0: supervisor.TransientFault("injected"),
                        3: RuntimeError("RESOURCE_EXHAUSTED: pool")})
    cfg = supervisor.CampaignConfig(
        retry=RetryPolicy(max_retries=3, base_s=0.001, max_s=0.01))
    merged, report = supervisor.run_durable(
        flaky, batched, shared, N, dir=tmp_path, chunk_size=CHUNK,
        wrap="none", config=cfg)
    _assert_identical(merged, _reference())
    assert report.retries == 2 and not report.dead
    recs, _ = supervisor.read_journal(tmp_path / supervisor.JOURNAL_NAME)
    retries = [r for r in recs if r["k"] == "retry"]
    assert {r["reason"] for r in retries} == {"transient"}


def test_permanent_failure_dead_letters_and_campaign_continues(tmp_path):
    batched, shared = _grid()
    flaky = supervisor.FlakyGridFn(
        _toy, failures={1: ValueError("bad shapes")})
    merged, report = supervisor.run_durable(
        flaky, batched, shared, N, dir=tmp_path, chunk_size=CHUNK,
        wrap="none")
    assert [ci for ci, _ in report.dead] == [1]
    assert "bad shapes" in report.dead[0][1]
    ref = _reference()
    for k in ref:
        got, want = np.asarray(merged[k]), np.asarray(ref[k])
        np.testing.assert_array_equal(got[:CHUNK], want[:CHUNK])
        np.testing.assert_array_equal(got[2 * CHUNK:], want[2 * CHUNK:])


def test_sweep_refuses_a_result_with_dead_lettered_chunks(tmp_path,
                                                           monkeypatch):
    """A dead-lettered chunk's rows were never computed: `sweep` raises
    instead of returning them as results."""
    from repro.core import sim

    real = executor.run_grid
    calls = []

    def first_chunk_fails(*a, **kw):
        calls.append(1)
        if len(calls) == 1:
            raise ValueError("bad shapes")
        return real(*a, **kw)

    monkeypatch.setattr(executor, "run_grid", first_chunk_fails)
    with pytest.raises(RuntimeError, match="dead-lettered"):
        sim.sweep("gros", [0.1], range(4), total_work=100.0,
                  max_time=64.0, collect_traces=False, chunk_size=2,
                  durable=tmp_path)
    assert len(calls) == 2  # the campaign went on past the dead chunk


def test_retry_budget_exhaustion_dead_letters(tmp_path):
    batched, shared = _grid()
    fails = {i: supervisor.TransientFault(f"attempt {i}")
             for i in range(3)}  # chunk 0 faults on every attempt
    cfg = supervisor.CampaignConfig(
        retry=RetryPolicy(max_retries=2, base_s=0.001, max_s=0.01))
    flaky = supervisor.FlakyGridFn(_toy, failures=fails)
    merged, report = supervisor.run_durable(
        flaky, batched, shared, N, dir=tmp_path, chunk_size=CHUNK,
        wrap="none", config=cfg)
    assert [ci for ci, _ in report.dead] == [0]
    assert report.retries == 2


def test_watchdog_timeout_retries_bit_identical(tmp_path):
    batched, shared = _grid()
    flaky = supervisor.FlakyGridFn(_toy, delays={0: 2.0})
    cfg = supervisor.CampaignConfig(
        chunk_timeout_s=0.25,
        retry=RetryPolicy(max_retries=2, base_s=0.001, max_s=0.01))
    merged, report = supervisor.run_durable(
        flaky, batched, shared, N, dir=tmp_path, chunk_size=CHUNK,
        wrap="none", config=cfg)
    _assert_identical(merged, _reference())
    assert report.retries >= 1 and not report.dead
    recs, _ = supervisor.read_journal(tmp_path / supervisor.JOURNAL_NAME)
    assert any(r["k"] == "retry" and r["reason"] == "timeout"
               for r in recs)


def test_fingerprint_mismatch_rejected(tmp_path):
    batched, shared = _grid()
    supervisor.run_durable(_toy, batched, shared, N, dir=tmp_path,
                           chunk_size=CHUNK)
    other = {"x": np.arange(N, dtype=np.float32) + 1.0}
    with pytest.raises(ValueError, match="planned for grid"):
        supervisor.run_durable(_toy, other, shared, N, dir=tmp_path,
                               chunk_size=CHUNK)


def test_resume_finished_campaign_returns_checkpoint(tmp_path):
    batched, shared = _grid()
    supervisor.run_durable(_toy, batched, shared, N, dir=tmp_path,
                           chunk_size=CHUNK)
    flaky = supervisor.FlakyGridFn(_toy)  # counts calls
    merged, report = supervisor.run_durable(
        flaky, batched, shared, N, dir=tmp_path, chunk_size=CHUNK,
        wrap="none")
    _assert_identical(merged, _reference())
    assert report.resumed and report.committed == 0
    assert flaky.calls == 0  # nothing recomputed: checkpoint was final


def test_torn_tail_replays_chunk_bit_identical(tmp_path):
    """S4 torn-write: truncate the journal mid-record and drop the
    checkpoint — the partial record is discarded (counted) and the
    affected chunks recompute to the identical merge."""
    batched, shared = _grid()
    supervisor.run_durable(_toy, batched, shared, N, dir=tmp_path,
                           chunk_size=CHUNK)
    jpath = tmp_path / supervisor.JOURNAL_NAME
    raw = jpath.read_bytes()
    jpath.write_bytes(raw[:-10])  # tear the terminal record
    (tmp_path / supervisor.CHECKPOINT_NAME).unlink()
    merged, report = supervisor.run_durable(
        _toy, batched, shared, N, dir=tmp_path, chunk_size=CHUNK)
    _assert_identical(merged, _reference())
    assert report.resumed and report.torn_records == 1
    assert report.replayed >= 1  # checkpointless commits recomputed


def test_consume_mode_journal_is_authoritative(tmp_path):
    """Committed chunks are never re-delivered to a consume hook on
    resume — the journal, not the checkpoint, is the source of truth."""
    batched, shared = _grid()
    first, second = [], []
    supervisor.run_durable(_toy, batched, shared, N, dir=tmp_path,
                           chunk_size=CHUNK,
                           consume=lambda lo, hi, out:
                           first.append((lo, hi)))
    assert first == [(0, 4), (4, 8), (8, 12), (12, 16), (16, 20)]
    merged, report = supervisor.run_durable(
        _toy, batched, shared, N, dir=tmp_path, chunk_size=CHUNK,
        consume=lambda lo, hi, out: second.append((lo, hi)))
    assert merged is None and report.resumed and second == []


def test_campaign_events_stream_to_disk(tmp_path):
    batched, shared = _grid()
    flaky = supervisor.FlakyGridFn(
        _toy, failures={0: supervisor.TransientFault("x")})
    cfg = supervisor.CampaignConfig(
        retry=RetryPolicy(max_retries=2, base_s=0.001, max_s=0.01))
    supervisor.run_durable(flaky, batched, shared, N, dir=tmp_path,
                           chunk_size=CHUNK, wrap="none", config=cfg)
    from repro.obs import events as evt
    rows = [json.loads(ln) for ln in
            (tmp_path / supervisor.EVENTS_NAME).read_text().splitlines()]
    assert any(int(r["code"]) == evt.EV_CHUNK_RETRY for r in rows)
    assert all(int(r["source"]) == evt.SRC_SUPERVISOR for r in rows)


def test_supervisor_metrics_published(tmp_path):
    from repro.obs import metrics as obs_metrics
    batched, shared = _grid()
    flaky = supervisor.FlakyGridFn(
        _toy, failures={0: supervisor.TransientFault("x"),
                        2: ValueError("perm")})
    cfg = supervisor.CampaignConfig(
        retry=RetryPolicy(max_retries=2, base_s=0.001, max_s=0.01))
    reg = obs_metrics.get_registry()
    before = reg.counter("supervisor_retries_total",
                         labelnames=("reason",)
                         ).value(reason="transient")
    supervisor.run_durable(flaky, batched, shared, N, dir=tmp_path,
                           chunk_size=CHUNK, wrap="none", config=cfg)
    assert reg.counter("supervisor_retries_total",
                       labelnames=("reason",)
                       ).value(reason="transient") == before + 1
    snap = reg.snapshot()["metrics"]
    assert "supervisor_dead_letter_total" in snap
    assert "supervisor_backoff_seconds" in snap
    assert "supervisor_faults_injected_total" in snap


# --------------------------------------------------------- crash safety
def _sub_env(n_devices=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    if n_devices:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + f" --xla_force_host_platform_device_count"
                              f"={n_devices}")
    return env


_CHILD_TOY = """
import numpy as np, jax.numpy as jnp
from repro.core import supervisor
x = np.arange(24, dtype=np.float32)
def toy(b, c):
    return {{"y": b["x"] * c, "z": b["x"] + 1.0}}
cfg = supervisor.CampaignConfig(checkpoint_every=2, kill_after_commits=3,
                                kill_signal={sig})
supervisor.run_durable(toy, {{"x": x}}, (jnp.float32(2.0),), 24,
                       dir={dir!r}, chunk_size=4, config=cfg)
print("SURVIVED_KILL")
"""


@pytest.mark.parametrize("sig", [signal.SIGKILL, signal.SIGTERM],
                         ids=["kill9", "sigterm"])
def test_kill_mid_campaign_then_resume_bit_identical(tmp_path, sig):
    """S4: kill -9 (and SIGTERM) right after an fsync'd commit; the
    reopened campaign replays exactly the uncommitted chunks and the
    merge equals the uninterrupted run bit-for-bit."""
    code = _CHILD_TOY.format(sig=int(sig), dir=str(tmp_path))
    out = subprocess.run([sys.executable, "-c", code], env=_sub_env(),
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == -int(sig), out.stdout + out.stderr
    assert "SURVIVED_KILL" not in out.stdout

    import jax.numpy as jnp
    x = np.arange(24, dtype=np.float32)
    batched, shared = {"x": x}, (jnp.float32(2.0),)
    ref, _ = executor.run_grid(_toy, batched, shared, 24, chunk_size=4)
    merged, report = supervisor.run_durable(
        _toy, batched, shared, 24, dir=tmp_path, chunk_size=4)
    _assert_identical(merged, ref)
    assert report.resumed and not report.dead
    # kill landed after commit 3 with checkpoint cadence 2: one commit
    # was journaled but not yet snapshotted -> recomputed on resume
    assert report.replayed == 1


def test_quarantine_and_reinstate_two_devices(tmp_path):
    """DeviceLost quarantines the named shard, the campaign degrades to
    the surviving set, probes the device back in after clean commits,
    and still merges bit-identically. 2 forced host CPU devices."""
    code = f"""
import numpy as np, jax.numpy as jnp, jax
from repro.core import executor, supervisor
from repro.obs.retry import RetryPolicy
assert len(jax.local_devices()) == 2
x = np.arange(24, dtype=np.float32)
def toy(b, c):
    return {{"y": b["x"] * c}}
batched, shared = {{"x": x}}, (jnp.float32(2.0),)
ref, _ = executor.run_grid(toy, batched, shared, 24, chunk_size=4)
flaky = supervisor.FlakyGridFn(
    toy, failures={{2: supervisor.DeviceLost(device_id=1)}})
cfg = supervisor.CampaignConfig(
    probe_after=2, retry=RetryPolicy(max_retries=2, base_s=0.001))
merged, report = supervisor.run_durable(
    flaky, batched, shared, 24, dir={str(tmp_path)!r}, chunk_size=4,
    devices="all", wrap="none", config=cfg)
np.testing.assert_array_equal(np.asarray(merged["y"]),
                              np.asarray(ref["y"]))
assert report.reinstated == [1], report
assert report.quarantined == [], report
assert not report.dead and report.retries == 1, report
recs, _ = supervisor.read_journal(
    "{tmp_path}/" + supervisor.JOURNAL_NAME)
kinds = [r["k"] for r in recs]
assert "quarantine" in kinds and "reinstate" in kinds
print("QUARANTINE_OK")
"""
    out = subprocess.run([sys.executable, "-c", code],
                         env=_sub_env(n_devices=2),
                         capture_output=True, text=True, timeout=600)
    assert "QUARANTINE_OK" in out.stdout, out.stdout + out.stderr


# ------------------------------------------------- sweep/fleet/harvest
SWEEP_KW = dict(total_work=300.0, max_time=256.0, collect_traces=False)


def test_sweep_durable_matches_plain_and_resumes(tmp_path):
    from repro.core.sim import sweep
    one = sweep("gros", [0.1, 0.3], range(4), **SWEEP_KW)
    dur = sweep("gros", [0.1, 0.3], range(4), chunk_size=3,
                durable=tmp_path, **SWEEP_KW)
    np.testing.assert_array_equal(np.asarray(one.exec_time),
                                  np.asarray(dur.exec_time))
    np.testing.assert_array_equal(np.asarray(one.energy),
                                  np.asarray(dur.energy))
    np.testing.assert_array_equal(
        np.asarray(one.summary["progress_hist"]),
        np.asarray(dur.summary["progress_hist"]))
    # the saved spec re-dispatches through the finished journal
    res = supervisor.resume_campaign(tmp_path)
    np.testing.assert_array_equal(np.asarray(one.exec_time),
                                  np.asarray(res.exec_time))


def test_sweep_kill9_then_resume_campaign_bit_identical(tmp_path):
    """The acceptance scenario end to end: a durable sweep killed -9
    mid-campaign, then `resume_campaign(dir)` alone (the spec carries
    everything) reproduces the uninterrupted SweepResult bit-for-bit."""
    code = f"""
from repro.core.sim import sweep
from repro.core.supervisor import CampaignConfig
sweep("gros", [0.1, 0.3], range(6), total_work=300.0, max_time=256.0,
      collect_traces=False, chunk_size=3, durable={str(tmp_path)!r},
      campaign=CampaignConfig(checkpoint_every=1, kill_after_commits=2))
print("SURVIVED_KILL")
"""
    out = subprocess.run([sys.executable, "-c", code], env=_sub_env(),
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == -int(signal.SIGKILL), \
        out.stdout + out.stderr

    res = supervisor.resume_campaign(tmp_path)
    from repro.core.sim import sweep
    one = sweep("gros", [0.1, 0.3], range(6), **SWEEP_KW)
    np.testing.assert_array_equal(np.asarray(one.exec_time),
                                  np.asarray(res.exec_time))
    np.testing.assert_array_equal(np.asarray(one.energy),
                                  np.asarray(res.energy))
    for k in ("progress_mean", "power_mean", "progress_hist"):
        np.testing.assert_array_equal(np.asarray(one.summary[k]),
                                      np.asarray(res.summary[k]),
                                      err_msg=k)
    # the spec was sanitized: the resume must NOT inherit the chaos
    # injector that killed the first process
    with open(Path(tmp_path) / supervisor.SPEC_NAME, "rb") as fh:
        spec = pickle.load(fh)
    assert spec["kwargs"]["campaign"].kill_after_commits is None


def test_fleet_sweep_durable_matches_plain(tmp_path):
    from repro.core.hierarchy import FleetConfig, fleet_sweep
    from repro.core.plant import PROFILES
    prof = PROFILES["dahu"]
    peak = float(prof.power_of_pcap(prof.pcap_max)) * 8
    fc = FleetConfig(n_nodes=8, epsilon=0.1, power_budget=0.7 * peak)
    fs = fleet_sweep(prof, fc, steps=25, seeds=[0, 1, 2], chunk_size=2)
    fd = fleet_sweep(prof, fc, steps=25, seeds=[0, 1, 2], chunk_size=2,
                     durable=tmp_path)
    np.testing.assert_array_equal(np.asarray(fs["power"]),
                                  np.asarray(fd["power"]))
    np.testing.assert_array_equal(np.asarray(fs["energy_total"]),
                                  np.asarray(fd["energy_total"]))
    assert (Path(tmp_path) / supervisor.SPEC_NAME).exists()


def test_harvest_dataset_durable_spools_parts(tmp_path):
    from repro.core.policies.offline_rl import harvest_dataset
    plain = harvest_dataset("gros", [0.1], range(2), total_work=300.0,
                            max_time=256.0, chunk_size=1)
    dur = harvest_dataset("gros", [0.1], range(2), total_work=300.0,
                          max_time=256.0, chunk_size=1,
                          durable=tmp_path)
    for k in ("s", "a", "r", "s2"):
        np.testing.assert_array_equal(plain[k], dur[k], err_msg=k)
    parts = sorted((Path(tmp_path) / "parts").glob("part_*.npz"))
    assert len(parts) == 2  # one atomic spool file per chunk


def test_resume_campaign_requires_spec(tmp_path):
    with pytest.raises(FileNotFoundError, match="campaign spec"):
        supervisor.resume_campaign(tmp_path)
