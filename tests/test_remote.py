"""Remote sweep backend: shard packing, lease semantics, end-to-end
coordinator/worker runs bit-identical to serial execution, and the
injected-crash retry path (a killed worker never loses or duplicates a
scenario record)."""
import json
import threading
import time

import pytest

from repro.configs.paper_models import LLAMA3_8B
from repro.sim import SchedulerConfig, SimConfig, WorkloadConfig
from repro.sweep import GridSpec, ResultCache, SweepRunner, pack_shards
from repro.sweep import remote
from repro.sweep.remote import (ENV_CRASH_AFTER_GROUPS, RemoteOptions,
                                claim_shard, parse_shard_name,
                                publish_shard, reclaim_expired,
                                release_shard, shard_file_name,
                                spawn_worker, wait_for_workers)
from repro.sweep.worker import choose_mode

from _hypothesis_support import given, settings, st


def tiny_base(n_requests=10):
    return SimConfig(
        model=LLAMA3_8B,
        workload=WorkloadConfig(n_requests=n_requests, qps=4.0,
                                min_len=64, max_len=256, seed=0),
        scheduler=SchedulerConfig(batch_cap=8))


def tiny_grid(n_configs=3, n_report=4):
    """n_configs trace groups x n_report shared-trace scenarios."""
    return GridSpec(base=tiny_base(),
                    axes={"workload.qps": [2.0 + i for i in range(n_configs)],
                          "pue": [1.0 + 0.1 * k for k in range(n_report)]}
                    ).expand()


# --------------------------------------------------------------------------
# shard packing
# --------------------------------------------------------------------------

@given(costs=st.lists(st.floats(min_value=0.0, max_value=1e6,
                                allow_nan=False), min_size=1,
                      max_size=64),
       n_shards=st.integers(min_value=1, max_value=16))
@settings(max_examples=200, deadline=None)
def test_pack_shards_preserves_multiset_and_lpt_bound(costs, n_shards):
    shards = pack_shards(costs, n_shards)
    # the exact index multiset is preserved: nothing lost, duplicated
    # or invented
    flat = sorted(i for s in shards for i in s)
    assert flat == list(range(len(costs)))
    assert all(s for s in shards)            # no empty shards
    # greedy LPT guarantee: makespan <= total/k + max item
    k = max(1, min(n_shards, len(costs)))
    loads = [sum(costs[i] for i in s) for s in shards]
    assert max(loads) <= sum(costs) / k + max(costs) + 1e-6


def test_pack_shards_deterministic_and_balanced():
    costs = [100.0, 1.0, 1.0, 1.0, 50.0, 49.0]
    a = pack_shards(costs, 2)
    assert a == pack_shards(costs, 2)
    loads = sorted(sum(costs[i] for i in s) for s in a)
    # LPT splits this 202-cost instance exactly evenly (100+1 / 50+49+1+1)
    assert loads == [101.0, 101.0]


def test_pack_shards_more_shards_than_items():
    shards = pack_shards([3.0, 1.0], 8)
    assert sorted(i for s in shards for i in s) == [0, 1]
    assert len(shards) == 2


# --------------------------------------------------------------------------
# queue protocol: claims, leases, retries, quarantine
# --------------------------------------------------------------------------

def _job_dir(tmp_path):
    job = tmp_path / "job-t"
    for state in (remote.PENDING, remote.RUNNING, remote.DONE,
                  remote.FAILED):
        (job / state).mkdir(parents=True)
    return job


def test_shard_name_roundtrip():
    assert parse_shard_name(shard_file_name(7, 2)) == (7, 2, None)
    assert parse_shard_name(shard_file_name(7, 2, "w0")) == (7, 2, "w0")


def test_claim_is_exclusive(tmp_path):
    job = _job_dir(tmp_path)
    name = publish_shard(job, 0, {"shard": 0, "groups": []}).name
    first = claim_shard(job, name, "w0")
    assert first is not None
    assert claim_shard(job, name, "w1") is None   # lost the rename race
    payload, running = first
    assert payload["shard"] == 0
    assert running.exists()
    assert parse_shard_name(running.name) == (0, 0, "w0")


def test_lease_expiry_bumps_attempt_then_quarantines(tmp_path):
    job = _job_dir(tmp_path)
    name = publish_shard(job, 3, {"shard": 3, "groups": []}).name
    for expected_attempt in (1, 2):
        _, running = claim_shard(job, name, "w0")
        # age the lease past expiry without waiting
        import os
        old = time.time() - 3600
        os.utime(running, (old, old))
        exp, ret, quar = reclaim_expired(job, lease_s=5.0, max_attempts=3)
        assert (exp, ret, quar) == (1, 1, 0)
        pend = list((job / remote.PENDING).glob("shard-*.pkl"))
        assert len(pend) == 1
        name = pend[0].name
        assert parse_shard_name(name)[1] == expected_attempt
    # third failure exhausts max_attempts => quarantine
    _, running = claim_shard(job, name, "w0")
    outcome = release_shard(job, running, max_attempts=3, error="boom")
    assert outcome == "quarantined"
    assert not list((job / remote.PENDING).glob("shard-*.pkl"))
    manifest = json.loads(
        (job / remote.FAILED / "shard-0003.json").read_text())
    assert manifest["error"] == "boom" and manifest["attempts"] == 3


def test_heartbeat_refreshes_lease(tmp_path):
    job = _job_dir(tmp_path)
    name = publish_shard(job, 0, {"shard": 0, "groups": []}).name
    _, running = claim_shard(job, name, "w0")
    import os
    old = time.time() - 3600
    os.utime(running, (old, old))
    assert remote.heartbeat(running)
    assert reclaim_expired(job, lease_s=5.0, max_attempts=3) == (0, 0, 0)
    running.unlink()
    assert not remote.heartbeat(running)   # reclaimed/completed: False


def test_unreadable_payload_is_quarantined(tmp_path):
    job = _job_dir(tmp_path)
    path = job / remote.PENDING / shard_file_name(4, 0)
    path.write_bytes(b"not a pickle")
    assert claim_shard(job, path.name, "w0") is None
    assert (job / remote.FAILED / "shard-0004.json").exists()


def test_choose_mode():
    payload = {"mode": "vectorized",
               "groups": [[type("S", (), {"cfg": tiny_base()})()]]}
    assert choose_mode("inherit", payload) == "vectorized"
    assert choose_mode("device", payload) == "device"
    assert choose_mode("auto", payload) == "device"  # single-site shard


def test_device_worker_refuses_silent_cpu_fallback(monkeypatch):
    # the CPU is the device program's backend only where JAX_PLATFORMS
    # names it; otherwise a device-mode worker without an accelerator
    # fails and says why instead of running on the host
    from repro.sweep.worker import require_device_backend

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    require_device_backend()
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="no accelerator"):
        require_device_backend()


# --------------------------------------------------------------------------
# runner integration + validation
# --------------------------------------------------------------------------

def test_remote_backend_requires_cache_and_rejects_probe():
    with pytest.raises(ValueError, match="requires a ResultCache"):
        SweepRunner(cache=None, backend="remote")
    with pytest.raises(ValueError, match="unknown backend"):
        SweepRunner(backend="carrier-pigeon")
    cache = ResultCache.__new__(ResultCache)   # placeholder, not used
    with pytest.raises(ValueError, match="trace groups"):
        SweepRunner(cache=cache, backend="remote", mode="event_loop")
    from repro.obs.probe import NULL_PROBE
    with pytest.raises(ValueError, match="probe"):
        SweepRunner(cache=cache, backend="remote", probe=NULL_PROBE)


@pytest.mark.slow
def test_remote_run_matches_serial_bitwise(tmp_path):
    """Happy path: coordinator + 2 spawned workers over a real queue,
    records bit-identical to in-process execution, zero expired
    leases, and the follow-up run is all cache hits."""
    scenarios = tiny_grid(n_configs=4, n_report=3)
    cache = ResultCache(tmp_path / "cache")
    opts = RemoteOptions(queue_dir=tmp_path / "q", spawn_workers=2,
                         lease_s=15.0, verify_groups=1, timeout_s=180)
    records, stats = SweepRunner(cache=cache, backend="remote",
                                 remote=opts).run(scenarios)
    assert stats.executed == len(scenarios)
    assert stats.shards >= 1 and stats.remote_workers >= 1
    assert stats.lease_expired == 0 and stats.quarantined == 0

    ref, _ = SweepRunner(cache=None, mode="vectorized").run(scenarios)
    assert [r["metrics"] for r in records] == [r["metrics"] for r in ref]
    assert all(r["meta"]["cache_hit"] is False for r in records)

    again, stats2 = SweepRunner(cache=cache, backend="remote",
                                remote=opts).run(scenarios)
    assert stats2.executed == 0
    assert stats2.cache_hits == len(scenarios)
    assert [r["metrics"] for r in again] == [r["metrics"] for r in ref]


@pytest.mark.slow
def test_injected_crash_converges_bit_identical(tmp_path):
    """A worker killed mid-shard (after persisting one group) loses its
    lease; the shard is re-pended and a second worker re-executes it.
    The final records are bit-identical to serial execution — the
    partially-written cache entries are simply overwritten with
    identical bytes, never torn or duplicated."""
    scenarios = tiny_grid(n_configs=4, n_report=3)
    cache = ResultCache(tmp_path / "cache")
    q = tmp_path / "q"
    opts = RemoteOptions(queue_dir=q, spawn_workers=0, n_shards=2,
                         lease_s=1.0, poll_s=0.05, timeout_s=180)

    out = {}
    def coordinate():
        out["res"] = SweepRunner(cache=cache, backend="remote",
                                 remote=opts).run(scenarios)
    t = threading.Thread(target=coordinate)
    t.start()
    try:
        # worker A crashes (os._exit) after finishing exactly 1 group
        pa = spawn_worker(q, "crashy",
                          env={ENV_CRASH_AFTER_GROUPS: "1"},
                          log_path=tmp_path / "a.log")
        assert pa.wait(timeout=120) == 17
        # worker B drains the rest, including the reclaimed shard
        pb = spawn_worker(q, "steady", log_path=tmp_path / "b.log")
        t.join(timeout=150)
        pb.terminate()
        pb.wait(timeout=10)
    finally:
        (q / "stop").touch()
        t.join(timeout=30)
    assert not t.is_alive()
    records, stats = out["res"]

    assert stats.lease_expired >= 1 and stats.retried >= 1
    assert stats.quarantined == 0

    ref, _ = SweepRunner(cache=None, mode="vectorized").run(scenarios)
    assert [r["metrics"] for r in records] == [r["metrics"] for r in ref]

    # no torn or duplicated cache entries: exactly one valid JSON per
    # unique scenario key, each round-tripping its own digest
    keys = list(cache.iter_keys())
    assert sorted(keys) == sorted({sc.key for sc in scenarios})
    for key in keys:
        on_disk = json.loads(cache.path_for(key).read_text())
        assert on_disk["key"] == key


@pytest.mark.slow
def test_worker_skips_schema_mismatched_jobs(tmp_path):
    """Version skew: a worker whose checkout disagrees on the record
    schema must never execute the job (records under a stale digest
    would poison the shared cache)."""
    scenarios = tiny_grid(n_configs=1, n_report=2)
    q = tmp_path / "q"
    job = q / "job-skew"
    for state in (remote.PENDING, remote.RUNNING, remote.DONE,
                  remote.FAILED):
        (job / state).mkdir(parents=True)
    remote.atomic_write_json(job / "job.json", {
        "job": "skew", "status": "open", "schema": -1,
        "mode": "vectorized", "n_shards": 1, "lease_s": 30.0,
        "max_attempts": 3, "cache_root": str(tmp_path / "cache")})
    publish_shard(job, 0, {"job": "skew", "shard": 0, "schema": -1,
                           "mode": "vectorized",
                           "groups": [list(scenarios)]})
    proc = spawn_worker(q, "w0", log_path=tmp_path / "w.log")
    try:
        # wait until the worker is registered (warm) and has had time
        # to scan the queue, then check the shard is still pending
        wait_for_workers(q, 1, timeout_s=120)
        time.sleep(1.0)
        assert list((job / remote.PENDING).glob("shard-*.pkl"))
        assert not list((job / remote.RUNNING).glob("shard-*.pkl"))
        assert not list((job / remote.DONE).glob("*.json"))
    finally:
        proc.terminate()
        proc.wait(timeout=10)


def test_coordinator_rejects_fully_quarantined_job(tmp_path):
    """A poison shard that exhausts its attempts fails the job loudly
    instead of returning partial records."""
    scenarios = tiny_grid(n_configs=1, n_report=2)
    cache = ResultCache(tmp_path / "cache")
    opts = RemoteOptions(queue_dir=tmp_path / "q", spawn_workers=0,
                         n_shards=1, lease_s=0.2, poll_s=0.05,
                         max_attempts=1, timeout_s=60)
    out = {}
    def coordinate():
        try:
            SweepRunner(cache=cache, backend="remote",
                        remote=opts).run(scenarios)
        except RuntimeError as exc:
            out["err"] = exc
    t = threading.Thread(target=coordinate)
    t.start()
    # claim the only shard and let the lease lapse without heartbeat:
    # with max_attempts=1 the reclaim quarantines it immediately
    deadline = time.monotonic() + 30
    claimed = None
    while claimed is None and time.monotonic() < deadline:
        jobs = sorted((tmp_path / "q").glob("job-*"))
        for job in jobs:
            for p in (job / remote.PENDING).glob("shard-*.pkl"):
                claimed = claim_shard(job, p.name, "dead-worker")
                if claimed:
                    break
        time.sleep(0.05)
    assert claimed is not None
    t.join(timeout=60)
    assert not t.is_alive()
    assert "quarantined" in str(out["err"])


def test_shard_payload_roundtrips_scenarios(tmp_path):
    """Scenarios pickle losslessly through a shard file — the lazily
    cached digest fields don't leak stale state across the boundary."""
    scenarios = tiny_grid(n_configs=2, n_report=2)
    job = _job_dir(tmp_path)
    publish_shard(job, 0, {"shard": 0, "groups": [list(scenarios)]})
    name = shard_file_name(0, 0)
    payload, _ = claim_shard(job, name, "w0")
    thawed = payload["groups"][0]
    assert [sc.key for sc in thawed] == [sc.key for sc in scenarios]
    assert [sc.trace_key for sc in thawed] == \
        [sc.trace_key for sc in scenarios]
    assert thawed[0].cfg.workload.qps == scenarios[0].cfg.workload.qps
