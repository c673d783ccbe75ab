"""Remote sweep worker: ``python -m repro.sweep.worker <queue_dir>``.

One worker process per invocation. It scans the queue directory for
open jobs (published by ``repro.sweep.remote.RemoteCoordinator``),
claims pending shards by atomic rename, evaluates each shard's trace
groups through the existing execution paths (``vectorized`` — exact,
bit-identical to serial — or ``device`` — batched jax program within
``DEVICE_MODE_RTOL``), and writes the records straight into the shared
``ResultCache`` named by the job. A daemon heartbeat thread refreshes
the claimed shard's lease (mtime) so the coordinator can tell a slow
worker from a dead one.

Run it on any host that shares the queue/cache filesystem; nothing
else is coordinated. ``--once`` drains the current backlog and exits
(CI); without it the worker keeps polling until ``<queue>/stop``
exists, ``--idle-timeout-s`` elapses without work, or it is signalled.

Crash safety: a worker that dies mid-shard simply stops heartbeating;
the coordinator re-pends the shard after ``lease_s`` and another
worker re-executes it. Records it already wrote are bit-identical to
the re-execution's (deterministic sims, content-addressed keys, atomic
cache writes), so partial progress is never torn or duplicated —
``REPRO_WORKER_CRASH_AFTER_GROUPS`` injects exactly that failure for
the retry tests.
"""
from __future__ import annotations

import argparse
import json
import os
import threading
import time
import uuid
from pathlib import Path
from typing import Optional

from repro.obs.spans import PROFILER
from repro.sweep import remote
from repro.sweep.cache import ResultCache
from repro.sweep.grid import SCHEMA_VERSION


def choose_mode(worker_mode: str, payload: dict) -> str:
    """Resolve the shard's execution mode. ``inherit`` (default) uses
    whatever the coordinator ran with — the safe choice, preserving the
    backend's bit-identity contract when the sweep is vectorized.
    ``auto`` picks device for single-site shards (fastest, rtol
    contract) and vectorized otherwise; an explicit mode wins."""
    if worker_mode == "inherit":
        return payload.get("mode", "vectorized")
    if worker_mode == "auto":
        from repro.fleet.config import FleetConfig
        for group in payload["groups"]:
            if isinstance(group[0].cfg, FleetConfig):
                return "vectorized"
        return "device"
    return worker_mode


def require_device_backend() -> None:
    """A device-mode worker runs the grid program on an accelerator, or
    on the CPU only where ``JAX_PLATFORMS`` names it: JAX's silent
    fallback to the CPU would pass a host run off as a device run."""
    import jax
    if jax.default_backend() == "cpu" and "cpu" not in \
            os.environ.get("JAX_PLATFORMS", "").split(","):
        raise RuntimeError(
            "device mode found no accelerator (JAX's default backend is "
            "the CPU); set JAX_PLATFORMS=cpu to run the grid program on "
            "the CPU on purpose")


def execute_shard(payload: dict, cache: ResultCache, mode: str,
                  crash_after: Optional[int] = None) -> int:
    """Evaluate one shard's trace groups and persist every record into
    the shared cache. Returns the record count. ``crash_after`` kills
    the process (``os._exit``) after that many completed groups — the
    injected-crash hook exercising lease-expiry retry."""
    from repro.sweep.vectorized import execute_scenario_group

    n_records = 0
    done_groups = 0
    if mode == "device":
        from repro.sweep.device import execute_device_grid
        require_device_backend()
        flat = [sc for group in payload["groups"] for sc in group]
        with PROFILER.span("worker.device_grid"):
            records, _ = execute_device_grid(flat)
        with PROFILER.span("cache.store"):
            for rec in records:
                rec["meta"]["cache_hit"] = False
                cache.put(rec["key"], rec)
                n_records += 1
        return n_records

    for group in payload["groups"]:
        records = execute_scenario_group(group)
        with PROFILER.span("cache.store"):
            for rec in records:
                rec["meta"]["cache_hit"] = False
                cache.put(rec["key"], rec)
                n_records += 1
        done_groups += 1
        if crash_after is not None and done_groups >= crash_after:
            # simulated hard crash: no release, no manifest, no atexit
            os._exit(17)
    return n_records


def _start_heartbeat(running_path: Path, lease_s: float
                     ) -> threading.Event:
    """Refresh the shard lease from a daemon thread every lease_s/4;
    returns the stop event. OSErrors are swallowed — a reclaimed file
    just means the heartbeat is moot."""
    stop = threading.Event()

    def _beat():
        while not stop.wait(max(0.05, lease_s / 4.0)):
            remote.heartbeat(running_path)

    threading.Thread(target=_beat, daemon=True).start()
    return stop


def _open_jobs(queue_dir: Path):
    """Yield (job_dir, job_meta) for jobs still accepting work, oldest
    first. Schema-mismatched jobs are skipped (version skew between a
    worker's checkout and the coordinator's must never produce records
    under the wrong digest)."""
    for job_dir in sorted(queue_dir.glob("job-*")):
        try:
            meta = json.loads((job_dir / "job.json").read_text())
        except (OSError, json.JSONDecodeError):
            continue
        if meta.get("status") != "open":
            continue
        if meta.get("schema") != SCHEMA_VERSION:
            continue
        yield job_dir, meta


def _work_one_shard(job_dir: Path, meta: dict, worker_id: str,
                    worker_mode: str,
                    crash_after: Optional[int]) -> bool:
    """Try to claim and complete one shard of this job. Returns True if
    a shard was executed (or claimed-and-failed), False if nothing was
    claimable."""
    pending = sorted(p.name for p in
                     (job_dir / remote.PENDING).glob("shard-*.pkl"))
    if not pending:
        return False
    # start each worker at a different offset so concurrent claimers
    # mostly don't race for the same file
    offset = hash(worker_id) % len(pending)
    for name in pending[offset:] + pending[:offset]:
        claimed = remote.claim_shard(job_dir, name, worker_id)
        if claimed is None:
            continue
        payload, running_path = claimed
        lease_s = float(meta.get("lease_s", 30.0))
        beat_stop = _start_heartbeat(running_path, lease_s)
        t0 = time.perf_counter()
        PROFILER.enable(reset=True)
        try:
            cache = ResultCache(Path(meta["cache_root"]))
            mode = choose_mode(worker_mode, payload)
            n_records = execute_shard(payload, cache, mode,
                                      crash_after=crash_after)
        except BaseException as exc:
            PROFILER.disable()
            beat_stop.set()
            outcome = remote.release_shard(
                job_dir, running_path,
                int(meta.get("max_attempts", 3)), repr(exc))
            print(f"[worker {worker_id}] shard {payload['shard']} "
                  f"failed ({outcome}): {exc!r}", flush=True)
            return True
        PROFILER.disable()
        beat_stop.set()
        remote.complete_shard(job_dir, running_path, {
            "shard": payload["shard"],
            "worker": worker_id,
            "mode": mode,
            "n_groups": len(payload["groups"]),
            "n_records": n_records,
            "elapsed_s": round(time.perf_counter() - t0, 4),
            "phases": {k: {"count": int(a["count"]),
                           "total_s": a["total_s"]}
                       for k, a in PROFILER.aggregate().items()},
        })
        return True
    return False


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.sweep.worker",
        description="claim and execute sweep shards from a shared "
                    "work queue (see repro.sweep.remote)")
    ap.add_argument("queue", type=Path,
                    help="queue directory shared with the coordinator")
    ap.add_argument("--mode", default="inherit",
                    choices=("inherit", "auto", "vectorized", "device"),
                    help="per-shard execution mode (default: whatever "
                         "the coordinator ran with)")
    ap.add_argument("--poll-s", type=float, default=0.05,
                    help="idle poll period (default 0.05s)")
    ap.add_argument("--idle-timeout-s", type=float, default=None,
                    help="exit after this long without claimable work")
    ap.add_argument("--once", action="store_true",
                    help="drain the current backlog, then exit")
    ap.add_argument("--worker-id", default=None,
                    help="stable identity in claims/manifests "
                         "(default: host-pid-rand)")
    ap.add_argument("--crash-after-groups", type=int, default=None,
                    help=argparse.SUPPRESS)   # test hook
    args = ap.parse_args(argv)

    worker_id = args.worker_id or \
        f"{os.uname().nodename}-{os.getpid()}-{uuid.uuid4().hex[:4]}"
    crash_after = args.crash_after_groups
    if crash_after is None and os.environ.get(remote.ENV_CRASH_AFTER_GROUPS):
        crash_after = int(os.environ[remote.ENV_CRASH_AFTER_GROUPS])

    if args.mode == "vectorized":
        from repro.sweep.runner import use_cpu_only
        use_cpu_only()
    elif args.mode == "device":
        try:
            require_device_backend()
        except RuntimeError as exc:
            print(f"[worker] {exc}", flush=True)
            return 2

    # warm the execution stack BEFORE registering as alive, so
    # wait_for_workers() measures resident-cluster dispatch, not
    # python+jax import cost
    import repro.sim                                    # noqa: F401
    from repro.sweep.vectorized import execute_scenario_group  # noqa: F401

    queue: Path = args.queue
    workers_dir = queue / "workers"
    workers_dir.mkdir(parents=True, exist_ok=True)
    alive = workers_dir / f"{worker_id}.alive"
    alive.write_text(json.dumps({"pid": os.getpid(),
                                 "started": time.time()}))
    print(f"[worker {worker_id}] watching {queue}", flush=True)

    last_work = time.monotonic()
    try:
        while True:
            if (queue / "stop").exists():
                print(f"[worker {worker_id}] stop file — exiting",
                      flush=True)
                return 0
            worked = False
            for job_dir, meta in _open_jobs(queue):
                while _work_one_shard(job_dir, meta, worker_id,
                                      args.mode, crash_after):
                    worked = True
                    last_work = time.monotonic()
            if worked:
                continue
            if args.once:
                return 0
            if args.idle_timeout_s is not None and \
                    time.monotonic() - last_work > args.idle_timeout_s:
                print(f"[worker {worker_id}] idle "
                      f"{args.idle_timeout_s}s — exiting", flush=True)
                return 0
            time.sleep(args.poll_s)
    except KeyboardInterrupt:
        return 0
    finally:
        try:
            alive.unlink()
        except OSError:
            pass


if __name__ == "__main__":
    import sys
    sys.exit(main())
