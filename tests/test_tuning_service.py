"""Fleet tuning service (DESIGN.md §15): miss-fed job queue,
builder/evaluator workers, find-db artifact.

The multiprocess tests fork real worker processes through the
``tune_service`` CLI so queue claims exercise the actual cross-process
lock, and a crashed worker is a real ``os._exit`` mid-lease."""

import dataclasses
import json
import os
import stat
import subprocess
import sys
import time
from pathlib import Path

import pytest

from repro.core import registry
from repro.core.plan import Plan, Problem
from repro.tuning.find_db import (export_find_db, export_program_bundle,
                                  read_find_db, verify_program_bundle)
from repro.tuning.queue import JobQueue, TuneJob, harvest

SRC = str(Path(__file__).resolve().parents[1] / "src")

# cheap TSMM problems (k >= 512, one dim <= 256, ratio >= 8) that measure
# in milliseconds on CPU
P_SKINNY = Problem(2, 512, 512, "float32")
P_TALL = Problem(1024, 512, 128, "float32")
P_TALL2 = Problem(512, 512, 64, "float32")


@pytest.fixture
def fleet(tmp_path, monkeypatch):
    """One shared fleet directory: plan/measure caches, miss log, queue."""
    monkeypatch.setenv("REPRO_PLAN_CACHE", str(tmp_path / "plans.json"))
    monkeypatch.setenv("REPRO_MEASURE_CACHE", str(tmp_path / "meas.json"))
    monkeypatch.setenv("REPRO_MISS_LOG", str(tmp_path / "misses.json"))
    monkeypatch.setenv("REPRO_TUNE_QUEUE", str(tmp_path / "queue.json"))
    monkeypatch.delenv("REPRO_FIND_DB", raising=False)
    monkeypatch.delenv("REPRO_TUNE_CRASH", raising=False)
    registry.clear_memory()
    yield tmp_path
    registry.clear_memory()


def _fleet_env(extra=None):
    env = dict(os.environ, PYTHONPATH=SRC)
    env.update(extra or {})
    return env


def _miss(problem: Problem, times: int = 1) -> None:
    for _ in range(times):
        registry.get(problem.key())


# -- satellite: deduped miss records ------------------------------------


def test_miss_records_dedupe_and_count(fleet):
    _miss(P_SKINNY, 3)
    _miss(P_TALL)
    recs = registry.miss_records()
    assert [r["key"] for r in recs] == [P_SKINNY.key(), P_TALL.key()]
    assert recs[0]["count"] == 3 and recs[1]["count"] == 1
    assert recs[0]["last_seen"] > 0
    # snapshot does not drain; drain does
    assert len(registry.miss_records()) == 2
    assert len(registry.drain_miss_records()) == 2
    assert registry.drain_miss_records() == []
    assert registry.drain_misses() == []


def test_flush_misses_merges_counts_across_flushes(fleet):
    _miss(P_SKINNY, 2)
    assert registry.flush_misses() == 1          # one distinct record drained
    _miss(P_SKINNY)
    _miss(P_TALL)
    registry.flush_misses()
    raw = json.loads((fleet / "misses.json").read_text())
    k = f"{registry._platform()}/{P_SKINNY.key()}"
    assert raw[k]["count"] == 3, "second flush must merge, not overwrite"
    assert len(raw) == 2
    # nothing pending -> no write at all
    before = (fleet / "misses.json").stat().st_mtime_ns
    assert registry.flush_misses() == 0
    assert (fleet / "misses.json").stat().st_mtime_ns == before


# -- tentpole: harvest + queue semantics --------------------------------


def test_harvest_dedupes_ranks_and_consumes(fleet):
    _miss(P_SKINNY, 5)
    _miss(P_TALL)
    registry.flush_misses()
    q = JobQueue()
    counts = harvest(q)
    assert counts["enqueued"] == 2 and counts["skipped"] == 0
    assert not (fleet / "misses.json").exists(), "harvest consumes the log"
    jobs = q.jobs()
    assert len(jobs) == 2
    hot = jobs[f"{registry._platform()}/{P_SKINNY.key()}"]
    assert hot.priority == 5
    assert hot.candidates and hot.grammar_version
    # hottest miss claims first
    first = q.claim("w0")
    assert first.problem_key == P_SKINNY.key()
    # a re-harvest of fresh misses merges into the live job
    _miss(P_TALL, 4)
    registry.flush_misses()
    counts = harvest(q)
    assert counts["merged"] == 1
    assert q.jobs()[f"{registry._platform()}/{P_TALL.key()}"].priority == 5


def test_harvest_skips_done_jobs(fleet):
    _miss(P_SKINNY)
    registry.flush_misses()
    q = JobQueue()
    harvest(q)
    j = q.claim("w0")
    assert q.complete(j.job_id, "w0", result="winner")
    # the same miss arrives again from another engine: measured once
    _miss(P_SKINNY)
    registry.flush_misses()
    counts = harvest(q)
    assert counts["already_done"] == 1 and counts["enqueued"] == 0
    assert q.status()["done"] == 1 and q.status()["total"] == 1


def test_claims_are_exclusive_and_platform_filtered(fleet):
    q = JobQueue()
    q.enqueue([TuneJob(P_SKINNY.key(), "cpu"),
               TuneJob(P_TALL.key(), "cpu"),
               TuneJob(P_TALL2.key(), "tpu")])
    a = q.claim("wa", platform="cpu")
    b = q.claim("wb", platform="cpu")
    assert a.job_id != b.job_id
    assert q.claim("wc", platform="cpu") is None, "no third cpu job"
    assert q.claim("wt", platform="tpu").problem_key == P_TALL2.key()


def test_lease_expiry_requeues_then_parks(fleet):
    now = [1000.0]
    q = JobQueue(clock=lambda: now[0], max_attempts=2)
    q.enqueue([TuneJob(P_SKINNY.key(), "cpu")])
    j1 = q.claim("crasher", lease_s=10, platform="cpu")
    assert j1.attempts == 1
    assert q.claim("w2", platform="cpu") is None, "leased job not claimable"
    now[0] += 11                                  # crasher died; lease lapsed
    j2 = q.claim("w2", lease_s=10, platform="cpu")
    assert j2 is not None and j2.attempts == 2
    assert ("expire", "crasher") in {(e[0], e[1]) for e in j2.history}
    now[0] += 11                                  # w2 died too: over the cap
    assert q.claim("w3", platform="cpu") is None
    job = q.jobs()[j2.job_id]
    assert job.state == "failed" and "lease expired" in job.error
    # fresh demand revives a parked job
    q.enqueue([TuneJob(P_SKINNY.key(), "cpu", priority=2)])
    revived = q.claim("w3", platform="cpu")
    assert revived is not None and revived.attempts == 1


def test_complete_rejected_after_lease_reassignment(fleet):
    now = [0.0]
    q = JobQueue(clock=lambda: now[0])
    q.enqueue([TuneJob(P_SKINNY.key(), "cpu")])
    j = q.claim("slow", lease_s=5, platform="cpu")
    now[0] += 6
    j2 = q.claim("fast", lease_s=5, platform="cpu")
    assert j2.job_id == j.job_id
    assert not q.complete(j.job_id, "slow", result="stale"), \
        "a worker that lost its lease must not commit the ledger"
    assert q.complete(j2.job_id, "fast", result="fresh")
    assert q.jobs()[j.job_id].result == "fresh"
    done_events = [e for e in q.jobs()[j.job_id].history if e[0] == "done"]
    assert len(done_events) == 1


def test_queue_fail_releases_for_retry(fleet):
    q = JobQueue()
    q.enqueue([TuneJob(P_SKINNY.key(), "cpu")])
    j = q.claim("w0", platform="cpu")
    assert q.fail(j.job_id, "w0", error="flaky measure")
    job = q.jobs()[j.job_id]
    assert job.state == "pending" and job.error == "flaky measure"
    assert q.claim("w1", platform="cpu").attempts == 2


# -- tentpole: builder / evaluator workers ------------------------------


def test_builder_builds_payload_candidates(fleet):
    from repro.tuning.worker import Builder
    _miss(P_SKINNY)
    registry.flush_misses()
    q = JobQueue()
    harvest(q)
    job = q.claim("w0")
    built = Builder(build_k=3).build(job)
    assert len(built) == 3
    ok = [b for b in built if b.ok]
    assert ok, "no candidate AOT-lowered"
    payload = set(job.candidates)
    for b in ok:
        assert b.plan.tuning_key() in payload or b.plan.chosen_by == "model"
        assert b.build_s >= 0


def test_worker_in_process_drains_queue(fleet):
    from repro.tuning.worker import run_worker
    _miss(P_SKINNY)
    _miss(P_TALL2)
    registry.flush_misses()
    q = JobQueue()
    harvest(q)
    rep = run_worker(q, iters=1, warmup=0, top_k=2, stable=1, build_k=2)
    assert rep.done == 2 and rep.failed == 0
    assert q.status() == {"pending": 0, "leased": 0, "done": 2,
                          "failed": 0, "total": 2}
    for p in (P_SKINNY, P_TALL2):
        plan = registry.peek(p.key())
        assert plan is not None and plan.chosen_by == "measured"
    # the ledger records the winning tuning key
    for j in q.jobs().values():
        assert j.result == registry.peek(j.problem_key).tuning_key()


def test_background_tuner_defers_fleet_owned_misses(fleet, monkeypatch):
    from repro.core import autotuner
    from repro.serve.engine import _BackgroundTuner

    q = JobQueue()
    q.enqueue([TuneJob(P_SKINNY.key(), registry._platform())])
    tuned = []
    monkeypatch.setattr(autotuner, "make_plan",
                        lambda problem, *a, **kw: tuned.append(problem.key())
                        or Plan(problem, "tall_a", bm=8, bk=128, bn=128))
    tuner = _BackgroundTuner(queue=q)
    tuner.submit([P_SKINNY.key(), P_TALL.key()])
    tuner.join(timeout=60)
    assert tuned == [P_TALL.key()], \
        "fleet-owned miss must not be measured by the engine tuner"


# -- the subprocess fleet -----------------------------------------------


def _seed_jobs(problems) -> JobQueue:
    for p in problems:
        _miss(p)
    registry.flush_misses()
    q = JobQueue()
    harvest(q)
    return q


def _run_workers(n, *, max_jobs=0, lease_s=600, extra_env=None,
                 timeout=600):
    # the default lease must outlast a worst-case contended build+measure
    # (n jax processes sharing one core under a loaded full-suite run) or
    # an expiry mid-job turns into a spurious stale-holder rejection;
    # tests that WANT expiry pass a short lease_s explicitly
    cmd = [sys.executable, "-m", "repro.launch.tune_service", "work",
           "--workers", "1", "--iters", "1", "--warmup", "0",
           "--top-k", "2", "--stable", "1", "--build-k", "2",
           "--lease-s", str(lease_s)]
    if max_jobs:
        cmd += ["--max-jobs", str(max_jobs)]
    procs = [subprocess.Popen(cmd, env=_fleet_env(extra_env),
                              stdout=subprocess.PIPE, text=True)
             for _ in range(n)]
    outs = [p.communicate(timeout=timeout)[0] for p in procs]
    return [p.returncode for p in procs], outs


def _reports(outs):
    reps = []
    for out in outs:
        for line in out.splitlines():
            if line.startswith("worker: "):
                reps.append(json.loads(line[len("worker: "):]))
    return reps


def test_three_worker_fleet_measures_each_job_exactly_once(fleet):
    q = _seed_jobs([P_SKINNY, P_TALL, P_TALL2])
    assert q.status()["pending"] == 3
    rcs, outs = _run_workers(3)
    assert rcs == [0, 0, 0]
    assert q.status() == {"pending": 0, "leased": 0, "done": 3,
                          "failed": 0, "total": 3}
    # exactly-once: the per-job audit trail holds ONE done event, and the
    # union of the workers' ledgers covers every job with no overlap
    jobs = q.jobs()
    for j in jobs.values():
        assert len([e for e in j.history if e[0] == "done"]) == 1
    claimed = [r[0] for rep in _reports(outs) for r in rep["results"]]
    assert sorted(claimed) == sorted(jobs)
    # winners committed through the flush-merge: all measured, none lost
    registry.clear_memory()
    for p in (P_SKINNY, P_TALL, P_TALL2):
        plan = registry.peek(p.key())
        assert plan is not None and plan.chosen_by == "measured", p.key()


def test_work_refuses_more_workers_than_tpu_chips(fleet, monkeypatch):
    """On a host with one TPU chip, two workers would fight over it: the
    parent refuses before it starts any child."""
    from repro.launch import tune_service
    monkeypatch.setattr(tune_service, "local_tpu_chips", lambda: 1)

    def no_child(*a, **k):
        raise AssertionError("a worker process was started")

    monkeypatch.setattr(tune_service.subprocess, "Popen", no_child)
    with pytest.raises(SystemExit, match="1 TPU chip"):
        tune_service.main(["work", "--workers", "2"])


def test_work_binds_each_worker_to_its_own_chip(fleet, monkeypatch):
    from repro.launch import tune_service
    monkeypatch.setattr(tune_service, "local_tpu_chips", lambda: 2)
    envs = []

    class FakeProc:
        def __init__(self, cmd, env):
            envs.append(env)

        def wait(self):
            return 0

    monkeypatch.setattr(tune_service.subprocess, "Popen", FakeProc)
    assert tune_service.main(["work", "--workers", "2"]) == 0
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1"]


def test_local_tpu_chips_is_zero_off_tpu(monkeypatch):
    from repro.launch import tune_service
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert tune_service.local_tpu_chips() == 0


def test_crashed_worker_lease_is_requeued_and_completed(fleet):
    q = _seed_jobs([P_SKINNY])
    # worker 1 dies the hard way right after claiming (os._exit)
    rcs, _ = _run_workers(1, lease_s=3,
                          extra_env={"REPRO_TUNE_CRASH": "after-claim"})
    assert rcs == [17]
    job = next(iter(q.jobs().values()))
    assert job.state == "leased", "crash left the lease held"
    time.sleep(3.5)                               # let the lease lapse
    rcs, outs = _run_workers(1)
    assert rcs == [0]
    job = next(iter(q.jobs().values()))
    assert job.state == "done" and job.attempts == 2
    events = [e[0] for e in job.history]
    assert "expire" in events and events.count("done") == 1
    registry.clear_memory()                       # re-read the shared cache
    assert registry.peek(P_SKINNY.key()).chosen_by == "measured"


# -- tentpole: find-db artifact -----------------------------------------


def _measured_plan(problem: Problem) -> Plan:
    return dataclasses.replace(
        Plan(problem, "tall_a" if problem.skinny_dim == "n" else "skinny_a",
             bm=min(problem.m, 256), bk=512, bn=128),
        chosen_by="measured", score=1e-4)


def test_find_db_round_trips_and_is_read_only(fleet):
    registry.put(_measured_plan(P_TALL))
    registry.put(_measured_plan(P_SKINNY))
    out = fleet / "find_db.json"
    header = export_find_db(out)
    assert header["plan_count"] == 2
    assert header["grammar_version"]
    assert registry._platform() in header["platforms"]
    assert not (out.stat().st_mode & stat.S_IWUSR), "artifact is read-only"
    plans = read_find_db(out)
    assert plans[P_TALL.key()] == registry.peek(P_TALL.key())
    assert plans[P_SKINNY.key()] == registry.peek(P_SKINNY.key())
    # measured_only export drops model-ranked plans
    registry.put(Plan(P_TALL2, "tall_a", bm=256, bk=512, bn=128))
    h2 = export_find_db(fleet / "fdb2.json", measured_only=True)
    assert h2["plan_count"] == 2
    # re-export to the same (read-only) path still works
    export_find_db(out)


def test_find_db_rejects_stale_grammar(fleet):
    registry.put(_measured_plan(P_TALL))
    out = fleet / "find_db.json"
    export_find_db(out)
    blob = json.loads(out.read_text())
    blob["header"]["grammar_version"] = "gen-0-ancient"
    out.chmod(0o644)
    out.write_text(json.dumps(blob))
    assert read_find_db(out) == {}, "non-strict load degrades to empty"
    with pytest.raises(ValueError, match="grammar"):
        read_find_db(out, strict=True)
    # valid grammar again, but ask for a platform the file lacks
    from repro.kernels.variants.grammar import GRAMMAR_VERSION
    blob["header"]["grammar_version"] = GRAMMAR_VERSION
    out.write_text(json.dumps(blob))
    assert read_find_db(out, platform="tpu") == {}
    with pytest.raises(ValueError, match="platform"):
        read_find_db(out, platform="tpu", strict=True)


def test_registry_overlays_find_db_with_local_precedence(fleet,
                                                         monkeypatch):
    registry.put(_measured_plan(P_TALL))
    registry.put(_measured_plan(P_SKINNY))
    out = fleet / "find_db.json"
    export_find_db(out)
    # a fresh host: empty plan cache, artifact attached
    monkeypatch.setenv("REPRO_PLAN_CACHE", str(fleet / "host2_plans.json"))
    monkeypatch.setenv("REPRO_FIND_DB", str(out))
    registry.clear_memory()
    assert registry.get(P_TALL.key()) is not None
    assert registry.get(P_SKINNY.key()) is not None
    assert registry.stats() == {"hits": 2, "misses": 0}
    assert registry.miss_records() == []
    # local plans beat the artifact: host2 re-tunes P_TALL, then reloads
    local = dataclasses.replace(_measured_plan(P_TALL), bk=128)
    registry.put(local)
    registry.clear_memory()
    assert registry.get(P_TALL.key()).bk == 128, \
        "find-db must not displace a newer local plan"


def test_program_bundle_manifest_round_trip(fleet):
    src = fleet / "programs"
    src.mkdir()
    (src / "decode_b2_t1_abc.prog").write_bytes(b"x" * 64)
    (src / "prefill_b2_t8_def.prog").write_bytes(b"y" * 64)
    (src / "ignored.txt").write_text("not a program")
    bundle = fleet / "bundle"
    manifest = export_program_bundle(bundle, src_dir=src)
    assert len(manifest["files"]) == 2
    assert manifest["code_fingerprint"]
    res = verify_program_bundle(bundle)
    assert res["ok"] and res["checked"] == 2
    (bundle / "decode_b2_t1_abc.prog").write_bytes(b"tampered")
    res = verify_program_bundle(bundle)
    assert not res["ok"]
    assert any("digest mismatch" in p for p in res["problems"])


# -- E2E: engines -> harvest -> workers -> export -> zero-miss restart --


def test_fleet_end_to_end_engine_restart_is_lookup_only(fleet, monkeypatch):
    import jax
    import numpy as np

    from repro.configs.base import get_reduced_config
    from repro.models.registry import build_model
    from repro.serve.engine import Engine
    from repro.tuning.worker import run_worker

    monkeypatch.setenv("REPRO_PROGRAM_CACHE", str(fleet / "programs"))
    registry.clear_memory()
    cfg = get_reduced_config("qwen1_5_4b").reduced(
        d_model=512, d_ff=1024, num_layers=1, vocab_size=512,
        num_heads=8, num_kv_heads=8, head_dim=64)
    model = build_model(cfg)
    params, axes = model.init(jax.random.PRNGKey(0))
    reqs = [{"tokens": np.arange(4, dtype=np.int32)} for _ in range(2)]

    # 1. fleet-mode engine (no background tuner) serves and persists misses
    eng = Engine(model, params, axes, max_len=32, max_batch=2)
    assert eng.tuner is None
    eng.serve(reqs, steps=2)
    assert registry.stats()["misses"] > 0
    assert (fleet / "misses.json").exists(), \
        "fleet-mode engine must flush misses for harvest"
    assert registry.miss_records() == [], "flush drains the pending log"

    # 2. harvest -> one deduped job per distinct problem
    q = JobQueue()
    counts = harvest(q)
    assert counts["enqueued"] > 0 and counts["merged"] == 0
    n_jobs = q.status()["total"]

    # 3. a worker measures every job exactly once
    rep = run_worker(q, iters=1, warmup=0, top_k=2, stable=1, build_k=2)
    assert rep.done == n_jobs and rep.failed == 0

    # 4. export the find-db
    out = fleet / "find_db.json"
    header = export_find_db(out)
    assert header["plan_count"] >= n_jobs

    # 5. restarted engine on a FRESH plan cache + the artifact: zero
    # misses; a second restart against the warmed program cache also
    # performs zero traces (the lookup-only fleet contract)
    monkeypatch.setenv("REPRO_PLAN_CACHE", str(fleet / "host2_plans.json"))
    monkeypatch.setenv("REPRO_FIND_DB", str(out))
    for restart in range(2):
        registry.clear_memory()
        eng2 = Engine(model, params, axes, max_len=32, max_batch=2)
        eng2.serve(reqs, steps=2)
        s = registry.stats()
        assert s["misses"] == 0, \
            f"restart {restart}: {s['misses']} misses with find-db attached"
        if restart == 1:
            assert eng2.programs.stats()["traced"] == 0, \
                "warm restart must not trace"
