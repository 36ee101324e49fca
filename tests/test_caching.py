"""CacheBackend protocol: LRU bounds, persistent JSONL journal, key safety.

Acceptance bars:

* the solve-memo key holds the policy, the backend and the request
  multiset — a memo filled by one backend never serves another;
* a bounded LRU *smaller than the working set* on the seeded 240-request
  constrained-pool trace yields bit-identical schedules to an unbounded
  cache — eviction can only cost re-solves, never change a result;
* :class:`~repro.core.JsonlCacheBackend` round-trips its journal across
  restarts (replay -> memo hits without re-solving), tolerates torn/foreign
  lines (keys of another layout included), and ``compact()``/``clear()`` behave;
* both shipped backends satisfy the runtime-checkable
  :class:`~repro.core.CacheBackend` protocol;
* the journal is single-writer: a second live writer on the same path is
  refused with :class:`~repro.core.CacheLockedError` (two appenders would
  interleave torn lines), while a lockfile left by a dead process is taken
  over silently.
"""

import json
import os

import pytest

from repro.core import (
    CacheBackend,
    CacheLockedError,
    ExecutionContext,
    JsonlCacheBackend,
    SolveCache,
    solve,
)
from repro.serving import DriveCosts, demo_library, poisson_trace, serve_trace

from conftest import random_instance

SEED = 20260731
COSTS = DriveCosts(mount=150_000, unmount=60_000, load_seek=30_000)
DEV = ExecutionContext(backend="pallas-interpret")

#: summary() keys that measure *work done*, not *what was served* — cache
#: behavior is allowed to change these (a memo hit does zero DP work; an
#: evicted entry forces a re-solve), never anything else
WORK_KEYS = ("cache", "cells_evaluated", "cells_reused", "cells_per_batch")


def _scrub_work(summary):
    for key in WORK_KEYS:
        summary.pop(key, None)
    return summary


def build_trace(n_requests=240):
    return poisson_trace(
        demo_library(SEED), n_requests=n_requests, mean_interarrival=250_000,
        seed=SEED,
    )


# ---------------------------------------------------------------------------
# key: the backend is part of the identity
# ---------------------------------------------------------------------------
def test_cache_key_separates_backend(rng):
    """A memo filled by the python backend must not serve a
    ``pallas-interpret`` call: a hit reports the backend that computed it.
    Each backend re-hits its own entry, and the answers are equal."""
    cache = SolveCache()
    inst = random_instance(rng, lo=3, hi=8)
    py_ctx = ExecutionContext(cache=cache)
    dev_ctx = DEV.replace(cache=cache)
    r1 = solve(inst, policy="dp", context=py_ctx)
    assert cache.stats()["misses"] == 1 and cache.stats()["entries"] == 1
    r2 = solve(inst, policy="dp", context=dev_ctx)
    assert cache.stats()["hits"] == 0, "another backend must not hit"
    assert cache.stats() == {
        "hits": 0, "misses": 2, "entries": 2, "warm_entries": 0,
    }
    assert (r1.backend, r2.backend) == ("python", "pallas-interpret")
    # both are exact solves of the same instance -> same answer
    assert (r1.cost, r1.detours) == (r2.cost, r2.detours)
    # and each backend re-hits itself
    assert solve(inst, policy="dp", context=py_ctx).backend == "python"
    assert solve(inst, policy="dp", context=dev_ctx).backend == "pallas-interpret"
    assert cache.stats()["hits"] == 2


def test_positional_get_put_defaults_match_default_context(rng):
    """The 3-arg get/put form keys exactly as a solve under the context."""
    cache = SolveCache()
    inst = random_instance(rng, lo=2, hi=6)
    res = solve(inst, policy="dp", context=ExecutionContext(cache=cache))
    hit = cache.get(inst, "dp", "python")
    assert hit is not None and (hit.cost, hit.detours) == (res.cost, res.detours)
    assert list(cache._store) == [SolveCache.key(inst, "dp", "python")]
    assert cache.get(inst, "dp", "pallas-interpret") is None


# ---------------------------------------------------------------------------
# bounded LRU below the working set: slower, never different
# ---------------------------------------------------------------------------
def test_bounded_lru_below_working_set_is_bit_identical():
    """240-request constrained-pool trace, served twice through each cache:
    maxsize=4 thrashes (evictions force re-solves on the second pass) yet
    every schedule and timeline matches the unbounded run both times."""
    trace = build_trace(240)
    small = SolveCache(maxsize=4)
    big = SolveCache(maxsize=1 << 20)

    def run(cache):
        return _scrub_work(serve_trace(
            demo_library(SEED, with_cache=False), trace, "accumulate",
            window=400_000, policy="dp", n_drives=2, drive_costs=COSTS,
            context=ExecutionContext(cache=cache),
        ).summary())

    assert run(small) == run(big)  # first pass: cold caches
    assert small.stats()["entries"] == 4  # pinned at the bound
    assert big.stats()["entries"] > 4  # the true working set is larger
    assert run(small) == run(big)  # second pass: hits vs evictions
    # eviction forced strictly more solver work on the replay, and only that
    assert small.stats()["misses"] > big.stats()["misses"]
    assert big.stats()["hits"] > small.stats()["hits"]


# ---------------------------------------------------------------------------
# JSONL journal backend
# ---------------------------------------------------------------------------
def test_jsonl_backend_rewarms_across_restart(tmp_path, rng):
    path = tmp_path / "memo.jsonl"
    insts = [random_instance(rng, lo=2, hi=8) for _ in range(5)]
    first = JsonlCacheBackend(path)
    ctx = ExecutionContext(cache=first)
    originals = [solve(i, policy="dp", context=ctx) for i in insts]
    assert first.stats()["misses"] == 5 and first.stats()["loaded"] == 0
    first.close()

    second = JsonlCacheBackend(path)
    assert second.loaded == 5 and len(second) == 5
    replayed = [
        solve(i, policy="dp", context=ExecutionContext(cache=second))
        for i in insts
    ]
    assert second.stats()["hits"] == 5 and second.stats()["misses"] == 0
    assert [(r.cost, r.detours) for r in replayed] == [
        (r.cost, r.detours) for r in originals
    ]
    second.close()


def test_jsonl_backend_skips_torn_and_foreign_lines(tmp_path, rng):
    path = tmp_path / "memo.jsonl"
    inst = random_instance(rng, lo=2, hi=6)
    backend = JsonlCacheBackend(path)
    res = solve(inst, policy="dp", context=ExecutionContext(cache=backend))
    backend.close()
    with open(path, "a", encoding="utf-8") as fh:
        fh.write("not json at all\n")
        fh.write(json.dumps({"unrelated": True}) + "\n")
        # a row of the 9-field key journals held before the kernel options
        # left the key: it could never hit again
        old_key = ["dp", "python", "strict", None, *JsonlCacheBackend._encode_key(
            SolveCache.key(inst, "dp", "python"))[2:]]
        fh.write(json.dumps({"k": old_key, "cost": 0, "det": []}) + "\n")
        fh.write('{"k": ["dp", "python"')  # torn mid-write
    reopened = JsonlCacheBackend(path)
    assert reopened.loaded == 1 and len(reopened) == 1
    hit = reopened.get(inst, "dp", "python")
    assert hit is not None and (hit.cost, hit.detours) == (res.cost, res.detours)
    reopened.close()


def test_jsonl_backend_compact_and_clear(tmp_path, rng):
    path = tmp_path / "memo.jsonl"
    backend = JsonlCacheBackend(path, maxsize=3)
    ctx = ExecutionContext(cache=backend)
    insts = [random_instance(rng, lo=2, hi=6) for _ in range(6)]
    for i in insts:
        solve(i, policy="dp", context=ctx)
    assert len(backend) == 3  # LRU bound holds in memory
    assert sum(1 for _ in open(path)) == 6  # journal is append-only
    backend.compact()
    assert sum(1 for _ in open(path)) == 3  # rewritten to live entries
    # the three most-recent instances survive compaction as hits
    for i in insts[-3:]:
        assert backend.get(i, "dp", "python") is not None
    backend.clear()
    assert len(backend) == 0 and path.read_text() == ""
    backend.close()


def test_jsonl_backend_compact_survives_crash_midway(tmp_path, rng, monkeypatch):
    """A process killed mid-compaction must leave either the old journal or
    the new one — never a torn mix — and the backend must stay usable when
    the staging write itself fails."""
    import os as _os

    path = tmp_path / "memo.jsonl"
    backend = JsonlCacheBackend(path)
    ctx = ExecutionContext(cache=backend)
    insts = [random_instance(rng, lo=2, hi=6) for _ in range(4)]
    results = [solve(i, policy="dp", context=ctx) for i in insts]
    before = path.read_bytes()

    # kill the process at the atomic-rename instant: the staged temp file is
    # complete but never replaces the journal -> old journal intact
    def boom(*args, **kwargs):
        raise KeyboardInterrupt("killed mid-compact")

    monkeypatch.setattr(_os, "replace", boom)
    try:
        backend.compact()
    except KeyboardInterrupt:
        pass
    monkeypatch.undo()
    assert path.read_bytes() == before  # journal untouched by the crash
    # the backend reopened its append handle: still usable after the crash
    extra = random_instance(rng, lo=2, hi=6)
    solve(extra, policy="dp", context=ctx)
    backend.close()

    reopened = JsonlCacheBackend(path)
    assert reopened.loaded == len(insts) + 1
    for inst, res in zip(insts, results):
        hit = reopened.get(inst, "dp", "python")
        assert hit is not None and hit.cost == res.cost
    # a clean compaction after the crash converges the journal
    reopened.compact()
    assert sum(1 for _ in open(path)) == len(insts) + 1
    reopened.close()


def test_jsonl_backend_serves_trace_identically(tmp_path):
    """The persistent backend behind a serving run changes nothing but the
    journal on disk; a restarted run replays to pure memo hits."""
    trace = build_trace(120)
    path = tmp_path / "serve-memo.jsonl"

    def run(cache):
        return _scrub_work(serve_trace(
            demo_library(SEED, with_cache=False), trace, "accumulate",
            window=400_000, policy="dp",
            context=ExecutionContext(cache=cache),
        ).summary())

    journal = JsonlCacheBackend(path)
    with_journal = run(journal)
    journal.close()
    plain = run(SolveCache())
    assert with_journal == plain

    rewarmed = JsonlCacheBackend(path)
    assert rewarmed.loaded > 0
    assert run(rewarmed) == plain
    assert rewarmed.stats()["misses"] == 0  # every solve was a replayed hit
    rewarmed.close()


# ---------------------------------------------------------------------------
# protocol conformance
# ---------------------------------------------------------------------------
def test_shipped_backends_satisfy_protocol(tmp_path):
    assert isinstance(SolveCache(), CacheBackend)
    backend = JsonlCacheBackend(tmp_path / "p.jsonl")
    assert isinstance(backend, CacheBackend)
    backend.close()


# ---------------------------------------------------------------------------
# single-writer lockfile: concurrent appenders are refused, stale locks
# are taken over
# ---------------------------------------------------------------------------
def test_jsonl_backend_refuses_second_concurrent_writer(tmp_path):
    path = str(tmp_path / "memo.jsonl")
    first = JsonlCacheBackend(path)
    with pytest.raises(CacheLockedError) as exc:
        JsonlCacheBackend(path)
    assert exc.value.path == path
    assert exc.value.pid == os.getpid()
    # the refused constructor must not have stolen or removed the lock
    assert os.path.exists(path + ".lock")
    first.close()


def test_jsonl_backend_close_releases_the_lock(tmp_path, rng):
    path = str(tmp_path / "memo.jsonl")
    first = JsonlCacheBackend(path)
    inst = random_instance(rng, lo=2, hi=6)
    solve(inst, policy="dp", context=ExecutionContext(cache=first))
    first.close()
    assert not os.path.exists(path + ".lock")
    second = JsonlCacheBackend(path)  # reopen after close: allowed
    assert second.loaded == 1
    second.close()


def test_jsonl_backend_takes_over_stale_lock(tmp_path, monkeypatch):
    import repro.core.cache as cache_mod

    path = str(tmp_path / "memo.jsonl")
    # a lockfile whose owner pid is dead (monkeypatched probe — a real pid
    # could be recycled by the OS mid-test)
    (tmp_path / "memo.jsonl.lock").write_text("99999\n")
    monkeypatch.setattr(cache_mod, "_pid_alive", lambda pid: False)
    backend = JsonlCacheBackend(path)
    assert (tmp_path / "memo.jsonl.lock").read_text().strip() == str(os.getpid())
    backend.close()
    # a *live* foreign owner is refused
    (tmp_path / "memo.jsonl.lock").write_text("99999\n")
    monkeypatch.setattr(cache_mod, "_pid_alive", lambda pid: True)
    with pytest.raises(CacheLockedError) as exc:
        JsonlCacheBackend(path)
    assert exc.value.pid == 99999


def test_jsonl_backend_takes_over_corrupt_lock(tmp_path):
    path = str(tmp_path / "memo.jsonl")
    (tmp_path / "memo.jsonl.lock").write_text("not-a-pid\n")
    backend = JsonlCacheBackend(path)  # corrupt lockfile counts as stale
    assert (tmp_path / "memo.jsonl.lock").read_text().strip() == str(os.getpid())
    backend.close()


def test_warm_states_ride_the_backend():
    cache = SolveCache(warm_maxsize=2)
    cache.put_warm(("warm", "t1", "dp"), object())
    cache.put_warm(("warm", "t2", "dp"), object())
    s2 = cache.get_warm(("warm", "t2", "dp"))
    assert s2 is not None
    cache.put_warm(("warm", "t3", "dp"), object())  # evicts the LRU entry
    assert cache.get_warm(("warm", "t1", "dp")) is None
    assert cache.stats()["warm_entries"] == 2
    cache.clear()
    assert cache.get_warm(("warm", "t2", "dp")) is None
    assert cache.stats()["warm_entries"] == 0
