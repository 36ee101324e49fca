"""ExecutionContext surface: defaulting, immutability, validation — and the
deprecation shims (old ``backend=``/``cache=`` keyword paths must emit
``DeprecationWarning`` yet stay bit-identical to the context API).

The shim tests are marked ``shims``: CI runs the rest of the suite under
``-W error::DeprecationWarning`` (proving every in-repo caller is migrated)
and exercises the shims in a separate allowed-warning leg.
"""

import dataclasses
import warnings

import numpy as np
import pytest

from conftest import random_instance
from repro.core import (
    DEFAULT_CONTEXT,
    ExecutionContext,
    SolveCache,
    get_solver,
    resolve_context,
    solve,
    solve_batch,
)

DEV = ExecutionContext(backend="pallas-interpret")


# ---------------------------------------------------------------------------
# defaulting / immutability / validation
# ---------------------------------------------------------------------------
def test_context_defaults():
    ctx = ExecutionContext()
    assert ctx.backend == "python"
    assert ctx.cache is None
    assert ctx.bucketed is True
    assert ctx.budget is None and ctx.fleet is None and ctx.obs is None
    assert [f.name for f in dataclasses.fields(ctx)] == [
        "backend", "cache", "bucketed", "budget", "fleet", "obs"]
    assert ctx == DEFAULT_CONTEXT


def test_context_is_immutable():
    ctx = ExecutionContext()
    with pytest.raises(dataclasses.FrozenInstanceError):
        ctx.backend = "pallas"
    with pytest.raises(dataclasses.FrozenInstanceError):
        ctx.bucketed = False


def test_context_replace_derives_without_mutating():
    base = ExecutionContext(cache=SolveCache())
    derived = base.replace(backend="pallas-interpret", bucketed=False)
    assert derived.backend == "pallas-interpret"
    assert derived.bucketed is False
    assert derived.cache is base.cache  # shared memo, not copied
    assert base.backend == "python" and base.bucketed is True


def test_context_validates_fields():
    with pytest.raises(KeyError, match="unknown backend"):
        ExecutionContext(backend="cuda")
    with pytest.raises(TypeError, match="budget"):
        ExecutionContext(budget=3)
    with pytest.raises(TypeError, match="fleet"):
        ExecutionContext(fleet="two shards")
    with pytest.raises(TypeError, match="obs"):
        ExecutionContext(obs=object())


def test_resolve_context_precedence():
    ctx = ExecutionContext(backend="pallas-interpret")
    assert resolve_context(ctx) is ctx
    assert resolve_context(None) == DEFAULT_CONTEXT
    base = ExecutionContext(bucketed=False)
    assert resolve_context(None, default=base) is base
    with pytest.raises(TypeError, match="not both"):
        resolve_context(ctx, backend="python")


def test_new_api_emits_no_deprecation_warning(rng):
    inst = random_instance(rng, hi=6)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        res = solve(inst, policy="dp", context=DEV)
        [batch_res] = solve_batch([inst], policy="dp", context=DEV)
        assert res.cost == batch_res.cost


# ---------------------------------------------------------------------------
# deprecation shims: warn, then forward bit-identically
# ---------------------------------------------------------------------------
@pytest.mark.shims
def test_resolve_context_legacy_keywords_warn_and_fold():
    cache = SolveCache()
    with pytest.warns(DeprecationWarning, match="backend/cache"):
        ctx = resolve_context(None, backend="pallas-interpret", cache=cache)
    assert ctx.backend == "pallas-interpret" and ctx.cache is cache


@pytest.mark.shims
def test_solve_shim_bit_identical(rng):
    inst = random_instance(rng, hi=8)
    new = solve(inst, policy="dp", context=DEV)
    with pytest.warns(DeprecationWarning):
        old = solve(inst, policy="dp", backend="pallas-interpret")
    assert (old.cost, old.detours, old.backend) == (new.cost, new.detours, new.backend)


@pytest.mark.shims
def test_solve_batch_shim_bit_identical_with_cache(rng):
    insts = [random_instance(rng, hi=7) for _ in range(4)]
    cache_old, cache_new = SolveCache(), SolveCache()
    new = solve_batch(insts, policy="dp", context=ExecutionContext(cache=cache_new))
    with pytest.warns(DeprecationWarning):
        old = solve_batch(insts, policy="dp", cache=cache_old)
    assert [(r.cost, r.detours) for r in old] == [(r.cost, r.detours) for r in new]
    assert cache_old.stats() == cache_new.stats()


@pytest.mark.shims
def test_solver_backend_string_shim(rng):
    inst = random_instance(rng, hi=6)
    solver = get_solver("dp")
    new = solver.solve(inst, DEV)
    with pytest.warns(DeprecationWarning, match="backend string"):
        old = solver.solve(inst, "pallas-interpret")
    assert (old.cost, old.detours) == (new.cost, new.detours)
    with pytest.warns(DeprecationWarning, match="backend string"):
        [old_b] = solver.solve_batch([inst], "pallas-interpret")
    assert (old_b.cost, old_b.detours) == (new.cost, new.detours)


@pytest.mark.shims
def test_schedule_reads_shim_bit_identical():
    from repro.storage.tape import Tape, schedule_reads

    rng = np.random.default_rng(3)
    t = Tape("T0", capacity=400_000, u_turn=700)
    for i in range(10):
        t.append(f"f{i}", int(rng.integers(1_000, 30_000)))
    reqs = {f"f{i}": 1 + i % 3 for i in range(0, 10, 2)}
    new = schedule_reads(t, reqs, policy="dp", context=DEV)
    with pytest.warns(DeprecationWarning):
        old = schedule_reads(t, reqs, policy="dp", backend="pallas-interpret")
    assert old == new


@pytest.mark.shims
def test_tape_library_cache_kwarg_shim():
    from repro.storage.tape import TapeLibrary

    cache = SolveCache()
    with pytest.warns(DeprecationWarning):
        lib = TapeLibrary(capacity_per_tape=100_000, u_turn=500, cache=cache)
    assert lib.cache is cache and lib.context.cache is cache
    for i in range(4):
        lib.store(f"f{i}", 20_000)
    reqs = {f"f{i}": 1 for i in range(4)}
    new = lib.schedule(reqs, policy="dp")  # library context: no warning
    with pytest.warns(DeprecationWarning):
        old = lib.schedule(reqs, policy="dp", backend="python")
    assert old == new
    assert cache.hits > 0  # second plan re-hit the library memo


@pytest.mark.shims
def test_plan_restore_shim_bit_identical():
    from repro.distributed.checkpoint import plan_restore
    from repro.storage.tape import TapeLibrary

    lib = TapeLibrary(capacity_per_tape=200_000, u_turn=900)
    shards = [lib.store(f"s{i}", 30_000).name for i in range(8)]
    new = plan_restore(lib, shards, 2, policy="dp", context=DEV)
    with pytest.warns(DeprecationWarning):
        old = plan_restore(lib, shards, 2, policy="dp",
                           backend="pallas-interpret")
    assert old == new


@pytest.mark.shims
def test_serve_trace_shim_bit_identical():
    from repro.serving.queue import serve_trace
    from repro.serving.sim import demo_library, poisson_trace

    trace = poisson_trace(demo_library(1), 60, 200_000, seed=1)
    cache_old, cache_new = SolveCache(), SolveCache()
    new = serve_trace(
        demo_library(1), trace, "accumulate", window=300_000, policy="dp",
        context=ExecutionContext(cache=cache_new),
    )
    with pytest.warns(DeprecationWarning):
        old = serve_trace(
            demo_library(1), trace, "accumulate", window=300_000, policy="dp",
            cache=cache_old,
        )
    assert old.summary() == new.summary()
    assert [r.completed for r in old.served] == [r.completed for r in new.served]
