"""Continuous-batching scheduler: paged-cache invariants, the request-level
API contract, and the deterministic replay guarantee.

Four layers:
  * accounting: ``PagedKVCache`` never aliases rows, never over-commits the
    page budget, honors home-pod affinity, and rejects impossible requests
    at submit time (property tests over random op sequences);
  * API redesign: ``ServeSpec`` is the one way to shape the engine — legacy
    kwargs still work one release behind a ``DeprecationWarning`` and
    produce the same artifacts; mixing spec and kwargs is a ``TypeError``;
    ``Engine.generate`` is deprecated but intact;
  * determinism: the same trace on a ``StepClock`` replays to identical
    tokens, timestamps, slots and migration decisions;
  * parity + locality: ``submit``/``drain`` emits tokens bitwise equal to
    the lockstep ``generate`` rows, every stamped comm label reconciles
    against its compiled HLO, and pod-local prefills move ZERO non-local
    bytes.
"""
import dataclasses
import warnings

import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro.launch.mesh import make_mesh
from repro.serve.paged import PagedKVCache
from repro.serve.spec import Request, ServeSpec


# ---------------------------------------------------------------------------
# PagedKVCache accounting (pure python, no devices)
# ---------------------------------------------------------------------------
def test_paged_reserve_release_roundtrip():
    paged = PagedKVCache(batch=4, cache_len=32, page_len=8, n_pods=2)
    rows = [paged.reserve(rid, 10, 6) for rid in range(4)]
    assert sorted(rows) == [0, 1, 2, 3]
    assert paged.reserve(99, 4, 4) is None          # full -> None, not raise
    paged.check_invariants()
    assert paged.release(2) == rows[2]
    assert paged.reserve(99, 4, 4) == rows[2]       # freed row is reusable
    paged.check_invariants()


def test_paged_home_pod_affinity():
    paged = PagedKVCache(batch=8, cache_len=32, page_len=8, n_pods=2)
    # pod 1 owns rows 4..7 (contiguous blocks, pod-major)
    assert [paged.pod_of_row(r) for r in range(8)] == [0] * 4 + [1] * 4
    r = paged.reserve(0, 8, 8, home_pod=1)
    assert paged.pod_of_row(r) == 1
    for rid in range(1, 4):                          # fill the rest of pod 1
        assert paged.pod_of_row(paged.reserve(rid, 8, 8, home_pod=1)) == 1
    # pod 1 full -> falls back to a pod-0 row (the migration case)
    assert paged.pod_of_row(paged.reserve(4, 8, 8, home_pod=1)) == 0


def test_paged_rejects_impossible_and_double_reserve():
    paged = PagedKVCache(batch=2, cache_len=16, page_len=8)
    assert not paged.fits(12, 8)                     # 20 tokens > 16 slots
    with pytest.raises(ValueError):
        paged.reserve(0, 12, 8)
    paged.reserve(0, 4, 4)
    with pytest.raises(ValueError):
        paged.reserve(0, 2, 2)                       # rid already holds a row
    with pytest.raises(ValueError):
        PagedKVCache(batch=2, cache_len=16, page_len=5)   # 5 !| 16


@pytest.mark.hypothesis
@given(st.lists(st.tuples(st.integers(0, 1), st.integers(0, 15)),
                max_size=80),
       st.sampled_from([1, 2, 4]))
def test_paged_random_ops_hold_invariants(ops, n_pods):
    paged = PagedKVCache(batch=8, cache_len=32, page_len=4, n_pods=n_pods)
    live, rid = [], 0
    for kind, x in ops:
        if kind == 0:
            row = paged.reserve(rid, 1 + x, 4, home_pod=x % n_pods)
            if row is not None:
                live.append(rid)
            rid += 1
        elif live:
            paged.release(live.pop(x % len(live)))
        paged.check_invariants()
    assert len(live) == len(set(live)) <= paged.batch


@pytest.mark.hypothesis
@given(st.integers(1, 64), st.integers(1, 8), st.integers(1, 64),
       st.integers(0, 64))
def test_paged_pages_needed_is_conservative(page_len, ppr, prompt, max_new):
    paged = PagedKVCache(batch=1, cache_len=page_len * ppr, page_len=page_len)
    pages = paged.pages_needed(prompt, max_new)
    assert pages * page_len >= prompt + max_new      # never under-reserves
    assert (pages - 1) * page_len < prompt + max_new  # by less than a page
    assert paged.fits(prompt, max_new) == (pages <= ppr)


# ---------------------------------------------------------------------------
# Request / ServeSpec validation (no devices)
# ---------------------------------------------------------------------------
def test_request_validates_prompt_and_budget():
    with pytest.raises(ValueError):
        Request(tokens=np.zeros((2, 3), np.int32), max_new=4)
    with pytest.raises(ValueError):
        Request(tokens=np.zeros((0,), np.int32), max_new=4)
    with pytest.raises(ValueError):
        Request(tokens=np.zeros((4,), np.int32), max_new=0)
    r = Request(tokens=[1, 2, 3], max_new=2)
    assert r.tokens.dtype == np.int32 and r.tokens.shape == (3,)


def test_spec_resolve_single_device():
    import jax
    from repro import configs
    cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=1)
    mesh = make_mesh((1,), ("data",))
    res = ServeSpec(batch=2, cache_len=16).resolve(cfg, mesh)
    assert res.n_pods == 1 and res.p_local == 1
    assert res.combine.algorithm == "none"           # nothing to combine


# ---------------------------------------------------------------------------
# API redesign: the deprecation bridge (single device, tiny model)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny():
    import jax
    import jax.numpy as jnp
    from repro import configs
    from repro.models import transformer
    cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=1,
                              dtype=jnp.float32)
    mesh = make_mesh((1,), ("data",))
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, mesh, params


def test_legacy_kwargs_warn_and_match_spec(tiny):
    import jax
    from repro.serve.engine import make_serve_fns
    cfg, mesh, _ = tiny
    with jax.set_mesh(mesh):
        with pytest.warns(DeprecationWarning, match="ServeSpec"):
            legacy = make_serve_fns(cfg, mesh, batch=1, cache_len=16)
        spec = make_serve_fns(cfg, mesh, ServeSpec(batch=1, cache_len=16))
    assert legacy.combine.algorithm == spec.combine.algorithm
    assert legacy.fused_stats == spec.fused_stats


def test_spec_plus_legacy_kwargs_is_typeerror(tiny):
    import jax
    from repro.serve.engine import Engine, make_serve_fns
    cfg, mesh, params = tiny
    with jax.set_mesh(mesh):
        with pytest.raises(TypeError, match="both"):
            make_serve_fns(cfg, mesh, ServeSpec(batch=1, cache_len=16),
                           batch=1)
        with pytest.raises(TypeError, match="both"):
            Engine(cfg, mesh, params, ServeSpec(batch=1, cache_len=16),
                   cache_len=16)
        with pytest.raises(TypeError):
            make_serve_fns(cfg, mesh)                # neither spec nor kwargs


def test_generate_deprecated_but_intact(tiny):
    import jax
    from repro.serve.engine import Engine
    cfg, mesh, params = tiny
    with jax.set_mesh(mesh):
        eng = Engine(cfg, mesh, params, ServeSpec(batch=1, cache_len=16))
        prompts = np.arange(4, dtype=np.int32)[None, :]
        with pytest.warns(DeprecationWarning, match="submit"):
            toks = eng.generate(prompts, 3)
        # the request-level API decodes the same greedy continuation
        eng.submit(Request(tokens=prompts[0], max_new=3))
        res = eng.drain()
    (r,) = res.values()
    assert np.array_equal(r.tokens, toks[0]), (r.tokens, toks)
    assert r.finish_reason == "length" and r.n_tokens == 3


# ---------------------------------------------------------------------------
# determinism + parity + locality on the real 8-device batch path
# ---------------------------------------------------------------------------
TRACE_CODE = r"""
from repro.launch.mesh import make_mesh
import dataclasses, warnings
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.models import transformer
from repro.serve import Engine, Request, ServeSpec, StepClock

mesh = make_mesh((2, 4), ("pod", "data"))
jax.set_mesh(mesh)
cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=2,
                          dtype=jnp.float32)
params = transformer.init_params(jax.random.PRNGKey(0), cfg)
B, S, NEW = 8, 6, 4
spec = ServeSpec(batch=B, cache_len=32, page_len=8)
rng = np.random.default_rng(0)
prompts = rng.integers(0, cfg.vocab_size, (B, S), np.int32)
arrivals = np.sort(rng.uniform(0.0, 6.0, B))

def run_once(home_pods):
    eng = Engine(cfg, mesh, params, spec, clock=StepClock())
    rids = [eng.submit(Request(tokens=prompts[i], max_new=NEW,
                               home_pod=home_pods[i],
                               arrival_s=float(arrivals[i])))
            for i in range(B)]
    return eng, rids, eng.drain(), eng.scheduler.stats()

# 1. determinism: the same trace replays to the same everything
home = [i % 2 for i in range(B)]
eng1, rids1, res1, st1 = run_once(home)
eng2, rids2, res2, st2 = run_once(home)
assert rids1 == rids2
for rid in rids1:
    a, b = res1[rid], res2[rid]
    assert np.array_equal(a.tokens, b.tokens), (rid, a.tokens, b.tokens)
    assert a.token_times_s == b.token_times_s, rid
    assert (a.arrival_s, a.started_s, a.finished_s) == \
           (b.arrival_s, b.started_s, b.finished_s), rid
    assert (a.slot, a.migrated) == (b.slot, b.migrated), rid
assert st1["steps"] == st2["steps"]
assert st1["migrations"] == st2["migrations"]
print("DETERMINISM_OK")

# 2. every stamped comm label reconciles against its compiled HLO, and
#    pod-local prefills move ZERO non-local bytes
comm = st1["comm"]
assert comm, "comm telemetry missing"
for label, rec in comm.items():
    assert rec["match"], (label, rec)
    if label.startswith("serve/prefill:pod") and "podall" not in label:
        assert rec["actual_nonlocal_bytes"] == 0.0, (label, rec)
        assert rec["actual_nonlocal_msgs"] == 0.0, (label, rec)
print("LEDGER_OK")

# 3. parity: all-arrive-at-0, no home pod -> rows fill FCFS and every
#    request's tokens equal its lockstep generate row
eng3 = Engine(cfg, mesh, params, spec, clock=StepClock())
rids3 = [eng3.submit(Request(tokens=prompts[i], max_new=NEW, arrival_s=0.0))
         for i in range(B)]
res3 = eng3.drain()
with warnings.catch_warnings():
    warnings.simplefilter("ignore", DeprecationWarning)
    ref = eng3.generate(prompts, NEW)
for rid in rids3:
    r = res3[rid]
    assert np.array_equal(r.tokens, np.asarray(ref)[r.slot]), \
        (rid, r.slot, r.tokens, ref)
print("PARITY_OK")

# 4. layout guards: sequence-sharded layouts are one-request-at-a-time
cfg1 = dataclasses.replace(cfg, n_layers=1)
params1 = transformer.init_params(jax.random.PRNGKey(0), cfg1)
eng4 = Engine(cfg1, mesh, params1,
              ServeSpec(batch=2, cache_len=32, combine="locality"))
try:
    eng4.scheduler
except ValueError as e:
    assert "batch must be 1" in str(e), e
else:
    raise AssertionError("sequential scheduler accepted batch=2")
print("GUARD_OK")
"""


@pytest.mark.slow
def test_scheduler_trace_determinism_parity_locality(subproc):
    out = subproc(TRACE_CODE, devices=8, timeout=1800)
    for marker in ("DETERMINISM_OK", "LEDGER_OK", "PARITY_OK", "GUARD_OK"):
        assert marker in out, out
