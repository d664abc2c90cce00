"""The serve loop's span tree: every host moment of ``Scheduler.step()`` lies
in a span, on the same clock as the requests' stamps and the profiler's.

``serve/step`` is the root of each step; under it ``serve/idle`` (napping
toward an arrival), each admission's ``serve/prefill`` and ``serve/insert``,
and ``serve/decode_step``, whose two children ``serve/decode_launch`` and
``serve/decode_fetch`` cover it end to end. The sequential (sequence-
sharded) path is checked where a test builds one (``test_elastic``).
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.serve.spec import Request, ServeSpec


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


def _engine(tiny, tracer):
    from repro import telemetry
    from repro.serve import Engine
    cfg, mesh, params = tiny
    return Engine(cfg, mesh, params, ServeSpec(batch=2, cache_len=32,
                                               page_len=8),
                  comm_telemetry=False, tracer=tracer,
                  registry=telemetry.MetricsRegistry())


def _prompts(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, 4 + i, dtype=np.int32)
            for i in range(n)]


def test_batched_step_span_tree(tiny, serve_spans):
    """Three requests on two rows (one waits for a row), then one that
    arrives later, so a step naps toward it; the wall clock stamps the
    tokens on the tracer's clock."""
    import jax
    from repro import telemetry
    cfg, mesh, _ = tiny
    tracer = telemetry.Tracer(jax_annotations=False)
    with jax.set_mesh(mesh):
        eng = _engine(tiny, tracer)
        ps = _prompts(cfg, 4)
        for p in ps[:3]:
            eng.submit(Request(tokens=p, max_new=3))
        eng.drain()
        eng.submit(Request(tokens=ps[3], max_new=2,
                           arrival_s=time.perf_counter() + 0.08))
        results = eng.drain()
    assert len(results) == 4
    by_name = serve_spans(tracer.events(),
                          {rid: r.token_times_s[0]
                           for rid, r in results.items()},
                          batched=True, clock_t0=tracer._t0)
    assert by_name["serve/idle"]
    assert all(sp["parent"]["name"] == "serve/step"
               for sp in by_name["serve/idle"])
    assert {sp["args"]["row"] for sp in by_name["serve/insert"]} == {0, 1}
    # served tokens are counted from the results; ``serve/tokens`` is the
    # legacy ``Engine.generate``'s counter alone
    assert "serve/tokens" not in eng.registry.snapshot()["counters"]


def test_spans_nest_on_the_profilers_host_plane(tiny, tmp_path):
    """Mirrored into the profiler's trace, the step's spans nest there as
    they do in the tracer: what ``trace_reduce`` charges idle gaps to."""
    import jax
    from bench import trace_reduce
    from repro import telemetry
    cfg, mesh, _ = tiny
    with jax.set_mesh(mesh):
        eng = _engine(tiny, telemetry.Tracer())
        ps = _prompts(cfg, 2, seed=1)
        eng.submit(Request(tokens=ps[0], max_new=2))
        eng.drain()                       # compile outside the trace
        eng.submit(Request(tokens=ps[0], max_new=4))
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                for _ in range(3):
                    eng.step()
        finally:
            jax.profiler.stop_trace()
    host = [e for e in trace_reduce.load(str(tmp_path))
            if e["plane"].startswith("/host:")]
    (win,) = [e for e in host if e["name"] == trace_reduce.WINDOW]

    def inside(e, outer):
        return (outer["start_ns"] <= e["start_ns"] and e["start_ns"]
                + e["dur_ns"] <= outer["start_ns"] + outer["dur_ns"]
                and e["line"] == outer["line"])

    named = lambda n: [e for e in host if e["name"] == n]
    steps = named("serve/step")
    decodes = named("serve/decode_step")
    assert len(steps) == 3 and len(decodes) == 3
    assert all(inside(s, win) for s in steps)
    for d in decodes:
        assert sum(inside(d, s) for s in steps) == 1
        for child in ("serve/decode_launch", "serve/decode_fetch"):
            assert sum(inside(c, d) for c in named(child)) == 1
