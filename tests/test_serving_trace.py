"""The serving path's own observability: named executors, host spans in
the profiler trace, and the admission timestamp.

Spans are ``jax.profiler.TraceAnnotation``s on the host path; a profiler
session writes them into the same ``.xplane.pb`` as the device ops, which
is how an idle gap on the device is put down to what the host was doing.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from conftest import tiny_config
from repro.core.resources import Alloc
from repro.models import build_model
from repro.serving import ServingEngine
from repro.serving import engine as engine_mod

FULL = Alloc(sm=1.0, quota_request=0.9, quota_limit=0.9)


def _cow_arrivals():
    """Two identical 12-token prompts (one full block of 8 and a shared
    4-token tail) and a third of their own: the pair shares the tail
    block, so the first decode round resolves a copy-on-write."""
    rng = np.random.default_rng(3)
    twin = rng.integers(0, 64, 12, dtype=np.int32)
    return [(twin, 4), (twin.copy(), 5),
            (rng.integers(0, 64, 6, dtype=np.int32), 3)]


def _serve_paged(model, params, arrivals, *, max_batch=2):
    engine = ServingEngine(window=0.1)
    engine.deploy("f", model, params, FULL, max_batch=max_batch, max_len=32,
                  batching="paged", block_size=8)
    reqs = [engine.submit("f", p, max_new_tokens=n) for p, n in arrivals]
    assert engine.pump(budget_s=120.0) == len(reqs)
    return reqs, engine


# Reading event stats trips a warning about jaxlib's own nanobind type.
@pytest.mark.filterwarnings("ignore:builtin type event_stats")
def test_engine_pass_spans_nest_in_serve_pass(tiny_model, tiny_params,
                                              tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        reqs, engine = _serve_paged(tiny_model, tiny_params,
                                    _cow_arrivals())
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    spans = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
              dict(ev.stats))
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith("serve.")]
    names = {n for _, _, n, _ in spans}
    assert {"serve.schedule", "serve.pass", "serve.admit", "serve.cow",
            "serve.dispatch", "serve.sync", "serve.land"} <= names
    passes = [(s, e) for s, e, n, _ in spans if n == "serve.pass"]
    assert len(passes) == next(iter(engine.telemetry().values()))["steps"]
    for s, e, name, _ in spans:
        if name in ("serve.admit", "serve.cow", "serve.dispatch",
                    "serve.sync", "serve.land"):
            assert any(ps <= s and e <= pe for ps, pe in passes), name
    admits = [st for _, _, n, st in spans if n == "serve.admit"]
    assert sorted(a["req"] for a in admits) == sorted(r.req_id
                                                      for r in reqs)
    assert {a["prompt"] for a in admits} == {12, 6}
    assert {a["bucket"] for a in admits} == {16, 8}
    # The twin admitted second maps the first's full block and tail.
    assert max(a["shared_blocks"] for a in admits) == 2


def test_paged_executors_lower_to_named_modules(tmp_path):
    """Every executor the paged path runs compiles to ``jit_<key>``, so
    a device trace names the program each op belongs to."""
    model = build_model(tiny_config())  # fresh: its executors lower anew
    params = model.init(jax.random.key(0))
    jax.config.update("jax_dump_ir_to", str(tmp_path))
    try:
        _, engine = _serve_paged(model, params, _cow_arrivals())
    finally:
        jax.config.update("jax_dump_ir_to", "")
    assert next(iter(engine.telemetry().values()))["cow"] > 0
    modules = {f.split("_jit_", 1)[1].rsplit("_compile", 1)[0]
               for f in os.listdir(tmp_path) if "_jit_" in f}
    assert {"prefill_len", "greedy", "append", "copy_block",
            "decode_paged_tok"} <= modules
    assert not any("lambda" in m for m in modules)
    assert {k[0] for k in model._jit_executors} >= {"prefill_len",
                                                    "decode_paged_tok"}
    lowered = engine_mod._SET_TOK.lower(jnp.zeros((2,), jnp.int32),
                                        jnp.int32(0), jnp.int32(1))
    assert lowered.as_text().startswith("module @jit_set_tok ")


def test_admitted_at_orders_single_slot_admissions(tiny_model, tiny_params):
    rng = np.random.default_rng(7)
    arrivals = [(rng.integers(0, 64, 5, dtype=np.int32), 3),
                (rng.integers(0, 64, 9, dtype=np.int32), 2),
                (rng.integers(0, 64, 4, dtype=np.int32), 1)]
    reqs, _ = _serve_paged(tiny_model, tiny_params, arrivals, max_batch=1)
    for r in reqs:
        assert r.admitted_at is not None
        assert r.submitted_at <= r.admitted_at <= r.finished_at
    # One slot: a request is admitted only once the one before it left.
    assert reqs[1].admitted_at >= reqs[0].finished_at
    assert reqs[2].admitted_at >= reqs[1].finished_at


@pytest.mark.parametrize("batching", ["paged", "continuous"])
def test_admitted_at_keeps_first_admission(tiny_model, tiny_params,
                                           batching):
    """A request admitted again (re-executed after its node failed)
    keeps the time of its first admission."""
    prompt = np.arange(6, dtype=np.int32)
    engine = ServingEngine(window=0.1)
    engine.deploy("f", tiny_model, tiny_params, FULL, max_batch=2,
                  max_len=32, batching=batching, block_size=8)
    req = engine.submit("f", prompt, max_new_tokens=6)
    (inst,) = engine.instances.values()
    inst.run_step()
    first = req.admitted_at
    assert first is not None and not req.done
    ((_, stray),) = engine.fail()
    again = ServingEngine(window=0.1)
    again.deploy("f", tiny_model, tiny_params, FULL, max_batch=2,
                 max_len=32, batching=batching, block_size=8)
    (inst2,) = again.instances.values()
    again.enqueue(inst2, stray)
    assert again.pump(budget_s=120.0) == 1
    assert stray.admitted_at == first and len(stray.tokens_out) == 6
