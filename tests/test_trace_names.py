"""Names the device trace and the host spans attribute time by.

* every ``pallas_call`` passes a stable ``name=``;
* the kernel guard traces each attempt under the kernel's scope, so a
  kernel demoted to its ref path keeps its name in the compiled ops;
* the offloaded paged decode step keeps the model's layer scopes and
  names each near segment ``<first eqn's scope>/near/<kernel>``, the
  same string ``explain_decode()`` gives the segment's decision row;
* ``Engine.admit`` and ``Engine.step`` open the engine's spans, the
  step's four children in order.
"""
import ast
import contextlib
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.models import build_model
from repro.serve import Engine, Request
from repro.serve import engine as engine_mod

from conftest import tiny

KERNELS = pathlib.Path(__file__).resolve().parents[1] / "src/repro/kernels"
# op_name components that are JAX's calls and loops, not program scopes
_JAX_PARTS = {"while", "body", "cond", "closed_call"}


def _scopes(text: str) -> set[str]:
    """Each ``op_name`` of a compiled module's op metadata with JAX's own
    call and loop components dropped: ``decode/attn/qkv/dot_general``."""
    out = set()
    for loc in re.findall(r'op_name="([^"]*)"', text):
        parts = [p for p in loc.split("/")
                 if p and "(" not in p and p not in _JAX_PARTS]
        out.add("/".join(parts))
    return out


def _pallas_calls(path: pathlib.Path):
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Call) and \
                getattr(node.func, "attr", None) == "pallas_call":
            yield node


def test_every_pallas_call_is_named():
    names = []
    for path in sorted(KERNELS.glob("*.py")):
        for call in _pallas_calls(path):
            kw = {k.arg: k.value for k in call.keywords}
            assert "name" in kw, f"{path.name}:{call.lineno} has no name="
            assert isinstance(kw["name"], ast.Constant), path.name
            names.append(kw["name"].value)
    assert "paged_decode_attention" in names and "fused_matmul" in names
    assert len(names) == len(set(names)), names


def test_guard_scopes_the_ref_fallback():
    """A kernel run on its ref path still shows under its own name."""
    x = jnp.ones((8, 128), jnp.float32)
    s = jnp.ones((128,), jnp.float32)
    text = jax.jit(lambda a, b: ops.rmsnorm(a, b, impl="ref")).lower(
        x, s).compile().as_text()
    assert any(n.startswith("rmsnorm/") for n in _scopes(text))


@pytest.fixture(scope="module")
def offloaded():
    cfg = tiny("qwen3-1.7b")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    eng = Engine(cfg, params, slots=2, max_len=32, page_size=8,
                 offload=True)
    args = (eng.params, eng.cache, eng._state, jnp.asarray(eng.pool.tables),
            jax.random.PRNGKey(1), np.zeros((2,), bool))
    text = eng._step_fn.lower(*args).compile().as_text()
    return eng, _scopes(text)


def test_offloaded_decode_keeps_layer_scopes(offloaded):
    """Guard against the rewriter dropping name stacks: the compiled
    offloaded step's ops carry the model's scopes and the near
    segments'."""
    _, scopes = offloaded
    for want in ("decode/attn/paged_attention/", "decode/attn/kv_write/",
                 "decode/attn/qkv/", "decode/mlp/", "decode/lm_head/",
                 "decode/embed/"):
        assert any(s.startswith(want) for s in scopes), want
    assert any("/near/" in s for s in scopes)


def test_explain_rows_name_their_trace_scope(offloaded):
    """Every fused decision row's scope is a scope of the compiled ops
    (ending in near/<kernel>); declined rows carry no near scope."""
    eng, scopes = offloaded
    rows = eng.explain_decode().all_decisions()
    fused = [d for d in rows if d.fused]
    assert fused and all(d.scope for d in rows)
    for d in fused:
        assert "/near/fused_" in d.scope, d.scope
        assert any(s.startswith(d.scope + "/") for s in scopes), d.scope
    assert not any("near" in d.scope.split("/") for d in rows
                   if not d.fused)
    assert "scope: decode/" in str(eng.explain_decode())


def test_replayed_plan_names_the_scopes_of_this_trace():
    """A plan replayed from the plan cache (whose fingerprint ignores
    name stacks) reports the scopes of the jaxpr it is replayed onto,
    not the ones it was recorded under."""
    from repro.core.offload import (_plan_from_payload, _plan_payload,
                                    plan_offload)

    def under(scope):
        def fn(x, w):
            with jax.named_scope(scope):
                return jax.nn.gelu(x @ w) * 2.0
        return jax.make_jaxpr(fn)(jnp.ones((128, 64)), jnp.ones((64, 64)))

    old, new = under("mlp"), under("ffn")
    plan = plan_offload(old, bulk_threshold=64)
    assert [d.scope for d in plan.report().decisions] == [
        "mlp/near/fused_matmul"]
    payload = _plan_payload(plan, old)
    payload["decisions"][0]["scope"] = "stale"
    replayed = _plan_from_payload(payload, new, plan.policy)
    assert [d.scope for d in replayed.report().decisions] == [
        "ffn/near/fused_matmul"]


def test_engine_spans_nest(monkeypatch):
    """Two steps after an admit: one engine.admit with its arguments,
    then per step engine.step holding prepare, dispatch, sync, emit."""
    opened = []
    depth = [0]

    def recorder(name, **kw):
        @contextlib.contextmanager
        def span():
            opened.append((depth[0], name, kw))
            depth[0] += 1
            try:
                yield
            finally:
                depth[0] -= 1
        return span()

    monkeypatch.setattr(engine_mod, "TraceAnnotation", recorder)
    monkeypatch.setattr(engine_mod, "StepTraceAnnotation", recorder)
    cfg = tiny("qwen3-1.7b")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    eng = Engine(cfg, params, slots=2, max_len=32, page_size=8)
    assert eng.admit(Request(np.arange(5, dtype=np.int32),
                             max_new_tokens=4, rid=7))
    eng.step()
    eng.step()
    assert opened[0] == (0, "engine.admit",
                         {"rid": 7, "prompt_tokens": 5, "bucket_tokens": 8,
                          "slot": 0})
    step = [(d, n) for d, n, _ in opened[1:]]
    children = ["engine.prepare", "engine.dispatch", "engine.sync",
                "engine.emit"]
    assert step == 2 * ([(0, "engine.step")] + [(1, c) for c in children])
    assert [kw for _, n, kw in opened if n == "engine.step"] == [
        {"step_num": 1, "active": 1}, {"step_num": 2, "active": 1}]
