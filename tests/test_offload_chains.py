"""The planner's fusion contract over the fifteen chains of
``repro.analysis.chains``.

* ``test_chain_contract``: each chain plans to exactly its committed
  segment count, with traffic reduction at or above its floor, at least
  its committed number of anchored backward segments, and no verifier
  finding of severity ``error``.
* ``test_cost_never_exceeds_greedy``: ``cost`` mode picks, per
  candidate, the side the model prices cheaper, so its decision-modelled
  bytes never exceed greedy's.

Planning only: nothing is executed.
"""
import pytest

from repro.analysis import verify_plan
from repro.analysis.chains import MUST_FUSE, POLICY, chains
from repro.core import offload_report


@pytest.fixture(scope="module")
def chain_args():
    return {name: (fn, args, donate) for name, fn, args, donate in chains()}


def _plan(chain_args, name, policy):
    fn, args, donate = chain_args[name]
    return offload_report(fn, *args, policy=policy, donate_argnums=donate)


def _decision_bytes(plan) -> int:
    """The plan's traffic under the decision model: each candidate at
    its chosen side's price (fused: near bytes, declined: modelled far
    bytes).  This is what ``cost`` minimises per candidate; the plan's
    naive accounting prices unfused eqns per round trip and can report
    a correct decline as a traffic increase."""
    return sum(d.near_bytes if d.fused else d.far_bytes
               for d in plan.decisions)


def test_every_chain_has_a_contract(chain_args):
    assert set(chain_args) == set(MUST_FUSE)


@pytest.mark.parametrize("name", list(MUST_FUSE))
def test_chain_contract(chain_args, name):
    segments, traffic_floor, bwd_floor = MUST_FUSE[name]
    plan = _plan(chain_args, name, POLICY)
    assert len(plan.segments) == segments
    assert plan.traffic_reduction >= traffic_floor
    anchored_bwd = sum(1 for s in plan.segments if s.matmul is not None
                       and s.matmul.form in ("dlhs", "drhs"))
    assert anchored_bwd >= bwd_floor
    errors = [f for f in verify_plan(plan) if f.severity == "error"]
    assert not errors, errors


@pytest.mark.parametrize("name", list(MUST_FUSE))
def test_cost_never_exceeds_greedy(chain_args, name):
    greedy = _plan(chain_args, name, POLICY)
    cost = _plan(chain_args, name, POLICY.replace(mode="cost"))
    assert _decision_bytes(cost) <= _decision_bytes(greedy)
