"""The traffic generator: stratified blocks, open-loop windows, backlogs."""
import collections
import json
import pathlib

import numpy as np
import pytest

from bench.generator import plan_requests, stratified_lengths

ROOT = pathlib.Path(__file__).resolve().parents[2]
TRAFFIC = ROOT / "bench" / "traffic"
RUN_SECONDS = json.loads((ROOT / "BENCHMARK.json").read_text())["run_seconds"]


def mix(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


def test_same_lengths_per_block_for_every_seed():
    spec = {"dist": "lognormal", "median": 256, "sigma": 0.8,
            "min": 32, "max": 1024}
    a = stratified_lengths(spec, 128, 64, np.random.default_rng(1))
    b = stratified_lengths(spec, 128, 64, np.random.default_rng(2**33 + 7))
    for blk in (slice(0, 64), slice(64, 128)):
        assert collections.Counter(a[blk]) == collections.Counter(b[blk])
    assert not np.array_equal(a, b)
    assert a.min() >= 32 and a.max() <= 1024


@pytest.mark.parametrize("name", ["chat", "rag-prefill"])
def test_open_loop_window(name):
    m = mix(name)
    reqs, backlog = plan_requests(m, 2**31 + 5, RUN_SECONDS, 1000)
    assert not backlog
    due = np.array([r.due_s for r in reqs])
    pre = m["preroll_s"]
    # arrivals start a pre-roll before the window opens at 0
    assert (due[0] == -pre and np.all(np.diff(due) >= 0)
            and due[-1] < RUN_SECONDS)
    rate = m["arrivals"]["rate_per_s"]
    # whole blocks span block / rate seconds exactly
    block = m["block"]
    assert len(reqs) == round(rate * (pre + RUN_SECONDS))
    lo, hi = m["prompt_len"]["min"], m["prompt_len"]["max"]
    assert all(lo <= len(r.prompt) <= hi for r in reqs)
    again, _ = plan_requests(m, 2**31 + 5, RUN_SECONDS, 1000)
    assert all(np.array_equal(x.prompt, y.prompt) and x.due_s == y.due_s
               for x, y in zip(reqs, again))


def test_backlog():
    m = mix("longdoc-batch")
    reqs, backlog = plan_requests(m, 3, 40.0, 1000)
    assert backlog and len(reqs) == m["arrivals"]["count"]
    assert all(r.due_s == -m["preroll_s"] for r in reqs)
    assert all(len(r.prompt) + r.max_new_tokens < 4096 for r in reqs)


@pytest.mark.parametrize("name", ["chat", "rag-prefill"])
def test_same_sizes_in_the_window_for_every_seed(name):
    """Blocks line up with the pre-roll and the window, so any two seeds
    send the same prompt lengths, and the same answer lengths, into the
    window."""
    m = mix(name)

    def window_sizes(seed):
        reqs, _ = plan_requests(m, seed, RUN_SECONDS, 1000)
        inside = [r for r in reqs if r.due_s >= 0]
        return (collections.Counter(len(r.prompt) for r in inside),
                collections.Counter(r.max_new_tokens for r in inside))

    assert window_sizes(2**31 + 5) == window_sizes(7)


@pytest.mark.parametrize("preroll, seconds", [(20.0, 45.0), (15.0, 40.0)])
def test_misaligned_blocks_are_refused(preroll, seconds):
    """A block span (here 14 / 0.7 = 20 s) that does not divide the
    pre-roll or the window is an error, not a silent change of work."""
    m = {**mix("chat"), "preroll_s": preroll}
    with pytest.raises(ValueError, match="does not divide"):
        plan_requests(m, 1, seconds, 1000)
