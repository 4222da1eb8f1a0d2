"""The timed path broken underneath a rehearsed run: `correct` must come
out false for every fault a cell can have."""
import numpy as np
import pytest


def _state_unchanged(monkeypatch):
    from repro.core import vcprog
    monkeypatch.setattr(vcprog, "run_loop",
                        lambda step, init_state, max_iter: init_state)


def _answer_altered(monkeypatch):
    from repro.core import operators
    orig = operators.sssp

    def altered(*a, **kw):
        out, info = orig(*a, **kw)
        out = np.array(out)
        out[np.argmax(np.where(np.isfinite(out), out, -1))] += 1
        return out, info
    monkeypatch.setattr(operators, "sssp", altered)


FAULTS = [
    ("graph500-21.sssp", 9, _state_unchanged),
    ("graph500-21.sssp", 9, _answer_altered),
]


@pytest.mark.parametrize("workload,scale,fault", FAULTS,
                         ids=[f"{w}-{f.__name__.strip('_')}"
                              for w, _, f in FAULTS])
def test_fault_is_not_correct(rehearse, fresh_runners, monkeypatch,
                              workload, scale, fault):
    fault(monkeypatch)
    r = rehearse(workload, scale, seconds=1.0)
    assert r["correct"] is False, r["checks"]
    assert any(c["value"] > c["limit"] for c in r["checks"].values())
