"""Each fault a cell can have, planted under the timed path, turns
``correct`` false; so does the control (the reference one precision below
the configuration's, in the program's place)."""
import pytest

from bench import harness


@pytest.fixture
def cell(tiny_root):
    def get(name):
        return harness.Cell(name, tiny_root, tiny_root / "bench")
    return get


def run_driver(cell, name, control=False, seed=21):
    import time
    c = cell(name)
    out = c.driver.run(c, seed, 2.0, False, c.root, time.perf_counter(),
                       control=control)
    return c, out


def test_serve_token_altered_where_produced(cell, monkeypatch):
    from repro.serving.engine import ServingEngine
    emit = ServingEngine._emit

    def altered(self, req, token, stream):
        # every third token of a request comes out one id off
        if len(req.tokens) % 3 == 2:
            token = (token + 1) % self.model_cfg.vocab_size
        return emit(self, req, token, stream)

    monkeypatch.setattr(ServingEngine, "_emit", altered)
    c, out = run_driver(cell, "tiny-chat")
    assert not c.driver.correct(out["checks"]), out["checks"]


def test_serve_control_fails_where_the_program_passes(cell):
    c, out = run_driver(cell, "tiny-chat", control=True)
    assert c.driver.correct(out["checks"]), out["checks"]
    ctrl = c.driver.checks_of(out["control"], c.config["limits"],
                              out["checks"]["checked_tokens"]["value"])
    assert not c.driver.correct(ctrl), ctrl


def test_serve_nothing_checked_is_not_correct(cell):
    c = cell("tiny-chat")
    checks = c.driver.checks_of({"logit_gap": 0.0}, c.config["limits"], 0)
    assert not c.driver.correct(checks)
