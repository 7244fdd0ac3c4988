"""The comparison that decides ``correct``: sound runs pass, the float8
control and planted faults fail.  Whole runs on the CPU at a tiny size,
past the harness's look for a chip."""
import json

import pytest

import pbench_tiny


def _info(lines):
    for ln in lines:
        if ln.startswith("check: "):
            return json.loads(ln[len("check: "):])
    raise AssertionError(f"no check line in {lines}")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return pbench_tiny.make(str(tmp_path_factory.mktemp("tiny")))


@pytest.mark.parametrize("cell", ["minicpm_2b.chat", "phi4_mini.code"])
def test_sound_run_is_correct(root, cell):
    res, lines = pbench_tiny.run(root, cell)
    assert res["correct"], lines
    assert list(res)[-1] == "checks"
    gap = res["checks"]["logit_gap"]
    assert gap["value"] <= gap["limit"]
    assert lines[-1].startswith("check logit_gap: ")


@pytest.mark.parametrize("cell", ["minicpm_2b.chat", "phi4_mini.code"])
def test_control_in_the_programs_place_is_not_correct(root, cell):
    """The float8 reference's first choices go through the same comparison
    in the served tokens' place."""
    res, lines = pbench_tiny.run(root, cell, control=True)
    assert not res["correct"], lines
    gap = res["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]
    info = _info(lines)
    assert info["program_logit_gap"] <= gap["limit"], info


def _fault_token(monkeypatch):
    """The decode program's tokens altered where they are produced."""
    from repro.runtime import server as srv
    make = srv.Server._make_decode

    def bad(self):
        fn = make(self)
        v = self.cfg.vocab_size

        def f(*args):
            nxt, caches, *rest = fn(*args)
            return ((nxt + 1) % v, caches, *rest)
        return f
    monkeypatch.setattr(srv.Server, "_make_decode", bad)


def _fault_state(monkeypatch):
    """A step that returns its K/V state unchanged."""
    from repro.models import layers
    monkeypatch.setattr(layers, "pool_update_rows",
                        lambda pool, *a, **k: pool)


@pytest.mark.parametrize("plant", [_fault_token, _fault_state],
                         ids=["token_altered", "state_unchanged"])
def test_planted_fault_is_not_correct(root, monkeypatch, plant):
    plant(monkeypatch)
    res, lines = pbench_tiny.run(root, "minicpm_2b.chat")
    assert not res["correct"], lines
    gap = res["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]
