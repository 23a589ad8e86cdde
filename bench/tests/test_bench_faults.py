"""The harness with its timed path broken underneath: ``correct`` has to
come out false, once for each fault a cell can have.  One-chip cells have
no exchange between chips to leave out."""
import jax
import pytest

import rehearse  # noqa: I001 - puts bench/ on the path first
import control
import run as bench_run

CELLS = {n: rehearse.small_cell(n)[2]["kind"] for n in rehearse.names()}


def _cells(kind):
    return [n for n, k in CELLS.items() if k == kind]


def _broken(kind, cls_factory):
    mod = bench_run.load("kinds", kind)
    return type("Broken", (), {"Cell": cls_factory(mod)})


def state_unchanged(mod):
    class Unchanged(mod.Cell):
        def make_step(self, step_fn):
            return super().make_step(
                lambda p, s, b: (p, s, step_fn(p, s, b)[2]))
    return Unchanged


def answer_altered(mod):
    """One position's logits are altered where the forward produces them."""
    class Altered(mod.Cell):
        def make_forward(self):
            fwd = self.model.forward

            def altered(p, t):
                logits, aux = fwd(p, t)
                return logits.at[:, t.shape[1] // 2, 3].set(1e4), aux
            return jax.jit(altered)
    return Altered


@pytest.mark.parametrize("name", _cells("train"))
@pytest.mark.parametrize("fault", [state_unchanged, control.half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_training_faults_are_not_correct(name, fault, monkeypatch):
    result = rehearse.rehearse(name, cell_mod=_broken("train", fault),
                               monkeypatch=monkeypatch)
    assert result["correct"] is False, result["compared"]


@pytest.mark.parametrize("name", _cells("forward"))
def test_forward_altered_answer_is_not_correct(name, monkeypatch):
    result = rehearse.rehearse(name, cell_mod=_broken("forward", answer_altered),
                               monkeypatch=monkeypatch)
    assert result["correct"] is False, result["compared"]
