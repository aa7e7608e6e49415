"""A whole run with the timed path broken underneath: ``correct`` must
come out false.  The cells run inference on one chip, so the fault they
can have is an answer altered where it is produced."""
import jax.numpy as jnp
import pytest

import tiny


def _alter_outputs(monkeypatch):
    from repro.conv.plan import PreparedConv
    call = PreparedConv.__call__

    def altered(self, x, **kw):
        y = call(self, x, **kw)
        return y.at[:, 0, 0, 0].add(1e-3 * jnp.max(jnp.abs(y)))
    monkeypatch.setattr(PreparedConv, "__call__", altered)


@pytest.mark.parametrize("cell", sorted(tiny.CELLS))
def test_sound_run_is_correct(cell):
    out = tiny.run(cell)
    assert out["correct"], out["check"]
    assert list(out["check"])[0] == "max_rel_err"
    assert list(out)[-1] == "check"


@pytest.mark.parametrize("cell", sorted(tiny.CELLS))
def test_nothing_is_built_inside_the_window(cell, capsys):
    tiny.run(cell)
    info = [line for line in capsys.readouterr().out.splitlines()
            if line.startswith("info: ") and "window_builds" in line]
    assert '"built": 0,' in info[-1], info[-1]


@pytest.mark.parametrize("cell", sorted(tiny.CELLS))
def test_altered_answer_is_not_correct(cell, monkeypatch):
    _alter_outputs(monkeypatch)
    out = tiny.run(cell)
    assert not out["correct"]
    assert out["check"]["max_rel_err"]["value"] > 1e-4

