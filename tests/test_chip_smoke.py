"""``chip_smoke.py``'s phases, driven at tiny widths under the Pallas
interpreter: the serving loop, the HIGHEST-precision reference and
the fallback / degradation / account-only checks keep working between
chip runs (on the chip the same phases run at full width under
``target="compiled"``)."""

import importlib.util
from pathlib import Path

import jax
import pytest

from repro.models.cnn import init_resnet, init_vgg, resnet_graph, vgg_graph


@pytest.fixture(scope="module")
def smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("model", ["vgg", "resnet"])
def test_serve_phase_matches_reference(smoke, model, capsys):
    key = jax.random.PRNGKey(0)
    if model == "vgg":
        params = init_vgg(key, n_classes=10, width_mult=0.05)
        graph, hw = vgg_graph(params), 16
    else:
        graph, hw = resnet_graph(width_mult=0.25), 8
        params = init_resnet(key, graph)
    smoke.serve_phase(model, graph, params, hw, key, n_requests=4,
                      target="interpret")
    line = capsys.readouterr().out
    assert "requests=4" in line and "fallbacks=0" in line
    assert "degraded_dispatches=0 account_only=0" in line


def test_train_phase_matches_reference_vjp(smoke, capsys):
    smoke.train_phase(jax.random.PRNGKey(0), batch=2, width_mult=0.05,
                      hw=16, target="interpret")
    line = capsys.readouterr().out
    assert "steps=2" in line and "fallbacks=0" in line


def test_serve_phase_fails_on_account_only_completions(smoke):
    """A server that completes requests without logits is a failure,
    not a served request."""
    key = jax.random.PRNGKey(1)
    params = init_vgg(key, n_classes=10, width_mult=0.05)
    with pytest.raises(SystemExit):
        smoke.serve_phase("vgg", vgg_graph(params), params, 16, key,
                          n_requests=2, target="account-only")
