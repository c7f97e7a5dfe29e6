"""Parts found by name from their own files; keys no part takes refused."""
import jax
import pytest

import graphgen
import harness
import plugins
from conftest import SMALL

jax.config.update("jax_enable_x64", True)
CONFIG = dict(SMALL, name="small", c=0.85, xi=1e-10, dtype="float64",
              plan={"step_impl": "dense"})


@pytest.fixture(scope="module")
def graph():
    return graphgen.run_edges(SMALL, 3)


def test_a_new_driver_is_found_by_its_name(tmp_path, monkeypatch):
    (tmp_path / "drivers").mkdir()
    (tmp_path / "drivers" / "echo.py").write_text(
        "SPANS = ()\n"
        "def make(engine, config, dtype, graph, warm_xi, *, word):\n"
        "    return word\n")
    monkeypatch.setattr(plugins, "BENCH", str(tmp_path))
    driver, mod = harness.make_driver(dict(query="echo", word="hi",
                                           why="a test"),
                                      None, CONFIG, "float64", None)
    assert driver == "hi" and mod.SPANS == ()


@pytest.mark.parametrize("kind, name", [("drivers", "open_loop"),
                                        ("generators", "rmat"),
                                        ("metrics", "no_such_metric")])
def test_an_unknown_part_is_a_setup_error(kind, name):
    with pytest.raises(plugins.SetupError, match=f"bench/{kind}/{name}.py"):
        plugins.load(kind, name)


def test_an_unknown_generator_kind_is_a_setup_error():
    config = dict(SMALL, generator=dict(SMALL["generator"], kind="rmat"))
    with pytest.raises(plugins.SetupError, match="no generator 'rmat'"):
        graphgen.run_edges(config, 1)


def test_a_generator_key_it_does_not_take_is_refused():
    config = dict(SMALL, generator=dict(SMALL["generator"], edge_factor=16))
    with pytest.raises(plugins.SetupError, match="generator 'powerlaw_web'"):
        graphgen.run_edges(config, 1)


@pytest.mark.parametrize("traffic", [
    dict(query="topk", arrivals="open", stream_seed=1, clients=32,
         think_s=0.0, zipf=1.1, k=10, batch=16, queue_cap=32),
    dict(query="topk", stream_seed=1, clients=32, think_s=0.0, zipf=1.1,
         k=10, batch=16),
    dict(query="rank", batch=16),
])
def test_a_mix_its_driver_does_not_fit_is_refused(traffic, graph):
    with pytest.raises(plugins.SetupError,
                       match=f"traffic query {traffic['query']!r}"):
        harness.make_driver(traffic, None, CONFIG, "float64", graph)


def test_the_plan_reaches_the_engine(graph):
    config = dict(CONFIG, plan={"step_impl": "dense", "cache": True})
    engine = harness.prepare_engine(config, *graph[:2], "float64", 1)
    assert engine.engine_plan.cache is True
    assert engine.engine_plan.step_impl == "dense"
    assert engine.engine_plan.c == 0.85


@pytest.mark.parametrize("plan, chips, match", [
    ({"step_impl": "dense", "cahce": True}, 1, "plan"),
    ({"step_impl": "dense", "mesh": [2, 2]}, 1, "mesh of 4 chips"),
    ({"step_impl": "dense"}, 4, "mesh of 1 chips"),
])
def test_a_plan_the_cell_cannot_run_is_refused(plan, chips, match, graph):
    with pytest.raises(plugins.SetupError, match=match):
        harness.prepare_engine(dict(CONFIG, plan=plan), *graph[:2],
                               "float64", chips)
