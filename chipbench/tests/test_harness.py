"""Modes and algorithms are files the harness finds by name."""

import pytest

import tiny
from chipbench.lib import harness


@pytest.mark.parametrize("key,kind,known", [
    ("mode", "targets", "plain"), ("algorithm", "algorithms", "ossart")])
def test_find_cell_rejects_an_unknown_mode_or_algorithm(monkeypatch, key,
                                                        kind, known):
    real = harness.load_json

    def load(path):
        d = real(path)
        if key in d:
            d[key] = "nonesuch"
        return d
    monkeypatch.setattr(harness, "load_json", load)
    with pytest.raises(KeyError) as err:
        tiny.load_cell("cbct512.cgls")
    assert f"no {kind}/nonesuch.py" in str(err.value)
    assert known in str(err.value)


def test_every_cell_finds_its_mode_and_algorithm():
    for name in ("cbct512.cgls", "cbct512-x4.cgls", "cbct256.ossart"):
        cell = tiny.load_cell(name)
        assert hasattr(harness.target(cell.config["mode"]), "build")
        algo = harness.algorithm(cell.traffic["algorithm"])
        assert set(cell.params["limits"]) == set(algo.NUMBERS)


def test_dist_mesh_comes_from_the_config(monkeypatch):
    import jax
    from repro.launch import mesh as launch_mesh
    seen = []
    monkeypatch.setattr(launch_mesh, "make_mesh",
                        lambda shape, names: seen.append((shape, names)))
    monkeypatch.setattr(jax, "local_device_count", lambda: 4)
    dist = harness.target("dist")
    dist.mesh({"mesh": {"data": 2, "model": 2}})
    dist.mesh(tiny.load_cell("cbct512-x4.cgls").config)
    assert seen == [((2, 2), ("data", "model")), ((4, 1), ("data", "model"))]
    with pytest.raises(RuntimeError, match="mesh 2 x 1 for 4 local devices"):
        dist.mesh({"mesh": {"data": 2, "model": 1}})
