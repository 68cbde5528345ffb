"""Fixtures: the harness reads the tiny configurations of tiny.py from a
temporary directory laid out like bench/."""
import dataclasses
import json

import pytest

from bench.tests.tiny import LIMIT, MIXES, ROOT, TINY, tiny_spec

@pytest.fixture
def tiny_program(monkeypatch):
    """The harness builds the program's reduced preset for a tiny spec."""
    from bench import model
    from repro.configs import get_config, reduced

    def program_config(spec):
        cfg = reduced(get_config(spec["program_arch"]))
        over = {"num_layers": spec["num_hidden_layers"]}
        if model.is_moe(spec):
            over["capacity_factor"] = float(spec["capacity_factor"])
        return dataclasses.replace(cfg, **over)

    monkeypatch.setattr(model, "program_config", program_config)


@pytest.fixture
def bench_dir(tmp_path):
    """A bench/-like tree with the tiny cells, their mixes and limits;
    returns harness.Paths for it."""
    from bench import harness

    for sub in ("configs", "traffic", "limits"):
        (tmp_path / sub).mkdir()
    workloads = []
    for family in TINY:
        spec = tiny_spec(family)
        (tmp_path / "configs" / f"{spec['name']}.json").write_text(
            json.dumps(spec))
        for loop in MIXES:
            name = f"{spec['name']}.{loop}"
            workloads.append({"name": name, "config": spec["name"],
                              "traffic": loop, "chips": 1, "why": "test"})
            (tmp_path / "limits" / f"{name}.json").write_text(
                json.dumps({"limits": {"logit_gap": LIMIT}}))
    for loop, mix in MIXES.items():
        (tmp_path / "traffic" / f"{loop}.json").write_text(json.dumps(mix))
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench = dict(real, workloads=workloads)
    for group in ("end_to_end", "per_layer"):
        bench[group] = [{k: v for k, v in m.items() if k != "workloads"}
                        for m in real[group]]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return harness.Paths(benchmark=tmp_path / "BENCHMARK.json",
                         configs=tmp_path / "configs",
                         traffic=tmp_path / "traffic",
                         limits=tmp_path / "limits",
                         metrics=ROOT / "bench" / "metrics",
                         cache=tmp_path / "jax_cache",
                         scratch=tmp_path / "scratch")
