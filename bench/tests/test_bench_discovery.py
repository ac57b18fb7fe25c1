"""BENCHMARK.json against the contract's form, and discovery by name."""
from __future__ import annotations

import hashlib
import json
import re

import numpy as np
import pytest

from bench_fixtures import REPO, TINY, run_off_chip, tiny_root
from benchkit import spec, traffic

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BM = json.loads((REPO / "BENCHMARK.json").read_text())


def test_benchmark_file_has_the_contract_keys_and_names():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["command"] == ["python3", "bench/run.py"]
    assert 1 <= BM["run_seconds"] <= 51
    for c in BM["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("bench/") and (REPO / c["file"]).is_file()
    for w in BM["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in BM["workloads"]) <= 1
    metrics = BM["end_to_end"] + BM["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BM["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in BM["end_to_end"]}
    assert {m["moves"] for m in BM["per_layer"]} <= e2e


@pytest.mark.parametrize("cell", [w["name"] for w in BM["workloads"]])
def test_every_cell_finds_its_files_and_readers(cell):
    c = spec.load_cell(cell)
    assert c.config["chips"] == c.chips
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(spec.metric_reader(m["name"]))
    assert set(c.config["limits"]) == {"answer_err_max"}
    keys = {k for cfg in BM["configs"] if cfg["name"] == c.workload["config"]
            for k in cfg["reduced"]}
    assert keys == set(c.config["reduced"])


@pytest.mark.parametrize("mix", ["planted-pool8", "planted-pool16"])
def test_traffic_is_seeded_and_planted(mix):
    mix_d = json.loads(spec.traffic_path(REPO, mix).read_text())
    cfg = {"dataset": "ECG", "ref_len": 300 * (mix_d["pool"] + 1),
           "query_len": 128}
    seed = 2**31 + 5
    a, b = traffic.build(cfg, mix_d, seed), traffic.build(cfg, mix_d, seed)
    assert np.array_equal(a.ref, b.ref) and np.array_equal(a.pool, b.pool)
    assert a.ref.dtype == np.float32 and a.pool.shape == (mix_d["pool"], 128)
    assert len(a.offsets) == mix_d["pool"]
    assert all(t - s >= 128 for s, t in zip(a.offsets, a.offsets[1:]))
    other = traffic.build(cfg, {**mix_d, "data_seed": mix_d["data_seed"] + 1},
                          seed)
    assert not np.array_equal(a.ref, other.ref)


@pytest.mark.parametrize("mix", ["planted-pool8", "planted-pool16"])
def test_seed_orders_the_same_work(mix):
    # Every seed sends the same pool against the same reference: each
    # cycle of P queries is a permutation of the pool, drawn from the seed.
    mix_d = json.loads(spec.traffic_path(REPO, mix).read_text())
    cfg = {"dataset": "ECG", "ref_len": 300 * (mix_d["pool"] + 1),
           "query_len": 128}
    a = traffic.build(cfg, mix_d, 2**31 + 5)
    b = traffic.build(cfg, mix_d, 2**33 + 7)
    assert np.array_equal(a.ref, b.ref) and np.array_equal(a.pool, b.pool)
    n = mix_d["pool"]
    order_a = [a.query_index(i) for i in range(3 * n)]
    order_b = [b.query_index(i) for i in range(3 * n)]
    for c in range(3):
        assert sorted(order_a[c * n:(c + 1) * n]) == list(range(n))
        assert sorted(order_b[c * n:(c + 1) * n]) == list(range(n))
    assert order_a != order_b
    assert order_a == [a.query_index(i) for i in range(3 * n)]


def _digest(root):
    return {p.relative_to(root).as_posix(): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted((root / "bench").rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_added_config_and_mix_are_found_without_editing_a_file(tmp_path):
    before = {k: v for k, v in _digest(REPO).items()
              if not k.startswith("bench/tests/")}
    root = tiny_root(tmp_path)
    after = _digest(root)
    assert all(after[k] == v for k, v in before.items())  # nothing edited
    assert set(after) - set(before) == {"bench/configs/tiny.json",
                                        "bench/traffic/planted-tiny.json"}
    c = spec.load_cell(TINY, root)
    assert c.config["ref_len"] == 4096 and c.traffic["pool"] == 4
    wl = traffic.build(c.config, c.traffic, seed=3)
    assert wl.ref.shape == (4096,) and wl.pool.shape == (4, 128)
    with pytest.raises(KeyError):
        spec.load_cell("no-such-cell", root)


COUNTING_ENTRY = '''"""``single``'s entry, counting its dispatches."""
from pathlib import Path

from benchkit import spec

Single = spec.entry_class("single", Path(__file__).resolve().parents[2])


class Entry(Single):
    dispatches = 0

    def dispatch(self, query):
        type(self).dispatches += 1
        return super().dispatch(query)
'''


def test_added_layout_is_found_without_editing_a_file(tmp_path, monkeypatch):
    before = {k: v for k, v in _digest(REPO).items()
              if not k.startswith("bench/tests/")}
    root = tiny_root(tmp_path, ref_len=1024, pool=2, layout="tiny_counting")
    spec.entry_path(root, "tiny_counting").write_text(COUNTING_ENTRY)
    after = _digest(root)
    assert all(after[k] == v for k, v in before.items())  # nothing edited
    assert set(after) - set(before) == {"bench/configs/tiny.json",
                                        "bench/traffic/planted-tiny.json",
                                        "bench/entries/tiny_counting.py"}
    loaded, entry_class = {}, spec.entry_class

    def recorded(layout, root):
        loaded[layout] = entry_class(layout, root)
        return loaded[layout]

    monkeypatch.setattr(spec, "entry_class", recorded)
    res = run_off_chip(monkeypatch, root, seconds=0.3)
    assert res["correct"] is True, res
    counting = loaded["tiny_counting"]
    assert counting.__module__ == "bench_entry_tiny_counting"
    assert counting.dispatches > res["attempted"] >= 1  # and the warm-up


def test_unknown_layout_names_the_path_looked_for(tmp_path, monkeypatch):
    root = tiny_root(tmp_path, layout="no_such_layout")
    path = str(spec.entry_path(root, "no_such_layout"))
    with pytest.raises(FileNotFoundError, match=re.escape(path)):
        run_off_chip(monkeypatch, root)
