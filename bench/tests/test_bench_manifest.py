"""BENCHMARK.json against the contract's shape, and every file it names."""
import json
import re
from pathlib import Path

import pytest

from bench import run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
MANIFEST = run.load_manifest()


def test_top_level_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["bench"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert len(json.dumps(MANIFEST)) < 64 * 1024
    for word in MANIFEST["command"]:
        assert not word.startswith("/") and ".." not in word


def test_names_and_units_use_allowed_characters():
    names = [m["name"] for k in ("end_to_end", "per_layer")
             for m in MANIFEST[k]]
    names += [c["name"] for c in MANIFEST["configs"]]
    names += [w["name"] for w in MANIFEST["workloads"]]
    names += [w["traffic"] for w in MANIFEST["workloads"]]
    for n in names:
        assert NAME.match(n), n
    assert len(names) == len(set(names))
    for k in ("end_to_end", "per_layer"):
        for m in MANIFEST[k]:
            assert UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher")


def test_bounds():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_every_cell_names_existing_files():
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    for cell in MANIFEST["workloads"]:
        assert cell["chips"] in (1, 4)
        c = configs[cell["config"]]
        assert (run.ROOT / c["file"]).is_file()
        spec = run.load_config(cell["config"])
        assert set(c["reduced"]) == set(spec["reduced"])
        assert (run.BENCH / "mixes" / f"{cell['traffic']}.json").is_file()
        assert "widest_gap" in run.load_limits(cell["name"])
        assert len(cell["why"]) <= 200


def test_every_metric_has_a_reader_and_a_reported_target():
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    layers = {}
    for m in MANIFEST["per_layer"]:
        mod = run.load_reader(m["name"])
        assert callable(mod.read)
        assert m["moves"] in e2e
        layers.setdefault(m["layer"], set()).add(m["name"])
    for cell in MANIFEST["workloads"]:
        got = {m["name"] for m in run.cell_metrics(MANIFEST, cell,
                                                   "end_to_end")}
        assert "setup_s" in got and len(got) >= 2
        assert run.cell_metrics(MANIFEST, cell, "per_layer")


def test_configs_are_used_and_widths_untouched():
    used = {w["config"] for w in MANIFEST["workloads"]}
    assert used == {c["name"] for c in MANIFEST["configs"]}
    for c in MANIFEST["configs"]:
        for key in c["reduced"]:
            assert not re.search(r"(_dim|_rank|d_model|d_ff|heads)$", key)


@pytest.mark.parametrize("name", ["compiles_in_window", "device_idle_share"])
def test_reader_dropped_in_is_found_by_name(tmp_path, name):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "fresh_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    assert run.load_reader("fresh_metric", tmp_path).read(None) == 42.0
    assert run.load_reader(name).read is not None


def test_config_dropped_in_is_found_by_name(tmp_path):
    (tmp_path / "configs").mkdir()
    spec = run.load_config("granite-3-2b")
    spec["name"] = "fresh"
    (tmp_path / "configs" / "fresh.json").write_text(json.dumps(spec))
    assert run.load_config("fresh", tmp_path)["model"] == spec["model"]
    with pytest.raises(run.BenchError):
        run.load_config("absent", tmp_path)


def test_a_new_cell_needs_only_new_files(tmp_path):
    """A mix, a limit and a metric reader dropped beside the existing ones
    are enough for the harness to plan a new cell."""
    for d in ("configs", "mixes", "limits", "metrics"):
        (tmp_path / d).mkdir()
    (tmp_path / "configs" / "granite-3-2b.json").write_text(
        json.dumps(run.load_config("granite-3-2b")))
    (tmp_path / "mixes" / "burst.json").write_text(json.dumps({
        "loop": "closed", "clients": 4,
        "prompt": {"median": 160, "sigma": 0.4, "min": 100, "max": 256},
        "output": {"min": 8, "max": 16},
        "engine": {"max_slots": 4, "prefill_chunk": 128,
                   "token_budget": 64}}))
    (tmp_path / "limits" / "granite-3-2b.burst.json").write_text(
        json.dumps({"widest_gap": 1.0}))
    from bench import traffic
    mix = traffic.load_mix("burst", tmp_path / "mixes")
    es = run.engine_settings(run.load_config("granite-3-2b", tmp_path), mix)
    assert es["max_blocks_per_seq"] == -(-(256 + 16) // 16)
    shapes = run.reachable_shapes(es, mix)
    # ragged prompts under a 64-token budget: every flat bucket up to the
    # budget, with every segment bound up to 64
    assert {tb for tb, _ in shapes} == set(range(4, 69, 4))
    assert {chm for _, chm in shapes} >= {1, 2, 4, 8, 16, 32, 64}
    assert all(chm <= tb for tb, chm in shapes)
    assert run.load_limits("granite-3-2b.burst", tmp_path)["widest_gap"] == 1


@pytest.mark.parametrize("prompt", [128, 100, 7])
def test_warm_up_follows_the_mix(prompt):
    """A prompt of one chunk or less streams in one grant: a tick packs
    ``k`` such grants and ``n`` decode rows, which the engine pads to a
    multiple of 4 (capped) with its longest segment rounded to a power
    of two."""
    R, C = 16, 128
    es = {"max_slots": R, "prefill_chunk": C, "token_budget": None}
    mix = {"prompt": {"min": prompt, "max": prompt}}
    want = set()
    for k in range(R + 1):
        for n in range(R + 1 - k):
            if k + n:
                tb = min(-(-(n + k * prompt + 1) // 4) * 4, R * C + 1)
                seg = prompt if k else 1
                want.add((tb, min(1 << (seg - 1).bit_length(), tb)))
    assert set(run.reachable_shapes(es, mix)) == want
