"""Architecture modules: a configuration's file names the module that
gives the harness its weights, reference, program config, work counts and
kernel names, so a new kind of model arrives as new files only; without
the name the dense module serves, and gives what the harness gave before
there were modules."""
import dataclasses
import gzip
import hashlib
import json
import shutil
import time
from types import SimpleNamespace as NS

import jax
import numpy as np
import pytest

from bench import run, trace_reduce, traffic
from bench import window as win

DATA = run.BENCH / "tests" / "data"
DENSE = run.load_arch("dense_gqa")
PEAK_V5E = json.loads((run.BENCH / "peaks.json").read_text())["TPU v5 lite"]

PROBE = '''"""A test architecture: the dense parts, each call logged."""
from pathlib import Path

from bench import flops, run

_dense = run.load_arch("dense_gqa")
LOG = Path(__file__).with_name("calls.log")
KERNELS = {"probe_kernel": ("probe_kernel_pallas",)}


def _logged(name):
    def call(*a, **kw):
        with LOG.open("a") as f:
            f.write(name + "\\n")
        return getattr(_dense, name)(*a, **kw)
    return call


program_config = _logged("program_config")
leaf_specs = _logged("leaf_specs")
make_params = _logged("make_params")
served_gaps = _logged("served_gaps")
step_flops = _logged("step_flops")


def kernel_cost(model, segments):
    n, keys, walked = flops.segments_work(segments)
    return {"probe_kernel": (1000 * keys, 10 * walked)}
'''

READER = '''def read(run):
    return run.roofline_share("probe_kernel")
'''


@pytest.fixture
def probe_bench(tmp_path):
    """A bench directory holding a configuration that names a module
    which exists only there, a roofline reader for its kernel, and the
    shipped readers."""
    shutil.copytree(run.BENCH / "metrics", tmp_path / "metrics")
    (tmp_path / "metrics" / "probe_kernel_roofline.py").write_text(READER)
    (tmp_path / "archs").mkdir()
    (tmp_path / "archs" / "probe.py").write_text(PROBE)
    (tmp_path / "configs").mkdir()
    spec = run.load_config("tiny", DATA)
    spec["bench_arch"] = "probe"
    (tmp_path / "configs" / "probe.json").write_text(json.dumps(spec))
    return tmp_path


def _calls(bench):
    log = bench / "archs" / "calls.log"
    return set(log.read_text().split()) if log.exists() else set()


def test_a_new_architecture_needs_only_new_files(probe_bench, monkeypatch):
    spec = run.load_config("probe", probe_bench)
    arch = run.arch_for(spec, probe_bench)
    assert arch.KERNELS == {"probe_kernel": ("probe_kernel_pallas",)}
    tables = []
    reduce_dir = trace_reduce.reduce_dir

    def spy(trace_dir, kernels):
        tables.append(kernels)
        return reduce_dir(trace_dir, kernels)
    monkeypatch.setattr(trace_reduce, "reduce_dir", spy)
    man = {"end_to_end": [],
           "per_layer": [{"name": "model_step_mfu", "unit": "%"},
                         {"name": "probe_kernel_roofline", "unit": "%"}]}
    cell = {"name": "probe.tiny", "config": "probe", "traffic": "tiny",
            "chips": 1}
    res = run.run_cell(
        man, cell, 2 ** 33 + 5, 1.0, True, spec=spec,
        mix=traffic.load_mix("tiny", DATA / "mixes"),
        limits=run.load_limits("tiny", DATA), t_process=time.perf_counter(),
        peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
        bench=probe_bench)
    assert res["correct"], res["checks"]
    assert res["metrics"]["model_step_mfu"]["value"] > 0
    # weights, program config, reference and counts all came through it
    assert {"program_config", "make_params", "served_gaps",
            "step_flops"} <= _calls(probe_bench)
    assert tables == [{"probe_kernel": ("probe_kernel_pallas",)}]
    arch.leaf_specs(spec["model"])
    assert "leaf_specs" in _calls(probe_bench)


def test_a_new_kernel_reads_its_roofline_from_its_module(probe_bench):
    spec = run.load_config("probe", probe_bench)
    arch = run.arch_for(spec, probe_bench)
    ticks = [{"t0": 0.1, "t1": 0.2, "segments": [(0, 4), (9, 10)],
              "logits_rows": 2}]
    peak = {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e3}
    rd = _run_data(spec["model"], arch, ticks, 1.0,
                   {"kernel_s": {"probe_kernel": 0.5}}, peak)
    reader = run.load_reader("probe_kernel_roofline", probe_bench)
    # keys 10 + 10, walked 4 + 10: max(20000 / 1e6, 140 / 1e3) = 0.14 s
    assert reader.read(rd) == pytest.approx(100 * 0.14 / 0.5)
    # the dense kernel's reader finds nothing of its own to read here
    assert run.load_reader("paged_attn_roofline").read(rd) is None


def test_without_the_key_the_dense_module_serves():
    for name in ("granite-3-2b", "glm4-9b-20l"):
        spec = run.load_config(name)
        assert "bench_arch" not in spec
        assert run.arch_for(spec).__file__ == DENSE.__file__
    assert run.arch_for(run.load_config("tiny", DATA)).__file__ == \
        DENSE.__file__
    with pytest.raises(run.BenchError):
        run.load_arch("absent")


@pytest.mark.parametrize("name", ["granite-3-2b", "glm4-9b-20l", "tiny"])
def test_dense_program_config_is_the_registered_arch_at_the_files_sizes(
        name):
    from repro.config import get_config
    spec = run.load_config(name, DATA if name == "tiny" else run.BENCH)
    cfg = DENSE.program_config(spec)
    base = get_config(spec["arch"])
    for f in dataclasses.fields(cfg):
        want = (spec["model"][f.name] if f.name in DENSE.PROGRAM_KEYS
                else getattr(base, f.name))
        assert getattr(cfg, f.name) == want, f.name


# the tiny configuration's leaves as the harness built them before there
# were modules
TINY_LEAVES = {
    ("embed", "table"): ((512, 64), "float32", 0.02),
    ("layers", "ln1", "scale"): ((2, 64), "float32", 0.1),
    ("layers", "ln2", "scale"): ((2, 64), "float32", 0.1),
    ("layers", "attn", "wq"): ((2, 64, 64), "float32", 0.125),
    ("layers", "attn", "wk"): ((2, 64, 32), "float32", 0.125),
    ("layers", "attn", "wv"): ((2, 64, 32), "float32", 0.125),
    ("layers", "attn", "wo"): ((2, 64, 64), "float32", 0.125),
    ("layers", "mlp", "wg"): ((2, 64, 128), "float32", 0.125),
    ("layers", "mlp", "wu"): ((2, 64, 128), "float32", 0.125),
    ("layers", "mlp", "wo"): ((2, 128, 64), "float32", 0.08838834764831845),
    ("final_ln", "scale"): ((64,), "float32", 0.1),
    ("lm_head", "kernel"): ((64, 512), "float32", 0.125),
}


# the first 16 hex digits of the sha256 of each leaf's bytes, as the
# harness built the tiny model from seed 2 ** 40 + 1 before there were
# modules
TINY_DIGESTS = {
    "['embed']['table']": "a4676d094483fbe9",
    "['final_ln']['scale']": "bf68ee99360403cd",
    "['layers']['attn']['wk']": "12af29e153e11595",
    "['layers']['attn']['wo']": "d18de8860b469c85",
    "['layers']['attn']['wq']": "a6365a73725284c5",
    "['layers']['attn']['wv']": "9eef3fa22030bd21",
    "['layers']['ln1']['scale']": "bc9da1d810275ce6",
    "['layers']['ln2']['scale']": "fe46815d365b846b",
    "['layers']['mlp']['wg']": "41589642e4b5bc23",
    "['layers']['mlp']['wo']": "f17ee023d8ec633e",
    "['layers']['mlp']['wu']": "d67975c5800ffdb4",
    "['lm_head']['kernel']": "cf7a4d778d00a93c",
}


def test_dense_weights_are_the_accepted_ones():
    model = run.load_config("tiny", DATA)["model"]
    assert DENSE.leaf_specs(model) == TINY_LEAVES
    assert list(DENSE.leaf_specs(model)) == list(TINY_LEAVES)
    params = DENSE.make_params(model, 2 ** 40 + 1)
    got = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        assert a.dtype == np.float32
        got[jax.tree_util.keystr(path)] = hashlib.sha256(
            a.tobytes()).hexdigest()[:16]
    assert got == TINY_DIGESTS


# the reference's gaps on the tiny model from seed 2 ** 35 + 9, 30 prompt
# and 12 served tokens drawn by ``default_rng(4)``, as the harness read
# them before there were modules: float32, then the float8 control's
GAPS = [0.9692330360412598, 4.583215236663818, 4.188611030578613,
        2.9865663051605225, 1.6554349660873413, 0.8141703605651855,
        5.574978828430176, 3.068570613861084, 3.0114173889160156,
        1.2500813007354736, 2.864255666732788, 2.830775499343872]
CONTROL_GAPS = [0.0, 0.0, 0.02549910545349121, 0.0, 0.0, 0.0,
                0.13992047309875488, 0.0, 0.0, 0.0, 0.0,
                0.05513453483581543]


def test_dense_reference_is_the_accepted_one():
    model = run.load_config("tiny", DATA)["model"]
    params = DENSE.make_params(model, 2 ** 35 + 9)
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, model["vocab"], 30)
    served = rng.integers(0, model["vocab"], 12)
    want = np.asarray(GAPS, np.float32)
    np.testing.assert_array_equal(
        DENSE.served_gaps(model, params, prompt, served, pad_to=64), want)
    g, c = DENSE.served_gaps(model, params, prompt, served,
                             low="float8_e4m3fn")
    np.testing.assert_array_equal(g, want)
    np.testing.assert_array_equal(c, np.asarray(CONTROL_GAPS, np.float32))


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=())


def _recorded(name):
    doc = json.loads(gzip.decompress((DATA / name).read_bytes()))
    names = doc["names"]
    return [NS(name=p["name"], lines=[
        NS(name=ln["name"], events=[_ev(names[i], s, d)
                                    for i, s, d in ln["events"]])
        for ln in p["lines"]]) for p in doc["planes"]]


def _run_data(model, arch, ticks, window_s, trace, peak):
    return win.RunData(
        model=model, arch=arch, mix={}, engine={}, t_open=0.0,
        t_close=window_s, ticks=ticks, telemetry_ticks=[],
        telemetry_spans=[], telemetry_epoch=None, window={}, reached_t={},
        submit_t={}, compiles_in_window=0, trace=trace, peak=peak)


# each recorded excerpt's ticks as segments (granite: 16 decode rows at
# depths spread over the mix's; glm4: a decode-only tick, then one with
# a prefill chunk), and what the harness read from them before there
# were modules: per tick its step FLOPs and the kernel's (FLOPs, bytes),
# then the two readers' values
RECORDED = {
    "granite-3-2b": (
        "granite_ticks.trace.json.gz",
        [([(136 * i + 127, 136 * i + 128) for i in range(16)], 16)] * 2,
        [87086530560, 87086530560],
        [(6018826240, 1511260160), (6018826240, 1511260160)],
        0.002928870362078607, 0.5679209943707142),
    "glm4-9b-20l": (
        "glm4_ticks.trace.json.gz",
        [([(2048 + 16 * i, 2049 + 16 * i) for i in range(8)], 8),
         ([(1024, 1152)] + [(2048 + 16 * i, 2049 + 16 * i)
                            for i in range(7)], 7)],
        [80713613312, 1160474984448],
        [(5518131200, 347668480), (50465013760, 371220480)],
        0.061892503122337, 0.4002323037322154),
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_dense_counts_read_as_before_on_the_recorded_ticks(name):
    fixture, ticks, step, kernel, mfu, roofline = RECORDED[name]
    model = run.load_config(name)["model"]
    tr = trace_reduce.reduce_planes(_recorded(fixture), DENSE.KERNELS)
    w = tr["window_s"]
    assert [DENSE.step_flops(model, s, r) for s, r in ticks] == step
    assert [DENSE.kernel_cost(model, s) for s, _ in ticks] == [
        {"paged_attention": k} for k in kernel]
    rd = _run_data(model, DENSE, [
        {"t0": w * k / 4, "t1": w * (k + 1) / 4, "segments": s,
         "logits_rows": r} for k, (s, r) in enumerate(ticks)], w, tr,
        PEAK_V5E)
    assert run.load_reader("model_step_mfu").read(rd) == mfu
    assert run.load_reader("paged_attn_roofline").read(rd) == roofline
