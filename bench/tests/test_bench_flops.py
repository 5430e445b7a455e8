"""FLOP and byte counts against hand-worked values."""
import json

import pytest

from bench import flops, run

GRANITE = run.load_config("granite-3-2b")["model"]
GLM = run.load_config("glm4-9b-20l")["model"]
MIB = 2 ** 20


def test_granite_page_bytes():
    # 40 layers x (K, V) x 8 kv heads x 16 rows x 64 dims x 2 bytes
    assert flops.kv_page_bytes(GRANITE) == 40 * 2 * 8 * 16 * 64 * 2
    assert flops.kv_page_bytes(GRANITE) == 1.25 * MIB
    assert flops.kv_page_bytes(GRANITE, stored=True) == 2.5 * MIB


def test_glm_page_bytes_need_no_padding():
    # 20 layers x 2 x 2 kv heads x 16 x 128 x 2 bytes = 320 KiB
    assert flops.kv_page_bytes(GLM) == 320 * 1024
    assert flops.kv_page_bytes(GLM, stored=True) == 320 * 1024


def test_layer_params():
    # granite: q 2048x2048, k/v 2048x512 each, o 2048x2048, mlp 3x2048x8192
    assert flops.layer_matmul_params(GRANITE) == (
        2048 * 2048 * 2 + 2 * 2048 * 512 + 3 * 2048 * 8192)
    # glm4: q 4096x4096, k/v 4096x256, o 4096x4096, mlp 3x4096x13696
    assert flops.layer_matmul_params(GLM) == (
        4096 * 4096 * 2 + 2 * 4096 * 256 + 3 * 4096 * 13696)


def test_segments_work_counts_causal_keys():
    # a decode at position 9 sees 10 keys; a chunk 0..3 sees 1+2+3+4
    n, keys, walked = flops.segments_work([(9, 10), (0, 4)])
    assert (n, keys, walked) == (5, 10 + 10, 10 + 4)


def test_paged_kernel_cost_one_decode_row():
    # one granite decode row at position 1023: 1024 keys per head
    fl, nb = flops.paged_kernel_cost(GRANITE, [(1023, 1024)])
    assert fl == 4 * 40 * 32 * 64 * 1024
    per_layer = (2 * 8 * 1024 * 64 * 2          # K and V, 1024 rows
                 + 2 * 32 * 1 * 64 * 2          # q in, out
                 + 2 * 8 * 1 * 64 * 2)          # fresh K/V row
    assert nb == 40 * per_layer


def test_step_flops_and_roofline():
    fl = flops.step_flops(GLM, [(0, 128)], logits_rows=1)
    keys = 128 * 129 // 2
    assert fl == (2 * 20 * flops.layer_matmul_params(GLM) * 128
                  + 4 * 20 * 32 * 128 * keys + 2 * 4096 * 151552)
    peak = json.loads((run.BENCH / "peaks.json").read_text())["TPU v5 lite"]
    assert flops.roofline_seconds(197e12, 0, peak) == pytest.approx(1.0)
    assert flops.roofline_seconds(0, 819e9, peak) == pytest.approx(1.0)
