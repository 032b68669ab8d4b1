"""work.py against counts worked out by hand at the cells' widths."""
import json
import os

import work

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _model(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)["model"]


QWEN = _model("qwen2-0.5b")
MAMBA = _model("mamba2-780m")


def test_dense_layer_matmul_flops():
    # q 896x896, k and v 896x128, o 896x896, gate/up/down 896x4864
    assert work.dense_layer_matmul_flops(QWEN) == 2 * 14_909_440


def test_paged_decode_work():
    # prompt 100, 3 tokens: decode steps see 101 and 102 positions
    flops, nbytes = work.paged_decode_work(QWEN, 100, 3)
    assert flops == 24 * 4 * 14 * 64 * 203 == 17_461_248
    # K and V: 2 kv heads x 64 x 2 B x 2 x 24 layers = 12,288 B a position,
    # plus q and out (14 x 64 x 2 B each) per step and layer
    assert nbytes == 203 * 12_288 + 2 * 24 * 2 * 14 * 64 * 2
    assert nbytes == 2_666_496


def test_paged_prefill_work():
    # 4 positions see 1 + 2 + 3 + 4 = 10 positions
    flops, nbytes = work.paged_prefill_work(QWEN, 4)
    assert flops == 86_016 * 10
    assert nbytes == 24 * 4 * 2_048 * 2 == 393_216


def test_dense_request_flops():
    # 4 prompt positions and 1 decode step: 5 positions, contexts 1..5
    want = (5 * 24 * 29_818_880 + 86_016 * 15 + 2 * 2 * 896 * 151_936)
    assert work.dense_request_flops(QWEN, 4, 2) == want


def test_ssd_scan_work():
    # one 256-chunk, 48 layers x 48 heads: 2 Q^2 (N + P) + 4 Q N P
    flops, nbytes = work.ssd_scan_work(MAMBA, 256)
    assert flops == 2_304 * (25_165_824 + 8_388_608) == 77_309_411_328
    # x dt and y (48 x 64), log decay (48), B and C (128 each), f32
    assert nbytes == 48 * 256 * 6_448 * 4 == 316_932_096
    assert work.ssd_scan_work(MAMBA, 512)[0] == 2 * flops


def test_ssm_request_flops():
    # in_proj 1536 x 6448, conv 4 x 3328, out_proj 3072 x 1536
    per_layer = 2 * 1536 * 6448 + 2 * 4 * 3328 + 2 * 3072 * 1536
    assert work.ssm_layer_matmul_flops(MAMBA) == per_layer == 29_272_064
    flops = work.ssm_request_flops(MAMBA, 256, 3)
    want = (258 * 48 * per_layer + 77_309_411_328
            + 2 * 48 * 48 * 4 * 128 * 64 + 3 * 2 * 1536 * 50_288)
    assert flops == want


def test_kernel_work_sums_requests():
    reqs = [(100, 3), (4, 1)]
    got = work.kernel_work("dense", QWEN, reqs)
    assert got["paged_decode"] == work.paged_decode_work(QWEN, 100, 3)
    assert got["paged_prefill"][0] == 86_016 * (5_050 + 10)
    assert set(work.kernel_work("ssm", MAMBA, [(256, 5)])) == {"ssd_scan"}
