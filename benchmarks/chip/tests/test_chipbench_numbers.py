"""flops.py against hand counts, and the traffic generator's seeds."""
import numpy as np
import pytest

from _chipbench_smoke import BENCH, cells
from chipbench import flops, traffic
from chipbench.reference import Arch


def arch(name):
    return Arch.from_config(cells.load_json(BENCH / "configs"
                                            / f"{name}.json"))


def test_minicpm3_flops_by_hand():
    a = arch("minicpm3-4b")
    # q_down 2560x768, q_up 768x40x96, kv_down 2560x288, k_up+v_up
    # 256x40x128, o 2560x2560, SwiGLU 3x2560x6400
    per_layer = (2560 * 768 + 768 * 40 * 96 + 2560 * 288 + 256 * 40 * 128
                 + 40 * 64 * 2560 + 3 * 2560 * 6400)
    assert per_layer == 62_668_800 == flops.matmul_params(a)
    # one token attends ctx positions: 40 heads x (64+32 scores + 64 values)
    assert flops.token_flops(a, 1000) == (2 * per_layer * 62
                                          + 2 * 40 * 160 * 1000 * 62)
    assert flops.head_flops(a) == 2 * 2560 * 73448
    assert flops.chunk_flops(a, 100, 3, True) == (
        sum(flops.token_flops(a, c) for c in (101, 102, 103))
        + flops.head_flops(a))


def test_command_r7b_flops_by_hand():
    a = arch("command-r7b")
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert per_layer == 218_103_808 == flops.matmul_params(a)
    # 16 layers; the window (4096) never cuts a context of 4096 or less
    assert flops.token_flops(a, 4096) == (2 * per_layer * 16
                                          + 2 * 2 * 32 * 128 * 4096 * 16)
    # past the window, the 12 windowed layers attend 4096 positions
    assert flops.attn_flops(a, 5000) == 2 * 2 * 32 * 128 * (
        4 * 5000 + 12 * 4096)
    assert flops.head_flops(a) == 2 * 4096 * 256000
    # a 256-token chunk is ~1.8 TFLOP of matmuls
    assert abs(256 * 2 * per_layer * 16 / 1.787e12 - 1) < 1e-3
    assert flops.iteration_flops(a, [(0, 2, True)], [10]) == (
        flops.chunk_flops(a, 0, 2, True) + flops.token_flops(a, 10)
        + flops.head_flops(a))


@pytest.mark.parametrize("mix", ["chat", "batch"])
def test_traffic_is_the_seeds_and_every_seed_serves_the_same_work(mix):
    params = cells.load_json(BENCH / "traffic" / f"{mix}.json")["params"]
    a = traffic.requests(params, 2**35 + 1, vocab=1000)
    b = traffic.requests(params, 2**35 + 1, vocab=1000)
    c = traffic.requests(params, 7, vocab=1000)
    assert [(x.arrival, x.max_new_tokens, x.prompt.tolist()) for x in a] == \
        [(x.arrival, x.max_new_tokens, x.prompt.tolist()) for x in b]
    assert [(x.arrival, len(x.prompt), x.max_new_tokens) for x in a] == \
        [(x.arrival, len(x.prompt), x.max_new_tokens) for x in c]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    assert all(len(x.prompt) + x.max_new_tokens <= params["max_total"]
               for x in a)
    assert a[0].arrival == 0.0 and len(a) == params["n"]


@pytest.mark.parametrize("mix", ["chat", "batch"])
def test_traffic_medians_as_stated(mix):
    params = dict(cells.load_json(BENCH / "traffic" / f"{mix}.json")
                  ["params"], n=20000, max_total=10**9, max_output=10**9)
    prompts, outs, gaps = traffic.shape(params)
    assert abs(np.median(prompts) / params["prompt_median"] - 1) < 0.03
    assert abs(np.median(outs) / params["output_median"] - 1) < 0.08
    if params["rate"] != "backlog":
        assert abs(1 / gaps.mean() / params["rate"] - 1) < 0.03
