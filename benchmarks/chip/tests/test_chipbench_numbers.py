"""flops.py against hand counts, and the traffic generator's seeds."""
import numpy as np
import pytest

from _chipbench_smoke import BENCH, cells
from chipbench import flops, traffic


def arch(name):
    return cells.config_module(name).arch(
        cells.load_json(BENCH / "configs" / f"{name}.json"))


def test_minicpm3_flops_by_hand():
    a = arch("minicpm3-4b")
    # q_down 2560x768, q_up 768x40x96, kv_down 2560x288, k_up+v_up
    # 256x40x128, o 2560x2560, SwiGLU 3x2560x6400
    per_layer = (2560 * 768 + 768 * 40 * 96 + 2560 * 288 + 256 * 40 * 128
                 + 40 * 64 * 2560 + 3 * 2560 * 6400)
    assert per_layer == 62_668_800 == a.layer_weights(0)
    assert flops.matmul_params(a) == 62 * per_layer
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
    assert per_layer == 218_103_808 == a.layer_weights(0)
    assert flops.matmul_params(a) == 16 * per_layer
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


# ---------------------------------------------------------------------------
# the numbers of the harness before each configuration brought its own
# file (``tests/data/parity.json``, computed by that harness): FLOP
# integers, ``served_gaps`` on seeded arrays, ``call_point`` decisions
# ---------------------------------------------------------------------------

PARITY = cells.load_json(BENCH / "tests" / "data" / "parity.json")


@pytest.mark.parametrize("name", ["minicpm3-4b", "command-r7b"])
def test_flops_as_before(name):
    a = arch(name)
    chunks = [tuple(c) for c in PARITY["inputs"]["chunks"]]
    decodes = PARITY["inputs"]["decodes"]
    want = PARITY["flops"][name]
    assert flops.iteration_flops(a, chunks, decodes) == want["iteration"]
    assert [flops.chunk_flops(a, *c) for c in chunks] == want["per_chunk"]
    assert [flops.token_flops(a, c) + flops.head_flops(a)
            for c in decodes] == want["per_decode"]


@pytest.mark.parametrize("cell_name", ["minicpm3-4b.chat",
                                       "command-r7b.batch"])
def test_served_gaps_as_before(cell_name):
    """The float32 reference and its fp8 control at smoke width, on the
    same seeded sequences and weights (equal here to the last bit;
    ``atol`` leaves room for another CPU's summation order)."""
    from _chipbench_smoke import smoke_cell
    from chipbench import reference, weights
    cell = smoke_cell(cell_name)
    cfg = cells.model_config(cell.config)
    inp = PARITY["inputs"]
    rng = np.random.default_rng(inp["gap_rng"])
    seqs = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            for n in inp["gap_lens"]]
    served = [rng.integers(0, cfg.vocab_size, n - s).astype(np.int32)
              for n, s in zip(inp["gap_lens"], inp["gap_starts"])]
    params = weights.make_params(cfg, inp["weights_seed"])
    got = reference.served_gaps(cell.arch, params, seqs, inp["gap_starts"],
                                served, control="fp8")
    want = PARITY["gaps"][cell.config["name"]]
    assert set(got) == set(want)
    for key in want:
        np.testing.assert_allclose(got[key], want[key], rtol=0, atol=1e-6)


def test_call_point_decisions_as_before():
    """Every operand shape of a full-width minicpm3-4b pass, and each with
    one size doubled, judged as before."""
    from chipbench import profile
    cell = cells.find_cell(cells.load_benchmark(), "minicpm3-4b.profile")
    a, sweep = cell.arch, profile.sweep_config(cell.traffic)
    decisions = PARITY["call_point"]["minicpm3-4b"]
    assert len(decisions) == 352 and sum(d[1] for d in decisions) == 59
    for (kind, params, ins), ok, point in decisions:
        shapes = (kind, tuple((p, tuple(s)) for p, s in params),
                  tuple(tuple(x) for x in ins))
        got_ok, got_point = profile.call_point(a, sweep, shapes)
        assert (got_ok, list(got_point) if got_point else None) == \
            (ok, point), shapes


def _gqa_call(window, ctx, toks, lengths, seed=0):
    """The program's attention module context for a command-r7b layer at
    smoke width in float32, and float32 operands for it."""
    from _chipbench_smoke import smoke_cell
    import jax
    import jax.numpy as jnp
    from repro.serving import context
    cell = smoke_cell("command-r7b.batch")
    conf = dict(cell.config, sliding_window=window)
    conf["overrides"] = dict(conf["overrides"], sliding_window=window,
                             dtype="float32")
    cfg = cells.model_config(conf)
    phase = "decode" if toks == 1 else "prefill"
    mc = context.build_context(cfg, "self_attn", phase=phase,
                               window=window)
    args = mc.input_spec(toks, len(lengths), ctx)
    keys = jax.random.split(jax.random.key(seed), 8)
    params = jax.tree.map(
        lambda s: jax.random.normal(keys[0], s.shape, jnp.float32)
        / np.sqrt(s.shape[0]), mc.params)
    x, k, v = (jax.random.normal(kk, s.shape, jnp.float32)
               for kk, s in zip(keys[1:], args[:3]))
    n = jnp.asarray(lengths, jnp.int32)
    return cell.model.arch(conf), mc.fn, (params, x, k, v, n)


@pytest.mark.parametrize("window,ctx,toks,lengths", [
    (0, 64, 8, [0, 0]),          # a chunk, as the profile times it
    (16, 64, 8, [5, 30]),        # a chunk past the window of its cache
    (0, 64, 1, [0, 40]),         # one token, global layer
    (16, 64, 1, [3, 40]),        # one token, a ring of the window's slots
                                 # (16), wrapped on the second row
])
def test_gqa_module_reference_is_the_published_mask(window, ctx, toks,
                                                    lengths):
    """command-r7b's module reference (q - W < k <= q, over a chunk's
    cache and over the decode context's ring) against the program's
    module, both in float32: where the ring holds the window, they
    agree."""
    a, fn, args = _gqa_call(window, ctx, toks, lengths)
    want = a.module_reference(args, window)
    got = fn(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
