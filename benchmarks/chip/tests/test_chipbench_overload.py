"""The cell ``minicpm3-4b.chat-overload`` at smoke width on the CPU:
``correct``, its traffic the chat mix arriving faster; and the bucket and
padding the engine's chunk spans report."""
import numpy as np
import pytest

from _chipbench_smoke import BENCH, cells, run, smoke_cell


@pytest.mark.parametrize("name", ["minicpm3-4b.chat-overload"])
def test_new_serve_cell_is_correct(name):
    res = run(smoke_cell(name), seconds=2.0)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}
    assert res["metrics"]["tokens_per_s"]["value"] > 0


def test_chat_overload_is_the_chat_mix_arriving_faster():
    from chipbench import traffic
    chat = cells.load_json(BENCH / "traffic" / "chat.json")["params"]
    over = cells.load_json(BENCH / "traffic" / "chat-overload.json")["params"]
    assert over["rate"] == 0.75 and over["rate"] / 0.6 == 1.25
    p0, o0, g0 = traffic.shape(chat)
    p1, o1, g1 = traffic.shape(over)
    assert (p0 == p1).all() and (o0 == o1).all()
    np.testing.assert_allclose(g1, g0 * 0.48 / 0.75)


def test_chunk_spans_carry_their_bucket_and_pad(tmp_path):
    """The ``bucket`` and ``pad`` arguments of the engine's
    ``engine.prefill_chunk`` spans in a real profiler trace are each
    chunk's bucket and the padding that fills it."""
    import jax
    from chipbench import serve
    from jax.profiler import ProfileData
    from repro.serving.engine import bucket_chunk
    cell = smoke_cell("minicpm3-4b.chat-overload")
    eng, cap, reqs = serve.setup(cell, 7)
    for r in reqs:
        r.arrival = 0.0
    jax.profiler.start_trace(str(tmp_path))
    iters, _ = serve.serve_loop(eng, cap, reqs[:6], 1.0)
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    args = [dict(e.stats) for pl in ProfileData.from_file(str(path)).planes
            for ln in pl.lines for e in ln.events
            if e.name == "dooly.engine.prefill_chunk"]
    chunks = [c for it in iters for c in it.chunks]
    assert len(args) == len(chunks) > 0
    size = cell.config["engine"]["chunk_size"]
    assert any(int(a["pad"]) > 0 for a in args)
    for a, (_, _, n, _) in zip(args, chunks):
        assert int(a["bucket"]) == bucket_chunk(n, size)
        assert int(a["pad"]) == int(a["bucket"]) - n
