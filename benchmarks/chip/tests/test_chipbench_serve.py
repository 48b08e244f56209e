"""The serve driver rehearsed at smoke width on the CPU, and ``correct``
coming out false with the timed path broken underneath."""
import pytest

from _chipbench_smoke import run, smoke_cell


@pytest.mark.parametrize("name", ["command-r7b.batch", "minicpm3-4b.chat"])
def test_serve_run_is_correct_and_reports_its_metrics(name):
    cell = smoke_cell(name)
    res = run(cell)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {m.name for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"


def test_traced_serve_run_reports_per_layer_metrics():
    cell = smoke_cell("minicpm3-4b.chat")
    res = run(cell, seconds=3.0, traced=True)
    assert res["correct"], res["checks"]
    # the CPU has no device plane: the idle share and the decode
    # program's device time are left out, not 0
    want = {m.name for m in cell.per_layer} - {
        "device_idle_share.chat", "engine.decode_device_ms.chat"}
    assert res["metrics"]["engine.host_ms_per_iter.chat"]["value"] > 0
    assert set(res["metrics"]) == want
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def _negate(out, args):
    logits, cache = out
    return -logits, cache


def _state_unchanged(out, args):
    return out[0], args[1]


def _half_batch(out, args):
    logits, cache = out
    half = logits.shape[0] // 2
    return logits.at[half:].set(0.0), cache


@pytest.mark.parametrize("fault", [_negate, _state_unchanged, _half_batch],
                         ids=["token_altered", "state_unchanged",
                              "half_batch_left_out"])
def test_broken_decode_step_is_not_correct(monkeypatch, fault):
    from repro.models.zoo import Model
    orig = Model.decode_step

    def broken(self, params, cache, *a, **k):
        return fault(orig(self, params, cache, *a, **k), (params, cache))
    monkeypatch.setattr(Model, "decode_step", broken)
    res = run(smoke_cell("command-r7b.batch"))
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]


def test_altered_first_token_is_not_correct(monkeypatch):
    from repro.models.zoo import Model
    orig = Model.prefill_chunk

    def broken(self, *a, **k):
        logits, cache = orig(self, *a, **k)
        return -logits, cache
    monkeypatch.setattr(Model, "prefill_chunk", broken)
    res = run(smoke_cell("minicpm3-4b.chat"))
    assert not res["correct"]


@pytest.mark.parametrize("name", ["command-r7b.batch", "minicpm3-4b.chat"])
def test_lower_precision_reference_in_the_programs_place_is_not_correct(
        monkeypatch, name):
    """The control: each judged request's tokens replaced by those the
    reference puts first with every matrix product on fp8 operands, at the
    same positions of the same prompts."""
    import numpy as np
    from chipbench import reference, serve
    orig = serve.check

    def control(run, served, chosen, params):
        a = run.arch
        served = dict(served)
        seqs = [np.concatenate([np.asarray(r.prompt, np.int32),
                                np.asarray(serve.decode_inputs(r.generated),
                                           np.int32)]) for r in chosen]
        hidden = reference.forward_hidden(a, params, seqs, "fp8")
        for r, h in zip(chosen, hidden):
            rows = h[r.prompt_len - 1:r.prompt_len - 1 + r.generated]
            toks = reference._argmax(reference.head(params, rows, a=a,
                                                    quant="fp8"))
            served[r.rid] = dict(enumerate(np.asarray(toks).tolist()))
        return orig(run, served, chosen, params)
    monkeypatch.setattr(serve, "check", control)
    res = run(smoke_cell(name))
    assert not res["correct"]
    assert res["checks"]["requests_missing_tokens"]["value"] == 0
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]


def test_step_calls_out_of_line_with_the_plan_stop_the_run(monkeypatch):
    """Served tokens are read by call order: an engine that makes another
    number of step-program calls than its plan has chunks and a decode
    stops the run instead of scoring misaligned tokens."""
    from chipbench import serve
    orig = serve.Capture._wrap

    def doubled(self, fn):
        wrapped = orig(self, fn)

        def twice(*args):
            out = wrapped(*args)
            self.log.append(self.log[-1])
            return out
        return twice
    monkeypatch.setattr(serve.Capture, "_wrap", doubled)
    with pytest.raises(RuntimeError, match="cannot be read"):
        run(smoke_cell("minicpm3-4b.chat"))
