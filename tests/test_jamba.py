"""AI21-Jamba2-3B (``jamba2-3b``) against a plain float32 reference.

The reference below is the published block written out in ``jax.numpy``
on the program's parameter layout: one sequence at a time, every layer in
order, the Mamba-1 recurrence a sequential ``lax.scan`` over positions,
every product at ``jax.default_matmul_precision("highest")``; no kernel,
cache, padding or batching.  At smoke width in float32 the program must
agree with it whichever way a prompt is fed: whole, in exact-length chunks,
or in chunks padded to the engine's buckets, then decoded through the
cache; and a cache row the engine hands from one request to the next must
give what a fresh engine gives.

Tolerances: the program and the reference compute in float32 and differ in
the order of their sums (the program's associative scan against the
sequential one, fused against unfused products).  Logits here are of order
10, and the two read up to 2e-4 apart; ``ATOL`` = 1e-3 with ``RTOL`` =
1e-4 leaves five times that, and stays far below what a stale state or an
unmasked pad moves (more than ``100 * ATOL``, the fault tests below).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.models import build_model
from repro.serving.engine import Engine
from repro.serving.scheduler import Request, SchedulerConfig

ATOL, RTOL = 1e-3, 1e-4
F32 = jnp.float32


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------

def _norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _layer(params, pattern, i):
    j, period = i % len(pattern), i // len(pattern)
    return jax.tree.map(lambda t: t[period].astype(F32), params["blocks"][j])


def _attention(cfg, p, h):
    s, hd = h.shape[0], cfg.resolved_head_dim
    q = (h @ p["q"]["w"]).reshape(s, cfg.n_heads, hd)
    k = (h @ p["k"]["w"]).reshape(s, cfg.n_kv_heads, hd)
    v = (h @ p["v"]["w"]).reshape(s, cfg.n_kv_heads, hd)
    group = cfg.n_heads // cfg.n_kv_heads
    k, v = jnp.repeat(k, group, 1), jnp.repeat(v, group, 1)
    logits = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    p_attn = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), -1)
    return jnp.einsum("hqk,khd->qhd", p_attn, v).reshape(s, -1) @ p["o"]["w"]


def _mixer(cfg, p, h):
    """Returns (y, (conv tail, SSM state)) after the last position."""
    s, kw, n = h.shape[0], cfg.ssm_conv, cfg.ssm_state
    u_raw, z = jnp.split(h @ p["in_proj"]["w"], 2, -1)
    u_pad = jnp.concatenate([jnp.zeros((kw - 1, u_raw.shape[1])), u_raw])
    u = jax.nn.silu(sum(u_pad[i:i + s] * p["conv_w"][i] for i in range(kw))
                    + p["conv_b"])
    dt_in, b, c = jnp.split(u @ p["x_proj"]["w"],
                            [cfg.resolved_dt_rank, cfg.resolved_dt_rank + n],
                            -1)
    dt_in = _norm(dt_in, p["dt_norm"]["scale"], cfg.norm_eps)
    b = _norm(b, p["b_norm"]["scale"], cfg.norm_eps)
    c = _norm(c, p["c_norm"]["scale"], cfg.norm_eps)
    dt = jax.nn.softplus(dt_in @ p["dt_w"] + p["dt_b"])
    a = -jnp.exp(p["A_log"])

    def step(state, inp):
        u_t, dt_t, b_t, c_t = inp
        state = jnp.exp(dt_t[:, None] * a) * state \
            + (dt_t * u_t)[:, None] * b_t[None, :]
        return state, state @ c_t + p["D"] * u_t

    state, y = jax.lax.scan(step, jnp.zeros(a.shape), (u, dt, b, c))
    return (y * jax.nn.silu(z)) @ p["out_proj"]["w"], (u_pad[s:], state)


def reference(cfg, params, tokens):
    """(logits (S, V), [(conv tail, SSM state) of each Mamba layer])."""
    pattern = build_model(cfg).pattern
    eps = cfg.norm_eps
    with jax.default_matmul_precision("highest"):
        table = params["embed"]["table"].astype(F32)
        x = table[jnp.asarray(tokens)]
        states = []
        for i in range(cfg.n_layers):
            lp = _layer(params, pattern, i)
            h = _norm(x, lp["ln1"]["scale"], eps)
            if cfg.layer_attends(i):
                x = x + _attention(cfg, lp["attn"], h)
            else:
                y, st = _mixer(cfg, lp["mamba"], h)
                x = x + y
                states.append(st)
            h = _norm(x, lp["ln2"]["scale"], eps)
            f = lp["ffn"]
            x = x + (jax.nn.silu(h @ f["gate"]["w"]) * (h @ f["up"]["w"])) \
                @ f["down"]["w"]
        x = _norm(x, params["final_norm"]["scale"], eps)
        return x @ table.T, states


# ---------------------------------------------------------------------------
# the program, fed three ways
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def jamba():
    cfg = get_smoke_config("jamba2-3b")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    # a non-trivial recurrence: dt biases and D away from the init's zeros
    # and ones, A spread as in Mamba's init
    for blk, desc in zip(params["blocks"], model.pattern):
        if desc.kind == "mamba":
            m = blk["mamba"]
            m["dt_b"] = jax.random.normal(jax.random.key(1), m["dt_b"].shape)
            m["D"] = jax.random.normal(jax.random.key(2), m["D"].shape)
            m["A_log"] = jnp.log(jnp.broadcast_to(
                jnp.arange(1.0, cfg.ssm_state + 1), m["A_log"].shape))
            m["conv_b"] = 0.1 * jax.random.normal(jax.random.key(3),
                                                  m["conv_b"].shape)
    tokens = np.asarray(jax.random.randint(jax.random.key(4), (45,), 0,
                                           cfg.vocab_size))
    ref_logits, ref_states = reference(cfg, params, tokens)
    return cfg, model, params, tokens, ref_logits, ref_states


def _mamba_states(model, cache, row=0):
    """(conv tail, SSM state) of each Mamba layer of cache row ``row``,
    in layer order."""
    p = len(model.pattern)
    out = []
    for i in range(model.cfg.n_layers):
        blk = cache["blocks"][i % p]
        if "h" in blk:
            out.append((blk["conv"][i // p, row], blk["h"][i // p, row]))
    return out


def _chunked(model, params, tokens, sizes, bucket):
    """Prefill ``tokens`` in chunks of ``sizes``, each padded to ``bucket``
    positions (exact length when 0).  Returns the logits after each chunk
    and the cache."""
    cache = model.zero_cache(1, 64, use_ring=False)
    start, logits = 0, []
    for n in sizes:
        width = bucket or n
        toks = np.zeros((1, width), np.int32)
        toks[0, :n] = tokens[start:start + n]
        lg, cache = model.prefill_chunk(
            params, cache, jnp.asarray(toks), jnp.asarray([start]),
            last_pos=jnp.asarray([n - 1]))
        start += n
        logits.append((start - 1, lg[0]))
    return logits, cache


def _close(got, want):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=ATOL, rtol=RTOL)


def test_jamba_layers_as_published():
    cfg = get_config("jamba2-3b")
    kinds = cfg.layer_kinds()
    assert [i for i, k in enumerate(kinds) if k != "mamba"] == [7, 21]
    pattern, periods = build_model(cfg).pattern, build_model(cfg).n_periods
    assert (len(pattern), periods) == (14, 2)
    assert cfg.rope_theta == 0 and cfg.ssm_ffn and cfg.ssm_dt_norms
    assert abs(cfg.param_count() / 1e9 - 3.03) < 0.01


@pytest.mark.parametrize("feed", ["whole", "exact_chunks", "padded_buckets",
                                  "padded_buckets_pallas"])
def test_prefill_matches_the_reference(jamba, feed, monkeypatch):
    cfg, model, params, tokens, ref_logits, ref_states = jamba
    s = len(tokens)
    if feed.endswith("_pallas"):
        # a TPU's path: the Pallas scan, here run by its interpreter
        from repro.kernels import ops as kops
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(kops, "_interpret", lambda: True)
    if feed == "whole":
        logits, _ = model.forward(params, {"tokens": jnp.asarray(tokens)[None]})
        _close(logits[0], ref_logits)
        _, cache = model.prefill(params, {"tokens": jnp.asarray(tokens)[None]},
                                 max_seq=64)
    else:
        sizes = [16, 16, 8, 5]
        assert sum(sizes) == s
        pairs, cache = _chunked(model, params, tokens, sizes,
                                16 if feed.startswith("padded") else 0)
        for pos, lg in pairs:
            _close(lg, ref_logits[pos])
    got = _mamba_states(model, cache)
    assert len(got) == len(ref_states) == 6
    for (conv, h), (ref_conv, ref_h) in zip(got, ref_states):
        _close(conv, ref_conv)
        _close(h, ref_h)


def test_decode_after_padded_chunks_matches_the_reference(jamba):
    cfg, model, params, tokens, ref_logits, _ = jamba
    prompt = 37
    _, cache = _chunked(model, params, tokens, [16, 16, 5], 16)
    for pos in range(prompt, len(tokens)):
        lg, cache = model.decode_step(params, cache,
                                      jnp.asarray([tokens[pos]]),
                                      jnp.asarray([pos]),
                                      active=jnp.asarray([True]))
        _close(lg[0], ref_logits[pos])


def test_padding_left_in_the_state_is_caught(jamba, monkeypatch):
    """The check above fails where the pads reach the state: the mixer
    told nothing of the chunk's real length."""
    from repro.models import mamba as mamba_mod
    cfg, model, params, tokens, ref_logits, ref_states = jamba
    orig = mamba_mod.mamba_mixer
    monkeypatch.setattr(mamba_mod, "mamba_mixer",
                        lambda *a, n_valid=None, **k: orig(*a, **k))
    pairs, cache = _chunked(model, params, tokens, [16, 16, 8, 5], 16)
    state = _mamba_states(model, cache)[0][1]
    assert float(jnp.max(jnp.abs(state - ref_states[0][1]))) > 100 * ATOL


# ---------------------------------------------------------------------------
# the engine: rows handed from request to request, rows between chunks
# ---------------------------------------------------------------------------

class Logged:
    """The logits the engine's step programs return, per call."""

    def __init__(self, eng):
        self.log = []
        eng._decode_fn = self._wrap(eng._decode_fn)
        eng._chunk_fns = {b: self._wrap(f) for b, f in eng._chunk_fns.items()}

    def _wrap(self, fn):
        def wrapped(*args):
            out = fn(*args)
            self.log.append(np.asarray(out[0]))
            return out
        return wrapped


def _serve(cfg, params, requests, rows):
    """Serve ``requests`` (prompt, outputs) all due at once; returns each
    request's logits in order: its last chunk's, then its decodes'."""
    eng = Engine(cfg, sched_config=SchedulerConfig(
        max_num_seqs=rows, max_batch_tokens=24, chunk_size=16,
        prefix_caching=False), max_seq=64, params=params)
    logged = Logged(eng)
    reqs = [Request(rid=i, arrival=0.0, prompt=list(map(int, p)),
                    max_new_tokens=n) for i, (p, n) in enumerate(requests)]
    for r in reqs:
        eng.sched.add_request(r)
    out = {r.rid: [] for r in reqs}
    while eng.sched.has_work():
        plan = eng.sched.schedule()
        first = len(logged.log)
        eng.execute(plan)
        for k, c in enumerate(plan.prefills):
            if c.start + c.length >= c.req.prompt_len:
                out[c.req.rid].append(logged.log[first + k][0])
        if plan.decodes:
            row = logged.log[first + len(plan.prefills)]
            for r in plan.decodes:
                out[r.rid].append(row[r.slot])
        eng.sched.complete_iteration(plan, 0.0)
    return [np.stack(out[r.rid]) for r in reqs]


def test_a_reused_row_gives_the_fresh_engines_result(jamba):
    cfg, _, params, tokens, _, _ = jamba
    first, second = (tokens[:30], 5), (tokens[7:45], 4)
    after_reuse = _serve(cfg, params, [first, second], rows=1)[1]
    fresh = _serve(cfg, params, [second], rows=1)[0]
    _close(after_reuse, fresh)


def test_a_row_between_chunks_keeps_its_state_through_decodes(jamba):
    """A request prefilled over several chunks while another decodes gives
    what it gives alone: the decode step leaves its state as it is."""
    cfg, _, params, tokens, _, _ = jamba
    short, long_ = (tokens[:6], 12), (tokens[3:45], 3)
    together = _serve(cfg, params, [short, long_], rows=2)[1]
    alone = _serve(cfg, params, [long_], rows=1)[0]
    _close(together, alone)


def test_a_row_left_unreset_inherits_the_last_requests_state(jamba,
                                                           monkeypatch):
    from repro.models.zoo import Model
    cfg, _, params, tokens, _, _ = jamba
    monkeypatch.setattr(Model, "reset_recurrent", lambda self, cache: cache)
    first, second = (tokens[:30], 5), (tokens[7:45], 4)
    after_reuse = _serve(cfg, params, [first, second], rows=1)[1]
    fresh = _serve(cfg, params, [second], rows=1)[0]
    assert float(np.max(np.abs(after_reuse - fresh))) > 100 * ATOL


def test_pallas_scan_gradient_is_the_chunked_scans():
    """On a TPU the mixer runs the forward-only Pallas kernel; its gradient
    is the chunked XLA scan's (checked here with the kernel interpreted)."""
    from repro.models import mamba as mamba_mod
    k = jax.random.split(jax.random.key(5), 6)
    b, s, di, n = 1, 16, 8, 4
    x = jax.random.normal(k[0], (b, s, di))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, s, di)))
    a = -jnp.exp(jax.random.normal(k[2], (di, n)))
    bc, cc = (jax.random.normal(kk, (b, s, n)) for kk in k[3:5])
    d, h0 = jnp.ones((di,)), jax.random.normal(k[5], (b, di, n))

    def loss(scan):
        return lambda *args: jnp.sum(scan(*args)[0] ** 2)
    args = (x, dt, a, bc, cc, d, h0)
    got = jax.grad(loss(mamba_mod._pallas_scan), argnums=(0, 1))(*args)
    want = jax.grad(loss(mamba_mod.selective_scan_chunked),
                    argnums=(0, 1))(*args)
    for g, w in zip(got, want):
        _close(g, w)


def test_profile_plan_of_jamba_measures_both_module_kinds():
    """``ProfileStore.plan`` + ``execute`` of the smoke configuration:
    every task measured, nothing quarantined, and the Mamba and the
    position-free attention modules timed in both phases."""
    from repro.api import ProfileStore
    from repro.core.profiler import QUICK_SWEEP
    with ProfileStore(hardware="tpu-v5e", oracle="tpu_analytical",
                      sweep=QUICK_SWEEP) as store:
        rep = store.execute(store.plan(get_smoke_config("jamba2-3b")))
        assert rep.measured == rep.n_tasks > 0 and rep.quarantined == 0
        rows = store.db.conn.execute(
            "SELECT s.op_name, m.phase FROM measurements m JOIN signatures s"
            " ON s.hash = m.sig_hash").fetchall()
    for module in ("mamba", "self_attn"):
        assert {p for op, p in rows if op == module} == {"prefill", "decode"}
