"""Jitted sampling head + self-speculative decode
(``serve/sampling.py``, ``serve/speculative.py`` and their engine/step
integration).

Pins the contracts ISSUE 9 landed:

* **Greedy is the oracle** — ``temperature=0`` through the fused
  sampling head is token-identical (bitwise argmax) to the engine
  default, and logprobs ride along without changing selection.
* **Sampling is layout-independent** — a request's stream depends only
  on ``(seed, uid, position)``: identical across runs, and identical
  whether the lane decodes through the B=1 solo step or the full-width
  batch step.
* **Top-k / top-p mass properties** on :func:`select_tokens` directly —
  fixed cases always, hypothesis sweeps when available.
* **Nothing vocab-sized leaves the jit** — the step returns ``[B, C]``
  int32 tokens, dead columns carry ``DEAD_TOKEN``, and a whole round
  reaches the device through exactly ONE attributed step dispatch
  (the stray post-step ``jnp.argmax`` this PR killed would show up as
  either a second dispatch or a ``[B, C, V]`` output).
* **Self-speculative greedy is token-identical** to plain greedy at
  every k, verify grants draw on the round prefill budget, and EOS
  truncates acceptance (the EOS contract: the eos token IS emitted,
  then generation stops — mid-chunk and at a chunk boundary alike).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serve import sampling as samplib
from repro.serve import speculative
from repro.serve import steps as serve_steps
from repro.serve.engine import Request, ServeEngine
from repro.serve.sampling import DEAD_TOKEN, SamplingParams, select_tokens
from repro.serve.scheduler import FifoScheduler, SchedulerConfig

PAGE = 8
SLOTS = 4
MAX_LEN = 48


def _engine(cfg, params, **kw):
    kw.setdefault("slots", SLOTS)
    return ServeEngine(cfg, params, max_len=MAX_LEN, page_size=PAGE, **kw)


def _batch_only_steps(cfg):
    """Step set with the solo lane stripped — forces every round through
    the full-width batch step (layout-invariance tests)."""
    full = serve_steps.build_paged_steps(
        cfg, page=PAGE, n_pages=serve_steps.default_n_pages(
            SLOTS, MAX_LEN // PAGE),
        max_slots=SLOTS, max_pages_per_seq=MAX_LEN // PAGE)
    return dataclasses.replace(full, solo_step=None)


def _reqs(n=3, max_new=8, seed=13, vocab=64, sampling=None):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(2, vocab, int(L)).astype(np.int32),
                    max_new_tokens=max_new, sampling=sampling)
            for i, L in enumerate(rng.integers(5, 14, size=n))]


def _rep_reqs(n=3, max_new=12, seed=29, vocab=64):
    """Repetitive prompts so the prompt-lookup draft actually fires."""
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=np.tile(rng.integers(2, vocab, 4),
                                   4).astype(np.int32),
                    max_new_tokens=max_new)
            for i in range(n)]


# ==========================================================================
# greedy oracle + logprobs
# ==========================================================================
def test_temperature_zero_is_greedy_oracle(serve_cfg, serve_params):
    base = _engine(serve_cfg, serve_params).run(_reqs())
    sp = SamplingParams(temperature=0.0, logprobs=True)
    out = _engine(serve_cfg, serve_params).run(_reqs(sampling=sp))
    assert [r.out_tokens for r in out] == [r.out_tokens for r in base]
    for r in out:
        assert len(r.out_logprobs) == len(r.out_tokens)
        assert all(lp <= 0.0 for lp in r.out_logprobs)
    for r in base:                       # logprobs only on request
        assert r.out_logprobs == []


def test_fixed_seed_determinism_across_runs(serve_cfg, serve_params):
    sp = SamplingParams(temperature=0.9, seed=5, logprobs=True)
    a = _engine(serve_cfg, serve_params).run(_reqs(sampling=sp))
    b = _engine(serve_cfg, serve_params).run(_reqs(sampling=sp))
    assert [r.out_tokens for r in a] == [r.out_tokens for r in b]
    assert [r.out_logprobs for r in a] == [r.out_logprobs for r in b]
    greedy = _engine(serve_cfg, serve_params).run(_reqs())
    assert [r.out_tokens for r in a] != [r.out_tokens for r in greedy]


def test_sampled_stream_is_layout_independent(serve_cfg, serve_params):
    """One request, solo lane vs full-width batch step: the PRNG stream
    keys on (seed, uid, position) only, so the drawn tokens must match
    across batch layouts bit for bit."""
    sp = SamplingParams(temperature=0.8, seed=3)
    def one():
        return [Request(uid=7, prompt=np.arange(2, 12, dtype=np.int32),
                        max_new_tokens=8, sampling=sp)]
    solo = _engine(serve_cfg, serve_params)
    out_s = solo.run(one())
    batch = _engine(serve_cfg, serve_params,
                    step_set=_batch_only_steps(serve_cfg))
    out_b = batch.run(one())
    assert solo.stats.solo_rounds > 0 and batch.stats.solo_rounds == 0
    assert out_s[0].out_tokens == out_b[0].out_tokens


def test_sampling_params_validation():
    with pytest.raises(ValueError):
        SamplingParams(temperature=-0.1)
    with pytest.raises(ValueError):
        SamplingParams(top_k=-1)
    with pytest.raises(ValueError):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError):
        SamplingParams(top_p=1.5)


# ==========================================================================
# select_tokens: the pure head, called directly
# ==========================================================================
def _head(logits, *, temp=1.0, top_k=0, top_p=1.0, seed=0, n_new=None):
    b, c, _ = logits.shape
    pos = np.broadcast_to(np.arange(c, dtype=np.int32), (b, c))
    key = np.stack([samplib.request_key(seed, u) for u in range(b)])
    return select_tokens(
        jnp.asarray(logits), jnp.full(b, temp, jnp.float32),
        jnp.full(b, top_k, jnp.int32), jnp.full(b, top_p, jnp.float32),
        jnp.asarray(key), jnp.asarray(pos),
        jnp.asarray(n_new if n_new is not None
                    else np.full(b, c, np.int32)))


def test_head_greedy_matches_argmax_bitwise(rng):
    lg = rng.standard_normal((3, 5, 32)).astype(np.float32)
    tok, logp = _head(lg, temp=0.0)
    np.testing.assert_array_equal(np.asarray(tok), lg.argmax(-1))
    want = jax.nn.log_softmax(jnp.asarray(lg), axis=-1)
    got = np.take_along_axis(np.asarray(want), lg.argmax(-1)[..., None],
                             axis=-1)[..., 0]
    np.testing.assert_array_equal(np.asarray(logp), got)


def test_head_dead_columns_are_sentinel(rng):
    lg = rng.standard_normal((2, 4, 16)).astype(np.float32)
    tok, logp = _head(lg, temp=0.0, n_new=np.array([2, 0], np.int32))
    tok, logp = np.asarray(tok), np.asarray(logp)
    assert (tok[0, 2:] == DEAD_TOKEN).all() and (tok[1] == DEAD_TOKEN).all()
    assert (logp[0, 2:] == 0.0).all() and (logp[1] == 0.0).all()
    assert (tok[0, :2] == lg[0, :2].argmax(-1)).all()
    assert tok.dtype == np.int32 and logp.dtype == np.float32


def _topk_ok(lg_row, k, tok):
    return lg_row[tok] >= np.sort(lg_row)[-k]


def _topp_ok(lg_row, p, temp, tok):
    scaled = lg_row / temp
    probs = np.exp(scaled - scaled.max())
    probs /= probs.sum()
    order = np.argsort(-scaled)
    n_keep = int(np.sum(np.cumsum(probs[order]) < p)) + 1
    return tok in order[:n_keep]


def test_head_top_k_membership(rng):
    lg = rng.standard_normal((4, 6, 64)).astype(np.float32)
    for k in (1, 3, 8):
        tok = np.asarray(_head(lg, temp=0.7, top_k=k, seed=k)[0])
        for b in range(4):
            for c in range(6):
                assert _topk_ok(lg[b, c], k, tok[b, c])
    # k=1 at any temperature IS greedy
    tok1 = np.asarray(_head(lg, temp=5.0, top_k=1)[0])
    np.testing.assert_array_equal(tok1, lg.argmax(-1))


def test_head_top_p_nucleus_membership(rng):
    lg = (3.0 * rng.standard_normal((4, 6, 64))).astype(np.float32)
    for p in (0.1, 0.5, 0.9):
        tok = np.asarray(_head(lg, temp=0.7, top_p=p, seed=int(p * 10))[0])
        for b in range(4):
            for c in range(6):
                assert _topp_ok(lg[b, c], p, 0.7, tok[b, c])


def test_head_hypothesis_mass_properties():
    pytest.importorskip(
        "hypothesis", reason="property tests need hypothesis "
        "(requirements-dev)")
    from hypothesis import given, settings
    import hypothesis.strategies as st

    @settings(max_examples=30, deadline=None)
    @given(st.integers(0, 2 ** 31 - 1), st.integers(1, 16),
           st.floats(0.05, 1.0), st.floats(0.2, 3.0))
    def prop(data_seed, k, p, temp):
        r = np.random.default_rng(data_seed)
        lg = (3.0 * r.standard_normal((2, 3, 32))).astype(np.float32)
        tok = np.asarray(_head(lg, temp=temp, top_k=k, top_p=p,
                               seed=data_seed % 97)[0])
        for b in range(2):
            for c in range(3):
                assert _topk_ok(lg[b, c], k, tok[b, c])
                assert _topp_ok(lg[b, c], p, temp, tok[b, c])

    prop()


# ==========================================================================
# nothing vocab-sized leaves the jit
# ==========================================================================
def test_step_returns_tokens_not_logits(serve_cfg, serve_params):
    """Direct step call: outputs are [B, C] int32 / float32 — no vocab
    axis crosses the boundary — an idle lane reads DEAD_TOKEN, and a
    prefill lane selects a token only at its last column (the one it
    emits from; the head runs on the last ``select_width`` columns)."""
    eng = _engine(serve_cfg, serve_params)
    eng.run([Request(uid=0, prompt=np.arange(2, 6, dtype=np.int32),
                     max_new_tokens=1)])     # materializes pool + arena
    steps, arena = eng._steps, eng._arena
    c = steps.chunk
    toks = jnp.zeros((SLOTS, c), jnp.int32) + 2
    n_new = jnp.asarray([c, 0, 0, 0], jnp.int32)
    samp = {k: jnp.asarray(v)
            for k, v in samplib.lane_inputs(SLOTS).items()}
    tok, logp, _ = steps.step(eng._exec_params, toks, arena,
                              jnp.zeros(SLOTS, jnp.int32), n_new, samp)
    assert tok.shape == (SLOTS, c) and tok.dtype == jnp.int32
    assert logp.shape == (SLOTS, c) and logp.dtype == jnp.float32
    tok = np.asarray(tok)
    assert (tok[1:] == DEAD_TOKEN).all()
    assert steps.select_width == 1
    assert (tok[0, :-1] == DEAD_TOKEN).all()
    assert 0 <= tok[0, -1] < serve_cfg.vocab
    assert (np.asarray(logp)[0, :-1] == 0.0).all()


def test_one_attributed_dispatch_per_round(serve_cfg, serve_params):
    """The regression this PR exists for: token selection is fused into
    the compiled step, so a round issues exactly ONE attributed device
    dispatch — the stray out-of-jit argmax would break this count."""
    from repro.obs import costs as obs_costs
    prev = obs_costs.enable_capture()
    try:
        eng = _engine(serve_cfg, serve_params, slots=2)
        eng.run(_reqs())
        rep = eng.last_cost_report
        step_rows = [r for r in rep.fns if r.fn in ("step", "solo_step")]
        assert sum(r.calls for r in step_rows) == eng.stats.rounds
    finally:
        obs_costs.enable_capture(prev)


# ==========================================================================
# self-speculative decode
# ==========================================================================
def test_propose_prompt_lookup():
    h = np.array([5, 6, 7, 8, 5, 6, 7], np.int32)
    np.testing.assert_array_equal(speculative.propose(h, 3), [8, 5, 6])
    assert speculative.propose(np.array([1, 2, 3], np.int32), 4).size == 0
    assert speculative.propose(h, 0).size == 0


def test_accept_greedy_prefix():
    d = np.array([4, 5, 6], np.int32)
    assert speculative.accept_greedy(d, np.array([4, 5, 6, 7])) == 4
    assert speculative.accept_greedy(d, np.array([4, 9, 1, 2])) == 2
    assert speculative.accept_greedy(d, np.array([9, 9, 9, 9])) == 1
    assert speculative.accept_greedy(d, np.array([4])) == 1


@pytest.mark.parametrize("k", [2, 4])
def test_speculative_greedy_token_identical(serve_cfg, serve_params, k):
    base = _engine(serve_cfg, serve_params).run(_rep_reqs())
    spec = _engine(serve_cfg, serve_params, speculative_k=k)
    out = spec.run(_rep_reqs())
    assert [r.out_tokens for r in out] == [r.out_tokens for r in base]
    s = spec.stats
    assert s.spec_rounds > 0 and s.spec_draft_tokens > 0
    assert 0.0 <= s.spec_acceptance_rate <= 1.0
    assert s.spec_accepted_tokens <= s.spec_draft_tokens


def test_speculative_sampled_lanes_fall_back(serve_cfg, serve_params):
    """temperature > 0 lanes never verify (no rejection sampling yet) —
    the run completes with zero speculative rounds and stays equal to
    the non-speculative sampled stream."""
    sp = SamplingParams(temperature=0.9, seed=2)
    reqs = lambda: [dataclasses.replace(r, sampling=sp)
                    for r in _rep_reqs()]
    plain = _engine(serve_cfg, serve_params).run(reqs())
    spec = _engine(serve_cfg, serve_params, speculative_k=4)
    out = spec.run(reqs())
    assert spec.stats.spec_rounds == 0
    assert [r.out_tokens for r in out] == [r.out_tokens for r in plain]


def test_grant_verify_draws_on_round_budget():
    sched = FifoScheduler(SchedulerConfig(chunk=6, max_prefill_tokens=8))
    sched.start_round()
    assert sched.grant_chunk(6) == 6      # first grant, budget -> 2
    assert sched.grant_verify(4) == 2     # clamped to what is left
    assert sched.grant_verify(4) == 0     # exhausted
    sched.start_round()
    assert sched.grant_verify(30) == 8    # no first-grant exemption
    assert sched.grant_verify(1) == 0


# ==========================================================================
# EOS contract: emitted, then stop — all paths agree
# ==========================================================================
def _learned_eos_run(cfg, params, prompt_len, *, idx, max_new=10, **kw):
    """Run greedy once, pick the ``idx``-th generated token as eos_id,
    re-run: output must be the baseline truncated just past that token's
    FIRST occurrence (the eos is emitted, nothing follows)."""
    prompt = np.arange(2, 2 + prompt_len, dtype=np.int32)
    def one(eos=None):
        return [Request(uid=0, prompt=prompt, max_new_tokens=max_new,
                        eos_id=eos)]
    base = ServeEngine(cfg, params, slots=2, max_len=MAX_LEN,
                       page_size=PAGE, **kw).run(one())[0].out_tokens
    eos = base[idx]
    streamed = []
    out = ServeEngine(cfg, params, slots=2, max_len=MAX_LEN,
                      page_size=PAGE, **kw).run(
        one(eos), on_token=lambda s, t, r: streamed.append(int(t)))
    got = out[0].out_tokens
    assert got == base[:base.index(eos) + 1]
    assert got[-1] == eos                 # emitted, not swallowed
    assert streamed == got                # on_token saw the eos too
    return base


def test_eos_emitted_then_stop_mid_chunk(serve_cfg, serve_params):
    # prompt ends mid-page (11 % 8 != 0): the first token comes from a
    # chunk whose last column is mid-chunk
    _learned_eos_run(serve_cfg, serve_params, 11, idx=3)


def test_eos_emitted_then_stop_at_chunk_boundary(serve_cfg, serve_params):
    # chunk_tokens=8 and a 16-token prompt: the final prefill chunk ends
    # exactly at the chunk boundary, then eos at the very first token
    _learned_eos_run(serve_cfg, serve_params, 16, idx=0, chunk_tokens=8)


def test_eos_truncates_speculative_acceptance(serve_cfg, serve_params):
    """Speculative greedy with an eos learned from the baseline: still
    token-identical, and nothing ever follows the eos even when the
    verify step accepted a longer prefix."""
    base = _engine(serve_cfg, serve_params).run(_rep_reqs(n=1))
    toks = base[0].out_tokens
    eos = toks[len(toks) // 2]
    def one(eos_id):
        r = _rep_reqs(n=1)[0]
        return [dataclasses.replace(r, eos_id=eos_id)]
    plain = _engine(serve_cfg, serve_params).run(one(eos))
    spec = _engine(serve_cfg, serve_params, speculative_k=4)
    out = spec.run(one(eos))
    assert out[0].out_tokens == plain[0].out_tokens
    assert out[0].out_tokens[-1] == eos
    assert eos not in out[0].out_tokens[:-1]
