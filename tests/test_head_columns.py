"""The LM head and token selection run only on the columns a lane can emit
from (``serve/steps.py:_step_body``).

A lane emits from its last ``W = min(C, select_width)`` live columns at
most, ``select_width = 1 + speculative_k``: a prefill lane from its last
column, a verify lane from its ``1 + k``, a decode lane from its one. The
oracle is the epilogue the step used to run, built here only: the head of
``model.forward`` over every column, then ``select_tokens`` over all of
them. Inside each lane's window the step must select what the oracle
selects; every other column reads ``DEAD_TOKEN`` and logprob 0.0.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.model import forward
from repro.serve import sampling as samplib
from repro.serve import steps as serve_steps
from repro.serve.engine import Request, ServeEngine
from repro.serve.paged_kv import PagedKVPool
from repro.serve.sampling import DEAD_TOKEN, SamplingParams

PAGE = 8
SLOTS = 4
MAX_LEN = 48
MPPS = MAX_LEN // PAGE
CHUNK = 8
K = 2                      # speculative_k: the window is 1 + K columns


def _steps(cfg, select_width):
    return serve_steps.build_paged_steps(
        cfg, page=PAGE, n_pages=serve_steps.default_n_pages(SLOTS, MPPS),
        max_slots=SLOTS, max_pages_per_seq=MPPS, chunk=CHUNK,
        select_width=select_width)


def _oracle(cfg):
    """The old epilogue: the head at every column, selection at all."""
    def run(params, tokens, arena, start, n_new, samp):
        c = tokens.shape[1]
        positions = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None]
        logits, _, _ = forward(cfg, params, tokens, positions=positions,
                               cache=arena, valid_len=start + n_new)
        return samplib.select_tokens(logits, samp["temp"], samp["top_k"],
                                     samp["top_p"], samp["key"], positions,
                                     n_new)
    return jax.jit(run)


def _arena(cfg, lengths, seed=0):
    """An arena whose slots own the pages their ``lengths`` cover, filled
    with noise so that every lane attends over real values."""
    pool = PagedKVPool(cfg, n_pages=serve_steps.default_n_pages(SLOTS, MPPS),
                       page=PAGE, max_slots=SLOTS, max_pages_per_seq=MPPS,
                       cache_dtype=jnp.float32)
    for s, n in enumerate(lengths):
        pool.ensure(s, n)
    arena = pool.install_tables(pool.init_arena())
    key = jax.random.PRNGKey(seed)
    leaves, tree = jax.tree_util.tree_flatten_with_path(arena)
    return jax.tree_util.tree_unflatten(tree, [
        jax.random.normal(jax.random.fold_in(key, i), leaf.shape, leaf.dtype)
        if path[-1].key.endswith("_pages") else leaf
        for i, (path, leaf) in enumerate(leaves)])


def _round(cfg, c, n_new, start, sampled_lane=None, seed=5):
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(2, cfg.vocab, (SLOTS, c)), jnp.int32)
    samp = samplib.lane_inputs(SLOTS)
    if sampled_lane is not None:
        samplib.set_lane(samp, sampled_lane,
                         SamplingParams(temperature=0.8, seed=9), uid=3)
    samp = {k: jnp.asarray(v) for k, v in samp.items()}
    return (tokens, jnp.asarray(start, jnp.int32),
            jnp.asarray(n_new, jnp.int32), samp)


def _window(n, w):
    """Columns of a lane's window that are live."""
    off = max(n - w, 0)
    return [t for t in range(off, off + w) if t < n]


def test_mixed_round_matches_the_old_epilogue(serve_cfg, serve_params):
    """One prefill lane (sampled), one verify lane, one decode lane and
    one idle lane in a chunk-wide round."""
    w = 1 + K
    n_new = [CHUNK - 3, 1 + K, 1, 0]
    start = [0, 11, 20, 0]
    steps = _steps(serve_cfg, w)
    toks, start_d, n_d, samp = _round(serve_cfg, CHUNK, n_new, start,
                                      sampled_lane=0)
    lengths = [s + n for s, n in zip(start, n_new)]
    want_tok, want_logp = _oracle(serve_cfg)(
        serve_params, toks, _arena(serve_cfg, lengths), start_d, n_d, samp)
    tok, logp, _ = steps.step(serve_params, toks,
                              _arena(serve_cfg, lengths), start_d, n_d,
                              samp)
    tok, logp = np.asarray(tok), np.asarray(logp)
    want_tok, want_logp = np.asarray(want_tok), np.asarray(want_logp)
    assert tok.shape == logp.shape == (SLOTS, CHUNK)
    for b, n in enumerate(n_new):
        live = _window(n, w)
        dead = [t for t in range(CHUNK) if t not in live]
        np.testing.assert_array_equal(tok[b, live], want_tok[b, live])
        np.testing.assert_allclose(logp[b, live], want_logp[b, live],
                                   atol=1e-5, rtol=0)
        assert (tok[b, dead] == DEAD_TOKEN).all()
        assert (logp[b, dead] == 0.0).all()
    # the emitting columns are the ones the engine reads
    assert tok[0, n_new[0] - 1] >= 0
    assert (tok[1, :1 + K] >= 0).all() and tok[2, 0] >= 0
    assert (tok[3] == DEAD_TOKEN).all()


def test_decode_step_is_the_old_epilogue_bit_for_bit(serve_cfg,
                                                     serve_params):
    """At C = 1 the window is the whole step: nothing is gathered."""
    steps = _steps(serve_cfg, 1 + K)
    n_new, start = [1, 1, 0, 1], [7, 30, 0, 12]
    toks, start_d, n_d, samp = _round(serve_cfg, 1, n_new, start,
                                      sampled_lane=1)
    lengths = [s + n for s, n in zip(start, n_new)]
    want = _oracle(serve_cfg)(serve_params, toks,
                              _arena(serve_cfg, lengths), start_d, n_d,
                              samp)
    got = steps.step(serve_params, toks, _arena(serve_cfg, lengths),
                     start_d, n_d, samp)[:2]
    for g, e in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(e))


def _sort_columns(hlo: str, vocab: int) -> set:
    """Column counts of the ``[B, cols, vocab]`` operands of every sort."""
    cols = set()
    for line in hlo.splitlines():
        if re.search(r'stablehlo\.sort"|@sort\(', line):
            cols.update(int(m) for m in re.findall(
                rf"tensor<\d+x(\d+)x{vocab}xf32>", line))
    return cols


def test_prefill_width_step_sorts_only_the_window(serve_cfg, serve_params):
    steps = _steps(serve_cfg, 1 + K)
    toks, start, n_new, samp = _round(serve_cfg, CHUNK, [CHUNK] * SLOTS,
                                      [0] * SLOTS)
    arena = _arena(serve_cfg, [CHUNK] * SLOTS)
    hlo = steps.step._fn.lower(serve_params, toks, arena, start, n_new,
                               samp).as_text()
    cols = _sort_columns(hlo, serve_cfg.vocab)
    assert cols == {1 + K}, cols


@pytest.mark.parametrize("k", [0, K])
def test_head_rows_counts_rows_times_width(serve_cfg, serve_params, k):
    """``EngineStats.head_rows`` is rows x min(C, 1 + k) summed over every
    launched step; speculative greedy stays plain greedy's tokens."""
    def reqs():
        rng = np.random.default_rng(29)
        return [Request(uid=i, prompt=np.tile(rng.integers(2, 64, 4),
                                              4).astype(np.int32),
                        max_new_tokens=10) for i in range(3)]

    built = ServeEngine(serve_cfg, serve_params, slots=SLOTS,
                        max_len=MAX_LEN, page_size=PAGE, chunk_tokens=CHUNK,
                        speculative_k=k)._build_steps()
    shapes = []

    def seen(fn):
        def call(params, tokens, *a):
            shapes.append(tokens.shape)
            return fn(params, tokens, *a)
        return call

    traced = dataclasses.replace(built, step=seen(built.step),
                                 solo_step=seen(built.solo_step))
    eng = ServeEngine(serve_cfg, serve_params, slots=SLOTS, max_len=MAX_LEN,
                      page_size=PAGE, chunk_tokens=CHUNK, speculative_k=k,
                      step_set=traced)
    out = eng.run(reqs())
    s = eng.stats
    assert shapes and s.head_rows == sum(r * min(c, 1 + k)
                                         for r, c in shapes)
    assert s.head_rows < s.positions_computed
    if k:
        assert s.spec_rounds > 0
        plain = ServeEngine(serve_cfg, serve_params, slots=SLOTS,
                            max_len=MAX_LEN, page_size=PAGE,
                            chunk_tokens=CHUNK).run(reqs())
        assert [r.out_tokens for r in out] == [r.out_tokens for r in plain]


def test_select_width_is_part_of_the_step_geometry(serve_cfg,
                                                   serve_params):
    narrow = _steps(serve_cfg, 1)
    assert narrow.compatible_with(
        page=PAGE, n_pages=narrow.n_pages, max_slots=SLOTS,
        max_pages_per_seq=MPPS, cache_dtype=jnp.float32, chunk=CHUNK)
    with pytest.raises(ValueError, match="select_width"):
        ServeEngine(serve_cfg, serve_params, slots=SLOTS, max_len=MAX_LEN,
                    page_size=PAGE, chunk_tokens=CHUNK, speculative_k=K,
                    step_set=narrow)
    # the lru key holds the width: one jit per width, shared within it
    assert _steps(serve_cfg, 1).step._fn is narrow.step._fn
    assert _steps(serve_cfg, 1 + K).step._fn is not narrow.step._fn
    with pytest.raises(ValueError, match="select_width"):
        _steps(serve_cfg, 0)
