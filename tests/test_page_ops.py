"""Batched page-ops, the solo-lane step and the serving weight plan
(`serve/engine.py` + `serve/steps.py:apply_page_ops`/`solo_step`).

The engine queues every COW copy / state reset / table update of a round
host-side and flushes them in ONE fused jit dispatch before the step.
These tests pin the contract: the fused path is token-identical to the
legacy one-dispatch-per-op path, strictly cheaper in host↔device round
trips, and conserves page refcounts (every live page's refcount equals
its slot mappings plus its prefix-cache hold; free pages are refcount 0).
Same file covers the B=1 solo-lane fast path and the one-time weight
execution plan (`core.serving_quant.build_exec_weights`) — both new ways
a round can reach the device, both required to be greedy-token-exact."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core.qconfig import QMCConfig
from repro.core.serving_quant import quantize_for_serving
from repro.serve import steps as serve_steps
from repro.serve.engine import Request, ServeEngine

PAGE = 8
SLOTS = 4
MAX_LEN = 48


def _reqs(n=6, sys_len=24, max_new=5, seed=3, vocab=64):
    rng = np.random.default_rng(seed)
    sys_prompt = rng.integers(2, vocab, sys_len)
    return [Request(uid=i,
                    prompt=np.concatenate(
                        [sys_prompt,
                         rng.integers(2, vocab, int(u))]).astype(np.int32),
                    max_new_tokens=max_new)
            for i, u in enumerate(rng.integers(4, 12, size=n))]


def _engine(cfg, params, *, step_set=None, **kw):
    return ServeEngine(cfg, params, slots=SLOTS, max_len=MAX_LEN,
                       page_size=PAGE, step_set=step_set, **kw)


def _legacy_steps(cfg):
    """The same step set the engine would build, with the fused flush and
    the solo lane stripped — forcing the one-dispatch-per-op path."""
    full = serve_steps.build_paged_steps(
        cfg, page=PAGE, n_pages=serve_steps.default_n_pages(
            SLOTS, MAX_LEN // PAGE),
        max_slots=SLOTS, max_pages_per_seq=MAX_LEN // PAGE)
    return dataclasses.replace(full, apply_page_ops=None, solo_step=None)


def _check_refcounts(eng):
    pool = eng._pool
    pool.check_tables()
    held = set()
    if eng.prefix_cache is not None:
        eng.prefix_cache.check_invariants()
        held = set(eng.prefix_cache._nodes)
    mapped = {}
    for pages in pool.slot_pages:
        for pid in pages:
            mapped[pid] = mapped.get(pid, 0) + 1
    for pid in range(1, pool.n_pages + 1):
        want = mapped.get(pid, 0) + (1 if pid in held else 0)
        assert pool.ref[pid] == want, \
            f"page {pid}: refcount {pool.ref[pid]} != " \
            f"{mapped.get(pid, 0)} mappings + {pid in held} cache hold"
        assert (pid in pool._free_set) == (want == 0)


def test_fused_page_ops_token_parity_and_fewer_round_trips(
        serve_cfg, serve_params):
    """Fused vs sequential page-ops on the shared-prefix workload:
    identical tokens, fewer device table rebuilds, refcounts conserved
    on both engines."""
    fused = _engine(serve_cfg, serve_params, prefix_cache=True)
    out_f = fused.run(_reqs())
    legacy = _engine(serve_cfg, serve_params, prefix_cache=True,
                     step_set=_legacy_steps(serve_cfg))
    out_l = legacy.run(_reqs())

    assert [r.out_tokens for r in out_f] == [r.out_tokens for r in out_l]
    assert fused.stats.page_op_flushes > 0
    assert legacy.stats.page_op_flushes == 0
    # the fused engine uploads tables only on mutation rounds; the
    # legacy path re-installs per admission event
    assert fused.stats.device_tables_rebuilds <= \
        legacy.stats.device_tables_rebuilds
    assert fused.stats.cache_hits > 0
    _check_refcounts(fused)
    _check_refcounts(legacy)


def test_fused_flush_batches_cow_copies(serve_cfg, serve_params):
    """A fully-cached prompt restarts mid-page (the last prompt token
    must be recomputed for its logit), writing into a shared page — the
    COW copy must ride the fused flush, not its own dispatch, and end
    with conserved refcounts."""
    rng = np.random.default_rng(11)
    base = rng.integers(2, 64, 16).astype(np.int32)   # 2 full pages of 8
    eng = _engine(serve_cfg, serve_params, prefix_cache=True)
    eng.run([Request(uid=0, prompt=base, max_new_tokens=4)])
    eng.run([Request(uid=1, prompt=base.copy(), max_new_tokens=4)])
    s = eng.stats
    assert s.cow_copies > 0
    assert s.page_copy_calls == s.cow_copies
    # every queued op was absorbed by a fused flush: ops batched counts
    # copies + resets + one table rebuild per flush, and no flush ran
    # without work or a dirty table
    assert s.page_ops_batched >= s.page_op_flushes + s.cow_copies
    _check_refcounts(eng)


def test_solo_step_parity(serve_cfg, serve_params):
    """A single in-flight request decodes through the B=1 solo lane —
    token-identical to the full-width batch step, and no dead-lane
    sentinel ever surfaces through on_token (the solo scatter used to
    fill dead lanes with vocab id 0, indistinguishable from a real
    emission; they now carry DEAD_TOKEN = -1 and must never escape)."""
    from repro.serve.sampling import DEAD_TOKEN
    prompt = np.arange(2, 12, dtype=np.int32)
    streamed = []
    solo = _engine(serve_cfg, serve_params)
    out_s = solo.run([Request(uid=0, prompt=prompt, max_new_tokens=6)],
                     on_token=lambda s, t, r: streamed.append(int(t)))
    batch = _engine(serve_cfg, serve_params,
                    step_set=_legacy_steps(serve_cfg))
    out_b = batch.run([Request(uid=0, prompt=prompt, max_new_tokens=6)])
    assert out_s[0].out_tokens == out_b[0].out_tokens
    assert solo.stats.solo_rounds > 0
    assert batch.stats.solo_rounds == 0
    assert DEAD_TOKEN not in streamed
    assert streamed == out_s[0].out_tokens


def test_solo_pipelined_parity(serve_cfg, serve_params):
    """The B=1 solo lane participates in the device-token carry: a solo
    pipelined run (carry is a passthrough — prev round's [1, C] output
    feeds the next solo step directly) and a batched pipelined run
    (legacy step set, carry slices the lane's row) both match the plain
    sync solo run token for token."""
    prompt = np.arange(2, 12, dtype=np.int32)
    mk = lambda: [Request(uid=0, prompt=prompt, max_new_tokens=8)]
    sync = _engine(serve_cfg, serve_params)
    out_sync = sync.run(mk())
    solo = _engine(serve_cfg, serve_params, pipelined=True)
    out_solo = solo.run(mk())
    batch = _engine(serve_cfg, serve_params, pipelined=True,
                    step_set=_legacy_steps(serve_cfg))
    out_batch = batch.run(mk())
    assert out_sync[0].out_tokens == out_solo[0].out_tokens
    assert out_sync[0].out_tokens == out_batch[0].out_tokens
    assert solo.stats.solo_rounds > 0
    assert solo.stats.pipelined_rounds > 0
    assert batch.stats.solo_rounds == 0
    assert batch.stats.pipelined_rounds > 0
    _check_refcounts(solo)
    _check_refcounts(batch)


def test_weight_plan_parity(serve_cfg, serve_params):
    """The one-time exec-weight lowering is greedy-token-identical to
    per-call stream compute, and a dense tree passes through untouched."""
    from repro.core.qtensor import QTensor
    from repro.core.qtensor_sharded import ShardedQTensor
    qparams = quantize_for_serving(
        serve_params, QMCConfig(rho=0.3, granularity="subtile"),
        tp_shards=1, min_dim=64)
    q_leaves = [l for l in jax.tree_util.tree_leaves(
        qparams, is_leaf=lambda x: isinstance(
            x, (QTensor, ShardedQTensor)))
        if isinstance(l, (QTensor, ShardedQTensor))]
    assert q_leaves, "config too small to quantize — test is vacuous"

    planned = _engine(serve_cfg, qparams)
    out_p = planned.run(_reqs())
    streamed = _engine(serve_cfg, qparams, weight_plan=False)
    out_s = streamed.run(_reqs())
    assert [r.out_tokens for r in out_p] == [r.out_tokens for r in out_s]
    # the plan lowered every stream leaf; dense engines pay nothing
    assert planned._exec_params is not None
    assert not any(isinstance(l, (QTensor, ShardedQTensor))
                   for l in jax.tree_util.tree_leaves(
                       planned._exec_params,
                       is_leaf=lambda x: isinstance(
                           x, (QTensor, ShardedQTensor))))
    dense = _engine(serve_cfg, serve_params)
    assert dense._exec_params is serve_params


def test_pure_decode_rounds_skip_flush(serve_cfg, serve_params):
    """Rounds that neither allocated, COWed, nor reset anything must not
    dispatch apply_page_ops at all: flush count stays well below round
    count on a decode-heavy run."""
    eng = _engine(serve_cfg, serve_params)
    eng.run([Request(uid=i, prompt=np.arange(2, 8, dtype=np.int32),
                     max_new_tokens=8) for i in range(3)])
    s = eng.stats
    assert s.page_op_flushes < s.rounds
    assert s.rounds > 4


def test_engine_round_donates_arena(serve_cfg, serve_params):
    """The serving steps donate the arena on every backend, the CPU
    included: after a run the K/V pages the engine started from are dead
    (updated in place) and the engine holds only live buffers."""
    eng = _engine(serve_cfg, serve_params)
    eng._ensure_pool()
    first = [leaf for path, leaf in
             jax.tree_util.tree_flatten_with_path(eng._arena)[0]
             if jax.tree_util.keystr(path).endswith("_pages']")]
    assert first
    eng.run(_reqs(n=2))
    assert all(leaf.is_deleted() for leaf in first)
    assert not any(leaf.is_deleted()
                   for leaf in jax.tree_util.tree_leaves(eng._arena))


def _aligned_copy(a):
    """``a`` copied onto a 64-byte boundary, where the CPU client maps a
    host buffer into a device array without copying it."""
    raw = np.zeros(a.nbytes + 64, np.uint8)
    off = -raw.ctypes.data % 64
    out = raw[off:off + a.nbytes].view(a.dtype).reshape(a.shape)
    out[...] = a
    return out


class _AlignedNumpy:
    """numpy with ``zeros`` on a 64-byte boundary."""

    def __getattr__(self, name):
        return getattr(np, name)

    @staticmethod
    def zeros(shape, dtype=float):
        return _aligned_copy(np.zeros(shape, dtype))


def test_flush_uploads_copies_of_rewritten_host_buffers(
        serve_cfg, serve_params, monkeypatch):
    """The fused flush hands the step copies of the host buffers the
    engine rewrites right after it (the reset mask, the pool's block
    tables). An aliased upload lets the asynchronous step read the
    rewrite: a reused lane then kept its previous request's SSM state."""
    from repro.serve import engine as engine_mod
    eng = _engine(serve_cfg, serve_params)
    pool = eng._ensure_pool()
    seen = []

    def spy(arena, src, dst, tables, mask):
        seen.append((tables, mask))
        return arena

    eng._steps = dataclasses.replace(eng._steps, apply_page_ops=spy)
    monkeypatch.setattr(engine_mod, "np", _AlignedNumpy())
    pool.block_tables = _aligned_copy(pool.block_tables)
    eng._pending_resets.append(1)
    eng._flush_page_ops(pool)
    pool.block_tables[0, 0] = 7
    (tables, mask), = seen
    assert np.asarray(mask).tolist() == [False, True, False, False]
    assert not np.asarray(tables).any()
