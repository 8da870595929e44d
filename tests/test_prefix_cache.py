"""Radix-trie prefix cache + prefix-sharing admission tests.

Covers the trie itself (longest-prefix match, edge splitting, LRU
byte-budget eviction), the slot-alignment gate that decides whether a
finalized (pruned) cache may donate raw prefix rows, the model-level
bitwise guarantee — resuming a chunked prefill from cached workspace
rows reproduces the from-scratch whole-prompt prefill bit-for-bit, for
bf16 AND int8 caches — and the ServeLoop integration end to end
(Request API, exact-state hits, suffix-resume hits, lane isolation,
deprecation of the positional/legacy surface).
"""
import dataclasses
import math
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, reduced
from repro.core import baselines
from repro.launch.prefix_cache import PrefixCache, RowsEntry, StateEntry
from repro.launch.serve import (Request, RequestHandle, SamplingParams,
                                ServeLoop)
from repro.models.transformer import Model

jax.config.update("jax_platform_name", "cpu")

PRUNE = baselines.unicaim(heavy=48, reserve=16, select_k=16,
                          sink_tokens=2, recent_window=8)


@pytest.fixture(scope="module")
def setup():
    # attn_chunk == 16 matches the chunk_prefill grid used throughout, so
    # whole-bucket and chunked prefills share one accumulation order
    cfg = dataclasses.replace(reduced(get_config("granite-3-2b")),
                              attn_chunk=16)
    model = Model(cfg, PRUNE)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompt(cfg, t, seed=0):
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, t)


def _rows(depth, seed=0, nbytes=None):
    rng = np.random.default_rng(seed)
    e = RowsEntry(depth, rng.standard_normal((2, 2, depth, 4)),
                  rng.standard_normal((2, 2, depth, 4)),
                  rng.standard_normal((2, 2, depth)))
    if nbytes is not None:
        e.nbytes = nbytes
    return e


# -- trie ---------------------------------------------------------------------


def test_trie_longest_prefix_match():
    pc = PrefixCache(1 << 30)
    toks = list(range(100, 164))                     # 64 distinct tokens
    pc.insert_rows(toks[:16], _rows(16))
    pc.insert_rows(toks[:48], _rows(48))
    # deepest boundary within the cap wins
    assert pc.match_rows(toks, cap=64).depth == 48
    assert pc.match_rows(toks, cap=32).depth == 16
    assert pc.match_rows(toks, cap=8) is None
    # a diverging suffix only matches the shared part
    fork = toks[:32] + [7] * 32
    assert pc.match_rows(fork, cap=64).depth == 16
    # match_state is exact-only
    pc.insert_state(toks, StateEntry(64, 64, np.zeros(8), {"x": np.zeros(4)}))
    assert pc.match_state(toks).length == 64
    assert pc.match_state(toks[:48]) is None
    assert pc.match_state(toks + [1]) is None


def test_trie_edge_split_preserves_entries():
    """Inserting a diverging key splits a compressed edge without losing
    the entry that lived past the split point."""
    pc = PrefixCache(1 << 30)
    a = [1, 2, 3, 4, 5, 6]
    b = [1, 2, 3, 9, 9, 9]
    pc.insert_rows(a, _rows(6))
    pc.insert_rows(b, _rows(6, seed=1))
    assert pc.match_rows(a, cap=6).depth == 6
    assert pc.match_rows(b, cap=6).depth == 6
    assert pc.match_rows([1, 2, 3, 4], cap=6) is None
    assert pc.entries == 2


def test_trie_lru_eviction_under_byte_budget():
    one = _rows(4, nbytes=100).nbytes               # pin entry size
    pc = PrefixCache(250)                           # room for two
    pc.insert_rows([1], _rows(1, nbytes=100))
    pc.insert_rows([2], _rows(1, seed=1, nbytes=100))
    assert pc.entries == 2 and pc.evictions == 0
    # touching [1] makes [2] the LRU victim of the next insert
    assert pc.match_rows([1, 5], cap=1).depth == 1
    pc.insert_rows([3], _rows(1, seed=2, nbytes=100))
    assert pc.entries == 2 and pc.evictions == 1
    assert pc.match_rows([2, 5], cap=1) is None     # evicted
    assert pc.match_rows([1, 5], cap=1) is not None
    assert pc.match_rows([3, 5], cap=1) is not None
    assert pc.bytes == 200
    assert one == 100


def test_trie_oversized_and_disabled_inserts_refused():
    pc = PrefixCache(50)
    assert not pc.insert_rows([1, 2], _rows(2, nbytes=100))  # > budget
    assert pc.entries == 0 and pc.bytes == 0
    off = PrefixCache(0)
    assert not off.insert_rows([1], _rows(1))
    assert off.match_rows([1], cap=1) is None


# -- slot-alignment gate ------------------------------------------------------


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_slot_alignment_rejects_pruned_and_quantized(setup, kv_dtype):
    """`cache_prefix_rows` only accepts a finalized cache whose slots are
    the raw identity-ordered prefix: a prefill short enough that static
    pruning kept everything (and full precision) passes; a pruned layout
    (prompt > heavy budget ⇒ top-k rewrote the slots) and any int8
    mirror are refused — their rows are not the raw prefix."""
    from repro.surgery import cache_prefix_rows, prefix_slot_aligned
    cfg, _, params = setup
    prune = dataclasses.replace(PRUNE, kv_dtype=kv_dtype)
    model = Model(cfg, prune)
    short = _prompt(cfg, 16, seed=1)
    _, st = jax.jit(model.prefill_one)(params, jnp.asarray(short),
                                       jnp.asarray(16, jnp.int32))
    if kv_dtype == "int8":
        assert not prefix_slot_aligned(st.kv, 16)
        assert cache_prefix_rows(st.kv, 16) is None
        return
    assert prefix_slot_aligned(st.kv, 16)
    k, v, acc = cache_prefix_rows(st.kv, 16)
    assert k.shape[-2] == 16 and acc.shape[-1] == 16
    long = _prompt(cfg, 64, seed=2)                 # > heavy=48 ⇒ pruned
    padded = np.zeros(64, long.dtype)
    padded[:64] = long
    _, st2 = jax.jit(model.prefill_one)(params, jnp.asarray(padded),
                                        jnp.asarray(64, jnp.int32))
    assert not prefix_slot_aligned(st2.kv, 64)
    assert cache_prefix_rows(st2.kv, 64) is None


# -- model-level bitwise resume ----------------------------------------------


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_resume_from_cached_rows_bitwise(setup, kv_dtype):
    """The tentpole invariant: workspace rows snapped at a chunk boundary
    of prompt A, resumed with prompt B's suffix chunks, reproduce B's
    from-scratch prefill BIT-FOR-BIT — logits and every cache leaf, for
    bf16 and int8 alike (the snapshot predates pruning/quantization)."""
    cfg, _, params = setup
    prune = dataclasses.replace(PRUNE, kv_dtype=kv_dtype)
    model = Model(cfg, prune)
    t, bucket, C = 64, 64, 16
    shared = _prompt(cfg, 48, seed=3)
    a = np.concatenate([shared, _prompt(cfg, 16, seed=4)])
    b = np.concatenate([shared, _prompt(cfg, 16, seed=5)])
    chunk = jax.jit(model.prefill_chunk)
    fin = jax.jit(model.prefill_finalize)
    length = jnp.asarray([t])

    def run_chunks(ps, toks, lo, hi, x_last=None):
        for ci in range(lo, hi):
            x_last, ps = chunk(params, ps,
                               jnp.asarray(toks[None, ci * C:(ci + 1) * C]),
                               jnp.asarray(ci * C, jnp.int32), length)
        return x_last, ps

    # prefill A from scratch, snapping the boundary-48 workspace prefix
    ps = model.init_prefill_chunk_state(1, bucket)
    _, ps = run_chunks(ps, a, 0, 3)
    snap = RowsEntry(48, np.asarray(ps.k[:, 0, :, :48]),
                     np.asarray(ps.v[:, 0, :, :48]),
                     np.asarray(ps.acc[:, 0, :, :48]))
    # resume B's final chunk on the snapshot vs B fully from scratch
    ps_r = model.resume_prefill_chunk_state(snap.k, snap.v, snap.acc, bucket)
    x_r, ps_r = run_chunks(ps_r, b, 3, 4)
    lg_r, st_r = fin(params, ps_r, x_r, jnp.asarray(48, jnp.int32), length)
    ps_f = model.init_prefill_chunk_state(1, bucket)
    x_f, ps_f = run_chunks(ps_f, b, 0, 4)
    lg_f, st_f = fin(params, ps_f, x_f, jnp.asarray(48, jnp.int32), length)
    np.testing.assert_array_equal(np.asarray(lg_r), np.asarray(lg_f))
    for x, y in zip(jax.tree.leaves(st_r), jax.tree.leaves(st_f)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # and the chunked path itself is bitwise vs the whole-prompt prefill
    # (C == attn_chunk), so transitively resume == whole-prompt
    lg_w, st_w = jax.jit(model.prefill_one)(params, jnp.asarray(b),
                                            jnp.asarray(t, jnp.int32))
    np.testing.assert_array_equal(np.asarray(lg_r[0]), np.asarray(lg_w))
    for x, y in zip(jax.tree.leaves(st_r), jax.tree.leaves(st_w)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# -- engine integration -------------------------------------------------------


def _loop(model, params, **kw):
    kw.setdefault("lanes", 2)
    kw.setdefault("max_new", 8)
    kw.setdefault("block", 4)
    kw.setdefault("chunk_prefill", 16)
    return ServeLoop(model, params, **kw)


def _shared_prompts(cfg, n=4, shared=48, suffix=16, seed=0):
    rng = np.random.default_rng(seed)
    head = rng.integers(0, cfg.vocab_size, shared)
    return [np.concatenate([head, rng.integers(0, cfg.vocab_size, suffix)])
            for _ in range(n)]


def test_serve_prefix_reuse_matches_cold_loop(setup):
    """Shared-prefix admission through the cache: fewer chunk dispatches,
    hit/dedup counters populated, and every token stream identical to a
    cache-less twin loop."""
    cfg, model, params = setup
    prompts = _shared_prompts(cfg)
    warm = _loop(model, params, prefix_cache_bytes=64 << 20)
    cold = _loop(model, params)
    hw = [warm.submit(Request(prompt=p)) for p in prompts]
    hc = [cold.submit(Request(prompt=p)) for p in prompts]
    warm.run()
    cold.run()
    for a, b in zip(hw, hc):
        assert a.done and b.done
        assert a.tokens == b.tokens
    assert warm.counters["chunk_dispatches"] < cold.counters["chunk_dispatches"]
    agg = warm.aggregate()
    assert agg["prefix_hit_rate"] == pytest.approx(0.75)   # 3 of 4 hit
    assert agg["prefix_dedup_ratio"] > 0.5                 # 144/256 reused
    assert warm.counters["prefix_copies"] == 3
    assert warm.counters["prefix_tokens_reused"] == 144
    # the trie's own tallies, read through aggregate(), not mirrored
    assert agg["prefix_inserts"] == warm.prefix_cache.inserts > 0
    assert "prefix_inserts" not in warm.counters
    hit_stats = [h.stats for h in hw[1:]]
    assert all(s.prefix_tokens == 48 for s in hit_stats)
    assert all(not s.prefix_exact for s in hit_stats)
    assert hw[0].stats.prefix_tokens == 0


def test_serve_exact_hit_skips_prefill_entirely(setup):
    cfg, model, params = setup
    loop = _loop(model, params, max_new=4, prefix_cache_bytes=64 << 20)
    prompt = _shared_prompts(cfg, n=1)[0]
    h1 = loop.submit(Request(prompt=prompt, max_new=4))
    loop.run()
    before = (loop.counters["prefill_dispatches"],
              loop.counters["chunk_dispatches"])
    h2 = loop.submit(Request(prompt=prompt, max_new=4))
    loop.run()
    after = (loop.counters["prefill_dispatches"],
             loop.counters["chunk_dispatches"])
    assert before == after                         # zero prefill work
    assert loop.counters["prefix_exact_hits"] == 1
    assert h2.stats.prefix_exact and h2.stats.prefill_chunks == 0
    assert h1.tokens == h2.tokens


def test_serve_prefix_copy_does_not_alias_lane_state(setup):
    """Lane isolation: decoding on a lane admitted from a cached prefix
    must not mutate the cached donor — later hits see the same bytes."""
    cfg, model, params = setup
    prompts = _shared_prompts(cfg)
    loop = _loop(model, params, prefix_cache_bytes=64 << 20)
    loop.submit(Request(prompt=prompts[0]))
    loop.run()
    entry = loop.prefix_cache.match_rows(prompts[1], cap=48)
    saved = (entry.k.copy(), entry.v.copy(), entry.acc.copy())
    for p in prompts[1:]:
        loop.submit(Request(prompt=p))
    loop.run()
    assert entry is loop.prefix_cache.match_rows(prompts[1], cap=48)
    for got, want in zip((entry.k, entry.v, entry.acc), saved):
        np.testing.assert_array_equal(got, want)


def test_serve_reuse_prefix_opt_out(setup):
    cfg, model, params = setup
    prompts = _shared_prompts(cfg, n=2)
    loop = _loop(model, params, prefix_cache_bytes=64 << 20)
    for p in prompts:
        loop.submit(Request(prompt=p, reuse_prefix=False))
    loop.run()
    assert loop.counters["prefix_lookups"] == 0
    assert loop.counters["prefix_hits"] == 0
    assert loop.prefix_cache.entries == 0          # nothing inserted either


def test_serve_whole_bucket_donor_feeds_chunked_resume(setup):
    """A short prompt admitted whole-bucket (bucket <= C) whose layout
    stayed slot-aligned becomes a rows donor for a longer chunked
    admission sharing it as a prefix."""
    cfg, model, params = setup
    rng = np.random.default_rng(7)
    head = rng.integers(0, cfg.vocab_size, 16)     # <= heavy ⇒ unpruned
    long = np.concatenate([head, rng.integers(0, cfg.vocab_size, 48)])
    loop = _loop(model, params, prefix_cache_bytes=64 << 20)
    loop.submit(Request(prompt=head, max_new=2))
    loop.run()
    assert loop.prefix_cache.match_rows(long, cap=48) is not None
    h = loop.submit(Request(prompt=long, max_new=2))
    loop.run()
    assert h.stats.prefix_tokens == 16
    cold = _loop(model, params)
    h2 = cold.submit(Request(prompt=long, max_new=2))
    cold.run()
    assert h.tokens == h2.tokens


# -- Request API + deprecations ----------------------------------------------


def test_request_api_surface(setup):
    cfg, model, params = setup
    loop = _loop(model, params)
    h = loop.submit(Request(prompt=_prompt(cfg, 20), max_new=3))
    assert isinstance(h, RequestHandle) and not h.done
    with pytest.raises(TypeError):                 # mixing old+new forms
        loop.submit(Request(prompt=_prompt(cfg, 8)), max_new=4)
    req = Request(prompt=_prompt(cfg, 8), max_new=2)
    loop.submit(req)
    with pytest.raises(ValueError):                # double submission
        loop.submit(req)
    with pytest.raises(TypeError):                 # positional construction
        Request(_prompt(cfg, 8))
    loop.run()
    assert h.done and len(h.tokens) == 3


def test_per_request_sampling_seed_is_deterministic(setup):
    """Same prompt + same `sample_seed` ⇒ the same sampled first token,
    independent of loop-stream history; overrides force solo admission."""
    cfg, model, params = setup
    prompt = _prompt(cfg, 12, seed=11)
    sp = SamplingParams(temperature=0.8, top_k=5)
    loop = _loop(model, params, lanes=2, max_new=1)
    hs = [loop.submit(Request(prompt=prompt, max_new=1, sampling=sp,
                              sample_seed=123)) for _ in range(2)]
    loop.run()
    assert hs[0].tokens == hs[1].tokens and len(hs[0].tokens) == 1
    assert all(h.stats.group_size == 1 for h in hs)


def test_legacy_surface_warns(setup):
    cfg, model, params = setup
    loop = _loop(model, params)
    with pytest.warns(DeprecationWarning):
        rid = loop.submit(_prompt(cfg, 12), 2, 0.0)
    assert isinstance(rid, int)
    loop.run()
    with pytest.warns(DeprecationWarning):
        loop.admit(np.stack([_prompt(cfg, 16, seed=i) for i in range(2)]))
    with pytest.warns(DeprecationWarning):
        loop.step()
    with pytest.warns(DeprecationWarning):
        loop.step_block()


# -- preemption-aware caching -------------------------------------------------


def test_preempted_lane_feeds_prefix_cache(setup):
    """A preempted lane's captured state donates its prefix rows to the
    trie through the same slot-alignment gate as finalization — but only
    when the capture is not decode-advanced (fill == step == prompt
    length): an un-decoded victim donates, a mid-decode victim is
    refused by the gate."""
    cfg, model, params = setup
    loop = _loop(model, params, lanes=1, prefix_cache_bytes=64 << 20)
    p = _prompt(cfg, 32, 5)
    h_v = loop.submit(Request(prompt=p, max_new=8, priority=0))
    for _ in range(8):                         # drive the chunked prefill
        loop.schedule()
        loop._advance_chunked()
        if loop.active.any():
            break
    assert loop.active.any()
    loop.submit(Request(prompt=_prompt(cfg, 16, 6), max_new=4, priority=5))
    loop.schedule()                            # evicts the un-decoded victim
    assert loop.counters["preemptions"] == 1
    assert loop.counters["preempt_cache_inserts"] == 1

    # a sibling sharing the 32-token prefix resumes from the donated rows
    sib = np.concatenate([p, _prompt(cfg, 16, 7)])
    h_s = loop.submit(Request(prompt=sib, max_new=4))
    loop.run()
    assert h_s.stats.prefix_tokens == 32
    assert loop.counters["prefix_copies"] >= 1
    cold = _loop(model, params, lanes=1)
    h_c = cold.submit(Request(prompt=sib, max_new=4))
    cold.run()
    assert h_s.tokens == h_c.tokens            # donated rows are bitwise
    assert h_v.tokens == _solo_tokens(model, params,
                                      dict(prompt=p, max_new=8))

    # round 2: a victim that already decoded a block is refused
    h2 = loop.submit(Request(prompt=_prompt(cfg, 32, 8), max_new=8,
                             priority=0))
    for _ in range(8):
        loop.schedule()
        loop._advance_chunked()
        if loop.active.any():
            break
    loop._step_block()
    loop.submit(Request(prompt=_prompt(cfg, 16, 9), max_new=4, priority=5))
    loop.schedule()
    assert loop.counters["preemptions"] == 2
    assert loop.counters["preempt_cache_inserts"] == 1   # gate refused
    loop.run()
    assert h2.done


def _solo_tokens(model, params, req_kw):
    loop = _loop(model, params, lanes=1)
    h = loop.submit(Request(**req_kw))
    loop.run()
    return h.tokens


# -- surgery namespace --------------------------------------------------------


def test_surgery_namespace_reexports():
    import repro.surgery as surgery
    from repro.core import cache as kvcache
    from repro.models import transformer as T
    for name in surgery.__all__:
        assert getattr(surgery, name) is not None
    assert surgery.state_lane_insert is T.lane_insert
    assert surgery.state_lanes_insert is T.lanes_insert
    assert surgery.state_lane_select is T.lane_select
    assert surgery.kv_lane_insert is kvcache.lane_insert
    assert surgery.slot_window is kvcache.slot_window
    assert surgery.cache_prefix_rows is kvcache.cache_prefix_rows
