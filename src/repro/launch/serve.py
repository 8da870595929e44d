"""Serving driver: lane-granular continuous batching over the UniCAIM cache.

The engine keeps a fixed number of decode *lanes* (batch slots) and a
request queue. Each request carries its own prompt (arbitrary length ≤ max)
and `max_new` budget. Admission is *grouped*: every arrived request that
pads to the same bucket is prefilled in ONE batched dispatch
(`Model.prefill_group`) and spliced into the free lanes of the live
batched `DecodeState` with ONE vectorized multi-lane insert
(`transformer.lanes_insert`) — shortest-bucket-first under load so short
prompts are never starved behind a long arrival; a lone request takes the
batch-1 path (`Model.prefill_one` + `lane_insert`). Decode runs as a
single jitted multi-step `lax.scan` over the whole lane batch — one
dispatch per block of tokens — with the state donated so XLA updates it
in place.

Termination is **in-device**: an `active` lane mask rides through the
scanned block, finished lanes stop contributing state writes, and the block
returns per-step (token, emitted) pairs so the host bookkeeping is
vectorized numpy instead of a per-token/per-lane Python loop. A lane that
hits EOS or its budget is freed and refilled from the queue mid-flight —
the fixed-budget cache (the paper's point) stays busy under realistic
mixed traffic. This is the paper's target regime: memory-bound
autoregressive decoding where per-token Python dispatch otherwise
dominates the step time.

Serving knobs are **per-lane runtime state**: temperature/top-k/top-p,
the stop token, the remaining budget, and the PRNG carry are all
[lanes]-shaped arrays threaded through the scanned block
(`decode_block_lanes`), so ONE compiled program per (steps, window)
serves any mix of greedy and sampled lanes — per-request
`SamplingParams` are honoured across the whole stream, not just the
admission-seeded first token, and knob values never recompile. The
scheduler is drain-aware (predicts lane free-times from remaining
budgets + observed EOS lengths and reserves/pre-groups queued requests
so admission fires the moment lanes free) and priority-preemptive (a
higher-priority arrival may evict the lowest-priority lane via
`lane_slice` capture; the victim requeues and later resumes
token-identically).

Requests enter through the keyword-only `Request` dataclass
(`submit(Request(prompt=..., max_new=...)) -> RequestHandle`); the
positional `submit(prompt, max_new, arrival)` shim and the all-lanes
`admit()`/`step()`/`step_block()` surface survive with a
`DeprecationWarning`, routed through the same internals. With
`prefix_cache_bytes > 0` admission consults a host-side radix-trie
prefix cache (`launch/prefix_cache.py`): exact-prompt hits splice the
cached finalized state straight into a lane, and shared-prefix hits
resume the sliced prefill from cached pre-pruning workspace rows —
bit-identical to prefilling the whole prompt from scratch.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import math
import time
import warnings
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config, reduced
from repro.core import baselines
from repro.core.attention import decode_engine
from repro.launch.prefix_cache import PrefixCache, RowsEntry, StateEntry
from repro.models.transformer import Model
from repro.surgery import (cache_prefix_rows, state_lane_insert,
                           state_lane_select, state_lane_slice,
                           state_lanes_insert)


# ---------------------------------------------------------------------------
# Prompt-length buckets — shape-stable prefill.
#
# `Model.prefill_one` compiles one XLA program per distinct prompt WIDTH.
# Right-padding every prompt to a small doubling bucket grid and passing the
# true length (masked all the way through attention, charge-domain
# accumulation, and the static top-k) bounds the jit cache at len(buckets)
# programs regardless of traffic — the serving-side analogue of the paper's
# statically-shaped FeFET slot array. Two prompts padded to the same bucket
# produce bit-identical logits/caches to a same-bucket full-batch prefill.
# ---------------------------------------------------------------------------

MIN_BUCKET = 16


def bucket_length(t: int, buckets: Optional[Sequence[int]] = None) -> int:
    """Smallest bucket >= t. Default grid: powers of two from MIN_BUCKET.
    With an explicit grid, lengths beyond the largest bucket fall back to
    the exact length (correct, but one extra compile per such length)."""
    if buckets is None:
        return max(MIN_BUCKET, 2 ** math.ceil(math.log2(max(t, 1))))
    for b in buckets:
        if b >= t:
            return int(b)
    return t


def pad_to_bucket(prompt: np.ndarray,
                  buckets: Optional[Sequence[int]] = None
                  ) -> Tuple[np.ndarray, int]:
    """Right-pad `prompt` to its bucket → (padded [bucket], true length)."""
    prompt = np.asarray(prompt)
    t = len(prompt)
    b = bucket_length(t, buckets)
    if b == t:
        return prompt, t
    out = np.zeros(b, prompt.dtype)
    out[:t] = prompt
    return out, t


def greedy_generate(model: Model, params, batch, steps: int,
                    temperature: float = 0.0, key=None, top_k: int = 0,
                    top_p: float = 0.0):
    """Prefill + `steps` decode steps. Returns [B, steps] generated ids.

    One Python dispatch per token — the REFERENCE loop. Production
    serving uses the scanned paths, which support the same
    temperature/top-k/top-p sampling in-device (`ServeLoop(
    temperature=..., top_k=..., top_p=...)` / `decode_block_masked`);
    this loop shares their `_next_token` rule, so both stay
    interchangeable. `key` defaults to PRNGKey(0) when sampling
    (temperature > 0).
    """
    if temperature > 0 and key is None:
        key = jax.random.PRNGKey(0)
    logits, state = _prefill_fn(_model_key(model))(params, batch)
    decode = _decode_step_fn(_model_key(model))
    toks = []
    tok = jnp.argmax(logits, -1)
    for i in range(steps):
        toks.append(tok)
        logits, state = decode(params, state, tok)
        if temperature > 0:
            key, sub = jax.random.split(key)
        else:
            sub = key
        tok = _next_token(logits, sub, temperature, top_k, top_p)
    return jnp.stack(toks, axis=1), state


def decode_block(model: Model, params, state, tok, steps: int,
                 window: Optional[int] = None):
    """`steps` greedy decode steps as one lax.scan (pure, traceable).

    tok: [B] current token → (state, next_tok [B], toks [steps, B]) where
    toks[0] == tok (the scan emits, then advances — same order as the
    per-token loop). `window` (static) runs every step over the
    `[:window]` slot prefix — the caller guarantees it covers
    max(fill) + steps (see `core/cache.decode_window`).
    """
    def body(carry, _):
        state, tok = carry
        logits, state = model.decode_step(params, state, tok,
                                          window=window)
        nxt = jnp.argmax(logits, -1)
        return (state, nxt), tok

    (state, tok), toks = jax.lax.scan(body, (state, tok), None, length=steps)
    return state, tok, toks


def _next_token(logits, key, temperature: float, top_k: int,
                top_p: float = 0.0):
    """Next-token rule shared by the decode block and admission seeding:
    argmax when temperature == 0 (key unused), else categorical over
    logits/temperature, optionally truncated to the per-row top_k
    highest logits and/or the top-p (nucleus) smallest set of tokens
    whose probability mass reaches `top_p` (top-k truncation applies
    first, matching the usual sampler convention; top_p outside (0, 1)
    disables nucleus truncation). logits: [..., V] → [...] token ids."""
    if temperature <= 0:
        return jnp.argmax(logits, -1)
    if top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if 0.0 < top_p < 1.0:
        sl = jnp.sort(logits, axis=-1)[..., ::-1]          # descending
        p = jax.nn.softmax(sl / temperature, axis=-1)
        # keep the minimal prefix whose mass reaches top_p: a token stays
        # iff the mass BEFORE it is < top_p (the first token always does)
        keep = jnp.cumsum(p, axis=-1) - p < top_p
        cut = jnp.min(jnp.where(keep, sl, jnp.inf), -1, keepdims=True)
        logits = jnp.where(logits < cut, -jnp.inf, logits)
    return jax.random.categorical(key, logits / temperature, axis=-1)


def decode_block_masked(model: Model, params, state, tok, active, rem,
                        eos, key, steps: int, temperature: float = 0.0,
                        top_k: int = 0, top_p: float = 0.0,
                        window: Optional[int] = None):
    """`steps` decode steps with in-device per-lane termination.

    active: [B] bool lane-live mask; rem: [B] int32 remaining budget;
    eos: RUNTIME scalar int32 (a traced argument, not a compile-time
    constant — one compiled program per `steps` serves every eos id;
    token ids are >= 0, so eos = -1 simply never matches); key: PRNG key
    threaded through the scan carry (ignored when greedy). Each step
    emits the carried token for active lanes, then advances; a lane
    deactivates on EOS or on exhausting its budget, and from then on its
    state is frozen (lane_select drops its writes) while the other lanes
    keep decoding. The EOS token itself is a stop signal, NOT an output:
    it is never emitted (it would otherwise inflate token counts and
    every tokens/s metric derived from them), while budget-terminated
    lanes still emit exactly their `rem` tokens.

    `temperature`/`top_k`/`top_p` are compile-time sampling knobs:
    temperature 0 (default) keeps the bitwise-greedy argmax path with no
    RNG in the loop; temperature > 0 samples from logits/temperature,
    optionally truncated to the top_k highest-probability tokens and/or
    the top-p nucleus per lane. `window` (static) runs every decode step
    over the `[:window]` slot prefix; the caller sizes it to cover
    max(fill over active lanes) + steps, so active-lane math is
    bit-identical to full width, while inactive lanes (whose fills the
    window may NOT cover) are safe because their state writes are
    dropped by `lane_select` and their tokens are never emitted. Returns
    (state, tok, active, rem, key, toks [steps, B], emitted [steps, B]).
    """
    inplace = model.supports_inplace_decode()

    def body(carry, _):
        state, tok, active, rem, key = carry
        if inplace:
            # zero-copy path: finished lanes are frozen at the write
            # source (dropped scatters), so no full-width lane_select
            # merge — the state pytree stays input-output aliased
            logits, state = model.decode_step(params, state, tok,
                                              window=window, active=active)
        else:
            logits, new_state = model.decode_step(params, state, tok,
                                                  window=window)
            state = state_lane_select(active, new_state, state)
        live = active & (rem > 0)      # robust to active lanes w/o budget
        emit = live & (tok != eos)
        rem = rem - emit.astype(rem.dtype)
        active = emit & (rem > 0)
        if temperature > 0:
            key, sub = jax.random.split(key)
        else:
            sub = key
        nxt = _next_token(logits, sub, temperature, top_k,
                          top_p).astype(tok.dtype)
        return (state, nxt, active, rem, key), (tok, emit)

    eos = jnp.asarray(eos, jnp.int32)
    (state, tok, active, rem, key), (toks, emitted) = jax.lax.scan(
        body, (state, tok, active, rem, key), None, length=steps)
    return state, tok, active, rem, key, toks, emitted


def _next_token_lanes(logits, keys, temperature, top_k, top_p):
    """Vectorized per-lane next-token rule: every knob is a RUNTIME array.

    logits [B, V]; keys [B, 2] per-lane PRNG subkeys; temperature/top_k/
    top_p [B]-shaped traced arrays — one compiled program serves any mix
    of greedy and sampled lanes, so knob values never recompile. Per-row
    semantics match `_next_token`: rows with temperature <= 0 take the
    bitwise argmax of the RAW logits (key unused); sampled rows truncate
    to the top_k highest logits first (top_k <= 0 disables — the kth
    threshold comes from one descending sort instead of `lax.top_k`,
    whose k must be static), then to the minimal top-p nucleus (top_p
    outside (0, 1) disables), then draw categorical(logits/temperature)
    with the row's own key.
    """
    v = logits.shape[-1]
    greedy = temperature <= 0.0
    t = jnp.where(greedy, 1.0, temperature)[:, None]       # no div-by-0
    sl = jnp.sort(logits, axis=-1)[..., ::-1]              # descending
    kth = jnp.take_along_axis(sl, (jnp.clip(top_k, 1, v) - 1)[:, None],
                              axis=-1)                     # [B, 1]
    use_k = (top_k > 0)[:, None]
    lg = jnp.where(use_k & (logits < kth), -jnp.inf, logits)
    # masking the tail of an already-sorted row keeps it sorted, so the
    # nucleus scan runs over the top-k-truncated distribution directly
    sl = jnp.where(use_k & (sl < kth), -jnp.inf, sl)
    p = jax.nn.softmax(sl / t, axis=-1)
    keep = jnp.cumsum(p, axis=-1) - p < top_p[:, None]
    cut = jnp.min(jnp.where(keep, sl, jnp.inf), -1, keepdims=True)
    use_p = ((top_p > 0.0) & (top_p < 1.0))[:, None]
    lg = jnp.where(use_p & (lg < cut), -jnp.inf, lg)
    sampled = jax.vmap(jax.random.categorical)(keys, lg / t)
    return jnp.where(greedy, jnp.argmax(logits, -1), sampled)


def decode_block_lanes(model: Model, params, state, tok, active, rem,
                       eos, keys, temperature, top_k, top_p, fault=None,
                       steps: int = 1, window: Optional[int] = None):
    """`steps` decode steps with per-lane termination AND per-lane
    sampling knobs — the engine's decode block.

    Same in-device termination contract as `decode_block_masked`, but
    every serving knob is a [B]-shaped RUNTIME array: `eos` (per-lane
    stop token; ids are >= 0 so -1 never matches), `temperature`/
    `top_k`/`top_p` (per-lane sampling, `_next_token_lanes` semantics),
    and `keys` ([B, 2] uint32 per-lane PRNG carries, split once per
    scanned step). The jit cache is keyed on (steps, window) ONLY — one
    compiled program serves arbitrary knob mixes.

    Greedy guarantees: a lane with temperature <= 0 emits the bitwise
    argmax stream (identical to `decode_block_masked`'s greedy path),
    and when NO resident lane samples a `lax.cond` skips the sampler —
    an all-greedy engine carries no RNG work and leaves `keys`
    untouched. When any lane samples, every lane's key advances once
    per step via its OWN split chain, so a lane's sampled stream is a
    function of (its initial key, steps resident) alone — independent
    of its neighbours, its lane index, and any preempt/resume boundary.

    **Non-finite sentinel.** Every step checks each lane's logits for
    NaN/Inf (a numerical fault: bad weights row, flaky interconnect,
    injected chaos). A poisoned lane is deactivated IN-DEVICE before it
    can emit from the corrupt logits and flagged in the returned
    `poison` mask; the host quarantines it and retries the request
    deterministically. The all-clean path is behind a `lax.cond` on
    `any(active & ~finite)` — when nothing is poisoned the carried
    masks pass through untouched and the block stays bitwise-identical
    to the sentinel-free engine (lanes are independent: a NaN can never
    cross the batch axis, so neighbours stay exact). `fault` (optional
    [steps, B] bool, a RUNTIME array) overwrites masked lanes' logits
    with NaN before the check — the injection point used by
    `runtime/chaos.py`; an all-False mask is a bitwise no-op.

    Returns (state, tok, active, rem, keys, poison [B],
    toks [steps, B], emitted [steps, B]).
    """
    inplace = model.supports_inplace_decode()
    eos = jnp.asarray(eos, jnp.int32)
    temperature = jnp.asarray(temperature, jnp.float32)
    top_k = jnp.asarray(top_k, jnp.int32)
    top_p = jnp.asarray(top_p, jnp.float32)
    sampled_any = jnp.any(temperature > 0.0)

    def body(carry, frow):
        state, tok, active, rem, keys, poison = carry
        if inplace:
            logits, state = model.decode_step(params, state, tok,
                                              window=window, active=active)
        else:
            logits, new_state = model.decode_step(params, state, tok,
                                                  window=window)
            state = state_lane_select(active, new_state, state)
        if frow is not None:
            logits = jnp.where(frow[:, None],
                               jnp.asarray(jnp.nan, logits.dtype), logits)
        finite = jnp.all(jnp.isfinite(logits), axis=-1)
        bad = active & ~finite
        # all-clean fast path: healthy blocks take the identity branch,
        # so the sentinel never perturbs a clean lane's masks or stream
        poison, active = jax.lax.cond(
            jnp.any(bad),
            lambda p, a: (p | bad, a & finite),
            lambda p, a: (p, a), poison, active)
        live = active & (rem > 0)
        emit = live & (tok != eos)
        rem = rem - emit.astype(rem.dtype)
        active = emit & (rem > 0)

        def sample(keys):
            ks = jax.vmap(jax.random.split)(keys)          # [B, 2, 2]
            nxt = _next_token_lanes(logits, ks[:, 1], temperature,
                                    top_k, top_p)
            return ks[:, 0], nxt

        def greedy(keys):
            return keys, jnp.argmax(logits, -1)

        keys, nxt = jax.lax.cond(sampled_any, sample, greedy, keys)
        return (state, nxt.astype(tok.dtype), active, rem, keys,
                poison), (tok, emit)

    poison = jnp.zeros(tok.shape, bool)
    carry = (state, tok, active, rem, keys, poison)
    if fault is None:
        step = lambda c, _: body(c, None)
        carry, (toks, emitted) = jax.lax.scan(step, carry, None,
                                              length=steps)
    else:
        fault = jnp.asarray(fault, bool)                   # [steps, B]
        carry, (toks, emitted) = jax.lax.scan(body, carry, fault)
    state, tok, active, rem, keys, poison = carry
    return state, tok, active, rem, keys, poison, toks, emitted


def decode_block_lanes_sharded(model: Model, mesh, params, state, tok,
                               active, rem, eos, keys, temperature,
                               top_k, top_p, fault=None, steps: int = 1,
                               window: Optional[int] = None):
    """`decode_block_lanes` over a lane batch sharded ``P("data")``.

    Lanes are independent — attention, sampling, and EOS/budget masking
    never read across the batch axis — so the block is a pure data-
    parallel map over shards. Wrapping the body in `shard_map` (rather
    than relying on SPMD propagation) pins that down: every shard runs
    the per-shard program on its own contiguous block of lanes, the
    all-greedy `lax.cond` fast path (`jnp.any(temperature > 0)`) stays
    a SHARD-LOCAL reduction instead of lowering to an all-reduce on a
    knob operand, and the compiled module carries ZERO collectives on
    cache/knob operands (asserted from the HLO in
    `tests/test_sharded_serve.py`, like the PR-7 aliasing guard).

    Per-shard per-lane math is the unsharded block's on fewer lanes. On
    CPU the sharded engine streams token-identically to the unsharded
    one (`tests/test_sharded_serve.py`); on a TPU the two sides admit in
    different prefill groups and their greedy streams can part, so
    `chip_smoke.py --chips 4` compares one decode step on a shared state.
    """
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from repro.runtime.sharding import lane_pspecs

    state_specs = lane_pspecs(state, mesh)
    lane = P("data")
    body = functools.partial(decode_block_lanes, model, steps=steps,
                             window=window)
    in_specs = (P(), state_specs, lane, lane, lane, lane,
                P("data", None), lane, lane, lane)
    args = (params, state, tok, active, rem, eos, keys, temperature,
            top_k, top_p)
    if fault is not None:
        # the [steps, lanes] fault mask shards on its LANE axis, like
        # the per-step outputs — injection stays shard-local too
        in_specs += (P(None, "data"),)
        args += (fault,)
    fn = shard_map(
        body, mesh=mesh,
        in_specs=in_specs,
        out_specs=(state_specs, lane, lane, lane, P("data", None), lane,
                   P(None, "data"), P(None, "data")),
        check_vma=False)
    return fn(*args)


def donation_mode() -> str:
    """Whether jit buffer donation is honoured on this backend: ``"on"``,
    or ``"cpu-noop"`` where `_donate_argnums` silently disables it (the
    CPU runtime ignores donation). Recorded in `ServeLoop.counters` and
    the BENCH_* rows so CPU fill-sweep floors read as copy-bound rather
    than as regressions of the in-place decode path."""
    return "cpu-noop" if jax.default_backend() == "cpu" else "on"


def _donate_argnums(*argnums):
    # buffer donation is a no-op (and warns) on CPU; donate the decode
    # state + carries everywhere it is actually honoured
    return () if donation_mode() == "cpu-noop" else argnums


# Jitted entry points are cached on the Model's full constructor identity
# (config, prune, slots, remat knobs) — all hashable — NOT on Model
# instances: a Model-keyed cache would pin jit caches (and their
# params-sized constants) for every short-lived Model/ServeLoop ever
# created. Functionally identical Models share one compiled program.


def _model_key(model: Model):
    return (model.cfg, model.prune, model.decode_slots, model.remat,
            model.remat_policy)


def _rebuild(cfg, prune, slots, remat, remat_policy) -> Model:
    return Model(cfg, prune, remat=remat, decode_slots=slots,
                 remat_policy=remat_policy)


@functools.lru_cache(maxsize=64)
def _block_fn(key, steps: int, window: Optional[int] = None):
    model = _rebuild(*key)
    return jax.jit(functools.partial(decode_block, model, steps=steps,
                                     window=window),
                   donate_argnums=_donate_argnums(1, 2))


@functools.lru_cache(maxsize=64)
def _masked_block_fn(key, steps: int, temperature: float = 0.0,
                     top_k: int = 0, top_p: float = 0.0,
                     window: Optional[int] = None):
    # keyed on `steps` (+ the static sampling knobs + the slot window)
    # ONLY: eos and the PRNG key are runtime arguments, so one compiled
    # program serves every (steps, eos) combination instead of one per
    # pair. Windows are powers of two (core/cache.decode_window), so the
    # window axis adds at most log2(slots) programs per steps value.
    model = _rebuild(*key)
    fn = functools.partial(decode_block_masked, model, steps=steps,
                           temperature=temperature, top_k=top_k,
                           top_p=top_p, window=window)
    return jax.jit(fn, donate_argnums=_donate_argnums(1, 2, 3, 4, 6))


@functools.lru_cache(maxsize=64)
def _lanes_block_fn(key, steps: int, window: Optional[int] = None,
                    mesh=None):
    # the engine's decode block — keyed on (steps, window[, mesh]) ONLY.
    # eos, the per-lane PRNG carries, and every sampling knob are runtime
    # [lanes]-shaped arguments, so one compiled program serves arbitrary
    # per-lane knob mixes (the windows axis still adds at most
    # log2(slots) programs per steps value). The scan carries (state,
    # tok, active, rem, keys) are donated wherever donation is honoured.
    # With a mesh the body runs under `shard_map` over the "data" axis —
    # same runtime-knob contract, one collective-free program per shard
    # (jax.sharding.Mesh is hashable, so it keys the same lru cache).
    model = _rebuild(*key)
    if mesh is None:
        fn = functools.partial(decode_block_lanes, model, steps=steps,
                               window=window)
    else:
        fn = functools.partial(decode_block_lanes_sharded, model, mesh,
                               steps=steps, window=window)
    return jax.jit(fn, donate_argnums=_donate_argnums(1, 2, 3, 4, 6))


@functools.lru_cache(maxsize=32)
def _lane_slice_fn(key):
    # preemption capture: one batch-1 DecodeState slice per model key
    # (the lane index is traced — one program covers every lane)
    del key
    return jax.jit(state_lane_slice)


def _resume_lane_state(state, tok, lane, fresh, next_tok):
    """Preemption resume: splice the captured batch-1 state back into a
    free lane and restore its carried (not-yet-emitted) next token —
    the exact inverse of the `_lane_slice_fn` capture, so the resumed
    stream continues token-identically (state/tok donated in place)."""
    state = state_lane_insert(state, lane, fresh)
    tok = tok.at[lane].set(next_tok.astype(tok.dtype))
    return state, tok


@functools.lru_cache(maxsize=4)
def _resume_fn():
    return jax.jit(_resume_lane_state,
                   donate_argnums=_donate_argnums(0, 1))


@functools.lru_cache(maxsize=32)
def _prefill_fn(key):
    return jax.jit(_rebuild(*key).prefill)


@functools.lru_cache(maxsize=32)
def _prefill_one_fn(key):
    return jax.jit(_rebuild(*key).prefill_one)


@functools.lru_cache(maxsize=32)
def _prefill_group_fn(key):
    return jax.jit(_rebuild(*key).prefill_group)


@functools.lru_cache(maxsize=32)
def _decode_step_fn(key):
    return jax.jit(_rebuild(*key).decode_step)


@functools.lru_cache(maxsize=32)
def _prefill_chunk_fn(key):
    # the workspace is rewritten every chunk — donate it in place
    return jax.jit(_rebuild(*key).prefill_chunk,
                   donate_argnums=_donate_argnums(1))


@functools.lru_cache(maxsize=32)
def _prefill_finalize_fn(key):
    return jax.jit(_rebuild(*key).prefill_finalize,
                   donate_argnums=_donate_argnums(1))


@functools.lru_cache(maxsize=32)
def _resume_chunk_fn(key):
    # one program per (donor depth, workspace width) pair — both shape
    # axes are bounded by the bucket grid over the chunk grid
    return jax.jit(_rebuild(*key).resume_prefill_chunk_state,
                   static_argnums=(3,))


def _jit_decode_block(model: Model, steps: int):
    return _block_fn(_model_key(model), steps)


def _admit_lane_state(state, tok, lane, fresh, logits, key,
                      temperature, top_k, top_p):
    """One-dispatch admission: splice `fresh` into `lane` and seed its
    first token from the prefill logits — via the engine's vectorized
    next-token rule, so sampling covers the FIRST generated token too.
    temperature/top_k/top_p are [1]-shaped RUNTIME arrays: one compiled
    program per bucket shape serves every override value (state/tok
    donated in place; key unused when the row is greedy)."""
    state = state_lane_insert(state, lane, fresh)
    seed = _next_token_lanes(logits[None], key[None], temperature,
                             top_k, top_p)[0]
    tok = tok.at[lane].set(seed.astype(tok.dtype))
    return state, tok


@functools.lru_cache(maxsize=2)
def _admit_fn():
    return jax.jit(_admit_lane_state,
                   donate_argnums=_donate_argnums(0, 1))


def _admit_group_state(state, tok, src, fresh, logits, keys,
                       temperature, top_k, top_p):
    """One-dispatch grouped admission: splice every mapped row of the
    batch-G `fresh` state into the live state (`lanes_insert` over the
    whole pytree) and seed each spliced lane's first token from its row
    of the group-prefill logits. keys [G, 2] and the [G]-shaped sampling
    knobs are RUNTIME arrays — each row draws from its own request's
    stream, and knob values never recompile. `src` maps live lane ->
    fresh row (-1 = lane untouched); state/tok donated in place."""
    state = state_lanes_insert(state, src, fresh)
    seeded = _next_token_lanes(logits, keys, temperature, top_k,
                               top_p)                              # [G]
    picked = jnp.take(seeded.astype(tok.dtype), jnp.maximum(src, 0))
    tok = jnp.where(src >= 0, picked, tok)
    return state, tok


@functools.lru_cache(maxsize=2)
def _admit_group_fn():
    return jax.jit(_admit_group_state,
                   donate_argnums=_donate_argnums(0, 1))


def generate_scan(model: Model, params, batch, steps: int):
    """lax.scan'd decode loop (single dispatch; production serving path).

    The decode block is jitted with the (state, token) carry donated; under
    an outer jit the inner jit inlines and the whole call stays traceable.
    """
    logits, state = _prefill_fn(_model_key(model))(params, batch)
    tok0 = jnp.argmax(logits, -1)
    state, _, toks = _jit_decode_block(model, steps)(params, state, tok0)
    return toks.swapaxes(0, 1), state


# ---------------------------------------------------------------------------
# Requests + per-request serving metrics
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling override (same knobs as the loop-level
    `temperature`/`top_k`/`top_p`, plus an optional per-request stop
    token `eos`). Honoured across the request's WHOLE stream: the
    admission-seeded first token and every scanned decode step — the
    block's knobs are [lanes]-shaped runtime arrays, so arbitrary
    overrides share one compiled program and never recompile. Requests
    carrying an override are still admitted solo (the seeding draw is
    per-request), then decode mixed with everyone else."""
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 0.0
    eos: Optional[int] = None        # None → the loop's eos


@dataclasses.dataclass(eq=False, kw_only=True)
class Request:
    """One generation request (keyword-only; `submit()` assigns `rid`).

    `arrival` is seconds from `run()` start (0 = already waiting);
    `submit()` keeps the queue arrival-ordered. `sampling` overrides the
    loop's sampling knobs for this request's whole stream (seeded first
    token + every scanned step); `sample_seed` pins its PRNG stream
    (both force solo admission — the seeding draw is per-request — but
    decode runs mixed). `priority` (higher = more urgent, default 0)
    picks the scheduling class: higher classes are admitted first and
    may PREEMPT the lowest-priority active lane when no lane is free
    (the victim's state is captured and it resumes token-identically
    later). `reuse_prefix=False` opts the request out of the prefix
    cache in both directions: its admission never matches a cached
    prefix and its prefill is never inserted as a donor.
    `deadline_s` is a completion deadline in seconds from ARRIVAL: a
    request still waiting or still decoding when it expires resolves
    with outcome ``"deadline"`` (partial tokens kept; its lane frees at
    the next block boundary). `RequestHandle.cancel()` resolves the
    same way with outcome ``"cancelled"``.
    Identity-compared (eq=False): the scheduler removes grouped requests
    from the queue by identity, and field equality over an ndarray
    prompt is ill-defined anyway."""
    prompt: np.ndarray
    max_new: Optional[int] = None        # None → the loop's default
    arrival: float = 0.0
    sample_seed: Optional[int] = None
    sampling: Optional[SamplingParams] = None
    priority: int = 0
    reuse_prefix: bool = True
    deadline_s: Optional[float] = None   # completion deadline from arrival
    # engine-assigned fields — never pass these to the constructor
    rid: int = -1
    bucket: int = 0            # memoized pad width under the loop's grid
    admitted: bool = False     # lazy-prune marker for the FIFO-order deque
    resume: Optional["_ResumeState"] = None   # set while preempted
    cancelled: bool = False    # set by RequestHandle.cancel()
    retries: int = 0           # quarantine retries consumed so far
    legacy: bool = False       # came through a deprecated surface
    # first-admission PRNG draw, memoized so a quarantine RETRY replays
    # the identical sampled stream even when the seed came from the loop
    # stream (see `_seed_keys`) — never pass to the constructor either
    seed_keys: Optional[tuple] = None


class RequestHandle:
    """Ticket returned by `ServeLoop.submit(Request(...))`: a live view
    onto one request's progress (`done`, `tokens`, `stats`) without
    holding any engine state of its own."""
    __slots__ = ("rid", "_loop")

    def __init__(self, loop: "ServeLoop", rid: int):
        self.rid = rid
        self._loop = loop

    @property
    def stats(self) -> "RequestStats":
        return self._loop.stats[self.rid]

    @property
    def done(self) -> bool:
        return self.rid in self._loop._finished

    @property
    def tokens(self) -> List[int]:
        """Generated token ids so far (complete once `done`)."""
        return list(self.stats.tokens)

    @property
    def outcome(self) -> Optional[str]:
        """Terminal outcome — ``"done" | "cancelled" | "deadline" |
        "rejected" | "failed"`` — or None while the request is live."""
        return self.stats.outcome if self.done else None

    def cancel(self) -> bool:
        """Request cancellation. Returns True if the request was still
        live (it resolves with outcome ``"cancelled"`` at the next
        scheduler round — a decoding lane frees at the next block
        boundary); False if it already reached a terminal outcome."""
        return self._loop.cancel(self.rid)

    def __repr__(self) -> str:
        return f"RequestHandle(rid={self.rid}, done={self.done})"


@dataclasses.dataclass
class RequestStats:
    rid: int
    prompt_len: int
    max_new: int
    lane: int = -1
    tokens: List[int] = dataclasses.field(default_factory=list)
    t_arrival: float = 0.0     # run-relative seconds
    t_admit: float = 0.0       # prefilled + spliced into a lane; under
    #                            chunked admission this is when the LAST
    #                            prefill slice finished, so ttft still
    #                            covers the whole (time-sliced) prefill
    t_first: float = 0.0       # first generated token on the host
    t_done: float = 0.0
    occupancy: float = 0.0     # mean cache fill fraction at completion
    bucket: int = 0            # padded prefill width (== prompt_len unbucketed)
    prefill_chunks: int = 1    # dispatches the prefill was sliced into
    admit_seq: int = -1        # admission order (0 = admitted first)
    group_size: int = 1        # requests sharing this admission dispatch
    prefix_tokens: int = 0     # prompt tokens served from the prefix cache
    prefix_exact: bool = False  # whole prompt hit (state splice, no prefill)
    priority: int = 0          # scheduling class (higher = more urgent)
    preemptions: int = 0       # times this request was evicted + requeued
    outcome: str = "done"      # terminal: done|cancelled|deadline|rejected|failed
    detail: str = ""           # human-readable reason for a non-done outcome
    retries: int = 0           # quarantine retries this request consumed
    retry_after: float = 0.0   # suggested resubmit delay (outcome "rejected")
    degraded: bool = False     # admitted with a degraded-mode budget cap

    @property
    def latency(self) -> float:
        return self.t_done - self.t_arrival

    @property
    def ttft(self) -> float:
        """Time to first token (prefill-only requests: to prefill done)."""
        return self.t_first - self.t_arrival

    @property
    def decode_tps(self) -> float:
        return len(self.tokens) / max(self.t_done - self.t_admit, 1e-9)


@dataclasses.dataclass
class _ResumeState:
    """One preempted lane, captured exact to the token: the batch-1
    DecodeState slice (`_lane_slice_fn`), the carried not-yet-emitted
    next token, the unspent budget, the lane's PRNG carry, and the
    tokens emitted so far. `_admit_resumed` splices it back with zero
    prefill work; because the block advances a lane's key once per
    resident step, the resumed stream is token-identical to an
    uninterrupted run — greedy AND seeded-sampled lanes alike."""
    state: Any                 # batch-1 DecodeState (device)
    tok: int                   # next token to emit (block carry)
    rem: int                   # unspent budget
    key: np.ndarray            # [2] uint32 per-lane PRNG carry
    outputs: List[int]         # tokens emitted before the eviction


@dataclasses.dataclass
class _ChunkedPrefill:
    """Host-side progress of one in-flight time-sliced prefill."""
    req: Request
    lane: int
    bucket: int
    padded: np.ndarray
    pstate: Any                # PrefillChunkState (device)
    n_chunks: int
    next_chunk: int = 0
    x_last: Any = None         # final-stack hidden of the latest chunk
    base: int = 0              # rows [0, base) came from a prefix-cache donor
    collect: bool = False      # snapshot chunk boundaries for the trie
    # (boundary q, host acc[:, :, :q]) — acc is only valid at its exact
    # boundary (each column keeps absorbing mass from later query rows),
    # so every boundary stores its own full-prefix copy; K/V rows are
    # write-once, so ONE workspace snapshot at finalize covers them all
    snap_acc: List[Tuple[int, np.ndarray]] = dataclasses.field(
        default_factory=list)


class ServeLoop:
    """Lane-granular continuous batching: fixed decode lanes + request queue.

    New-style use::

        loop = ServeLoop(model, params, lanes=4, eos=2, block=8)
        h_a = loop.submit(Request(prompt=prompt_a, max_new=64))
        h_b = loop.submit(Request(prompt=prompt_b, max_new=16,
                                  sampling=SamplingParams(temperature=0.7),
                                  sample_seed=7))
        stats = loop.run()                    # List[RequestStats]
        h_a.done, h_a.tokens                  # per-request progress view

    Lanes are freed on EOS/budget **in-device** and refilled from the
    queue mid-flight. The positional `submit(prompt, max_new, arrival)`
    shim and the legacy all-lanes API (`admit(prompts)` +
    `step()`/`step_block()`) survive with a `DeprecationWarning` and
    drive the same engine (the legacy admit does a single full-batch
    prefill).

    **Prefix caching** (`prefix_cache_bytes > 0`). Admission consults a
    host-side radix-trie prefix cache (`launch/prefix_cache.py`) before
    touching the device. An exact-prompt hit splices the cached
    finalized DecodeState straight into the free lane — zero prefill
    dispatches, any policy/dtype. A shared-prefix hit (chunked-prefill
    path only) copies cached PRE-pruning workspace rows into a fresh
    chunk workspace (`Model.resume_prefill_chunk_state`) and dispatches
    only the suffix slices; because those rows/column-sums depend only
    on the shared tokens, the result is BIT-IDENTICAL to prefilling the
    whole prompt from scratch — for bf16 and int8 caches alike (the
    snapshot predates quantization and the slot rewrite). Completed
    prefills are inserted back: the finalized state always, plus
    per-chunk-boundary rows donors along the sliced path, and a rows
    donor derived from a finalized state when the static pruning left it
    slot-aligned (`surgery.cache_prefix_rows`) — a pruned layout is
    refused (its rows are a position-scattered subset, not the raw
    prefix). Eviction is LRU under the byte budget. Per-request opt-out:
    `Request(reuse_prefix=False)`. The `counters` dict tracks
    lookups/hits/copies/tokens-reused; `aggregate()` adds
    `prefix_hit_rate` and `prefix_dedup_ratio`.

    **Grouped admission (default).** At each admission point the
    scheduler collects every already-arrived queue request that pads to
    the SAME bucket (up to the number of free lanes) and admits the whole
    group with ONE batched prefill dispatch (`Model.prefill_group`) plus
    ONE vectorized multi-lane splice (`transformer.lanes_insert` over the
    whole DecodeState pytree) — replacing G (prefill_one + lane_insert)
    dispatch pairs. Under load (more arrived requests than free lanes)
    the group is chosen **shortest-bucket-first**, so a burst of short
    prompts is never starved behind one long arrival — bounded by aging
    (`max_head_skips`: after the FIFO head is passed over that many
    rounds in a row its bucket is forced, so long prompts can't starve
    indefinitely either); off load the FIFO head always leads the
    admission, with same-bucket followers riding along in its group (a
    later same-bucket arrival can therefore be admitted ahead of an
    earlier different-bucket one — order is FIFO per bucket, not
    globally). The group
    prefill is padded up to the next power-of-two row count (duplicating
    a real row; surplus rows are dropped by the splice's source map), so
    the jit cache holds at most log2(lanes)+1 group programs per bucket
    while a small group never pays a full lanes-row prefill. A grouped
    admission is bit-identical to admitting the same requests
    sequentially — it is purely a dispatch-count optimization
    (`group_admit=False` restores the sequential path; the `counters`
    dict tracks prefill/admit/decode dispatches either way).

    `block` sets how many tokens each dispatch decodes: the scanned block
    amortizes launch overhead across `block` tokens, at the cost of up to
    `block - 1` speculative steps after a lane hits EOS/budget (their
    outputs are masked out in-device).

    **Bucketed prefill (default).** Prompts are right-padded to a small
    doubling bucket grid and prefilled with a true-length mask, so the
    prefill jit cache holds at most len(buckets) programs no matter how
    many distinct lengths the traffic carries — mixed traffic no longer
    stalls on per-length recompiles. A bucketed prefill is bit-identical
    to a same-bucket full-batch prefill and matches an exact-length
    prefill to float-association noise (~1e-7; see `Model.prefill`).
    `buckets="auto"` uses powers of two from MIN_BUCKET; pass an explicit
    sorted tuple to pin the grid, or `buckets=None` for legacy
    exact-length prefills (one compile per distinct length).

    **Windowed decode (default).** Before each decode block the engine
    reads the active lanes' cache fills (a [L, lanes] int32 — a few
    hundred bytes of host traffic it pays anyway when it consumes the
    block's tokens) and dispatches the block over the smallest
    power-of-two slot window covering `max(fill) + block` (
    `core/cache.decode_window`). Live slots always occupy the fill
    prefix, so the windowed block is bit-identical to full width while
    every stage — CAM scoring over the mirror, the top-k race, the
    winner gather, exact attention, and the charge-domain accumulation —
    touches O(window) instead of O(slots) bytes: decode cost tracks the
    LIVE context, which is the paper's premise. The window only grows
    back to full width when a lane actually approaches the slot budget
    (where eviction/ring-wrap engages), and the pow2 grid bounds the jit
    cache at log2(slots) extra programs (`counters["decode_windows"]`
    counts the distinct windows this loop compiled). `window=None`
    disables it (always full width).

    **Sampling** (`temperature`, `top_k`, `top_p`): temperature > 0
    switches the engine from argmax to categorical sampling over
    logits/temperature (optionally truncated to the top_k most likely
    tokens and/or the minimal top-p nucleus per lane, top-k first) —
    covering the admission-seeded FIRST token as well as the scanned
    decode steps. The loop scalars are just per-lane DEFAULTS: every
    knob (plus the stop token and the PRNG carry) lives in a
    [lanes]-shaped runtime array fed to `decode_block_lanes`, and a
    request's `SamplingParams` override rides its lane for the whole
    stream. Each lane carries its OWN PRNG key (seeded from
    `sample_seed` pins via `jax.random.PRNGKey(seed)`, otherwise drawn
    from the loop stream at admission) and the block splits it once per
    scanned step — so a seeded request's sampled stream depends only on
    (seed, tokens generated): identical whether it runs solo, grouped,
    on any lane, or across a preempt/resume boundary. Greedy
    (temperature=0, the default) stays bitwise-unchanged and carries no
    RNG; knob values never recompile the block.

    **Drain-aware reservation + priority preemption.** See
    `predicted_free_blocks`, `_reserve`, and `_try_preempt`:
    with every lane busy, the scheduler predicts which lanes free
    within `reserve_blocks` decode blocks (remaining budgets bounded by
    the observed mean EOS-termination length) and pops that many queued
    requests ahead of time, so the grouped prefill fires the moment the
    lanes actually free; and a waiting request whose `priority` strictly
    outranks the lowest-priority active lane evicts that lane
    (`lane_slice` capture → requeue → token-identical resume). The
    `preemptions`/`reservations`/`reserved_admits` counters track both.

    **Scheduler cost.** The queue is per-bucket FIFO deques plus an
    arrival spill list: each `schedule()` round drains newly-arrived
    requests into their bucket deque (O(1) each, amortized), then picks
    the target bucket by scanning the O(len(buckets)) non-empty deque
    heads — NOT the O(arrived-requests) queue — so admission stays flat
    under a million-deep backlog. FIFO order within a bucket is the
    deque order; the global-FIFO head used by the off-load path and the
    aging bound is tracked with a lazily-pruned arrival-order deque.

    **Fault tolerance & graceful degradation.** `Request(deadline_s=…)`
    and `RequestHandle.cancel()` terminate waiting or decoding requests
    with outcomes ``"deadline"``/``"cancelled"`` (active lanes free at
    the next block boundary through the in-device active mask — no
    recompile; partial tokens kept). The decode block's non-finite
    sentinel flags lanes whose logits went NaN/Inf; the loop quarantines
    them and retries the request by full deterministic replay (memoized
    admission seed → token-identical stream, greedy AND sampled), up to
    `max_retries` before outcome ``"failed"``. `max_queue` bounds the
    waiting population: an overflowing submit is rejected — or sheds a
    strictly lower-priority waiter — with outcome ``"rejected"`` and a
    `retry_after` hint. A `degrade` ladder steps the engine down under
    sustained pressure (smaller decode block → tighter decode window,
    then budget caps for new admissions) and back up on hysteresis;
    token VALUES never change, only schedule shape. `chaos` attaches a
    deterministic `runtime.chaos.ChaosConfig` fault injector (logit
    corruption / dispatch stalls / shard blackouts) for testing every
    path above. Un-admittable submissions (empty prompt, `max_new<=0`,
    prompt exceeding a pinned bucket grid) resolve to structured
    rejections at submit, and `run()` is hang-proof: a stuck queue
    resolves to rejections instead of spinning (`_fail_stuck`).

    **Chunked-prefill admission** (`chunk_prefill=C`, Sarathi-style): a
    prompt whose bucket exceeds C is prefilled in C-token slices that
    interleave with decode blocks — one slice, one decode block, … — so a
    long arrival no longer head-of-line-blocks live decode lanes. The
    sliced prefill streams per-layer K/V + accumulated column sums into a
    fixed-size workspace and finalizes with the same one-shot static
    pruning; `t_admit`/ttft cover the whole sliced prefill. Requires
    `model.supports_chunked_prefill()` (plain attention stacks); others
    fall back to whole-bucket admission.
    """

    def __init__(self, model: Model, params, lanes: int,
                 prompt_len: Optional[int] = None, max_new: int = 64,
                 eos: int = -1, block: int = 1,
                 buckets: Union[str, Sequence[int], None] = "auto",
                 chunk_prefill: int = 0, group_admit: bool = True,
                 max_head_skips: int = 8, reserve_blocks: int = 1,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 0.0, sample_seed: int = 0,
                 window: Union[str, None] = "auto",
                 window_grid: Union[str, int] = "pow2",
                 prefix_cache_bytes: int = 0,
                 mesh=None, max_retries: int = 2, max_queue: int = 0,
                 degrade: Union[str, Sequence[Dict[str, int]], None] = None,
                 degrade_high: int = 0, degrade_low: int = 0,
                 chaos=None):
        self.model = model
        self.params = params
        self.lanes = lanes
        # Data-sharded lane parallelism: `mesh` is a 1-D jax Mesh over a
        # "data" axis (or an int shard count — `launch.mesh.make_serve_mesh`
        # builds the mesh). The lane batch, per-lane knob arrays, and the
        # stacked DecodeState shard P("data") on the lane axis; decode
        # dispatches ONE collective-free per-shard program
        # (`decode_block_lanes_sharded`) and admission works one shard's
        # lane rows at a time so splice scatters stay shard-local.
        if isinstance(mesh, int):
            from repro.launch.mesh import make_serve_mesh
            mesh = make_serve_mesh(mesh)
        self.mesh = mesh
        self.shards = 1
        if mesh is not None:
            assert "data" in mesh.shape, f"serve mesh needs a data axis: {mesh}"
            assert mesh.size == mesh.shape["data"], (
                f"serve mesh must be 1-D over data: {mesh}")
            self.shards = int(mesh.shape["data"])
            assert lanes % self.shards == 0, (
                f"lanes={lanes} not divisible by {self.shards} shards")
            # replicate the weights once: every shard reads all of them,
            # and weights left on one device would be re-broadcast to the
            # mesh on every dispatch. A caller that keeps its own reference
            # to single-device params keeps that copy as well (one more
            # copy of the weights on that device).
            from jax.sharding import NamedSharding, PartitionSpec as P
            self.params = jax.device_put(params, NamedSharding(mesh, P()))
        self.lanes_per_shard = lanes // self.shards
        self._shard_tokens = np.zeros(self.shards, np.int64)
        self._state_shardings = None          # built lazily with the state
        self.max_new = max_new
        self.eos = eos
        self.prompt_len = prompt_len          # legacy hint; not enforced
        self.block = max(1, block)
        self.buckets = (tuple(buckets)
                        if isinstance(buckets, (list, tuple)) else buckets)
        if self.buckets is not None and not model.supports_bucketed_prefill():
            self.buckets = None               # documented fallback
        self.chunk_prefill = max(0, chunk_prefill)
        if self.chunk_prefill and not model.supports_chunked_prefill():
            self.chunk_prefill = 0            # documented fallback
        self.group_admit = bool(group_admit)
        self.max_head_skips = max(0, max_head_skips)
        self._head_skips = 0
        # drain-aware reservation horizon, in decode blocks (0 = off)
        self.reserve_blocks = max(0, reserve_blocks)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        assert window in ("auto", None), window   # no silent full-width
        self.window = window                  # "auto" | None
        # window quantization grid: "pow2" (≤ log2(slots) programs) |
        # "chunk" (multiples of cfg.attn_chunk) | int (multiples of it) —
        # see core/cache.decode_window
        self.window_grid: Union[str, int] = (
            model.cfg.attn_chunk if window_grid == "chunk" else window_grid)
        assert (self.window_grid == "pow2"
                or int(self.window_grid) > 0), window_grid
        self._windows: set = set()            # distinct windows dispatched
        self._key = jax.random.PRNGKey(sample_seed)
        self._prefill = _prefill_fn(_model_key(model))
        self._prefill_one = _prefill_one_fn(_model_key(model))
        self._prefill_group = _prefill_group_fn(_model_key(model))
        self._chunk = _prefill_chunk_fn(_model_key(model))
        self._finalize = _prefill_finalize_fn(_model_key(model))
        self._resume = _resume_chunk_fn(_model_key(model))
        self.state = None
        self.tok = None
        self.active = np.zeros(lanes, bool)
        self.remaining = np.zeros(lanes, np.int32)
        self.outputs: List[List[int]] = [[] for _ in range(lanes)]
        self.done: List[List[int]] = []
        # Per-lane serving knobs — RUNTIME arrays fed to the decode
        # block every dispatch (loop scalars are just the defaults a
        # request without overrides inherits). `_lane_keys` holds the
        # per-lane PRNG carries the block splits once per scanned step.
        self.lane_temp = np.full(lanes, self.temperature, np.float32)
        self.lane_topk = np.full(lanes, self.top_k, np.int32)
        self.lane_topp = np.full(lanes, self.top_p, np.float32)
        self.lane_eos = np.full(lanes, self.eos, np.int32)
        self._lane_keys = np.broadcast_to(
            np.asarray(self._key, np.uint32), (lanes, 2)).copy()
        self._lane_prio = np.zeros(lanes, np.int64)
        # Scheduler state: `_arrivals` holds not-yet-arrived requests in
        # arrival order; once arrived they move into their bucket's FIFO
        # deque (`_bucket_q`) and onto `_arrived_fifo` (arrival order,
        # admitted entries lazily pruned — Request.admitted flags them).
        self._arrivals: Deque[Request] = deque()
        # keyed by (-priority, bucket): min() picks the highest class
        # first, shortest bucket within it — all-default-priority
        # traffic reduces to plain shortest-bucket ordering
        self._bucket_q: Dict[Tuple[int, int], Deque[Request]] = {}
        self._arrived_fifo: Deque[Request] = deque()
        self._arrived_count = 0
        self._reserved: Deque[Request] = deque()   # drain-aware pre-group
        self._req_by_rid: Dict[int, Request] = {}
        self._drained_hwm = float("-inf")     # newest arrival drained
        self.stats: Dict[int, RequestStats] = {}
        self.completed: List[RequestStats] = []
        self._lane_rid: List[Optional[int]] = [None] * lanes
        self._next_rid = 0
        self._t0: Optional[float] = None
        self._pending: Optional[_ChunkedPrefill] = None
        self._prefill_shapes: set = set()     # (kind, width) seen this loop
        self._admit_seq = 0
        # drain-prediction inputs: generated lengths of EOS-terminated
        # requests vs. count of budget-exhausted ones (see
        # `predicted_free_blocks`)
        self._eos_lens: List[int] = []
        self._budget_done = 0
        self._finished: set = set()           # rids with t_done recorded
        self.prefix_cache: Optional[PrefixCache] = (
            PrefixCache(prefix_cache_bytes) if prefix_cache_bytes > 0
            else None)
        # suffix-resume (rows) donors ride the chunked-prefill path; the
        # resume grid must equal the donor prefill's accumulation grid
        # for the f32 column sums to match bit-for-bit, and finalized
        # states whose acc came from a whole-bucket prefill accumulate
        # on cfg.attn_chunk — so derive rows from them only when the
        # loop's chunk size IS cfg.attn_chunk
        self._rows_reuse = (self.prefix_cache is not None
                            and self.chunk_prefill > 0)
        # dispatch accounting: how many device calls each stage issued
        # (prefill_dispatches counts whole-prompt/group prefills and
        # chunked finalizes; chunk slices are tallied separately)
        # `donation` is a string-valued counter: whether the donated
        # decode-block buffers are actually reused on this backend (CPU
        # silently no-ops donation, so its fill-sweep floor is copy-bound)
        self.counters: Dict[str, Any] = {
            "prefill_dispatches": 0, "admit_dispatches": 0,
            "chunk_dispatches": 0, "decode_blocks": 0,
            "grouped_admissions": 0, "grouped_requests": 0,
            "decode_windows": 0, "decode_block_programs": 0,
            "preemptions": 0, "reservations": 0, "reserved_admits": 0,
            "donation": donation_mode(),
            "prefix_lookups": 0, "prefix_hits": 0,
            "prefix_exact_hits": 0, "prefix_copies": 0,
            "prefix_tokens_reused": 0,
            "prefix_inserts": 0, "prefix_evictions": 0,
            "preempt_cache_inserts": 0,
        }
        # per-(priority, bucket) EOS-length samples — drain prediction
        # uses a class-local mean once a class has >= 4 EOS completions,
        # so short bursty and long bulk traffic stop polluting each
        # other's free-lane forecasts (global mean is the fallback)
        self._eos_by_class: Dict[Tuple[int, int], List[int]] = {}
        # -- fault tolerance -------------------------------------------------
        # quarantine retries per request before outcome "failed"
        self.max_retries = max(0, max_retries)
        # bounded admission: > 0 caps the WAITING population; an
        # overflowing submit is rejected (or sheds a strictly
        # lower-priority waiter) with outcome "rejected" + retry_after
        self.max_queue = max(0, max_queue)
        # degradation ladder: each level maps to overrides applied under
        # queue pressure — "block" (smaller decode block → tighter decode
        # window via `decode_window(fill, steps)`, token values
        # UNCHANGED) and "max_new_cap" (budget cap for NEW admissions).
        # None disables; "auto" derives a two-level ladder from `block`.
        if degrade == "auto":
            degrade = ({"block": max(1, self.block // 2)},
                       {"block": max(1, self.block // 4),
                        "max_new_cap": 4 * self.block})
        self.degrade_ladder: Tuple[Dict[str, int], ...] = (
            tuple(degrade) if degrade else ())
        # pressure thresholds on the WAITING population (hysteresis:
        # step down at >= high with every lane busy, back up at <= low)
        self.degrade_high = degrade_high if degrade_high > 0 else 2 * lanes
        self.degrade_low = max(0, degrade_low)
        self._degrade_level = 0
        self.chaos = chaos            # Optional[runtime.chaos.ChaosConfig]
        self._rounds = 0              # scheduler rounds (run() iterations)
        self._blackout_on = False
        self._block_s_ema: Optional[float] = None  # wall secs / decode block
        self.counters.update({
            "deadline_expired": 0, "cancelled_requests": 0,
            "rejected_requests": 0, "shed_requests": 0,
            "quarantined_lanes": 0, "retried_requests": 0,
            "failed_requests": 0, "degrade_down": 0, "degrade_up": 0,
            "chaos_faults": 0, "chaos_stalls": 0, "chaos_blackouts": 0,
        })

    # -- time ----------------------------------------------------------------

    def _now(self) -> float:
        return 0.0 if self._t0 is None else time.monotonic() - self._t0

    # -- request intake ------------------------------------------------------

    def submit(self, request, max_new: Optional[int] = None,
               arrival: float = 0.0):
        """Queue one request.

        New style: ``submit(Request(prompt=..., max_new=...)) ->
        RequestHandle``. The positional form ``submit(prompt, max_new,
        arrival) -> rid`` is deprecated (it predates the Request
        dataclass being public API) and warns."""
        if isinstance(request, Request):
            if max_new is not None or arrival != 0.0:
                raise TypeError(
                    "submit(Request(...)) takes no extra arguments — set "
                    "max_new/arrival on the Request")
            return self._enqueue(request)
        warnings.warn(
            "submit(prompt, max_new, arrival) is deprecated; pass "
            "submit(Request(prompt=..., max_new=..., arrival=...)) and "
            "use the returned RequestHandle",
            DeprecationWarning, stacklevel=2)
        req = Request(prompt=np.asarray(request), max_new=max_new,
                      arrival=float(arrival), legacy=True)
        return self._enqueue(req).rid

    def _enqueue(self, req: Request) -> RequestHandle:
        if req.rid >= 0:
            raise ValueError(f"Request already submitted (rid={req.rid})")
        req.prompt = np.asarray(req.prompt)
        if req.max_new is None:
            req.max_new = self.max_new
        req.rid = self._next_rid
        self._next_rid += 1
        self._req_by_rid[req.rid] = req
        arrival = float(req.arrival)
        # un-admittable shapes resolve to a STRUCTURED rejection at
        # submit instead of wedging `run()` (outcome "rejected"). The
        # deprecated positional surface keeps its documented
        # prefill-only max_new=0 behaviour (outcome "done").
        reason = self._unadmittable(req)
        if reason is not None:
            return self._reject_new(req, reason)
        if self.max_queue and self._waiting_count() >= self.max_queue:
            victim = self._shed_candidate(req)
            if victim is None:
                return self._reject_new(req, "queue full", backpressure=True)
            self._shed(victim)
        req.bucket = self._bucket_of(req)     # memoized for the scheduler
        if arrival < self._drained_hwm:
            # backdated submit landing AMONG already-drained requests:
            # splice it into the arrived structures at its arrival rank
            # (O(arrived) — a rare replay/test path; the hot path below
            # stays O(1)/O(log)) so the global-FIFO head and the aging
            # bound keep protecting the true oldest request
            self._insert_arrived(req)
        elif self._arrivals and arrival < self._arrivals[-1].arrival:
            # keep arrival order (FIFO among ties) — the drain pops head
            idx = next(i for i, r in enumerate(self._arrivals)
                       if r.arrival > arrival)
            self._arrivals.insert(idx, req)
        else:
            self._arrivals.append(req)
        self.stats[req.rid] = RequestStats(req.rid, len(req.prompt),
                                           req.max_new, t_arrival=arrival,
                                           priority=req.priority)
        return RequestHandle(self, req.rid)

    # -- structured rejection + backpressure ---------------------------------

    def _unadmittable(self, req: Request) -> Optional[str]:
        """Reason this request can NEVER be served (reject at submit
        instead of wedging `run()` later), or None when admittable.
        Legacy-surface requests keep the documented prefill-only
        `max_new=0` behaviour and are never shape-rejected here."""
        if req.legacy:
            return None
        if len(req.prompt) == 0:
            return "empty prompt"
        if req.max_new <= 0:
            return "max_new <= 0 generates nothing (prefill-only runs " \
                   "ride the legacy surface)"
        if isinstance(self.buckets, tuple) and self.buckets \
                and len(req.prompt) > max(self.buckets):
            return (f"prompt length {len(req.prompt)} exceeds every "
                    f"bucket of the pinned grid {self.buckets}")
        return None

    def _waiting_count(self) -> int:
        """Current waiting population: arrived-but-unadmitted + future
        arrivals + drain-reserved (everything `max_queue` bounds)."""
        return (self._arrived_count + len(self._arrivals)
                + len(self._reserved))

    def _retry_after(self) -> float:
        """Suggested resubmit delay for a backpressure rejection: the
        waiting population's predicted drain time under the observed
        per-block wall clock (a coarse, monotonic-in-depth hint)."""
        blk = self._block_s_ema if self._block_s_ema is not None else 0.05
        depth = self._waiting_count() / max(self.lanes, 1)
        tokens = np.mean([r.max_new for r in self._arrived_fifo
                          if not r.admitted] or [self.max_new])
        return depth * math.ceil(float(tokens) / self.block) * blk

    def _finish_queued(self, req: Request, outcome: str,
                       detail: str = "") -> None:
        """Resolve a request that never reached (or no longer holds) a
        lane with a terminal outcome — the queued-side twin of
        `_finish_lane`."""
        st = self.stats[req.rid]
        now = self._now()
        if req.resume is not None:             # preempted mid-stream:
            st.tokens = list(req.resume.outputs)   # keep partial tokens
            req.resume = None
        st.outcome = outcome
        st.detail = detail
        st.t_done = max(now, st.t_arrival)
        if st.t_first < st.t_admit:
            st.t_first = st.t_done
        req.admitted = True                    # lazy-prune marker
        self.completed.append(st)
        self.done.append(st.tokens)
        self._finished.add(req.rid)
        self._req_by_rid.pop(req.rid, None)

    def _reject_new(self, req: Request, reason: str,
                    backpressure: bool = False) -> RequestHandle:
        """Resolve a just-submitted request as "rejected" without ever
        queueing it (structured refusal: the handle is immediately done,
        `stats.retry_after` hints when to resubmit under backpressure)."""
        self.stats[req.rid] = RequestStats(
            req.rid, len(req.prompt), max(req.max_new, 0),
            t_arrival=float(req.arrival), priority=req.priority)
        self.counters["rejected_requests"] += 1
        self._finish_queued(req, "rejected", reason)
        if backpressure:
            self.stats[req.rid].retry_after = self._retry_after()
        return RequestHandle(self, req.rid)

    def _shed_candidate(self, new: Request) -> Optional[Request]:
        """Lowest-priority waiter strictly below `new`'s class — the
        latest arrival in the worst waiting class (least invested) —
        or None when nothing outranks: then `new` itself is rejected.
        O(len(buckets) + future arrivals), not O(backlog)."""
        worst: Optional[Request] = None
        if self._bucket_q:
            key = max(self._bucket_q)          # (-prio, bucket): max = worst
            worst = self._bucket_q[key][-1]
        for r in self._arrivals:               # future arrivals spill list
            if worst is None or r.priority < worst.priority or (
                    r.priority == worst.priority
                    and r.arrival >= worst.arrival):
                worst = r
        if worst is None or worst.priority >= new.priority:
            return None
        return worst

    def _shed(self, victim: Request) -> None:
        """Drop one waiting request to make room (outcome "rejected",
        counted as shed; its handle stays valid)."""
        try:
            self._arrivals.remove(victim)
        except ValueError:
            dq = self._bucket_q.get(self._qkey(victim))
            dq.remove(victim)
            if not dq:
                del self._bucket_q[self._qkey(victim)]
            self._arrived_count -= 1
        self.counters["shed_requests"] += 1
        self.counters["rejected_requests"] += 1
        self._finish_queued(victim, "rejected", "shed under backpressure")
        self.stats[victim.rid].retry_after = self._retry_after()

    # -- cancellation + deadlines --------------------------------------------

    def cancel(self, rid: int) -> bool:
        """Flag one request for cancellation (see RequestHandle.cancel).
        Resolution happens at the next scheduler round: a waiting
        request resolves when popped (or swept), an active lane frees at
        the next block boundary through the in-device active mask."""
        if rid in self._finished:
            return False
        req = self._req_by_rid.get(rid)
        if req is None:
            return False
        req.cancelled = True
        return True

    def _deadline_over(self, req: Request, now: float) -> bool:
        return (req.deadline_s is not None
                and now >= self.stats[req.rid].t_arrival + req.deadline_s)

    def _resolve_dead(self, req: Request, now: Optional[float] = None
                      ) -> bool:
        """Resolve a WAITING request that was cancelled or whose
        deadline expired (True = it is gone; don't admit it). Called at
        every pop point so the scheduler's O(buckets) round never scans
        the backlog for corpses."""
        now = self._now() if now is None else now
        if req.cancelled:
            self.counters["cancelled_requests"] += 1
            self._finish_queued(req, "cancelled")
            return True
        if self._deadline_over(req, now):
            self.counters["deadline_expired"] += 1
            self._finish_queued(req, "deadline",
                                f"deadline_s={req.deadline_s} expired "
                                "before admission")
            return True
        return False

    def _sweep_lanes(self, now: float) -> None:
        """Terminate ACTIVE lanes whose request was cancelled or hit its
        deadline: clear the host active mask (the next dispatch's
        in-device mask drops their writes — no recompile) and finish the
        lane with partial tokens. Runs every scheduler round, so an
        expired lane frees within one decode block."""
        for lane in np.flatnonzero(self.active):
            lane = int(lane)
            rid = self._lane_rid[lane]
            req = self._req_by_rid.get(rid) if rid is not None else None
            if req is None:                    # legacy admit() batch
                continue
            if req.cancelled:
                self.counters["cancelled_requests"] += 1
                outcome, detail = "cancelled", ""
            elif self._deadline_over(req, now):
                self.counters["deadline_expired"] += 1
                outcome = "deadline"
                detail = f"deadline_s={req.deadline_s} expired mid-decode"
            else:
                continue
            self.active[lane] = False
            self.remaining[lane] = 0
            self._finish_lane(lane, now, outcome=outcome, detail=detail)

    def _qkey(self, req: Request) -> Tuple[int, int]:
        """Scheduling-class deque key: sorts as (-priority, bucket)."""
        return (-req.priority, req.bucket)

    def _insert_arrived(self, req: Request) -> None:
        """Insert at arrival rank (after ties) into the arrived deques."""
        def rank(dq):
            for i, r in enumerate(dq):
                if r.arrival > req.arrival:
                    return i
            return len(dq)
        self._arrived_fifo.insert(rank(self._arrived_fifo), req)
        dq = self._bucket_q.setdefault(self._qkey(req), deque())
        dq.insert(rank(dq), req)
        self._arrived_count += 1

    @property
    def queue(self) -> List[Request]:
        """Waiting (un-admitted) requests in arrival order — arrived
        first, then future arrivals. A snapshot view over the scheduler's
        per-bucket deques + arrival spill list (read-only)."""
        waiting = [r for r in self._arrived_fifo if not r.admitted]
        return waiting + list(self._arrivals)

    def _drain_arrivals(self, now: float) -> None:
        """Move every request whose arrival time has passed into its
        bucket's FIFO deque. O(newly arrived) — each request is moved
        exactly once over the loop's lifetime."""
        while self._arrivals and self._arrivals[0].arrival <= now:
            req = self._arrivals.popleft()
            self._bucket_q.setdefault(self._qkey(req), deque()).append(req)
            self._arrived_fifo.append(req)
            self._arrived_count += 1
            self._drained_hwm = max(self._drained_hwm, req.arrival)

    def _fifo_head(self) -> Optional[Request]:
        """Oldest arrived, un-admitted request (lazy-pruned deque head)."""
        fifo = self._arrived_fifo
        while fifo and fifo[0].admitted:
            fifo.popleft()
        return fifo[0] if fifo else None

    @staticmethod
    def _needs_solo(req: Request) -> bool:
        """Per-request sampling/seed overrides draw their seed at the
        admission-seeding dispatch, which is per-request — so such a
        request never shares a grouped admission (it still decodes mixed
        with everyone else). A preempted request resuming splices its
        captured state instead of prefilling, so it is always solo."""
        return (req.sampling is not None or req.sample_seed is not None
                or req.resume is not None)

    def _take_bucket(self, key: Tuple[int, int], n: int) -> List[Request]:
        """Pop up to `n` FIFO requests from one class deque; a request
        needing a solo admission (sampling overrides / a resume splice)
        terminates (or solely forms) the group."""
        dq = self._bucket_q.get(key)
        group: List[Request] = []
        while dq and len(group) < n:
            if group and self._needs_solo(dq[0]):
                break
            req = dq.popleft()
            req.admitted = True
            group.append(req)
            if self._needs_solo(req):
                break
        if dq is not None and not dq:
            del self._bucket_q[key]
        self._arrived_count -= len(group)
        return group

    def _take_reserved(self, n: int) -> List[Request]:
        """Pop a same-bucket prefix of the reservation queue (≤ n), with
        the same solo boundaries as `_take_bucket`."""
        rq = self._reserved
        group: List[Request] = []
        while rq and len(group) < n:
            if group and (self._needs_solo(rq[0])
                          or rq[0].bucket != group[0].bucket):
                break
            req = rq.popleft()
            group.append(req)
            if self._needs_solo(req):
                break
        return group

    # -- admission -----------------------------------------------------------

    def _ensure_state(self):
        if self.state is None:
            self.state = self.model.init_decode_state(self.lanes)
            self.tok = jnp.zeros((self.lanes,), jnp.int32)
            self._pin_state()

    def _lane_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P("data"))

    def _pin_state(self) -> None:
        """Re-commit the live state to the lane-sharded layout
        (`runtime.sharding.lane_pspecs`: DecodeState P("data") on the
        lane axis, tok P("data")). Admission/resume splices run as plain
        jits whose inferred output shardings may drift; pinning before
        each decode dispatch keeps the shard_map'd block's input layout
        stable so it compiles ONCE and never reshards mid-stream. A
        no-op without a mesh, and free when the layout already matches
        (device_put to an identical sharding is the identity)."""
        if self.mesh is None or self.state is None:
            return
        from repro.runtime.sharding import lane_shardings
        if self._state_shardings is None:
            self._state_shardings = lane_shardings(self.state, self.mesh)
        self.state = jax.device_put(self.state, self._state_shardings)
        self.tok = jax.device_put(self.tok, self._lane_sharding())

    def _padded_prompt(self, req: Request) -> Tuple[np.ndarray, int]:
        """(padded prompt, bucket width) under this loop's bucket policy."""
        prompt = np.asarray(req.prompt)
        if self.buckets is None:
            return prompt, len(prompt)
        grid = None if self.buckets == "auto" else self.buckets
        padded, _ = pad_to_bucket(prompt, grid)
        return padded, len(padded)

    def _bucket_of(self, req: Request) -> int:
        """Bucket width alone (no padding allocation — scheduler hot path)."""
        if self.buckets is None:
            return len(req.prompt)
        grid = None if self.buckets == "auto" else self.buckets
        return bucket_length(len(req.prompt), grid)

    def _admit_lane(self, lane: int, req: Request):
        """Prefill one request (whole-bucket) and splice it into `lane`.
        Consults the prefix cache for an exact-prompt hit first, and
        inserts the finished prefill back as a donor."""
        self._ensure_state()
        hit, _ = self._cache_match(req, rows_cap=None)
        if hit is not None:
            self._splice_cached(lane, req, hit)
            return
        padded, bucket = self._padded_prompt(req)
        if bucket == len(req.prompt) and self.buckets is None:
            self._prefill_shapes.add(("exact", bucket))
            logits, fresh = self._prefill_one(self.params, jnp.asarray(padded))
        else:
            self._prefill_shapes.add(("bucket", bucket))
            logits, fresh = self._prefill_one(
                self.params, jnp.asarray(padded),
                jnp.asarray(len(req.prompt), jnp.int32))
        self.counters["prefill_dispatches"] += 1
        self._splice(lane, req, logits, fresh, bucket=bucket)
        self._cache_insert_finalized(req, logits, fresh, bucket)

    def _sample_key(self):
        """Fresh subkey for an admission seed when sampling; when greedy
        the key is passed through untouched (and unused in-device), so
        the greedy stream stays bitwise-identical to pre-sampling code."""
        if self.temperature <= 0:
            return self._key
        self._key, sub = jax.random.split(self._key)
        return sub

    def _req_sampling(self, req: Request) -> Tuple[float, int, float]:
        """(temperature, top_k, top_p) for this request's seeded first
        token: its SamplingParams override, else the loop knobs."""
        sp = req.sampling
        if sp is None:
            return self.temperature, self.top_k, self.top_p
        return float(sp.temperature), int(sp.top_k), float(sp.top_p)

    def _seed_keys(self, req: Request):
        """(admission draw key, lane PRNG carry) for one request. A
        pinned `sample_seed` derives both from PRNGKey(seed); otherwise
        from the loop stream — advanced only when the effective
        temperature actually samples, so greedy admissions leave the
        stream untouched (and both keys unused in-device). The lane
        carry is what the decode block splits once per scanned step:
        a seeded request's sampled stream is a function of (seed,
        tokens generated) alone — identical solo, grouped, on any lane,
        or across a preempt/resume boundary. The pair is memoized on
        the Request at first admission so a quarantine RETRY replays
        the identical stream even when the seed came from the loop
        stream (a re-draw would silently fork the tokens)."""
        if self._req_sampling(req)[0] <= 0:
            return self._key, self._key        # unused in-device
        if req.seed_keys is not None:
            return req.seed_keys
        if req.sample_seed is not None:
            base = jax.random.PRNGKey(req.sample_seed)
        else:
            self._key, base = jax.random.split(self._key)
        req.seed_keys = tuple(jax.random.split(base))
        return req.seed_keys

    def _splice(self, lane: int, req: Request, logits, fresh,
                bucket: int, prefill_chunks: int = 1,
                prefix_tokens: int = 0):
        """Insert a freshly prefilled batch-1 state into a free lane."""
        t, k, p = self._req_sampling(req)
        draw, carry = self._seed_keys(req)
        self.state, self.tok = _admit_fn()(
            self.state, self.tok, lane, fresh, logits, draw,
            jnp.asarray([t], jnp.float32), jnp.asarray([k], jnp.int32),
            jnp.asarray([p], jnp.float32))
        self.counters["admit_dispatches"] += 1
        self._register_admit(lane, req, bucket=bucket,
                             prefill_chunks=prefill_chunks,
                             prefix_tokens=prefix_tokens, lane_key=carry)

    # -- prefix cache --------------------------------------------------------

    def _cache_match(self, req: Request, rows_cap: Optional[int]
                     ) -> Tuple[Optional[StateEntry], Optional[RowsEntry]]:
        """One admission-time lookup: (exact-state hit, rows donor) —
        at most one is non-None. `rows_cap` bounds the usable donor depth
        (the deepest chunk boundary strictly inside the prompt); None
        skips the rows search (whole-bucket path)."""
        pc = self.prefix_cache
        if pc is None or not req.reuse_prefix:
            return None, None
        self.counters["prefix_lookups"] += 1
        st = pc.match_state(req.prompt)
        if st is not None:
            self.counters["prefix_hits"] += 1
            self.counters["prefix_exact_hits"] += 1
            return st, None
        if rows_cap is not None and rows_cap >= self.chunk_prefill:
            rows = pc.match_rows(req.prompt, rows_cap)
            if rows is not None:
                self.counters["prefix_hits"] += 1
                return None, rows
        return None, None

    def _splice_cached(self, lane: int, req: Request, entry: StateEntry):
        """Admit from an exact-prompt hit: splice the cached finalized
        state straight into `lane` — zero prefill dispatches. The cached
        logits seed the first token through the request's sampling rule,
        so a greedy twin of the original request reproduces its stream."""
        fresh = jax.tree.map(jnp.asarray, entry.state)
        t, k, p = self._req_sampling(req)
        draw, carry = self._seed_keys(req)
        self.state, self.tok = _admit_fn()(
            self.state, self.tok, lane, fresh, jnp.asarray(entry.logits),
            draw, jnp.asarray([t], jnp.float32),
            jnp.asarray([k], jnp.int32), jnp.asarray([p], jnp.float32))
        self.counters["admit_dispatches"] += 1
        self.counters["prefix_copies"] += 1
        self.counters["prefix_tokens_reused"] += entry.length
        self._register_admit(lane, req, bucket=entry.bucket,
                             prefill_chunks=0, prefix_tokens=entry.length,
                             prefix_exact=True, lane_key=carry)

    def _sync_cache_counters(self):
        pc = self.prefix_cache
        self.counters["prefix_inserts"] = pc.inserts
        self.counters["prefix_evictions"] = pc.evictions

    def _cache_insert_finalized(self, req: Request, logits, fresh,
                                bucket: int):
        """Insert a completed whole-bucket prefill into the trie: the
        finalized state always; additionally a rows donor when the
        static pruning left the prefix slot-aligned (nothing evicted,
        identity positions, full precision), the prompt length sits on
        the resume chunk grid, and that grid equals the donor's
        accumulation grid (cfg.attn_chunk) so the f32 column sums carry
        the exact from-scratch accumulation order."""
        pc = self.prefix_cache
        if pc is None or not req.reuse_prefix:
            return
        host_state = jax.tree.map(np.asarray, fresh)
        pc.insert_state(req.prompt, StateEntry(
            length=len(req.prompt), bucket=bucket,
            logits=np.asarray(logits), state=host_state))
        c = self.chunk_prefill
        n = len(req.prompt)
        if (self._rows_reuse and n % c == 0
                and c == self.model.cfg.attn_chunk
                and getattr(host_state, "kv", None) is not None):
            rows = cache_prefix_rows(host_state.kv, n)
            if rows is not None:
                pc.insert_rows(req.prompt, RowsEntry(n, *rows))
        self._sync_cache_counters()

    def _admit_group(self, lanes: List[int], group: List[Request]):
        """Admit G same-bucket requests with ONE batched prefill dispatch
        and ONE multi-lane splice. The token batch is padded UP to the
        next power-of-two row count (duplicating row 0, a well-formed
        real prompt) so the prefill jit cache holds at most
        log2(lanes)+1 group programs per bucket while small groups on
        wide-lane engines don't pay a full lanes-row prefill; the
        splice's source map drops the surplus rows. Bit-identical to
        admitting the same requests sequentially via `_admit_lane`."""
        self._ensure_state()
        padded = [self._padded_prompt(r)[0] for r in group]
        bucket = len(padded[0])
        g = len(group)
        gp = min(1 << (g - 1).bit_length(), self.lanes)      # pow2 rows
        rows = np.stack(padded)                              # [G, W]
        lengths = np.fromiter((len(r.prompt) for r in group), np.int32, g)
        if g < gp:
            pad_rows = np.broadcast_to(rows[:1], (gp - g, bucket))
            rows = np.concatenate([rows, pad_rows], axis=0)
            lengths = np.concatenate(
                [lengths, np.full(gp - g, lengths[0], np.int32)])
        src = np.full(self.lanes, -1, np.int32)
        for i, lane in enumerate(lanes):
            src[lane] = i
        if self.buckets is None:               # exact-width group
            self._prefill_shapes.add(("group-exact", bucket, gp))
            logits, fresh = self._prefill_group(self.params,
                                                jnp.asarray(rows))
        else:
            self._prefill_shapes.add(("group", bucket, gp))
            logits, fresh = self._prefill_group(self.params,
                                                jnp.asarray(rows),
                                                jnp.asarray(lengths))
        self.counters["prefill_dispatches"] += 1
        # per-row seeding: each request draws from its OWN stream and
        # gets its own lane PRNG carry (pad rows mirror row 0 — their
        # draws are dropped by the splice's source map anyway)
        t_arr = np.empty(gp, np.float32)
        k_arr = np.empty(gp, np.int32)
        p_arr = np.empty(gp, np.float32)
        draws = np.empty((gp, 2), np.uint32)
        carries: List[np.ndarray] = []
        for i, r in enumerate(group):
            t_arr[i], k_arr[i], p_arr[i] = self._req_sampling(r)
            draw, carry = self._seed_keys(r)
            draws[i] = np.asarray(draw, np.uint32)
            carries.append(np.asarray(carry, np.uint32))
        t_arr[g:], k_arr[g:], p_arr[g:] = t_arr[0], k_arr[0], p_arr[0]
        draws[g:] = draws[0]
        self.state, self.tok = _admit_group_fn()(
            self.state, self.tok, jnp.asarray(src), fresh, logits,
            jnp.asarray(draws), jnp.asarray(t_arr), jnp.asarray(k_arr),
            jnp.asarray(p_arr))
        self.counters["admit_dispatches"] += 1
        self.counters["grouped_admissions"] += 1
        self.counters["grouped_requests"] += g
        for lane, req, carry in zip(lanes, group, carries):
            self._register_admit(lane, req, bucket=bucket, group_size=g,
                                 lane_key=carry)

    def _set_lane_knobs(self, lane: int, req: Request) -> None:
        """Load one lane's runtime knob slots from the request (its
        SamplingParams override, else the loop defaults)."""
        t, k, p = self._req_sampling(req)
        self.lane_temp[lane] = t
        self.lane_topk[lane] = k
        self.lane_topp[lane] = p
        sp = req.sampling
        self.lane_eos[lane] = (self.eos if sp is None or sp.eos is None
                               else sp.eos)
        self._lane_prio[lane] = req.priority

    def _reset_lane_knobs(self, lane: int) -> None:
        """Back to the loop defaults when a lane frees — a stale
        sampled-lane temperature would otherwise keep the block's
        all-greedy fast path (`lax.cond` on any(temp > 0)) disabled."""
        self.lane_temp[lane] = self.temperature
        self.lane_topk[lane] = self.top_k
        self.lane_topp[lane] = self.top_p
        self.lane_eos[lane] = self.eos
        self._lane_prio[lane] = 0

    def _register_admit(self, lane: int, req: Request, bucket: int,
                        prefill_chunks: int = 1, group_size: int = 1,
                        prefix_tokens: int = 0, prefix_exact: bool = False,
                        lane_key=None):
        """Host-side bookkeeping for a request just spliced into `lane`."""
        cap = self._degrade_cap()
        budget = req.max_new if cap is None else min(req.max_new, cap)
        st_deg = cap is not None and budget < req.max_new
        self.active[lane] = budget > 0
        self.remaining[lane] = max(budget, 0)
        self.outputs[lane] = []
        self._lane_rid[lane] = req.rid
        self._set_lane_knobs(lane, req)
        if lane_key is not None:
            self._lane_keys[lane] = np.asarray(lane_key, np.uint32)
        st = self.stats[req.rid]
        st.lane = lane
        st.t_admit = self._now()
        st.bucket = bucket
        st.prefill_chunks = prefill_chunks
        st.admit_seq = self._admit_seq
        st.group_size = group_size
        st.prefix_tokens = prefix_tokens
        st.prefix_exact = prefix_exact
        st.degraded = st.degraded or st_deg
        self._admit_seq += 1
        if req.max_new <= 0:                   # prefill-only request
            st.t_first = st.t_admit            # ttft == prefill completion
            self._finish_lane(lane, self._now())

    # -- priority preemption + drain-aware reservation -----------------------

    def _admit_resumed(self, lane: int, req: Request) -> None:
        """Splice a preempted request's captured state back into a free
        lane — zero prefill work; the stream continues exactly where it
        stopped (outputs, budget, PRNG carry, and the carried next token
        all restored)."""
        self._ensure_state()
        rs = req.resume
        req.resume = None
        self.state, self.tok = _resume_fn()(
            self.state, self.tok, lane, rs.state,
            jnp.asarray(rs.tok, jnp.int32))
        self.counters["admit_dispatches"] += 1
        self.active[lane] = rs.rem > 0
        self.remaining[lane] = rs.rem
        self.outputs[lane] = list(rs.outputs)
        self._lane_rid[lane] = req.rid
        self._set_lane_knobs(lane, req)
        self._lane_keys[lane] = np.asarray(rs.key, np.uint32)
        st = self.stats[req.rid]
        st.lane = lane
        st.admit_seq = self._admit_seq
        self._admit_seq += 1

    def _preempt_lane(self, lane: int) -> None:
        """Evict one active lane for a higher class: capture its exact
        mid-stream snapshot (`_lane_slice_fn` state slice + carried next
        token + budget + PRNG carry + emitted tokens) onto the request
        and requeue it at its arrival rank."""
        rid = self._lane_rid[lane]
        req = self._req_by_rid[rid]
        fresh = _lane_slice_fn(_model_key(self.model))(self.state, lane)
        req.resume = _ResumeState(
            state=fresh, tok=int(np.asarray(self.tok)[lane]),
            rem=int(self.remaining[lane]),
            key=self._lane_keys[lane].copy(),
            outputs=list(self.outputs[lane]))
        self.active[lane] = False
        self.remaining[lane] = 0
        self.outputs[lane] = []
        self._lane_rid[lane] = None
        self._reset_lane_knobs(lane)
        st = self.stats[rid]
        st.preemptions += 1
        st.lane = -1
        self.counters["preemptions"] += 1
        self._cache_insert_preempted(req, fresh)
        self._requeue(req)

    def _cache_insert_preempted(self, req: Request, fresh) -> None:
        """Preemption-aware prefix caching: instead of idling on the
        Request until resume, the captured snapshot ALSO feeds the radix
        trie as a rows donor when its prompt prefix is still slot-aligned
        (`surgery.prefix_slot_aligned` via `cache_prefix_rows`) — a
        re-admitted sibling prompt then resumes its chunked prefill from
        the victim's rows. The gate naturally refuses decode-advanced
        captures (step > prompt length after the first emitted token) and
        quantized/latent caches, so only donors whose rows equal the
        pre-pruning workspace bit-for-bit get in; grid conditions mirror
        `_cache_insert_finalized` (prompt on the resume chunk grid, chunk
        == cfg.attn_chunk for exact f32 acc association)."""
        pc = self.prefix_cache
        if pc is None or not req.reuse_prefix:
            return
        c = self.chunk_prefill
        n = len(req.prompt)
        if not (self._rows_reuse and n % c == 0
                and c == self.model.cfg.attn_chunk):
            return
        kv = getattr(fresh, "kv", None)
        if kv is None:
            return
        # cache_prefix_rows checks alignment on the light fields
        # (fill/step/pos/valid) before pulling k/v/acc to host, so a
        # refused donor costs no heavy device->host copy
        rows = cache_prefix_rows(kv, n)
        if rows is not None:
            pc.insert_rows(req.prompt, RowsEntry(n, *rows))
            self.counters["preempt_cache_inserts"] += 1
        self._sync_cache_counters()

    def _requeue(self, req: Request) -> None:
        """Re-insert a preempted request at its arrival rank: it resumes
        as soon as its class is schedulable again (its old rank keeps it
        ahead of later arrivals in the same class)."""
        req.admitted = False
        dq = self._bucket_q.setdefault(self._qkey(req), deque())
        idx = next((i for i, r in enumerate(dq)
                    if r.arrival > req.arrival), len(dq))
        dq.insert(idx, req)
        if req not in self._arrived_fifo:      # identity compare (eq=False)
            fifo = self._arrived_fifo
            idx = next((i for i, r in enumerate(fifo)
                        if r.arrival > req.arrival), len(fifo))
            fifo.insert(idx, req)
        self._arrived_count += 1

    def _try_preempt(self) -> bool:
        """With every lane busy: if the best waiting class strictly
        outranks the lowest-priority active lane, evict that lane (ties
        broken toward the most predicted remaining work — evicting it
        frees capacity for the longest). Returns True when a lane was
        freed. Equal-priority traffic never preempts, and a lane running
        a legacy `admit()` batch (no Request to requeue) is exempt."""
        if not self._bucket_q:
            return False
        top = min(self._bucket_q)
        head = self._bucket_q[top][0]
        if (head.resume is None and self._needs_chunking(top[1])
                and self._pending is not None):
            return False          # couldn't be admitted this round anyway
        pred = self.predicted_free_blocks()
        victim: Optional[int] = None
        vrank: Tuple[int, int] = (0, 0)
        for lane in np.flatnonzero(self.active):
            lane = int(lane)
            if self._pending is not None and lane == self._pending.lane:
                continue
            rid = self._lane_rid[lane]
            if rid is None or rid not in self._req_by_rid:
                continue
            rank = (int(self._lane_prio[lane]), -pred.get(lane, 0))
            if victim is None or rank < vrank:
                victim, vrank = lane, rank
        if victim is None or -top[0] <= vrank[0]:
            return False
        self._preempt_lane(victim)
        return True

    def predicted_free_blocks(self) -> Dict[int, int]:
        """Per-active-lane drain prediction: decode blocks until the
        lane frees. The expected remaining tokens are the lane's unspent
        budget, bounded by an observed mean EOS-termination length
        (minus what the lane already emitted). The bound is CLASS-LOCAL
        first: a lane whose (priority, bucket) class has accumulated at
        least 4 EOS completions uses that class's own mean — short
        bursty and long bulk traffic stop polluting each other's
        forecasts when they mix. Below the class sample floor the
        global mean applies under the original gate (at least 4
        observed EOS overall and no fewer than budget exhaustions), so
        EOS-heavy traffic predicts earlier than its worst-case budget."""
        eos_mean = None
        if (len(self._eos_lens) >= 4
                and len(self._eos_lens) >= self._budget_done):
            eos_mean = float(np.mean(self._eos_lens))
        out: Dict[int, int] = {}
        for lane in np.flatnonzero(self.active):
            lane = int(lane)
            exp = int(self.remaining[lane])
            mean = eos_mean
            rid = self._lane_rid[lane]
            st = self.stats.get(rid) if rid is not None else None
            if st is not None:
                cell = self._eos_by_class.get((st.priority, st.bucket))
                if cell is not None and len(cell) >= 4:
                    mean = float(np.mean(cell))
            if mean is not None:
                exp = min(exp, max(1, round(mean)
                                   - len(self.outputs[lane])))
            out[lane] = max(1, math.ceil(exp / self.block))
        return out

    def _reserve(self) -> None:
        """Drain-aware pre-grouping: with every lane busy, predict which
        lanes free within `reserve_blocks` decode blocks and pop that
        many queued requests NOW, so their (grouped) admission fires the
        moment the lanes actually free instead of waiting out another
        scheduling round. Reserved requests follow the normal target
        ordering (priority class, then shortest bucket, aging bound
        included) and are admitted ahead of the queues."""
        if (not self.reserve_blocks or not self.group_admit
                or not self._bucket_q):
            return
        soon = sum(1 for b in self.predicted_free_blocks().values()
                   if b <= self.reserve_blocks)
        room = soon - len(self._reserved)
        if room <= 0:
            return
        fifo_head = self._fifo_head()
        if fifo_head is None:
            return
        target = min(self._bucket_q)
        if (-target[0] <= fifo_head.priority
                and target != self._qkey(fifo_head)
                and self._head_skips >= self.max_head_skips):
            target = self._qkey(fifo_head)     # aging kicks in
        if (self._needs_chunking(target[1])
                and self._bucket_q[target][0].resume is None):
            return          # sliced prefills reserve their own lane
        group = self._take_bucket(target, room)
        if not group:
            return
        self._head_skips = (0 if fifo_head in group
                            else self._head_skips + 1)
        self._reserved.extend(group)
        self.counters["reservations"] += len(group)

    # -- graceful degradation ------------------------------------------------

    def _effective_block(self) -> int:
        """Decode block size under the current degradation level (the
        ladder's "block" override; level 0 = the configured block). A
        smaller block both amortizes less AND tightens the decode window
        (`decode_window(fill, steps)` covers fill + steps), trading peak
        throughput for shorter admission latency and a finer-grained
        deadline/cancel/quarantine response — token values are UNCHANGED
        (block size never enters the per-lane math)."""
        if not self._degrade_level:
            return self.block
        lvl = self.degrade_ladder[self._degrade_level - 1]
        return max(1, int(lvl.get("block", self.block)))

    def _degrade_cap(self) -> Optional[int]:
        """Budget cap applied to NEW admissions at the current level
        (the ladder's "max_new_cap"; None = uncapped). Capped requests
        complete with outcome "done" and `stats.degraded=True`."""
        if not self._degrade_level:
            return None
        cap = self.degrade_ladder[self._degrade_level - 1].get(
            "max_new_cap")
        return int(cap) if cap else None

    def _pressure_tick(self) -> None:
        """The pressure controller: one hysteresis step per scheduler
        round. DOWN when every lane is busy, the waiting population is
        at least `degrade_high`, and `predicted_free_blocks()` says no
        lane frees within the reservation horizon (genuine sustained
        pressure, not a drain already in flight); UP when the waiting
        population falls to `degrade_low`. Every transition counts
        (`degrade_down`/`degrade_up` — count-class in CI)."""
        if not self.degrade_ladder:
            return
        waiting = self._arrived_count + len(self._reserved)
        if (waiting >= self.degrade_high
                and self._degrade_level < len(self.degrade_ladder)
                and not any(len(f) for f in self.shard_free_lanes())):
            pred = self.predicted_free_blocks()
            if pred and min(pred.values()) > max(1, self.reserve_blocks):
                self._degrade_level += 1
                self.counters["degrade_down"] += 1
        elif waiting <= self.degrade_low and self._degrade_level:
            self._degrade_level -= 1
            self.counters["degrade_up"] += 1

    # -- chunked (time-sliced) admission -------------------------------------

    def _needs_chunking(self, bucket: int) -> bool:
        return 0 < self.chunk_prefill < bucket

    def _start_chunked(self, lane: int, req: Request, padded: np.ndarray,
                       bucket: int):
        """Reserve `lane` and open a sliced prefill for a long prompt. Only
        the chunks that contain real tokens are ever dispatched — trailing
        all-pad chunks of the bucket contribute nothing by construction.

        The workspace is rounded up to a multiple of the chunk size so
        every dispatched slice is full-width: a ragged final slice would
        silently compile one extra program per distinct ragged width (the
        true-length mask makes the extra pad columns free).

        Prefix cache: an exact-prompt hit splices the cached finalized
        state directly (no slices, no reserved pending prefill); a rows
        hit at depth p pre-fills the workspace with the cached rows and
        resumes at chunk p/C — the remaining slices repeat the
        from-scratch accumulation bit-for-bit."""
        self._ensure_state()
        c = self.chunk_prefill
        # deepest usable donor boundary: the final chunk (the one holding
        # the last real token, whose hidden feeds the logits) always runs
        cap = ((len(req.prompt) - 1) // c) * c
        hit, rows = self._cache_match(req, rows_cap=cap)
        if hit is not None:
            self._splice_cached(lane, req, hit)
            return
        ws = math.ceil(bucket / c) * c
        if ws != bucket:
            ext = np.zeros(ws, padded.dtype)
            ext[:len(padded)] = padded
            padded = ext
        if rows is not None:
            pstate = self._resume(rows.k, rows.v, rows.acc, ws)
            base = rows.depth
            self.counters["prefix_copies"] += 1
            self.counters["prefix_tokens_reused"] += base
        else:
            pstate = self.model.init_prefill_chunk_state(1, ws)
            base = 0
        self._pending = _ChunkedPrefill(
            req=req, lane=lane, bucket=ws, padded=padded, pstate=pstate,
            n_chunks=math.ceil(len(req.prompt) / c), next_chunk=base // c,
            base=base, collect=(self._rows_reuse and req.reuse_prefix))
        self._prefill_shapes.add(("chunk", c, ws))

    def _advance_chunked(self) -> bool:
        """Run ONE prefill slice of the in-flight chunked admission (the
        caller interleaves decode blocks between slices). Returns True if
        a slice was dispatched."""
        p = self._pending
        if p is None:
            return False
        if p.req.cancelled or self._deadline_over(p.req, self._now()):
            # drop the in-flight sliced prefill: the reserved lane frees
            # immediately and the remaining slices are never dispatched
            self._pending = None
            self._resolve_dead(p.req)
            return False
        c = self.chunk_prefill
        ci = p.next_chunk
        tok_c = jnp.asarray(p.padded[ci * c:(ci + 1) * c][None])
        length = jnp.asarray([len(p.req.prompt)], jnp.int32)
        p.x_last, p.pstate = self._chunk(self.params, p.pstate, tok_c,
                                         jnp.asarray(ci * c, jnp.int32),
                                         length)
        self.counters["chunk_dispatches"] += 1
        p.next_chunk += 1
        q = p.next_chunk * c
        if p.collect and p.base < q <= (len(p.req.prompt) // c) * c:
            # host snapshot of the acc prefix at boundary q: acc columns
            # [0, q) depend only on tokens [0, q) (columns past a chunk's
            # causal reach carry exactly-zero mass), so together with the
            # write-once K/V rows this is a bit-exact resume donor for
            # ANY continuation sharing those tokens. Boundaries whose
            # chunk holds pad tokens (q > prompt length) are never taken.
            p.snap_acc.append((q, np.asarray(p.pstate.acc[:, 0, :, :q])))
        if p.next_chunk >= p.n_chunks:
            rows_kv = None
            if p.snap_acc:
                # ONE workspace K/V snapshot covers every boundary (rows
                # are write-once) — taken before finalize donates pstate
                q_max = p.snap_acc[-1][0]
                rows_kv = (np.asarray(p.pstate.k[:, 0, :, :q_max]),
                           np.asarray(p.pstate.v[:, 0, :, :q_max]))
            logits, fresh = self._finalize(
                self.params, p.pstate, p.x_last,
                jnp.asarray((p.n_chunks - 1) * c, jnp.int32), length)
            self.counters["prefill_dispatches"] += 1
            self._pending = None
            self._splice(p.lane, p.req, logits[0], fresh, bucket=p.bucket,
                         prefill_chunks=p.n_chunks, prefix_tokens=p.base)
            # trie insertion AFTER the splice: admission latency (ttft)
            # never pays for the host copies; fresh/logits survive the
            # splice (only state/tok are donated)
            self._cache_insert_chunked(p, logits[0], fresh, rows_kv)
        return True

    def _cache_insert_chunked(self, p: _ChunkedPrefill, logits, fresh,
                              rows_kv):
        """Insert a finished sliced prefill: the finalized state at the
        full prompt, plus one rows donor per collected chunk boundary
        (each boundary needs its own acc copy — columns keep absorbing
        mass from later query rows, so acc is only valid at the exact
        boundary it was snapped at)."""
        pc = self.prefix_cache
        if pc is None or not p.req.reuse_prefix:
            return
        tokens = np.asarray(p.req.prompt)
        pc.insert_state(tokens, StateEntry(
            length=len(tokens), bucket=p.bucket, logits=np.asarray(logits),
            state=jax.tree.map(np.asarray, fresh)))
        if rows_kv is not None:
            k_all, v_all = rows_kv                     # [L, Hk, q_max, dh]
            for q, acc_q in p.snap_acc:
                pc.insert_rows(tokens[:q], RowsEntry(
                    q, k_all[:, :, :q].copy(), v_all[:, :, :q].copy(),
                    acc_q))
        self._sync_cache_counters()

    def schedule(self) -> int:
        """Admit queued, already-arrived requests into free lanes.

        Grouped admission (default): each round gathers up to
        len(free_lanes) arrived requests that pad to one shared bucket
        and admits them with a single batched prefill + multi-lane
        splice. The target bucket is the FIFO head's off load; under
        load (more arrived requests than free lanes) it is the SHORTEST
        bucket present, so short prompts are not starved behind long
        ones — bounded by AGING: after the FIFO head has been passed
        over `max_head_skips` rounds in a row, its bucket is forced, so
        a long prompt can never starve indefinitely under sustained
        short-prompt overload. Requests sharing a bucket keep FIFO order
        within it. Long prompts (bucket > chunk_prefill) open a
        time-sliced prefill on a reserved lane instead of blocking on a
        whole-prompt dispatch; at most one sliced prefill is in flight
        at a time — while one is, a chunk-needing target falls back to
        the shortest chunk-free bucket (aging credit untouched) so free
        lanes never idle behind the sliced prefill.

        Priority classes sort ahead of bucket width: the target class is
        the best (-priority, bucket) tuple present, so higher classes
        always admit first and equal-priority traffic reduces exactly to
        the bucket ordering above. With NO free lane, a strictly-higher
        waiting class may preempt the lowest-priority active lane
        (`_try_preempt`); otherwise drain-aware reservation pre-pops the
        requests predicted to fit within `reserve_blocks` decode blocks
        (`_reserve`) so their grouped prefill fires the moment lanes
        free. A preempted request resumes via a zero-prefill state
        splice (`_admit_resumed`), always solo, never chunked.

        Each round is O(newly arrived + len(buckets)): requests whose
        arrival passed are drained once into their bucket's FIFO deque,
        the target bucket comes from the deque heads, and the group is
        popped from one deque — never a scan over the arrived backlog.

        Under a lane mesh admission is SHARD-AWARE: the scheduler tracks
        free lanes per shard (`shard_free_lanes`) and each round admits
        into ONE shard's lane rows — the shard with the most free lanes
        (lowest index on ties) — so a grouped prefill's `lanes_insert`
        splice and the subsequent `write_token_stacked` scatters stay
        shard-local; the loop covers the remaining shards on its next
        iterations. When preemption frees a lane, the next round's
        most-free shard IS the victim's shard, so the admission lands on
        the lane that was freed for it. A 1-shard engine reduces exactly
        to the unsharded free-lane list.
        """
        n = 0
        while True:
            self._drain_arrivals(self._now())
            if self._arrived_count == 0 and not self._reserved:
                break
            free = max(self.shard_free_lanes(), key=len)
            if not free:
                if self._try_preempt():
                    continue
                self._reserve()
                break
            if self._reserved:
                group = self._take_reserved(len(free))
                self.counters["reserved_admits"] += len(group)
                n += self._admit_chosen(free, group)
                continue
            fifo_head = self._fifo_head()      # arrived_count > 0 ⇒ set
            if not self.group_admit:
                target, take = self._qkey(fifo_head), 1
            else:
                best = min(self._bucket_q)     # best class, shortest bucket
                if self._arrived_count > len(free):
                    target = best
                    if (-best[0] <= fifo_head.priority
                            and target != self._qkey(fifo_head)
                            and self._head_skips >= self.max_head_skips):
                        target = self._qkey(fifo_head)  # aging kicks in
                else:                          # off load: FIFO head, unless
                    target = self._qkey(fifo_head)      # a class outranks it
                    if -best[0] > fifo_head.priority:
                        target = best
                take = len(free)
            if self._bucket_q[target][0].resume is not None:
                # preempted request resuming: zero-prefill solo splice
                req = self._take_bucket(target, 1)[0]
                if self._resolve_dead(req):
                    continue
                self._head_skips = (0 if fifo_head is req
                                    else self._head_skips + 1)
                self._admit_resumed(free[0], req)
                n += 1
                continue
            if (self.group_admit and self._pending is not None
                    and self._needs_chunking(target[1])):
                # one sliced prefill at a time — instead of idling the
                # free lanes behind it, admit the shortest chunk-free
                # bucket this round (resume heads are chunk-free by
                # construction); the head's aging credit is NOT touched
                # on a blocked round, so the max_head_skips bound keeps
                # holding
                alts = [k for k in self._bucket_q
                        if not self._needs_chunking(k[1])
                        or self._bucket_q[k][0].resume is not None]
                if not alts:
                    break
                target = min(alts)
                if self._bucket_q[target][0].resume is not None:
                    req = self._take_bucket(target, 1)[0]
                    if self._resolve_dead(req):
                        continue
                    self._admit_resumed(free[0], req)
                    n += 1
                    continue
            if self._needs_chunking(target[1]):
                if self._pending is not None:
                    break                      # one sliced prefill at a time
                # aging accounting: `is`/`in` are identity comparisons
                # (Request eq=False); only rounds that ADMIT something
                # consume or earn credit
                head = self._take_bucket(target, 1)[0]
                if self._resolve_dead(head):
                    continue
                self._head_skips = (0 if fifo_head is head
                                    else self._head_skips + 1)
                self._start_chunked(free[0], head,
                                    self._padded_prompt(head)[0],
                                    head.bucket)
                continue
            group = self._take_bucket(target, take)
            self._head_skips = (0 if fifo_head in group
                                else self._head_skips + 1)
            n += self._admit_chosen(free, group)
        return n

    def _admit_chosen(self, free: List[int], group: List[Request]) -> int:
        """Dispatch an already-popped admission group into free lanes
        (resume-aware: a captured-state head splices without prefill).
        Cancelled / deadline-expired members resolve here instead of
        being admitted — the group shrinks, never the dispatch count."""
        group = [r for r in group if not self._resolve_dead(r)]
        if not group:
            return 0
        if group[0].resume is not None:
            self._admit_resumed(free[0], group[0])
        elif len(group) == 1:
            self._admit_lane(free[0], group[0])
        else:
            self._admit_group(free[:len(group)], group)
        return len(group)

    # -- shard accounting ----------------------------------------------------

    def _shard_of(self, lane: int) -> int:
        """Shard owning `lane`: the P("data") layout gives each shard a
        contiguous block of lanes_per_shard lane rows."""
        return lane // self.lanes_per_shard

    def shard_free_lanes(self) -> List[List[int]]:
        """Free (admittable) lanes grouped by shard — the scheduler's
        shard-local admission view. A pending sliced prefill's reserved
        lane is excluded, same as the unsharded free-lane rule. Without
        a mesh this is a single list (shards == 1).

        A chaos shard BLACKOUT hides that shard's free lanes here (a
        brownout: resident lanes keep decoding, no NEW work lands) —
        admission routes around it through the most-free-shard rule and
        the round counter guarantees it expires (`run()` keeps ticking
        rounds even when nothing else progresses)."""
        free: List[List[int]] = [[] for _ in range(self.shards)]
        for lane in np.flatnonzero(~self.active):
            lane = int(lane)
            if self._pending is not None and lane == self._pending.lane:
                continue
            free[self._shard_of(lane)].append(lane)
        if self.chaos is not None and self.chaos.blackout_shard >= 0:
            black = False
            for s in range(self.shards):
                if self.chaos.blacked_out(self._rounds, s):
                    black = True
                    free[s] = []
            if black and not self._blackout_on:
                self.counters["chaos_blackouts"] += 1
            self._blackout_on = black
        return free

    def _blackout_active(self) -> bool:
        return (self.chaos is not None
                and self.chaos.blackout_shard >= 0
                and any(self.chaos.blacked_out(self._rounds, s)
                        for s in range(self.shards)))

    def admit(self, prompts: np.ndarray):
        """Deprecated legacy all-lanes admission: prompts
        [lanes, prompt_len] are prefilled in one batch (one compile, no
        lane splicing) and every lane restarts with the shared `max_new`
        budget. Submit `Request`s and `run()` instead."""
        warnings.warn(
            "ServeLoop.admit() is deprecated; submit(Request(...)) per "
            "request and drive with run()",
            DeprecationWarning, stacklevel=2)
        if self._t0 is None:
            self._t0 = time.monotonic()
        batch = {"tokens": jnp.asarray(prompts)}
        logits, self.state = self._prefill(self.params, batch)
        self.counters["prefill_dispatches"] += 1
        # same next-token rule as lane admission: sampling (when enabled)
        # covers the first generated token on this path too
        self.tok = _next_token(logits, self._sample_key(), self.temperature,
                               self.top_k, self.top_p).astype(jnp.int32)
        # broadcast the engine-wide scalars through the per-lane runtime
        # slots so the vectorized block serves the deprecated surface too
        if self.temperature > 0:
            self._key, *subs = jax.random.split(self._key, self.lanes + 1)
            self._lane_keys = np.stack(
                [np.asarray(s, np.uint32) for s in subs])
        else:
            self._lane_keys = np.broadcast_to(
                np.asarray(self._key, np.uint32), (self.lanes, 2)).copy()
        self.lane_temp[:] = self.temperature
        self.lane_topk[:] = self.top_k
        self.lane_topp[:] = self.top_p
        self.lane_eos[:] = self.eos
        self._lane_prio[:] = 0
        self.active[:] = self.max_new > 0
        self.remaining[:] = max(self.max_new, 0)
        self.outputs = [[] for _ in range(self.lanes)]
        now = self._now()
        for lane in range(self.lanes):
            rid = self._next_rid
            self._next_rid += 1
            self._lane_rid[lane] = rid
            self.stats[rid] = RequestStats(
                rid, prompts.shape[1], self.max_new, lane=lane,
                t_arrival=now, t_admit=now, bucket=prompts.shape[1])

    # -- decode --------------------------------------------------------------

    def step(self) -> bool:
        """Deprecated: one decode step over all lanes; returns True while
        any lane is live. Drive the engine with `run()` instead."""
        warnings.warn(
            "ServeLoop.step() is deprecated; drive the engine with run()",
            DeprecationWarning, stacklevel=2)
        return self._step_block(1)

    def _decode_window(self, steps: int) -> Optional[int]:
        """Slot window for the next decode block: the smallest pow2 prefix
        covering every ACTIVE lane's fill plus the block's appends (None =
        full width). Inactive lanes may overflow the window; their writes
        are dropped in-device by `lane_select` and their outputs masked,
        so only active-lane coverage matters for bit-exactness."""
        if self.window != "auto" or self.state is None \
                or self.state.kv is None or not self.active.any():
            return None
        from repro.core.cache import decode_window
        fill = np.asarray(self.state.kv.fill)          # [L, lanes]
        max_fill = int(fill[:, self.active].max())
        return decode_window(max_fill, steps, self.model.decode_slots,
                             self.model.prune, grid=self.window_grid)

    def step_block(self, steps: int = 0) -> bool:
        """Deprecated public alias of the engine's decode block; `run()`
        drives the same internals without the warning."""
        warnings.warn(
            "ServeLoop.step_block() is deprecated; drive the engine with "
            "run()", DeprecationWarning, stacklevel=2)
        return self._step_block(steps)

    def _step_block(self, steps: int = 0) -> bool:
        """Decode `steps` (default: self.block) tokens in one dispatch.

        Finished lanes stop writing in-device; the host side consumes the
        (token, emitted) pairs with vectorized numpy — no per-token loop.
        Each block dispatches over the fill-covering slot window (see
        `_decode_window`), so step cost tracks the live context.

        Under degradation the default block size follows the ladder
        (`_effective_block`); with a `ChaosConfig` attached, stalls
        sleep before the dispatch and the deterministic per-block fault
        mask rides in as a runtime array (an all-zeros mask is always
        passed, so the chaos path and the clean path share ONE compiled
        program). Lanes flagged by the in-device non-finite sentinel
        are quarantined and their requests retried (`_quarantine_lane`).
        """
        steps = steps or self._effective_block()
        if self.state is None or not self.active.any():
            return bool(self.active.any())
        window = self._decode_window(steps)
        self._windows.add(window)
        self.counters["decode_windows"] = len(self._windows)
        fn = _lanes_block_fn(_model_key(self.model), steps, window,
                             self.mesh)
        was_active = self.active.copy()
        blk = self.counters["decode_blocks"]
        if self.chaos is not None and self.chaos.any_faults:
            stall = self.chaos.stall(blk)
            if stall > 0:
                self.counters["chaos_stalls"] += 1
                time.sleep(stall)
            fault = self.chaos.fault_mask(blk, steps, self.lanes)
            self.counters["chaos_faults"] += int(fault.sum())
        else:
            fault = np.zeros((steps, self.lanes), bool)
        t_disp = time.monotonic()
        (self.state, self.tok, active, rem, keys, poison, toks,
         emitted) = fn(*self._block_args(fault))
        self._lane_keys = np.asarray(keys).astype(np.uint32)
        self.counters["decode_blocks"] += 1
        # knob values ride in as [lanes] arrays, so the jit cache holds ONE
        # program per (steps, window) regardless of the knob mix on board
        self.counters["decode_block_programs"] = fn._cache_size()
        host_toks = np.asarray(toks)                       # [steps, lanes]
        host_emit = np.asarray(emitted)                    # [steps, lanes]
        host_poison = np.asarray(poison)                   # [lanes]
        # per-block wall seconds (host-sync included): feeds the
        # backpressure retry_after hint; an EMA so one noisy block
        # doesn't swing the estimate
        dt = time.monotonic() - t_disp
        self._block_s_ema = (dt if self._block_s_ema is None
                             else 0.8 * self._block_s_ema + 0.2 * dt)
        self.active = np.asarray(active).copy()
        self.remaining = np.asarray(rem).astype(np.int32)
        # per-shard emission accounting (host-side — the ONLY cross-shard
        # traffic the sharded engine has)
        self._shard_tokens += host_emit.sum(axis=0).reshape(
            self.shards, self.lanes_per_shard).sum(axis=1)
        now = self._now()
        for lane in np.flatnonzero(host_emit.any(axis=0)):
            lane = int(lane)
            new = host_toks[host_emit[:, lane], lane].tolist()
            if not self.outputs[lane]:
                rid = self._lane_rid[lane]
                if rid is not None:
                    self.stats[rid].t_first = now
            self.outputs[lane].extend(new)
        # poisoned lanes never take the normal EOS/budget finish path —
        # they are quarantined and their requests retried
        for lane in np.flatnonzero(was_active & ~self.active
                                   & ~host_poison):
            self._finish_lane(int(lane), now)
        for lane in np.flatnonzero(host_poison & was_active):
            self._quarantine_lane(int(lane), now)
        return bool(self.active.any())

    def _block_args(self, fault: np.ndarray) -> tuple:
        """The decode block's arguments from the live engine state, with
        `fault` ([steps, lanes] bool) as the injected-fault mask."""
        if self.mesh is None:
            def put(a, dtype=None):
                return jnp.asarray(a, dtype)
            fault_dev = jnp.asarray(fault)
        else:
            # commit every host-side lane array to the P("data") layout
            # (and re-pin the state after any admission splice) so the
            # shard_map'd block never inserts input reshards
            self._pin_state()
            lane_sh = self._lane_sharding()

            def put(a, dtype=None):
                return jax.device_put(np.asarray(a, dtype), lane_sh)
            from jax.sharding import NamedSharding, PartitionSpec as P
            fault_dev = jax.device_put(
                fault, NamedSharding(self.mesh, P(None, "data")))
        return (self.params, self.state, self.tok,
                put(self.active), put(self.remaining),
                put(self.lane_eos, np.int32),
                put(self._lane_keys, np.uint32),
                put(self.lane_temp, np.float32),
                put(self.lane_topk, np.int32),
                put(self.lane_topp, np.float32),
                fault_dev)

    def _quarantine_lane(self, lane: int, now: float) -> None:
        """One lane tripped the non-finite sentinel: free it (its state
        rows are garbage but fully overwritten by the next admission's
        splice) and retry the request by FULL deterministic replay —
        requeued at its arrival rank, re-prefilled from the prompt, with
        its memoized admission seed (`_seed_keys`) so greedy AND
        seeded-sampled streams come back token-identical. Partial tokens
        from the poisoned incarnation are discarded (the replay re-emits
        them). After `max_retries` quarantines the request resolves with
        outcome "failed", keeping the clean partial stream."""
        rid = self._lane_rid[lane]
        self.counters["quarantined_lanes"] += 1
        partial = list(self.outputs[lane])
        self.active[lane] = False
        self.remaining[lane] = 0
        self.outputs[lane] = []
        self._lane_rid[lane] = None
        self._reset_lane_knobs(lane)
        req = self._req_by_rid.get(rid) if rid is not None else None
        st = self.stats.get(rid) if rid is not None else None
        if req is None:
            # legacy admit() batch — no Request to replay
            if st is not None and rid not in self._finished:
                self.counters["failed_requests"] += 1
                st.tokens = partial
                st.outcome = "failed"
                st.detail = "non-finite logits (legacy lane: no retry)"
                st.t_done = now
                if st.t_first < st.t_admit:
                    st.t_first = now
                st.occupancy = self._lane_occupancy(lane)
                self.completed.append(st)
                self.done.append(st.tokens)
                self._finished.add(rid)
            return
        req.retries += 1
        st.retries = req.retries
        st.lane = -1
        if req.retries > self.max_retries:
            self.counters["failed_requests"] += 1
            st.tokens = partial                # keep the clean prefix
            self._finish_queued(req, "failed",
                                "non-finite logits; max_retries="
                                f"{self.max_retries} exhausted")
        else:
            self.counters["retried_requests"] += 1
            self._requeue(req)

    def _finish_lane(self, lane: int, now: float, outcome: str = "done",
                     detail: str = ""):
        rid = self._lane_rid[lane]
        if rid is None:
            return
        st = self.stats[rid]
        if st.t_first < st.t_admit:
            # nothing was ever emitted (e.g. the very first generated token
            # was EOS, which is a stop signal, not an output) — anchor ttft
            # at completion so it can never go negative
            st.t_first = now
        st.tokens = list(self.outputs[lane])
        st.t_done = now
        st.outcome = outcome
        st.detail = detail
        st.occupancy = self._lane_occupancy(lane)
        self.completed.append(st)
        self.done.append(st.tokens)
        self._finished.add(rid)
        self._lane_rid[lane] = None
        self._req_by_rid.pop(rid, None)
        self._reset_lane_knobs(lane)
        if st.max_new > 0 and outcome == "done":
            # drain-prediction statistics — natural completions only: a
            # cancelled/expired lane still has budget left and would
            # otherwise masquerade as a (short) EOS sample
            if self.remaining[lane] > 0:
                self._eos_lens.append(len(st.tokens))
                # class-local sample for predicted_free_blocks: EOS
                # lengths cluster by traffic class, not globally
                self._eos_by_class.setdefault(
                    (st.priority, st.bucket), []).append(len(st.tokens))
            else:
                self._budget_done += 1

    def _lane_occupancy(self, lane: int) -> float:
        kv = self.state.kv if self.state is not None else None
        if kv is None:
            return 0.0
        fill = np.asarray(kv.fill)                         # [L, lanes]
        return float(fill[:, lane].mean() / kv.slots)

    # -- driver ---------------------------------------------------------------

    def run(self) -> List[RequestStats]:
        """Drive until the queue is drained and every lane is idle. Each
        iteration (a scheduler ROUND) sweeps deadlines/cancellations off
        the active lanes, admits, ticks the pressure controller, then
        interleaves (at most) one prefill slice with one decode block,
        so live lanes keep emitting tokens while a long prompt prefills.

        The loop is hang-proof by construction: a round that makes NO
        progress (nothing admitted, sliced, or decoded) with waiting
        work, idle lanes, and nothing due to arrive can only mean the
        scheduler cannot place what is queued — after a few such rounds
        the stuck requests resolve to structured rejections
        (`_fail_stuck`) instead of spinning forever. A chaos blackout is
        exempted (it expires with the round counter)."""
        if self._t0 is None:
            self._t0 = time.monotonic()
        idle = 0
        while (self._arrived_count or self._arrivals or self._reserved
               or self.active.any() or self._pending is not None):
            self._rounds += 1
            self._sweep_lanes(self._now())
            admitted = self.schedule()
            self._pressure_tick()
            stepped = self._advance_chunked()
            if self.active.any():
                self._step_block()
            elif stepped or admitted:
                pass
            else:
                # never sleep out the arrival timer of a cancelled
                # future arrival — resolve it now
                while self._arrivals and self._arrivals[0].cancelled:
                    self._resolve_dead(self._arrivals.popleft())
                if self._arrivals:
                    wait = self._arrivals[0].arrival - self._now()
                    if wait > 0:
                        time.sleep(min(wait, 0.05))
                elif self._blackout_active():
                    time.sleep(0.001)   # rounds tick; the blackout expires
                elif self._arrived_count or self._reserved:
                    idle += 1
                    if idle >= 3:
                        self._fail_stuck()
                        idle = 0
                continue
            idle = 0
        return self.completed

    def _fail_stuck(self) -> None:
        """Last-resort hang breaker: rounds make zero progress while
        requests wait, lanes idle, and nothing is pending or arriving —
        the scheduler cannot place the waiting work (an un-admittable
        shape that slipped past submit validation, or a scheduler bug).
        Resolve every waiting request as a structured rejection instead
        of looping forever."""
        stuck: List[Request] = list(self._reserved)
        self._reserved.clear()
        for key in list(self._bucket_q):
            dq = self._bucket_q.pop(key)
            self._arrived_count -= len(dq)
            stuck.extend(dq)
        for req in stuck:
            self.counters["rejected_requests"] += 1
            self._finish_queued(req, "rejected",
                                "scheduler made no progress — request "
                                "cannot be placed")

    def prefill_programs(self) -> Dict[str, int]:
        """Compile accounting for the prefill path.

        `loop_shapes`: distinct prefill shapes THIS loop dispatched (what a
        bounded bucket grid guarantees). `jit_cache`: entries in the
        process-wide jit caches backing this model's prefill/chunk/finalize
        entry points (shared across ServeLoops of functionally identical
        models — the actual number of compiled XLA programs)."""
        jit_cache = sum(fn._cache_size()
                        for fn in (self._prefill_one, self._prefill_group,
                                   self._chunk, self._finalize)
                        if hasattr(fn, "_cache_size"))
        return {"loop_shapes": len(self._prefill_shapes),
                "jit_cache": int(jit_cache)}

    def aggregate(self) -> Dict[str, Any]:
        """Serving metrics over completed requests (+ dispatch counters;
        the string-valued `donation` marker passes through unchanged).

        With a prefix cache enabled, adds `prefix_hit_rate`
        (hits / admission lookups), `prefix_dedup_ratio` (prompt tokens
        served from cache / prompt tokens of completed requests — the
        fraction of prefill work deduplicated), and the trie's live
        bytes/entries/insert/eviction tallies."""
        counters = {k: (v if isinstance(v, str) else float(v))
                    for k, v in self.counters.items()}
        prefix: Dict[str, float] = {}
        if self.prefix_cache is not None:
            self._sync_cache_counters()
            counters.update({k: float(v) for k, v in
                             self.prefix_cache.stats().items()})
            lookups = self.counters["prefix_lookups"]
            prefix["prefix_hit_rate"] = (
                self.counters["prefix_hits"] / lookups if lookups else 0.0)
            prompt_toks = sum(s.prompt_len for s in self.completed)
            prefix["prefix_dedup_ratio"] = (
                sum(s.prefix_tokens for s in self.completed) / prompt_toks
                if prompt_toks else 0.0)
        if not self.completed:
            return {"requests": 0.0, "tokens": 0.0, "wall_s": 0.0,
                    "tokens_per_s": 0.0, "mean_latency_s": 0.0,
                    "mean_occupancy": 0.0, "p50_ttft_s": 0.0,
                    "p99_ttft_s": 0.0, "prefill_programs": 0.0,
                    **counters, **prefix}
        toks = sum(len(s.tokens) for s in self.completed)
        t_end = max(s.t_done for s in self.completed)
        t_begin = min(s.t_arrival for s in self.completed)
        wall = max(t_end - t_begin, 1e-9)
        ttfts = [s.ttft for s in self.completed]
        shard_rows: Dict[str, float] = {}
        if self.shards > 1:
            # per-shard throughput + the dispatch-normalized rate the
            # scaling acceptance row is built on: wall-clock cannot scale
            # on forced host devices, tokens per decode-block dispatch can
            blocks = max(self.counters["decode_blocks"], 1)
            shard_rows["shards"] = float(self.shards)
            for i, t in enumerate(self._shard_tokens):
                shard_rows[f"shard{i}_tokens"] = float(t)
                shard_rows[f"shard{i}_tok_s"] = float(t) / wall
            shard_rows["tokens_per_dispatch"] = (
                float(self._shard_tokens.sum()) / blocks)
        return {
            **counters,
            "requests": float(len(self.completed)),
            "tokens": float(toks),
            "wall_s": wall,
            "tokens_per_s": toks / wall,
            "mean_latency_s": float(np.mean([s.latency
                                             for s in self.completed])),
            "mean_occupancy": float(np.mean([s.occupancy
                                             for s in self.completed])),
            "p50_ttft_s": float(np.percentile(ttfts, 50)),
            "p99_ttft_s": float(np.percentile(ttfts, 99)),
            "prefill_programs": float(len(self._prefill_shapes)),
            **shard_rows,
            **prefix,
        }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=256)
    ap.add_argument("--new-tokens", type=int, default=64)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--policy", default="unicaim",
                    choices=["unicaim", "h2o", "streaming", "dense"])
    engine_flags = ap.add_mutually_exclusive_group()
    engine_flags.add_argument(
        "--fused", dest="fused", action="store_const", const=True,
        default="auto",
        help="force the fused single-pass decode engine (unicaim only; "
             "default: auto — the Pallas kernels on TPU, the composed "
             "path elsewhere)")
    engine_flags.add_argument(
        "--composed", dest="fused", action="store_const", const=False,
        help="force the composed three-pass decode engine")
    ap.add_argument("--no-scan", action="store_true",
                    help="per-token Python loop instead of lax.scan")
    ap.add_argument("--serve", action="store_true",
                    help="continuous-batching demo: 2x batch staggered "
                         "variable-length requests through ServeLoop")
    ap.add_argument("--chunk-prefill", type=int, default=0,
                    help="slice prefills into this many tokens per "
                         "dispatch, interleaved with decode (--serve only)")
    ap.add_argument("--prefix-cache", type=int, default=0, metavar="BYTES",
                    help="radix-trie prefix cache byte budget (0 = off; "
                         "--serve only)")
    ap.add_argument("--no-buckets", action="store_true",
                    help="legacy exact-length prefills (one compile per "
                         "distinct prompt length)")
    ap.add_argument("--sequential-admit", action="store_true",
                    help="disable grouped admission (one prefill + splice "
                         "dispatch per request; --serve only)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature for the scanned decode "
                         "block (0 = greedy; --serve only)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="truncate sampling to the k most likely tokens "
                         "(0 = full distribution; --serve only)")
    ap.add_argument("--top-p", type=float, default=0.0,
                    help="nucleus sampling: truncate to the smallest "
                         "token set with cumulative probability >= p "
                         "(0 = disabled; --serve only)")
    ap.add_argument("--no-window", action="store_true",
                    help="always decode at full slot width instead of "
                         "the fill-covering pow2 window (--serve only)")
    args = ap.parse_args(argv)
    from repro.runtime.compile_cache import configure_compile_cache
    configure_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    budget = max(64, args.prompt_len // 2)
    if args.policy == "unicaim":
        prune = baselines.unicaim(heavy=budget, reserve=64,
                                  select_k=max(16, budget // 8),
                                  fused=args.fused)
    elif args.policy == "h2o":
        prune = baselines.h2o(heavy=budget, reserve=64)
    elif args.policy == "streaming":
        prune = baselines.streaming(budget + 64)
    else:
        prune = baselines.dense(args.prompt_len + args.new_tokens)
    model = Model(cfg, prune)
    # only unicaim has two decode engines; the summary names the one used
    engine = ""
    if args.policy == "unicaim":
        engine = f" engine={decode_engine(prune, cfg.mla is not None)}"
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    if args.serve:
        loop = ServeLoop(model, params, lanes=args.batch,
                         max_new=args.new_tokens, block=8,
                         buckets=None if args.no_buckets else "auto",
                         chunk_prefill=args.chunk_prefill,
                         group_admit=not args.sequential_admit,
                         temperature=args.temperature, top_k=args.top_k,
                         top_p=args.top_p,
                         window=None if args.no_window else "auto",
                         prefix_cache_bytes=args.prefix_cache)
        lens = (args.prompt_len, max(8, args.prompt_len // 2),
                max(8, args.prompt_len - 7), max(8, args.prompt_len // 3))
        for i in range(2 * args.batch):
            loop.submit(Request(
                prompt=rng.integers(0, cfg.vocab_size, lens[i % len(lens)]),
                max_new=args.new_tokens // (1 + i % 2)))
        t0 = time.time()
        stats = loop.run()
        dt = time.time() - t0
        agg = loop.aggregate()
        for s in stats:
            print(f"  req {s.rid}: lane={s.lane} prompt={s.prompt_len} "
                  f"bucket={s.bucket} chunks={s.prefill_chunks} "
                  f"new={len(s.tokens)} latency={s.latency:.2f}s "
                  f"ttft={s.ttft:.2f}s occ={s.occupancy:.2f}")
        print(f"arch={cfg.name} policy={args.policy}{engine} "
              f"served {len(stats)} reqs on {args.batch} lanes in {dt:.2f}s "
              f"({agg['tokens_per_s']:.1f} tok/s, "
              f"p99_ttft={agg['p99_ttft_s']:.2f}s, "
              f"{loop.prefill_programs()['loop_shapes']} prefill shapes, "
              f"{loop.counters['prefill_dispatches']} prefill + "
              f"{loop.counters['admit_dispatches']} admit dispatches, "
              f"{loop.counters['grouped_requests']} reqs group-admitted)")
        if loop.prefix_cache is not None:
            print(f"prefix cache: hit_rate={agg['prefix_hit_rate']:.2f} "
                  f"dedup={agg['prefix_dedup_ratio']:.2f} "
                  f"{int(agg['prefix_cache_bytes'])} bytes, "
                  f"{int(agg['prefix_cache_entries'])} entries, "
                  f"{loop.counters['prefix_evictions']} evictions")
        return

    prompts = rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len))
    batch = {"tokens": jnp.asarray(prompts)}
    t0 = time.time()
    if args.no_scan:
        toks, _ = greedy_generate(model, params, batch, args.new_tokens)
    else:
        toks, _ = generate_scan(model, params, batch, args.new_tokens)
    toks = jax.block_until_ready(toks)
    dt = time.time() - t0
    mode = "loop" if args.no_scan else "scan"
    print(f"arch={cfg.name} policy={args.policy} mode={mode}{engine} "
          f"cache_slots={prune.slots} "
          f"generated {toks.shape} in {dt:.2f}s "
          f"({args.batch * args.new_tokens / dt:.1f} tok/s)")


if __name__ == "__main__":
    main()
