"""One run of one cell: build, warm up, serve a timed window through
`ServeLoop`, reduce, check against the plain reference, report.

The program is used through its serving surface: the model (`Model`,
built from the configuration file's sizes and prune settings) and
`ServeLoop.submit` / `ServeLoop.run`, exactly as served. Traffic starts
`lead_s` seconds (the workload file's) before the window opens, so that
the window sees a loaded system. The window closes through
`Request.deadline_s`: every request's deadline is the window's end, so
at the close queued requests resolve with their censored time to first
token and decoding lanes stop at the next block.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

from bench import (program_spans, requests, spec, trace_reduce, traffic,
                   weights)
from bench.reference import Reference

FIRST_TOKENS = 12        # served requests whose first token is compared
# What the dense Model computes where its ModelConfig has no field of the
# name: no o-projection bias, no MLP biases, norm epsilon 1e-6, no window
PROGRAM_DEFAULTS = {"out_bias": False, "mlp_bias": False, "norm_eps": 1e-6,
                    "sliding_window": None}


def log(msg: str) -> None:
    print(msg, flush=True)


def configure_compile_cache(root: Path) -> str:
    """JAX's persistent compile cache: $JAX_COMPILATION_CACHE_DIR when
    set, else a fixed directory inside the checkout."""
    import jax
    if jax.default_backend() != "tpu":
        return ""                     # CPU runs (the tests) cache nothing
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileClock:
    """Counts JAX's backend compiles (and their seconds) while armed."""

    def __init__(self):
        import jax
        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def take(self):
        out = (self.count, self.seconds)
        self.seconds, self.count = 0.0, 0
        return out


def peak_table(kind: str) -> Dict[str, float]:
    table = spec.load_json(spec.BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


# -- the program under test ---------------------------------------------------

def program_model(cfg_spec: Dict[str, Any]):
    """The program's Model for the configuration file, checked against
    the repo's registered configuration of the same model when named.

    A block the program cannot serve as stated is refused by name: an
    activation it has no form of (its `gelu` is the tanh form), and an
    o-projection bias, MLP biases, a norm epsilon other than 1e-6 or a
    sliding window while `ModelConfig` has no field `out_bias`,
    `mlp_bias`, `norm_eps` or `sliding_window` (one error lists every
    field missing). Where it has the field, the file's value is set."""
    from repro.configs.base import ModelConfig, PruneConfig, get_config
    from repro.models.transformer import Model
    m = cfg_spec["model"]
    act = {"gated": "swiglu", "gelu_tanh": "gelu"}.get(weights.activation(m))
    if act is None:
        raise ValueError(f"hidden_act {m['hidden_act']!r}: the program's "
                         f"MLP has no such form (its gelu is tanh-GELU)")
    fields = dict(
        family="dense", num_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        norm="ln" if weights.layernorm(m) else "rms",
        act=act, pos="rope",
        rope_theta=float(m["rope_theta"]),
        tie_embeddings=bool(m["tie_word_embeddings"]),
        qkv_bias=weights.qkv_bias(m))
    stated = {"out_bias": weights.out_bias(m),
              "mlp_bias": weights.mlp_bias(m),
              "norm_eps": weights.norm_eps(m),
              "sliding_window": weights.sliding_window(m)}
    have = {f.name for f in dataclasses.fields(ModelConfig)}
    missing = [k for k, v in stated.items()
               if k not in have and v != PROGRAM_DEFAULTS[k]]
    if missing:
        raise ValueError(
            "the configuration states " + ", ".join(
                f"{k}={stated[k]!r}" for k in missing)
            + "; the program's ModelConfig has no field "
            + ", ".join(missing))
    fields.update({k: v for k, v in stated.items() if k in have})
    if cfg_spec.get("repo_config"):
        mc = get_config(cfg_spec["repo_config"])
        differ = {k: (getattr(mc, k), v) for k, v in fields.items()
                  if getattr(mc, k) != v}
        if differ:
            raise ValueError(f"configuration file and the repo's "
                             f"{mc.name} differ: {differ}")
    else:
        mc = ModelConfig(name=cfg_spec["name"], **fields)
    if mc.param_dtype != "bfloat16" or mc.compute_dtype != "bfloat16":
        raise ValueError("the configuration states bfloat16 weights and "
                         "activations")
    return Model(mc, PruneConfig(**cfg_spec["prune"]))


def serve_options(cfg_spec: Dict[str, Any]) -> Dict[str, Any]:
    return {k: v for k, v in cfg_spec["serve"].items()
            if not k.startswith("lanes_")}


def check_tree(model, params) -> None:
    """The bench-made tree has the program's layout, shapes and dtypes."""
    import jax
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("bench weights do not match the program's "
                         "parameter layout")


def bucket_set(wl: Dict[str, Any]) -> List[int]:
    from repro.launch.serve import bucket_length
    out = set()
    for c in wl["classes"]:
        b = bucket_length(int(c["prompt"]["min"]))
        while True:
            out.add(b)
            if b >= bucket_length(int(c["prompt"]["max"])):
                break
            b *= 2
    return sorted(out)


def group_sizes(lanes: int) -> List[int]:
    sizes, g = {1}, 2
    while True:
        sizes.add(min(g, lanes))
        if g >= lanes:
            break
        g *= 2
    return sorted(sizes)


def _serve_once(model, params, opts, reqs) -> Any:
    from repro.launch.serve import Request, ServeLoop
    loop = ServeLoop(model, params, **opts)
    for r in reqs:
        loop.submit(Request(prompt=r["prompt"], max_new=r["max_new"]))
    loop.run()
    return loop


def warm_up(model, params, opts, wl, vocab: int) -> Dict[str, Any]:
    """Run every program the cell's traffic can dispatch once, in
    throwaway loops: for each prefill bucket, a lone admission and each
    padded group size up to the lanes; then each decode-block slot
    window the fills can reach."""
    from repro.core.cache import decode_window
    lanes, block = opts["lanes"], opts.get("block", 1)
    prune, slots = model.prune, model.decode_slots
    rng = np.random.default_rng(0)
    lo = min(int(c["prompt"]["min"]) for c in wl["classes"])
    hi = max(int(c["prompt"]["max"]) for c in wl["classes"])
    out_max = max(int(c["output"]["max"]) for c in wl["classes"])
    windows, loops = set(), 0
    for b in bucket_set(wl):
        t = max(lo, min(b, hi))
        for g in group_sizes(lanes):
            loop = _serve_once(model, params, opts, [
                {"prompt": rng.integers(0, vocab, t, dtype=np.int32),
                 "max_new": 1} for _ in range(g)])
            windows |= loop._windows
            loops += 1
            del loop                  # one loop's state on the chip at a time
    reach = {decode_window(f, block, slots, prune, opts.get(
        "window_grid", "pow2")) for f in range(
            min(lo, prune.heavy_budget),
            min(min(hi, prune.heavy_budget) + out_max, slots) + 1)}
    for w in sorted(reach - windows, key=lambda w: w or 1 << 30):
        f = next(f for f in range(min(lo, prune.heavy_budget), slots + 1)
                 if decode_window(f, block, slots, prune) == w)
        p = f if lo <= f <= min(hi, prune.heavy_budget) else lo
        grow = max(f - min(p, prune.heavy_budget), 0) + block
        loop = _serve_once(model, params, opts, [
            {"prompt": rng.integers(0, vocab, p, dtype=np.int32),
             "max_new": grow}])
        windows |= loop._windows
        loops += 1
        del loop
    return {"loops": loops, "windows": sorted(
        windows, key=lambda w: w or 1 << 30)}


# -- spans (traced runs only) ---------------------------------------------------

SPANNED = ("schedule", "_step_block", "_admit_group", "_admit_lane",
           "_sweep_lanes", "_advance_chunked")


def add_spans(loop) -> None:
    """Host spans around the loop's phases, from the benchmark's side:
    each call is wrapped in a profiler TraceAnnotation `bench.<phase>`."""
    import functools
    import jax

    for name in SPANNED:
        fn = getattr(loop, name, None)
        if fn is None:
            continue

        def wrapped(*a, _fn=fn, _span="bench." + name.strip("_"), **k):
            with jax.profiler.TraceAnnotation(_span):
                return _fn(*a, **k)

        setattr(loop, name, functools.wraps(fn)(wrapped))


# -- the timed window -----------------------------------------------------------

def watch_blocks(loop, opener) -> List[Dict[str, Any]]:
    """Records every decode block the loop dispatches: its start and end
    (host clock) and, per lane that was decoding, (prompt length, tokens
    the request had, tokens the block emitted). `opener(t)` runs before
    each block starts; it opens the window when its time has come."""
    blocks: List[Dict[str, Any]] = []
    step = loop._step_block

    def watched(*a, **k):
        opener(time.perf_counter())
        lanes = [(int(i), loop._lane_rid[i]) for i in np.flatnonzero(
            loop.active) if loop._lane_rid[i] is not None]
        before = [len(loop.outputs[i]) for i, _ in lanes]
        t0 = time.perf_counter()
        out = step(*a, **k)
        blocks.append({"t0": t0, "t1": time.perf_counter(), "lanes": [
            (loop.stats[rid].prompt_len, n0,
             max(len(loop.outputs[i]) - n0, 0))
            for (i, rid), n0 in zip(lanes, before)]})
        return out

    loop._step_block = watched
    return blocks


def serve_window(model, params, opts, reqs, seconds: float, lead: float,
                 clock, trace_dir: Optional[str] = None) -> Dict[str, Any]:
    """Serves `reqs` (arrivals over lead + seconds) through one ServeLoop
    run. The window opens at the first decode block that starts `lead`
    seconds or more into the run (at the run's start where lead is 0):
    the traffic has been arriving for a request's lifetime by then, so
    the window sees the system loaded, not filling up. Every request's
    deadline is lead + seconds after the run's start."""
    import jax
    from repro.launch.serve import Request, ServeLoop
    loop = ServeLoop(model, params, **opts)
    handles = [loop.submit(Request(prompt=r["prompt"], max_new=r["max_new"],
                                   arrival=r["arrival"],
                                   deadline_s=lead + seconds - r["arrival"]))
               for r in reqs]
    if trace_dir is not None:
        add_spans(loop)
    win: Dict[str, Any] = {"t_run": math.inf}

    def opener(t):
        if "t_open" in win or t - win["t_run"] < lead:
            return
        win["t_open"] = t
        win["lead_compiles"] = clock.take()
        if trace_dir is not None:
            jax.profiler.start_trace(trace_dir)
            win["span"] = jax.profiler.TraceAnnotation("bench.window")
            win["span"].__enter__()
        win["t_open"] = time.perf_counter()

    blocks = watch_blocks(loop, opener)
    clock.take()
    win["t_run"] = time.perf_counter()
    if lead <= 0:
        opener(win["t_run"])
    loop.run()
    t_end = time.perf_counter()
    opener(t_end + lead)          # a run that ended before the window opened
    compiles = clock.take()
    if trace_dir is not None:
        win["span"].__exit__(None, None, None)
        jax.profiler.stop_trace()
    stats = [h.stats for h in handles]
    t_open = win["t_open"]
    return {"loop": loop, "params": params, "stats": stats,
            "window_stats": [s for s, r in zip(stats, reqs)
                             if r["arrival"] >= lead],
            "blocks": [b for b in blocks if b["t0"] >= t_open],
            "t_open": t_open, "open_rel": t_open - win["t_run"],
            "elapsed": t_end - t_open, "compiles": compiles,
            "lead_compiles": win["lead_compiles"]}


def timeline(win: Dict[str, Any]) -> str:
    """Where the window's host time went around its decode blocks: the
    wait from the window's opening to its first block, the blocks'
    median and longest durations, the longest gaps between one block's
    end and the next one's start, and the program's blocking reads by
    what they wait for. A run that stalls names the block and the read
    it stalled in."""
    blocks = win["blocks"]
    if not blocks:
        return "no decode blocks in the window"
    durs = [b["t1"] - b["t0"] for b in blocks]
    gaps = [b["t0"] - a["t1"] for a, b in zip(blocks, blocks[1:])]

    def top(xs, first=0):
        return [(i + first, round(x, 4)) for i, x in sorted(
            enumerate(xs), key=lambda p: -p[1])[:3]]

    w = program_spans.window(win)
    reads = {} if w is None else {
        k: (round(t, 4), round(m, 4), n)
        for k, (t, m, n) in program_spans.waits(
            w[2], win["t_open"], w[1]).items()}
    return (f"first block {blocks[0]['t0'] - win['t_open']:.4f} s after the "
            f"window opened; block s median {float(np.median(durs)):.4f}, "
            f"longest (block, s) {top(durs)}; longest gaps before a block "
            f"{top(gaps, 1)}; blocking reads (s, longest, count) {reads}")


def end_to_end(win: Dict[str, Any]) -> Dict[str, float]:
    stats = win["window_stats"]
    return {
        "output_tok_s": requests.block_tokens(win["blocks"]) / win["elapsed"],
        "ttft_p90_s": requests.percentile(requests.ttft_s(stats), 90),
        "tpot_p90_ms": requests.percentile(requests.tpot_ms(stats), 90),
    }


# -- correctness ----------------------------------------------------------------
#
# Four numbers, each against the plain reference (bench/reference.py):
#
#   first_token_gap       how far the first served token's logit lies below
#                         the reference's best after the prompt, over
#                         FIRST_TOKENS served requests: the answer itself,
#                         end to end through prefill and the LM head;
#   prefill_lockstep_err  the served prefill program on the longest of those
#                         prompts, the reference beside it at every layer on
#                         the same q, k, v: the attention rows;
#   prefill_evict_err     the same prefill, the static eviction: the kept
#                         slots' column sums and keys, and how far a kept
#                         position falls short of what the eviction needs;
#   decode_lockstep_err   one decode step of the served program on its own
#                         state after the window (every lane, the served
#                         window), the reference beside it at every layer:
#                         attention rows (CAM pass, top-k, exact attention
#                         over the winners) and the accumulated scores after
#                         the step (token write, eviction, accumulation).
#
# Decode tokens are not compared one by one against a teacher-forced
# reference: the pruned decode is discontinuous in its inputs, bf16
# rounding flips winners and the streams part (PERF.md). In lockstep every
# layer starts from the program's own inputs, so nothing diverges.

def choose_first(stats, seed: int, n: int = FIRST_TOKENS) -> List[Any]:
    """Served requests whose first token is compared: the one with the
    longest prompt, then others drawn from the seed, n in all."""
    served = [s for s in stats if s.tokens]
    if not served:
        return []
    longest = max(served, key=lambda s: s.prompt_len)
    rest = [s for s in served if s is not longest]
    rng = np.random.default_rng(seed + 1)
    return [longest] + [rest[i] for i in rng.permutation(len(rest))[:n - 1]]


def first_gaps(ref, w, prompts, stats, vocab, other=None) -> List[float]:
    """Per chosen request: how far its first served token (or the token
    Reference `other` puts first) lies below the reference's best after
    the prompt."""
    out = []
    for s in stats:
        prompt = prompts[s.rid]
        lg = ref.first_logits(w, prompt)
        tok = (int(np.clip(s.tokens[0], 0, vocab - 1)) if other is None
               else int(other.first_logits(w, prompt).argmax()))
        out.append(float(lg.max() - lg[tok]))
    return out


def reference_pad(wl: Dict[str, Any]) -> int:
    return max(bucket_set(wl))


def _rel(a, b, axes):
    """max |a - b| over `axes`, over max |b| there."""
    import jax.numpy as jnp
    num = jnp.max(jnp.abs(a - b), axis=axes)
    return num / jnp.maximum(jnp.max(jnp.abs(b), axis=axes), 1e-30)


DECODE_PARTS = ("rows_acc",)
PREFILL_PARTS = ("rows_max", "rows_rms", "colsum", "keys", "keep")
# Parts compared, by number. The worst row is left out: over 40 layers x
# 32 heads x ~4k rows it reads the extreme of bf16 rounding, 2.7x under
# the int8 control; the per-head root mean square reads 2.8-3.1x under
# it over a dozen seeds, the eviction's parts (f32 column sums) 700x and
# more (PERF.md)
PREFILL_COMPARED = {"prefill_lockstep_err": ("rows_rms",),
                    "prefill_evict_err": ("colsum", "keys", "keep")}


class Lockstep:
    """The program's own step on the window's data, with the plain
    reference computed beside it at every layer on the same inputs (and,
    for the control, the reference in int8 in the program's place), so
    that no layer's rounding reaches the next:

      decode   one decode step (`Model.decode_step`, the in-place layer
               scan the decode block runs, at the served window) from the
               window's final state; the reference sits beside
               `decode_attention_stacked` (write, CAM pass, top-k, exact
               attention, accumulation);
      prefill  the lone-admission prefill (`Model.prefill_one`, at the
               prompt's bucket) of a served prompt; the reference sits
               beside `prefill_and_prune` (causal attention, column sums,
               the static eviction into the slots).

    Each returns the worst relative error over layers, lanes and kv
    heads. One compiled program per shape and process."""

    def __init__(self, model, m, p, control: bool):
        self.model, self.control = model, control
        self.ref = Reference(m, p, "f32", prompt_pad=1)
        self.ref8 = Reference(m, p, "int8", prompt_pad=1) if control else None
        # the program's query carries W_q's fold (bench/weights.py): the
        # reference takes it back to the published query
        self.q_fold = weights.folds(m).get("wq", 1.0)
        self.rows: List[Any] = []
        self.fns: Dict[Any, Any] = {}
        self.parts: Dict[str, Dict[str, float]] = {}

    def _record(self, errs) -> None:
        import jax
        jax.debug.callback(lambda *e: self.rows.append(
            [np.asarray(x) for x in e]), *errs, ordered=True)

    def _run(self, module, name, beside, key, make, *args
             ) -> Dict[str, Dict[str, float]]:
        """Runs `make()`'s program with `beside` around `module.name`;
        returns, for the program (and the control), the worst value of
        each part the wrapper recorded ([lanes, parts, kv heads] a
        layer)."""
        import jax
        orig = getattr(module, name)
        setattr(module, name, beside(orig))
        self.rows = []
        try:
            if key not in self.fns:
                self.fns[key] = make()
            jax.block_until_ready(self.fns[key](*args))
            jax.effects_barrier()
        finally:
            setattr(module, name, orig)
        layers = self.model.cfg.num_layers
        if len(self.rows) != layers:
            raise RuntimeError(f"lockstep {key[0]} saw {len(self.rows)} of "
                               f"{layers} layers")
        names = ["program", "control"][:len(self.rows[0])]
        parts = PREFILL_PARTS if key[0] == "prefill" else DECODE_PARTS
        return {n: dict(zip(parts, np.max([r[i].max((0, 2)) for r in
                                           self.rows], 0).tolist()))
                for i, n in enumerate(names)}

    # -- decode -------------------------------------------------------------------

    def _beside_decode(self, orig):
        import jax
        import jax.numpy as jnp
        from bench.reference import Layer

        def both(kv, li, q, k_new, v_new, prune, window, active):
            kv2, out = orig(kv, li, q, k_new, v_new, prune, window, active)
            w = kv.slots if window is None or window >= kv.slots else window

            def at(a, cut=True):
                x = jax.lax.dynamic_index_in_dim(a, li, 0, keepdims=False)
                return x[:, :, :w] if cut else x

            pre = Layer(k=at(kv.k), v=at(kv.v), kq=at(kv.kq),
                        ks=at(kv.kscale), acc=at(kv.acc), valid=at(kv.valid),
                        pos=at(kv.pos), fill=at(kv.fill, False),
                        step=at(kv.step, False))
            args = (pre, q.astype(jnp.float32) / self.q_fold,
                    k_new.astype(jnp.float32), v_new.astype(jnp.float32))
            r_out, r_acc = jax.vmap(self.ref.attend)(*args)
            b, hk = r_acc.shape[:2]
            g = q.shape[1] // hk
            live = pre.fill > 0

            def err(o, acc):
                o = o.astype(jnp.float32).reshape(b, hk, g, -1)
                e = jnp.maximum(
                    _rel(o, r_out.reshape(b, hk, g, -1), (3,)).max(2),
                    _rel(acc, r_acc, (2,)))
                return jnp.where(live[:, None], e, 0.0)[:, None]

            errs = [err(out, at(kv2.acc))]
            if self.control:
                errs.append(err(*jax.vmap(self.ref8.attend)(*args)))
            self._record(errs)
            return kv2, out

        return both

    def decode(self, params, state, tok) -> Dict[str, float]:
        """{"program": worst error[, "control": ...]} of one decode step
        from `state`: attention rows and accumulated scores."""
        import functools
        import jax
        from repro.core.cache import decode_window
        from repro.models import attention_layer
        m = self.model
        fill = int(np.asarray(state.kv.fill).max())
        window = decode_window(fill, 1, m.decode_slots, m.prune)
        out = self._run(
            attention_layer, "decode_attention_stacked", self._beside_decode,
            ("decode", window), lambda: jax.jit(functools.partial(
                m.decode_step, window=window, inplace=True)),
            params, state, tok)
        return {who: v["rows_acc"] for who, v in out.items()}

    # -- prefill ------------------------------------------------------------------

    def _prefill_err(self, o_r, c_r, k, n, o, pos, valid, acc, kk):
        """[PREFILL_PARTS, Hk]: per kv head, the worst and the root mean
        square relative error of the attention rows; the kept slots'
        column sums (relative to the largest) and keys against the
        reference's; how far a kept position's rank (`Reference.rank`)
        falls short of what the reference's static eviction needs
        (relative to the largest column sum): a protected one never, one
        the reference never keeps (past the prompt, or out of a window)
        by inf."""
        import jax.numpy as jnp
        N, hq, _ = o_r.shape
        hk = c_r.shape[0]
        real = (jnp.arange(N) < n)[:, None]
        row = jnp.where(real, _rel(o, o_r, (2,)), 0.0)            # [N, Hq]
        rows_max = row.max(0).reshape(hk, -1).max(1)
        rows_rms = jnp.sqrt((row * row).sum(0) / n).reshape(hk, -1).max(1)
        cmax = jnp.maximum(c_r.max(-1), 1e-30)                     # [Hk]
        sp = jnp.where(valid, pos, 0)
        c_at = jnp.take_along_axis(c_r, sp, 1)
        colsum = jnp.where(valid, jnp.abs(acc - c_at), 0.0).max(-1) / cmax
        kt = jnp.swapaxes(k, 0, 1)                                 # [Hk,N,dh]
        k_at = jnp.take_along_axis(kt, sp[..., None], 1)
        keys = jnp.where(valid[..., None], jnp.abs(kk - k_at), 0.0).max(
            (1, 2)) / jnp.maximum(jnp.abs(kt).max((1, 2)), 1e-30)
        _, need = self.ref.keep(c_r, n)
        r_at = jnp.take_along_axis(self.ref.rank(c_r, n), sp, 1)
        keep = jnp.where(valid, jnp.maximum(need[:, None] - r_at, 0),
                         0.0).max(-1) / cmax
        return jnp.stack([rows_max, rows_rms, colsum, keys, keep])

    def _beside_prefill(self, orig):
        import jax
        import jax.numpy as jnp

        def both(cache, q, k, v, prune, chunk=512, length=None):
            cache2, out = orig(cache, q, k, v, prune, chunk=chunk,
                               length=length)
            b, _, N, _ = q.shape
            n_all = (jnp.full((b,), N, jnp.int32) if length is None
                     else length.astype(jnp.int32))

            def lane(a):
                qb, kb, vb, n, ob, pos, valid, acc, kk = a
                q_ = jnp.swapaxes(qb.astype(jnp.float32), 0, 1) / self.q_fold
                k_ = jnp.swapaxes(kb.astype(jnp.float32), 0, 1)
                v_ = jnp.swapaxes(vb.astype(jnp.float32), 0, 1)
                o_r, c_r = self.ref.prefill_attend(q_, k_, v_, n)
                o_p = jnp.swapaxes(ob.astype(jnp.float32), 0, 1)
                errs = [self._prefill_err(o_r, c_r, k_, n, o_p, pos, valid,
                                          acc, kk.astype(jnp.float32))]
                if self.control:
                    o8, c8 = self.ref8.prefill_attend(q_, k_, v_, n)
                    idx, _ = self.ref.keep(c8, n)
                    kk8 = jnp.take_along_axis(jnp.swapaxes(k_, 0, 1),
                                              idx[..., None], 1)
                    errs.append(self._prefill_err(
                        o_r, c_r, k_, n, o8, idx, jnp.ones(idx.shape, bool),
                        jnp.take_along_axis(c8, idx, 1), kk8))
                return errs

            heavy = self.ref.heavy
            errs = jax.lax.map(lane, (
                q, k, v, n_all, out, cache2.pos[..., :heavy],
                cache2.valid[..., :heavy], cache2.acc[..., :heavy],
                cache2.k[..., :heavy, :]))
            self._record(errs)
            return cache2, out

        return both

    def prefill(self, params, prompt) -> Dict[str, Dict[str, float]]:
        """{"program": {number: worst error}[, "control": ...]} of the
        prefill of `prompt` at its bucket, each number over its parts in
        PREFILL_COMPARED; every part's worst value is kept in
        `self.parts`."""
        import jax
        import jax.numpy as jnp
        from repro.launch.serve import pad_to_bucket
        from repro.models import attention_layer
        tokens, n = pad_to_bucket(np.asarray(prompt, np.int32))
        self.parts = self._run(
            attention_layer, "prefill_and_prune", self._beside_prefill,
            ("prefill", len(tokens)),
            lambda: jax.jit(self.model.prefill_one),
            params, jnp.asarray(tokens), jnp.int32(n))
        return {who: {num: max(v[k] for k in parts)
                      for num, parts in PREFILL_COMPARED.items()}
                for who, v in self.parts.items()}


def limits(wl: Dict[str, Any]) -> Dict[str, float]:
    chk = wl["check"]
    return {"first_token_gap": float(chk["first_token_gap_limit"]),
            "prefill_lockstep_err": float(chk["prefill_lockstep_limit"]),
            "prefill_evict_err": float(chk["prefill_evict_limit"]),
            "decode_lockstep_err": float(chk["decode_lockstep_limit"])}


def judge(values: Dict[str, float], lim: Dict[str, float],
          in_vocab: bool) -> Dict[str, Any]:
    """`correct` and the numbers compared, each beside its limit."""
    compared = {k: {"value": values.get(k, math.inf), "limit": lim[k]}
                for k in lim}
    ok = in_vocab and all(math.isfinite(v["value"])
                          and v["value"] <= v["limit"]
                          for v in compared.values())
    return {"correct": bool(ok), "compared": compared}


# -- the run --------------------------------------------------------------------

def device_info():
    import jax
    d = jax.devices()
    return d[0].platform, d[0].device_kind, len(d)


def memory_peak() -> Optional[int]:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return None if peak is None else int(peak)


class Bench:
    """What one process keeps across the windows it serves: the program's
    model, serving options, compiled warm-up and lockstep step."""

    def __init__(self, cell: Dict[str, Any], root: Path, control=False):
        self.wl, self.cfg = cell["workload"], cell["config"]
        configure_compile_cache(root)
        self.clock = CompileClock()
        self.model = program_model(self.cfg)
        self.opts = serve_options(self.cfg)
        self.m = self.cfg["model"]
        self.vocab = self.m["vocab_size"]
        self.lead = float(self.wl.get("lead_s", 0.0))
        self.lockstep = Lockstep(self.model, self.m, self.cfg["prune"],
                                 control)
        self.warm: Optional[Dict[str, Any]] = None
        self.ref: Optional[Reference] = None
        self.ctl: Optional[Reference] = None

    def params(self, seed: int):
        import jax
        params = weights.program_params(self.m, seed)
        check_tree(self.model, params)
        jax.block_until_ready(params)
        if self.warm is None:
            self.warm = warm_up(self.model, params, self.opts, self.wl,
                                self.vocab)
        return params

    def requests(self, seed: int, seconds: float):
        return traffic.generate(self.wl, seed, self.lead + seconds,
                                self.vocab, self.opts["lanes"])

    def check(self, win, seed: int, prompts) -> Dict[str, Any]:
        """Runs the lockstep decode step on the window's final state and
        the lockstep prefill of the longest chosen prompt, frees the
        program's state and weights (`win` holds the only references),
        then reads the first tokens. Returns, for the program and (with
        a control) the control, the numbers compared."""
        loop, params, stats = win.pop("loop"), win.pop("params"), \
            win["stats"]
        chosen = choose_first(stats, seed)
        t0 = time.perf_counter()
        dec = self.lockstep.decode(params, loop.state, loop.tok)
        del loop
        gc.collect()
        pre = self.lockstep.prefill(params, prompts[chosen[0].rid]) \
            if chosen else {}
        t_lock = time.perf_counter() - t0
        del params
        gc.collect()
        w = weights.make(self.m, seed)
        if self.ref is None:
            pad = reference_pad(self.wl)
            self.ref = Reference(self.m, self.cfg["prune"], "f32",
                                 prompt_pad=pad)
            self.ctl = Reference(self.m, self.cfg["prune"], "int8",
                                 prompt_pad=pad)
        ref = self.ref
        t0 = time.perf_counter()
        out = {"program": {"first_token_gap": max(first_gaps(
            ref, w, prompts, chosen, self.vocab), default=math.inf),
            **pre.get("program", {}),
            "decode_lockstep_err": dec["program"]}}
        if "control" in dec:
            out["control"] = {"first_token_gap": max(first_gaps(
                ref, w, prompts, chosen, self.vocab, other=self.ctl),
                default=math.inf),
                **pre.get("control", {}),
                "decode_lockstep_err": dec["control"]}
        del w
        log(f"prefill lockstep parts {finite_json(self.lockstep.parts)}")
        log(f"checks: lockstep decode step and prefill of a "
            f"{len(prompts[chosen[0].rid]) if chosen else 0}-token prompt "
            f"over {self.model.cfg.num_layers} layers in {t_lock:.1f} s; "
            f"first tokens of {len(chosen)} served requests in "
            f"{time.perf_counter() - t0:.1f} s")
        return out


def run_cell(cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
             t_process: float, root: Path = spec.ROOT,
             peak: Optional[Dict[str, float]] = None) -> Dict[str, Any]:
    """Everything after the chip check. Returns the result line's dict
    (with "compared" last); prints the earlier lines. `peak` stands in
    for the peak table's entry where the device has none (tests on the
    CPU)."""
    b = Bench(cell, root)
    platform, kind, count = device_info()
    params = b.params(seed)
    from repro.core.attention import decode_engine
    engine = decode_engine(b.model.prune)
    reqs = b.requests(seed, seconds)
    prompts = {i: r["prompt"] for i, r in enumerate(reqs)}
    setup_compiles = b.clock.take()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    win = serve_window(b.model, params, b.opts, reqs, seconds, b.lead,
                       b.clock, trace_dir)
    del params                  # `win` holds the program's weights now
    setup_s = win["t_open"] - t_process
    mem_peak = memory_peak()
    stats, wstats = win["stats"], win["window_stats"]
    counters = dict(win["loop"].counters)
    outcome = requests.outcomes(wstats)
    e2e = end_to_end(win)
    log(f"device {platform} / {kind} x{count}; engine fused=\"auto\" -> "
        f"{engine}; donation {counters.get('donation')}")
    log(f"setup {setup_s:.3f} s: warm-up loops {b.warm['loops']}, decode "
        f"windows {b.warm['windows']}, setup compiles {setup_compiles[0]} "
        f"({setup_compiles[1]:.1f} s); lead-in {b.lead} s of traffic, "
        f"window opened {win['open_rel']:.3f} s into the run, compiles "
        f"during the lead-in {win['lead_compiles'][0]}")
    log(f"window {seconds} s, closed after {win['elapsed']:.3f} s; compiles "
        f"inside the window: {win['compiles'][0]} "
        f"({win['compiles'][1]:.3f} s)")
    waits = [s.t_admit - s.t_arrival for s in wstats if s.t_admit > 0]
    log(f"generator: {len(reqs)} requests scheduled before the run, "
        f"{len(wstats)} arriving in the window, each stamped at its due "
        f"time (lateness 0 by construction); mean queue wait "
        f"{np.mean(waits) if waits else float('nan'):.3f} s over "
        f"{len(waits)} admitted")
    log(f"samples: window requests {len(wstats)}, outcomes {outcome}, "
        f"never admitted at the close "
        f"{sum(1 for s in wstats if s.lane < 0 and s.t_admit <= 0)}, "
        f"tokens emitted in the window "
        f"{requests.block_tokens(win['blocks'])}, ttft samples "
        f"{len(wstats)}, tpot samples {len(requests.tpot_ms(wstats))}, "
        f"decode blocks in the window {len(win['blocks'])} (run "
        f"{counters['decode_blocks']}), prefill dispatches "
        f"{counters['prefill_dispatches']}")
    log(f"host timeline: {timeline(win)}")
    log(f"peak_bytes_in_use {mem_peak}")
    ctx = None
    if trace:
        t_read = time.perf_counter()
        path = trace_reduce.find_xplane(trace_dir)
        events = trace_reduce.read_xplane(path)
        shutil.rmtree(trace_dir, ignore_errors=True)
        span = trace_reduce.window_ns(events, "bench.window")
        blocks = trace_reduce.time_in_spans(events, "bench.step_block",
                                            ("prefill", "admit"))
        log(f"trace: read in {time.perf_counter() - t_read:.1f} s, "
            f"{len(events)} events on "
            f"{trace_reduce.device_planes(events)}; {blocks[1]} device "
            f"programs started in the loop's decode phase "
            f"({len(win['blocks'])} decode blocks, {blocks[0]:.3f} s); "
            f"device programs by time "
            f"{trace_reduce.top_ops(events, 8, trace_reduce.MODULES)}")
        open_rel = win["open_rel"]
        ctx = {"events": events, "span": span, "blocks": win["blocks"],
               "prompt_tokens": sum(s.prompt_len for s in stats
                                    if s.t_admit >= open_rel),
               "lanes": b.opts["lanes"], "block": b.opts.get("block", 1),
               "model": b.m, "prune": b.cfg["prune"],
               "peak": peak or peak_table(kind)}
    in_vocab = all(0 <= t < b.vocab for s in stats for t in s.tokens)
    values = b.check(win, seed, prompts)["program"]
    verdict = judge(values, limits(b.wl), in_vocab)
    log(f"all served tokens in the vocabulary: {in_vocab}")
    names = {x["name"] for x in cell["end_to_end"]}
    e2e["setup_s"] = setup_s
    units = {x["name"]: x["unit"] for x in cell["end_to_end"]
             + cell["per_layer"]}
    result: Dict[str, Any] = {
        "correct": verdict["correct"], "attempted": len(wstats),
        "failed": sum(outcome.get(k, 0) for k in requests.MISSING)}
    device = {"platform": platform, "kind": kind, "count": count,
              "memory_peak_bytes": mem_peak}
    if trace:
        lo, hi = ctx["span"]
        busy = trace_reduce.busy_seconds(ctx["events"], clip=(lo, hi))
        device.update(busy_s=busy, window_s=(hi - lo) * 1e-9)
        ctx.update(busy_s=busy, window_s=(hi - lo) * 1e-9)
        metrics = {}
        for x in cell["per_layer"]:
            v = spec.metric_reader(x["name"])(ctx)
            if v is not None and math.isfinite(v):
                metrics[x["name"]] = {"value": v, "unit": x["unit"]}
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(ctx["events"]),
            "idle_gaps": trace_reduce.idle_gaps(ctx["events"], lo, hi)}
        for k, v in e2e.items():
            log(f"(traced run) {k} {v}")
    else:
        result["metrics"] = {k: {"value": v, "unit": units[k]}
                             for k, v in e2e.items() if k in names}
        result["device"] = device
    for k, v in result["metrics"].items():
        log(f"{k} {v['value']} {v['unit']}")
    result["compared"] = verdict["compared"]
    return result


def finite_json(obj) -> str:
    """JSON with non-finite numbers written as null."""
    def fix(x):
        if isinstance(x, float) and not math.isfinite(x):
            return None
        if isinstance(x, dict):
            return {k: fix(v) for k, v in x.items()}
        if isinstance(x, (list, tuple)):
            return [fix(v) for v in x]
        return x
    return json.dumps(fix(obj))


def report(result: Dict[str, Any]) -> None:
    for k, v in result["compared"].items():
        print(f"compared {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr, flush=True)
    print(finite_json(result), flush=True)


def sweep(cell: Dict[str, Any], seed: int, seconds: float,
          rates: List[float], root: Path = spec.ROOT) -> None:
    """Knee sweep: one lead-in and window per offered rate, one process,
    one line per rate. A rate is sustained while nearly every request
    that arrived in the window was admitted before its close."""
    import copy
    b = Bench(cell, root)
    params = b.params(seed)
    for rate in rates:
        w2 = copy.deepcopy(b.wl)
        w2["arrivals"]["rate_per_s"] = rate
        reqs = traffic.generate(w2, seed, b.lead + seconds, b.vocab,
                                b.opts["lanes"])
        win = serve_window(b.model, params, b.opts, reqs, seconds, b.lead,
                           b.clock)
        win.pop("params")
        stats = win["window_stats"]
        e2e = end_to_end(win)
        admitted = sum(1 for s in stats if s.lane >= 0 or s.t_admit > 0)
        occ = requests.lane_occupancy_pct(
            requests.block_tokens(win["blocks"]), len(win["blocks"]),
            b.opts.get("block", 1), b.opts["lanes"])
        log(f"sweep rate {rate} req/s: window requests {len(stats)}, "
            f"admitted {admitted} ({admitted / max(len(stats), 1):.2f}), "
            f"outcomes {requests.outcomes(stats)}, output_tok_s "
            f"{e2e['output_tok_s']:.2f}, ttft_p90_s {e2e['ttft_p90_s']:.3f}, "
            f"tpot_p90_ms {e2e['tpot_p90_ms']:.1f}, lane occupancy "
            f"{occ:.1f}%, compiles in window {win['compiles'][0]}")
        del win
        gc.collect()


def prefill_readings(cell: Dict[str, Any], seeds: List[int],
                     root: Path = spec.ROOT) -> List[Dict[str, Any]]:
    """Every part of the lockstep prefill, for the program and the int8
    control, on the longest prompt of each seed's traffic (the prompt a
    run's lockstep prefill takes when it is served), without a window."""
    b = Bench(cell, root, control=True)
    rows = []
    for seed in seeds:
        params = weights.program_params(b.m, seed)
        prompt = max((r["prompt"] for r in b.requests(seed, 30.0)), key=len)
        b.lockstep.prefill(params, prompt)
        del params
        gc.collect()
        row = {"seed": seed, "prompt": len(prompt), **b.lockstep.parts}
        log(finite_json(row))
        rows.append(row)
    return rows


def readings(cell: Dict[str, Any], seeds: List[int], seconds: float,
             root: Path = spec.ROOT) -> List[Dict[str, Any]]:
    """The program's and the control's numbers, each judged against the
    cell's limits exactly as a run judges it, one window per seed in one
    process (bench/control.py). The control is the reference in int8 in
    the program's place: the token it puts first after each prompt, and
    its attention at every layer of the lockstep step."""
    b = Bench(cell, root, control=True)
    lim = limits(b.wl)
    rows = []
    for seed in seeds:
        t0 = time.perf_counter()
        params = b.params(seed)
        reqs = b.requests(seed, seconds)
        prompts = {i: r["prompt"] for i, r in enumerate(reqs)}
        win = serve_window(b.model, params, b.opts, reqs, seconds, b.lead,
                           b.clock)
        del params
        in_vocab = all(0 <= t < b.vocab for s in win["stats"]
                       for t in s.tokens)
        vals = b.check(win, seed, prompts)
        row = {"seed": seed, "seconds": time.perf_counter() - t0}
        for who, v in vals.items():
            row[who] = judge(v, lim, in_vocab)
        log(finite_json(row))
        rows.append(row)
    return rows
