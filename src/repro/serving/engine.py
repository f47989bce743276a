"""Serving engines: LM decode slots and batched ragged geometry inference.

``ServingEngine`` — two LM generation modes sharing one projection/decode
numeric core:

* LOCKSTEP (``generate``): a fixed number of slots decode together with one
  shared cache position.  Prefill is DECODE REPLAY: prompts stream
  token-by-token through ``serve_step``, which is exactly the cache
  semantics the train path matches (unit-tested bit-consistency), so
  generation after a replayed prefill equals teacher forcing.
* CONTINUOUS BATCHING (``paged=True``, ``serve``): slots hold independent
  requests at independent positions over a PAGED KV cache
  (``serving/paged_cache.py`` block tables + ``nsa_causal_decode_paged``).
  Every step advances every occupied slot one token — prefill replay and
  decode interleave freely — finished slots retire on EOS and freed slots
  admit queued requests mid-flight; hash-chained prefix caching reuses
  cached KV blocks across requests sharing prompt prefixes (copy-on-write
  on divergence).  docs/serving.md walks the lifecycle.

Jit boundaries: ONE compiled step per mode (the paged step takes the block
table + per-slot lengths as data, so admissions never recompile).

``GeometryEngine`` — the batched path for variable-size point clouds: each
request cloud is ball-tree ordered on the host, packed with its batch-mates
into one padded (B, L, ·) batch + per-sample mask
(``core.balltree.pack_ragged``), pushed through ONE jitted forward, and
un-packed / inverse-permuted back to per-cloud predictions.  Padded lengths
are quantised to geometric buckets so the number of distinct compiled shapes
stays logarithmic in the size range.
"""

from __future__ import annotations

import contextlib
import math
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.backend import use_backend
from repro.core.balltree import (bucket_length, pack_ragged, pack_varlen,
                                 build_balltree_permutations)
from repro.launch.steps import (make_paged_serve_step, make_paged_serve_window,
                                make_serve_step)
from repro.serving.paged_cache import PagedKVCache


def _backend_scope(name: str | None, mesh_info=None):
    """Fresh context forcing attention backend ``name`` (None = config's).

    Backend resolution is TRACE-time, so wrapping every jitted call is
    enough: the first call bakes the backend into the compiled step and
    later calls replay it.  ``mesh_info`` — a (mesh, axis) pair captured at
    engine construction — re-enters :func:`mesh_context` around the call so
    mesh-requiring backends resolve their mesh even when the engine is used
    outside the user's original ``with mesh_context(...)`` block."""
    stack = contextlib.ExitStack()
    if name:
        stack.enter_context(use_backend(name))
    if mesh_info is not None:
        from repro.distributed.sharded_backend import mesh_context
        stack.enter_context(mesh_context(mesh_info[0], axis=mesh_info[1]))
    return stack


def _require_mesh_if_needed(backend_name: str | None, api, engine: str):
    """(mesh, axis) when the engine's effective backend needs a mesh.

    Fails fast at construction with an actionable error instead of crashing
    inside ``shard_map`` at first trace.  Resolution mirrors the backend
    precedence (config < engine override < env)."""
    import os
    eff = (os.environ.get("REPRO_ATTENTION_BACKEND") or backend_name
           or getattr(api.mcfg.bsa, "backend", None) or "auto")
    from repro.core.backend import get_backend
    try:
        bk = get_backend(eff)
    except KeyError:
        return None          # unknown names error later, in use_backend
    if not getattr(bk, "requires_mesh", False):
        return None
    from repro.distributed.sharded_backend import current_mesh_axis
    ctx = current_mesh_axis()
    if ctx is None:
        raise ValueError(
            f"{engine}(backend={eff!r}) needs an active mesh: construct the "
            "engine inside a mesh context, e.g.\n"
            "    from repro.distributed import mesh_context\n"
            "    from repro.launch.mesh import make_local_mesh\n"
            "    with mesh_context(make_local_mesh()):\n"
            f"        engine = {engine}(...)\n"
            "(the engine captures the mesh, so later calls may happen "
            "outside the with-block)")
    return ctx


class ServingEngine:
    def __init__(self, api, params, *, batch_slots: int, max_len: int,
                 cache_dtype=jnp.float32, temperature: float = 0.0, seed: int = 0,
                 backend: str | None = None, paged: bool = False,
                 page: int | None = None, num_blocks: int | None = None,
                 prefix_cache: bool = True):
        """``paged=True`` enables the continuous-batching mode (``serve``):
        ``page`` tokens per pool block (default: the smallest size aligned
        to both the local window and the compression block), ``num_blocks``
        pool blocks shared by all slots (default: full dedicated capacity,
        ``batch_slots · max_len/page`` — prefix sharing then only ADDS
        headroom), ``prefix_cache`` toggles cross-request prefix block
        reuse (forced off for models with recurrent per-slot state, which a
        cached KV page cannot restore)."""
        self.api = api
        self.params = params
        self.B = batch_slots
        self.max_len = max_len
        self.temperature = temperature
        self.backend = backend          # attention-backend override (by name)
        # fail fast (with a recipe) if a mesh-requiring backend was asked
        # for outside mesh_context(); capture the mesh for later calls
        self._mesh = _require_mesh_if_needed(backend, api, "ServingEngine")
        self.cache_dtype = cache_dtype
        self._rng = jax.random.PRNGKey(seed)
        self.paged = paged
        if paged:
            if not api.has_paged_decoder:
                raise ValueError(f"family {api.mcfg.family!r} has no paged "
                                 "decode path")
            if page is None:
                bsa = api.mcfg.bsa
                page = math.lcm(bsa.effective_local_window, bsa.cmp_block)
            if max_len % page:
                raise ValueError(f"max_len={max_len} not a multiple of "
                                 f"page={page}")
            self.page = page
            self.n_pages = max_len // page
            self.num_blocks = num_blocks or batch_slots * self.n_pages
            if self._mesh is not None:
                # sharded decode row-partitions the flat pools: bump the
                # block count until both pool row counts divide the mesh
                # axis (extra blocks only add headroom)
                p = self._mesh[0].shape[self._mesh[1]]
                cpp = page // api.mcfg.bsa.cmp_block
                while ((self.num_blocks + 1) * page) % p or \
                        ((self.num_blocks + 1) * cpp) % p:
                    self.num_blocks += 1
            self._prefix_enabled = prefix_cache and not api.has_recurrent_state
            self._pstep = jax.jit(make_paged_serve_step(api, page=page))
            self._wstep = jax.jit(make_paged_serve_window(api, page=page))
            self._copy = jax.jit(
                lambda c, s, d: api.cache_copy_block(c, s, d, page))
            self._reset_slot = jax.jit(api.cache_reset_slot)
            self._alloc_state()
        else:
            self.caches = api.cache_init(batch_slots, max_len, cache_dtype)
        self._step = jax.jit(make_serve_step(api))
        self.tokens_generated = 0
        self.decode_time = 0.0
        self.serve_steps = 0

    def _alloc_state(self):
        self.kv = PagedKVCache(n_slots=self.B, num_blocks=self.num_blocks,
                               page=self.page, n_pages=self.n_pages,
                               prefix_cache=self._prefix_enabled)
        self.caches = self.api.paged_cache_init(self.B, self.num_blocks,
                                                self.page, self.cache_dtype)

    def reset(self, cache_dtype=None):
        """Drop all cached state.  ``cache_dtype=None`` keeps the dtype the
        engine was constructed with; passing one switches it from here on."""
        if cache_dtype is not None:
            self.cache_dtype = cache_dtype
        if self.paged:
            self._alloc_state()
        else:
            self.caches = self.api.cache_init(self.B, self.max_len,
                                              self.cache_dtype)

    def prefill(self, prompts: np.ndarray) -> np.ndarray:
        """prompts: (B, P) int32 — replayed through the decode path.
        Returns last logits' argmax (first generated token)."""
        assert prompts.shape[0] == self.B
        nxt = None
        with _backend_scope(self.backend, self._mesh):
            for t in range(prompts.shape[1]):
                tok = jnp.asarray(prompts[:, t], jnp.int32)
                nxt, logits, self.caches = self._step(self.params, self.caches, tok)
        return np.asarray(nxt)

    def _sample(self, logits):
        if self.temperature <= 0.0:
            return jnp.argmax(logits, -1).astype(jnp.int32)
        self._rng, k = jax.random.split(self._rng)
        return jax.random.categorical(k, logits / self.temperature).astype(jnp.int32)

    def generate(self, prompts: np.ndarray, n_tokens: int,
                 eos_id: int | None = None, pad_id: int = 0) -> np.ndarray:
        """Greedy/temperature generation.  Returns (B, n_tokens).

        With ``eos_id`` set, a slot that samples it RETIRES: its remaining
        columns are ``pad_id``, it stops being sampled (and counted), and
        the loop exits early once every slot is done instead of burning
        decode steps on a fully-retired batch."""
        first = np.asarray(self.prefill(prompts))
        done = np.zeros(self.B, bool)
        if eos_id is not None:
            done |= first == eos_id
        emit = np.where(done, pad_id, first).astype(np.int32)
        out = [emit]
        self.tokens_generated += int((~done).sum())
        tok = jnp.asarray(emit)
        t0 = time.time()
        with _backend_scope(self.backend, self._mesh):
            for _ in range(n_tokens - 1):
                if done.all():
                    break
                nxt, logits, self.caches = self._step(self.params, self.caches,
                                                      tok)
                s = np.asarray(self._sample(logits))
                if eos_id is not None:
                    done |= s == eos_id
                emit = np.where(done, pad_id, s).astype(np.int32)
                out.append(emit)
                self.tokens_generated += int((~done).sum())
                tok = jnp.asarray(emit)
        jax.block_until_ready(tok)
        self.decode_time += time.time() - t0
        while len(out) < n_tokens:                   # early-exit padding
            out.append(np.full(self.B, pad_id, np.int32))
        return np.stack(out, axis=1)

    # -- continuous batching over the paged cache ---------------------------

    def serve(self, prompts, max_new_tokens: int,
              eos_id: int | None = None) -> list[np.ndarray]:
        """Continuous-batching generation over an arbitrary request list.

        ``prompts``: sequence of 1-D int token arrays (ANY lengths up to
        ``max_len``).  Returns one generated-token array per prompt (EOS
        excluded, at most ``max_new_tokens``; a slot also stops at cache
        capacity).  Iteration-level scheduling: every engine step advances
        every occupied slot by one token — replaying its prompt (prefill)
        or feeding its last sample (decode) — so short requests drain early
        and their slots admit queued work mid-flight.
        """
        if not self.paged:
            raise RuntimeError("serve() requires ServingEngine(paged=True)")
        prompts = [np.asarray(p, np.int32).reshape(-1) for p in prompts]
        if eos_id is None and self.temperature <= 0.0:
            return self._serve_windowed(prompts, max_new_tokens)
        results: list = [None] * len(prompts)
        queue = deque(range(len(prompts)))
        kv = self.kv
        slot_req = np.full(self.B, -1, np.int64)
        slot_feed = np.zeros(self.B, np.int32)
        slot_decode = np.zeros(self.B, bool)    # feed = last sample, not prompt
        slot_gen: list[list] = [[] for _ in range(self.B)]
        # EOS makes the schedule VALUE-dependent: the sample must come back to
        # the host every step to decide retirement.  Without it the schedule
        # is length-only, so steps pipeline: samples feed back device-side
        # (prev → where(slot_decode)) and the whole token history is pulled
        # ONCE at the end — the same async-dispatch regime lockstep prefill
        # enjoys, now covering decode too.
        sync = eos_id is not None
        hist: list = []                          # (B,) device samples per step
        dev_table, tver = None, -1
        prev = None
        t0 = time.time()
        with _backend_scope(self.backend, self._mesh):
            while queue or (slot_req >= 0).any():
                # 1) admission into free slots (prefix-reuse aware)
                for s in range(self.B):
                    if slot_req[s] < 0 and queue:
                        rid = queue.popleft()
                        reused = kv.admit(s, prompts[rid])
                        if self.api.has_recurrent_state:
                            self.caches = self._reset_slot(self.caches, s)
                        slot_req[s] = rid
                        slot_gen[s] = []
                        slot_feed[s] = prompts[rid][reused]
                        slot_decode[s] = False
                # 2) make every occupied slot's next position writable
                for s in np.nonzero(slot_req >= 0)[0]:
                    for op in kv.prepare_append(int(s)):
                        self.caches = self._copy(self.caches, op.src, op.dst)
                if kv.version != tver:           # table changed since last push
                    dev_table = jnp.asarray(kv.table.copy())
                    tver = kv.version
                # 3) one decode step for the whole batch.  Host arrays are
                # pushed as COPIES: with async dispatch the step may still be
                # in flight when step 4 mutates them, and the CPU backend can
                # alias a pushed numpy buffer instead of copying it.
                tok = jnp.asarray(slot_feed.copy())
                if not sync and prev is not None and slot_decode.any():
                    tok = jnp.where(jnp.asarray(slot_decode.copy()), prev, tok)
                nxt, logits, self.caches = self._pstep(
                    self.params, self.caches, tok, dev_table,
                    jnp.asarray(kv.lengths.copy()))
                prev = nxt if self.temperature <= 0.0 else self._sample(logits)
                if sync:
                    sampled = np.asarray(prev)
                else:
                    hist.append(prev)
                self.serve_steps += 1
                # 4) commit, transition, retire, publish prefix pages
                step_idx = len(hist) - 1
                for s in range(self.B):
                    rid = int(slot_req[s])
                    if rid < 0:
                        continue
                    prompt = prompts[rid]
                    fed_pos = int(kv.lengths[s])
                    kv.committed(s)
                    kv.seal_prompt_page(s, prompt)
                    if fed_pos < len(prompt) - 1:
                        slot_feed[s] = prompt[fed_pos + 1]   # prefill replay
                        slot_decode[s] = False
                        continue
                    done = False                             # decode sample
                    if sync:
                        t_s = int(sampled[s])
                        done = t_s == eos_id
                        if not done:
                            slot_gen[s].append(t_s)
                            slot_feed[s] = t_s
                    else:
                        slot_gen[s].append((step_idx, s))    # resolved at end
                    if not done:
                        self.tokens_generated += 1
                        slot_decode[s] = True
                        done = (len(slot_gen[s]) >= max_new_tokens
                                or int(kv.lengths[s]) >= kv.capacity)
                    if done:
                        results[rid] = slot_gen[s]
                        kv.retire(s)
                        slot_req[s] = -1
                        slot_feed[s] = 0
                        slot_decode[s] = False
        if hist:
            all_samples = np.asarray(jnp.stack(hist))        # the ONE pull
            results = [np.asarray([all_samples[i, s] for i, s in r], np.int32)
                       for r in results]
        else:
            results = [np.asarray(r, np.int32) for r in results]
        if prev is not None:
            jax.block_until_ready(prev)
        self.decode_time += time.time() - t0
        return results

    MAX_WINDOW = 32

    def _serve_windowed(self, prompts, max_new_tokens: int) -> list[np.ndarray]:
        """The greedy/no-EOS fast path of :meth:`serve`: W-step windows.

        Without EOS the whole schedule depends only on LENGTHS, which the
        host knows in advance — so between scheduling events (a slot
        retiring, a request admitted) there is nothing to decide per step.
        The engine picks the window W = steps until the next retirement
        (quantized to powers of two, capped at ``MAX_WINDOW`` so at most
        log₂ variants compile), pre-allocates every page the window
        touches, and runs all W steps in one compiled ``lax.scan`` —
        per-token host overhead is amortized W-fold and samples come back
        in one (W, B) array per window, pulled once at the very end.
        """
        results: list = [None] * len(prompts)
        queue = deque(range(len(prompts)))
        kv = self.kv
        slot_req = np.full(self.B, -1, np.int64)
        slot_gen: list[list] = [[] for _ in range(self.B)]
        hist: list = []                          # (W, B) device samples
        base = 0                                 # global step index of window
        dev_table, tver = None, -1
        prev = jnp.zeros(self.B, jnp.int32)
        t0 = time.time()
        with _backend_scope(self.backend, self._mesh):
            while queue or (slot_req >= 0).any():
                for s in range(self.B):          # admission into free slots
                    if slot_req[s] < 0 and queue:
                        rid = queue.popleft()
                        kv.admit(s, prompts[rid])
                        if self.api.has_recurrent_state:
                            self.caches = self._reset_slot(self.caches, s)
                        slot_req[s] = rid
                        slot_gen[s] = []
                occ = np.nonzero(slot_req >= 0)[0]
                # window = steps until the FIRST slot must retire
                horizon = self.MAX_WINDOW
                for s in occ:
                    pr = prompts[slot_req[s]]
                    stop = min(len(pr) - 1 + max_new_tokens, kv.capacity)
                    horizon = min(horizon, stop - int(kv.lengths[s]))
                W = 1 << (int(horizon).bit_length() - 1)     # quantize down
                feed = np.zeros((W, self.B), np.int32)
                use_prev = np.zeros((W, self.B), bool)
                for s in occ:
                    pr = prompts[slot_req[s]]
                    t = int(kv.lengths[s])
                    for op in kv.prepare_window(int(s), W):
                        self.caches = self._copy(self.caches, op.src, op.dst)
                    n_pref = max(0, min(W, len(pr) - t))     # prompt feeds
                    feed[:n_pref, s] = pr[t:t + n_pref]
                    use_prev[n_pref:, s] = True              # then self-feed
                if kv.version != tver:
                    dev_table = jnp.asarray(kv.table.copy())
                    tver = kv.version
                samples, self.caches = self._wstep(
                    self.params, self.caches, jnp.asarray(feed),
                    jnp.asarray(use_prev), prev, dev_table,
                    jnp.asarray(kv.lengths.copy()),
                    jnp.asarray((slot_req >= 0).astype(np.int32)))
                prev = samples[-1]
                hist.append(samples)
                self.serve_steps += W
                for s in occ:
                    rid = int(slot_req[s])
                    pr = prompts[rid]
                    old = int(kv.lengths[s])
                    kv.committed(int(s), W)
                    kv.seal_prompt_pages(int(s), pr, old)
                    gen0 = min(W, max(0, len(pr) - 1 - old))  # 1st decode step
                    for i in range(gen0, W):
                        slot_gen[s].append((base + i, s))
                    self.tokens_generated += W - gen0
                    if (len(slot_gen[s]) >= max_new_tokens
                            or old + W >= kv.capacity):
                        results[rid] = slot_gen[s]
                        kv.retire(int(s))
                        slot_req[s] = -1
                base += W
        if hist:                                 # the ONE device→host pull
            allv = np.concatenate([np.asarray(h) for h in hist])
            results = [np.asarray([allv[i, s] for i, s in r], np.int32)
                       for r in results]
        self.decode_time += time.time() - t0
        return results

    @property
    def tokens_per_second(self) -> float:
        return self.tokens_generated / max(self.decode_time, 1e-9)


class GeometryEngine:
    """Batched inference over ragged point clouds (the pointcloud family).

    Requests are (points, feats) pairs of ANY sizes; the engine owns the
    whole ragged pipeline: per-cloud ball-tree permutation → pack to a
    bucketed length with per-sample masks → one jitted batched forward →
    unpack + inverse-permute.  Clouds are served in request order, grouped
    into batches of ``batch_slots``.

    Two batch LAYOUTS (docs/varlen.md):

    * ``"packed"`` (default when the model runs BSA) — clouds concatenated
      on ONE packed axis with an ``offsets`` boundary array
      (``core.balltree.pack_varlen``); no dummy batch slots, no
      per-slot padding to the largest cloud, so the forward spends FLOPs
      proportional to Σnᵢ rather than B·max(nᵢ).
    * ``"padded"`` — the classic (B, L, ·) bucket-padded batch with
      per-sample masks; required for non-BSA attention mechanisms, whose
      layers don't take offsets.

    With ``backend="sharded"`` the ``"packed"`` layout's offsets reach the
    varlen ops as TRACED values (they are jitted batch data here), so the
    host-side LPT segment planner cannot run and those ops warn once and
    fall back to the inner backend unsharded — by design; use the
    ``"padded"`` layout (ring-sharded dense ops) when mesh scaling of
    geometry serving matters.  See docs/distributed.md.

    ``pad_to`` freezes the compiled length (use the dataset's
    ``max_padded_len`` when the size range is known): the per-slot padded
    length in ``"padded"`` layout, the TOTAL packed capacity in
    ``"packed"``.  Otherwise each batch pads to a geometric bucket (of the
    largest cloud, resp. of the packed total), giving at most
    O(log size-range) compilations.  A short final batch costs nothing
    extra when packed (offsets simply repeat); padded layout fills it with
    fully-masked dummy slots rather than recompiling at a smaller B.
    """

    def __init__(self, api, params, *, batch_slots: int = 8,
                 pad_to: int | None = None, backend: str | None = None,
                 layout: str | None = None):
        self.api = api
        self.params = params
        self.batch_slots = batch_slots
        self.pad_to = pad_to
        self.backend = backend          # attention-backend override (by name)
        self._mesh = _require_mesh_if_needed(backend, api, "GeometryEngine")
        if layout is None:
            layout = "packed" if api.mcfg.attention == "bsa" else "padded"
        if layout not in ("packed", "padded"):
            raise ValueError(f"layout must be 'packed' or 'padded', got {layout!r}")
        self.layout = layout
        self.ball_size = api.mcfg.bsa.ball_size
        self._fwd = jax.jit(api.forward)
        self._fwd_sel = (jax.jit(api.forward_selection)
                         if api.forward_selection else None)
        self.clouds_served = 0
        self.points_served = 0

    def predict(self, clouds, *, select=None, return_selection: bool = False):
        """clouds: sequence of ``(points (n_i, d), feats (n_i, in_dim))``
        pairs (or dicts with those keys).  Returns one (n_i, out_dim) array
        per cloud, rows in the CALLER's original point order.

        ``return_selection`` (BSA models) returns ``(predictions,
        selections)``: per cloud ``{"indices": (n_layers, G_i, Hkv, k*)}``,
        the selected block ids of its G_i query groups in its own ball
        order (−1: invalid), independent of layout and batch.  ``select``
        takes a list in that form (e.g. another engine's) and REPLAYS it
        (``models.pointcloud.pc_apply``), adding the cloud's batch's
        per-layer ``gap``/``flips``: two layouts or backends then compare
        without a near-tie in top-k breaking differently in each."""
        clouds = [(c["points"], c["feats"]) if isinstance(c, dict) else c
                  for c in clouds]
        want_sel = return_selection or select is not None
        if want_sel and self._fwd_sel is None:
            raise NotImplementedError(
                f"{self.api.mcfg.attention!r} attention selects no blocks")
        results: list[np.ndarray] = []
        selections: list[dict] = []
        n_points = sum(len(p) for p, _ in clouds)
        with TraceAnnotation("repro.engine.predict", clouds=len(clouds),
                             points=n_points):
            for s in range(0, len(clouds), self.batch_slots):
                preds, sels = self._predict_batch(
                    clouds[s:s + self.batch_slots], want_sel,
                    None if select is None else select[s:s + self.batch_slots])
                results.extend(preds)
                selections.extend(sels)
        self.clouds_served += len(clouds)
        self.points_served += n_points
        return (results, selections) if want_sel else results

    def _predict_batch(self, chunk, want_sel=False, select=None):
        """One engine batch, under the host spans ``repro.engine.batch`` >
        ``balltree``, ``pack``, ``forward`` (the jitted call up to its
        return: enqueue, or trace and compile), ``fetch`` (the wait for the
        device and the copy) and ``unpack``; docs/architecture.md, Tracing."""
        with TraceAnnotation("repro.engine.batch", clouds=len(chunk),
                             points=sum(len(p) for p, _ in chunk),
                             layout=self.layout) as span:
            with TraceAnnotation("repro.engine.balltree"):
                pts_list = [np.asarray(p) for p, _ in chunk]
                fts_list = [np.asarray(f, np.float32) for _, f in chunk]
                perms = build_balltree_permutations(pts_list, self.ball_size)
                ordered = [f[perm] for f, perm in zip(fts_list, perms)]
            with TraceAnnotation("repro.engine.pack"):
                batch, places = self._pack(ordered, fts_list)
                if select is not None:
                    batch["select"] = jnp.asarray(self._replay_ids(
                        select, places, *batch["mask"].shape))
            span.set_metadata(length=batch["mask"].shape[1])
            with TraceAnnotation("repro.engine.forward"), \
                    _backend_scope(self.backend, self._mesh):
                if want_sel:
                    pred, sel = self._fwd_sel(self.params, batch)
                else:
                    pred = self._fwd(self.params, batch)
            with TraceAnnotation("repro.engine.fetch"):
                pred = np.asarray(pred)
            with TraceAnnotation("repro.engine.unpack"):
                out = []
                for (row, start, _), f, perm in zip(places, fts_list, perms):
                    unperm = np.empty((len(f),) + pred.shape[2:], pred.dtype)
                    unperm[perm] = pred[row, start:start + len(f)]  # ball → caller order
                    out.append(unperm)
                return out, (self._cloud_ids(sel, places, batch["mask"].shape[1])
                             if want_sel else [])

    def _pack(self, ordered, fts_list):
        """Ball-ordered features → the device batch in this engine's layout,
        and the (batch row, first token, ball-padded length) of every
        cloud."""
        if self.layout == "packed":
            feats, offsets, mask = pack_varlen(
                ordered, self.ball_size, pad_to=self.pad_to,
                max_samples=self.batch_slots)
            batch = {"feats": jnp.asarray(feats)[None],
                     "mask": jnp.asarray(mask)[None],
                     "offsets": jnp.asarray(offsets)}
            n = len(fts_list)
            return batch, [(0, int(a), int(b - a))
                           for a, b in zip(offsets[:n], offsets[1:n + 1])]
        target = self.pad_to or bucket_length(
            max(f.shape[0] for f in ordered), self.ball_size)
        # fully-masked dummy slots keep B static for the final short
        # batch (every branch returns exact zeros for an all-invalid one)
        pad_slots = self.batch_slots - len(ordered)
        if pad_slots > 0:
            ordered = ordered + [np.zeros((1, ordered[0].shape[1]),
                                          np.float32)] * pad_slots
        feats, mask = pack_ragged(ordered, self.ball_size, pad_to=target)
        if pad_slots > 0:
            mask[len(fts_list):] = False
        batch = {"feats": jnp.asarray(feats), "mask": jnp.asarray(mask)}
        return batch, [(i, 0, bucket_length(len(f), self.ball_size,
                                            geometric=False))
                       for i, f in enumerate(fts_list)]

    def _replay_ids(self, select, places, n_rows, n_tokens):
        """Per-cloud ids (n_layers, G_i, Hkv, k*) → the batch's replay array
        (n_layers, rows, G, Hkv, k*): packed-axis block ids, −1 elsewhere."""
        ids = [np.asarray(s["indices"]) for s in select]
        per_group = places[0][2] // ids[0].shape[1]
        n_layers, _, hkv, k_star = ids[0].shape
        ell = self.api.mcfg.bsa.cmp_block
        if k_star != min(self.api.mcfg.bsa.top_k, n_tokens // ell):
            raise ValueError(f"{k_star} ids per group cannot replay where "
                             f"{n_tokens // ell} blocks bound top-k")
        out = np.full((n_layers, n_rows, n_tokens // per_group, hkv, k_star),
                      -1, np.int32)
        for cloud, (row, start, _) in zip(ids, places):
            g0 = start // per_group
            out[:, row, g0:g0 + cloud.shape[1]] = np.where(
                cloud >= 0, cloud + start // ell, -1)
        return out

    def _cloud_ids(self, sel, places, n_tokens):
        """The batch's ids → per cloud, in its own block coordinates."""
        idx = np.asarray(sel["indices"])              # (n_layers, rows, G, ..)
        per_group = n_tokens // idx.shape[2]
        ell = self.api.mcfg.bsa.cmp_block
        stats = {k: np.asarray(sel[k]) for k in ("gap", "flips") if k in sel}
        out = []
        for row, start, length in places:
            ids = idx[:, row, start // per_group:(start + length) // per_group]
            out.append({"indices": np.where(ids >= 0, ids - start // ell, -1),
                        **stats})
        return out
