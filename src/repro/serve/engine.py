"""Continuous-batching engine: the device side of the serving subsystem.

Two KV layouts share one engine surface (submit/step/run):

**paged** (default for attention-only stacks): KV lives in a shared
:class:`repro.serve.cache.PagePool`; a **single compiled program** — the
chunk step from ``train.steps.make_serve_chunk_step`` — advances every
slot each round. It is donated the KV pool and writes the fed tokens
into it in place, so one pool set lives on the device; the engine's
other pool writers (copy-on-write, defrag, the enc-dec cross insert)
rebind ``_cache`` before the next step. Decode rows feed one token;
admitted prompts are fed as fixed-size **chunked-prefill** slices of
the same (B, C) batch, so a prompt of any length maps onto the one
compile shape: there are zero per-prompt-length prefill
specializations (asserted via the jit
cache-miss counter in tests/test_serve.py). Admission is by free-page
budget (``PagedScheduler``); when decode growth exhausts the pool the
engine preempts youngest-first — the victim re-queues at the FIFO front
and is later re-prefilled from prompt + tokens-so-far (recompute-style,
token-identical under greedy). Enc-dec stacks run their fixed-shape
encoder once per admission into a dense per-slot cross slab.

With ``prefix_cache=True`` the paged engine additionally shares KV
**across requests**: every full page a slot writes is registered in a
radix prefix index (``serve.prefix.PrefixIndex``, keyed on token ids at
page granularity; enc-dec streams are namespaced by a digest of their
media), and admission looks the stream up first — cached pages are
mapped straight into the new slot's table (refcounted, see
``cache.PagePool``), the budget is charged only for the *new* pages,
and prefill starts at the first uncached token (fully-cached chunks are
never fed). A stream whose every page is cached copy-on-writes the
final page and re-feeds just its last token to produce logits. Under
pool pressure the engine first evicts LRU unreferenced index entries,
then preempts. Preempted requests resume *through the index*, so a
victim's own surviving pages are rediscovered instead of recomputed.
Cache hits change only host-side page tables, positions and lengths —
never the compiled program — so the one-chunk-program contract holds,
and greedy outputs are token-identical to the cache-off engine
(tests/test_prefix.py).

**slab** (recurrent/hybrid/VLM stacks, or ``kv_layout="slab"``): the
PR 3 dense slot-slab with two compiled programs —

  * ``prefill``: one request's (padded) prompt -> its first-token logits
    + its KV cache, fused with the write of that cache into the slot-slab
    (``serve.cache.write_slot``) and the padding invalidation;
  * ``decode``: one token for *every* slot, with a per-slot position
    vector.

Both layouts run under ``dist.Rules`` (any serve mode incl. tp2d): the
same code lowers on the 1x1 CPU mesh and on pod meshes.

Exactness: with greedy sampling both layouts are token-identical to a
sequential single-request prefill+decode loop and to each other
(tests/test_serve.py). Stacks with recurrent mixers (mamba/rwkv6) carry
prompt state, so they prefill at exact prompt length (one compile per
distinct length) and always use the slab layout. MoE capacity is a known
batching asymmetry: at tight capacity factors routing depends on batch
composition (reduced configs use no-drop capacity).
"""
from __future__ import annotations

import dataclasses
import heapq
import itertools
import time
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.dist import Rules, use_rules
from repro.optim.precision import serving_copy
from repro.serve import cache as slab_ops
from repro.serve import slo
from repro.serve.metrics import ServeReport, StepTrace
from repro.serve.prefix import PrefixIndex
from repro.serve.request import Request
from repro.serve.scheduler import PagedScheduler, Scheduler
from repro.train.steps import (
    ModelAPI,
    make_serve_chunk_step,
    make_serve_decode_step,
    make_serve_prefill_step,
)

KV_LAYOUTS = ("auto", "slab", "paged")


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Engine knobs. ``max_len`` is the per-request token budget (media +
    prompt + generation). ``prefill_len`` is the slab layout's padded
    prompt compile shape; the paged layout ignores it (any prompt length
    streams through ``prefill_chunk``-sized chunks). ``page_size`` /
    ``n_pages`` size the paged pool: ``n_pages`` defaults to capacity
    parity with the slab (``max_batch * ceil(max_len / page_size)``) —
    size it smaller to serve more concurrent requests than dense slots
    could and let admission/preemption manage the overcommit."""

    max_batch: int = 4
    max_len: int = 128
    prefill_len: int = 32
    temperature: float = 0.0
    eos_id: Optional[int] = None
    seed: int = 0
    kv_layout: str = "auto"      # auto | slab | paged
    page_size: int = 16
    prefill_chunk: int = 8
    n_pages: Optional[int] = None
    prefix_cache: bool = False   # cross-request KV sharing (paged only)
    kv_dtype: str = ""           # '' inherit model cfg | bfloat16 | float32
                                 # | int8 | int4 (int4: paged only)
    spec_decode: str = "off"     # off | ngram (paged layout, greedy only)
    draft_len: int = 4           # tokens proposed per row per step

    def __post_init__(self):
        if self.kv_layout != "paged" and self.prefill_len > self.max_len:
            # the paged layout never pads to prefill_len; don't make its
            # users tune a knob the chunk program ignores
            raise ValueError("prefill_len exceeds max_len")
        if self.kv_layout not in KV_LAYOUTS:
            raise ValueError(
                f"kv_layout must be one of {KV_LAYOUTS}, got "
                f"{self.kv_layout!r}")
        if self.page_size < 1 or self.prefill_chunk < 1:
            raise ValueError("page_size and prefill_chunk must be >= 1")
        if self.n_pages is not None and self.n_pages < 1:
            raise ValueError("n_pages must be >= 1")
        if self.kv_dtype not in ("", "bfloat16", "float32", "int8", "int4"):
            raise ValueError(
                f"kv_dtype must be '', 'bfloat16', 'float32', 'int8' or "
                f"'int4', got {self.kv_dtype!r}")
        if self.spec_decode not in ("off", "ngram"):
            raise ValueError(
                f"spec_decode must be 'off' or 'ngram', got "
                f"{self.spec_decode!r} (model-based drafting passes a "
                f"DraftModelDrafter to the Engine)")
        if self.draft_len < 1:
            raise ValueError("draft_len must be >= 1")

    @property
    def max_pages(self) -> int:
        """Page-table width: pages a single request can map."""
        return -(-self.max_len // self.page_size)

    @property
    def pool_pages(self) -> int:
        return self.n_pages or self.max_batch * self.max_pages


class Engine:
    def __init__(self, cfg: ModelConfig, params, rules: Optional[Rules] = None,
                 serve: Optional[ServeConfig] = None, drafter=None):
        self.scfg = serve or ServeConfig()
        if self.scfg.kv_dtype:
            # The KV pool dtype is a serving knob: override the model
            # config's kv_cache_dtype for cache construction + inserts.
            cfg = dataclasses.replace(cfg, kv_cache_dtype=self.scfg.kv_dtype)
        self.cfg = cfg
        self.rules = rules
        self.api = ModelAPI(cfg)
        # Recurrent mixers carry prompt state -> exact-length prefill.
        self._exact = any(s.mixer != "attn" for s in cfg.block_pattern)
        paged_ok = not self._exact and cfg.frontend != "vision_patches"
        layout = self.scfg.kv_layout
        if layout == "auto":
            layout = "paged" if paged_ok else "slab"
        elif layout == "paged" and not paged_ok:
            raise ValueError(
                f"kv_layout='paged' needs an attention-only, token-frontend "
                f"stack; {cfg.name} has "
                f"{'a recurrent mixer' if self._exact else 'a vision frontend'}"
                f" — use kv_layout='slab'")
        if self.scfg.prefix_cache and layout != "paged":
            raise ValueError(
                "prefix_cache shares pages of the paged KV pool; the slab "
                "layout has no pages to share — use kv_layout='paged' "
                "(or drop prefix_cache for this arch)")
        # Unsupported dtype/layout combos fail HERE, at construction —
        # not as a shape error in the middle of a serving step.
        if cfg.kv_cache_dtype == "int4":
            if layout != "paged":
                raise ValueError(
                    "kv_dtype='int4' packs pool pages two-dims-per-byte; "
                    "only the paged layout supports it — use "
                    "kv_layout='paged' or kv_dtype='int8'")
            if cfg.head_dim % 2:
                raise ValueError(
                    f"kv_dtype='int4' needs an even head_dim; {cfg.name} "
                    f"has head_dim={cfg.head_dim}")
        self._drafter = drafter
        if self._drafter is None and self.scfg.spec_decode != "off":
            from repro.serve.speculative import get_drafter
            self._drafter = get_drafter(self.scfg.spec_decode)
        if self._drafter is not None:
            if layout != "paged":
                raise ValueError(
                    "speculative decoding verifies drafts through the "
                    "paged chunk program; use kv_layout='paged'")
            if self.scfg.temperature > 0.0:
                raise ValueError(
                    "speculative decoding is greedy-only (acceptance "
                    "compares against argmax); set temperature=0")
            if self.scfg.draft_len + 1 > self.scfg.prefill_chunk:
                raise ValueError(
                    f"draft_len+1 ({self.scfg.draft_len + 1}) tokens must "
                    f"fit one chunk; raise prefill_chunk "
                    f"({self.scfg.prefill_chunk}) or lower draft_len")
        self.layout = layout
        # one entry per windowed attention layer of the stack
        self._windows = [s.window for s in cfg.block_pattern
                         if s.mixer == "attn" and s.window] * cfg.n_blocks

        # Every program the engine runs, with abstract arguments at its
        # compile shapes: what they do with each weight decides which
        # weights are served from a compute-dtype copy.
        B, sds, i32 = self.scfg.max_batch, jax.ShapeDtypeStruct, jnp.int32
        if layout == "paged":
            # With a drafter the one chunk program returns the head over
            # all C positions (verify needs every draft's logits) — a
            # different jit, but still exactly one compiled program. It
            # is donated the KV pool and updates it in place: every
            # holder of ``self._cache`` rebinds it to the program's
            # output before the next step.
            chunk = make_serve_chunk_step(
                cfg, rules, full_logits=self._drafter is not None)
            self._chunk_jit = jax.jit(chunk, donate_argnums=(2,))
            cache = jax.eval_shape(lambda: self.api.init_paged_cache(
                B, self.scfg.pool_pages, self.scfg.page_size))
            programs = [(chunk, (
                sds((B, self.scfg.prefill_chunk), i32), cache,
                sds((B, self.scfg.max_pages), i32), sds((B,), i32),
                sds((B,), i32)))]
            if cfg.is_encdec:
                api = self.api

                def encode_insert(params, frames, cross, slot):
                    with use_rules(rules):
                        kv = api.encode_cross(params, frames)
                    return slab_ops.write_slot(cross, kv, slot)

                self._encode_jit = jax.jit(encode_insert)
                programs.append((encode_insert, (
                    sds((1, cfg.enc_source_len, cfg.d_model), jnp.float32),
                    cache["cross"], sds((), i32))))
        else:
            prefill_step = make_serve_prefill_step(
                cfg, rules, cache_len=self.scfg.max_len)
            decode_step = make_serve_decode_step(cfg, rules)

            def prefill_insert(params, batch, last_pos, true_len, slab, slot):
                logits, c = prefill_step(params, batch, last_pos)
                c = slab_ops.invalidate_beyond(c, true_len)
                return logits, slab_ops.write_slot(slab, c, slot)

            self._prefill_jit = jax.jit(prefill_insert)
            self._decode_jit = jax.jit(decode_step)
            slab = jax.eval_shape(lambda: slab_ops.init_slab(
                self.api, B, self.scfg.max_len))
            n_media = (cfg.enc_source_len if cfg.is_encdec else
                       cfg.n_media_tokens
                       if cfg.frontend == "vision_patches" else 0)
            batch = {"tokens": sds((1, self.scfg.prefill_len), i32)}
            if n_media:
                batch["media"] = sds((1, n_media, cfg.d_model), jnp.float32)
            programs = [
                (prefill_insert, (batch, sds((1,), i32), sds((1,), i32),
                                  slab, sds((), i32))),
                (decode_step, (sds((B, 1), i32), slab, sds((B,), i32)))]
        # Served from a copy in the compute dtype, made once: the weights
        # never change, so the programs no longer cast them every step.
        self.params = serving_copy(params, programs, cfg.dtype)
        dt = jnp.dtype(cfg.dtype)
        leaves = jax.tree_util.tree_leaves(self.params)
        in_copy = sum(w.size for w in leaves
                      if w.dtype == dt and dt != jnp.float32)
        self._cast_share = 100.0 * in_copy / max(
            1, sum(w.size for w in leaves))
        self._key = jax.random.PRNGKey(self.scfg.seed)
        self.reset()

    def reset(self) -> None:
        """Fresh scheduler/cache/trace state; compiled programs are kept,
        so one engine can serve successive workloads without recompiling
        (e.g. the offline and server scenarios of one benchmark)."""
        B = self.scfg.max_batch
        self._tok = np.zeros((B,), np.int32)
        self._pos = np.zeros((B,), np.int32)
        self._rid = np.zeros((B,), np.uint32)
        self._arrivals: list = []
        self._arrival_seq = itertools.count()
        self._finished: List[Request] = []
        self._trace: List[StepTrace] = []
        self._step_idx = 0
        self._preempted = 0
        # Cross-request prefix-cache state (None/zeros when off or slab).
        self._prefix: Optional[PrefixIndex] = None
        self._ns: dict = {}                       # slot -> trie namespace
        self._start: dict = {}                    # slot -> prefill offset
        self._n_indexed = np.zeros((B,), np.int32)  # full pages registered
        self._prefill_total = 0
        self._prefill_skipped = 0
        self._pages_shared = 0
        self._cow = 0
        self._draft_total = 0     # draft tokens proposed (spec decode)
        self._draft_accepted = 0  # draft tokens accepted by verification
        if self.layout == "paged":
            self._pool = slab_ops.PagePool(
                self.scfg.pool_pages, self.scfg.page_size)
            if self.scfg.prefix_cache:
                self._prefix = PrefixIndex(self._pool, self.scfg.page_size)
                self.sched: Scheduler = PagedScheduler(
                    B, self._pool, acquire=self._acquire_paged,
                    on_shortfall=self._admission_preempt)
            else:
                self.sched = PagedScheduler(
                    B, self._pool, self._admission_pages,
                    on_shortfall=self._admission_preempt)
            # Commit the fresh pools to the replicated sharding the chunk
            # program's outputs carry; otherwise the first call (fresh,
            # uncommitted arrays) and every later call (committed jit
            # outputs) would compile separate specializations of the one
            # program.
            cache = self.api.init_paged_cache(
                B, self.scfg.pool_pages, self.scfg.page_size)
            if self.rules is not None and hasattr(self.rules.mesh, "devices"):
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P

                sh = NamedSharding(self.rules.mesh, P())
                cache = jax.device_put(cache, sh)
            else:
                cache = jax.device_put(cache)
            self._cache = cache
            self._ptab = np.full((B, self.scfg.max_pages), -1, np.int32)
            self._stream = {}
            self._admit_seq = np.zeros((B,), np.int64)
            self._admit_counter = itertools.count(1)
        else:
            self.sched = Scheduler(B)
            self._slab = slab_ops.init_slab(self.api, B, self.scfg.max_len)

    # ------------------------------------------------------------------ #
    def _admission_pages(self, req: Request) -> int:
        """Pages the pending prefill stream needs (prompt + any tokens
        generated before a preemption)."""
        return self._pool.pages_for(len(req.prompt) + len(req.tokens))

    def _media_ns(self, req: Request):
        """Trie namespace: enc-dec KV depends on the encoder input, so
        only requests with bitwise-identical media may share pages."""
        if req.media is None:
            return None
        import hashlib
        return hashlib.sha1(
            np.ascontiguousarray(np.asarray(req.media)).tobytes()).digest()

    def _acquire_paged(self, slot: int, req: Request) -> bool:
        """Prefix-cache admission: map the stream's longest cached
        page-aligned prefix into ``slot`` (refcounted ``pool.share``),
        charge the page budget only for the uncached tail, and stage the
        prefill offset for :meth:`_admit_paged`. A stream whose every
        page is cached copy-on-writes its final page (the slot re-feeds
        just the last token to produce logits). All-or-nothing: on any
        shortfall — even after evicting LRU index entries — every
        mapping is rolled back and admission falls back to the plain
        cache-off allocation, so the cache never admits *less* than the
        cache-off engine would."""
        stream = list(req.prompt) + list(req.tokens)
        S = len(stream)
        ps = self.scfg.page_size
        need_total = self._pool.pages_for(S)
        cached = self._prefix.lookup(stream, self._media_ns(req))
        k = len(cached)
        full_match = k > 0 and k * ps == S
        # Shared pages cost nothing; the tail needs fresh pages (a full
        # match needs exactly one, for the copy-on-write of page k-1).
        need_new = 1 if full_match else need_total - k
        if k:
            self._pool.share(slot, cached)  # pins them against evict
        if self._pool.free_pages < need_new:
            self._prefix.evict(need_new - self._pool.free_pages)
        ok = self._pool.free_pages >= need_new
        if ok and full_match:
            src, dst = self._pool.cow(slot, k - 1)
            self._cache = slab_ops.copy_pages(self._cache, [src], [dst])
            self._cow += 1
        elif ok and need_new:
            self._pool.alloc(slot, need_new)
        if not ok:
            # Roll back the shares; behave exactly like the cache-off
            # admission (which may itself fail -> blocked queue head).
            self._pool.free_slot(slot)
            if not self._pool.alloc(slot, need_total):
                return False
            k = full_match = 0
        start = S - 1 if full_match else k * ps
        self._start[slot] = start
        self._prefill_total += S
        self._prefill_skipped += start
        self._pages_shared += k
        return True

    def _register(self, slot: int, req: Request) -> None:
        """Index every complete page ``slot`` has written (fed tokens are
        always ``(prompt + tokens)[:pos]``). First-writer-wins in the
        trie, so re-registering shared pages is a no-op touch."""
        ps = self.scfg.page_size
        full = int(self._pos[slot]) // ps
        if full <= int(self._n_indexed[slot]):
            return
        seq = (list(req.prompt) + list(req.tokens))[:full * ps]
        self._prefix.insert(seq, self._pool.slot_pages(slot)[:full],
                            self._ns.get(slot))
        self._n_indexed[slot] = full

    def submit(self, req: Request) -> None:
        """Register a request; it enters the queue at ``req.arrival_step``."""
        if self.cfg.is_encdec and req.media is None:
            raise ValueError(
                f"request {req.id}: enc-dec arch {self.cfg.name} requires "
                f"media (encoder frames of shape (enc_source_len, d_model))")
        n_media = self._n_media(req)
        if n_media + req.prompt_len + req.max_new_tokens > self.scfg.max_len:
            raise ValueError(
                f"request {req.id}: media+prompt+generation "
                f"({n_media}+{req.prompt_len}+{req.max_new_tokens}) "
                f"exceeds max_len={self.scfg.max_len}")
        if self.layout == "paged":
            if req.media is not None and not self.cfg.is_encdec:
                raise ValueError(
                    f"request {req.id}: the paged layout feeds token ids "
                    f"only — decoder-side media needs kv_layout='slab'")
            need = self._pool.pages_for(req.prompt_len + req.max_new_tokens)
            if need > self.scfg.pool_pages:
                raise ValueError(
                    f"request {req.id}: needs {need} pages but the pool "
                    f"has {self.scfg.pool_pages}; raise n_pages or shrink "
                    f"the request")
        else:
            if not self._exact and req.prompt_len > self.scfg.prefill_len:
                raise ValueError(
                    f"request {req.id}: prompt_len {req.prompt_len} exceeds "
                    f"prefill_len={self.scfg.prefill_len}")
            # The padded prefill sequence must fit the cache whole —
            # otherwise lm.prefill truncates to the trailing cache_len
            # positions and the slot_pos labels would no longer match.
            pad_to = req.prompt_len if self._exact else self.scfg.prefill_len
            if n_media + pad_to > self.scfg.max_len:
                raise ValueError(
                    f"request {req.id}: media+padded prompt "
                    f"({n_media}+{pad_to}) exceeds "
                    f"max_len={self.scfg.max_len}")
        heapq.heappush(
            self._arrivals, (req.arrival_step, next(self._arrival_seq), req))

    def run(self) -> ServeReport:
        """Drive steps until every submitted request has finished.

        The engine is reset on return (compiled programs kept), so a
        reused engine reports each workload separately — metrics never
        accumulate across runs."""
        t0 = time.perf_counter()
        self.drain()
        return self.finalize(t0)

    @property
    def current_step(self) -> int:
        """The step index the next :meth:`step` call will run as — the
        issue-time stamp for issue-on-completion drivers."""
        return self._step_idx

    @property
    def finished(self) -> List[Request]:
        """Requests retired so far in the current run (grows as steps
        drain; cleared by :meth:`finalize`/:meth:`reset`). Incremental
        drivers — the fleet replica harvest loop — read it between
        steps instead of waiting for the report."""
        return self._finished

    def drain(self) -> None:
        """Step until no submitted request remains unfinished, without
        building a report — drivers that interleave submission with
        progress (SingleStream issue-on-completion) drain per request
        and call :meth:`finalize` once at the end."""
        while self._arrivals or self.sched.has_work:
            self.step()

    def finalize(self, t0: float) -> ServeReport:
        """Build the run's report (elapsed since ``t0``) and reset."""
        report = ServeReport(
            requests=list(self._finished),
            steps=list(self._trace),
            elapsed_s=time.perf_counter() - t0,
            preemptions=self._preempted,
            prefix_hit_rate=(
                self._prefill_skipped / max(self._prefill_total, 1)
                if self._prefix is not None else None),
            pages_shared=self._pages_shared,
            prefill_tokens_skipped=self._prefill_skipped,
            cow_copies=self._cow,
            spec_accept_rate=(
                self._draft_accepted / max(self._draft_total, 1)
                if self._drafter is not None else None),
            draft_tokens=self._draft_total,
            weights_cast_share=self._cast_share,
        )
        self.reset()
        return report

    # ------------------------------------------------------------------ #
    def step(self) -> None:
        """One scheduling round: arrivals -> admissions -> batched step.

        On the paged layout the round's phases run inside profiler spans
        (``jax.profiler.TraceAnnotation``, which records only while a
        profiler runs): ``repro.serve.schedule`` (arrivals, admission,
        page growth and preemption), ``repro.serve.prepare`` (the
        program's host inputs), ``repro.serve.device`` (dispatch until
        the sampled tokens are on the host) and ``repro.serve.emit``
        (tokens appended, rows retired)."""
        if self.layout == "paged":
            with jax.profiler.TraceAnnotation("repro.serve.schedule"):
                self._arrive_and_admit()
                plan = self._chunk_plan() if self.sched.n_active else ()
            if plan:
                self._chunk_once(*plan)
        else:
            self._arrive_and_admit()
            if self.sched.n_active:
                self._decode_once()
        self._step_idx += 1

    def _arrive_and_admit(self) -> None:
        while self._arrivals and self._arrivals[0][0] <= self._step_idx:
            _, _, req = heapq.heappop(self._arrivals)
            if req.t_arrival is None:
                req.t_arrival = time.perf_counter()
                req.s_arrival = self._step_idx
            self.sched.submit(req)
        admit = (self._admit_paged if self.layout == "paged"
                 else self._admit_slab)
        for slot, req in self.sched.admit():
            admit(slot, req)

    def compiled_programs(self) -> dict:
        """Program name -> jit cache size (compiled specializations).

        The paged engine's contract is chunk == 1 regardless of the mix
        of prompt lengths served: every prompt streams through the one
        (B, C) compile shape."""
        def sz(f):
            return getattr(f, "_cache_size", lambda: -1)()

        if self.layout == "paged":
            out = {"chunk": sz(self._chunk_jit)}
            if self.cfg.is_encdec:
                out["encode"] = sz(self._encode_jit)
            return out
        return {"prefill": sz(self._prefill_jit),
                "decode": sz(self._decode_jit)}

    def defrag(self) -> None:
        """Compact the page pool (paged layout): occupied pages move to
        the lowest physical indices, page tables are rewritten, decode
        output is unchanged (tested)."""
        if self.layout != "paged":
            raise ValueError("defrag is a paged-layout operation")
        perm = self._pool.defrag()
        self._cache = slab_ops.apply_defrag(self._cache, perm)
        if self._prefix is not None:
            self._prefix.remap(slab_ops.PagePool.remap_from_perm(perm))
        for slot in range(self.scfg.max_batch):
            self._ptab[slot] = self._pool.table_row(
                slot, self.scfg.max_pages)

    # ------------------------------------------------------------------ #
    def _n_media(self, req: Request) -> int:
        """Positions the media prefix occupies in the decoder stream."""
        if req.media is None or self.cfg.is_encdec:
            return 0  # enc-dec media feeds the encoder, not the decoder
        return int(np.asarray(req.media).shape[0])

    # ---- paged layout ------------------------------------------------- #
    def _preempt_slot(self, victim: int) -> None:
        """Evict the request in ``victim`` back to its priority band's
        queue front (it keeps its scheduler ticket) and drop the
        engine-side staging; pages are freed by the scheduler. The
        victim later re-prefills from prompt + tokens-so-far — through
        the prefix index when the cache is on, so its own surviving
        pages are rediscovered instead of recomputed."""
        self.sched.preempt(victim)
        self._ptab[victim] = -1
        self._stream.pop(victim, None)
        self._ns.pop(victim, None)
        self._n_indexed[victim] = 0
        self._preempted += 1

    def _admission_preempt(self, req: Request) -> bool:
        """SLO-aware admission (``PagedScheduler`` ``on_shortfall``):
        free pages for a latency-critical candidate by evicting one
        running request of a strictly lower class with more slack.
        Never fires for a candidate whose budget is already blown —
        evicting live work cannot un-miss its SLO (the admission oracle
        in tests/test_scenarios.py). Only engine-staged slots are
        eligible: a slot admitted earlier in this same scheduling round
        has no staging yet (``_ptab`` row still -1) and must not be
        kicked before its prefill is even staged."""
        staged = [(s, r) for s, r in self.sched.running()
                  if self._ptab[s, 0] >= 0]
        victim = slo.admission_victim(
            req, staged, self._step_idx,
            {s: int(self._admit_seq[s]) for s, _ in staged})
        if victim is None:
            return False
        self._preempt_slot(victim)
        return True

    def _admit_paged(self, slot: int, req: Request) -> None:
        """Stage the prefill stream; pages were reserved by the
        scheduler's budget check. Enc-dec: run the fixed-shape encoder
        into the slot's cross slab (one compile, any prompt length)."""
        stream = list(req.prompt) + list(req.tokens)
        start = self._start.pop(slot, 0)  # first uncached token (prefix)
        self._stream[slot] = stream[start:]
        self._pos[slot] = start
        self._rid[slot] = req.id
        self._admit_seq[slot] = next(self._admit_counter)
        self._ptab[slot] = self._pool.table_row(slot, self.scfg.max_pages)
        if self._prefix is not None:
            self._ns[slot] = self._media_ns(req)
            self._n_indexed[slot] = start // self.scfg.page_size
        if self.cfg.is_encdec:
            t0 = time.perf_counter()
            cross = self._encode_jit(
                self.params, jnp.asarray(req.media)[None],
                self._cache["cross"], jnp.int32(slot))
            self._cache = {**self._cache,
                           "cross": jax.block_until_ready(cross)}
            self._trace.append(StepTrace(
                "encode", time.perf_counter() - t0, 0,
                pool_util=self._pool.utilization()))

    def _draft(self, active) -> dict:
        """Propose up to ``draft_len`` tokens for each decode row.

        A draft is capped so (1) the fed group [last_tok, d_1..d_k] fits
        the chunk (k <= C-1) and (2) even full acceptance plus the bonus
        token never exceeds the request's generation budget (k <=
        remaining-1), so verified positions never outgrow the pages the
        request was admitted for. Rows still prefilling, and rows whose
        drafter returns nothing, decode plainly and contribute no
        accounting."""
        drafts = {}
        k_max = min(self.scfg.draft_len, self.scfg.prefill_chunk - 1)
        for slot in sorted(active):
            if self._stream.get(slot):
                continue
            req = active[slot]
            remaining = req.max_new_tokens - len(req.tokens)
            k = min(k_max, remaining - 1)
            if k <= 0:
                continue
            ctx = list(req.prompt) + list(req.tokens)
            d = list(self._drafter.propose(ctx, k))[:k]
            if d:
                drafts[slot] = [int(t) for t in d]
        return drafts

    def _chunk_plan(self):
        """The rows the next chunk dispatch feeds, and their drafts:
        ``(active, drafts)``, after growing decode rows' pages (and
        shedding drafts, evicting cached prefixes or preempting when the
        pool runs dry); ``()`` when no row is left."""
        active = dict(self.sched.running())
        drafts = self._draft(active) if self._drafter is not None else {}

        # Lazy decode growth; when the pool runs dry, first shed drafts
        # (verifying fewer tokens is strictly cheaper than evicting KV),
        # then drop cold prefix-cache entries, then preempt the slot
        # with the most SLO slack (ties: youngest-first, which is the
        # whole policy when no request carries a class — see serve.slo).
        while active:
            growth = {}
            for slot in active:
                if self._stream.get(slot):
                    continue  # prefill pages were reserved at admission
                want = int(self._pos[slot]) + 1 + len(drafts.get(slot, ()))
                need = (self._pool.pages_for(want)
                        - len(self._pool.slot_pages(slot)))
                if need > 0:
                    growth[slot] = need
            shortfall = sum(growth.values()) - self._pool.free_pages
            if shortfall <= 0:
                for slot in growth:
                    self._pool.ensure(
                        slot,
                        int(self._pos[slot]) + 1
                        + len(drafts.get(slot, ())))
                break
            if drafts:
                drafts.pop(sorted(drafts)[0])  # degrade, deterministically
                continue
            if self._prefix is not None and self._prefix.evict(shortfall):
                continue
            victim = slo.choose_victim(
                active, self._step_idx,
                {s: int(self._admit_seq[s]) for s in active})
            self._preempt_slot(victim)
            active.pop(victim)
            drafts.pop(victim, None)
        return (active, drafts) if active else ()

    def _chunk_once(self, active: dict, drafts: dict) -> None:
        """One mixed dispatch: every row of ``active`` advances — decode
        rows by one token (plus any speculative draft), prefilling rows
        by up to ``prefill_chunk`` prompt tokens — through the single
        compiled chunk program.

        Speculative decode rides the same dispatch: a decode row feeds
        [last_tok, d_1..d_k] with n_valid = 1+k; the full-logits head
        gives argmax targets at every fed position, the accepted prefix
        is the run of drafts matching those targets, and the row emits
        accept+1 tokens (the +1 is the model's own next token — free,
        and exactly what non-speculative greedy would produce next)."""
        with jax.profiler.TraceAnnotation("repro.serve.prepare"):
            toks, nv, posb, prefilling = self._chunk_inputs(active, drafts)
        with jax.profiler.TraceAnnotation("repro.serve.device"):
            t0 = time.perf_counter()
            nxt, routed = self._chunk_device(toks, nv, posb)
            dt = time.perf_counter() - t0
        with jax.profiler.TraceAnnotation("repro.serve.emit"):
            produced = self._chunk_emit(active, drafts, nv, nxt)
            trace = StepTrace(
                "mixed" if prefilling else "decode", dt, produced,
                pool_util=self._pool.utilization(),
                n_fed=sum(int(nv[slot]) for slot in active),
                n_positions=toks.size,
                n_kv_pages=sum(self._pool.pages_for(int(posb[s] + nv[s]))
                               for s in active))
            if self._windows:
                trace.n_kv_pages_window = sum(
                    self._window_pages(int(posb[s]), int(nv[s]), w)
                    for s in active for w in self._windows)
            if routed is not None:
                from repro.kernels.moe_gmm import rows_visited

                trace.n_routed_here = int(routed.sum())
                trace.n_gmm_groups = int((routed > 0).sum())
                trace.n_gmm_rows = sum(rows_visited(r) for r in
                                       routed.reshape(-1, routed.shape[-1]))
            self._trace.append(trace)

    def _window_pages(self, pos: int, nv: int, window: int) -> int:
        """Page slots a layer with this window visits for a row feeding
        ``nv`` tokens from ``pos``: from the page of the first key the
        window reaches to the row's last page."""
        first = max(0, pos - window + 1) // self.scfg.page_size
        return self._pool.pages_for(pos + nv) - first

    def _chunk_inputs(self, active: dict, drafts: dict):
        """The chunk program's host inputs: tokens (B, C), fed counts,
        start positions, and whether any row is still prefilling; the
        rows' page tables are refreshed in ``_ptab``."""
        C = self.scfg.prefill_chunk
        B = self.scfg.max_batch
        toks = np.zeros((B, C), np.int32)
        nv = np.zeros((B,), np.int32)  # idle rows feed nothing
        posb = np.zeros((B,), np.int32)
        prefilling = False
        for slot in active:
            posb[slot] = self._pos[slot]
            stream = self._stream.get(slot)
            if stream:
                n = min(C, len(stream))
                toks[slot, :n] = stream[:n]
                nv[slot] = n
                prefilling = True
            else:
                toks[slot, 0] = self._tok[slot]
                nv[slot] = 1
                d = drafts.get(slot)
                if d:
                    toks[slot, 1:1 + len(d)] = d
                    nv[slot] = 1 + len(d)
            self._ptab[slot] = self._pool.table_row(
                slot, self.scfg.max_pages)
        return toks, nv, posb, prefilling

    def _chunk_device(self, toks, nv, posb):
        """Run the chunk program and sample: the next tokens on the host,
        and with them, for a stack with MoE layers, the held experts'
        assignment counts (``lm.decode_chunk``'s ``routed``), else None."""
        logits, self._cache, *routed = self._chunk_jit(
            self.params, jnp.asarray(toks), self._cache,
            jnp.asarray(self._ptab), jnp.asarray(posb), jnp.asarray(nv))
        if self._drafter is not None:
            # full-logits head: targets[b, i] is the model's next token
            # after fed position i (greedy — spec mode is argmax-only).
            nxt = jnp.argmax(logits, -1)
        else:
            # each row's sampled token sits right after its last fed token
            nxt = self._sample(logits, self._rid, posb + nv)
        nxt, routed = jax.device_get((nxt, routed[0] if routed else None))
        return np.asarray(nxt), routed

    def _chunk_emit(self, active: dict, drafts: dict, nv, nxt) -> int:
        """Advance each fed row, append its emitted tokens and retire
        finished requests; returns the tokens emitted."""
        spec = self._drafter is not None
        produced = 0
        for slot, req in active.items():
            n = int(nv[slot])
            stream = self._stream.get(slot)
            d = drafts.get(slot)
            if stream or not d:
                # plain path: advance by the fed count, then maybe emit
                # one token — byte-identical to the pre-speculative loop.
                self._pos[slot] += n
                if self._prefix is not None:
                    self._register(slot, req)
                if stream:
                    self._stream[slot] = stream[n:]
                    if self._stream[slot]:
                        continue  # mid-prompt: logits not sampled yet
                emit = [int(nxt[slot, n - 1] if spec else nxt[slot])]
            else:
                k = len(d)
                a = 0
                while a < k and d[a] == int(nxt[slot, a]):
                    a += 1
                emit = [int(nxt[slot, i]) for i in range(a + 1)]
                self._draft_total += k
                self._draft_accepted += a
                # Rejected positions (pos+a+1 ..) hold stale draft KV;
                # they sit past the new n_valid limit so attention never
                # reads them, and the real tokens overwrite them when
                # those positions are eventually fed.
                self._pos[slot] += a + 1
            alive = True
            for tok in emit:
                req.tokens.append(tok)
                produced += 1
                if req.t_first_token is None:
                    req.t_first_token = time.perf_counter()
                    req.s_first_token = self._step_idx
                self._tok[slot] = tok
                if req.done or tok == self.scfg.eos_id:
                    self._retire_paged(slot, req)
                    alive = False
                    break
            if d and not stream and alive and self._prefix is not None:
                # register AFTER the accepted tokens joined req.tokens —
                # the index slices (prompt + tokens)[:pos] and every
                # position below _pos is now a verified token.
                self._register(slot, req)
        return produced

    def _retire_paged(self, slot: int, req: Request) -> None:
        self.sched.retire(slot)  # frees the slot's pages too
        self._ptab[slot] = -1
        self._stream.pop(slot, None)
        self._ns.pop(slot, None)
        self._n_indexed[slot] = 0
        req.t_done = time.perf_counter()
        req.s_done = self._step_idx
        self._finished.append(req)

    # ---- slab layout --------------------------------------------------- #
    def _admit_slab(self, slot: int, req: Request) -> None:
        """Prefill ``req`` into ``slot``; samples its first token."""
        P = req.prompt_len
        n_media = self._n_media(req)
        pad_to = P if self._exact else self.scfg.prefill_len
        toks = np.zeros((1, pad_to), np.int32)
        toks[0, :P] = req.prompt
        batch = {"tokens": jnp.asarray(toks)}
        if req.media is not None:
            batch["media"] = jnp.asarray(req.media)[None]
        last = jnp.full((1,), n_media + P - 1, jnp.int32)
        true_len = jnp.full((1,), n_media + P, jnp.int32)

        t0 = time.perf_counter()
        logits, self._slab = self._prefill_jit(
            self.params, batch, last, true_len, self._slab,
            jnp.int32(slot))
        tok = int(np.asarray(jax.block_until_ready(
            self._sample(logits, req.id, n_media + P)))[0])
        dt = time.perf_counter() - t0

        req.tokens.append(tok)
        req.t_first_token = time.perf_counter()
        req.s_first_token = self._step_idx
        self._trace.append(StepTrace("prefill", dt, 1, n_fed=P,
                                     n_positions=pad_to))
        if req.done or tok == self.scfg.eos_id:
            self._retire_slab(slot, req)
        else:
            self._tok[slot] = tok
            self._pos[slot] = n_media + P
            self._rid[slot] = req.id

    def _decode_once(self) -> None:
        """Advance every occupied slot by one token (single dispatch)."""
        t0 = time.perf_counter()
        logits, self._slab = self._decode_jit(
            self.params, jnp.asarray(self._tok[:, None]), self._slab,
            jnp.asarray(self._pos))
        # the fed token sits at _pos; the drawn token's position is +1
        next_tok = np.asarray(jax.block_until_ready(
            self._sample(logits, self._rid, self._pos + 1)))
        dt = time.perf_counter() - t0

        running = self.sched.running()
        for slot, req in running:
            tok = int(next_tok[slot])
            req.tokens.append(tok)
            self._tok[slot] = tok
            self._pos[slot] += 1
            if req.done or tok == self.scfg.eos_id:
                self._retire_slab(slot, req)
        self._trace.append(StepTrace(
            "decode", dt, len(running), n_fed=len(running),
            n_positions=self.scfg.max_batch))

    def _retire_slab(self, slot: int, req: Request) -> None:
        self.sched.retire(slot)
        req.t_done = time.perf_counter()
        req.s_done = self._step_idx
        self._finished.append(req)

    # ------------------------------------------------------------------ #
    def _sample(self, logits, rid, pos):
        """Greedy, or temperature sampling keyed by (seed, request id,
        position).

        Every token of a generation draws from its own key (prefill's
        first token and the same round's decode draw can never share
        one), and the key depends only on the request — not on which
        slot the scheduler assigned or which other requests are in
        flight, so sampled generations are as schedule-independent as
        greedy ones. rid/pos broadcast from scalars (prefill, B=1) or
        arrive as (B,) vectors (batched decode)."""
        if self.scfg.temperature <= 0.0:
            return jnp.argmax(logits, -1)
        t = self.scfg.temperature
        B = logits.shape[0]
        rids = jnp.broadcast_to(
            jnp.asarray(rid, jnp.uint32).reshape(-1), (B,))
        posv = jnp.broadcast_to(
            jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
        keys = jax.vmap(
            lambda r, p: jax.random.fold_in(
                jax.random.fold_in(self._key, r), p)
        )(rids, posv)
        return jax.vmap(
            lambda k, l: jax.random.categorical(k, l / t))(keys, logits)


# --------------------------------------------------------------------------- #
# Synthetic workload construction. The MLPerf-Inference scenario drivers
# and trace generators live in ``serve.scenarios`` (re-exported below
# for backwards compatibility); ``run.dispatch`` and the launcher shim
# address scenarios by name and build workloads from RunSpec fields.
# --------------------------------------------------------------------------- #
def synthetic_requests(cfg, *, n: int, tokens: int, prompt_len: int,
                       scenario: str = "offline", seed: int = 0,
                       arrival_rate: float = 0.5,
                       prompt_lens: Optional[Sequence[int]] = None,
                       shared_prefix_len: int = 0, n_templates: int = 1,
                       suffix_spread: Optional[Sequence[int]] = None,
                       ) -> List[Request]:
    """Synthetic workload with mixed prompt lengths; the server scenario
    staggers arrivals (a Poisson process at ``arrival_rate``
    requests/step, drawn from the workload rng) so admissions interleave
    with in-flight decodes.

    ``prompt_lens`` pins the per-request lengths explicitly (cycled over
    the ``n`` requests) — serve benchmarks and tests pass a wide spread
    so ragged batches are the default exercise; ``None`` keeps the
    seeded random spread in ``[prompt_len // 2, prompt_len]``. Enc-dec
    archs get encoder frames, VLM archs get vision patches.

    ``shared_prefix_len > 0`` switches to the **shared-prefix** shape
    real traffic has (system prompts / few-shot templates): request
    ``i`` opens with template ``i % n_templates`` (each template is a
    fixed ``shared_prefix_len``-token prefix) followed by a private
    suffix — ``suffix_spread`` cycles explicit suffix lengths, else
    every suffix is ``max(1, prompt_len - shared_prefix_len)`` tokens.
    Same-template enc-dec requests also share their encoder media, so
    the prefix cache's media-namespaced trie can match them."""
    if shared_prefix_len < 0 or n_templates < 1:
        raise ValueError("shared_prefix_len >= 0 and n_templates >= 1")
    rng = np.random.RandomState(seed)
    templates = [rng.randint(0, cfg.vocab, size=shared_prefix_len).tolist()
                 for _ in range(n_templates)] if shared_prefix_len else []
    reqs = []
    for i in range(n):
        if shared_prefix_len:
            if suffix_spread:
                s_len = max(1, int(suffix_spread[i % len(suffix_spread)]))
            else:
                s_len = max(1, prompt_len - shared_prefix_len)
            prompt = (templates[i % n_templates]
                      + rng.randint(0, cfg.vocab, size=s_len).tolist())
            # The template tokens themselves are the routing key: the
            # fleet router hashes it so same-template requests land on
            # the replica whose prefix cache already holds these pages.
            template = tuple(templates[i % n_templates])
        else:
            template = None
            if prompt_lens:
                p_len = max(1, int(prompt_lens[i % len(prompt_lens)]))
            else:
                lo = max(1, min(prompt_len // 2, prompt_len))
                p_len = int(rng.randint(lo, max(lo + 1, prompt_len + 1)))
            prompt = rng.randint(0, cfg.vocab, size=p_len).tolist()
        req = Request(prompt=prompt, max_new_tokens=tokens,
                      template=template)
        media_key = i % n_templates if shared_prefix_len else i
        if cfg.is_encdec:
            req.media = np.asarray(jax.random.normal(
                jax.random.PRNGKey(seed + media_key),
                (cfg.enc_source_len, cfg.d_model)))
        elif cfg.frontend == "vision_patches":
            req.media = np.asarray(jax.random.normal(
                jax.random.PRNGKey(seed + media_key),
                (cfg.n_media_tokens, cfg.d_model)))
        reqs.append(req)
    if scenario != "offline":
        # Poisson arrivals from the *workload* rng — drawn after every
        # prompt so the prompt streams stay byte-identical across
        # scenarios with the same seed (a trace is scenario-invariant up
        # to arrival stamps; tests/test_scenarios.py pins this).
        from repro.serve.scenarios import poisson_arrivals
        for r, a in zip(reqs, poisson_arrivals(rng, n, arrival_rate)):
            r.arrival_step = int(a)
    return reqs


from repro.serve.scenarios import (  # noqa: E402  (import cycle: scenarios
    SCENARIO_DRIVERS,                 # lazily imports synthetic_requests)
    run_multi_stream,
    run_offline,
    run_server,
    run_single_stream,
    scenario_driver,
)

__all__ = [
    "Engine",
    "KV_LAYOUTS",
    "SCENARIO_DRIVERS",
    "ServeConfig",
    "run_multi_stream",
    "run_offline",
    "run_server",
    "run_single_stream",
    "scenario_driver",
    "synthetic_requests",
]
