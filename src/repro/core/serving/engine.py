"""Serving engine over the paged SVA layer.

Two step loops, picked from what the engine can observe:

  continuous  ``offload_mode="zero_copy"`` on a model whose every KV layer
              lives in the shared global pool
              (``models.blocks.kv_in_global_pool``). A token-budget
              :class:`~repro.core.serving.scheduler.Scheduler` composes
              each step from chunked prefills and a masked decode over the
              full slot width, admits lazily and preempts under pool
              pressure. This is the served path.

  fixed-wave  Everything else: the staging-copy baseline
              (``offload_mode="copy"``, the paper's Fig. 2 memcpy mode:
              each admission prefills a fresh single-sequence cache,
              duplicates it and copies it leaf by leaf into the batch
              cache), and zero-copy models with per-slot state
              (sliding-window rings, recurrent states, cross-KV, image
              tokens). Each step admits what fits, prefills it in one
              padded call and decodes every active slot.

Zero-copy admission writes block-table rows (ints) and the prefill
scatters KV directly into the shared pool through those tables: no
per-request cache, no staging copy. Decode consumes delta table uploads:
only rows whose tables changed since the last step are re-sent
(``PagedKVManager.delta_rows()``), with a full upload only on epoch
invalidation, the serving-level analogue of a warm IOTLB.

Copy-on-write prefix sharing (continuous loop): admission consults the
manager's :class:`~repro.core.sva.kv_manager.PrefixIndex` and maps a
prompt's already-resident prefix pages via refcount++; chunked prefill
then feeds only the non-shared suffix, reading the prefix's KV back out
of the pool and scattering through ``write_tables`` whose shared entries
are NULLed. A decode append into a page another sequence still maps
queues a CoW duplication, which ``_apply_cow`` executes device-side (one
batched pool-to-pool page copy) before the next device op. Completed
requests leave their prompt pages behind as a warm prefix cache.

On a TPU the decode hot path runs through the ragged Pallas kernel
(kernels/paged_attention, chosen by ``attention.ragged_decode_serves``):
the per-slot block tables live in SMEM and drive the DMAs of each row's
live KV pages, so reading through *shared* block tables costs the same as
private ones.

Adaptive translation front-end (``ModelConfig.serve_tlb_prefetch_*`` /
``serve_tlb_autotune*``; both default-off): the engine arms the manager's
IOMMU with an IOTLB prefetcher and/or attaches the online geometry
auto-tuner. Auto-tuning implies running ``translate_step`` every decode
step (the tuner's only signal is live traffic); each geometry switch is a
flush + epoch bump, which this engine absorbs as one full table upload —
decode outputs are unaffected (placement-invariance, pinned by
``tests/test_adaptive_tlb.py``).

CPU-testable with reduced configs; runs on one device.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.core.serving.scheduler import Scheduler, SchedulerOutput
from repro.core.serving.sequence_buffer import SequenceBuffer
from repro.core.sva.iommu import (AutoTuneConfig, PrefetchConfig, TLBConfig,
                                  default_autotune_candidates)
from repro.core.sva.kv_manager import PagedKVManager
from repro.models import (MeshInfo, NO_MESH, forward_decode, forward_prefill,
                          init_cache)
from repro.models import attention as attn
from repro.models.blocks import MAMBA_KINDS, _sp_mode, kv_in_global_pool

# Host spans of one continuous step, written into a running jax.profiler
# trace on the clock of its device events; with no trace running a span
# costs about a microsecond. Every span the step opens is named here, in
# nesting order (engine.step holds the rest).
SPANS = ("engine.step", "engine.cow", "engine.schedule",
         "engine.prefill.pack", "engine.prefill.launch",
         "engine.prefill.sync", "engine.prefill.commit", "engine.tables",
         "engine.decode.pack", "engine.decode.launch", "engine.decode.sync",
         "engine.decode.commit", "engine.release")


@dataclass
class Request:
    req_id: int
    prompt: List[int]
    max_tokens: int
    tenant: Optional[str] = None   # owning TenantDomain (None = untenanted)
    out_tokens: List[int] = field(default_factory=list)
    submitted_at: float = 0.0
    first_token_at: Optional[float] = None
    done_at: Optional[float] = None
    # step-counter stamps (steps-to-first-token is the wall-clock-free
    # latency proxy the benchmarks report)
    submitted_step: Optional[int] = None
    first_token_step: Optional[int] = None
    # first token produced by a DECODE step (== first_token_step + queueing
    # in a colocated engine; in a disaggregated engine the gap additionally
    # covers the prefill->decode KV transfer — the TTFDT metric)
    first_decode_step: Optional[int] = None


# ------------------------------------------------------------ cache walks

def _map_tables(cache, tables: np.ndarray, lengths: np.ndarray):
    """Install per-slot block tables + lengths into a PER-SLOT-layout cache
    pytree (the copy-baseline path). Rejects — instead of silently wrapping —
    table entries that exceed a leaf's pool (sliding-window leaves have
    fewer pages than the manager row): wrapping page indices aliases
    distinct logical pages onto one physical page and corrupts KV."""
    t_np = np.asarray(tables)
    ln = jnp.asarray(lengths)

    def walk(tree):
        if isinstance(tree, attn.PagedKV):
            bt = tree.block_table
            n_pages = bt.shape[-1]
            sub = t_np[..., :n_pages]
            if sub.size and int(sub.max()) >= n_pages:
                raise ValueError(
                    f"block-table entry {int(sub.max())} out of range for a "
                    f"{n_pages}-page pool (sliding-window leaf); refusing to "
                    "wrap page indices — serve this config in zero_copy "
                    "mode, which gives window layers per-slot ring buffers")
            tt = jnp.broadcast_to(jnp.asarray(sub), bt.shape).astype(jnp.int32)
            return tree._replace(block_table=tt,
                                 length=jnp.broadcast_to(ln, tree.length.shape)
                                 .astype(jnp.int32))
        if isinstance(tree, dict):
            return {k: walk(v) for k, v in tree.items()}
        return tree
    return walk(cache)


def _write_slot(batch_cache, single_cache, slot: int):
    """Copy one sequence's prefilled cache into batch slot ``slot`` (the
    staging-copy baseline's O(cache-size) admission walk).

    Leaves under 'blocks' carry a leading (n_blocks,) axis -> batch axis 1;
    everything else has batch axis 0.
    """
    def walk(bt, st, under_blocks):
        if bt is None:                     # PagedKV.layer outside a scan
            return None
        if isinstance(bt, dict):
            return {k: walk(bt[k], st[k], under_blocks or k == "blocks")
                    for k in bt}
        if isinstance(bt, attn.PagedKV):
            return attn.PagedKV(*(walk(b, s, under_blocks)
                                  for b, s in zip(bt, st)))
        if isinstance(bt, tuple) and hasattr(bt, "_fields"):
            return type(bt)(*(walk(b, s, under_blocks)
                              for b, s in zip(bt, st)))
        ax = 1 if under_blocks and bt.ndim >= 2 else 0
        if bt.ndim == st.ndim and bt.shape == st.shape:
            return bt                      # scalar-ish leaves (lengths handled separately)
        idx = (slice(None),) * ax + (slot,)
        src = jnp.take(st, 0, axis=ax) if st.shape[ax] == 1 else st
        return bt.at[idx].set(src.astype(bt.dtype))
    return walk(batch_cache, single_cache, False)


def _build_prefill_view(cache, tables: jax.Array, lengths: jax.Array):
    """Per-admission view of the shared batch cache for a batched prefill of
    ``Nb = tables.shape[0]`` new sequences.

    Global-pool leaves keep THE SAME pool arrays (KV lands in place through
    the tables — zero-copy); per-slot leaves (sliding-window rings,
    recurrent states, cross-KV) become fresh zero rows that are scattered
    back to their slots afterwards. All of this traces inside one jit: no
    host-side cache materialization per admission.
    """
    nb = tables.shape[0]

    def walk(tree, under_blocks):
        if isinstance(tree, attn.PagedKV):
            lead = tree.block_table.shape[:tree.block_table.ndim - 2]
            if attn.is_global_layout(tree):
                return tree._replace(
                    block_table=jnp.broadcast_to(tables, lead + tables.shape),
                    length=jnp.broadcast_to(lengths, lead + lengths.shape))
            n_pages = tree.block_table.shape[-1]
            pool_tail = tree.k_pool.shape[len(lead) + 1:]
            kz = jnp.zeros(lead + (nb,) + pool_tail, tree.k_pool.dtype)
            iota = jnp.broadcast_to(jnp.arange(n_pages, dtype=jnp.int32),
                                    lead + (nb, n_pages))
            return attn.PagedKV(
                k_pool=kz, v_pool=kz, block_table=iota,
                length=jnp.zeros(lead + (nb,), tree.length.dtype))
        if isinstance(tree, dict):
            return {k: walk(v, under_blocks or k == "blocks")
                    for k, v in tree.items()}
        if isinstance(tree, tuple) and hasattr(tree, "_fields"):
            return type(tree)(*(walk(v, under_blocks) for v in tree))
        ax = 1 if under_blocks and tree.ndim >= 2 else 0
        shape = tree.shape[:ax] + (nb,) + tree.shape[ax + 1:]
        return jnp.zeros(shape, tree.dtype)
    return walk(cache, False)


def _merge_prefill_view(cache, view, slots: jax.Array):
    """Fold a prefilled view back into the batch cache. Global-pool leaves
    were written in place (just adopt the updated pool arrays); per-slot
    leaves scatter their rows to ``slots`` (out-of-bounds padding rows are
    dropped)."""
    def walk(c, w, under_blocks):
        if isinstance(c, attn.PagedKV):
            if attn.is_global_layout(c):
                return c._replace(k_pool=w.k_pool, v_pool=w.v_pool)
            lead_n = c.block_table.ndim - 2
            def scat(dst, src):
                if lead_n:
                    return dst.at[:, slots].set(src.astype(dst.dtype),
                                                mode="drop")
                return dst.at[slots].set(src.astype(dst.dtype), mode="drop")
            return c._replace(k_pool=scat(c.k_pool, w.k_pool),
                              v_pool=scat(c.v_pool, w.v_pool))
        if isinstance(c, dict):
            return {k: walk(c[k], w[k], under_blocks or k == "blocks")
                    for k in c}
        if isinstance(c, tuple) and hasattr(c, "_fields"):
            return type(c)(*(walk(a, b, under_blocks) for a, b in zip(c, w)))
        ax = 1 if under_blocks and c.ndim >= 2 else 0
        if ax == 0:
            return c.at[slots].set(w.astype(c.dtype), mode="drop")
        return c.at[:, slots].set(w.astype(c.dtype), mode="drop")
    return walk(cache, view, False)


def _install_tables(cache, tables: jax.Array, lengths: jax.Array):
    """Per-decode-step install of the device-resident table array + current
    per-slot lengths into a GLOBAL-layout cache (pure leaf replacement
    inside jit — the host uploaded at most the delta rows)."""
    def walk(tree):
        if isinstance(tree, attn.PagedKV):
            ln = jnp.broadcast_to(lengths, tree.length.shape).astype(jnp.int32)
            if attn.is_global_layout(tree):
                bt = jnp.broadcast_to(tables, tree.block_table.shape) \
                    .astype(jnp.int32)
                return tree._replace(block_table=bt, length=ln)
            return tree._replace(length=ln)     # window ring: identity table
        if isinstance(tree, dict):
            return {k: walk(v) for k, v in tree.items()}
        return tree
    return walk(cache)


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, n_slots: int, max_len: int,
                 page_size: int = 8, mi: MeshInfo = NO_MESH,
                 offload_mode: str = "zero_copy", src_len: int = 16,
                 eos_token: Optional[int] = None,
                 prefix_sharing: bool = True,
                 record_translation_trace: bool = False,
                 translation_stats: bool = False,
                 pool_pages: Optional[int] = None,
                 tenants: Optional[Dict[str, dict]] = None):
        self.cfg, self.params, self.mi = cfg, params, mi
        self.n_slots, self.max_len, self.page_size = n_slots, max_len, page_size
        self.src_len = src_len
        self.eos = eos_token
        self.max_pages = -(-max_len // page_size)
        kv_bytes = (2 * cfg.n_kv_heads * cfg.d_head
                    * sum(1 for k in cfg.layer_kinds() if "attn" in k or k == "cross_mlp")
                    * jnp.dtype(cfg.activation_dtype).itemsize)
        self.offload_mode = offload_mode
        # Chunked prefill and prefix sharing both rebuild a sequence's state
        # from the pool alone, so the continuous loop serves exactly the
        # zero-copy models with no per-slot state.
        continuous = offload_mode == "zero_copy" and kv_in_global_pool(cfg)
        self._can_share = continuous and prefix_sharing
        # Adaptive translation front-end (both default-off): IOTLB
        # prefetching on the decode gather stream, and online geometry
        # auto-tuning driven by the live hit-rate signal (which requires
        # translate_step to run — see _translation_stats below).
        prefetch = PrefetchConfig(cfg.serve_tlb_prefetch_policy,
                                  degree=cfg.serve_tlb_prefetch_degree,
                                  distance=cfg.serve_tlb_prefetch_distance)
        autotune = None
        if cfg.serve_tlb_autotune:
            base_tlb = TLBConfig(cfg.serve_tlb_entries, cfg.serve_tlb_policy,
                                 ways=cfg.serve_tlb_ways,
                                 ranges=cfg.serve_tlb_ranges)
            cands = tuple(TLBConfig(e, p, ways=w,
                                    ranges=cfg.serve_tlb_ranges)
                          for e, w, p
                          in cfg.serve_tlb_autotune_candidates) \
                or default_autotune_candidates(base_tlb)
            autotune = AutoTuneConfig(interval_steps=cfg.serve_tlb_autotune,
                                      candidates=cands)
        self.mgr = PagedKVManager(n_slots, self.max_pages, page_size,
                                  kv_bytes_per_token=kv_bytes,
                                  offload_mode=offload_mode,
                                  prefix_sharing=self._can_share,
                                  prefix_policy=cfg.prefix_cache_policy,
                                  prefix_cap_pages=cfg.prefix_cache_pages,
                                  tlb_entries=cfg.serve_tlb_entries,
                                  tlb_policy=cfg.serve_tlb_policy,
                                  tlb_ways=cfg.serve_tlb_ways,
                                  tlb_ranges=cfg.serve_tlb_ranges,
                                  # None defers to REPRO_SVASAN (svasan)
                                  sanitize=True if cfg.svasan else None,
                                  tlb_prefetch=prefetch,
                                  autotune=autotune,
                                  prefix_autotune=cfg.prefix_cache_autotune,
                                  pool_pages=pool_pages,
                                  # multi-tenant domains: per-tenant ASID
                                  # ownership, quotas, IOTLB way partitions
                                  tenants=tenants)
        # Translation trace: ("map", fresh_pages) at admission (Listing-1
        # host map pass) and ("step", accesses, tokens_read) per decode step
        # — replayable through any IOMMU walk model (see
        # benchmarks/paged_serving.py --translation-report).
        # ``translation_stats`` runs every decode step's page gathers
        # through the manager's IOMMU (live IOTLB hit/miss signal) — a
        # host-side Python sweep over resident pages, so it is opt-in and
        # implied by tracing; the default hot path pays nothing.
        self.translation_trace: Optional[List[tuple]] = \
            [] if record_translation_trace else None
        # The auto-tuner's only signal — and the prefetcher's only trigger —
        # is the live IOMMU demand traffic, so arming either implies
        # running translate_step each decode step (otherwise the knob
        # would be a silent no-op).
        self._translation_stats = (translation_stats
                                   or record_translation_trace
                                   or autotune is not None
                                   or prefetch.enabled)
        self.queue: deque = deque()
        self.active: Dict[int, Request] = {}
        # Continuous loop: requests submitted or preempted but not currently
        # holding a slot (their tokens live in the scheduler's waiting queue).
        self._waiting_reqs: Dict[int, Request] = {}
        self._next_id = 0
        self._step_count = 0
        # Recurrent layers (mamba/rwkv) scan left-to-right: right-padding
        # would corrupt their final states, so those archs prefill at exact
        # lengths (batching only same-length prompts).
        self._exact_prefill = any(k in MAMBA_KINDS or k == "rwkv"
                                  for k in cfg.layer_kinds())
        self.metrics = {"prefills": 0, "decode_steps": 0,
                        "tokens": 0, "staging_copies": 0, "prefill_s": 0.0,
                        "admit_s": 0.0,
                        # prompt tokens prefilled, and the padded slots the
                        # prefill program ran over (rows x bucketed length)
                        "prefill_tokens": 0, "prefill_padded_tokens": 0,
                        "table_uploads_full": 0, "table_uploads_delta": 0,
                        "table_rows_uploaded": 0, "table_upload_bytes": 0,
                        "admit_table_bytes": 0,
                        "prefill_tokens_saved": 0, "shared_admissions": 0,
                        "cow_page_copies": 0,
                        # KV pages the decoding rows hold, summed over
                        # decode steps: what the decode attention reads
                        "decode_kv_pages": 0}

        if offload_mode == "zero_copy":
            if _sp_mode(cfg, n_slots, max_len):
                raise NotImplementedError(
                    "zero_copy serving does not support the SP cache layout")
            self.null_page = self.mgr.null_page     # == device pool pages
            self.cache = init_cache(cfg, n_slots, max_len, page_size,
                                    src_len=src_len, per_seq=True,
                                    global_pages=self.null_page)
            self._tables_dev = jnp.full((n_slots, self.max_pages),
                                        self.null_page, jnp.int32)
            self._epoch_seen = -1
            self._prefill = jax.jit(self._prefill_zero_copy,
                                    donate_argnums=(2,))
            self._decode = jax.jit(self._decode_zero_copy,
                                   donate_argnums=(4,))
            self._decode_m = jax.jit(self._decode_masked, donate_argnums=(5,))
            self._cow = jax.jit(self._cow_copy_pages, donate_argnums=(0,))
        else:
            if (cfg.sliding_window
                    and any(k == "attn_mlp_local" for k in cfg.layer_kinds())
                    and -(-min(max_len, cfg.sliding_window) // page_size)
                    < self.max_pages):
                # Fail fast: per-slot window leaves have fewer pages than a
                # manager table row, so _map_tables would reject every
                # admission mid-run (data-dependent) — reject at
                # construction instead.
                raise NotImplementedError(
                    "copy-mode serving cannot map block-table rows onto "
                    "sliding-window leaves (fewer pages than the slot "
                    "table); serve this config with offload_mode='zero_copy'")
            self.cache = init_cache(cfg, n_slots, max_len, page_size,
                                    src_len=src_len, per_seq=True)
            self._decode = jax.jit(
                lambda p, t, pos, c: forward_decode(cfg, p, t, pos, c, mi))
            self._prefill = jax.jit(
                lambda p, b, c: forward_prefill(cfg, p, b, c, mi))

        # Continuous loop (core/serving/scheduler.py): dense SequenceBuffer
        # state + a token-budget scheduler composing mixed
        # decode/chunked-prefill steps, with preemption under pool pressure.
        self.buffer: Optional[SequenceBuffer] = None
        self.sched: Optional[Scheduler] = None
        if continuous:
            self.buffer = SequenceBuffer(n_slots,
                                         self.max_pages * page_size)
            self.sched = Scheduler(self.mgr, self.buffer,
                                   cfg.sched_token_budget,
                                   cfg.sched_prefill_chunk,
                                   share_tokens=self._can_share,
                                   on_event=self._trace_event)

    @property
    def scheduler_mode(self) -> str:
        """The step loop this engine runs: "continuous" or "fixed"."""
        return "continuous" if self.sched is not None else "fixed"

    # --------------------------------------------------------------- API
    def submit(self, prompt: List[int], max_tokens: int = 16,
               tenant: Optional[str] = None) -> int:
        self.mgr._check_tenant_name(tenant)     # unknown tenant: fail here,
                                                # not steps later at admit
        # reject, never wrap (and never over a tenant's whole quota)
        self.mgr.ensure_fits(len(prompt), max_tokens, tenant=tenant)
        if self.sched is not None and not prompt:
            raise ValueError("continuous scheduling needs a non-empty prompt")
        rid = self._next_id
        self._next_id += 1
        self.queue.append(Request(rid, list(prompt), max_tokens,
                                  tenant=tenant,
                                  submitted_at=time.perf_counter(),
                                  submitted_step=self._step_count))
        return rid

    @property
    def has_work(self) -> bool:
        return bool(self.queue or self.active
                    or (self.sched is not None and self.sched.has_work))

    def step(self, finished: Dict[int, Request]) -> None:
        """Run ONE engine step (admission + compute + completion harvest)
        under the engine's step loop. Benchmarks drive this directly to
        inject arrivals between steps; :meth:`run` is the closed loop."""
        with TraceAnnotation("engine.step", step=self._step_count):
            if self.sched is not None:
                self._continuous_step()
            else:
                self._admit()
                # Prefill's first token can already finish a request
                # (max_tokens=1): release it before the decode step, which
                # appends a token to every active request.
                self._release_done(finished)
                self._decode_step()
            self._step_count += 1
            self._release_done(finished)

    def run(self, max_steps: int = 10_000) -> Dict[int, Request]:
        finished: Dict[int, Request] = {}
        steps = 0
        while self.has_work and steps < max_steps:
            self.step(finished)
            steps += 1
        return finished

    def _release_done(self, finished: Dict[int, Request]) -> None:
        done = [r for r in self.active if self.mgr.seqs[r].done]
        with TraceAnnotation("engine.release", finished=len(done)):
            for rid in done:
                req = self.active.pop(rid)
                req.done_at = time.perf_counter()
                st = self.mgr.seqs[rid]
                if self.sched is not None:
                    # Generations preempted along the way were folded into
                    # out_tokens at preemption time; append the rest.
                    req.out_tokens.extend(st.tokens)
                    self.sched.finish(rid)
                else:
                    req.out_tokens = st.tokens
                if self.translation_trace is not None:
                    self.translation_trace.append(
                        ("unmap", st.slot, len(st.pages)))
                self.mgr.release(rid)
                finished[rid] = req

    def _trace_event(self, ev: tuple) -> None:
        """Scheduler lifecycle events (map/unmap/preempt/resume) join the
        translation trace in order, keeping it replayable."""
        if self.translation_trace is not None:
            self.translation_trace.append(ev)

    def invalidate_epoch(self) -> None:
        """Flush every device translation (paper Listing 1); the next decode
        step performs a full-table upload."""
        self.mgr.invalidate_epoch()

    # --------------------------------------------------- fixed-wave loop
    def _admit(self):
        admitted: List = []
        while self.queue:
            req = self.queue[0]
            t0 = time.perf_counter()
            st = self.mgr.admit(req.req_id, len(req.prompt), req.max_tokens,
                                tenant=req.tenant)
            self.metrics["admit_s"] += time.perf_counter() - t0
            if st is None:
                break                      # no slot/pages: wait for a release
            self.queue.popleft()
            if self.offload_mode == "copy":
                self._prefill_into_slot(req, st.slot)
                self.active[req.req_id] = req
                continue
            if self.translation_trace is not None:
                # Listing-1 map pass over the freshly allocated pages. The
                # extended fields (slot + the row's full logical->physical
                # table) let a replaying prefetcher resolve upcoming pages
                # the way the hardware reads the page table.
                self.translation_trace.append(
                    ("map", list(st.pages), st.slot, list(st.pages)))
            admitted.append(req)
        if not admitted:
            return
        if self._exact_prefill:
            # Recurrent layers cannot right-pad: batch same-length prompts.
            groups: Dict[int, list] = {}
            for req in admitted:
                groups.setdefault(len(req.prompt), []).append(req)
            for group in groups.values():
                self._batched_prefill(group)
        else:
            self._batched_prefill(admitted)
        for req in admitted:
            self.active[req.req_id] = req

    def _bucket_len(self, longest: int) -> int:
        """Power-of-two token bucket (stable jit cache keys), capped at slot
        capacity."""
        lb = self.page_size
        while lb < longest:
            lb *= 2
        return min(lb, self.max_pages * self.page_size)

    def _batched_prefill(self, group: List[Request]):
        """ONE padded prefill call for all newly admitted requests: KV is
        scattered straight into the shared global pool through the admitted
        rows' block tables. Admission's host->device traffic is the token
        ids plus int32 table entries — not KV bytes."""
        t0 = time.perf_counter()
        lens = [len(req.prompt) for req in group]
        lb = max(lens) if self._exact_prefill else self._bucket_len(max(lens))
        nb = 1
        while nb < len(group):
            nb *= 2
        nb = max(min(nb, self.n_slots), len(group))
        tokens = np.zeros((nb, lb), np.int32)
        lengths = np.zeros((nb,), np.int32)
        slots = np.full((nb,), self.n_slots, np.int32)   # OOB: scatter-dropped
        tables = np.full((nb, self.max_pages), self.mgr.null_page, np.int32)
        for i, req in enumerate(group):
            st = self.mgr.seqs[req.req_id]
            tokens[i, :lens[i]] = req.prompt
            lengths[i] = lens[i]
            slots[i] = st.slot
            tables[i] = self.mgr.tables[st.slot]
        # Admission upload accounting: only the REAL rows' table entries
        # (padding rows exist for jit-key stability, not data movement).
        self.metrics["admit_table_bytes"] += len(group) * self.max_pages * 4
        batch = {"tokens": jnp.asarray(tokens),
                 "lengths": jnp.asarray(lengths),
                 "tables": jnp.asarray(tables),
                 "slots": jnp.asarray(slots)}
        logits, self.cache = self._prefill(self.params, batch, self.cache)
        logits = np.asarray(logits)
        now = time.perf_counter()
        for i, req in enumerate(group):
            first = int(np.argmax(logits[i, -1]))
            self.mgr.append_token(req.req_id, first)
            req.first_token_at = now
            req.first_token_step = self._step_count
        self.metrics["prefills"] += 1
        self.metrics["prefill_tokens"] += sum(lens)
        self.metrics["prefill_padded_tokens"] += nb * lb
        self.metrics["prefill_s"] += time.perf_counter() - t0

    def _prefill_zero_copy(self, params, batch, cache):
        cfg = self.cfg
        view = _build_prefill_view(cache, batch["tables"], batch["lengths"])
        fb = {"tokens": batch["tokens"], "lengths": batch["lengths"]}
        if "prefix_lens" in batch:      # chunked prefill (continuous loop)
            fb["prefix_lens"] = batch["prefix_lens"]
            fb["write_tables"] = batch["write_tables"]
        nb = batch["tokens"].shape[0]
        if cfg.is_encdec:
            fb["enc_x"] = jnp.zeros((nb, self.src_len, cfg.d_model),
                                    jnp.dtype(cfg.activation_dtype))
        elif cfg.n_image_tokens:
            fb["img_x"] = jnp.zeros((nb, cfg.n_image_tokens, cfg.d_model),
                                    jnp.dtype(cfg.activation_dtype))
        logits, view = forward_prefill(cfg, params, fb, view, self.mi)
        cache = _merge_prefill_view(cache, view, batch["slots"])
        return logits, cache

    def _prefill_into_slot(self, req: Request, slot: int):
        """Copy-mode baseline: materialize a fresh single-sequence cache,
        prefill it, physically duplicate it (the staging copy), then walk it
        leaf-by-leaf into the batch cache."""
        t0 = time.perf_counter()
        cfg = self.cfg
        single = init_cache(cfg, 1, self.max_len, self.page_size,
                            src_len=self.src_len, per_seq=True)
        row = self.mgr.tables[slot:slot + 1]
        single = _map_tables(single, row, np.zeros(1, np.int32))
        toks = jnp.asarray(req.prompt, jnp.int32)[None]
        batch = {"tokens": toks}
        if cfg.is_encdec:
            batch["enc_x"] = jnp.zeros((1, self.src_len, cfg.d_model),
                                       jnp.dtype(cfg.activation_dtype))
        elif cfg.n_image_tokens:
            batch["img_x"] = jnp.zeros((1, cfg.n_image_tokens, cfg.d_model),
                                       jnp.dtype(cfg.activation_dtype))
        logits, single = self._prefill(self.params, batch, single)
        # staging copy baseline: physically duplicate the KV pools once
        single = jax.tree.map(lambda x: x + 0, single)
        self.metrics["staging_copies"] += 1
        self.cache = _write_slot(self.cache, single, slot)
        first = int(jnp.argmax(logits[0, -1]))
        self.mgr.append_token(req.req_id, first)
        req.first_token_at = time.perf_counter()
        req.first_token_step = self._step_count
        self.metrics["prefills"] += 1
        self.metrics["prefill_tokens"] += len(req.prompt)
        self.metrics["prefill_padded_tokens"] += len(req.prompt)
        self.metrics["prefill_s"] += time.perf_counter() - t0

    # --------------------------------------------------------------- CoW
    def _cow_copy_pages(self, cache, src, dst):
        """One batched physical page duplication in every global-pool KV
        leaf: ``pool[dst] = pool[src]`` (padding pairs carry dst == NULL and
        are scatter-dropped). This is the entire device-side cost of a CoW
        divergence — page_size tokens of KV per layer, instead of
        re-prefilling the whole shared prefix."""
        def walk(tree):
            if isinstance(tree, attn.PagedKV):
                if not attn.is_global_layout(tree):
                    return tree
                lead = tree.block_table.ndim - 2
                def cp(pool):
                    if lead:
                        return pool.at[:, dst].set(pool[:, src], mode="drop")
                    return pool.at[dst].set(pool[src], mode="drop")
                return tree._replace(k_pool=cp(tree.k_pool),
                                     v_pool=cp(tree.v_pool))
            if isinstance(tree, dict):
                return {k: walk(v) for k, v in tree.items()}
            if isinstance(tree, tuple) and hasattr(tree, "_fields"):
                return type(tree)(*(walk(v) for v in tree))
            return tree
        return walk(cache)

    def _apply_cow(self):
        """Execute queued copy-on-write page duplications (src -> dst)
        before the next device op reads a duplicated page or a new
        admission recycles a released source page."""
        pairs = self.mgr.drain_cow_copies()
        if not pairs:
            return
        with TraceAnnotation("engine.cow", pairs=len(pairs)):
            if self.translation_trace is not None:
                # A CoW remap is a fresh mapping: the host map pass warms
                # the duplicated pages' PTE lines before the device touches
                # them.
                self.translation_trace.append(("map", [d for _, d in pairs]))
            n = 1
            while n < len(pairs):
                n *= 2
            src = np.zeros((n,), np.int32)
            dst = np.full((n,), self.mgr.null_page, np.int32)  # pad: dropped
            for i, (s, d) in enumerate(pairs):
                src[i], dst[i] = s, d
            self.cache = self._cow(self.cache, jnp.asarray(src),
                                   jnp.asarray(dst))
            self.metrics["cow_page_copies"] += len(pairs)

    # --------------------------------------------------------------- decode
    def _upload_tables(self):
        """Delta table upload: send only rows that changed since last step;
        a full-table upload happens only after an epoch invalidation."""
        with TraceAnnotation("engine.tables") as span:
            if self.mgr.epoch != self._epoch_seen:
                self.mgr.delta_rows()    # superseded by the full upload
                self._tables_dev = jnp.asarray(self.mgr.tables)
                self._epoch_seen = self.mgr.epoch
                self.metrics["table_uploads_full"] += 1
                self.metrics["table_rows_uploaded"] += self.n_slots
                self.metrics["table_upload_bytes"] += \
                    int(self.mgr.tables.nbytes)
                span.set_metadata(rows=self.n_slots)
                return
            rows = self.mgr.delta_rows()
            span.set_metadata(rows=len(rows))
            if rows:
                idx = np.asarray(rows)
                sub = self.mgr.tables[idx]
                self._tables_dev = self._tables_dev.at[jnp.asarray(idx)].set(
                    jnp.asarray(sub))
                self.metrics["table_uploads_delta"] += 1
                self.metrics["table_rows_uploaded"] += len(rows)
                self.metrics["table_upload_bytes"] += int(sub.nbytes)

    def _count_kv_pages(self, kv_len: np.ndarray) -> int:
        """Count the KV pages that decoding rows with ``kv_len`` cached
        tokens hold once this step's token is written, and return them."""
        pages = int((-(-(kv_len.astype(np.int64) + 1) // self.page_size))
                    .sum())
        self.metrics["decode_kv_pages"] += pages
        return pages

    def _decode_zero_copy(self, params, tokens, kv_len, tables, cache):
        cache = _install_tables(cache, tables, kv_len)
        return forward_decode(self.cfg, params, tokens, kv_len, cache, self.mi)

    def _decode_step(self):
        if not self.active:
            return
        lengths = self.mgr.device_lengths()
        # KV length = tokens whose KV is in cache; exactly one token is
        # pending per active sequence (the one this step feeds in).
        kv_len = np.maximum(lengths - 1, 0).astype(np.int32)
        last = np.zeros((self.n_slots, 1), np.int32)
        for rid, req in self.active.items():
            st = self.mgr.seqs[rid]
            last[st.slot, 0] = st.tokens[-1] if st.tokens else \
                (req.prompt[-1] if req.prompt else 0)
        self._count_kv_pages(kv_len[[self.mgr.seqs[r].slot
                                     for r in self.active]])
        pos = jnp.asarray(kv_len)                       # write/rope position
        if self.offload_mode == "zero_copy":
            self._upload_tables()
            if self._translation_stats:
                # Run this step's page gathers through the IOMMU front-end:
                # the live-traffic IOTLB hit/miss signal (CountingWalk), and
                # the trace --translation-report replays through Sv39Walk.
                accesses = self.mgr.translate_step()
                if self.translation_trace is not None:
                    self.translation_trace.append(
                        ("step", accesses, int(kv_len.sum())))
            logits, self.cache = self._decode(
                self.params, jnp.asarray(last), pos, self._tables_dev,
                self.cache)
        else:
            # copy baseline: full table re-upload + re-map every step
            tables = self.mgr.device_tables()
            self.cache = _map_tables(self.cache, tables, kv_len)
            self.metrics["table_uploads_full"] += 1
            self.metrics["table_rows_uploaded"] += self.n_slots
            self.metrics["table_upload_bytes"] += int(tables.nbytes)
            logits, self.cache = self._decode(self.params, jnp.asarray(last),
                                              pos, self.cache)
        nxt = np.asarray(jnp.argmax(logits[:, 0], axis=-1))
        for rid in list(self.active):
            st = self.mgr.seqs[rid]
            tok = int(nxt[st.slot])
            self.mgr.append_token(rid, tok)
            self.metrics["tokens"] += 1
            if self.eos is not None and tok == self.eos:
                st.done = True
        self.metrics["decode_steps"] += 1
        self.mgr.observe_step()

    # ------------------------------------------------- continuous batching
    def _continuous_step(self):
        out = self._schedule()
        if out.chunks:
            self._chunk_prefill(out.chunks)
        self._decode_continuous(out)

    def _schedule(self) -> SchedulerOutput:
        """Land queued CoW copies, hand the queue to the scheduler, compose
        this step, and move preempted and admitted requests between
        ``active`` and ``_waiting_reqs``."""
        # Queued CoW copies must land BEFORE the scheduler can preempt: a
        # preemption frees its sequence's pages, and a same-step resume or
        # chunk prefill could recycle a pending copy's source page.
        self._apply_cow()
        with TraceAnnotation("engine.schedule") as span:
            while self.queue:
                req = self.queue.popleft()
                self.sched.submit(req.req_id, req.prompt, req.max_tokens,
                                  tenant=req.tenant)
                self._waiting_reqs[req.req_id] = req
            t0 = time.perf_counter()
            out = self.sched.schedule()
            self.metrics["admit_s"] += time.perf_counter() - t0
            for sid, folded in out.preempted:
                req = self.active.pop(sid)
                req.out_tokens.extend(folded)
                self._waiting_reqs[sid] = req
            for sid in out.admitted + out.resumed:
                self.active[sid] = self._waiting_reqs.pop(sid)
            span.set_metadata(decodes=len(out.decode_slots),
                              chunks=len(out.chunks),
                              preempted=len(out.preempted))
        return out

    def _chunk_prefill(self, chunks):
        """One padded prefill call for this step's chunk spans — the
        chunked-prefill counterpart of ``_batched_prefill``. Every chunk
        runs the prefix path: ``prefix_lens`` positions earlier chunks'
        (and shared prefixes') KV as context, and ``write_tables`` NULLs
        every page before the chunk's own span — the scatter zero-scrubs
        ALL non-NULL entries, so leaving an earlier chunk's page mapped
        would erase its KV. Pages past the span are harmlessly re-scrubbed
        (still unwritten). The final chunk's logits produce the first
        token (or re-inject a preempted sequence's pending token)."""
        t0 = time.perf_counter()
        with TraceAnnotation("engine.prefill.pack") as span:
            sufs = [c.end - c.start for c in chunks]
            lb = self._bucket_len(max(sufs))
            nb = 1
            while nb < len(chunks):
                nb *= 2
            nb = max(min(nb, self.n_slots), len(chunks))
            real, padded = sum(sufs), nb * lb
            span.set_metadata(rows=nb, tokens=padded, real=real)
            tokens = np.zeros((nb, lb), np.int32)
            lengths = np.zeros((nb,), np.int32)
            prefix = np.zeros((nb,), np.int32)
            slots = np.full((nb,), self.n_slots, np.int32)  # OOB: dropped
            tables = np.full((nb, self.max_pages), self.mgr.null_page,
                             np.int32)
            wtables = np.full((nb, self.max_pages), self.mgr.null_page,
                              np.int32)
            for i, c in enumerate(chunks):
                st = self.mgr.seqs[c.seq_id]
                tokens[i, :sufs[i]] = self.buffer.chunk_tokens(
                    c.slot, c.start, c.end)
                lengths[i] = sufs[i]
                prefix[i] = c.start
                slots[i] = c.slot
                tables[i] = self.mgr.tables[c.slot]
                wtables[i] = tables[i]
                keep_from = max(st.shared_pages, c.start // self.page_size)
                wtables[i, :keep_from] = self.mgr.null_page
            self.metrics["admit_table_bytes"] += \
                len(chunks) * self.max_pages * 4
            batch = {"tokens": jnp.asarray(tokens),
                     "lengths": jnp.asarray(lengths),
                     "tables": jnp.asarray(tables),
                     "slots": jnp.asarray(slots),
                     "prefix_lens": jnp.asarray(prefix),
                     "write_tables": jnp.asarray(wtables)}
        with TraceAnnotation("engine.prefill.launch"):
            logits, self.cache = self._prefill(self.params, batch, self.cache)
        finals = [(i, c) for i, c in enumerate(chunks) if c.is_final]
        if finals:
            with TraceAnnotation("engine.prefill.sync"):
                logits = np.asarray(logits)
        with TraceAnnotation("engine.prefill.commit"):
            now = time.perf_counter()
            for i, c in enumerate(chunks):
                self.buffer.advance(c.slot, c.end)
                # Progressive prefix registration: the chunk's KV is
                # resident NOW, so its pages may join the index (an eager
                # registration at lazy admission would publish uncomputed
                # pages).
                self.mgr.register_progress(c.seq_id,
                                           self.buffer.token_ids[c.slot],
                                           c.end)
            for i, c in finals:
                first = (c.pending if c.pending is not None
                         else int(np.argmax(logits[i, -1])))
                self.mgr.append_token(c.seq_id, first)
                self.buffer.append(c.slot, first)
                req = self.active[c.seq_id]
                if req.first_token_at is None:
                    req.first_token_at = now
                    req.first_token_step = self._step_count
            self.metrics["prefills"] += 1
            self.metrics["prefill_tokens"] += real
            self.metrics["prefill_padded_tokens"] += padded
            self.metrics["prefill_s"] += time.perf_counter() - t0

    def _decode_masked(self, params, tokens, kv_len, tables, mask, cache):
        """Decode with non-decoding slots masked out: their table rows
        become all-NULL (KV writes dropped, gathers read zero) and their
        kv_len arrives pre-masked to 0, so a mid-prefill slot's pages are
        never touched. Masked rows compute garbage logits that nothing
        consumes — identical shapes every step, one jit trace."""
        tables = jnp.where(mask[:, None], tables, self.null_page)
        cache = _install_tables(cache, tables, kv_len)
        return forward_decode(self.cfg, params, tokens, kv_len, cache,
                              self.mi)

    def _resident_tokens(self) -> Dict[int, int]:
        """Per-sequence resident-token counts for this step's translation
        accounting: a decoding sequence gathers everything it has; a
        mid-prefill sequence only its computed chunks. (A disaggregated
        front-end extends this with its decode worker's sequences.)"""
        resident = {}
        for sid in self.sched.running:
            slot = self.buffer.slot_of(sid)
            resident[sid] = (self.mgr.seqs[sid].length
                             if self.buffer.is_decoding(slot)
                             else int(self.buffer.n_computed[slot]))
        return resident

    def _decode_continuous(self, out: SchedulerOutput):
        self._apply_cow()       # final-chunk first tokens may queue CoW
        self._upload_tables()
        if self._translation_stats:
            resident = self._resident_tokens()
            if resident:
                accesses = self.mgr.translate_step(resident=resident)
                if self.translation_trace is not None:
                    self.translation_trace.append(
                        ("step", accesses, int(sum(resident.values()))))
        if not out.decode_slots:
            return
        rows = len(out.decode_slots)
        with TraceAnnotation("engine.decode.pack", rows=rows) as span:
            lengths = self.mgr.device_lengths()
            mask = np.zeros((self.n_slots,), bool)
            mask[out.decode_slots] = True
            kv_len = np.where(mask, np.maximum(lengths - 1, 0), 0) \
                .astype(np.int32)
            span.set_metadata(pages=self._count_kv_pages(kv_len[mask]))
            last = np.where(mask, self.buffer.last_tokens(), 0) \
                .astype(np.int32)[:, None]
            args = (jnp.asarray(last), jnp.asarray(kv_len), self._tables_dev,
                    jnp.asarray(mask))
        with TraceAnnotation("engine.decode.launch"):
            logits, self.cache = self._decode_m(self.params, *args,
                                                self.cache)
        with TraceAnnotation("engine.decode.sync"):
            nxt = np.asarray(jnp.argmax(logits[:, 0], axis=-1))
        with TraceAnnotation("engine.decode.commit", rows=rows):
            for slot in out.decode_slots:
                sid = int(self.buffer.seq_ids[slot])
                tok = int(nxt[slot])
                self.mgr.append_token(sid, tok)
                self.buffer.append(slot, tok)
                self.metrics["tokens"] += 1
                req = self.active.get(sid)
                if req is not None and req.first_decode_step is None:
                    req.first_decode_step = self._step_count
                if self.eos is not None and tok == self.eos:
                    self.mgr.seqs[sid].done = True
            self.metrics["decode_steps"] += 1
            self.mgr.observe_step()

    def decode_step_hlo(self) -> str:
        """Optimized HLO of the zero-copy decode step at this engine's
        shapes: what the device runs (e.g. whether the Pallas kernel is in
        it as a ``tpu_custom_call``)."""
        if self.sched is None:
            raise NotImplementedError(
                "decode_step_hlo needs the continuous engine")
        lowered = self._decode_m.lower(
            self.params, jnp.zeros((self.n_slots, 1), jnp.int32),
            jnp.zeros((self.n_slots,), jnp.int32), self._tables_dev,
            jnp.zeros((self.n_slots,), bool), self.cache)
        return lowered.compile().as_text()

    def stats(self) -> dict:
        s = self.mgr.stats()
        m = dict(self.metrics)
        # Single source of truth is the manager's prefix index; the engine
        # keys are kept as the stable serving-level aliases
        # (``cow_page_copies`` stays engine-owned: copies EXECUTED
        # device-side, vs the manager's queued count).
        pf = s.get("prefix")
        if pf is not None:
            m["prefill_tokens_saved"] = pf["tokens_saved"]
            m["shared_admissions"] = pf["hits"]
        if self.sched is not None:
            m["sched"] = self.sched.stats()
        return {**m, **s}
