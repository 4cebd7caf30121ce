"""Real serving engine: continuous batching over an actual JAX model.

One ``Engine`` = one model replica.  Each iteration:

  1. the configured policy (SlideBatching or a baseline — the SAME code
     that drives the simulator) forms a batch against the shared
     BlockManager accounting;
  2. reload/eviction directives are applied to the PagedKVPool (host
     mirrors, drops, restores) — with ``overlap_transfers`` the copies run
     on a background worker (serving/transfer.py): offloads are enqueued
     as one-gather snapshots, reloads consume pre-staged buffers, and
     completions feed the BlockManager's accounting lanes + the measured
     ``t_block`` behind the §4.3 adaptive copy budget;
  3. decode entries run as one ``decode_batch`` call; prefill chunks run
     PACKED — every request's chunk concatenated into one flat-stream
     ``prefill_packed`` call (per-request ``prefill_chunk`` kept as a
     fallback) — greedy-sampling the first token when a prompt completes;
  4. measured wall-clock batch latencies feed the §4.1 estimator, which is
     refit online every ``refit_every`` batches (the offline-profiling
     bootstrap happens in ``calibrate``).

The engine clock can be virtual (``clock=manual``) for deterministic tests.

Two driving modes:

* synchronous — a caller (tests, ``ServiceController``) invokes ``step()``
  directly and inspects the returned dict;
* threaded — an ``EngineDriver`` owns the engine on its own thread, pulls
  submissions from a per-instance inbox queue, and forwards per-token
  ``TokenEvent``s plus per-step ``StepEvent``s to a sink (the async
  ``ServiceFrontend``).  All engine state is touched only on the driver
  thread, so the engine itself needs no locks.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import tracing
from ..core.batching import (BatchPlan, EngineConfig, SchedView,
                             compute_remaining, evict_for_space,
                             needed_context)
from ..core.blocks import BlockManager, blocks_for
from ..core.estimator import BatchLatencyEstimator
from ..core.request import Phase, Request
from ..kernels import kv_block_dequantize
from ..models.model import ArchConfig
from . import model_exec
from .kv_pool import PagedKVPool
from .prefix_cache import RadixPrefixCache
from .spec import DraftRunner
from .transfer import TransferWorker

logger = logging.getLogger(__name__)


def _on_device(method):
    """Run an Engine entry point with the replica's device as JAX's default
    (thread-local), so every array the engine builds from host data lands
    on its own chip.  ``device=None`` keeps the process default."""
    @functools.wraps(method)
    def wrapped(self, *args, **kwargs):
        with jax.default_device(self.device):
            return method(self, *args, **kwargs)
    return wrapped


@dataclass(frozen=True)
class TokenEvent:
    """One token leaving an engine, stamped on the driver thread."""
    rid: int
    token: int
    index: int                   # 1-based output position
    t_wall: float                # time.monotonic() at emission
    first: bool
    last: bool


@dataclass
class HandoffPayload:
    """One finished prefill leaving a prefill-role replica: the request,
    everything needed to resume it (prompt + tokens already streamed), and
    its KV as host-side block payloads — fp32 arrays, or ``(int8 vals,
    fp32 scales)`` pairs when the handoff wire is quantized (the same
    per-(layer, K/V)-plane scheme as the cold tier, dequantized ON DEVICE
    at adoption)."""
    req: Request
    prompt: np.ndarray
    outputs: list            # tokens already emitted (streamed by src)
    kv_tokens: int           # KV extent shipped == needed_context(req)
    payloads: list           # per-block: np.ndarray | (vals, scales)
    quantized: bool
    src_iid: int = -1        # stamped by the EngineDriver at emission

    @property
    def n_blocks(self) -> int:
        return len(self.payloads)

    @property
    def wire_bytes(self) -> int:
        return sum(b[0].nbytes + b[1].nbytes if isinstance(b, tuple)
                   else b.nbytes for b in self.payloads)


@dataclass(frozen=True)
class HandoffEvent:
    """A prefill replica finished a request's prefill leg: its KV payload
    is ready to be adopted by a decode replica."""
    iid: int                 # source (prefill) instance
    payload: HandoffPayload


@dataclass(frozen=True)
class HandoffAdopted:
    """A decode replica adopted a payload: the decode leg is live there."""
    iid: int                 # adopting (decode) instance
    payload: HandoffPayload


@dataclass(frozen=True)
class HandoffDropped:
    """A decode replica could not adopt a delivered payload (no device
    blocks even after policy eviction) — the router should fail the
    request over to a re-prefill."""
    iid: int                 # target (decode) instance that refused
    payload: HandoffPayload


@dataclass(frozen=True)
class DriverFailed:
    """A driver thread died: ``Engine.step`` (or an import) raised.  The
    replica serves nothing more; the frontend fails its open streams with
    ``error`` instead of leaving their consumers waiting forever."""
    iid: int
    error: BaseException


@dataclass(frozen=True)
class StepEvent:
    """Engine-side summary of one iteration, for router bookkeeping."""
    iid: int
    free_blocks: int
    latency: float
    est_time: float
    prefill_done: tuple = ()     # rids whose first token just came out
    finished: tuple = ()         # rids fully generated this step


@dataclass
class EngineStats:
    iterations: int = 0
    tokens_out: int = 0
    prefill_tokens: int = 0
    evictions: int = 0
    reload_blocks: int = 0
    cache_hit_tokens: int = 0      # prompt tokens served from the prefix cache
    cache_insert_blocks: int = 0   # blocks adopted into the prefix cache
    cow_forks: int = 0             # copy-on-write forks of shared blocks
    packed_prefill_calls: int = 0  # batched multi-request prefill launches
    offload_blocks: int = 0        # async D2H blocks landed on host
    staged_hits: int = 0           # reloads served from pre-staged buffers
    staged_misses: int = 0         # reloads that fell back to a sync copy
    transfer_failures: int = 0     # background copies that raised (fell
    # back to the synchronous path; first one is logged by the worker)
    t_block_measured: float = 0.0  # EWMA per-block copy time (closed loop)
    refit_failures: int = 0        # online estimator refits that failed
    decode_launches: int = 0       # jitted decode calls (one per step with
    # decode work; fused or logits path)
    host_bytes: int = 0            # current hot host-tier bytes (<= budget)
    spill_blocks: int = 0          # cumulative prefix-cache blocks spilled
    # to the host tier instead of destroyed (tiered KV cache)
    cold_blocks: int = 0           # current int8 cold-tier blocks
    host_syncs: int = 0            # device->host fetches in the hot loop —
    # the perf gate asserts exactly one per model launch (no hidden syncs)
    # --- disaggregation (prefill/decode split) ---------------------------
    handoffs_out: int = 0          # prefill legs exported to a decode peer
    handoff_blocks_out: int = 0    # KV blocks shipped out
    handoff_bytes_out: int = 0     # wire bytes shipped out (int8 < fp32)
    handoffs_in: int = 0           # payloads adopted from a prefill peer
    handoff_blocks_in: int = 0     # KV blocks adopted
    handoff_bytes_in: int = 0      # wire bytes adopted
    # --- speculative decoding (draft propose + packed verify) ------------
    spec_proposed: int = 0         # draft tokens proposed for verification
    spec_accepted: int = 0         # proposals matching the target argmax
    spec_rejected: int = 0         # proposals refuted (== proposed - accepted)
    draft_launches: int = 0        # draft-model jit calls (prefill + rounds)
    spec_depth_hist: dict = field(default_factory=dict)  # depth -> entries


class Engine:
    def __init__(self, cfg: ArchConfig, params, eng_cfg: EngineConfig,
                 policy, *, num_blocks: int = 512, block_size: int = 16,
                 t_block: float = 5e-4, max_ctx: int = 1024,
                 est: Optional[BatchLatencyEstimator] = None,
                 bm_kwargs: Optional[dict] = None, seed: int = 0,
                 prefix_cache: bool = True,
                 cache_blocks: Optional[int] = None,
                 packed_prefill: bool = True,
                 overlap_transfers: bool = True,
                 fused_decode: bool = True,
                 host_tier_bytes: Optional[int] = None,
                 cold_quantize: bool = True,
                 role: str = "coloc",
                 handoff_quantize: bool = False,
                 spec_draft: Optional[tuple] = None,
                 spec_draft_blocks: Optional[int] = None,
                 device: Optional[jax.Device] = None):
        if role not in ("coloc", "prefill", "decode"):
            raise ValueError(f"unknown engine role: {role!r}")
        if eng_cfg.spec_k > 0 and spec_draft is None:
            raise ValueError("spec_k > 0 requires spec_draft=(cfg, params)")
        self.cfg = cfg
        # ``device`` pins this replica (params, KV pool, draft, staging
        # buffers) to one chip; None keeps the default device
        self.device = device
        self.params = (params if device is None
                       else jax.device_put(params, device))
        # a role-parameterized replica runs the same pipeline; the role
        # only (a) flips the policy's pd_mode (prefill replicas price
        # admission with the prefill-phase phi), (b) arms the handoff
        # export path (prefill) / import path (decode)
        self.role = role
        if role != "coloc" and eng_cfg.pd_mode != role:
            eng_cfg = dataclasses.replace(eng_cfg, pd_mode=role)
        self.eng_cfg = eng_cfg
        # int8 handoff wire: quantize the exported KV on device (the cold
        # tier's kernel pair) so the cross-replica copy is ~4x narrower;
        # lossy-but-deterministic (|x - deq| <= scale/2 per plane)
        self.handoff_quantize = handoff_quantize
        self.policy = policy
        self.max_ctx = max_ctx
        # host_tier_bytes bounds the hot host tier (LRU demotion into the
        # int8 cold tier, see kv_pool.KVTierStore); None = legacy
        # unbounded host mirror with bitwise-identical token streams
        self.pool = PagedKVPool(cfg, num_blocks, block_size,
                                host_tier_bytes=host_tier_bytes,
                                cold_quantize=cold_quantize, device=device)
        self.bm = BlockManager(num_blocks - 1, block_size, t_block,
                               **(bm_kwargs or {}))
        # radix prefix cache: shares prompt KV across requests (refcounted
        # blocks, CoW); holds at most ``cache_blocks`` beyond live pins and
        # yields them back on demand (BlockManager.reclaim_cache).  With a
        # bounded host tier, evictions SPILL into it instead of destroying
        # the KV (restorable on a later match).
        self.cache: Optional[RadixPrefixCache] = (
            RadixPrefixCache(self.pool, self.bm, max_blocks=cache_blocks,
                             spill=host_tier_bytes is not None)
            if prefix_cache else None)
        self.est = est or BatchLatencyEstimator(
            a_p=1e-8, b_p=1e-8, c_p=1e-5, a_d=1e-8, b_d=1e-4, t_c=1e-3)
        # --- overlapped execution (packed prefill + async transfer lanes)
        self.packed_prefill = packed_prefill
        # fused decode: argmax on device, batch/table padded to shape
        # buckets so the jit cache persists across steps (see
        # model_exec.decode_step); the logits path is kept as a safety
        # hatch and for the fused-vs-unfused perf/equivalence gate
        self.fused_decode = fused_decode
        self.overlap_transfers = overlap_transfers
        # speculative decoding: a draft replica proposes, the target packs
        # all (request, position) rows into ONE verify_step launch; greedy
        # acceptance keeps streams bitwise-identical to plain decode
        self.draft: Optional[DraftRunner] = None
        if spec_draft is not None and self.eng_cfg.spec_k > 0:
            dcfg, dparams = spec_draft
            self.draft = DraftRunner(
                dcfg, dparams, num_blocks=spec_draft_blocks or num_blocks,
                block_size=block_size, max_ctx=max_ctx, device=device)
        self.worker: Optional[TransferWorker] = (
            TransferWorker(device=device) if overlap_transfers else None)
        if self.cache is not None:
            # spill restores prefer buffers the worker pre-staged
            self.cache.worker = self.worker
        # per-rid transfer epoch: bumped on evict/release so background
        # completions for a superseded residency generation are discarded
        self._epoch: dict[int, int] = {}
        # proactive-offload directives recorded during form_batch (the K/V
        # they name is only fully written once the step's exec completes)
        self._offload_directives: list[tuple[int, int, int, int]] = []
        if self.worker is not None:
            self.bm.external_lanes = True
            self.bm.offload_sink = self._note_offload_directive
        # full token sequence (prompt + outputs) per request, appended
        # incrementally — avoids the per-chunk prompt+outputs rebuild
        self._seqs: dict[int, np.ndarray] = {}
        self._seq_fill: dict[int, int] = {}
        # prefill-role export state: payloads whose D2H copy is riding the
        # background lane (rid -> payload + retained device snapshot), and
        # completed payloads awaiting pickup by the driver/controller
        self._handoff_wait: dict[int, tuple[HandoffPayload, object, int]] = {}
        self._handoff_ready: list[HandoffPayload] = []
        self.queue: list[Request] = []
        self.now = 0.0
        # when set (frontend mode), ``now`` tracks wall time relative to a
        # shared epoch so token stamps are monotonic ACROSS replicas —
        # required for cross-replica failover and client-edge metrics.
        self._wall_epoch: Optional[float] = None
        self.stats = EngineStats()
        self._profile: list[tuple[list, float]] = []
        self.refit_every = 50
        self.alive = True
        self.outputs: dict[int, list[int]] = {}
        # streaming hook: called as on_token(req, tok, first, last) from
        # whichever thread steps the engine, at the instant of emission —
        # this is what lets TTFT/TPOT be measured at the client edge.
        self.on_token: Optional[Callable[[Request, int, bool, bool],
                                         None]] = None

    # ------------------------------------------------------------------
    @_on_device
    def add_request(self, req: Request, prompt_tokens: np.ndarray,
                    prior_outputs: Optional[list[int]] = None) -> None:
        """``prior_outputs``: tokens already streamed to the client before a
        failover — the engine resumes mid-generation by recomputing their
        KV (they are ordinary known tokens) and continuing exactly."""
        req.instance = id(self) & 0xffff
        self.queue.append(req)
        self.outputs[req.rid] = list(prior_outputs or [])
        prompt = np.asarray(prompt_tokens, np.int32)
        req._prompt = prompt  # type: ignore
        # pre-size the full token sequence once; _emit appends in place
        prior = self.outputs[req.rid]
        seq = np.zeros(len(prompt) + max(req.output_len, len(prior)) + 1,
                       np.int32)
        seq[:len(prompt)] = prompt
        if prior:
            seq[len(prompt):len(prompt) + len(prior)] = prior
        self._seqs[req.rid] = seq
        self._seq_fill[req.rid] = len(prompt) + len(prior)
        if self.cache is not None:
            hit, blocks = self.cache.match(prompt, self.now, req.rid,
                                           req.weight)
            req.prefilled = hit
            if hit:
                # point the table at the cached blocks; only the uncached
                # suffix remains as (chunked) prefill work
                self.pool.share(req.rid, blocks)
                self.bm.attach_cached(req, hit)
                self.stats.cache_hit_tokens += hit

    def has_work(self) -> bool:
        return (any(r.phase != Phase.FINISHED for r in self.queue)
                or bool(self._handoff_wait) or bool(self._handoff_ready))

    # ------------------------------------------------------------------
    # §4.3 transfer lanes (background worker plumbing)
    # ------------------------------------------------------------------
    def _note_offload_directive(self, rid: int, start: int, n: int) -> None:
        """BlockManager offload_sink: a proactive D2H mirror was scheduled
        during form_batch.  The blocks' K/V is only guaranteed written once
        this step's exec completes, so just record the directive; the
        device snapshot happens in ``_dispatch_offloads``."""
        self._offload_directives.append(
            (rid, start, n, self._epoch.get(rid, 0)))

    def _dispatch_offloads(self) -> None:
        """Snapshot each recorded directive's blocks (one device gather)
        and hand them to the background D2H lane."""
        directives, self._offload_directives = self._offload_directives, []
        if self.worker is None:
            return
        for rid, start, n, epoch in directives:
            if epoch != self._epoch.get(rid, 0):
                continue            # evicted/released since the directive
            t = self.pool.tables.get(rid)
            if not t:
                continue
            logical = [bi for bi in range(start, start + n) if bi < len(t)]
            if not logical:
                continue
            with tracing.span("kv.offload", rid=rid):
                if self.pool.tier.prefer_cold(len(logical)):
                    # this mirror would land demote-bound in the cold
                    # tier: quantize on device so the D2H wire is int8
                    gathered = self.pool.gather_blocks_quantized(rid,
                                                                 logical)
                else:
                    gathered = self.pool.gather_blocks(rid, logical)
                self.worker.offload(rid, epoch, logical, gathered)

    def _drain_transfers(self) -> None:
        """Collect background-copy completions; feed the accounting lanes
        (real transfers replace the virtual clock) and the measured-
        throughput side of the adaptive copy budget."""
        if self.worker is None:
            return
        for d in self.worker.drain():
            if d.kind == "d2h" and d.rid in self._handoff_wait:
                # handoff export riding the D2H lane: the local leg is
                # already released, so this must be intercepted BEFORE the
                # stale/dead guards.  Failure falls back to a synchronous
                # fetch of the retained device snapshot (functional, so
                # still intact regardless of later pool writes).
                payload, gathered, epoch = self._handoff_wait[d.rid]
                if d.epoch == epoch:
                    del self._handoff_wait[d.rid]
                    self._epoch.pop(d.rid, None)
                    if d.ok:
                        payload.payloads = [d.blocks[bi]
                                            for bi in sorted(d.blocks)]
                    else:
                        self.stats.transfer_failures += 1
                        payload.payloads = self._materialize_handoff(
                            gathered, payload.quantized)
                    self._finalize_handoff(payload)
                continue
            stale = d.epoch != self._epoch.get(d.rid, 0)
            dead = d.rid not in self.bm.table
            if d.kind == "h2d":
                # a staging buffer that can no longer be consumed would pin
                # one of the double-buffer slots forever: job finished after
                # invalidate() (stale), after the request was released
                # (dead), or after the reload it was staged for already ran
                # synchronously (nothing left on host to restore)
                if d.rid < 0:
                    # radix-cache spill pseudo-rid: never in bm.table, so
                    # the dead-guard must instead ask the cache whether the
                    # spilled group still exists (restore consumes the
                    # buffer; re-adoption/prune invalidates it)
                    if (self.cache is None
                            or not self.cache.has_spilled(d.rid)):
                        self.worker.invalidate(d.rid)
                    continue
                s = self.bm.table.get(d.rid)
                if dead or (s is not None and s.host_tokens == 0):
                    self.worker.invalidate(d.rid)
                elif stale:
                    self.worker.discard_stale(d.rid,
                                              self._epoch.get(d.rid, 0))
            if stale:
                continue
            if not d.ok:
                self.stats.transfer_failures += 1
                if d.kind == "d2h":
                    # release the pending claim; mirroring retries later
                    self.bm.note_offload_failed(d.rid, d.n_blocks)
                continue
            if d.kind == "d2h" and d.rid in self.bm.table:
                self.pool.host_store(d.rid, d.blocks)
                self.bm.note_offload_complete(d.rid, d.n_blocks)
                self.stats.offload_blocks += d.n_blocks
            if not d.quantized:
                # int8-wire copies are excluded: the copy budget scales
                # them by COLD_WIRE_RATIO on top of the fp32 t_block,
                # so folding their samples in would count the 4x twice
                self.bm.observe_transfer(d.n_blocks, d.seconds)
                self.stats.t_block_measured = self.bm.t_block

    def _prefetch_reloads(self) -> None:
        """Hint the H2D staging lane: evicted requests near the head of the
        (policy-sorted) queue will likely reload next round — stage their
        host blocks now so the copy lands before the batch that needs it.
        Payloads go out in tier wire format: cold groups ship int8 and the
        worker dequantizes on device.  Leftover slots stage the most
        recently touched radix-cache spill groups."""
        if self.worker is None:
            return
        hinted = 0
        for r in self.queue:
            if hinted >= self.worker.max_staged:
                break
            s = self.bm.table.get(r.rid)
            if s is None or s.host_tokens <= 0 or s.dev_tokens > 0:
                continue
            nb = blocks_for(s.host_tokens, self.bm.block_size)
            payloads = self.pool.tier.payloads(r.rid, range(nb))
            if payloads is None:
                continue
            if self.worker.prefetch(r.rid, self._epoch.get(r.rid, 0),
                                    payloads):
                hinted += 1
        if self.cache is not None and hinted < self.worker.max_staged:
            for host_rid, payloads in self.cache.spill_candidates(
                    self.worker.max_staged - hinted):
                if self.worker.prefetch(host_rid, 0, payloads):
                    hinted += 1

    def _forget_transfers(self, rid: int) -> None:
        """Invalidate all in-flight transfer state for rid (evict/release)."""
        self._epoch[rid] = self._epoch.get(rid, 0) + 1
        if self.worker is not None:
            self.worker.invalidate(rid)

    def _sync_tier_state(self) -> None:
        """Mirror the tier store into the scheduling layer: mark each live
        request's host span cold when its tier group was demoted (the
        copy-budget control then prices its reload at the int8 wire), and
        refresh the tier gauges on EngineStats.  With an unbounded host
        tier nothing is ever cold and this is a no-op on the accounting."""
        tier = self.pool.tier
        if tier.budget_bytes is not None:
            for rid, s in self.bm.table.items():
                s.cold_tokens = (s.host_tokens if tier.is_cold(rid) else 0)
        self.stats.host_bytes = tier.host_bytes
        self.stats.cold_blocks = tier.cold_blocks
        if self.cache is not None:
            self.stats.spill_blocks = self.cache.stats.spilled_blocks

    def _evict_to_host(self, r: Request) -> None:
        """Apply one (already accounted) eviction to the data layer: the
        surviving span must be on host — with overlap the async mirror
        already landed (mirrored_blocks only counts real completions);
        otherwise copy the missing blocks now, in one batched device
        fetch — then drop the device references."""
        s = self.bm.state(r)
        keep_blocks = blocks_for(s.host_tokens, self.bm.block_size)
        if keep_blocks:
            h = self.pool.host.get(r.rid, {})
            missing = [bi for bi in range(keep_blocks) if bi not in h]
            self.pool.offload_blocks(r.rid, missing)
        self.pool.drop_device_blocks(r.rid)
        self._forget_transfers(r.rid)
        if self.draft is not None:
            self.draft.drop(r.rid)
        self.stats.evictions += 1

    def _sync_pool_with_bm(self, plan: BatchPlan) -> None:
        """Apply the §4.3 directives the policy issued on the accounting
        layer (BlockManager) to the actual data (PagedKVPool)."""
        for r in plan.evictions:
            self._evict_to_host(r)

    # ------------------------------------------------------------------
    # disaggregation: prefill -> decode KV handoff
    # ------------------------------------------------------------------
    def _materialize_handoff(self, gathered, quantized: bool) -> list:
        """Synchronous fetch of a handoff snapshot into per-block host
        payloads (the no-worker path, and the failure fallback)."""
        if quantized:
            vals, scales = jax.device_get(gathered)
            vals, scales = np.asarray(vals), np.asarray(scales)
            return [(vals[i], scales[i]) for i in range(vals.shape[0])]
        data = np.asarray(jax.device_get(gathered))
        return [data[i] for i in range(data.shape[0])]

    def _finalize_handoff(self, payload: HandoffPayload) -> None:
        self.stats.handoffs_out += 1
        self.stats.handoff_blocks_out += payload.n_blocks
        self.stats.handoff_bytes_out += payload.wire_bytes
        self._handoff_ready.append(payload)

    def _collect_handoffs(self) -> None:
        """Prefill role: any queued request whose prefill leg is complete
        (first token emitted — or a failover recompute caught up — and the
        KV fully device-resident) is exported.  Runs before form_batch so
        an export-ready request is never decoded locally, and again after
        the step so the common case (prefill finished this iteration)
        ships without an extra scheduling round."""
        ready = []
        for r in self.queue:
            if r.phase != Phase.DECODE:
                continue        # output_len == 1 finishes on this replica
            s = self.bm.table.get(r.rid)
            if s is None or s.dev_tokens < needed_context(r):
                continue
            ready.append(r)
        for r in ready:
            self._export_handoff(r)

    def _export_handoff(self, r: Request) -> None:
        rid = r.rid
        kv_tokens = needed_context(r)
        nb = blocks_for(kv_tokens, self.pool.block_size)
        logical = list(range(nb))
        payload = HandoffPayload(
            req=r, prompt=np.asarray(r._prompt, np.int32),  # type: ignore
            outputs=list(self.outputs.get(rid, [])),
            kv_tokens=kv_tokens, payloads=[],
            quantized=self.handoff_quantize)
        # ONE device gather (quantized on device when the wire is int8);
        # jax arrays are functional, so the snapshot is race-free and the
        # local blocks can be released immediately
        gathered = (self.pool.gather_blocks_quantized(rid, logical)
                    if self.handoff_quantize
                    else self.pool.gather_blocks(rid, logical))
        epoch = self._epoch.get(rid, 0) + 1
        self._epoch[rid] = epoch
        if self.worker is not None:
            self._handoff_wait[rid] = (payload, gathered, epoch)
            self.worker.offload(rid, epoch, logical, gathered)
        # release the local leg — the decode replica owns the request now
        self.bm.release(r)
        self.pool.release(rid)
        if self.worker is not None:
            self.worker.invalidate(rid)
        self.outputs.pop(rid, None)
        self._seqs.pop(rid, None)
        self._seq_fill.pop(rid, None)
        if self.draft is not None:
            self.draft.drop(rid)
        self.queue = [q for q in self.queue if q.rid != rid]
        r.instance = None
        if self.worker is None:
            self._epoch.pop(rid, None)
            payload.payloads = self._materialize_handoff(
                gathered, payload.quantized)
            self._finalize_handoff(payload)

    def take_handoffs(self) -> list[HandoffPayload]:
        """Completed handoff payloads since the last call (driver picks
        these up after each step and forwards them to the router)."""
        out, self._handoff_ready = self._handoff_ready, []
        return out

    def handoff_outputs(self, rid: int) -> Optional[list[int]]:
        """Streamed tokens of a request currently in handoff-export state.

        ``_export_handoff`` pops ``self.outputs[rid]`` the moment the KV
        snapshot is taken, so a caller mirroring outputs into a durable
        log after the step would otherwise miss the prefill leg's first
        token — and a failover resume from that log would drop it.  The
        payload keeps the authoritative copy until delivery."""
        ent = self._handoff_wait.get(rid)
        if ent is not None:
            return list(ent[0].outputs)
        for p in self._handoff_ready:
            if p.req.rid == rid:
                return list(p.outputs)
        return None

    @_on_device
    def import_handoff(self, payload: HandoffPayload) -> bool:
        """Decode side: adopt a prefill peer's KV payload and continue the
        decode leg exactly where the source stopped.  All blocks land in
        ONE batched scatter; int8 wire payloads are dequantized ON DEVICE.
        Returns False if device blocks could not be made available (the
        caller should fail over to a re-prefill)."""
        req, rid = payload.req, payload.req.rid
        nb = len(payload.payloads)
        ok = self.bm.grow(req, payload.kv_tokens, self.now)
        if not ok:
            # the admission-time reservation should make this impossible;
            # evict per policy (mirrors EngineSim.import_request)
            view = SchedView(self.queue, self.bm, self.est, self.eng_cfg,
                             self.now)
            need = self.bm.blocks_needed_for_growth(req, payload.kv_tokens)
            for v in evict_for_space(view, need, {rid}):
                self._evict_to_host(v)
            ok = self.bm.grow(req, payload.kv_tokens, self.now)
        if not ok or not self.pool.alloc(rid, nb):
            self.bm.release(req)
            self.pool.release(rid)
            return False
        entries = payload.payloads
        if entries and all(isinstance(e, tuple) for e in entries):
            vals = jnp.asarray(np.stack([e[0] for e in entries]))
            scales = jnp.asarray(np.stack([e[1] for e in entries]))
            data = kv_block_dequantize(vals, scales)
        else:
            data = jnp.asarray(np.stack(entries))
        phys = jnp.asarray(self.pool.tables[rid], jnp.int32)
        self.pool.kv = self.pool.kv.at[:, :, phys].set(
            jnp.moveaxis(data, 0, 2))
        req.instance = id(self) & 0xffff
        self.queue.append(req)
        self.outputs[rid] = list(payload.outputs)
        prompt = np.asarray(payload.prompt, np.int32)
        req._prompt = prompt  # type: ignore
        prior = payload.outputs
        seq = np.zeros(len(prompt) + max(req.output_len, len(prior)) + 1,
                       np.int32)
        seq[:len(prompt)] = prompt
        if prior:
            seq[len(prompt):len(prompt) + len(prior)] = prior
        self._seqs[rid] = seq
        self._seq_fill[rid] = len(prompt) + len(prior)
        self.stats.handoffs_in += 1
        self.stats.handoff_blocks_in += nb
        self.stats.handoff_bytes_in += payload.wire_bytes
        return True

    def use_wall_clock(self, epoch: float) -> None:
        """Drive ``now`` from ``time.monotonic() - epoch`` (shared across
        replicas) instead of the per-engine virtual latency accumulator."""
        self._wall_epoch = epoch
        self.now = max(self.now, time.monotonic() - epoch)

    @_on_device
    def step(self) -> Optional[dict]:
        if not self.alive:
            return None
        with tracing.span("engine.step"):
            return self._step()

    def _step(self) -> Optional[dict]:
        if self._wall_epoch is not None:
            self.now = max(self.now, time.monotonic() - self._wall_epoch)
        with tracing.span("kv.drain"):
            self._drain_transfers()
            self.bm.complete_offloads(self.now)
            self._sync_tier_state()
        if self.role == "prefill":
            # straggler exports (e.g. a full-prompt cache hit made the
            # request decode-ready without any prefill work this step) —
            # and keeps export-ready requests out of the local batch
            self._collect_handoffs()
        view = SchedView(self.queue, self.bm, self.est, self.eng_cfg,
                         self.now)
        with tracing.span("sched.form_batch"):
            plan = self.policy.form_batch(view)
        if not plan.entries:
            # evictions can outlive a failed admission round: keep the
            # pool consistent with the accounting before going idle, and
            # use the idle gap to stage likely reloads
            if plan.evictions:
                with tracing.span("kv.sync"):
                    self._sync_pool_with_bm(plan)
            self._offload_directives.clear()
            with tracing.span("kv.prefetch"):
                self._prefetch_reloads()
            return None
        t0 = time.monotonic()
        with tracing.span("kv.sync"):
            self._sync_pool_with_bm(plan)
        self._reload_for(plan)

        decode_entries = [e for e in plan.entries if not e.is_prefill]
        prefill_entries = [e for e in plan.entries if e.is_prefill]
        emitted: list[Request] = []

        if prefill_entries:
            if self.packed_prefill:
                self._run_prefill_packed(prefill_entries, emitted)
            else:
                self._run_prefill_fallback(prefill_entries, emitted)

        # --- decode batch ---------------------------------------------------
        if decode_entries:
            if self.draft is not None:
                self._run_decode_spec(decode_entries, emitted)
            else:
                self._run_decode(decode_entries, emitted)

        latency = time.monotonic() - t0
        if self._wall_epoch is not None:
            self.now = max(self.now, time.monotonic() - self._wall_epoch)
        else:
            self.now += latency
        self.stats.iterations += 1
        self._profile.append((plan.work_items(), latency))
        if len(self._profile) >= self.refit_every:
            with tracing.span("est.refit"):
                self._refit()

        with tracing.span("engine.finish"):
            finished = self._release_finished()
        if self.role == "prefill":
            # export every request whose prefill leg just completed (the
            # gather runs before the proactive-mirror dispatch below, so
            # the exported KV ships exactly once)
            self._collect_handoffs()
        # all K/V written and finished requests released — snapshot +
        # enqueue the proactive D2H mirrors the policy scheduled (the
        # released requests' directives drop out via their empty tables,
        # sparing a full dead-request gather), then stage likely reloads
        self._dispatch_offloads()
        with tracing.span("kv.prefetch"):
            self._prefetch_reloads()
        return {"emitted": emitted, "finished": finished,
                "latency": latency, "plan": plan}

    def _reload_for(self, plan: BatchPlan) -> None:
        """Reload data for requests whose plan restored host blocks; prefer
        the background lane's pre-staged buffers (the H2D copy already
        landed), falling back to a synchronous batched copy."""
        for e in plan.entries:
            s = self.bm.state(e.req)
            hb = self.pool.host_blocks(e.req.rid)
            dev_blocks_needed = blocks_for(s.dev_tokens, self.bm.block_size)
            have = len(self.pool.tables.get(e.req.rid, []))
            # only copy what apply_reload promised (restore_pending): with
            # async mirroring, host entries also exist for live
            # device-resident requests, so ``hb > 0`` alone would trigger
            # phantom reloads on every block-boundary growth
            if not (s.restore_pending > 0 and have < dev_blocks_needed
                    and hb):
                continue
            n = min(s.restore_pending, dev_blocks_needed - have)
            s.restore_pending = 0
            with tracing.span("kv.reload", rid=e.req.rid):
                staged = (self.worker.take_staged(
                    e.req.rid, self._epoch.get(e.req.rid, 0))
                    if self.worker is not None else None)
                if staged is not None and staged[0] > 0:
                    # ``n`` also counts blocks this step will write fresh
                    # (grown chunk/decode tokens); the staged buffer covers
                    # exactly the restorable host prefix — consume what it
                    # has, the rest is new capacity allocated at exec time
                    # (same semantics as reload_blocks, which stops at the
                    # first non-host block)
                    self.pool.reload_from_device(e.req.rid, staged[1],
                                                 min(n, staged[0]))
                    self.stats.staged_hits += 1
                else:
                    self.pool.reload_blocks(e.req.rid, n)
                    if self.worker is not None:
                        self.stats.staged_misses += 1
            self.stats.reload_blocks += n

    def _release_finished(self) -> list[Request]:
        """Release every finished request's blocks and per-request state;
        returns them."""
        finished = [r for r in self.queue if r.phase == Phase.FINISHED]
        for r in finished:
            self.bm.release(r)
            self.pool.release(r.rid)
            if self.draft is not None:
                self.draft.drop(r.rid)
            # drop all per-request transfer state — long-lived replicas
            # must not grow without bound.  A late completion for this rid
            # is caught by the dead-request guard in _drain_transfers (rid
            # no longer in bm.table), so no epoch bump is needed here.
            if self.worker is not None:
                self.worker.invalidate(r.rid)
            self._epoch.pop(r.rid, None)
            self._seqs.pop(r.rid, None)
            self._seq_fill.pop(r.rid, None)
        self.queue = [r for r in self.queue if r.phase != Phase.FINISHED]
        return finished

    # ------------------------------------------------------------------
    # decode execution
    # ------------------------------------------------------------------
    def _run_decode(self, decode_entries: list, emitted: list) -> None:
        """Plain decode: one token per request in one launch (fused argmax
        or the logits fallback)."""
        rids = [e.req.rid for e in decode_entries]
        nb = len(decode_entries)
        bs = self.pool.block_size
        # pages each row's attention reads, and the table width once
        # ensure_capacity below has grown each table to cover them
        pages = [-(-(e.l_kv + 1) // bs) for e in decode_entries]
        maxp = max(self.pool.table_len(e.req.rid, e.l_kv + 1)
                   for e in decode_entries)
        if self.fused_decode:
            # pad batch/table to shape buckets (extra rows: token 0, len 0,
            # null-block table) and fetch only the (B,) argmax
            b_b = model_exec.seg_bucket(nb)
            maxp = model_exec.table_bucket(maxp)
        else:
            b_b = nb
        with tracing.span("exec.decode", valid_pages=sum(pages),
                          table_pages=b_b * maxp):
            with tracing.span("exec.stage"):
                for e in decode_entries:
                    self.pool.ensure_capacity(e.req.rid, e.l_kv + 1)
                    if self.pool.ensure_writable(e.req.rid, e.l_kv // bs):
                        self.bm.note_fork(e.req)
                        self.stats.cow_forks += 1
                lens = np.zeros(b_b, np.int32)
                lens[:nb] = [e.l_kv for e in decode_entries]
                last = np.zeros(b_b, np.int32)
                last[:nb] = [self._last_token(e.req)
                             for e in decode_entries]
                table = self.pool.table_array(rids, maxp=maxp, rows=b_b)
                last, lens = jnp.asarray(last), jnp.asarray(lens)
            with tracing.span("exec.launch"):
                if self.fused_decode:
                    toks, self.pool.kv = model_exec.decode_step(
                        self.cfg, self.params, self.pool.kv, last, table,
                        lens)
                else:
                    logits, self.pool.kv = model_exec.decode_batch(
                        self.cfg, self.params, self.pool.kv, last, table,
                        lens)
                    toks = jnp.argmax(logits, -1)
            with tracing.span("exec.wait"):
                nxt = np.asarray(toks)[:nb]
        self.stats.decode_launches += 1
        self.stats.host_syncs += 1
        with tracing.span("engine.emit"):
            for e, tok in zip(decode_entries, nxt):
                self._emit(e.req, int(tok), emitted)

    def _run_decode_spec(self, decode_entries: list, emitted: list) -> None:
        """Speculative decode: the draft proposes up to ``e.depth`` tokens
        per request, then ONE ``verify_step`` launch scores every
        (request, position) row packed together — depth-0 requests
        contribute their single plain-decode row.  Greedy acceptance takes
        the leading proposals that match the target argmax and emits one
        bonus token per match, so the stream is bitwise-identical to plain
        decode (the verify rows ARE plain decode rows; see
        kernels/spec_verify.py).  Depth was capped at admission to the
        current block's remainder, so all speculative writes land in
        blocks the +1-token growth already reserved."""
        with tracing.span("exec.decode"):
            for e in decode_entries:
                self.pool.ensure_capacity(e.req.rid, e.l_kv + 1 + e.depth)
                if self.pool.ensure_writable(e.req.rid,
                                             e.l_kv // self.pool.block_size):
                    self.bm.note_fork(e.req)
                    self.stats.cow_forks += 1
            launches0 = self.draft.launches
            syncs0 = self.draft.syncs
            items = [(e.req.rid, self._seq_view(e.req), e.depth)
                     for e in decode_entries if e.depth > 0]
            proposals = self.draft.propose(items) if items else {}
            self.stats.draft_launches += self.draft.launches - launches0
            self.stats.host_syncs += self.draft.syncs - syncs0
            for e in decode_entries:
                if e.depth > 0 and e.req.rid not in proposals:
                    e.depth = 0    # draft pool exhausted: plain decode row

            with tracing.span("exec.stage"):
                # pack one verify row per (request, draft position);
                # tables stay compact — one row per REQUEST — addressed
                # via row_seg.  The segment bucket reserves one extra
                # all-zero row so padding rows' K/V write lands in the
                # null block (decode_step convention).
                rids = [e.req.rid for e in decode_entries]
                n_seg = len(decode_entries)
                rows: list[tuple] = []   # (entry index, token)
                for i, e in enumerate(decode_entries):
                    rows.append((i, self._last_token(e.req)))
                    for t in proposals.get(e.req.rid, [])[:e.depth]:
                        rows.append((i, t))
                n_rows = len(rows)
                r_b = model_exec.seg_bucket(n_rows)
                s_b = model_exec.seg_bucket(n_seg + 1)
                maxp = max(len(self.pool.tables[r]) for r in rids)
                maxp_b = model_exec.table_bucket(maxp)
                tokens = np.zeros(r_b, np.int32)
                lens = np.zeros(r_b, np.int32)
                # padding rows -> the zero table row
                row_seg = np.full(r_b, n_seg, np.int32)
                starts = np.zeros(n_seg, np.int32)
                prev = -1
                for ri, (i, tok) in enumerate(rows):
                    if i != prev:
                        starts[i] = ri
                        prev = i
                    tokens[ri] = tok
                    lens[ri] = decode_entries[i].l_kv + (ri - starts[i])
                    row_seg[ri] = i
                tables = self.pool.table_array(rids, maxp=maxp_b, rows=s_b)
                tokens, lens = jnp.asarray(tokens), jnp.asarray(lens)
                row_seg = jnp.asarray(row_seg)
            with tracing.span("exec.launch"):
                toks, self.pool.kv = model_exec.verify_step(
                    self.cfg, self.params, self.pool.kv, tokens, tables, lens,
                    row_seg)
            with tracing.span("exec.wait"):
                out = np.asarray(toks)

        self.stats.decode_launches += 1
        self.stats.host_syncs += 1
        with tracing.span("engine.emit"):
            for i, e in enumerate(decode_entries):
                d = e.depth
                g = out[starts[i]:starts[i] + d + 1]
                props = proposals.get(e.req.rid, [])[:d]
                a = 0
                while a < d and props[a] == g[a]:
                    a += 1
                for t in g[:a + 1]:
                    self._emit(e.req, int(t), emitted)
                # bonus tokens advance context inside blocks the +1 growth
                # already covers (depth <= block remainder at admission)
                self.bm.state(e.req).dev_tokens += a
                if d > 0:
                    self.draft.observe(e.req.rid, d, a)
                    accept = getattr(self.policy, "spec_accept", None)
                    if accept is not None:
                        accept.update(d, a)
                self.stats.spec_proposed += d
                self.stats.spec_accepted += a
                self.stats.spec_rejected += d - a
                self.stats.spec_depth_hist[d] = \
                    self.stats.spec_depth_hist.get(d, 0) + 1

    # ------------------------------------------------------------------
    # prefill execution
    # ------------------------------------------------------------------
    def _seq_view(self, r: Request) -> np.ndarray:
        """Full known token sequence (prompt + outputs so far), maintained
        incrementally — no per-chunk concatenation."""
        return self._seqs[r.rid][:self._seq_fill[r.rid]]

    def _prepare_prefill(self, e) -> None:
        """Block-table growth + CoW guard shared by both prefill paths."""
        r, ctx = e.req, e.l_kv
        self.pool.ensure_capacity(r.rid, ctx + e.n_tokens)
        # CoW guard: the first block written this pass may be shared
        # (all later blocks are freshly allocated)
        if self.pool.ensure_writable(r.rid, ctx // self.pool.block_size):
            self.bm.note_fork(r)
            self.stats.cow_forks += 1

    def _finish_prefill(self, e, tok: int, emitted: list) -> None:
        """Prompt-completion bookkeeping shared by both prefill paths."""
        r = e.req
        self._emit(r, tok, emitted)
        if self.cache is not None:
            # adopt the prompt's full blocks into the prefix cache
            # (charge moves request -> cache; blocks now shared)
            prompt: np.ndarray = r._prompt  # type: ignore
            adopted = self.cache.insert(
                prompt, self.pool.tables[r.rid], r.rid, self.now, r.weight)
            if adopted:
                self.bm.donate_to_cache(r, adopted)
                self.stats.cache_insert_blocks += adopted
            self.cache.shrink_to_capacity()

    def _run_prefill_packed(self, entries: list, emitted: list) -> None:
        """Packed multi-request prefill: every chunk this step concatenated
        into one flat token stream and executed in a single bucketed jit
        call — and each segment stages only the blocks it needs, instead
        of the engine-wide ``max_ctx`` span per chunk."""
        with tracing.span("exec.prefill"):
            with tracing.span("exec.stage"):
                for e in entries:
                    self._prepare_prefill(e)
                chunks = [(self._seq_view(e.req)[e.l_kv:e.l_kv + e.n_tokens],
                           e.l_kv, self.pool.tables[e.req.rid])
                          for e in entries]
                arrays, smax, sq = model_exec.pack_prefill(
                    chunks, self.pool.block_size)
                arrays = [jnp.asarray(a) for a in arrays]
            with tracing.span("exec.launch"):
                logits, self.pool.kv = model_exec.prefill_packed(
                    self.cfg, self.params, self.pool.kv, *arrays, smax, sq)
                toks = jnp.argmax(logits, -1)
            with tracing.span("exec.wait"):
                nxt = np.asarray(toks)
        self.stats.packed_prefill_calls += 1
        self.stats.host_syncs += 1
        with tracing.span("engine.emit"):
            for i, e in enumerate(entries):
                r = e.req
                self.stats.prefill_tokens += e.n_tokens
                if e.l_kv + e.n_tokens >= r.prompt_len and r.generated == 0:
                    self._finish_prefill(e, int(nxt[i]), emitted)
                # recompute completion emits nothing (next decode pass does)

    def _run_prefill_fallback(self, entries: list, emitted: list) -> None:
        """Per-request chunked prefill (the pre-packed path, kept for
        equivalence testing and as a safety hatch)."""
        for e in entries:
            r = e.req
            c = model_exec.bucket(e.n_tokens)
            ctx = e.l_kv
            done = ctx + e.n_tokens >= r.prompt_len and r.generated == 0
            with tracing.span("exec.prefill"):
                with tracing.span("exec.stage"):
                    self._prepare_prefill(e)
                    toks = np.zeros((1, c), np.int32)
                    seq = self._seq_view(r)
                    toks[0, :e.n_tokens] = seq[ctx:ctx + e.n_tokens]
                    max_ctx = model_exec.bucket(ctx + c, buckets=(
                        self.max_ctx,)) if ctx + c <= self.max_ctx else ctx + c
                    maxp = max_ctx // self.pool.block_size
                    table = self.pool.table_array([r.rid], maxp=maxp)
                    toks = jnp.asarray(toks)
                    ctx_len = jnp.asarray([ctx], jnp.int32)
                with tracing.span("exec.launch"):
                    logits, self.pool.kv = model_exec.prefill_chunk(
                        self.cfg, self.params, self.pool.kv, toks, table,
                        ctx_len, max_ctx)
                    if done:
                        tok = jnp.argmax(logits[0, e.n_tokens - 1])
                if done:
                    with tracing.span("exec.wait"):
                        tok = int(tok)
            self.stats.prefill_tokens += e.n_tokens
            if done:
                self.stats.host_syncs += 1
                with tracing.span("engine.emit"):
                    self._finish_prefill(e, tok, emitted)
            # recompute completion emits nothing (next decode pass does)

    # ------------------------------------------------------------------
    def _last_token(self, r: Request) -> int:
        outs = self.outputs[r.rid]
        if outs:
            return outs[-1]
        return int(r._prompt[-1])  # type: ignore

    def _emit(self, r: Request, tok: int, emitted: list) -> None:
        self.outputs[r.rid].append(tok)
        seq, fill = self._seqs.get(r.rid), self._seq_fill.get(r.rid, 0)
        if seq is not None:
            if fill >= len(seq):    # defensive: output ran past output_len
                seq = np.concatenate([seq, np.zeros(len(seq), np.int32)])
                self._seqs[r.rid] = seq
            seq[fill] = tok
            self._seq_fill[r.rid] = fill + 1
        first = r.generated == 0
        r.emit_token(self.now)
        self.stats.tokens_out += 1
        emitted.append(r)
        if self.on_token is not None:
            self.on_token(r, tok, first, r.phase == Phase.FINISHED)

    def _refit(self) -> None:
        try:
            batches = [b for b, _ in self._profile]
            lats = [l for _, l in self._profile]
            self.est = BatchLatencyEstimator.fit(batches, lats)
        except Exception:
            # keep serving on the previous fit, but never silently: count
            # every failure and log the first one per engine
            self.stats.refit_failures += 1
            if self.stats.refit_failures == 1:
                logger.warning(
                    "online estimator refit failed (keeping previous "
                    "coefficients); further failures are only counted",
                    exc_info=True)
        self._profile = self._profile[-200:]

    @_on_device
    def flush_transfers(self, timeout: float = 30.0) -> bool:
        """Wait for the background lanes to drain, then fold the completed
        transfers into the accounting (tests / benchmarks)."""
        if self.worker is None:
            return True
        ok = self.worker.flush(timeout)
        self._drain_transfers()
        return ok

    def run_until_drained(self, max_iters: int = 10000) -> None:
        it = 0
        while self.has_work() and it < max_iters:
            if self.step() is None:
                # idle but queued work exists only if nothing schedulable
                break
            it += 1

    def kill(self) -> list[Request]:
        self.alive = False
        if self.worker is not None:
            self.worker.stop()
        orphans = [r for r in self.queue if r.phase != Phase.FINISHED]
        for r in orphans:
            self.bm.release(r)
            self.pool.release(r.rid)
            if self.draft is not None:
                self.draft.drop(r.rid)
            r.instance = None
        self.queue.clear()
        # handoff payloads in flight or awaiting pickup die with the
        # replica — their requests must re-prefill elsewhere
        for payload, _, _ in self._handoff_wait.values():
            payload.req.instance = None
            orphans.append(payload.req)
        self._handoff_wait.clear()
        for payload in self._handoff_ready:
            payload.req.instance = None
            orphans.append(payload.req)
        self._handoff_ready.clear()
        return orphans


# --------------------------------------------------------------------------
# threaded driver loop
# --------------------------------------------------------------------------

class EngineDriver:
    """Runs one ``Engine``'s iteration loop on a dedicated thread.

    Submissions arrive on a per-instance inbox queue (fed by GoRouting
    dispatch in the ``ServiceFrontend``); each loop iteration drains the
    inbox into the engine queue, forms/executes one batch, and forwards
    token + step events to ``sink(event)``.  The sink is called on the
    driver thread and must be thread-safe (the frontend bridges into its
    asyncio loop with ``call_soon_threadsafe``).
    """

    def __init__(self, iid: int, engine: Engine,
                 sink: Callable[[object], None],
                 *, idle_wait: float = 2e-3, name: Optional[str] = None):
        self.iid = iid
        self.engine = engine
        self.sink = sink
        self.idle_wait = idle_wait
        self.inbox: "queue.Queue[tuple]" = queue.Queue()
        # rids added to THIS engine that have not yet emitted here —
        # drives StepEvent.prefill_done.  ``generated == 1`` would miss
        # failover-resumed requests whose first token predates this engine.
        self._awaiting_first: set[int] = set()
        self._first_done: list[int] = []
        self._stop = threading.Event()
        self._idle = threading.Event()
        self._idle.set()
        # the exception that killed the driver thread, if one did
        self.error: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._run, name=name or f"engine-driver-{iid}",
            daemon=True)
        engine.on_token = self._on_token

    # -- submission (any thread) ---------------------------------------
    def submit(self, req: Request, prompt_tokens,
               prior_outputs: Optional[list] = None) -> None:
        self.inbox.put(("req", req, prompt_tokens, prior_outputs))
        self._idle.clear()

    def submit_handoff(self, payload: HandoffPayload) -> None:
        """Deliver a prefill peer's KV payload for adoption (decode leg)."""
        self.inbox.put(("handoff", payload))
        self._idle.clear()

    def pending(self) -> int:
        return self.inbox.qsize()

    @property
    def idle(self) -> bool:
        """True when the inbox is drained and the engine has no work."""
        return self._idle.is_set()

    # -- lifecycle ------------------------------------------------------
    def start(self) -> None:
        self._thread.start()

    def stop(self, timeout: float = 10.0) -> None:
        self._stop.set()
        if self._thread.ident is not None:   # never-started threads can't join
            self._thread.join(timeout)

    def join_idle(self, timeout: float = 60.0) -> bool:
        """Block until the driver has drained all submitted work."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self._idle.is_set() and self.inbox.empty():
                return True
            time.sleep(1e-3)
        return False

    def kill(self) -> list[Request]:
        """Hard-stop the thread and return orphaned requests (plus any
        submissions still sitting in the inbox, never started)."""
        self.stop(timeout=120.0)     # a mid-step JIT compile can be slow
        orphans = self.engine.kill()
        while True:
            try:
                item = self.inbox.get_nowait()
            except queue.Empty:
                break
            orphans.append(item[1].req if item[0] == "handoff"
                           else item[1])
        return orphans

    # -- driver thread --------------------------------------------------
    def _on_token(self, req: Request, tok: int, first: bool,
                  last: bool) -> None:
        if req.rid in self._awaiting_first:
            self._awaiting_first.discard(req.rid)
            self._first_done.append(req.rid)
        self.sink(TokenEvent(req.rid, tok, req.generated,
                             time.monotonic(), first, last))

    def _run(self) -> None:
        try:
            self._loop()
        except Exception as e:
            # the thread's only way out besides stop(): record the error
            # and hand it to the sink so the failure reaches the caller
            logger.exception("engine driver %d died", self.iid)
            self.error = e
            self._idle.set()
            self.sink(DriverFailed(self.iid, e))

    def _admit(self) -> bool:
        """Move everything in the inbox into the engine; True if anything
        was there."""
        eng, drained = self.engine, False
        while True:
            try:
                item = self.inbox.get_nowait()
            except queue.Empty:
                return drained
            if item[0] == "handoff":
                payload = item[1]
                if eng.import_handoff(payload):
                    self.sink(HandoffAdopted(self.iid, payload))
                else:
                    self.sink(HandoffDropped(self.iid, payload))
            else:
                _, req, prompt, prior = item
                eng.add_request(req, prompt, prior_outputs=prior)
                self._awaiting_first.add(req.rid)
            drained = True

    def _loop(self) -> None:
        eng = self.engine
        while not self._stop.is_set():
            drained = False
            if not self.inbox.empty():
                with tracing.span("driver.admit"):
                    drained = self._admit()
            res = eng.step() if eng.alive else None
            for payload in eng.take_handoffs():
                payload.src_iid = self.iid
                self.sink(HandoffEvent(self.iid, payload))
            if res is None:
                if not drained and not eng.has_work():
                    self._idle.set()
                # park until new work or shutdown (also avoids a hot spin
                # when queued work is temporarily unschedulable)
                self._stop.wait(self.idle_wait)
                continue
            self._idle.clear()
            first_done, self._first_done = self._first_done, []
            self.sink(StepEvent(
                iid=self.iid, free_blocks=eng.bm.free_blocks,
                latency=res["latency"], est_time=res["plan"].est_time,
                prefill_done=tuple(first_done),
                finished=tuple(r.rid for r in res["finished"])))
            if not eng.has_work() and self.inbox.empty():
                self._idle.set()
