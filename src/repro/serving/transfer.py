"""Background host↔device KV transfer lanes (the §4.3 mechanisms, real).

The BlockManager models two serial copy lanes (D2H offload, H2D reload)
whose occupancy drives the adaptive copy budget.  This module is the
matching *mechanism*: a single worker thread that performs the actual
copies off the engine's critical path, so ``Engine.step()`` only enqueues
transfers and drains completions.

* **D2H offload ring** — the engine snapshots the blocks to mirror with
  one device-side gather (`PagedKVPool.gather_blocks`; functional jax
  arrays make the snapshot race-free — later pool writes build new
  arrays) and hands the worker the gathered array.  The worker performs
  the blocking ``jax.device_get`` and reports a completion carrying the
  host block contents, the block count and the measured copy time.

* **H2D reload staging (double-buffered)** — the engine hints which
  evicted requests are likely to reload next round; the worker stages
  their host blocks into a ready device array (``jnp.asarray``) so the
  reload lands before the batch that needs it.  At most ``max_staged``
  requests are staged at a time (classic double buffering).

Both lanes speak the TIERED wire format: a D2H job whose snapshot was
quantized on device carries an ``(int8 vals, fp32 scales)`` pair and
lands as per-block tuples (the pool routes them into the cold tier);
an H2D job whose host payloads are such tuples uploads the int8 data
(~4x fewer wire bytes) and dequantizes ON DEVICE (Pallas kernel) so the
staged buffer the engine consumes is always fp32.

Every job carries the request's transfer *epoch*; the engine bumps the
epoch on eviction/release so completions for a superseded residency
generation are discarded instead of corrupting the accounting.

The engine drains completions at step start and feeds them back into
``BlockManager.note_offload_complete`` / ``observe_transfer`` — the
accounting lanes then track real transfers instead of a virtual clock.
"""
from __future__ import annotations

import logging
import queue
import threading
import time
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import kv_block_dequantize

logger = logging.getLogger(__name__)


@dataclass(frozen=True)
class TransferDone:
    """One completed background copy, as drained by the engine."""
    kind: str                    # "d2h" (offload) | "h2d" (reload staging)
    rid: int
    epoch: int
    n_blocks: int
    seconds: float               # measured wall time of the copy
    blocks: Optional[dict] = None   # d2h only: {logical index -> ndarray}
    ok: bool = True              # False: the copy raised; nothing landed
    quantized: bool = False      # int8 wire: excluded from the t_block
    # EWMA (the copy budget already scales cold copies by COLD_WIRE_RATIO)


class TransferWorker:
    """One background thread owning both copy lanes of one engine."""

    def __init__(self, max_staged: int = 2,
                 device: Optional[jax.Device] = None):
        self.max_staged = max_staged
        # staged H2D buffers land on the engine's device (None: default)
        self.device = device
        self._jobs: "queue.Queue[Optional[tuple]]" = queue.Queue()
        self._lock = threading.Lock()
        self._done: list[TransferDone] = []
        # rid -> (epoch, n_blocks, (n, L, 2, Hkv, bs, hd) device array)
        self._staged: dict[int, tuple[int, int, object]] = {}
        # rids with a staging job enqueued but not yet landed: reserves the
        # slot so the engine's per-step hints don't enqueue duplicates
        self._inflight: set[int] = set()
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._warned = False

    # -- engine thread ----------------------------------------------------
    def _ensure_started(self) -> None:
        if self._stop.is_set():
            return
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._run, name="kv-transfer", daemon=True)
            self._thread.start()

    def offload(self, rid: int, epoch: int, logical: list[int],
                gathered) -> None:
        """Enqueue a D2H mirror: ``gathered`` is the (n, L, 2, Hkv, bs, hd)
        device-side snapshot of the blocks (already dispatched)."""
        self._ensure_started()
        self._jobs.put(("d2h", rid, epoch, logical, gathered))

    def prefetch(self, rid: int, epoch: int,
                 host_blocks: list[np.ndarray]) -> bool:
        """Enqueue H2D staging of ``host_blocks``; False if the staging
        ring is full or this rid is already staged/in flight."""
        with self._lock:
            if (rid in self._staged or rid in self._inflight
                    or len(self._staged) + len(self._inflight)
                    >= self.max_staged):
                return False
            self._inflight.add(rid)
        self._ensure_started()
        self._jobs.put(("h2d", rid, epoch, list(host_blocks)))
        return True

    def take_staged(self, rid: int, epoch: int):
        """Consume a staged reload buffer: (n_blocks, device array) or
        None if absent / stale-epoch."""
        with self._lock:
            got = self._staged.pop(rid, None)
        if got is None or got[0] != epoch:
            return None
        return got[1], got[2]

    def invalidate(self, rid: int) -> None:
        with self._lock:
            self._staged.pop(rid, None)

    def discard_stale(self, rid: int, current_epoch: int) -> None:
        """Drop a staged buffer whose epoch is no longer current — a
        staging job that completed AFTER ``invalidate`` would otherwise
        occupy one of the ``max_staged`` slots forever."""
        with self._lock:
            got = self._staged.get(rid)
            if got is not None and got[0] != current_epoch:
                del self._staged[rid]

    def drain(self) -> list[TransferDone]:
        with self._lock:
            out, self._done = self._done, []
        return out

    def flush(self, timeout: float = 30.0) -> bool:
        """Block until every enqueued job has executed (tests/benches).
        Uses the queue's unfinished-task count, so a job popped but still
        mid-execution keeps flush waiting."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self._jobs.unfinished_tasks == 0:
                return True
            time.sleep(1e-3)
        return False

    def stop(self, timeout: float = 10.0) -> None:
        self._stop.set()
        self._jobs.put(None)
        if self._thread is not None and self._thread.is_alive():
            self._thread.join(timeout)

    # -- worker thread ------------------------------------------------------
    def _run(self) -> None:
        while not self._stop.is_set():
            job = self._jobs.get()
            if job is None:
                self._jobs.task_done()
                break
            try:
                with jax.default_device(self.device):
                    self._execute(job)
            except Exception:
                # never kill the lane — the engine's synchronous fallback
                # stays correct — but never swallow silently either: report
                # a failed completion so pending-offload accounting drains
                # and the engine can count it.
                if not self._warned:
                    self._warned = True
                    logger.warning("background KV transfer failed; engine "
                                   "falls back to synchronous copies "
                                   "(further failures only counted)",
                                   exc_info=True)
                kind, rid, epoch = job[0], job[1], job[2]
                n = len(job[3])
                done = TransferDone(kind, rid, epoch, n, 0.0, ok=False)
                with self._lock:
                    self._inflight.discard(rid)
                    self._done.append(done)
            finally:
                self._jobs.task_done()

    def _execute(self, job: tuple) -> None:
        kind, rid, epoch = job[0], job[1], job[2]
        t0 = time.monotonic()
        if kind == "d2h":
            logical, gathered = job[3], job[4]
            if isinstance(gathered, tuple):
                # quantized-on-device snapshot: the wire carries int8 vals
                # + per-plane scales (~4x fewer bytes than fp32)
                vals, scales = jax.device_get(gathered)
                vals, scales = np.asarray(vals), np.asarray(scales)
                dt = time.monotonic() - t0
                blocks = {bi: (vals[i], scales[i])
                          for i, bi in enumerate(logical)}
                quant = True
            else:
                data = np.asarray(jax.device_get(gathered))
                dt = time.monotonic() - t0
                blocks = {bi: data[i] for i, bi in enumerate(logical)}
                quant = False
            done = TransferDone("d2h", rid, epoch, len(logical), dt,
                                blocks=blocks, quantized=quant)
            with self._lock:
                self._done.append(done)
        else:
            host_blocks = job[3]
            quant = any(isinstance(b, tuple) for b in host_blocks)
            if all(isinstance(b, tuple) for b in host_blocks):
                # cold-tier group: upload int8 + scales, dequantize on
                # device so the staged buffer is fp32 like any other
                vals = jnp.asarray(np.stack([b[0] for b in host_blocks]))
                scales = jnp.asarray(np.stack([b[1] for b in host_blocks]))
                arr = kv_block_dequantize(vals, scales)
            else:
                # whole-group tiering never mixes; thaw stray tuples
                # defensively so a mixed hint still stages correctly
                arr = jnp.asarray(np.stack(
                    [np.asarray(kv_block_dequantize(
                        jnp.asarray(b[0])[None], jnp.asarray(b[1])[None]))[0]
                     if isinstance(b, tuple) else b for b in host_blocks]))
            arr.block_until_ready()
            dt = time.monotonic() - t0
            done = TransferDone("h2d", rid, epoch, len(host_blocks), dt,
                                quantized=quant)
            with self._lock:
                self._inflight.discard(rid)
                self._staged[rid] = (epoch, len(host_blocks), arr)
                self._done.append(done)
