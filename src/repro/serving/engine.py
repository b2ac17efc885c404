"""Serving engine: slot-based KV cache with continuous batching.

The engine owns a fixed pool of ``n_slots`` sequences sharing one
pre-allocated cache (`repro.models.init_cache`).  New requests prefill
into free slots; every decode tick advances *all* slots with one
compiled ``decode_step`` (single-token, full-batch, a length per slot;
the decode_* cells of the benchmark matrix lower its scalar-length
form).

Hardware note: prefill and decode are separate jit programs (different
shapes); the decode program is cache-resident and memory-bound — its
roofline terms come from the dry-run of ``serve_step``.

Per-slot state (lengths, completion) is host-side; the device-side
decode uses per-slot length masks so slots at different positions can
coexist in one batch (continuous batching), and writes each slot's new
KV rows in place into the donated cache.

Scheduling: each ``tick`` is driven through an AMT executor
(`repro.amt.Executor`) — one admission task per queued request
(priority = arrival order) and one decode task depending on all of
them, so prefill admission and decode advancement are ordinary tasks a
larger task graph can compose with.  ``use_executor=False`` keeps the
inline loop.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.profiler import TraceAnnotation

from repro.models import decode_step, init_cache, prefill
from repro.models.model import cache_batch_axes

PyTree = Any


@dataclasses.dataclass
class ServeConfig:
    n_slots: int = 8
    max_seq: int = 512
    temperature: float = 0.0          # 0 = greedy
    eos_token: Optional[int] = None
    max_new_tokens: int = 64
    seed: int = 0


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # [P] int32
    max_new_tokens: Optional[int] = None
    # filled by the engine:
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    error: Optional[str] = None        # set when the request was evicted
    submitted_at: float = 0.0


def sample_token(logits: jax.Array, temperature: float,
                 key: jax.Array) -> jax.Array:
    """logits [B, V] -> tokens [B]."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature,
                                  axis=-1).astype(jnp.int32)


def make_decode_fn(cfg: Any, kernels: Optional[Dict[str, Any]] = None):
    """Decode of every slot as one batch: tokens [B,1], lengths [B],
    each slot at its own fill (the model's per-row-length path).  The
    inner function's name names the program in a profiler trace
    (``jit_decode_slots``)."""

    def decode_slots(params: PyTree, tokens: jax.Array, caches: PyTree,
                     lengths: jax.Array) -> Tuple[jax.Array, PyTree]:
        return decode_step(cfg, params, tokens, caches, lengths,
                           kernels=kernels)

    return decode_slots


class ServingEngine:
    def __init__(self, cfg: Any, params: PyTree, scfg: ServeConfig,
                 kernels: Optional[Dict[str, Any]] = None, *,
                 use_executor: bool = True,
                 lcx_runtime: Optional[Any] = None,
                 lcx_device: Optional[Any] = None,
                 failover: bool = False,
                 heartbeat: Optional[Any] = None) -> None:
        self.cfg = cfg
        self.params = params
        self.scfg = scfg
        self.kernels = kernels
        self.heartbeat: Optional[Any] = heartbeat
        self.standby_device: Optional[Any] = None
        if use_executor:
            import repro.core as lcx
            from repro.amt import Executor
            # Library-interop pattern (docs/resources.md): the engine owns
            # a private LCX runtime unless the application injects one, so
            # its admission traffic never mixes with — or depends on — the
            # process-global default runtime.
            if lcx_runtime is None and lcx_device is not None:
                lcx_runtime = lcx_device.runtime
            if lcx_runtime is None:
                lcx_runtime = lcx.Runtime(name="serving")
            self.lcx_runtime: Optional[Any] = lcx_runtime
            self._executor: Optional[Executor] = Executor(
                name="serving", runtime=lcx_runtime, device=lcx_device)
            if failover or heartbeat is not None:
                from repro.runtime.fault import HeartbeatMonitor
                # Warm standby on the serving device's axis: if the
                # heartbeat declares the primary dead mid-stream, its
                # endpoints and in-flight admission traffic migrate here
                # and the executor re-dispatches the affected tasks.
                primary = self._executor.device
                self.standby_device = lcx_runtime.device(axis=primary.axis)
                if self.heartbeat is None:
                    self.heartbeat = HeartbeatMonitor(on_dead="failover")
                self.heartbeat.attach(lcx_runtime)
        else:
            self.lcx_runtime = lcx_runtime
            self._executor = None
        self.caches = init_cache(cfg, scfg.n_slots, scfg.max_seq)
        self.lengths = np.zeros((scfg.n_slots,), np.int32)
        self.slot_req: List[Optional[Request]] = [None] * scfg.n_slots
        self.queue: List[Request] = []
        self.finished: List[Request] = []
        self.failed: List[Request] = []
        self._key = jax.random.PRNGKey(scfg.seed)
        # the cache is donated: the decode writes its new rows in place,
        # and nothing else holds ``self.caches`` across the call
        self._decode = jax.jit(make_decode_fn(cfg, kernels),
                               donate_argnums=(2,))
        self._prefill_cache: Dict[int, Any] = {}
        self.stats = {"ticks": 0, "prefills": 0, "decoded_tokens": 0,
                      "evictions": 0}

    # -- request intake ------------------------------------------------------
    def submit(self, req: Request) -> None:
        req.submitted_at = time.perf_counter()
        self.queue.append(req)

    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def prefill_program(self, plen: int):
        """The jitted prefill of one slot for prompts of length ``plen``:
        (params, tokens [plen], slot cache) -> (last logits [V], cache)."""
        if plen not in self._prefill_cache:
            cfg, kernels = self.cfg, self.kernels

            def prefill_slot(params, toks, cache):
                axes = cache_batch_axes(cfg, cache)
                cache_b = jax.tree.map(jnp.expand_dims, cache, axes)
                lg, nc = prefill(cfg, params, toks[None], cache_b,
                                 kernels=kernels)
                nc = jax.tree.map(lambda t, a: jnp.squeeze(t, a), nc, axes)
                return lg[0, -1], nc

            self._prefill_cache[plen] = jax.jit(prefill_slot)
        return self._prefill_cache[plen]

    def _admit(self) -> None:
        while self._free_slots() and self.queue:
            req = self.queue.pop(0)
            self._admit_one(req)

    def _evict(self, req: Request, reason: str) -> None:
        """Terminally fail ``req`` without touching slot state: the tick
        loop keeps serving the other slots instead of wedging."""
        req.done = True
        req.error = reason
        self.finished.append(req)
        self.failed.append(req)
        self.stats["evictions"] += 1

    def _admit_one(self, req: Request) -> bool:
        """Prefill ``req`` into a free slot.  Returns False when no slot
        is free (caller re-queues); True when the request was placed or
        terminally handled (including eviction on prefill failure)."""
        free = self._free_slots()
        if not free:
            return False
        slot = free[0]
        plen = len(req.prompt)
        with TraceAnnotation("serve.admit", rid=req.rid, plen=plen,
                             slot=slot):
            if plen >= self.scfg.max_seq:
                self._evict(req, f"prompt length {plen} >= max_seq "
                                 f"{self.scfg.max_seq}")
                return True
            axes = cache_batch_axes(self.cfg, self.caches)
            try:
                with TraceAnnotation("serve.prefill", plen=plen):
                    toks = jnp.asarray(req.prompt, jnp.int32)
                    slot_cache = jax.tree.map(
                        lambda t, a: jnp.take(t, slot, axis=a), self.caches,
                        axes)
                    # exact-length prefill: one compiled program per distinct
                    # prompt length (bucketing would corrupt SSM prefill state
                    # — the recurrent state cannot mask padding the way KV
                    # rows can)
                    lg, new_cache = self.prefill_program(plen)(
                        self.params, toks, slot_cache)
            except Exception as e:
                # the shared cache was not written yet — evict the request
                # and leave the slot free for the next one
                self._evict(req, f"prefill failed: {type(e).__name__}: {e}")
                return True
            with TraceAnnotation("serve.slot_write", slot=slot):
                self.caches = jax.tree.map(
                    lambda buf, nc, a: jax.lax.dynamic_update_slice_in_dim(
                        buf, jnp.expand_dims(nc, a).astype(buf.dtype),
                        slot, axis=a),
                    self.caches, new_cache, axes)
            self.lengths[slot] = plen
            self.slot_req[slot] = req
            self.stats["prefills"] += 1
            # sample the first generated token from the prefill logits
            self._key, sub = jax.random.split(self._key)
            with TraceAnnotation("serve.sync", what="first"):
                tok = int(np.asarray(sample_token(
                    lg[None], self.scfg.temperature, sub))[0])
            req.output.append(tok)
            self.stats["decoded_tokens"] += 1
            # the first token may already terminate the request
            limit = req.max_new_tokens or self.scfg.max_new_tokens
            if (self.scfg.eos_token is not None
                    and tok == self.scfg.eos_token) \
                    or len(req.output) >= limit:
                req.done = True
                self.finished.append(req)
                self.slot_req[slot] = None
                self.lengths[slot] = 0
            return True

    # -- decode tick ----------------------------------------------------------
    def tick(self) -> int:
        """Admit + one decode step for all active slots.  Returns the
        number of live slots advanced.

        With an executor, admission and decode run as a per-tick task
        graph: one prefill-admission task per queued request (priority
        keeps arrival order) feeding one decode task."""
        with TraceAnnotation("serve.tick", queued=len(self.queue)):
            if self._executor is not None:
                return self._tick_executor()
            self._admit()
            return self._decode_tick()

    def _tick_executor(self) -> int:
        ex = self._executor
        queued, self.queue = list(self.queue), []
        admissions = []
        for k, req in enumerate(queued):
            def admit(ctx, _req=req):
                if not self._admit_one(_req):
                    self.queue.append(_req)   # no free slot: re-queue

            admissions.append(ex.spawn(
                admit, priority=len(queued) - k,
                name=f"prefill:{req.rid}"))
        decode = ex.spawn(lambda ctx: self._decode_tick(),
                          deps=tuple(admissions), priority=-1,
                          name="decode")
        ex.run()
        return decode.result

    def _decode_tick(self) -> int:
        active = [i for i, r in enumerate(self.slot_req) if r is not None]
        if not active:
            return 0
        with TraceAnnotation("serve.decode", active=len(active)):
            tokens = np.zeros((self.scfg.n_slots, 1), np.int32)
            for i in active:
                req = self.slot_req[i]
                tokens[i, 0] = req.output[-1] if req.output \
                    else req.prompt[-1]
            lengths = jnp.asarray(self.lengths)
            lg, self.caches = self._decode(self.params, jnp.asarray(tokens),
                                           self.caches, lengths)
            self._key, sub = jax.random.split(self._key)
            with TraceAnnotation("serve.sync", what="decode"):
                nxt = np.asarray(sample_token(lg[:, 0],
                                              self.scfg.temperature, sub))
            self.stats["ticks"] += 1
            for i in active:
                req = self.slot_req[i]
                self.lengths[i] += 1
                tok = int(nxt[i])
                req.output.append(tok)
                self.stats["decoded_tokens"] += 1
                limit = req.max_new_tokens or self.scfg.max_new_tokens
                if (self.scfg.eos_token is not None
                        and tok == self.scfg.eos_token) \
                        or len(req.output) >= limit \
                        or self.lengths[i] >= self.scfg.max_seq - 1:
                    req.done = True
                    self.finished.append(req)
                    self.slot_req[i] = None
                    self.lengths[i] = 0
            return len(active)

    def run_until_drained(self, max_ticks: int = 10000) -> List[Request]:
        for _ in range(max_ticks):
            if not self.queue and all(r is None for r in self.slot_req):
                break
            self.tick()
        return self.finished
