"""Cells of LCX messages between AMT ranks, one rank per chip, in a ring.

Each round every rank posts ``lanes`` independent 8-byte ``put_x`` to
its ring successor, one tag per lane, each with its own
``Synchronizer``, and then one ``progress_x``: the ``lcx`` design of
``benchmarks/pingpong.py`` (the paper's multithreaded ping-pong, Fig. 1)
without aggregation.  A round is one call of the jitted ``shard_map``
program; round r+1 sends what round r received, plus one, so rounds
are issued back to back and each depends on the one before.  The host
waits on the round ``in_flight`` rounds back, which bounds the queue
without draining it.

``correct``: after the window, every payload of a sample of rounds drawn
from the seed, and of the last round, is compared with a numpy ring roll
of the seed's first payloads: after r rounds rank i holds rank
(i - r) mod n's first payload plus r.  The limit is 0 wrong payloads.
"""
from __future__ import annotations

import collections
from typing import Any, Dict

import numpy as np

from bench import harness
from bench.harness import Run, now, span

MSG_WORDS = 2            # 8-byte messages: two int32 words


def ring_body(lanes: int):
    """Per-rank body: ``lanes`` payloads [1, lanes, MSG_WORDS] to the ring
    successor; returns what arrived, plus one."""
    import jax.numpy as jnp
    import repro.core as lcx

    def ring_round(x):
        lcx.init()
        pool = lcx.PacketPool(packet_size=1 << 16, aggregate=False)
        dev = lcx.Device(axis="x")
        peer = lcx.Perm.shift(1)
        x = x[0]
        syncs = [lcx.Synchronizer(threshold=1) for _ in range(lanes)]
        for i in range(lanes):
            lcx.put_x(x[i]).tag(i).perm(peer) \
                .remote_comp(syncs[i]).device(dev)()
        lcx.progress_x().pool(pool)()
        got = jnp.stack([s.wait()[0].payload for s in syncs])
        return (got + 1)[None]

    return ring_round


def ring_program(devs, lanes: int):
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.compat import shard_map
    mesh = jax.sharding.Mesh(np.asarray(devs), ("x",))
    fn = jax.jit(shard_map(ring_body(lanes), mesh,
                           in_specs=P("x", None, None),
                           out_specs=P("x", None, None)))
    sharding = jax.sharding.NamedSharding(mesh, P("x", None, None))
    return fn, sharding


def expected(x0: np.ndarray, r: int) -> np.ndarray:
    """Payloads after ``r`` rounds."""
    return np.roll(x0, r, axis=0) + r


def run_cell(cell: harness.Cell, seed: int, seconds: float, trace: bool,
             devs, limits: Dict[str, float], tag: str, t_start: float,
             compiles: harness.CompileLog) -> harness.Outcome:
    import jax
    mix = cell.mix
    n, lanes, depth = len(devs), mix["lanes"], mix["in_flight"]
    if cell.config["ranks"] != n:
        raise ValueError(f"{cell.config['ranks']} ranks on {n} chips")
    run = Run(cell.name, cell.config, mix, devs[0].device_kind, n)
    run.messages_per_round = n * lanes
    rng = np.random.default_rng(seed)
    x0 = rng.integers(0, 1 << 20, size=(n, lanes, MSG_WORDS),
                      dtype=np.int32)
    fn, sharding = ring_program(devs, lanes)
    x = jax.device_put(x0, sharding)
    jax.block_until_ready(fn(x))          # compile (or load) and run once
    # the rounds whose payloads are checked, drawn from the seed
    check_every = rng.integers(1, mix["check_every"] + 1)
    harness.log(tag, f"{n} ranks x {lanes} lanes of {4 * MSG_WORDS}-byte "
                     f"put_x a round, {depth} rounds in flight")

    before = compiles.compiles
    tracer = harness.Tracer(trace, mix["trace_lead_s"], mix["trace_s"])
    kept: Dict[int, Any] = {}
    recent: collections.deque = collections.deque()
    t0 = now()
    end = t0 + seconds
    run.window_t0 = t0
    r = 0
    while now() < end:
        tracer.poll(now() - t0)
        with span("round"):
            x = fn(x)
        r += 1
        if r % check_every == 0:
            kept[r] = x
        recent.append(x)
        if len(recent) > depth:
            with span("sync"):
                recent.popleft().block_until_ready()
    jax.block_until_ready(x)
    run.window_t1 = now()
    tracer.stop()
    kept[r] = x
    run.rounds = r
    run.setup_s = t0 - t_start
    harness.log(tag, f"compiles inside the window: "
                     f"{compiles.compiles - before}")
    harness.log(tag, f"window {run.window_s:.6f}s: {r} rounds, "
                     f"{r * n * lanes} messages")
    peak = harness.memory_peak(devs)
    run.trace = tracer.read()

    wrong = 0
    for k, got in kept.items():
        got = np.asarray(got)
        wrong += int((got != expected(x0, k)).any(axis=-1).sum())
    harness.log(tag, f"checked {len(kept)} rounds, "
                     f"{len(kept) * n * lanes} payloads")
    checks = {"wrong_payloads": {"value": wrong,
                                 "limit": limits["wrong_payloads"]}}
    return harness.Outcome(run, checks, r * n * lanes, wrong, peak)

