"""Drive the paged ``Server`` through its ``ChunkScheduler`` with a mix.

The benchmark wraps the calls it makes into each layer with spans of its
own (``bench.tick``, ``bench.admit``, ``bench.prefill_chunk``,
``bench.decode_step``) and records every dispatch on the host clock with
the work it carries: a decode step's slot positions, a chunk's offset and
true length.  The spans go into the profiler's trace only in a traced run.

Request times are the benchmark's own stamps: the scheduled arrival, the
return of the call that emitted a request's first token (its final prefill
chunk) and of each call that emitted a later one (a decode step).  The
program's own ``Request`` timestamps are not read.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from pbench import traffic as T


@dataclass
class Call:
    kind: str            # "decode" | "chunk"
    t0: float
    t1: float
    info: tuple          # decode: slot positions; chunk: (off, len, final)
    tokens: int          # output tokens this call emitted


@dataclass
class Recorder:
    tracing: bool = False
    calls: List[Call] = field(default_factory=list)
    arrival: Dict[int, float] = field(default_factory=dict)
    first: Dict[int, float] = field(default_factory=dict)
    last: Dict[int, float] = field(default_factory=dict)
    emitted: Dict[int, int] = field(default_factory=dict)

    def emit(self, rid: int, t: float) -> None:
        """Request ``rid`` got an output token from a call that returned
        at ``t``."""
        self.first.setdefault(rid, t)
        self.last[rid] = t
        self.emitted[rid] = self.emitted.get(rid, 0) + 1

    def ttft_s(self, rid: int) -> float:
        return self.first[rid] - self.arrival[rid]

    def per_token_s(self, rid: int) -> float:
        """Mean gap between a request's output tokens after its first."""
        return (self.last[rid] - self.first[rid]) / max(
            1, self.emitted[rid] - 1)

    def span(self, name: str):
        if not self.tracing:
            return nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def wrap(self, server) -> None:
        """Replace the server's entry points, on this instance only, with
        recording wrappers around the originals."""
        step, chunk, admit = (server.step, server.prefill_chunk,
                              server.begin_admission)

        def decode_step():
            live = [(int(server.positions[i]), r)
                    for i, r in enumerate(server.slots)
                    if r is not None and server.ready[i]]
            if not live:                # nothing generating: no dispatch
                return step()
            t0 = time.perf_counter()
            with self.span("bench.decode_step"):
                out = step()
            t1 = time.perf_counter()
            for _, r in live:
                self.emit(r.rid, t1)
            self.calls.append(Call("decode", t0, t1,
                                   tuple(p for p, _ in live), len(live)))
            return out

        def prefill_chunk(job):
            n, off = len(job.req.prompt), job.off
            length = min(server.sc.prefill_chunk, n - off)
            t0 = time.perf_counter()
            with self.span("bench.prefill_chunk"):
                final = chunk(job)
            t1 = time.perf_counter()
            if final:
                self.emit(job.req.rid, t1)
            self.calls.append(Call("chunk", t0, t1,
                                   (off, length, bool(final)), int(final)))
            return final

        def begin_admission(req):
            with self.span("bench.admit"):
                return admit(req)

        server.step = decode_step
        server.prefill_chunk = prefill_chunk
        server.begin_admission = begin_admission


def model_config(cell):
    """The program's ModelConfig for this configuration file, checked
    against the sizes the file states."""
    from repro.configs.base import get_config
    prog = cell.config["program"]
    cfg = dataclasses.replace(get_config(prog["arch"]),
                              **prog.get("overrides", {}))
    m = cell.config["model"]
    want = {"num_layers": m["num_hidden_layers"], "d_model": m["hidden_size"],
            "num_heads": m["num_attention_heads"],
            "num_kv_heads": m["num_key_value_heads"],
            "d_ff": m["intermediate_size"], "vocab_size": m["vocab_size"],
            "resolved_head_dim": m.get("head_dim") or
            m["hidden_size"] // m["num_attention_heads"],
            "norm_eps": m["rms_norm_eps"], "rope_theta": m["rope_theta"],
            "tie_embeddings": m["tie_word_embeddings"], "rope_style": "rope",
            "pattern": (("attn", "ffn"),), "moe": None, "qkv_bias": False}
    got = {k: getattr(cfg, k) for k in want}
    bad = {k: (got[k], v) for k, v in want.items() if got[k] != v}
    if bad:
        raise ValueError(f"the program's {prog['arch']} differs from "
                         f"{cell.config['name']}.json: {bad}")
    return cfg


def init_params(cfg, par, mesh, seed: int):
    """Random weights from ``seed``, made on the device in one jitted call
    with the mesh's shardings."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.models import model as M
    init = functools.partial(M.init_model, cfg=cfg, par=par)
    key = jax.random.PRNGKey(seed)
    specs = M.param_specs(cfg, par, jax.eval_shape(init, key))
    shardings = jax.tree.map(lambda sp: NamedSharding(mesh, sp), specs,
                             is_leaf=lambda x: isinstance(x, P))
    return jax.block_until_ready(jax.jit(init, out_shardings=shardings)(key))


def build_server(cell, seed: int):
    from repro.configs.base import ParallelConfig
    from repro.launch.mesh import make_mesh
    cfg = model_config(cell)
    mesh = make_mesh(1, 1, cell.chips)
    par = ParallelConfig(tp=cell.chips, dp=1)
    return _warm_server(cell, cfg, par, mesh,
                        init_params(cfg, par, mesh, seed))


def _warm_server(cell, cfg, par, mesh, params):
    import jax

    from repro.runtime.server import Request, ServeConfig, Server
    sc = ServeConfig(**cell.config["server"])
    server = Server(cfg, par, mesh, params, sc)
    # warm both programs with the window's argument types: a two-chunk
    # prompt and one decode step
    keep, sc.max_new_tokens = sc.max_new_tokens, 2
    warm = np.arange(sc.prefill_chunk + 1, dtype=np.int32) % cfg.vocab_size
    server.serve([Request(rid=-1, prompt=warm)])
    sc.max_new_tokens = keep
    jax.block_until_ready(server.caches)
    return server


def fresh(cell, server):
    """An empty, warmed server on the same weights; the old one's cache
    pool is dropped first."""
    params = server.params
    del server.caches
    gc.collect()
    return _warm_server(cell, server.cfg, server.par, server.mesh, params)


def _sync(server) -> None:
    import jax
    jax.block_until_ready(server.caches)


class Tracer:
    """Profiler over a sub-window of the run: started and stopped between
    ticks, with the device drained, so the traced programs are whole."""

    def __init__(self, rec, sync, t_start: float, t_stop: float,
                 trace_dir: Optional[str]):
        """``rec`` has ``tracing``, set while the profiler runs; ``sync()``
        waits for the device to drain."""
        self.rec, self.sync = rec, sync
        self.t_start, self.t_stop, self.dir = t_start, t_stop, trace_dir
        self.state = "idle" if trace_dir else "off"
        self.span = None
        self.t0 = self.t1 = None

    def poll(self, now: float) -> None:
        import jax
        if self.state == "idle" and now >= self.t_start:
            self.sync()
            jax.profiler.start_trace(self.dir)
            self.span = jax.profiler.TraceAnnotation("bench.window")
            self.span.__enter__()
            self.t0 = time.perf_counter()
            self.rec.tracing = True
            self.state = "on"
        elif self.state == "on" and now >= self.t_stop:
            self.stop()

    def stop(self) -> None:
        import jax
        if self.state != "on":
            return
        self.sync()
        self.t1 = time.perf_counter()
        self.rec.tracing = False
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"


@dataclass
class Outcome:
    window: tuple                 # (start, end) perf_counter seconds
    counted: List                 # Requests counted (arrived in the window)
    finished: List                # counted Requests that finished
    tokens: int                   # output tokens emitted in the window
    tracer: Tracer
    failed: int = 0               # due but never served, or rejected
    lateness_s: float = 0.0       # worst delay of a submission
    compiles: int = 0


def _compile_counter():
    import jax
    box = [0]

    def listen(event, *_a, **_k):
        if event == "/jax/core/compile/backend_compile_duration":
            box[0] += 1
    jax.monitoring.register_event_duration_secs_listener(listen)
    return box


def run_open(server, rec: Recorder, mix: Dict, seed: int, seconds: float,
             trace_dir: Optional[str] = None, trace_s: float = 0.0
             ) -> Outcome:
    """Arrivals on the mix's schedule; the run ends when every request that
    arrived in the window has finished, or ``drain_cap_s`` after it."""
    from repro.runtime.scheduler import ChunkScheduler
    from repro.runtime.server import Request
    arrivals = T.open_loop(mix, seed, seconds, server.cfg.vocab_size)
    sched = ChunkScheduler(server)
    base = time.perf_counter() + mix["lead_s"]
    ws, we = base, base + seconds
    mid = ws + max(0.0, (seconds - trace_s) / 2)
    tracer = Tracer(rec, lambda: _sync(server), mid, mid + trace_s,
                    trace_dir)
    compiles = _compile_counter()
    counted: List[Request] = []
    n_counted = sum(a.counted for a in arrivals)
    late, i, n0 = 0.0, 0, None
    while True:
        now = time.perf_counter()
        if n0 is None and now >= ws:
            n0 = compiles[0]
        while i < len(arrivals) and base + arrivals[i].offset <= now:
            a = arrivals[i]
            req = Request(rid=a.rid, prompt=a.tokens,
                          t_arrival=base + a.offset)
            rec.arrival[a.rid] = base + a.offset
            late = max(late, now - rec.arrival[a.rid])
            sched.submit(req)
            if a.counted:
                counted.append(req)
            i += 1
        tracer.poll(now)
        if now >= we and len(counted) == n_counted and all(
                r.done for r in counted):
            break
        if now >= we + mix["drain_cap_s"]:
            break
        if not sched.has_work():
            if i < len(arrivals):
                time.sleep(max(0.0, min(base + arrivals[i].offset - now,
                                        0.005)))
            continue
        with rec.span("bench.tick"):
            sched.tick()
    tracer.stop()
    _sync(server)
    tokens = sum(c.tokens for c in rec.calls if ws <= c.t1 < we)
    finished = [r for r in counted if r.done and r.error is None]
    return Outcome((ws, we), counted, finished, tokens, tracer,
                   len(counted) - len(finished), late,
                   compiles[0] - (n0 or 0))


def run_closed(server, rec: Recorder, mix: Dict, seed: int, seconds: float,
               trace_dir: Optional[str] = None, trace_s: float = 0.0
               ) -> Outcome:
    """``clients`` sessions, each sending its next request when the last
    one returns; the run ends with the window."""
    from repro.runtime.scheduler import ChunkScheduler
    from repro.runtime.server import Request
    queue = T.closed_loop(mix, seed, server.cfg.vocab_size)
    sched = ChunkScheduler(server)
    start = time.perf_counter()
    ws = start + mix["lead_s"]
    we = ws + seconds
    mid = ws + max(0.0, (seconds - trace_s) / 2)
    tracer = Tracer(rec, lambda: _sync(server), mid, mid + trace_s,
                    trace_dir)
    compiles = _compile_counter()
    live: List[Request] = []
    counted: List[Request] = []
    nxt, n0 = 0, None

    def submit(now):
        nonlocal nxt
        if nxt >= len(queue):
            raise RuntimeError(f"closed loop ran out of its {len(queue)} "
                               f"requests; raise 'requests' in the mix")
        a = queue[nxt]
        nxt += 1
        req = Request(rid=a.rid, prompt=a.tokens, t_arrival=now)
        rec.arrival[a.rid] = now
        sched.submit(req)
        live.append(req)
        if ws <= now < we:
            counted.append(req)

    for _ in range(int(mix["clients"])):
        submit(time.perf_counter())
    while True:
        now = time.perf_counter()
        if n0 is None and now >= ws:
            n0 = compiles[0]
        tracer.poll(now)
        if now >= we:
            break
        with rec.span("bench.tick"):
            sched.tick()
        for r in [r for r in live if r.done]:
            live.remove(r)
            submit(time.perf_counter())
    tracer.stop()
    _sync(server)
    tokens = sum(c.tokens for c in rec.calls if ws <= c.t1 < we)
    finished = [r for r in counted if r.done and r.error is None]
    rejected = sum(1 for r in counted if r.error is not None)
    return Outcome((ws, we), counted, finished, tokens, tracer, rejected,
                   0.0, compiles[0] - (n0 or 0))


def run(server, rec: Recorder, mix: Dict, seed: int, seconds: float,
        trace_dir: Optional[str] = None, trace_s: float = 0.0) -> Outcome:
    loops = {"open": run_open, "closed": run_closed}
    if mix["loop"] not in loops:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    return loops[mix["loop"]](server, rec, mix, seed, seconds, trace_dir,
                              trace_s)


def end_to_end(out: Outcome, rec: Recorder, seconds: float
               ) -> Dict[str, float]:
    """Every end-to-end number a serving run can give, from the
    benchmark's own stamps; the cell reports those its manifest entry
    names."""
    ttft = [rec.ttft_s(r.rid) for r in out.finished]
    tpot = [rec.per_token_s(r.rid) for r in out.finished]
    return {"ttft_p90_ms": 1e3 * T.percentile(ttft, 90),
            "tpot_p90_ms": 1e3 * T.percentile(tpot, 90),
            "output_tok_s": out.tokens / seconds,
            "_ttft_p50_ms": 1e3 * T.percentile(ttft, 50),
            "_tpot_p50_ms": 1e3 * T.percentile(tpot, 50),
            "_samples": len(ttft)}


def free(server) -> None:
    """Drop the server's device state so the reference has the chip."""
    del server.params, server.caches
    gc.collect()
