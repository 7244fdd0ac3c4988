"""Paged serving runtime: block-table KV cache + chunked-prefill batching.

vLLM-shaped but TPU/JAX-idiomatic, built on TWO fixed-shape jit programs
total (the per-bucket prefill family is gone):

* **Per-slot paged decode** — ONE ``decode_step`` dispatch advances every
  generating slot at its OWN position (``pos: [B]``), reading and writing
  K/V through each slot's block table over the shared physical pool.
  Inactive slots pass all-zero table rows: their writes land in the
  reserved null block and their outputs are discarded here.
* **Chunked prefill** — admission reserves a slot plus enough pool blocks
  for the whole request up front (prefill can never die mid-flight), then
  the prompt streams through ONE compiled ``prefill_chunk_step`` program in
  fixed ``[1, C]`` chunks with traced slot/offset/length scalars.  Cost is
  O(n/C) dispatches of a single program — no recompiles, no O(n) decode
  loop — and the scheduler interleaves chunks with decode steps so a long
  prompt cannot head-of-line-block running generations.

Prefix reuse (pure-attention archs): full prompt blocks register in the
pool's hash-chain cache; a later admission sharing a prefix acquires those
blocks instead of recomputing them and starts prefilling at the first
unmatched position.  Shared blocks are never written — copy-on-write at
the block boundary — and freed prefixes stay matchable on an LRU until the
allocator actually needs the space.

``serve`` runs the queue through the ChunkScheduler; the server OWNS
request timing (t_arrival / t_first_token / t_finish — see ``Request``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.configs.base import ATTN, MLA, RWKV, ModelConfig, ParallelConfig
from repro.models import model as M
from repro.models import serve as S
from repro.models.model import expanded_pattern
from repro.parallel.sharding import TPContext
from repro.runtime.kvpool import BlockTable, KVPool


@dataclasses.dataclass
class ServeConfig:
    max_batch: int = 8            # decode slots
    max_seq: int = 512
    eos_token: int = 1
    max_new_tokens: int = 64
    block_size: int = 16          # tokens per KV pool block (page)
    num_blocks: Optional[int] = None   # pool size; default guarantees
    #                                    max_batch full-length sequences
    prefill_chunk: int = 32       # chunked-prefill rows per dispatch
    prefix_reuse: bool = True     # hash-chain prefix cache (attention archs)
    # both programs also return the next-token logits; each request keeps
    # one fp32 [vocab] row per emitted token in ``Request.logits``
    keep_logits: bool = False


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # [S_prompt] int32
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    error: Optional[str] = None   # set when the server rejected the request
    # timing is OWNED by the serving runtime: t_arrival at submit (or the
    # traffic generator's scheduled arrival — TTFT then includes queueing),
    # t_first_token when the final prefill chunk emits token 0, t_finish
    # at completion.  perf_counter seconds.
    t_arrival: Optional[float] = None
    t_first_token: Optional[float] = None
    t_finish: Optional[float] = None
    # teacher forcing: decode step t consumes forced[t] in place of the
    # model's own token t, so ``output`` holds the model's predictions
    # along a given continuation (needs max_new_tokens - 1 entries)
    forced: Optional[Sequence[int]] = None
    logits: List[np.ndarray] = dataclasses.field(default_factory=list)

    def ttft_s(self) -> Optional[float]:
        if self.t_arrival is None or self.t_first_token is None:
            return None
        return self.t_first_token - self.t_arrival

    def per_token_s(self) -> Optional[float]:
        """Mean inter-token latency after the first token (TPOT)."""
        if self.t_first_token is None or self.t_finish is None:
            return None
        return ((self.t_finish - self.t_first_token)
                / max(1, len(self.output) - 1))


@dataclasses.dataclass
class PrefillJob:
    """An admitted request mid-prefill: ``off`` is the next unprefilled
    prompt position (reused prefix blocks are skipped entirely)."""
    req: Request
    slot: int
    table: BlockTable
    off: int


def _arch_supports_reuse(cfg: ModelConfig) -> bool:
    """Prefix blocks are reusable only when EVERY layer's sequence memory
    lives in the paged pool.  Recurrent families (Mamba SSM/conv, RWKV
    wkv/token-shift) fold history into dense states that are not
    block-addressable, so hybrids keep paging + eviction but skip the
    prefix cache."""
    return all(mk in (ATTN, MLA) and fk != RWKV
               for mk, fk in expanded_pattern(cfg))


class Server:
    def __init__(self, cfg: ModelConfig, par: ParallelConfig, mesh,
                 params, sc: ServeConfig):
        self.cfg = cfg
        self.par = par
        self.mesh = mesh
        self.sc = sc
        from repro.tuning import plan_set_from_parallel
        # paged serving is PER-REPLICA (slots fill from a local queue), so
        # the context carries no dp axes and every program spec is
        # model-axis only; both programs force the replicated activation
        # layout internally (decode: S=1; chunk prefill: bounded C).
        self.ctx = TPContext(axis="model", dp_axes=(),
                             ep_axes=M._ep_axes(cfg, par),
                             mode=par.overlap_mode,
                             plans=plan_set_from_parallel(par))
        params_eval = jax.eval_shape(
            lambda: M.init_model(jax.random.PRNGKey(0), cfg, par))
        self.pspecs = M.param_specs(cfg, par, params_eval)
        # placed on the mesh ONCE: an unplaced tree would be re-sharded
        # from its home device on every dispatch
        self.params = jax.device_put(params, self._shardings(self.pspecs))

        self.pages = -(-sc.max_seq // sc.block_size)   # table width
        nb = sc.num_blocks or (sc.max_batch * self.pages + 1)
        self.pool = KVPool(nb, sc.block_size)
        # what a dense [max_batch, max_seq] cache would pin, in blocks —
        # the paged footprint baseline for benchmarks/tests
        self.dense_equiv_blocks = sc.max_batch * self.pages
        cache_sds, self.cache_specs = S.paged_cache_specs(
            cfg, par, nb, sc.block_size, sc.max_batch)
        self.caches = jax.jit(
            lambda: jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                 cache_sds),
            out_shardings=self._shardings(self.cache_specs))()
        self.positions = np.zeros((sc.max_batch,), np.int32)
        self.slots: List[Optional[Request]] = [None] * sc.max_batch
        self.ready: List[bool] = [False] * sc.max_batch  # prefill complete
        self.tables: List[Optional[BlockTable]] = [None] * sc.max_batch
        self._decode = self._make_decode()
        self._chunk = self._make_chunk()
        self._reuse_ok = sc.prefix_reuse and _arch_supports_reuse(cfg)
        self.prefill_dispatches = 0                 # observability/tests
        self.decode_dispatches = 0

    def _shardings(self, specs):
        return jax.tree.map(lambda sp: NamedSharding(self.mesh, sp), specs,
                            is_leaf=lambda x: isinstance(x, P))

    def compile(self) -> Dict[str, float]:
        """Compile both programs ahead of the first request (later
        dispatches reuse them); returns each one's compile seconds."""
        b, pages, c = self.sc.max_batch, self.pages, self.sc.prefill_chunk
        i32 = jnp.int32
        scalar = jnp.zeros((), i32)
        args = {
            "decode": (self._decode, jnp.zeros((b, 1), i32),
                       jnp.zeros((b,), i32), jnp.zeros((b, pages), i32),
                       jnp.zeros((b,), bool)),
            "chunk": (self._chunk, jnp.zeros((1, c), i32),
                      jnp.zeros((1, pages), i32), scalar, scalar, scalar),
        }
        secs = {}
        for name, (fn, *rest) in args.items():
            t0 = time.perf_counter()
            fn.lower(self.params, self.caches, *rest).compile()
            secs[name] = time.perf_counter() - t0
        return secs

    def _out_specs(self):
        """Programs' outputs: next tokens, caches (+ vocab-sharded logits)."""
        logits = (P(None, "model"),) if self.sc.keep_logits else ()
        return (P(None, None), self.cache_specs) + logits

    def _make_decode(self):
        ctx, cfg, par = self.ctx, self.cfg, self.par
        keep = self.sc.keep_logits

        def fn(params, caches, tokens, pos, bt, active):
            return S.decode_step(params, caches, tokens, pos, ctx, cfg, par,
                                 block_tables=bt, active=active,
                                 with_logits=keep)

        sm = compat.shard_map(
            fn, mesh=self.mesh,
            in_specs=(self.pspecs, self.cache_specs, P(None, None), P(None),
                      P(None, None), P(None)),
            out_specs=self._out_specs(), check_vma=False)
        return jax.jit(sm, donate_argnums=(1,))

    def _make_chunk(self):
        """The ONE prefill program: tokens [1, C], table row [1, pages],
        traced int32 slot/off/chunk_len scalars — every prompt length and
        every slot runs the same compiled signature."""
        ctx, cfg, par = self.ctx, self.cfg, self.par
        keep = self.sc.keep_logits

        def fn(params, caches, tokens, bt, slot, off, chunk_len):
            return S.prefill_chunk_step(params, caches, tokens, bt, slot,
                                        off, chunk_len, ctx, cfg, par,
                                        with_logits=keep)

        sm = compat.shard_map(
            fn, mesh=self.mesh,
            in_specs=(self.pspecs, self.cache_specs, P(None, None),
                      P(None, None), P(), P(), P()),
            out_specs=self._out_specs(), check_vma=False)
        return jax.jit(sm, donate_argnums=(1,))

    # ------------------------------------------------------------ admission
    def _blocks_needed(self, n: int) -> int:
        """Blocks reserved at admission: the whole request horizon (prompt
        + generation, clipped to max_seq) so decode NEVER allocates — a
        running request cannot die to pool pressure mid-flight."""
        horizon = min(n + self.sc.max_new_tokens, self.sc.max_seq)
        return min(-(-horizon // self.sc.block_size), self.pages)

    def begin_admission(self, req: Request) -> Optional[PrefillJob]:
        """Reserve a slot + KV blocks for a request (no dispatch).  Returns
        None when no slot is free or the pool cannot cover the request;
        raises ValueError for prompts that can never be served.  On
        success the returned job's ``off`` skips any reused prefix."""
        slot = next((i for i, cur in enumerate(self.slots) if cur is None),
                    None)
        if slot is None:
            return None
        n = len(req.prompt)
        if not 0 < n < self.sc.max_seq:
            raise ValueError(f"prompt length {n} outside (0, "
                             f"{self.sc.max_seq}) for rid {req.rid}")
        if req.t_arrival is None:
            req.t_arrival = time.perf_counter()
        matched: List[int] = []
        n_cached = 0
        if self._reuse_ok:
            matched, n_cached = self.pool.match_prefix(req.prompt)
        need = self._blocks_needed(n) - len(matched)
        if not self.pool.can_allocate(need):
            self.pool.release(matched)       # registered -> back to the LRU
            return None
        blocks = matched + self.pool.allocate(need)
        self.pool.note_reuse(len(matched))
        table = BlockTable(blocks, n_reused=len(matched))
        self.slots[slot] = req
        self.ready[slot] = False
        self.positions[slot] = 0
        self.tables[slot] = table
        return PrefillJob(req=req, slot=slot, table=table, off=n_cached)

    def prefill_chunk(self, job: PrefillJob) -> bool:
        """Dispatch ONE fixed-shape prefill chunk.  Returns True when the
        prompt is fully prefilled (first token emitted, slot generating)."""
        req, slot = job.req, job.slot
        n = len(req.prompt)
        c = self.sc.prefill_chunk
        clen = min(c, n - job.off)
        toks = np.zeros((1, c), np.int32)
        toks[0, :clen] = req.prompt[job.off:job.off + clen]
        bt = job.table.as_array(self.pages)[None]
        nxt, self.caches, *logits = self._chunk(
            self.params, self.caches, jnp.asarray(toks), jnp.asarray(bt),
            jnp.asarray(slot, jnp.int32), jnp.asarray(job.off, jnp.int32),
            jnp.asarray(clen, jnp.int32))
        self.prefill_dispatches += 1
        job.off += clen
        if job.off < n:
            return False
        # final chunk: its row clen-1 is the prompt's last position
        self.positions[slot] = n
        self.ready[slot] = True
        req.output.append(int(np.asarray(nxt)[0, 0]))
        if logits:
            req.logits.append(np.asarray(logits[0])[0, :self.cfg.vocab_size])
        req.t_first_token = time.perf_counter()
        if self._reuse_ok:
            # now-immutable FULL prompt blocks become reusable by later
            # admissions (the trailing partial block keeps growing under
            # decode — never shared)
            self.pool.register(
                job.table.blocks[:n // self.sc.block_size], req.prompt)
        self._finish_if_done(slot)
        return True

    def admit(self, req: Request) -> bool:
        """Synchronous admission: reserve, then run every prefill chunk
        back-to-back (O(n/C) dispatches of the one chunk program).  The
        scheduler path (``serve``) interleaves chunks with decode instead.
        Returns False when no slot or insufficient pool blocks are free."""
        job = self.begin_admission(req)
        if job is None:
            return False
        while not self.prefill_chunk(job):
            pass
        return True

    # --------------------------------------------------------------- decode
    def _finish_if_done(self, i: int) -> Optional[Request]:
        req = self.slots[i]
        if req is None:
            return None
        if (req.output[-1] == self.sc.eos_token
                or len(req.output) >= self.sc.max_new_tokens
                or self.positions[i] >= self.sc.max_seq - 1):
            req.done = True
            req.t_finish = time.perf_counter()
            self.pool.release(self.tables[i].blocks)
            self.tables[i] = None
            self.ready[i] = False
            self.slots[i] = None
            self.positions[i] = 0
            return req
        return None

    def step(self) -> List[Request]:
        """One decode step for every GENERATING slot — each at its own
        position through its own block-table row.  Mid-prefill slots pass
        zero rows (attention writes land in the null block) and a False
        ``active`` flag (their dense Mamba/RWKV state rows — threaded
        across prefill chunks — stay frozen), and are skipped on
        readback."""
        if not any(self.ready):
            return []
        b = self.sc.max_batch
        toks = np.zeros((b, 1), np.int32)
        bts = np.zeros((b, self.pages), np.int32)
        active = np.zeros((b,), bool)
        for i, req in enumerate(self.slots):
            if req is not None and self.ready[i]:
                active[i] = True
                toks[i, 0] = (req.output[-1] if req.forced is None
                              else req.forced[len(req.output) - 1])
                bts[i] = self.tables[i].as_array(self.pages)
        nxt, self.caches, *logits = self._decode(
            self.params, self.caches, jnp.asarray(toks),
            jnp.asarray(self.positions), jnp.asarray(bts),
            jnp.asarray(active))
        self.decode_dispatches += 1
        nxt = np.asarray(nxt)
        logits = np.asarray(logits[0])[:, :self.cfg.vocab_size] if logits \
            else None
        finished: List[Request] = []
        for i, req in enumerate(self.slots):
            if req is None or not self.ready[i]:
                continue
            req.output.append(int(nxt[i, 0]))
            if logits is not None:
                req.logits.append(logits[i])
            self.positions[i] += 1
            fin = self._finish_if_done(i)
            if fin is not None:
                finished.append(fin)
        return finished

    def serve(self, requests: List[Request]) -> List[Request]:
        """Run a request queue to completion through the chunk scheduler.
        Completion is tracked by rid (each finished request drains exactly
        once)."""
        from repro.runtime.scheduler import ChunkScheduler
        sched = ChunkScheduler(self)
        for req in requests:
            sched.submit(req)
        done: List[Request] = []
        done_rids = set()
        while sched.has_work():
            for fin in sched.tick():
                if fin.rid not in done_rids:
                    done_rids.add(fin.rid)
                    done.append(fin)
        return done
