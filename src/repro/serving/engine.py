"""The serving engine: continuous batching over a paged KV pool.

One ``Engine`` owns a fixed pool of decode slots, a paged KV cache, and a
scheduler.  ``step()`` advances the whole pool by one tick:

    1. **admission** — the queue head is admitted the moment a slot and its
       prompt's KV blocks are both free (FCFS);
    2. **chunked prefill** — the admitted prompt runs through the existing
       contiguous ``forward`` in fixed-size chunks (one compiled prefill
       shape), then a jitted scatter imports its K/V into the slot's pool
       blocks — long prompts never stall running decodes for more than one
       chunk;
    3. **decode** — ONE compiled step serves every running slot (static
       shapes; free slots compute into the null block and are ignored), each
       row sampled on the device with its request's own params and seeded
       stream; only the (B,) tokens come back to the host.

Because every slot attends only to its own blocks with its own positions,
rows are independent: a greedy request's output is bit-identical whether it
runs alone or packed with arbitrary batch-mates — the property
``tests/test_serving.py`` pins down.

At intake the engine keeps its weights as the programs read them: each leaf
read only in the compute dtype is rounded to it once
(``models.transformer.serving_params``), so a step reads half the bytes of
float32 storage and serves bit-identical logits.  ``last_stats`` carries
``intake_cast_bytes`` (storage rounded) and ``served_weight_bytes`` (what
the engine holds), and the ``engine.intake`` span times it.

Each tick leaves host spans in ``serving.telemetry`` (``engine.step`` and,
under it, admit / prefill / import / decode / fetch / sample), so the time
the host spends blocked on the device programs and on the token draw can be
read apart (docs/serving.md).

Under memory pressure (``ensure`` fails mid-decode) the scheduler's LIFO
victim is evicted: blocks freed, request re-queued at the front carrying its
generated tokens (re-prefilled on re-admission).

**Fail-safe serving** (``EngineConfig.verify``; docs/reliability.md): each
tick screens every request's logits row for nonfinite values — the signature
of corrupted KV blocks or a tripped verified matmul.  A faulted request is
retried (evicted so re-prefill rebuilds clean KV, with tick backoff), then
degraded to an ``xla``-compiled decode step, then failed — while its
batch-mates keep streaming untouched.  Requests may carry deadlines
(``ttl_s``); expired ones are swept each tick.  Counters
(``faults_detected`` / ``retries`` / ``deadline_evictions`` /
``degraded_requests``) surface in ``last_stats``, as do an MoE model's
routing counts (``moe_routed`` / ``moe_rows`` / ``moe_dropped``), which its
programs return and the engine reads at each fetch.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.models import transformer as tf_model
from repro.serving import kv_cache as kvc
from repro.serving import sampling, telemetry
from repro.serving.scheduler import (
    DONE, PREFILL, QUEUED, RUNNING, FCFSScheduler, SamplingParams, ServeRequest,
)

__all__ = ["Engine", "EngineConfig"]

# the first-token row of a prefill chunk, picked at a traced position: one
# program per chunk shape, none per prompt length
_first_row = jax.jit(lambda logits, i: jax.lax.dynamic_index_in_dim(logits[0], i))
_rows_finite = jax.jit(lambda rows: jnp.isfinite(rows).all(-1))


def _is_dip(x) -> bool:
    return isinstance(x, api.DipWeight)


def _storage_bytes(leaf) -> int:
    return int((leaf.data if _is_dip(leaf) else leaf).nbytes)


def _named(fn, name: str):
    """``fn`` renamed, so that its jitted program reads ``jit_<name>`` in a
    device trace."""
    fn.__name__ = fn.__qualname__ = name
    return fn


@dataclasses.dataclass
class EngineConfig:
    slots: int = 4
    max_seq: int = 512                   # hard per-sequence context cap
    block_size: Optional[int] = None     # None -> cfg.kv_block_size
    kv_quant: Optional[str] = None       # None -> cfg.kv_quant
    num_blocks: Optional[int] = None     # None -> full occupancy, no preemption
    prefill_chunk: int = 64
    eos_id: int = 1
    # --- reliability (docs/reliability.md §serving) ---
    verify: bool = False                 # screen decode logits for nonfinite
    max_retries: int = 1                 # fault-triggered re-prefills/request
    retry_backoff_ticks: int = 2         # admission backoff after a fault
    ttl_s: Optional[float] = None        # default per-request deadline


class Engine:
    """``add_request`` / ``step`` / ``run`` over a fixed slot pool."""

    def __init__(self, cfg, params=None, *, engine_cfg: Optional[EngineConfig] = None,
                 plan=None, scheduler: Optional[FCFSScheduler] = None,
                 on_preempt: Optional[Callable] = None, seed: int = 0):
        self.cfg = cfg
        self.ecfg = ecfg = engine_cfg or EngineConfig()
        be = api.get_backend(cfg.matmul_backend)  # fail fast on unknown backends
        if be.layout == "dip_q" and cfg.quant_scheme != be.scheme:
            raise ValueError(
                f"backend {be.name!r} consumes {be.scheme!r}-quantized weights "
                f"but cfg.quantization={cfg.quantization!r}"
            )
        if be.layout == "sharded" and plan is None:
            raise ValueError(
                f"backend {be.name!r} dispatches on the weights' ShardingPlan "
                "metadata; pass plan= (repro.distributed.make_plan) or serve "
                "through the implicit GSPMD path (matmul_backend='xla')"
            )
        self.plan = plan
        if params is None:
            params = tf_model.init_params(jax.random.PRNGKey(seed), cfg)
        if plan is not None:
            params = plan.attach_params(params)
            shardings = plan.param_shardings(params)
            params = jax.tree_util.tree_map(jax.device_put, params, shardings)
        # weight intake: each leaf the programs read only in the compute
        # dtype is rounded to it once, here, before the KV pools take their
        # memory; the engine keeps the rounded copy alone
        with telemetry.span("engine.intake") as span:
            self.params = tf_model.serving_params(cfg, params)
            jax.block_until_ready(self.params)
            cast = [_storage_bytes(a) for a, b in zip(
                jax.tree_util.tree_leaves(params, is_leaf=_is_dip),
                jax.tree_util.tree_leaves(self.params, is_leaf=_is_dip))
                if a is not b]
            span.n = len(cast)
        del params
        self._intake_stats = {
            "intake_cast_bytes": sum(cast),
            "served_weight_bytes": sum(
                int(a.nbytes) for a in jax.tree_util.tree_leaves(self.params)),
        }

        self.block_size = ecfg.block_size or cfg.kv_block_size
        self.kv_quant = ecfg.kv_quant if ecfg.kv_quant is not None else cfg.kv_quant
        if self.kv_quant != "none":
            api.quant.scheme_info(self.kv_quant)  # validate the scheme name
        if self.kv_quant != cfg.kv_quant:
            # the paged decode step reads its storage format off the config;
            # an EngineConfig override must be visible there too
            cfg = self.cfg = dataclasses.replace(cfg, kv_quant=self.kv_quant)
        blocks_per_seq = -(-ecfg.max_seq // self.block_size)
        num_blocks = ecfg.num_blocks or ecfg.slots * blocks_per_seq + 1
        # pure SSM has no attention KV: state is per-slot, nothing is paged
        self._paged = not cfg.is_ssm
        self.kv = kvc.PagedKVCache(
            cfg, num_blocks=num_blocks, block_size=self.block_size,
            slots=ecfg.slots, max_seq=ecfg.max_seq, kv_quant=self.kv_quant,
            plan=plan,
        )

        # MoE programs also return their layers' (routed, rows, dropped)
        # counts, kept on the device until the next fetch reads them
        self._moe = cfg.is_moe
        self._decode = jax.jit(_named(tf_model.paged_decode_step_fn(
            cfg, plan=plan, moe_stats=self._moe), "engine_decode"))
        # chunked prefill routes through the fused flash-attention kernel
        # (api.attention backend "flash") whenever the logits stay local: the
        # kernel takes the chunk's cache offset as a *traced* q_offset, so
        # every chunk of every prompt shares one compiled shape.  Sharded
        # plans keep the GSPMD online-softmax path (the kernel is per-shard).
        self._prefill_fwd = jax.jit(_named(tf_model.decode_step_fn(
            cfg, plan=plan, attn_backend="flash" if plan is None else None,
            moe_stats=self._moe,
        ), "engine_prefill_chunk"))
        self._import = jax.jit(kvc.make_import_fn(
            cfg, num_blocks, self.block_size, self.kv_quant
        ))
        # prefill buffer: padded so every chunk call has ONE compiled shape
        c = ecfg.prefill_chunk
        self._prefill_buf_len = -(-ecfg.max_seq // c) * c

        self.scheduler = scheduler or FCFSScheduler(on_preempt=on_preempt)
        self._slots: List[Optional[ServeRequest]] = [None] * ecfg.slots
        self._cur = np.zeros((ecfg.slots, 1), np.int32)     # next token to feed
        self._ctx = np.zeros((ecfg.slots,), np.int32)       # tokens in cache
        self._prefilling: Optional[ServeRequest] = None
        self._prefill_cache: Any = None
        self._prefill_tokens: Optional[np.ndarray] = None
        self._prefill_done: int = 0                         # tokens processed
        self._next_rid = 0
        self.results: Dict[int, List[int]] = {}
        self.request_stats: Dict[int, Dict[str, Any]] = {}
        self._decode_steps = 0
        self._prefill_chunks = 0
        self._preempt_count = 0
        self._generated_total = 0
        self._rows_greedy = 0
        self._rows_drawn = 0
        self._filtered_draws = 0
        self._moe_pending: List[Any] = []          # device counts not yet read
        self._moe_routed = 0
        self._moe_rows = 0
        self._moe_dropped = 0
        self.last_stats: Dict[str, Any] = dict(self._intake_stats)
        # reliability bookkeeping (docs/reliability.md §serving)
        self._tick = 0
        self._faults_detected = 0
        self._retries_total = 0
        self._deadline_evictions = 0
        self._degraded_requests = 0
        self._decode_xla = None             # degraded-path step (built lazily)

    # ------------------------------------------------------------ intake ---
    def add_request(self, prompt, sampling_params: Optional[SamplingParams] = None,
                    *, rid: Optional[int] = None,
                    on_token: Optional[Callable] = None,
                    ttl_s: Optional[float] = None) -> int:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if prompt.size >= self.ecfg.max_seq:
            raise ValueError(
                f"prompt of {prompt.size} tokens leaves no room to generate "
                f"under max_seq={self.ecfg.max_seq}"
            )
        if self._paged:
            # admission-time capacity check: a prompt needing more blocks
            # than the whole pool owns would sit at the queue head forever
            # (can_allocate never true) and spin the engine — fail fast
            need = self.kv.blocks_needed(prompt.size)
            usable = self.kv.num_blocks - 1     # block 0 is the null block
            if need > usable:
                raise ValueError(
                    f"prompt of {prompt.size} tokens needs {need} KV blocks "
                    f"but the entire pool has {usable} usable blocks of "
                    f"{self.block_size} — it can never be admitted"
                )
        if rid is None:
            rid = self._next_rid
        self._next_rid = max(self._next_rid, rid) + 1
        sp = sampling_params or SamplingParams()
        req = ServeRequest(rid=rid, prompt=prompt, sampling=sp, on_token=on_token)
        req.arrival_s = time.monotonic()
        ttl = ttl_s if ttl_s is not None else self.ecfg.ttl_s
        if ttl is not None:
            req.deadline_s = req.arrival_s + ttl
        self.scheduler.add(req)
        return rid

    # ----------------------------------------------------------- helpers ---
    @property
    def _running(self) -> List[ServeRequest]:
        return [r for r in self._slots if r is not None and r.state == RUNNING]

    def _busy(self) -> bool:
        return bool(len(self.scheduler) or self._prefilling is not None
                    or any(s is not None for s in self._slots))

    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self._slots):
            if s is None:
                return i
        return None

    def _ensure(self, slot: int, length: int) -> bool:
        return self.kv.ensure(slot, length) if self._paged else True

    def _evict(self, req: ServeRequest) -> None:
        slot = req.slot
        if self._paged:
            self.kv.release(slot)
        self._slots[slot] = None
        self._ctx[slot] = 0
        self._preempt_count += 1
        self.scheduler.preempt(req)

    def _finish(self, req: ServeRequest, *, deadline_expired: bool = False,
                fault_failed: bool = False) -> None:
        slot = req.slot
        if slot >= 0:
            if self._paged:
                self.kv.release(slot)
            self._slots[slot] = None
            self._ctx[slot] = 0
        req.state = DONE
        req.finish_s = time.monotonic()
        self.results[req.rid] = list(req.generated)
        self.request_stats[req.rid] = {
            "prompt_len": int(req.prompt.size),
            "new_tokens": len(req.generated),
            "queue_s": (req.admit_s - req.arrival_s
                        if req.admit_s is not None else None),
            "ttft_s": (req.first_token_s - req.arrival_s
                       if req.first_token_s is not None else None),
            "latency_s": req.finish_s - req.arrival_s,
            "preemptions": req.preemptions,
            "retries": req.retries,
            "degraded": req.degraded,
            "deadline_expired": deadline_expired,
            "fault_failed": fault_failed,
        }

    def _emit(self, req: ServeRequest, token: int, done: bool) -> None:
        req.generated.append(token)
        self._generated_total += 1
        if req.first_token_s is None:
            req.first_token_s = time.monotonic()
        if req.on_token is not None:
            req.on_token(req.rid, token, done)

    def _append_token(self, req: ServeRequest, token: int) -> bool:
        """Record one generated token; returns True if the request finished."""
        slot = req.slot
        done = (
            token == self.ecfg.eos_id
            or len(req.generated) + 1 >= req.sampling.max_new_tokens
            or int(self._ctx[slot]) >= self.ecfg.max_seq
        )
        self._emit(req, token, done)
        if done:
            self._finish(req)
            return True
        self._cur[slot, 0] = token
        return False

    def _sample_rows(self, logits, reqs: List[Optional[ServeRequest]]) -> np.ndarray:
        """One device draw over the (B, V) logits rows, still on the device;
        returns the (B,) tokens on the host.  Rows without a request fall
        back to greedy and are ignored by the caller."""
        b, v = logits.shape
        live = [r for r in reqs if r is not None]
        rid = live[0].rid if b == 1 and live else -1
        with telemetry.span("engine.sample", rid=rid, n=len(live)):
            temp = np.zeros(b, np.float32)
            top_k = np.zeros(b, np.int32)
            top_p = np.ones(b, np.float32)
            seeds = [0] * b
            counters = np.zeros(b, np.int32)
            for i, r in enumerate(reqs):
                if r is None:
                    continue
                sp = r.sampling
                temp[i], top_k[i], top_p[i] = sp.temperature, sp.top_k, sp.top_p
                seeds[i], counters[i] = sp.seed, len(r.generated)
            draw, on_k, on_p = sampling.row_filters(temp, top_k, top_p, v)
            drawn = int(draw.sum())
            self._rows_drawn += drawn
            self._rows_greedy += len(live) - drawn
            self._filtered_draws += bool((on_k | on_p).any())
            return sampling.sample_tokens(
                logits, temperature=temp, top_k=top_k, top_p=top_p,
                uniforms=sampling.RowSeeds(seeds, counters),
            )

    def _run(self, program, *args):
        """Dispatch one model program; an MoE program's counts wait on the
        device for ``_read_moe``."""
        out = program(*args)
        if self._moe:
            self._moe_pending.append(out[2])
        return out[0], out[1]

    def _read_moe(self) -> None:
        """Add the pending MoE counts to the host counters: called where the
        host has just waited for the programs anyway, so the read is a copy
        of finished results and no sync of its own."""
        if self._moe_pending:
            got = np.sum(jax.device_get(self._moe_pending), axis=0)
            self._moe_pending = []
            self._moe_routed += int(got[0])
            self._moe_rows += int(got[1])
            self._moe_dropped += int(got[2])

    # ------------------------------------------------------------- faults --
    def _handle_fault(self, req: ServeRequest) -> None:
        """A verified step tripped for ``req``: bounded retry (evict —
        re-prefill rebuilds clean KV — with tick backoff), then degrade the
        request to the ``xla`` decode path, then give up.  Peers are never
        touched: rows are independent, so one poisoned row costs one row."""
        self._faults_detected += 1
        if req is self._prefilling:
            self._prefilling = None
            self._prefill_cache = None
            self._prefill_tokens = None
        if req.degraded:
            # the fallback path faulted too — persistent corruption; stop
            # burning ticks on this request and surface the failure
            self._finish(req, fault_failed=True)
            return
        req.not_before_tick = self._tick + self.ecfg.retry_backoff_ticks
        if req.retries < self.ecfg.max_retries:
            req.retries += 1
            self._retries_total += 1
        else:
            req.degraded = True
            self._degraded_requests += 1
        self._evict(req)

    def _get_decode_xla(self):
        """Decode step compiled against the plain ``xla`` matmul backend —
        the bottom rung of the degradation ladder.  Built on first fault."""
        if self._decode_xla is None:
            cfg_xla = dataclasses.replace(self.cfg, matmul_backend="xla")
            self._decode_xla = jax.jit(_named(
                tf_model.paged_decode_step_fn(cfg_xla, plan=self.plan,
                                              moe_stats=self._moe),
                "engine_decode_xla"))
        return self._decode_xla

    def _expire(self, req: ServeRequest) -> None:
        self._deadline_evictions += 1
        if req is self._prefilling:
            self._prefilling = None
            self._prefill_cache = None
            self._prefill_tokens = None
        self._finish(req, deadline_expired=True)

    def _sweep_deadlines(self) -> None:
        now = time.monotonic()
        for req in self.scheduler.drop_expired(now):
            self._expire(req)
        for req in list(self._slots):
            if (req is not None and req.deadline_s is not None
                    and now >= req.deadline_s):
                self._expire(req)

    # ---------------------------------------------------------- admission --
    def _try_admit(self) -> None:
        if self._prefilling is not None:
            return
        req = self.scheduler.next_waiting(self._tick)
        if req is None:
            return
        slot = self._free_slot()
        if slot is None:
            return
        plen = int(req.serve_prompt.size)
        if self._paged and not self.kv.can_allocate(plen):
            return
        req = self.scheduler.pop(self._tick)
        if req.admit_s is None:
            req.admit_s = time.monotonic()
        with telemetry.span("engine.admit", rid=req.rid, n=plen):
            req.state = PREFILL
            req.slot = slot
            self._slots[slot] = req
            if self._paged:
                ok = self.kv.ensure(slot, plen)   # can_allocate held above
                assert ok, "allocator disagreed with can_allocate"
            buf = np.zeros(self._prefill_buf_len, np.int32)
            buf[:plen] = req.serve_prompt
            self._prefilling = req
            self._prefill_tokens = buf
            self._prefill_done = 0
            self._prefill_cache = tf_model.init_cache(self.cfg, 1, self._prefill_buf_len)

    # ------------------------------------------------------------ prefill --
    def _advance_prefill(self) -> None:
        req = self._prefilling
        if req is None:
            return
        c = self.ecfg.prefill_chunk
        plen = int(req.serve_prompt.size)
        done = self._prefill_done
        last_logits = None

        with telemetry.span("engine.prefill", rid=req.rid, n=min(c, plen - done)):
            if self.cfg.ssm_state:
                # The recurrent state is exact only over the real tokens, so
                # the tail that doesn't fill a chunk runs token-by-token
                # through the O(1) decode path (<= chunk-1 cheap steps)
                # instead of padding.
                if plen - done >= c:
                    chunk = self._prefill_tokens[done:done + c][None]
                    last_logits, self._prefill_cache = self._run(
                        self._prefill_fwd,
                        self.params, self._prefill_cache, jnp.asarray(chunk)
                    )
                    done += c
                    self._prefill_chunks += 1
                else:
                    while done < plen:
                        tok = self._prefill_tokens[done:done + 1][None]
                        last_logits, self._prefill_cache = self._run(
                            self._prefill_fwd,
                            self.params, self._prefill_cache, jnp.asarray(tok)
                        )
                        done += 1
                    self._prefill_chunks += 1
            else:
                # attention-only: the padded tail of the final chunk writes
                # cache rows >= plen, which the import drops and positions
                # never reach
                chunk = self._prefill_tokens[done:done + c][None]
                last_logits, self._prefill_cache = self._run(
                    self._prefill_fwd,
                    self.params, self._prefill_cache, jnp.asarray(chunk)
                )
                done += c
                self._prefill_chunks += 1
        self._prefill_done = done

        if done >= plen:
            self._finish_prefill(req, plen, last_logits)

    def _finish_prefill(self, req: ServeRequest, plen: int, last_logits) -> None:
        slot = req.slot
        with telemetry.span("engine.import", rid=req.rid, n=plen):
            pools = self.kv.pools["layers"]
            self.kv.pools["layers"] = self._import(
                pools, self._prefill_cache["layers"],
                jnp.int32(slot), jnp.int32(plen),
                jnp.asarray(self.kv.table_row(slot)),
            )
        # first token: logits row of the prompt's last position within the
        # final prefill call (padded chunk: plen-1 relative to chunk start;
        # SSM single-token tail: the only row)
        pos = (plen - 1) - (self._prefill_done - last_logits.shape[1])
        with telemetry.span("engine.fetch", rid=req.rid, n=1):
            row = _first_row(last_logits, np.int32(pos)).block_until_ready()
            self._read_moe()
        if self.ecfg.verify and not _rows_finite(row)[0]:
            self._handle_fault(req)
            return
        tok = int(self._sample_rows(row, [req])[0])
        self._prefilling = None
        self._prefill_cache = None
        self._prefill_tokens = None
        req.state = RUNNING
        self._ctx[slot] = plen
        if not self._append_token(req, tok):
            pass  # request keeps its slot; next decode feeds `tok`

    # ------------------------------------------------------------- decode --
    def _decode_once(self) -> None:
        running = self._running
        if not running:
            return
        with telemetry.span("engine.decode") as span:
            # grow every running slot's table for the position it writes
            # next; under exhaustion the LIFO victim is evicted until the
            # rest fit (the last one standing is never evicted)
            for req in sorted(running, key=lambda r: r.admit_index):
                if req.state != RUNNING:
                    continue
                while not self._ensure(req.slot, int(self._ctx[req.slot]) + 1):
                    victims = self._running
                    victim = self.scheduler.pick_victim(victims)
                    if victim is req and len(victims) == 1:
                        raise RuntimeError(
                            f"KV pool too small for one sequence: "
                            f"{self.kv.num_blocks} blocks of {self.block_size}"
                        )
                    self._evict(victim)
                    if victim is req:
                        break

            reqs = [r if (r is not None and r.state == RUNNING) else None
                    for r in self._slots]
            span.n = live = sum(r is not None for r in reqs)
            # a tick with any degraded request runs the WHOLE pool through
            # the xla-compiled step (one compiled step per tick is the engine
            # invariant; healthy rows are row-independent either way)
            decode = (
                self._get_decode_xla()
                if any(r is not None and r.degraded for r in reqs)
                else self._decode
            )
            logits, self.kv.pools = self._run(
                decode, self.params, self.kv.pools,
                jnp.asarray(self._cur), jnp.asarray(self._ctx),
                jnp.asarray(self.kv.block_tables),
            )
            self._decode_steps += 1
        with telemetry.span("engine.fetch", n=live):
            logits.block_until_ready()
            self._read_moe()
        rows = logits[:, -1]
        next_tokens = self._sample_rows(rows, reqs)
        finite = np.asarray(_rows_finite(rows)) if self.ecfg.verify else None
        for i, req in enumerate(reqs):
            if req is None:
                continue
            if finite is not None and not finite[i]:
                # corrupted KV / a tripped verified matmul surfaces here as a
                # nonfinite logits row; only this row's request pays
                self._handle_fault(req)
                continue
            self._ctx[i] += 1   # the fed token is now in the cache
            self._append_token(req, int(next_tokens[i]))

    # -------------------------------------------------------------- drive --
    def step(self) -> bool:
        """One engine tick (deadline sweep -> admit -> prefill chunk ->
        decode step).  Returns True while there is work left."""
        self._tick += 1
        with telemetry.span("engine.step", n=self._tick):
            self._sweep_deadlines()
            self._try_admit()
            self._advance_prefill()
            self._try_admit()    # a finished prefill may free the pipeline
            self._decode_once()
            return self._busy()

    def run(self) -> Dict[int, List[int]]:
        """Drain the queue; returns {rid: generated tokens} and fills
        ``last_stats`` / ``request_stats``."""
        t0 = time.monotonic()
        steps0, gen0 = self._decode_steps, self._generated_total
        while self.step():
            pass
        wall = time.monotonic() - t0
        self.last_stats = {
            "decode_steps": self._decode_steps - steps0,
            "sample_rows_greedy": self._rows_greedy,
            "sample_rows_drawn": self._rows_drawn,
            "sample_filtered_draws": self._filtered_draws,
            "moe_routed": self._moe_routed,
            "moe_rows": self._moe_rows,
            "moe_dropped": self._moe_dropped,
            "wall_s": wall,
            "tok_per_s": (self._generated_total - gen0) / max(wall, 1e-9),
            "prefill_chunks": self._prefill_chunks,
            "preemptions": self._preempt_count,
            "requests": len(self.results),
            "faults_detected": self._faults_detected,
            "retries": self._retries_total,
            "deadline_evictions": self._deadline_evictions,
            "degraded_requests": self._degraded_requests,
            **self._intake_stats,
        }
        return dict(self.results)
