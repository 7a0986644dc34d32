"""Paged-KV serving engine with continuous batching and Nezha-style cache GC.

The KV pool is the serving-side ValueLog (DESIGN.md §2): blocks are written
once at their allocation site; the per-sequence block table is the lightweight
key->offset index.  Slot reuse scrambles the physical layout over time
(fragmentation) exactly like Nezha's arrival-order ValueLog; `compact()` is
the GC — it re-packs each live sequence's blocks into logical order
(kernels/kv_compaction) so long decodes stream sequential HBM reads again.
Three-phase reads: compaction swaps the pool atomically per layer while the
old pool stays valid, so in-flight lookups never see a hole.

Scheduler: admit-on-free-slot continuous batching; one engine `step()` =
(admit+prefill new requests) + (dispatch one lockstep decode token for every
active sequence that has tokens left, ragged positions via the per-seq `pos`
vector, -1 for a free or finishing slot) + (fetch the previous step's tokens).
One decode step is in flight: its input tokens are the last step's `nxt`,
still on the device, with the prefill's first token in the slots admitted
since; the host knows every position and which request ends when, so it
never waits for a token value before dispatching.  A request's `out` gains
a step's token in the next `step()`, once the decode after it is
dispatched, and a request enters `finished` (and leaves `active`, freeing
its slot) only when its last token is on the host.  What drains the
pipeline: an admission (its prefill follows the decode in flight and its
first token is synced) and an engine with no token left to decode; a
compaction waits for the device to finish the decode in flight (its tokens
stay unfetched), so that its copies of the pool add to no buffer still in
use and the HBM peak is that of a step that syncs.
Admission prefills a prompt padded to the smallest of a few bucket
lengths that holds it (`prefill_buckets`), one compiled prefill a bucket.
The decode step reads and writes the pool in place through
kernels/paged_attention: each active sequence's live blocks, none of a free
slot's.

A hybrid model's recurrent state (Mamba-2: SSM state and conv tail, per
layer and slot) sits next to the pool: admission zeroes the slot's and its
prefill, told the prompt's length, writes it; the decode step updates it
in place (every slot's, free ones too); compaction leaves it alone.
"""
from __future__ import annotations

import collections
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MAMBA2, MAMBA2_MOE, ModelConfig
from repro.core import trace
from repro.kernels.kv_compaction.ops import compact_kv_pool
from repro.kernels.paged_attention import ops as paged_attention_ops
from repro.models import forward, init_cache, init_params


@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int
    out: List[int] = field(default_factory=list)
    slot: int = -1
    done: bool = False
    # time.perf_counter() stamps: submit, admission to a slot, last token
    submitted: float = 0.0
    admitted: float = 0.0
    finished: float = 0.0


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params=None, *, max_slots: int = 4,
                 max_seq: int = 256, seed: int = 0,
                 scramble_blocks: bool = True,
                 kv_backend: Optional[str] = None):
        self.cfg = cfg
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.scramble = scramble_blocks
        self.rng = np.random.default_rng(seed)
        self.params = params if params is not None else init_params(
            jax.random.PRNGKey(seed), cfg)
        self.caches = init_cache(cfg, max_slots, max_seq, "paged")
        # the decode step's paged-attention backend; None: the ops module's
        # default for this platform (tests may force "pallas_interpret")
        self.kv_backend = paged_attention_ops.resolve_backend(kv_backend)
        # an MoE model's prefill and decode also return their routings to
        # held experts; its prefill, and a recurrent model's, is told the
        # prompt's length (the state stops there, padding is not counted)
        self._counts = cfg.is_moe
        self._lengths = self._counts or bool(
            {MAMBA2, MAMBA2_MOE} & set(cfg.pattern_for_layers()))
        self._buckets = prefill_buckets(max_seq, cfg.kv_block_size)
        self._state_bytes_per_slot = sum(
            a.nbytes // a.shape[1] for g in self.caches["layers"]
            if "pool_k" not in g for a in jax.tree.leaves(g))
        self.pos = np.zeros(max_slots, np.int64)
        self.active: Dict[int, Request] = {}
        self.queue: "collections.deque[Request]" = collections.deque()
        self.free_slots = list(range(max_slots))
        self.finished: List[Request] = []
        self.decode_steps = 0
        self.compactions = 0
        self._rid = 0
        # the last decode's next tokens, on the device; the prefill's first
        # token of each slot admitted since (-1: none)
        self._last = jnp.zeros(max_slots, jnp.int32)
        self._first = np.full(max_slots, -1, np.int32)
        # the decode dispatched and not yet fetched: its next tokens, route
        # counts (traced only), serve.step span id and the slots it decoded
        self._inflight = None

        def decode_tokens(params, caches, tokens, pos):
            out = forward(params, tokens, cfg, mode="decode",
                          caches=caches, pos=pos, kv_backend=self.kv_backend,
                          count_routes=self._counts)
            return (jnp.argmax(out[0][:, -1], axis=-1),) + out[1:]

        # the model's decode of given tokens, traced into decode_fn (an
        # attribute of its own, so that a test can substitute a faulty one)
        self._decode = decode_tokens

        def decode_fn(params, caches, last, first, pos):
            if cfg.input_kind == "embeds":
                tokens = jnp.zeros((max_slots, 1, cfg.d_model),
                                   jnp.dtype(cfg.param_dtype))
            else:
                tokens = jnp.where(first >= 0, first, last)[:, None]
            return self._decode(params, caches, tokens, pos)

        self._decode_next = jax.jit(decode_fn, donate_argnums=(1,))

        def prefill_fn(params, caches, tokens, lengths=None):
            return forward(params, tokens, cfg, mode="prefill",
                           caches=caches, lengths=lengths,
                           count_routes=self._counts)

        self._prefill = jax.jit(prefill_fn)

    # ------------------------------------------------------------- client
    def submit(self, prompt: List[int], max_new: int = 16) -> Request:
        self._rid += 1
        req = Request(self._rid, list(prompt), max_new,
                      submitted=time.perf_counter())
        self.queue.append(req)
        return req

    # ---------------------------------------------------------- scheduler
    def _slot_cache(self, slot: int):
        return jax.tree.map(lambda a: a[:, slot:slot + 1], self.caches)

    def _write_slot_cache(self, slot: int, sub):
        self.caches = jax.tree.map(
            lambda a, u: a.at[:, slot:slot + 1].set(u.astype(a.dtype)),
            self.caches, sub)

    def _admit(self):
        while self.queue and self.free_slots:
            req = self.queue.popleft()
            req.admitted = time.perf_counter()
            trace.record("serve.queued", req.submitted, req.admitted)
            slot = self.free_slots.pop()
            req.slot = slot
            plen = len(req.prompt)
            assert plen + req.max_new <= self.max_seq
            padded = next(b for b in self._buckets if b >= plen)
            with trace.span("serve.admit", prompt_tokens=plen,
                            padded_tokens=padded) as sid:
                # fragmented allocation: reused slots get scrambled blocks
                sub = self._slot_cache(slot)
                sub = self._fresh_slot_tables(sub)
                toks = np.zeros((1, padded), np.int32)
                toks[0, :plen] = req.prompt
                kw = ({"lengths": jnp.asarray([plen], jnp.int32)}
                      if self._lengths else {})
                logits, sub, *counts = self._prefill(
                    self.params, sub, jnp.asarray(toks), **kw)
                nxt = int(jnp.argmax(logits[0, plen - 1]))
                if counts and sid is not None:
                    # indexed on the host: a device index would compile
                    trace.tag(sid, moe_routes_local=int(
                        np.asarray(counts[0])[0]))
                req.out.append(nxt)
                self._write_slot_cache(slot, sub)
            self._first[slot] = nxt
            self.pos[slot] = plen
            self.active[slot] = req

    def _fresh_slot_tables(self, sub):
        def reset(path, a):
            name = "/".join(str(getattr(p, "key", p)) for p in path)
            if name.endswith("table"):
                nblk = a.shape[-1]
                perm = (self.rng.permutation(nblk) if self.scramble
                        else np.arange(nblk)).astype(np.int32)
                return jnp.asarray(perm).reshape((1,) * (a.ndim - 1) + (nblk,)) \
                    * jnp.ones(a.shape, jnp.int32)
            if a.dtype == jnp.int32:
                return a
            return jnp.zeros_like(a)
        return jax.tree_util.tree_map_with_path(reset, sub)

    def step(self) -> int:
        """One engine iteration; returns number of tokens produced."""
        with trace.span("serve.step") as sid:
            return self._step(sid)

    def _step(self, sid=None) -> int:
        self._admit()
        # a request is decoded max_new times (once at least) from the
        # position after its prompt; a slot whose last decode is dispatched
        # waits for its token, undecoded
        slots = [slot for slot, req in self.active.items()
                 if self.pos[slot] < len(req.prompt) + max(req.max_new, 1)]
        landing, self._inflight = self._inflight, None
        if slots:
            self._inflight = self._dispatch(slots, sid, landing is not None)
        return self._land(*landing) if landing else 0

    def _dispatch(self, slots, sid, in_flight: bool):
        """Dispatches one decode of `slots` without waiting for it; returns
        what `_land` needs to fetch its tokens."""
        pos = np.full(self.max_slots, -1, np.int32)
        pos[slots] = self.pos[slots]
        if sid is not None:
            # per layer: the blocks the decode step's kernel reads (a
            # sequence's ceil((pos + 1) / bs)), and the pool's
            bs = self.cfg.kv_block_size
            live = pos[pos >= 0].astype(np.int64)
            trace.tag(sid, kv_blocks=int(np.sum(-(-(live + 1) // bs))),
                      pool_blocks=self.max_slots * (self.max_seq // bs),
                      in_flight=int(in_flight))
            if self._state_bytes_per_slot:
                # recurrent state of the active slots, read and written
                trace.tag(sid, ssm_state_bytes=2 * len(live)
                          * self._state_bytes_per_slot)
        nxt, self.caches, *counts = self._decode_next(
            self.params, self.caches, self._last, self._first, pos)
        # a fresh array: the one just passed may still be read by the call
        self._last, self._first = nxt, np.full(self.max_slots, -1, np.int32)
        self.pos[slots] += 1
        self.decode_steps += 1
        return (nxt, counts[0] if counts and sid is not None else None, sid,
                slots)

    def _land(self, nxt, counts, sid, slots) -> int:
        """Fetches a dispatched decode's tokens, and when traced its route
        counts (tagged on the serve.step that dispatched it), and appends
        them; a request whose last token this is finishes and frees its
        slot.  Returns the tokens fetched."""
        nxt, counts = jax.device_get((nxt, counts))
        if counts is not None:
            routes, hit = counts
            trace.tag(sid, moe_routes_local=int(routes),
                      moe_experts_hit=int(hit))
        for slot in slots:
            req = self.active[slot]
            req.out.append(int(nxt[slot]))
            if len(req.out) - 1 >= req.max_new:
                req.done = True
                req.finished = time.perf_counter()
                self.finished.append(req)
                del self.active[slot]
                self.free_slots.append(slot)
        return len(slots)

    def run_until_drained(self, max_steps: int = 10_000) -> int:
        total = 0
        for _ in range(max_steps):
            n = self.step()
            total += n
            if not self.active and not self.queue:
                return total
        raise TimeoutError("serving engine did not drain")

    # ------------------------------------------------------------- the GC
    def fragmentation(self) -> float:
        """Fraction of non-identity block-table entries (scatter level)."""
        leaves = [l for p, l in
                  jax.tree_util.tree_flatten_with_path(self.caches)[0]
                  if "table" in "".join(str(getattr(k, "key", k))
                                        for k in p)]
        total = ident = 0
        for t in leaves:
            t = np.asarray(t)
            ref = np.arange(t.shape[-1])
            ident += (t == ref).sum()
            total += t.size
        return 1.0 - ident / max(total, 1)

    def compact(self, backend: str):
        """Nezha GC for the KV pool (see `compact_caches`); `backend` picks
        the compaction kernel (repro.kernels.kv_compaction.ops)."""
        with trace.span("serve.compact"):
            # the work still queued (the decode in flight, an admission's
            # slot writes) holds buffers the host has let go; the copies of
            # the pool wait for it, so that they do not stack on those
            jax.block_until_ready(self.caches)
            self.caches = compact_caches(self.caches, backend)
        self.compactions += 1


def prefill_buckets(max_seq: int, block: int) -> List[int]:
    """The lengths admission pads a prompt to, ascending: the powers of two
    from 128 below `max_seq` that are whole KV blocks, then `max_seq`.

    A prefill of S tokens writes S // block blocks through the table, runs
    the Mamba-2 SSD in chunks of min(128, S) and causal attention in query
    chunks of min(512, S), so S must be a multiple of each: a power of two
    from 128 is a multiple of 128 and, above 512, of 512; one that is not
    whole blocks is skipped; `max_seq` is all three already.  Padding past
    the prompt changes nothing before it: attention is causal and a
    recurrent layer is told the prompt's length."""
    out, b = [], 128
    while b < max_seq:
        if b % block == 0:
            out.append(b)
        b *= 2
    return out + [max_seq]


def compact_caches(caches, backend: str):
    """Gather every live sequence's blocks into logical order and reset the
    tables to identity.  Returns new caches; the old pool stays valid until
    the caller swaps it in (three-phase read safety)."""
    def compact_group(group):
        pk, pv, tb = group["pool_k"], group["pool_v"], group["table"]
        shp = pk.shape                     # (reps, B, nblk, bs, nkv, hd)
        flat_k = pk.reshape((-1,) + shp[2:4] + (shp[4] * shp[5],))
        flat_v = pv.reshape((-1,) + shp[2:4] + (shp[4] * shp[5],))
        flat_t = jnp.broadcast_to(tb, shp[:2] + tb.shape[2:]).reshape(
            (-1, tb.shape[-1]))
        new_k, ident = compact_kv_pool(flat_k, flat_t, backend=backend)
        new_v, _ = compact_kv_pool(flat_v, flat_t, backend=backend)
        return dict(group,
                    pool_k=new_k.reshape(shp), pool_v=new_v.reshape(shp),
                    table=ident.reshape(tb.shape))

    # operate per attention cache group: pool_k/pool_v/table triplets
    def walk(tree):
        if isinstance(tree, dict):
            if "pool_k" in tree:
                return compact_group(tree)
            return {k: walk(v) for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            return type(tree)(walk(v) for v in tree)
        return tree

    return walk(caches)
