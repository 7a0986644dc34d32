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
(admit+prefill new requests) + (one lockstep decode token for every active
sequence, ragged positions via the per-seq `pos` vector).
"""
from __future__ import annotations

import collections
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels.kv_compaction.ops import compact_kv_pool
from repro.models import forward, init_cache, init_params


@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int
    out: List[int] = field(default_factory=list)
    slot: int = -1
    done: bool = False
    submitted: float = 0.0
    finished: float = 0.0


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params=None, *, max_slots: int = 4,
                 max_seq: int = 256, seed: int = 0, rules=None,
                 scramble_blocks: bool = True):
        self.cfg = cfg
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.rules = rules
        self.scramble = scramble_blocks
        self.rng = np.random.default_rng(seed)
        self.params = params if params is not None else init_params(
            jax.random.PRNGKey(seed), cfg)
        self.caches = init_cache(cfg, max_slots, max_seq, "paged")
        self.pos = np.zeros(max_slots, np.int64)
        self.active: Dict[int, Request] = {}
        self.queue: "collections.deque[Request]" = collections.deque()
        self.free_slots = list(range(max_slots))
        self.finished: List[Request] = []
        self.decode_steps = 0
        self.compactions = 0
        self._rid = 0

        def decode_fn(params, caches, tokens, pos):
            logits, new_caches = forward(params, tokens, cfg, rules,
                                         mode="decode", caches=caches,
                                         pos=pos)
            return jnp.argmax(logits[:, -1], axis=-1), new_caches

        self._decode = jax.jit(decode_fn, donate_argnums=(1,))

        def prefill_fn(params, caches, tokens):
            logits, new_caches = forward(params, tokens, cfg, rules,
                                         mode="prefill", caches=caches)
            return logits, new_caches

        self._prefill = jax.jit(prefill_fn)

    # ------------------------------------------------------------- client
    def submit(self, prompt: List[int], max_new: int = 16) -> Request:
        self._rid += 1
        req = Request(self._rid, list(prompt), max_new, submitted=time.time())
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
            slot = self.free_slots.pop()
            req.slot = slot
            plen = len(req.prompt)
            assert plen + req.max_new <= self.max_seq
            # fragmented allocation: reused slots get scrambled block order
            sub = self._slot_cache(slot)
            sub = self._fresh_slot_tables(sub)
            toks = np.zeros((1, self.max_seq), np.int32)
            toks[0, :plen] = req.prompt
            logits, sub = self._prefill(self.params, sub, jnp.asarray(toks))
            nxt = int(jnp.argmax(logits[0, plen - 1]))
            req.out.append(nxt)
            self._write_slot_cache(slot, sub)
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
        self._admit()
        if not self.active:
            return 0
        tokens = np.zeros((self.max_slots, 1), np.int32)
        for slot, req in self.active.items():
            tokens[slot, 0] = req.out[-1]
        if self.cfg.input_kind == "embeds":
            tok_in = jnp.zeros((self.max_slots, 1, self.cfg.d_model),
                               jnp.dtype(self.cfg.param_dtype))
        else:
            tok_in = jnp.asarray(tokens)
        pos = jnp.asarray(np.maximum(self.pos, 0), jnp.int32)
        nxt, self.caches = self._decode(self.params, self.caches, tok_in, pos)
        nxt = np.asarray(nxt)
        produced = 0
        for slot in list(self.active):
            req = self.active[slot]
            self.pos[slot] += 1
            req.out.append(int(nxt[slot]))
            produced += 1
            if len(req.out) - 1 >= req.max_new:
                req.done = True
                req.finished = time.time()
                self.finished.append(req)
                del self.active[slot]
                self.free_slots.append(slot)
        self.decode_steps += 1
        return produced

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
        self.caches = compact_caches(self.caches, backend)
        self.compactions += 1


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
