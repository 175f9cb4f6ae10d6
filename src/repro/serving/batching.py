"""Continuous batching for the generation model (vLLM/JetStream-style,
adapted to this substrate).

A fixed pool of ``num_slots`` decode slots shares one batched KV cache.
Requests are admitted into free slots (their prompt is prefilled
single-request, then its KV prefix is copied into the slot), the decode
step advances ALL active slots one token per tick with PER-SLOT cache
lengths (models.cache.KVCache.insert's vector path), and finished slots
(max tokens here; an EOS id in production) are freed immediately for the
next waiting request — no batch-wide barrier.

This is the host-side orchestration layer that the decode_32k serve_step
(and its §Perf sharded variant) executes per tick on the pod.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.cache import init_cache
from repro.models.model import decode_step, forward


@dataclasses.dataclass
class SlotState:
    request_id: int = -1
    tokens_out: List[int] = dataclasses.field(default_factory=list)
    budget: int = 0

    @property
    def free(self) -> bool:
        return self.request_id < 0


class ContinuousBatcher:
    def __init__(self, cfg: ModelConfig, params, *, num_slots: int = 4,
                 max_len: int = 256, compute_dtype=jnp.float32):
        self.cfg = cfg
        self.params = params
        self.num_slots = num_slots
        self.max_len = max_len
        self.compute_dtype = compute_dtype
        self.caches = init_cache(cfg, num_slots, max_len)
        self.lens = np.zeros(num_slots, np.int32)       # per-slot cache len
        self.next_tok = np.zeros(num_slots, np.int32)
        self.slots = [SlotState() for _ in range(num_slots)]
        self.completed: Dict[int, List[int]] = {}
        # perf_counter when each request's first token reached the host
        self.first_token_at: Dict[int, float] = {}

        self._prefill1 = jax.jit(self._prefill_one)
        # self.caches is replaced every tick, so the step updates it in place
        self._step = jax.jit(self._decode_all, donate_argnums=1)

    # ---- jitted kernels -------------------------------------------------
    def _prefill_one(self, params, tokens):
        """Prefill ONE request (1, L) against a fresh single-row cache."""
        caches1 = init_cache(self.cfg, 1, self.max_len)
        logits, new_caches, _ = forward(
            params, self.cfg, {"tokens": tokens}, mode="prefill",
            caches=caches1, cache_len=0, compute_dtype=self.compute_dtype,
            remat=False)
        return logits[:, -1], new_caches

    def _decode_all(self, params, caches, toks, lens):
        logits, new_caches = decode_step(
            params, self.cfg, toks[:, None], caches, lens,
            compute_dtype=self.compute_dtype)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), new_caches

    # ---- slot management -------------------------------------------------
    def _copy_prefix_into_slot(self, slot: int, caches1, length: int):
        def put(dst, src):
            # dst: (R, num_slots, ...); src: (R, 1, ...)
            return dst.at[:, slot].set(src[:, 0])
        self.caches = jax.tree.map(put, self.caches, caches1)
        self.lens[slot] = length

    def admit(self, request_id: int, prompt_tokens: List[int],
              max_new_tokens: int) -> Optional[int]:
        """Prefill into a free slot; returns the slot or None if full."""
        free = [i for i, s in enumerate(self.slots) if s.free]
        if not free:
            return None
        slot = free[0]
        L = min(len(prompt_tokens), self.max_len - max_new_tokens - 1)
        toks = jnp.asarray([prompt_tokens[:L]], jnp.int32)
        last_logits, caches1 = self._prefill1(self.params, toks)
        self._copy_prefix_into_slot(slot, caches1, L)
        self.next_tok[slot] = int(jnp.argmax(last_logits[0]))
        self.first_token_at[request_id] = time.perf_counter()
        self.slots[slot] = SlotState(request_id=request_id,
                                     budget=max_new_tokens)
        return slot

    def tick(self) -> int:
        """One decode step for all active slots; returns #active."""
        active = [i for i, s in enumerate(self.slots) if not s.free]
        if not active:
            return 0
        toks = jnp.asarray(self.next_tok, jnp.int32)
        lens = jnp.asarray(self.lens, jnp.int32)
        nxt, self.caches = self._step(self.params, self.caches, toks, lens)
        nxt = np.asarray(nxt)
        for i in active:
            s = self.slots[i]
            s.tokens_out.append(int(self.next_tok[i]))
            self.lens[i] += 1
            self.next_tok[i] = nxt[i]
            if len(s.tokens_out) >= s.budget or self.lens[i] >= self.max_len - 1:
                self.completed[s.request_id] = s.tokens_out
                self.slots[i] = SlotState()     # free immediately
        return len(active)

    def run(self, requests: List[Dict], tick_limit: int = 10_000
            ) -> Dict[int, List[int]]:
        """requests: [{id, prompt_tokens, max_new_tokens}] -> outputs."""
        pending = list(requests)
        ticks = 0
        while (pending or any(not s.free for s in self.slots)) \
                and ticks < tick_limit:
            while pending:
                r = pending[0]
                if self.admit(r["id"], r["prompt_tokens"],
                              r["max_new_tokens"]) is None:
                    break
                pending.pop(0)
            self.tick()
            ticks += 1
        return self.completed
