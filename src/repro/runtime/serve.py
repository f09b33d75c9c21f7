"""Serving runtime: continuous batching.

``ContinuousBatcher`` is the serving loop used by the examples: a
slot-based batcher whose admission queue is managed through the CWS (each
admitted request is a CWSI task, so serving inherits workflow-aware
ordering, provenance, and the runtime predictor for SLA estimates).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.model import Model


@dataclass
class Request:
    req_id: str
    prompt: List[int]
    max_new_tokens: int = 32
    submitted_at: float = 0.0
    tokens_out: List[int] = field(default_factory=list)
    done: bool = False


class ContinuousBatcher:
    """Slot-based continuous batching over a fixed decode batch.

    Slots hold independent requests; each engine step decodes one token for
    every active slot. Finished slots are refilled from the admission queue
    between steps (the queue order is whatever the CWS hands us — e.g.
    shortest-predicted-first under the Lotaru plugin).
    """

    def __init__(self, model: Model, params: Any, batch_slots: int,
                 max_len: int, eos_token: int = 2) -> None:
        self.model = model
        self.params = params
        self.slots: List[Optional[Request]] = [None] * batch_slots
        self.max_len = max_len
        self.eos = eos_token
        self.cache = model.init_cache(batch_slots, max_len)
        self.pos = np.zeros(batch_slots, np.int32)   # per-slot lengths
        self.queue: List[Request] = []
        self._step = jax.jit(model.decode_step)
        self.steps = 0
        # find each cache tensor's batch dim by diffing two spec batch sizes
        a = jax.tree.leaves(model.cache_specs(batch_slots, max_len))
        b = jax.tree.leaves(model.cache_specs(batch_slots + 1, max_len))
        self._batch_dims = [
            next(i for i, (x, y) in enumerate(zip(sa.shape, sb.shape))
                 if x != y)
            for sa, sb in zip(a, b)
        ]

    def submit(self, req: Request) -> None:
        self.queue.append(req)

    def _admit(self) -> None:
        for i, slot in enumerate(self.slots):
            if slot is None and self.queue:
                req = self.queue.pop(0)
                self.slots[i] = req
                # feed the prompt token-by-token (teacher-forced prefill)
                for t in req.prompt[:-1]:
                    self._advance(i, t, sample=False)
                self._last_token = req.prompt[-1]
                self._pending_first = i

    def _advance(self, slot: int, token: int, sample: bool) -> Optional[int]:
        tok = jnp.zeros(len(self.slots), jnp.int32).at[slot].set(token)
        logits, self.cache = self._step(self.params, self.cache, tok,
                                        jnp.int32(int(self.pos[slot])))
        self.pos[slot] += 1
        self.steps += 1
        if sample:
            return int(jnp.argmax(logits[slot]))
        return None

    def step(self) -> int:
        """One engine round: admit, decode one token per active slot."""
        self._admit()
        active = 0
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            active += 1
            last = req.tokens_out[-1] if req.tokens_out else req.prompt[-1]
            nxt = self._advance(i, last, sample=True)
            req.tokens_out.append(nxt)
            if (nxt == self.eos or len(req.tokens_out) >= req.max_new_tokens
                    or self.pos[i] >= self.max_len - 1):
                req.done = True
                self.slots[i] = None
                self.pos[i] = 0
                self._reset_slot(i)   # fresh request needs a clean KV range
        return active

    def _reset_slot(self, slot: int) -> None:
        leaves, treedef = jax.tree.flatten(self.cache)
        out = []
        for c, d in zip(leaves, self._batch_dims):
            idx = tuple(slot if i == d else slice(None)
                        for i in range(c.ndim))
            out.append(c.at[idx].set(0))
        self.cache = jax.tree.unflatten(treedef, out)

    def drain(self, max_rounds: int = 10_000) -> None:
        rounds = 0
        while (self.queue or any(s is not None for s in self.slots)):
            if self.step() == 0 and not self.queue:
                break
            rounds += 1
            if rounds > max_rounds:
                raise RuntimeError("batcher did not drain")


