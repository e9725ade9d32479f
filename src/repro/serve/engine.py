"""Lock-step batched greedy decode over a shared KV/state cache.

``ServeEngine.run_lockstep`` feeds a batch of equal-length prompts through
the model's jitted ``decode_step`` one position per call, then decodes
greedily for a fixed number of tokens.  Every slot advances together; a
batch narrower than the engine's slots is padded with token 0.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.api import Model


class ServeEngine:
    def __init__(self, model: Model, params: Any, *, batch_slots: int,
                 max_len: int):
        self.model = model
        self.params = params
        self.slots = batch_slots
        self.max_len = max_len
        self._decode = jax.jit(model.decode_step)

    def run_lockstep(self, prompts: list[list[int]], max_new: int
                     ) -> list[list[int]]:
        """Decode ``max_new`` tokens after each prompt; all prompts the
        same length.  Returns the generated token lists.

        Every token array handed to the step is a fresh one that nothing
        writes afterwards: on the CPU, ``jnp.asarray`` of a 64-byte-aligned
        numpy buffer aliases it, and the step runs asynchronously, so a
        host buffer refilled between steps can reach a step with the next
        step's tokens."""
        B = len(prompts)
        assert B <= self.slots
        plen = len(prompts[0])
        assert all(len(p) == plen for p in prompts), "lock-step needs equal"
        toks = jnp.zeros((self.slots, plen), jnp.int32).at[:B].set(
            jnp.asarray(prompts, jnp.int32))

        cache = self.model.init_cache(self.slots, self.max_len)
        for t in range(plen):
            logits, cache = self._decode(self.params, cache,
                                         toks[:, t:t + 1], t)
        outs = jnp.zeros((self.slots, max_new), jnp.int32)
        for s in range(max_new):
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            outs = outs.at[:, s].set(nxt)
            if s + 1 < max_new:
                logits, cache = self._decode(self.params, cache,
                                             nxt[:, None], plen + s)
        return np.asarray(outs)[:B].tolist()
