"""Batch-to-completion LM serving: the oracle for continuous decode.

`ServeEngine` runs two compiled programs (the same ones the dry-run
lowers):
  prefill_step — fills the KV/state cache for a batch of prompts;
  decode_step  — one token for the whole batch per call.

Each batch of requests runs to completion in static batch slots; short
batches are padded with dummy rows. `ServeEngine.serve` is the
arrival-order-independent ground truth that the continuous-batching loop
(`repro.serve.continuous`, served through `Server.register_decode`) is
tested against.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..models.config import ModelConfig
from ..models import prefill_step, decode_step, init_cache


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new_tokens: int = 16
    out: list[int] = dataclasses.field(default_factory=list)
    done: bool = False


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, batch_size: int = 4,
                 max_len: int = 256, greedy: bool = True):
        self.cfg = cfg
        self.params = params
        self.B = batch_size
        self.max_len = max_len
        self.greedy = greedy
        self._prefill = jax.jit(prefill_step(cfg))
        self._decode = jax.jit(decode_step(cfg), donate_argnums=(1,))
        self.metrics = {"prefills": 0, "decode_steps": 0, "tokens": 0}

    def _pad_prompts(self, prompts: list[list[int]]) -> np.ndarray:
        L = max(len(p) for p in prompts)
        arr = np.zeros((self.B, L), np.int32)
        for i, p in enumerate(prompts):
            arr[i, L - len(p):] = p          # left-pad (right-aligned)
        return arr

    def generate(self, requests: list[Request]) -> list[Request]:
        """Serve a batch of requests to completion (greedy decode)."""
        assert len(requests) <= self.B
        while len(requests) < self.B:       # pad batch with dummies
            requests = requests + [Request(rid=-1, prompt=[0],
                                           max_new_tokens=0)]
        prompts = self._pad_prompts([r.prompt for r in requests])
        S = prompts.shape[1]
        cache = init_cache(self.cfg, self.B, self.max_len, enc_len=S)
        batch = {"tokens": jnp.asarray(prompts)}
        if self.cfg.family == "encdec":
            batch["src_tokens"] = jnp.asarray(prompts)
        logits, cache = self._prefill(self.params, batch, cache)
        self.metrics["prefills"] += 1

        max_new = max(r.max_new_tokens for r in requests)
        tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        for r, t in zip(requests, np.asarray(tok)):
            if r.rid >= 0 and r.max_new_tokens > 0:
                r.out.append(int(t))
        for step in range(1, max_new):
            logits, cache = self._decode(self.params, cache, tok[:, None])
            self.metrics["decode_steps"] += 1
            tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
            tok_host = np.asarray(tok)
            for r, t in zip(requests, tok_host):
                if r.rid >= 0 and len(r.out) < r.max_new_tokens:
                    r.out.append(int(t))
                    self.metrics["tokens"] += 1
        for r in requests:
            r.done = True
        return [r for r in requests if r.rid >= 0]

    def serve(self, requests: list[Request],
              prompt_len: int | None = None) -> list[Request]:
        """Batch-to-completion oracle: FIFO groups of <= `batch_size`, each
        run to completion with `generate`.

        Every prompt is left-padded to ONE fixed `prompt_len` (default: the
        longest prompt in the set), so each request's context — and hence
        its greedy token stream — is independent of how requests are
        grouped into batches. That makes this the arrival-order-independent
        ground truth the continuous-batching loop
        (`repro.serve.continuous`) is differentially tested against.
        """
        P = prompt_len or max((len(r.prompt) for r in requests), default=1)
        for r in requests:
            if len(r.prompt) > P:
                raise ValueError(f"request {r.rid}: prompt length "
                                 f"{len(r.prompt)} exceeds prompt_len {P}")
        done: list[Request] = []
        for i in range(0, len(requests), self.B):
            group = requests[i:i + self.B]
            padded = [dataclasses.replace(
                r, prompt=[0] * (P - len(r.prompt)) + r.prompt, out=[])
                for r in group]
            for orig, p in zip(group, self.generate(padded)):
                orig.out = p.out
                orig.done = True
                done.append(orig)
        return done
