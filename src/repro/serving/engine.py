"""Pipeline serving engine: real JAX models behind each stage.

StageServer = one task's model variants (ArchConfigs) behind one callable
that runs a chosen variant on a batch of tokens. It holds no queue and no
configuration: the event-driven runtime (`serving.runtime`) queues and
batches requests, picks each stage's variant, batch size and replicas, and
attaches the server as the stage's ``executor``.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models import api
from repro.models.config import ArchConfig


class StageServer:
    """A stage's variants behind one callable ``server(z, tokens)``.

    A variant's weights are created, in its config's dtype, the first time
    a batch is dispatched to it — a stage holds device memory only for the
    variants the controller actually runs. Each distinct (variant, batch
    size) is compiled once, ahead of time, and ``_compiled[(z, B)]`` holds
    that executable together with the family's stub inputs (``enc_states``
    for ``audio``, ``vision_embeds`` for ``vlm``, none for a decoder):
    drawn on the first call of ``(z, B)`` and fed to every later one, which
    then runs no device program before the forward. Clearing ``_compiled``
    releases both. ``stats()`` reports what ran and what compiling cost.
    """

    def __init__(self, name: str, variants: list[ArchConfig], *,
                 seq_len: int = 32, seed: int = 0):
        self.name = name
        self.variants = variants
        self.seq_len = seq_len
        self.seed = seed
        self.params: dict[int, object] = {}       # z -> weights, on demand
        # (z, B) -> (executable, stub inputs)
        self._compiled: dict[tuple[int, int], tuple[object, dict]] = {}
        self.compile_s = 0.0
        self.stub_inputs = {"drawn": 0, "reused": 0}  # stage calls, by path
        self.batches: dict[int, int] = {}         # z -> batches executed

    @property
    def cfg(self) -> ArchConfig:
        """The first variant, the one ``z = 0`` runs."""
        return self.variants[0]

    def weights(self, z: int):
        """Variant ``z``'s weights, created by one jitted program on first
        use (a bf16 variant is drawn in bf16 and never exists in float32)."""
        if z not in self.params:
            cfg = self.variants[z]
            self.params[z] = jax.jit(lambda k: api.init_model(k, cfg))(
                jax.random.PRNGKey(self.seed + z))
        return self.params[z]

    def _executable(self, z: int, batch: dict):
        """(z, B)'s compiled forward, compiled on a miss and kept with the
        batch's stub inputs."""
        key = (z, batch["tokens"].shape[0])
        if key not in self._compiled:
            cfg = self.variants[z]

            def fwd(params, batch):
                logits, _ = api.forward(params, batch, cfg)
                return jnp.argmax(logits, axis=-1)

            with TraceAnnotation("stage.compile", z=z, batch=key[1]):
                t0 = time.perf_counter()
                exe = jax.jit(fwd).lower(self.weights(z), batch).compile()
                self.compile_s += time.perf_counter() - t0
            stubs = {k: v for k, v in batch.items() if k != "tokens"}
            self._compiled[key] = (exe, stubs)
        return self._compiled[key][0]

    @staticmethod
    def _host_tokens(tokens: np.ndarray, cfg: ArchConfig) -> np.ndarray:
        """Token ids on the host as int32: the compiled call transfers
        them, so no device program converts them."""
        return np.asarray(tokens % cfg.vocab, dtype=np.int32)

    def _make_batch(self, tokens: np.ndarray, cfg: ArchConfig) -> dict:
        """The forward's inputs: the tokens and the family's stub inputs,
        drawn op by op from a fixed key."""
        batch = {"tokens": self._host_tokens(tokens, cfg)}
        B = tokens.shape[0]
        dt = cfg.param_dtype
        if cfg.family == "vlm":
            key = jax.random.PRNGKey(0)
            batch["vision_embeds"] = jax.random.normal(
                key, (B, cfg.n_patches, cfg.d_model), dt) * 0.02
        if cfg.family == "audio":
            key = jax.random.PRNGKey(1)
            batch["enc_states"] = jax.random.normal(
                key, (B, cfg.enc_len, cfg.d_model), dt) * 0.02
        return batch

    def execute(self, z: int, tokens: np.ndarray) -> np.ndarray:
        """Run variant ``z`` on tokens [B, S] -> output tokens [B, S].

        This is the real-JAX execution hook: the event-driven runtime
        (serving.runtime) can attach the server as a stage ``executor`` so
        virtual time is charged analytically while outputs flow through
        live models. Batches arrive at their actual size (no tail padding)
        — each distinct (z, B) compiles once and is then reused.
        """
        z = int(z) % len(self.variants)
        cfg = self.variants[z]
        kept = self._compiled.get((z, tokens.shape[0]))
        reused = kept is not None and bool(kept[1])
        with TraceAnnotation("stage.prepare", batch=tokens.shape[0],
                             reused=int(reused)):
            if kept is None:
                batch = self._make_batch(tokens, cfg)
            else:
                batch = {"tokens": self._host_tokens(tokens, cfg), **kept[1]}
        if len(batch) > 1:                   # the family has stub inputs
            self.stub_inputs["reused" if reused else "drawn"] += 1
        with TraceAnnotation("stage.dispatch"):
            # returns before the device ends the forward
            out = self._executable(z, batch)(self.weights(z), batch)
        self.batches[z] = self.batches.get(z, 0) + 1
        with TraceAnnotation("stage.wait"):
            return np.asarray(out)

    __call__ = execute

    def stats(self) -> dict:
        """JSON-safe record of what this stage executed: per variant run,
        its dtype and batch count; the variants holding weights; distinct
        shapes compiled and the seconds spent compiling them; and the stage
        calls that drew their stub inputs or reused kept ones."""
        return {"stage": self.name,
                "executed": {self.variants[z].name:
                             {"batches": n, "dtype": self.variants[z].dtype}
                             for z, n in sorted(self.batches.items())},
                "loaded": [self.variants[z].name for z in sorted(self.params)],
                "compiled_shapes": len(self._compiled),
                "compile_s": self.compile_s,
                "stub_inputs": dict(self.stub_inputs)}
