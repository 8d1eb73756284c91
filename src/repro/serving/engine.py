"""Pipeline serving engine: real JAX models behind each stage.

StageServer = one task's deployment: a model variant (ArchConfig), a batch
size, and a replica count (replicas are data-parallel splits of a batch; on
the CPU dev box they execute sequentially but the abstraction mirrors the
mesh "data"-axis replica groups of the production launch).

PipelineServer chains stages (the paper's gRPC hops) and implements
``apply_config`` — the Kubernetes-API reconfiguration the OPD agent calls:
switching a stage's variant swaps model params (a re-shard/cold-start in
production, charged by the simulator).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.mdp import Config
from repro.models import api
from repro.models.config import ArchConfig
from repro.serving.batcher import Batcher, Request


class StageServer:
    """A stage's variants behind one callable ``server(z, tokens)``.

    A variant's weights are created, in its config's dtype, the first time
    a batch is dispatched to it — a stage holds device memory only for the
    variants the controller actually runs. Each distinct (variant, batch
    size) is compiled once, ahead of time; ``stats()`` reports what ran and
    what compiling cost.
    """

    def __init__(self, name: str, variants: list[ArchConfig], *,
                 seq_len: int = 32, batch_size: int = 4, replicas: int = 1,
                 seed: int = 0):
        self.name = name
        self.variants = variants
        self.seq_len = seq_len
        self.seed = seed
        self.z = 0
        self.replicas = replicas
        self.batcher = Batcher(batch_size, seq_len)
        self.params: dict[int, object] = {}       # z -> weights, on demand
        self._compiled: dict[tuple[int, int], object] = {}  # (z, B) -> exe
        self.compile_s = 0.0
        self.batches: dict[int, int] = {}         # z -> batches executed
        self.served = 0

    @property
    def cfg(self) -> ArchConfig:
        return self.variants[self.z]

    def weights(self, z: int):
        """Variant ``z``'s weights, created by one jitted program on first
        use (a bf16 variant is drawn in bf16 and never exists in float32)."""
        if z not in self.params:
            cfg = self.variants[z]
            self.params[z] = jax.jit(lambda k: api.init_model(k, cfg))(
                jax.random.PRNGKey(self.seed + z))
        return self.params[z]

    def _executable(self, z: int, batch: dict):
        key = (z, batch["tokens"].shape[0])
        if key not in self._compiled:
            cfg = self.variants[z]

            def fwd(params, batch):
                logits, _ = api.forward(params, batch, cfg)
                return jnp.argmax(logits, axis=-1)

            t0 = time.perf_counter()
            self._compiled[key] = jax.jit(fwd).lower(
                self.weights(z), batch).compile()
            self.compile_s += time.perf_counter() - t0
        return self._compiled[key]

    def configure(self, *, z: int | None = None, batch_size: int | None = None,
                  replicas: int | None = None):
        if z is not None:
            self.z = int(z) % len(self.variants)
        if batch_size is not None:
            self.batcher.batch_size = int(batch_size)
        if replicas is not None:
            self.replicas = int(replicas)

    def _make_batch(self, tokens: np.ndarray, cfg: ArchConfig) -> dict:
        batch = {"tokens": jnp.asarray(tokens % cfg.vocab)}
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
        batch = self._make_batch(tokens, self.variants[z])
        out = self._executable(z, batch)(self.weights(z), batch)
        self.batches[z] = self.batches.get(z, 0) + 1
        return np.asarray(out)

    __call__ = execute

    def stats(self) -> dict:
        """JSON-safe record of what this stage executed: per variant run,
        its dtype and batch count; the variants holding weights; distinct
        shapes compiled and the seconds spent compiling them."""
        return {"stage": self.name,
                "executed": {self.variants[z].name:
                             {"batches": n, "dtype": self.variants[z].dtype}
                             for z, n in sorted(self.batches.items())},
                "loaded": [self.variants[z].name for z in sorted(self.params)],
                "compiled_shapes": len(self._compiled),
                "compile_s": self.compile_s}

    def serve_pending(self) -> list[Request]:
        """Drain the queue; returns completed requests with stage output."""
        done = []
        while True:
            nb = self.batcher.next_batch()
            if nb is None:
                return done
            reqs, toks = nb
            # replicas split the batch (data parallel); sequential on CPU
            out = self.execute(self.z, toks)
            for i, req in enumerate(reqs):
                req.stage_outputs.append(out[i])
                req.result = out[i]
                done.append(req)
            self.served += len(reqs)


class PipelineServer:
    def __init__(self, stages: list[StageServer]):
        self.stages = stages
        self.completed: list[Request] = []
        self.switch_count = 0

    def apply_config(self, cfg: Config, batch_choices: list[int] | None = None):
        """The OPD action -> live reconfiguration (paper: K8s Python API)."""
        for n, stage in enumerate(self.stages):
            if stage.z != cfg.z[n] % len(stage.variants):
                self.switch_count += 1
            stage.configure(z=cfg.z[n], batch_size=cfg.b[n], replicas=cfg.f[n])

    def submit(self, req: Request):
        self.stages[0].batcher.put(req)

    def process(self) -> list[Request]:
        """Push every queued request through all stages (gRPC chain)."""
        for i, stage in enumerate(self.stages):
            finished = stage.serve_pending()
            if i + 1 < len(self.stages):
                for req in finished:
                    # next stage consumes this stage's output tokens
                    req.tokens = np.asarray(req.result, dtype=np.int32)
                    self.stages[i + 1].batcher.put(req)
            else:
                self.completed.extend(finished)
        return self.completed
