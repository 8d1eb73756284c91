"""Measured stage execution: run model-zoo variants on a jax device mesh
and time their serving step for real.

This is the sim-to-real layer ROADMAP calls "measured, sharded stage
execution": every latency the controller optimizes comes from the analytic
``(alpha, beta)`` perf model (``cluster/perf_model.py``); the paper
validated against a live Kubernetes cluster. ``StageExecutor`` closes that
gap — it takes an architecture from the model zoo (``configs/`` via
``models/api.py``), lowers its decode serving step jitted + sharded across
a device mesh using the ``distributed/sharding.py`` rules (Pallas
``kernels/`` backing attention when ``backend="flash"``), and measures
per-(arch × batch × quant × mesh) step latency with warmup +
``block_until_ready`` min-of-k timing (``repro.timing``).

Compiled executables are cached in an explicit AOT ``ExecutableCache``
keyed by ``(arch, batch, quant, backend, mesh, seq_len)`` — each serving
step is a fresh closure, so ``jax.jit``'s implicit cache can never hit
across reconfigurations; without this cache recompilation dominates the
wall clock of any measurement sweep. ``cluster/calibration.py`` fits the
measured ``latency(b)`` curves back into per-variant ``(alpha, beta)``.
"""
from __future__ import annotations

import time  # reprolint: ignore[RPL002] compile timing on the host, outside traced code
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from repro import compat
from repro.configs import ARCHS
from repro.distributed import sharding as shd
from repro.launch import hlo_cost
from repro.models import api, steps
from repro.models.config import ArchConfig, InputShape
from repro.timing import time_fn

BACKENDS = ("reference", "flash")     # jnp attention | Pallas kernels
QUANT_BITS = {"int8": 8, "int4": 4}


def default_mesh():
    """A (1, n_devices) ("data", "model") mesh over every local device —
    tensor-parallel serving on whatever this host exposes. CPU CI forces
    multiple host devices via ``--xla_force_host_platform_device_count``."""
    n = len(jax.devices())
    return compat.make_mesh((1, n), ("data", "model"))


def quantize_params(params, quant: str):
    """The serving quantisation axis, executably: ``bf16`` casts weights to
    bfloat16; ``int8``/``int4`` symmetric-fake-quantise each float leaf to
    2^bits levels (stored bfloat16 — the measured backend has no integer
    matmul kernels, and the calibration records that truthfully)."""
    if quant == "bf16":
        return jax.tree.map(
            lambda x: x.astype(jnp.bfloat16)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
    bits = QUANT_BITS[quant]
    qmax = float(2 ** (bits - 1) - 1)

    def q(x):
        if not jnp.issubdtype(x.dtype, jnp.floating):
            return x
        scale = jnp.max(jnp.abs(x)) / qmax
        scale = jnp.where(scale == 0.0, 1.0, scale)
        levels = jnp.clip(jnp.round(x / scale), -qmax - 1, qmax)
        return (levels * scale).astype(jnp.bfloat16)

    return jax.tree.map(q, params)


@dataclass(frozen=True)
class ExecKey:
    """Identity of one compiled stage executable."""
    arch: str
    batch: int
    quant: str
    backend: str
    mesh: tuple[tuple[str, int], ...]
    seq_len: int


@dataclass
class _Entry:
    compiled: object
    compile_s: float
    cost: dict | None = None      # hlo_cost.analyze, computed lazily


@dataclass
class ExecutableCache:
    """AOT executable cache with hit/miss accounting. ``lookups ==
    hits + misses``; a repeated configuration never triggers a recompile."""
    entries: dict = field(default_factory=dict)
    hits: int = 0
    misses: int = 0

    @property
    def lookups(self) -> int:
        return self.hits + self.misses

    def hit_rate(self) -> float:
        return self.hits / self.lookups if self.lookups else 0.0

    def get_or_build(self, key: ExecKey, build) -> tuple[_Entry, bool]:
        """-> (entry, was_hit). ``build()`` runs only on a miss."""
        entry = self.entries.get(key)
        if entry is not None:
            self.hits += 1
            return entry, True
        self.misses += 1
        entry = build()
        self.entries[key] = entry
        return entry, False


@dataclass(frozen=True)
class StageTiming:
    """One measured point of a variant's latency curve."""
    arch: str
    batch: int
    quant: str
    backend: str
    device_class: str
    latency_s: float          # min-of-k measured step latency
    compile_s: float          # 0.0 on a cache hit
    cache_hit: bool
    flops: float              # trip-count-aware HLO cost (per device)
    bytes: float


class StageExecutor:
    """Executes model-zoo serving steps on a device mesh and measures them.

    ``smoke=True`` (the CPU default) runs each architecture's reduced
    same-family variant (``ArchConfig.smoke``) so the sweep fits host
    memory; ``smoke=False`` runs the published widths. ``n_layers`` cuts
    every architecture to that depth with its widths unchanged (a stage
    too deep for the mesh's memory). ``cache`` may be shared between
    executors (e.g. one per mesh shape) so a fleet-wide sweep reuses
    executables across device classes; executors sharing a cache must
    agree on ``smoke``, ``n_layers`` and ``seed``.
    """

    def __init__(self, mesh=None, *, seq_len: int = 32, smoke: bool = True,
                 n_layers: int | None = None, seed: int = 0,
                 cache: ExecutableCache | None = None):
        self.mesh = mesh if mesh is not None else default_mesh()
        self.seq_len = seq_len
        self.smoke = smoke
        self.n_layers = n_layers
        self.seed = seed
        self.cache = cache if cache is not None else ExecutableCache()
        self._params: dict = {}       # (arch, quant) -> placed pytree

    # ----------------------------------------------------------- identity --

    @property
    def n_devices(self) -> int:
        n = 1
        for v in self.mesh.shape.values():
            n *= v
        return n

    @property
    def device_class(self) -> str:
        """Label for calibration tables: platform + mesh width (e.g.
        ``cpu2``) — map it onto ``NodeSpec.device_class`` names via
        ``calibration.apply_to_cluster``."""
        return f"{jax.devices()[0].platform}{self.n_devices}"

    def mesh_key(self) -> tuple[tuple[str, int], ...]:
        return tuple((str(a), int(self.mesh.shape[a]))
                     for a in self.mesh.axis_names)

    def key_for(self, arch: str, batch: int, quant: str = "bf16",
                backend: str = "reference") -> ExecKey:
        return ExecKey(arch=arch, batch=int(batch), quant=quant,
                       backend=backend, mesh=self.mesh_key(),
                       seq_len=self.seq_len)

    # ------------------------------------------------------------- builds --

    def arch_config(self, arch: str, backend: str = "reference") -> ArchConfig:
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; one of {BACKENDS}")
        cfg = ARCHS[arch]
        if self.smoke:
            cfg = cfg.smoke()
        if self.n_layers is not None:
            cfg = cfg.replace(n_layers=self.n_layers)
        # weights are created in bf16 (every quant stores bf16), so they
        # never occupy device memory at float32 size
        return cfg.replace(use_flash=(backend == "flash"), dtype="bfloat16")

    def params_for(self, arch: str, quant: str = "bf16",
                   backend: str = "reference"):
        """Init-once, quantise, and place params under the mesh's sharding
        rules (cached — params are batch- and backend-independent, so the
        reference and flash steps run the same weights). One jitted program
        creates each shard on its own device: no device ever holds the whole
        unsharded model."""
        pkey = (arch, quant)
        if pkey not in self._params:
            cfg = self.arch_config(arch, backend)
            init_key = jax.random.fold_in(
                jax.random.PRNGKey(self.seed), len(self._params))
            psh = shd.param_shardings(cfg, self.mesh, kind="decode")
            init = jax.jit(
                lambda k: quantize_params(api.init_model(k, cfg), quant),
                out_shardings=psh)
            self._params[pkey] = init(init_key)
        return self._params[pkey]

    def _inputs(self, cfg: ArchConfig, shape: InputShape, data_key):
        """Concrete decode-step (batch, cache) placed per the mesh rules."""
        batch = {"tokens": jax.random.randint(
            data_key, (shape.global_batch, 1), 0, cfg.vocab, dtype=jnp.int32)}
        ctx = steps.cache_context(cfg, shape)
        cache = api.init_cache(cfg, shape.global_batch, max(ctx, 1))
        bsh = shd.batch_shardings(cfg, shape, self.mesh)
        csh = shd.cache_shardings(cfg, shape, self.mesh)
        return (jax.device_put(batch, bsh), jax.device_put(cache, csh),
                bsh, csh)

    def compiled_step(self, arch: str, batch: int, quant: str = "bf16",
                      backend: str = "reference"):
        """-> (entry, args, was_hit): the AOT-compiled serving step for one
        configuration plus ready-to-call placed arguments."""
        key = self.key_for(arch, batch, quant, backend)
        cfg = self.arch_config(arch, backend)
        shape = InputShape(name=f"serve_b{batch}", seq_len=self.seq_len,
                           global_batch=batch, kind="decode")
        params = self.params_for(arch, quant, backend)
        data_key = jax.random.fold_in(jax.random.PRNGKey(self.seed + 1),
                                      batch)
        batch_in, cache_in, bsh, csh = self._inputs(cfg, shape, data_key)

        def build() -> _Entry:
            step = steps.make_serve_step(cfg, shape)
            psh = shd.param_shardings(cfg, self.mesh, kind="decode")
            with jax.set_mesh(self.mesh):
                lowered = jax.jit(step, in_shardings=(psh, bsh, csh)).lower(
                    params, batch_in, cache_in)
                t0 = time.perf_counter()
                compiled = lowered.compile()
            return _Entry(compiled=compiled,
                          compile_s=time.perf_counter() - t0)

        entry, was_hit = self.cache.get_or_build(key, build)
        return entry, (params, batch_in, cache_in), was_hit

    # -------------------------------------------------------- measurement --

    def cost(self, entry: _Entry) -> dict:
        """Trip-count-aware per-device flops/bytes of a compiled step
        (``launch/hlo_cost.py`` — XLA's own cost_analysis counts scanned
        layer stacks once)."""
        if entry.cost is None:
            entry.cost = hlo_cost.analyze(entry.compiled.as_text())
        return entry.cost

    def measure(self, arch: str, batch: int, quant: str = "bf16",
                backend: str = "reference", *, reps: int = 5,
                warmup: int = 1) -> StageTiming:
        """Min-of-``reps`` measured step latency for one configuration.

        Compilation happens outside the timed region (AOT, cached); each
        timed pass ``block_until_ready``s the step output. The returned
        timing carries the HLO roofline inputs for this executable.
        """
        entry, args, was_hit = self.compiled_step(arch, batch, quant, backend)
        timing = time_fn(lambda: entry.compiled(*args),
                         reps=reps, warmup=warmup)
        cost = self.cost(entry)
        return StageTiming(
            arch=arch, batch=int(batch), quant=quant, backend=backend,
            device_class=self.device_class, latency_s=timing.best,
            compile_s=0.0 if was_hit else entry.compile_s,
            cache_hit=was_hit, flops=float(cost["flops"]),
            bytes=float(cost["bytes"]))

    def measure_curve(self, arch: str, batches, quant: str = "bf16",
                      backend: str = "reference", *, reps: int = 5,
                      warmup: int = 1) -> list[StageTiming]:
        """The variant's measured ``latency(b)`` curve across ``batches`` —
        the calibration fit's input."""
        return [self.measure(arch, b, quant, backend, reps=reps,
                             warmup=warmup) for b in batches]
