"""Sharded trigger inference engine over the JEDI-net forward paths.

The serving-tier counterpart of the paper's FPGA trigger pipeline: one
object owning everything between "a batch of events exists on the host"
and "logits are ready", for ANY registered forward path
(:mod:`repro.core.paths`).

Since the fabric split, the generic machinery — warm compile cache,
pad-to-bucket dispatch, async :class:`~repro.serving.core.PendingResult`
in-flight window, watchdog, overlap-safe wall-union KGPS accounting,
fault seams — lives in :class:`~repro.serving.core.ExecutionCore` and is
shared with every other workload (LM decode, recsys).  This module adds
only what is trigger-specific:

* **data-parallel sharding** — the batch axis is ``shard_map``-ped over
  the local device mesh (``launch/mesh.make_host_mesh``); each device
  runs the whole fused kernel on its batch slice, the serving analogue
  of replicating the FPGA pipeline per link.  On one device the wrapper
  collapses to a plain ``jit``.
* **PathSpec resolution** — forward fn, Pallas-ness, params transform
  (e.g. int8 quantization), supported compute dtypes, VMEM working set
  for the bucket ladder, roofline level are all read off the path's
  :class:`~repro.core.paths.PathSpec`; registering a new path makes it
  servable with no engine edits.
* **per-path bucket ladder** — buckets come from
  ``spec.bucket_ladder`` scaled to the mesh, so quantized paths (int8
  weights resident at 1 B/element) earn deeper ladders with no engine
  knowledge of why.

:class:`TriggerWorkload` is the :class:`~repro.serving.core.Workload`
declaration; :class:`ServingEngine` composes it with the core and adds
the trigger workload's surface (``spec``, ``cfg``, ``roofline``, ...).
"""

from __future__ import annotations

import copy
import functools

import jax
import numpy as np

from jax.sharding import PartitionSpec as P

from repro.core import paths as forward_paths
from repro.launch.mesh import make_host_mesh
from repro.parallel.sharding import shard_map_unchecked
from repro.serving.core import ExecutionCore, Workload
from repro.serving import faults
from repro.serving.metrics import ServingMetrics


class TriggerWorkload(Workload):
    """Jet-classification over one forward path, sharded over the mesh.

    The :class:`~repro.serving.core.Workload` declaration for the
    paper's trigger tier: dense ``(batch, N_o, P)`` event batches through
    a registered :class:`~repro.core.paths.PathSpec`, data-parallel over
    the local device mesh.
    """

    def __init__(self, params, cfg, *, forward: str = "fused_full",
                 interpret: bool | None = None, mesh="auto"):
        self.spec = forward_paths.get(forward)   # raises listing choices
        if self.spec.model != cfg.family:
            raise ValueError(
                f"path {forward!r} serves {self.spec.model} models, not a "
                f"{type(cfg).__name__}")
        if not self.spec.supports_dtype(cfg.compute_dtype):
            raise ValueError(
                f"path {forward!r} supports compute dtypes "
                f"{self.spec.compute_dtypes}, not {cfg.compute_dtype!r}")
        # the spec's params transform (e.g. int8 quantization) runs ONCE,
        # here — every dispatch then serves the transformed weights
        self.params = self.spec.prepare_params(params)
        self.cfg = cfg
        self.name = forward
        if mesh == "auto":
            mesh = make_host_mesh() if len(jax.devices()) > 1 else None
        self.mesh = mesh
        self.n_shards = int(np.prod(mesh.devices.shape)) if mesh else 1
        # compiled Pallas needs a TPU: decided once, from the platform of
        # the device this workload runs on.  An explicit value is obeyed:
        # interpret=False off-TPU fails to compile instead of quietly
        # running the interpreter
        self.platform = (mesh.devices.flat[0] if mesh is not None
                         else jax.devices()[0]).platform
        if interpret is None:
            interpret = self.platform != "tpu"
        self.interpret = bool(interpret) and self.spec.pallas

    def bucket_ladder(self, max_batch: int) -> list[int]:
        # ceil so the top rung still covers max_batch after the
        # per-device ladder is scaled back up by the shard count.
        # The ladder is the PATH'S policy (spec.bucket_ladder):
        # per-sample working set AND weight-residency reservation
        # both come off the spec, so quantized paths (int8 weights
        # resident at 1 B/element) earn deeper ladders here with no
        # fabric knowledge of why.
        per_dev = -(-max_batch // self.n_shards)
        ladder = self.spec.bucket_ladder(self.cfg, self.params, per_dev)
        return [b * self.n_shards for b in ladder]

    def validate_buckets(self, bucket_sizes) -> None:
        if self.mesh is not None:
            bad = [b for b in bucket_sizes if b % self.n_shards]
            if bad:
                raise ValueError(
                    f"buckets {bad} do not divide the {self.n_shards}-way "
                    "data mesh")

    def cache_key(self, bucket) -> tuple:
        c = self.cfg
        return (self.name, int(bucket), c.n_objects, c.n_features,
                c.compute_dtype, self.interpret, self.n_shards)

    def build(self, bucket=None):
        fn = self.spec.forward
        if self.spec.pallas:
            fn = functools.partial(fn, interpret=self.interpret)
        cfg = self.cfg

        def call(params, x):
            # fp32 matmuls at fp32 on every rung: a TPU's default
            # precision would run them as one bf16 pass (Pallas kernels
            # set their precision explicitly and are unaffected)
            with jax.default_matmul_precision("highest"):
                return fn(params, cfg, x)

        if self.mesh is not None:
            call = shard_map_unchecked(call, self.mesh,
                                       in_specs=(P(), P("data")),
                                       out_specs=P("data"))
        # the step is a function named ``trigger_step``: it is
        # ``jit_trigger_step`` in HLO and on the trace's XLA Modules line
        if self.spec.params_as_args:
            # no rung's program holds a copy of the weights, so its
            # compile-cache entry stays small
            def trigger_step(params, x):
                return call(params, x)

            return functools.partial(jax.jit(trigger_step), self.params)

        params = self.params

        def trigger_step(x):
            # the weights closed over: HLO constants of every rung
            return call(params, x)

        return jax.jit(trigger_step)

    def kernel_rows(self, bucket: int) -> int:
        # the path's own tile decision for the per-device slice of the
        # bucket: a Pallas kernel pads its batch to whole tiles
        model = self.spec.residency_model
        if model is None:
            return bucket
        per_dev = bucket // self.n_shards
        tiles = model(self.cfg, self.params, per_dev)
        return tiles["grid"][0] * tiles["block_b"] * self.n_shards

    def kernel_lane_pack(self, bucket: int) -> int:
        # read off the same tile decision; kernels without lane packing
        # leave the key out
        model = self.spec.residency_model
        if model is None:
            return 1
        tiles = model(self.cfg, self.params, bucket // self.n_shards)
        return int(tiles.get("lane_pack", 1))

    def kernel_pairs(self, bucket: int) -> int:
        # particle pairs the path evaluates per jet, as it declares
        per_jet = self.spec.pairs_per_sample
        return 0 if per_jet is None else bucket * int(per_jet(self.cfg))

    def placeholder(self, bucket: int) -> np.ndarray:
        c = self.cfg
        return np.zeros((bucket, c.n_objects, c.n_features), np.float32)

    def corrupted(self, seam: str, factor: float, bucket):
        # Silent fault seams: rebuild the bucket's compiled fn from
        # corrupted params.  Returning None means "does not apply"
        # (e.g. scale_drift on an fp32 path with no w_scale leaves),
        # and the armed fault keeps its budget.
        if seam == "scale_drift":
            bad = faults.drift_scales(self.params, factor)
        elif seam == "weight_corrupt":
            bad = faults.corrupt_weight(self.params, factor)
        else:
            return None
        if bad is self.params:
            return None
        twin = copy.copy(self)
        twin.params = bad
        return twin.build(bucket)


class ServingEngine(ExecutionCore):
    """Bucketed, sharded, metered inference over one forward path —
    the trigger instantiation of the execution core."""

    def __init__(self, params, cfg, *, forward: str = "fused_full",
                 interpret: bool | None = None, mesh="auto",
                 bucket_sizes=None, max_batch: int = 1024,
                 metrics: ServingMetrics | None = None, injector=None):
        super().__init__(
            TriggerWorkload(params, cfg, forward=forward,
                            interpret=interpret, mesh=mesh),
            bucket_sizes=bucket_sizes, max_batch=max_batch,
            metrics=metrics, injector=injector)

    # -- trigger-workload surface (historical engine API) -------------------

    @property
    def spec(self):
        return self.workload.spec

    @property
    def params(self):
        return self.workload.params

    @property
    def cfg(self):
        return self.workload.cfg

    @property
    def forward(self) -> str:
        return self.workload.name

    @property
    def interpret(self) -> bool:
        return self.workload.interpret

    @property
    def platform(self) -> str:
        return self.workload.platform

    @property
    def mesh(self):
        return self.workload.mesh

    @property
    def n_shards(self) -> int:
        return self.workload.n_shards

    def _build(self):
        return self.workload.build()

    # -- roofline context ----------------------------------------------------

    def roofline(self, buckets=None, *, compute_bytes: int = 2) -> dict:
        """TPUModel step-time context per bucket, at the spec's declared
        fusion level and weight precision."""
        return self.spec.roofline_for(
            self.cfg, buckets if buckets is not None else self.bucket_sizes,
            compute_bytes=compute_bytes, chips=max(self.n_shards, 1))
