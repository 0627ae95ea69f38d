"""First-class forward-path registry: one declarative API per path.

The paper's co-design loop (Sec. 4.4) works because every candidate
design exposes the same knobs — precision, fusion level, parallelism —
through one hardware template.  This module is the software analogue:
a :class:`PathSpec` declaratively bundles everything a forward path
*is* — the forward fn, its numerical reference, the fusion level the
roofline should model it at, supported compute dtypes, an optional
params-transform hook (e.g. quantization), the VMEM working-set model
the bucket ladder derives from, and a roofline hook — so the serving
engine, batcher, CLI and the chip benchmark all introspect ONE object
instead of agreeing by convention across five files.

Registering a path makes it appear everywhere with zero consumer
edits::

    from repro.core.paths import register_path

    @register_path(name="my_path", ref=my_ref, fused_level="full",
                   tolerance=1e-4)
    def forward_my_path(params, cfg, x, *, interpret=False):
        ...

``paths.available()`` / ``paths.get(name)`` are the only lookups any
consumer performs; tag filters (``available(quantized=True)``,
``available(pallas=True)``, ``available(complexity="O(N)")``) answer
capability queries.  This registry IS the forward-path API: the
pre-registry surfaces (a flat forward-fn dict, lazy path-name
snapshots) are gone, and a repo-hygiene test keeps them gone.

Built-in paths live in the modules listed in :data:`_BUILTIN_MODULES`;
they are imported lazily on first registry access so importing
``repro.core.paths`` stays dependency-free (no jax work at import).
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable, Sequence

#: The fusion tiers a path can achieve, in increasing order (see
#: ``codesign.TPUModel.hbm_bytes``): "none" round-trips B/E through HBM,
#: "edge" keeps them in VMEM, "full" keeps every intermediate on-chip.
FUSED_LEVELS = ("none", "edge", "full")

#: Algorithmic complexity classes in N_o (the aggregation strategy):
#: "O(N^2)" — the dense pairwise edge grid; "O(N)" — JEDI-linear
#: globally-pooled aggregation.  A validated vocabulary (not free text)
#: so ``available(complexity="O(N)")`` can never silently miss a typo'd
#: registration.
COMPLEXITY_CLASSES = ("O(N^2)", "O(N)")


@dataclasses.dataclass(frozen=True)
class PathSpec:
    """Everything one forward path is, in one declarative object.

    ``forward`` / ``ref`` share the signature ``(params, cfg, x) ->
    logits`` (Pallas-backed paths additionally accept ``interpret=``;
    set ``pallas=True`` so consumers know to thread it).  When
    ``transform_params`` is set, BOTH fns receive the transformed
    params — the hook runs once, at bind time (e.g. the engine's
    constructor), not per call.
    """

    name: str
    forward: Callable                       # (params, cfg, x, ...) -> logits
    ref: Callable                           # numerical oracle, same signature
    fused_level: str = "none"               # roofline tier (FUSED_LEVELS)
    pallas: bool = False                    # Pallas kernel: interpret= off-TPU
    compute_dtypes: tuple = ("float32", "bfloat16")
    transform_params: Callable | None = None   # params -> params (quantize, ...)
    tolerance: float = 2e-4                 # max |forward - ref| in fp32
    quantized: bool = False                 # tag: weights are sub-fp32
    weight_bytes: int | None = None         # roofline weight precision override
    per_sample_bytes: Callable | None = None   # (cfg, params) -> bytes/jet
    fallback: str | None = None             # degrade-to path (see fallback_chain)
    complexity: str = "O(N^2)"              # aggregation class (COMPLEXITY_CLASSES)
    flops_model: Callable | None = None     # (cfg, batch) -> FLOPs of one step
    residency_model: Callable | None = None  # (cfg, params, batch) -> modeled
    #   tiling/residency dict (the kernel autotuner's introspection hook,
    #   e.g. fused_jedinet.autotune.modeled_residency) — what the static
    #   kernel-contract auditor cross-checks the traced pallas_call
    #   against.  Required for pallas=True paths (the auditor flags its
    #   absence); meaningless for XLA paths.
    model: str = "jedinet"                  # family of the configs it serves
    memory_budget_bytes: int | None = None  # HBM a bucket may fill; None:
    #   the ladder is sized against VMEM (see bucket_ladder)
    pairs_per_sample: Callable | None = None   # (cfg) -> pairs per jet
    bytes_model: Callable | None = None     # (cfg, batch, compute_bytes)
    #   -> HBM bytes of one step, for the roofline (None: TPUModel's
    #   JEDI-net traffic at ``fused_level``)
    params_as_args: bool = False            # the served step takes the
    #   weights as a jit argument; False: they are HLO constants of every
    #   rung (serving.engine.TriggerWorkload.build)
    description: str = ""

    def __post_init__(self):
        if self.fused_level not in FUSED_LEVELS:
            raise ValueError(
                f"path {self.name!r}: fused_level {self.fused_level!r} "
                f"not in {FUSED_LEVELS}")
        if self.complexity not in COMPLEXITY_CLASSES:
            raise ValueError(
                f"path {self.name!r}: complexity {self.complexity!r} "
                f"not in {COMPLEXITY_CLASSES}")

    # -- hooks with defaults -------------------------------------------------

    def prepare_params(self, params):
        """Apply the params-transform hook (identity when none)."""
        if self.transform_params is None:
            return params
        return self.transform_params(params)

    def supports_dtype(self, compute_dtype: str) -> bool:
        return compute_dtype in self.compute_dtypes

    def bucket_bytes(self, cfg, params) -> int:
        """Per-sample working set driving the serving bucket ladder: in
        VMEM, or in HBM for a path that sets ``memory_budget_bytes``.

        Defaults to the sender-TILED whole-network kernel's estimate at
        the smallest sender tile — the deepest honest ladder, since the
        kernel-side 2D autotuner can always fall back to that tile to
        fit any rung the ladder derives from it.
        """
        if self.per_sample_bytes is not None:
            return int(self.per_sample_bytes(cfg, params))
        from repro.kernels.fused_jedinet.autotune import (
            full_forward_tiled_bytes_per_sample, mlp_widths,
            sender_tile_candidates)
        return full_forward_tiled_bytes_per_sample(
            cfg.n_objects, cfg.n_features,
            mlp_widths(params["fr"]), mlp_widths(params["fo"]),
            mlp_widths(params["phi"]),
            block_s=sender_tile_candidates(cfg.n_objects)[0])

    def reserved_vmem_bytes(self, cfg, params) -> int:
        """VMEM the path's weights occupy before any batch row arrives,
        at their ACTUAL serving dtype — int8-quantized params reserve
        ~4x less than fp32, which is how quantized paths earn deeper
        bucket ladders (ROADMAP "per-path quantization-aware bucket
        policy").  ``params`` must already be transformed
        (:meth:`prepare_params`)."""
        from repro.kernels.autotune import weight_vmem_bytes
        return weight_vmem_bytes(params, cfg.compute_dtype)

    def bucket_ladder(self, cfg, params, max_batch: int,
                      budget_bytes: int | None = None) -> list[int]:
        """The serving pad-to-bucket ladder this path earns: rungs from
        :func:`repro.kernels.autotune.bucket_ladder` under the path's
        OWN per-sample working set and weight-residency reservation —
        the per-path policy every consumer (engine, CLI ``--list-paths``,
        the chip benchmark) resolves through one call.

        A path that sets ``memory_budget_bytes`` (an XLA path whose
        activations live in HBM) sizes its rungs against that budget
        instead of VMEM, and its ladder stops at the largest rung whose
        whole working set fits."""
        from repro.kernels import autotune
        per = self.bucket_bytes(cfg, params)
        reserved = self.reserved_vmem_bytes(cfg, params)
        if budget_bytes is None:
            budget_bytes = (self.memory_budget_bytes
                            or autotune.VMEM_BUDGET_BYTES)
        if self.memory_budget_bytes is not None:
            # one call holds its whole bucket in that memory, with no
            # batch grid to tile it: no rung above the largest that fits
            max_batch = min(max_batch, autotune.fitting_batch(
                per, budget_bytes, reserved))
        return autotune.bucket_ladder(max_batch, per, budget_bytes,
                                      reserved_bytes=reserved)

    def flops_for(self, cfg, batch: int) -> float:
        """Modeled FLOPs of one batched forward step through this path.

        The per-path FLOPs hook: O(N) paths plug in their own model
        (``codesign.jedi_linear_flops``) so codesign/roofline reason
        about the algorithmic class, not just bytes; the default is the
        dense edge-grid model (``codesign.TPUModel.flops``)."""
        if self.flops_model is not None:
            return float(self.flops_model(cfg, batch))
        from repro.core import codesign
        return float(codesign.TPUModel.flops(cfg, batch))

    def roofline_for(self, cfg, buckets, *, compute_bytes: int = 2,
                     chips: int = 1) -> dict:
        """TPUModel roofline per bucket at this path's declared level
        (and weight precision / FLOPs model, for quantized and O(N)
        paths)."""
        from repro.core import codesign
        return codesign.bucket_roofline(
            cfg, buckets, level=self.fused_level,
            compute_bytes=compute_bytes, chips=chips,
            weight_bytes=self.weight_bytes, flops_fn=self.flops_model,
            bytes_fn=self.bytes_model)

    def audit(self, cfg, params, *, max_batch: int = 1024) -> list:
        """Statically audit this path's kernel contract: trace the
        forward at every rung of its bucket ladder (abstract shapes, no
        kernel execution) and cross-check the pallas_call's grid /
        BlockSpecs / scratch / accumulator dtypes against
        :attr:`residency_model` and the VMEM budget.  Returns the list
        of findings (empty == contract holds).  ``params`` are RAW
        (untransformed) — the audit applies :meth:`prepare_params`
        itself so it sees the serving-time pytree."""
        from repro.analysis.kernel_audit import audit_path
        return audit_path(self, cfg, params, max_batch=max_batch)


# ---------------------------------------------------------------------------
# Registry.
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, PathSpec] = {}

# Modules that register built-in paths at import.  Imported lazily on
# first registry access, so a path lives entirely in its own module and
# still shows up in every consumer (engine, CLI, the chip benchmark).
_BUILTIN_MODULES = (
    "repro.core.interaction_net",
    "repro.core.int8_path",
    "repro.core.jedi_linear_path",
    "repro.core.particle_transformer",
)
_builtins_state = "pending"           # "pending" -> "loading" -> "done"


def _ensure_builtins() -> None:
    global _builtins_state
    if _builtins_state != "pending":  # "loading": modules re-enter via register
        return
    _builtins_state = "loading"
    try:
        for mod in _BUILTIN_MODULES:
            importlib.import_module(mod)
    except Exception:
        # don't latch a silently partial registry: the next registry
        # access retries (already-imported modules are sys.modules-cached,
        # so their register() calls don't re-run) and fails loudly again
        _builtins_state = "pending"
        raise
    _builtins_state = "done"


def register(spec: PathSpec, *, overwrite: bool = False) -> PathSpec:
    """Register a :class:`PathSpec`; returns it for chaining."""
    if not overwrite and spec.name in _REGISTRY:
        raise ValueError(f"forward path {spec.name!r} already registered")
    _REGISTRY[spec.name] = spec
    return spec


def register_path(name: str | None = None, **fields):
    """Decorator: register the decorated fn as a forward path.

        @register_path(name="int8_fused_full", ref=..., fused_level="full")
        def forward_int8_fused_full(params, cfg, x, *, interpret=False): ...

    ``name`` defaults to the fn's ``__name__`` with a leading
    ``forward_`` stripped.  The fn itself is returned unchanged.
    """
    def deco(fn):
        pname = name or fn.__name__.removeprefix("forward_")
        register(PathSpec(name=pname, forward=fn, **fields))
        return fn
    return deco


def get(name: str) -> PathSpec:
    """The spec for ``name``; raises ValueError listing the choices."""
    _ensure_builtins()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown forward path {name!r}; "
            f"available: {', '.join(sorted(_REGISTRY))}") from None


def specs(**tags: Any) -> list[PathSpec]:
    """All registered specs, sorted by name, filtered by spec fields.

    Any :class:`PathSpec` field is a filter: ``specs(quantized=True)``,
    ``specs(pallas=False, fused_level="full")``.  Unknown field names
    raise (a typo'd filter silently matching nothing is worse).
    """
    _ensure_builtins()
    for k in tags:
        if k not in PathSpec.__dataclass_fields__:
            raise ValueError(f"unknown PathSpec filter field {k!r}")
    return [s for _, s in sorted(_REGISTRY.items())
            if all(getattr(s, k) == v for k, v in tags.items())]


def available(**tags: Any) -> list[str]:
    """Names of all registered paths (sorted), filtered like :func:`specs`."""
    return [s.name for s in specs(**tags)]


def fallback_chain(name: str) -> list[str]:
    """The degradation ladder rooted at ``name``: ``[name, fallback,
    fallback-of-fallback, ...]`` down to a terminal path.

    The serving tier demotes along this chain when a rung fails (compile
    error, VMEM-fit rejection, non-finite outputs — see
    :mod:`repro.serving.resilient`), so the chain must be a safe ladder:
    every link resolves to a registered path, no cycles, and the
    terminal rung is a **non-Pallas reference path** — plain XLA cannot
    compile-fail the way a hand-written kernel can, so the bottom of the
    ladder always serves.  Raises ``ValueError`` on any violation.
    """
    chain, seen = [], set()
    cur: str | None = name
    while cur is not None:
        if cur in seen:
            raise ValueError(
                f"fallback chain of {name!r} cycles at {cur!r}: "
                f"{' -> '.join(chain + [cur])}")
        spec = get(cur)        # raises listing choices on unknown links
        chain.append(cur)
        seen.add(cur)
        cur = spec.fallback
    terminal = get(chain[-1])
    if terminal.pallas:
        raise ValueError(
            f"fallback chain of {name!r} terminates in Pallas path "
            f"{terminal.name!r} ({' -> '.join(chain)}); chains must end "
            "in a non-Pallas reference path so the degradation ladder "
            "always has a servable bottom rung")
    return chain


def terminal_rung(name: str) -> str:
    """The non-Pallas reference path at the bottom of ``name``'s
    fallback chain — the rung the sentinel's shadow re-execution trusts
    as its online oracle (:mod:`repro.serving.sentinel`), and the one
    :func:`fallback_chain` guarantees always serves."""
    return fallback_chain(name)[-1]


def validate_fallbacks() -> dict[str, list[str]]:
    """Resolve every registered path's fallback chain; raises on the
    first broken one (unknown link, cycle, or Pallas terminal).  Returns
    ``{name: chain}`` — the registry-wide degradation map."""
    return {name: fallback_chain(name) for name in available()}


def describe(names: Sequence[str] | None = None, *, cfg=None, params=None,
             max_batch: int = 1024) -> str:
    """Human-readable registry table (the CLI's ``--list-paths``).

    The static columns (fusion level, kernel kind, compute dtypes,
    roofline ``wB`` = weight bytes, tolerance) always print.  Given a
    ``cfg`` AND raw ``params``, each path's RESOLVED bucket policy is
    appended — per-sample VMEM bytes, weight-residency reservation and
    the bucket ladder it earns for ``max_batch`` — so an operator can
    see directly why a quantized path (smaller reservation) gets a
    deeper ladder than its fp32 twin.
    """
    rows = [get(n) for n in (names if names is not None else available())]
    lines = [f"{'path':<22} {'level':<5} {'cmplx':<6} {'kernel':<7} "
             f"{'dtypes':<18} {'wB':<3} {'tol':<7} "
             f"{'fallback chain':<34} description"]
    for s in rows:
        kind = "pallas" if s.pallas else "xla"
        if s.quantized:
            kind += "+q"
        wb = "-" if s.weight_bytes is None else str(s.weight_bytes)
        try:
            chain = fallback_chain(s.name)
            fb = ">".join(chain[1:]) if len(chain) > 1 else "-"
        except ValueError as e:          # surface broken chains, don't crash
            fb = f"!invalid ({e})"
        lines.append(
            f"{s.name:<22} {s.fused_level:<5} {s.complexity:<6} {kind:<7} "
            f"{','.join(s.compute_dtypes):<18} {wb:<3} {s.tolerance:<7.0e} "
            f"{fb:<34} {s.description}")
    if cfg is not None and params is not None:
        from repro.core.codesign import path_bucket_policy
        lines.append("")
        lines.append(f"bucket policy @ n_objects={cfg.n_objects} "
                     f"max_batch={max_batch} (per-path memory model; "
                     f"{cfg.family} paths):")
        lines.append(f"{'path':<22} {'B/sample':>9} {'reservedB':>10} ladder")
        for s in rows:
            if s.model != cfg.family:
                continue
            pol = path_bucket_policy(s, cfg, params, max_batch=max_batch,
                                     roofline=False)
            lines.append(
                f"{s.name:<22} {pol['per_sample_bytes']:>9} "
                f"{pol['reserved_vmem_bytes']:>10} "
                f"{','.join(str(b) for b in pol['bucket_ladder'])}")
    return "\n".join(lines)
