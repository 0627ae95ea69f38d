"""Logical-axis sharding: one rules table maps model-semantic axes to mesh axes.

Model code annotates activations with *logical* axis names
(``constrain(h, "batch", "seq", None)``); the launcher installs an
``axis_rules`` context binding those names to physical mesh axes for the
active mesh (single-pod ``(data, model)`` or multi-pod ``(pod, data,
model)``).  Outside a context every annotation is a no-op, so unit tests and
CPU examples run unsharded with the exact same model code.

Parameter sharding is path-regex based (``PARAM_RULES``): a handful of rules
per family cover embeddings, attention, MLP, MoE experts, GNN and recsys
tables.  Weights are sharded over BOTH mesh axes where possible
(tensor-parallel over ``model`` + FSDP/ZeRO-3 over ``data``) so the 480B
Arctic checkpoint fits 256 x 16 GiB chips; XLA inserts the corresponding
all-gathers / reduce-scatters.

Non-divisible cases (e.g. 56 heads over 16-way ``model``) are allowed: the
SPMD partitioner pads. The roofline analysis charges that padding honestly.
"""

from __future__ import annotations

import contextlib
import re
import threading
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common.tree import path_map

def shard_map_unchecked(fn, mesh, *, in_specs, out_specs):
    """``jax.shard_map`` with the VMA (replication) check off — required
    for bodies containing ``pallas_call`` (no replication rule) or
    manual collectives the checker cannot type."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


_CTX = threading.local()


# Logical axis -> tuple of mesh axes that shard it (filtered by mesh).
DEFAULT_RULES = {
    # activations
    "batch": ("pod", "data"),
    "seq": ("model",),                # sequence-parallel residual stream
    "tokens": ("pod", "data", "model"),  # flattened (batch*seq) token axis
    "embed": (),
    "heads": ("model",),
    "kv_heads": ("model",),
    "qlen": (),
    "kvlen": ("model",),       # seq-sharded KV cache when kv_heads < model
    "head_dim": (),
    "mlp": ("model",),
    "vocab": ("model",),
    "classes": (),
    # MoE
    "expert": ("data",),
    # NB: sharding expert_slot over `model` was tried in §Perf cell B and
    # measured neutral (227 vs 231 GB/device collectives) — the dispatch
    # scatter still all-gathers its payload; see EXPERIMENTS.md §Perf.
    "expert_slot": (),
    # graphs: node/edge sets are sharded over the full chip set
    "nodes": ("pod", "data", "model"),
    "edges": ("pod", "data", "model"),
    "graph_feat": (),
    # recsys
    "table_rows": ("pod", "data", "model"),
    "candidates": ("pod", "data", "model"),
    # weights
    "fsdp": ("data",),
    "w_model": ("model",),
    "replicated": (),
    # pipeline stage axis (only bound when PP over pods is enabled)
    "stage": ("pod",),
}


@contextlib.contextmanager
def axis_rules(mesh: Mesh, rules: Optional[dict] = None):
    """Install a (mesh, logical-rules) context for `constrain`."""
    prev = getattr(_CTX, "state", None)
    _CTX.state = (mesh, dict(DEFAULT_RULES, **(rules or {})))
    try:
        yield
    finally:
        _CTX.state = prev


def current_mesh() -> Optional[Mesh]:
    st = getattr(_CTX, "state", None)
    return st[0] if st else None


def _filter_axes(axes, mesh: Mesh):
    """Keep only axes present in the mesh (e.g. drop 'pod' on single-pod)."""
    present = tuple(a for a in axes if a in mesh.axis_names)
    if len(present) == 0:
        return None
    if len(present) == 1:
        return present[0]
    return present


def logical_to_spec(logical_axes, mesh: Mesh, rules: dict) -> P:
    """('batch', None, 'embed') -> PartitionSpec for this mesh."""
    spec = []
    for name in logical_axes:
        if name is None:
            spec.append(None)
            continue
        axes = rules.get(name)
        if axes is None:
            raise KeyError(f"unknown logical axis {name!r}")
        spec.append(_filter_axes(axes, mesh))
    return P(*spec)


def constrain(x, *logical_axes):
    """with_sharding_constraint via logical axes; no-op without a context."""
    st = getattr(_CTX, "state", None)
    if st is None:
        return x
    mesh, rules = st
    spec = logical_to_spec(logical_axes, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Parameter sharding rules (path-regex -> logical axes per dimension).
#
# Paths look like "layers/attn/wq/w" (scan-stacked layers carry a leading
# n_layers dim, which is always unsharded: the regex rules below give the
# *trailing* dims and we left-pad with None).
# ---------------------------------------------------------------------------

PARAM_RULES: list[tuple[str, tuple]] = [
    # --- LM ---
    (r".*embed/w$", ("vocab", "fsdp")),
    (r".*lm_head/w$", ("fsdp", "vocab")),
    (r".*(wq|wkv_q)/w$", ("fsdp", "w_model")),
    (r".*wk/w$", ("fsdp", "w_model")),
    (r".*wv/w$", ("fsdp", "w_model")),
    (r".*wo/w$", ("w_model", "fsdp")),
    (r".*(w_gate|w_in)/w$", ("fsdp", "w_model")),
    (r".*w_out/w$", ("w_model", "fsdp")),
    (r".*router/w$", ("fsdp", None)),
    # MoE experts: (E, d, ff) / (E, ff, d)
    (r".*experts/(w_gate|w_in)$", ("expert", None, "w_model")),
    (r".*experts/w_out$", ("expert", "w_model", None)),
    # --- GNN --- weights are small: shard the fan-in over data (FSDP) only.
    (r".*gnn.*/w$", ("fsdp", None)),
    # --- recsys ---
    (r".*tables/rows$", ("table_rows", None)),
    (r".*field_bias/rows$", ("table_rows",)),
]


def _divisible_entry(dim_size: int, entry, mesh: Mesh):
    """Trim a spec entry (axis | tuple | None) to the longest prefix of mesh
    axes whose product divides dim_size.

    jit in_shardings (unlike with_sharding_constraint) require exact
    divisibility; non-dividing dims fall back to fewer axes / replication.
    The roofline then charges the replication honestly.
    """
    if entry is None or dim_size is None:
        return entry
    axes = (entry,) if isinstance(entry, str) else tuple(entry)
    keep = []
    prod = 1
    for a in axes:
        sz = mesh.shape[a]
        if dim_size % (prod * sz) == 0:
            keep.append(a)
            prod *= sz
        else:
            break
    if not keep:
        return None
    return keep[0] if len(keep) == 1 else tuple(keep)


def divisible_spec(spec: P, shape, mesh: Mesh) -> P:
    t = tuple(spec) + (None,) * (len(shape) - len(tuple(spec)))
    return P(*[_divisible_entry(int(d), e, mesh)
               for d, e in zip(shape, t)])


def _spec_for_path(path: str, ndim: int, mesh: Mesh, rules: dict,
                   shape=None) -> P:
    for pat, logical in PARAM_RULES:
        if re.match(pat, path):
            pad = ndim - len(logical)
            axes = (None,) * pad + tuple(logical)
            spec = logical_to_spec(axes, mesh, rules)
            if shape is not None:
                spec = divisible_spec(spec, shape, mesh)
            return spec
    return P()  # replicate (norms, biases, small heads)


def param_shardings(params, mesh: Mesh, rules: Optional[dict] = None):
    """Pytree of NamedShardings for a param pytree, via PARAM_RULES."""
    rules = dict(DEFAULT_RULES, **(rules or {}))

    def _one(path, leaf):
        shape = tuple(leaf.shape)
        return NamedSharding(
            mesh, _spec_for_path(path, len(shape), mesh, rules, shape))

    return path_map(_one, params)


def _padded_spec(spec: P, ndim: int) -> tuple:
    t = tuple(spec)
    return t + (None,) * (ndim - len(t))


def train_state_shardings(state, mesh: Mesh, rules: Optional[dict] = None):
    """Shardings for a full trainer state {params, opt, step}.

    Optimizer moments follow their parameter's sharding; Adafactor's
    factored accumulators drop the reduced axis from the param spec
    (r = mean over last dim -> spec[:-1]; c = mean over second-to-last ->
    spec[:-2] + spec[-1:]), so the big per-expert accumulators stay
    sharded exactly like their weights.
    """
    rules_d = dict(DEFAULT_RULES, **(rules or {}))
    params = state["params"]

    flat_spec: dict = {}

    def _collect(path, leaf):
        shape = tuple(leaf.shape)
        flat_spec[path] = _padded_spec(
            _spec_for_path(path, len(shape), mesh, rules_d, shape),
            len(shape))
        return leaf

    path_map(_collect, params)

    p_sh = path_map(
        lambda p, l: NamedSharding(mesh, P(*flat_spec[p])), params)

    def _opt_leaf(path, leaf):
        parts = path.split("/")
        head, rest = parts[0], parts[1:]
        if head in ("m", "v", "mu"):
            key = "/".join(rest)
            spec = flat_spec.get(key)
            return NamedSharding(mesh, P(*spec) if spec else P())
        if head == "acc":
            kind = rest[-1]
            key = "/".join(rest[:-1])
            spec = flat_spec.get(key)
            if spec is None:
                return NamedSharding(mesh, P())
            if kind == "v":
                return NamedSharding(mesh, P(*spec))
            if kind == "r":
                return NamedSharding(mesh, P(*spec[:-1]))
            if kind == "c":
                return NamedSharding(mesh, P(*spec[:-2], spec[-1]))
        return NamedSharding(mesh, P())

    opt_sh = path_map(_opt_leaf, state["opt"])
    return {"params": p_sh, "opt": opt_sh,
            "step": NamedSharding(mesh, P())}


def kv_cache_shardings(cache, mesh: Mesh, rules: Optional[dict] = None):
    """Shardings for a decode KV cache {k, v, slot_pos, pos}.

    Preferred: shard the kv-head axis over `model` (head parallelism).
    When kv_heads doesn't divide the model axis (GQA with few KV heads,
    e.g. arctic kv=8 on a 16-way model axis), fall back to sharding the
    cache SEQUENCE axis over `model` instead — attention over a
    seq-sharded cache becomes a distributed flash-decode (partial softmax
    + all-reduce), which SPMD partitioning emits automatically.
    """
    rules_d = dict(DEFAULT_RULES, **(rules or {}))
    kshape = tuple(cache["k"].shape)      # (L, B, S, Hkv, D)
    model_sz = 1
    for a in rules_d["kv_heads"]:
        if a in mesh.axis_names:
            model_sz *= mesh.shape[a]
    heads_divide = kshape[3] % max(model_sz, 1) == 0

    def spec(shape, *axes):
        s = logical_to_spec(axes, mesh, rules_d)
        return NamedSharding(mesh, divisible_spec(s, shape, mesh))

    if heads_divide:
        kv_axes = (None, "batch", None, "kv_heads", None)
    else:
        kv_axes = (None, "batch", "kvlen", None, None)
    return {
        "k": spec(kshape, *kv_axes),
        "v": spec(kshape, *kv_axes),
        "slot_pos": spec(tuple(cache["slot_pos"].shape), "batch", None),
        "pos": spec(tuple(cache["pos"].shape), "batch"),
    }


def batch_shardings(batch, mesh: Mesh, axes_map: dict,
                    rules: Optional[dict] = None):
    """Shardings for an input batch dict via a {key: logical axes} map.

    Divisibility-aware: dims that don't divide their mesh axes keep only a
    dividing prefix (or replicate) so jit in_shardings always validate.
    """
    rules_d = dict(DEFAULT_RULES, **(rules or {}))
    out = {}
    for k, leaf in batch.items():
        axes = axes_map.get(k)
        if axes is None:
            out[k] = NamedSharding(mesh, P())
        else:
            spec = logical_to_spec(axes, mesh, rules_d)
            out[k] = NamedSharding(
                mesh, divisible_spec(spec, tuple(leaf.shape), mesh))
    return out
