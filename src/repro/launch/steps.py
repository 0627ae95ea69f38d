"""Per-(architecture x shape) step functions + abstract input specs.

Every dry-run cell resolves to a ``CellProgram``:

    step_fn      : pure function to jit (train_step or serve_step)
    make_abstract: () -> (args tuple of ShapeDtypeStruct pytrees,
                          in_shardings tuple, out_shardings)
    describe     : metadata for the roofline report

LM ``decode_*`` / ``long_*`` cells lower ``serve_step`` (one token against a
KV cache); ``prefill_*`` lowers a full-sequence forward returning last-token
logits + the built cache; ``train_*`` lowers loss+grad+optimizer-update.
GNN cells lower family-specific train steps; recsys cells lower train /
bulk-score / retrieval programs.  Encoder-only archs have no decode cells in
the assignment, so no special-casing is needed.

The optimizer for LM train cells is Adafactor (AdamW's fp32 moments for
arctic-480b would need ~3.8 TB — see configs/arctic_480b.py); GNN/recsys/
JEDI train cells use AdamW.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchSpec, ShapeSpec
from repro.core import interaction_net as inet
from repro.models import recsys as fm_lib
from repro.models import transformer as tfm
from repro.models.gnn import GNN_MODULES
from repro.models.gnn import segment_ops as seg
from repro.parallel import sharding as shd
from repro.training import make_optimizer, make_train_step
from repro.training.schedule import warmup_cosine
from repro.data.neighbor_sampler import static_budget


@dataclasses.dataclass
class CellProgram:
    arch_id: str
    shape_name: str
    kind: str                    # train | serve
    step_fn: Callable
    make_abstract: Callable      # () -> (args, in_shardings, out_shardings)
    notes: str = ""


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(int(x) for x in shape), dtype)


def pad512(n: int) -> int:
    """Round node/edge/candidate counts up to a 512 multiple so the flat
    set axes shard over the full 512-chip mesh.  The data pipeline pads
    with inert elements (features 0, edges into a sink node, labels -1)."""
    return -(-int(n) // 512) * 512


def _abstract_like(tree):
    return jax.tree_util.tree_map(
        lambda l: sds(l.shape, l.dtype), tree)


def _replicated(mesh, tree):
    return jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P()), tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


# ===========================================================================
# LM family
# ===========================================================================

def _lm_abstract_params(cfg):
    return jax.eval_shape(lambda k: tfm.init(k, cfg), jax.random.PRNGKey(0))


def _lm_loss_kw(cfg, seq_len: int) -> dict:
    v = tfm.padded_vocab(cfg)
    if cfg.unroll_scans:
        # cost variant: fewer, larger chunks keep the unrolled HLO
        # compilable while preserving the blockwise memory behaviour
        return dict(
            kv_chunk=min(8192, seq_len),
            q_chunk=None,
            logit_chunk=(1024 if v >= 32768 and seq_len >= 2048 else None),
        )
    return dict(
        kv_chunk=min(2048, seq_len),
        q_chunk=(2048 if seq_len > 8192 else None),
        logit_chunk=(512 if v >= 32768 and seq_len >= 2048 else None),
    )


def lm_train_cell(arch: ArchSpec, shape: ShapeSpec, mesh) -> CellProgram:
    cfg = arch.model
    b = shape.dim("global_batch")
    s = shape.dim("seq_len")
    opt = make_optimizer("adafactor", warmup_cosine(1e-3, 100, 10000))
    kw = _lm_loss_kw(cfg, s)
    step = make_train_step(
        lambda p, batch: tfm.loss_fn(p, cfg, batch, **kw), opt)

    def make_abstract():
        a_params = _lm_abstract_params(cfg)
        a_opt = jax.eval_shape(opt.init, a_params)
        a_state = {"params": a_params, "opt": a_opt,
                   "step": sds((), jnp.int32)}
        a_batch = {"tokens": sds((b, s), jnp.int32),
                   "labels": sds((b, s), jnp.int32)}
        st_sh = shd.train_state_shardings(a_state, mesh)
        b_sh = shd.batch_shardings(
            a_batch, mesh, {"tokens": ("batch", None),
                            "labels": ("batch", None)})
        out_sh = (st_sh, None)
        return (a_state, a_batch), (st_sh, b_sh), out_sh

    return CellProgram(arch.arch_id, shape.name, "train", step,
                       make_abstract)


def lm_prefill_cell(arch: ArchSpec, shape: ShapeSpec, mesh) -> CellProgram:
    cfg = arch.model
    b = shape.dim("global_batch")
    s = shape.dim("seq_len")

    if cfg.unroll_scans:                   # cost variant (see _lm_loss_kw)
        pf_kw = dict(kv_chunk=min(8192, s), q_chunk=None)
    else:
        pf_kw = dict(kv_chunk=2048, q_chunk=(2048 if s > 8192 else None))

    def prefill(params, tokens):
        logits, _, cache = tfm.forward(
            params, cfg, tokens, return_cache=True, **pf_kw)
        return logits[:, -1, :], cache

    def make_abstract():
        a_params = _lm_abstract_params(cfg)
        a_tokens = sds((b, s), jnp.int32)
        p_sh = shd.param_shardings(a_params, mesh)
        t_sh = shd.batch_shardings({"t": a_tokens}, mesh,
                                   {"t": ("batch", None)})["t"]
        return (a_params, a_tokens), (p_sh, t_sh), None

    return CellProgram(arch.arch_id, shape.name, "serve", prefill,
                       make_abstract)


def lm_decode_cell(arch: ArchSpec, shape: ShapeSpec, mesh) -> CellProgram:
    cfg = arch.model
    b = shape.dim("global_batch")
    s = shape.dim("seq_len")
    t = tfm.cache_len(cfg, s)

    def decode(params, cache, tokens):
        return tfm.decode_step(params, cfg, cache, tokens)

    def make_abstract():
        a_params = _lm_abstract_params(cfg)
        a_cache = jax.eval_shape(
            lambda: tfm.init_cache(cfg, b, s))
        a_tokens = sds((b,), jnp.int32)
        p_sh = shd.param_shardings(a_params, mesh)
        c_sh = shd.kv_cache_shardings(a_cache, mesh)
        t_sh = shd.batch_shardings({"t": a_tokens}, mesh,
                                   {"t": ("batch",)})["t"]
        return ((a_params, a_cache, a_tokens), (p_sh, c_sh, t_sh),
                (None, c_sh))

    notes = ""
    if cfg.sliding_window is not None and t < s:
        notes = (f"rolling SWA cache: window {t} << context {s} "
                 "(the sub-quadratic long-decode path)")
    return CellProgram(arch.arch_id, shape.name, "serve", decode,
                       make_abstract, notes=notes)


# ===========================================================================
# GNN family
# ===========================================================================

def _gnn_feat_dim(shape: ShapeSpec) -> int:
    return int(shape.dim("d_feat", 16))


def _needs_pos(kind: str) -> bool:
    return kind in ("meshgraphnet", "equiformer_v2")


def _gnn_loss(kind: str, cfg, out, graph):
    """Family-appropriate loss on model output."""
    if kind in ("gcn", "pna"):
        y = graph["y"]
        mask = (y >= 0).astype(jnp.float32)
        logp = jax.nn.log_softmax(out.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, jnp.maximum(y, 0)[:, None],
                                   axis=-1)[:, 0]
        loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        acc = jnp.sum((jnp.argmax(out, -1) == y) * mask) / jnp.maximum(
            jnp.sum(mask), 1.0)
        return loss, {"accuracy": acc}
    # regression heads
    y = graph["y"].astype(jnp.float32)
    if y.ndim == 1:
        y = y[:, None]
    mask = graph.get("seed_mask")
    err = jnp.square(out.astype(jnp.float32) - y)
    if mask is not None:
        m = mask.astype(jnp.float32)[:, None]
        loss = jnp.sum(err * m) / jnp.maximum(jnp.sum(m) * err.shape[-1], 1.0)
    else:
        loss = jnp.mean(err)
    return loss, {"mse": loss}


def _gnn_batch_axes(keys) -> dict:
    ax = {
        "x": ("nodes", None), "pos": ("nodes", None),
        "senders": ("edges",), "receivers": ("edges",),
        "edge_mask": ("edges",), "seed_mask": ("nodes",),
        "y": ("nodes",), "n_nodes": None,
    }
    return {k: ax.get(k) for k in keys}


def gnn_fullgraph_cell(arch: ArchSpec, shape: ShapeSpec, mesh,
                       *, minibatch: bool = False) -> CellProgram:
    cfg = arch.model
    kind = cfg.kind
    mod = GNN_MODULES[kind]
    d_in = _gnn_feat_dim(shape)

    if minibatch:
        n, e = static_budget(int(shape.dim("batch_nodes")),
                             tuple(shape.dim("fanout")))
    else:
        n = int(shape.dim("n_nodes"))
        e = int(shape.dim("n_edges"))
    n, e = pad512(n), pad512(e)

    n_out = cfg.n_classes
    opt = make_optimizer("adamw", warmup_cosine(1e-3, 100, 10000))

    def loss_fn(params, graph):
        out = mod.apply(params, cfg, graph)
        return _gnn_loss(kind, cfg, out, graph)

    step = make_train_step(loss_fn, opt)

    def make_abstract():
        a_params = jax.eval_shape(
            lambda k: mod.init(k, cfg, d_in, n_out), jax.random.PRNGKey(0))
        a_opt = jax.eval_shape(opt.init, a_params)
        a_state = {"params": a_params, "opt": a_opt,
                   "step": sds((), jnp.int32)}
        g = {
            "x": sds((n, d_in), jnp.float32),
            "senders": sds((e,), jnp.int32),
            "receivers": sds((e,), jnp.int32),
        }
        if _needs_pos(kind):
            g["pos"] = sds((n, 3), jnp.float32)
        if kind in ("gcn", "pna"):
            g["y"] = sds((n,), jnp.int32)
        elif kind == "meshgraphnet":
            g["y"] = sds((n, 3), jnp.float32)
        else:
            g["y"] = sds((n,), jnp.float32)
        if minibatch:
            g["edge_mask"] = sds((e,), jnp.bool_)
            g["seed_mask"] = sds((n,), jnp.bool_)
        st_sh = shd.train_state_shardings(a_state, mesh)
        g_sh = shd.batch_shardings(g, mesh, _gnn_batch_axes(g.keys()))
        return (a_state, g), (st_sh, g_sh), (st_sh, None)

    return CellProgram(arch.arch_id, shape.name, "train", step,
                       make_abstract,
                       notes=("sampled-subgraph (padded static shapes)"
                              if minibatch else "full-batch"))


def gnn_molecule_cell(arch: ArchSpec, shape: ShapeSpec, mesh) -> CellProgram:
    cfg = arch.model
    kind = cfg.kind
    mod = GNN_MODULES[kind]
    b = int(shape.dim("batch"))
    n = int(shape.dim("n_nodes"))
    e = int(shape.dim("n_edges"))
    d_in = _gnn_feat_dim(shape)
    n_out = cfg.n_classes
    opt = make_optimizer("adamw", warmup_cosine(1e-3, 100, 10000))

    def loss_fn(params, batch):
        x, s, r, gids = seg.flatten_batched_graphs(
            batch["x"], batch["senders"], batch["receivers"])
        g = {"x": x, "senders": s, "receivers": r}
        if "pos" in batch:
            g["pos"] = batch["pos"].reshape(-1, 3)
        out = mod.apply(params, cfg, g)                    # (B*N, n_out)
        per_graph = seg.scatter_mean(out, gids, b)         # (B, n_out)
        y = batch["y"]
        logp = jax.nn.log_softmax(per_graph.astype(jnp.float32), -1)
        nll = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
        acc = jnp.mean((jnp.argmax(per_graph, -1) == y).astype(jnp.float32))
        return jnp.mean(nll), {"accuracy": acc}

    step = make_train_step(loss_fn, opt)

    def make_abstract():
        a_params = jax.eval_shape(
            lambda k: mod.init(k, cfg, d_in, n_out), jax.random.PRNGKey(0))
        a_opt = jax.eval_shape(opt.init, a_params)
        a_state = {"params": a_params, "opt": a_opt,
                   "step": sds((), jnp.int32)}
        batch = {
            "x": sds((b, n, d_in), jnp.float32),
            "senders": sds((b, e), jnp.int32),
            "receivers": sds((b, e), jnp.int32),
            "y": sds((b,), jnp.int32),
        }
        if _needs_pos(kind):
            batch["pos"] = sds((b, n, 3), jnp.float32)
        st_sh = shd.train_state_shardings(a_state, mesh)
        b_sh = shd.batch_shardings(batch, mesh, {
            "x": ("batch", None, None), "pos": ("batch", None, None),
            "senders": ("batch", None), "receivers": ("batch", None),
            "y": ("batch",)})
        return (a_state, batch), (st_sh, b_sh), (st_sh, None)

    return CellProgram(arch.arch_id, shape.name, "train", step,
                       make_abstract, notes="batched small graphs")


# ===========================================================================
# recsys (FM)
# ===========================================================================

def fm_train_cell(arch: ArchSpec, shape: ShapeSpec, mesh) -> CellProgram:
    cfg = arch.model
    b = int(shape.dim("batch"))
    opt = make_optimizer("adamw", warmup_cosine(1e-3, 100, 10000),
                         weight_decay=0.0)
    step = make_train_step(
        lambda p, batch: fm_lib.loss_fn(p, cfg, batch), opt)

    def make_abstract():
        a_params = jax.eval_shape(
            lambda k: fm_lib.init(k, cfg), jax.random.PRNGKey(0))
        a_opt = jax.eval_shape(opt.init, a_params)
        a_state = {"params": a_params, "opt": a_opt,
                   "step": sds((), jnp.int32)}
        batch = {"ids": sds((b, cfg.n_sparse), jnp.int32),
                 "y": sds((b,), jnp.int32)}
        st_sh = shd.train_state_shardings(a_state, mesh)
        b_sh = shd.batch_shardings(batch, mesh, {
            "ids": ("batch", None), "y": ("batch",)})
        return (a_state, batch), (st_sh, b_sh), (st_sh, None)

    return CellProgram(arch.arch_id, shape.name, "train", step,
                       make_abstract)


def fm_serve_cell(arch: ArchSpec, shape: ShapeSpec, mesh) -> CellProgram:
    cfg = arch.model
    b = int(shape.dim("batch"))

    def score(params, ids):
        return fm_lib.forward(params, cfg, ids)

    def make_abstract():
        a_params = jax.eval_shape(
            lambda k: fm_lib.init(k, cfg), jax.random.PRNGKey(0))
        a_ids = sds((b, cfg.n_sparse), jnp.int32)
        p_sh = shd.param_shardings(a_params, mesh)
        i_sh = NamedSharding(mesh, shd.logical_to_spec(
            ("batch", None), mesh, shd.DEFAULT_RULES))
        return (a_params, a_ids), (p_sh, i_sh), None

    return CellProgram(arch.arch_id, shape.name, "serve", score,
                       make_abstract)


def fm_retrieval_cell(arch: ArchSpec, shape: ShapeSpec, mesh) -> CellProgram:
    cfg = arch.model
    n_cand = pad512(shape.dim("n_candidates"))

    def score(params, user_ids, cand_ids):
        return fm_lib.retrieval_score(params, cfg, user_ids, cand_ids)

    def make_abstract():
        a_params = jax.eval_shape(
            lambda k: fm_lib.init(k, cfg), jax.random.PRNGKey(0))
        a_user = sds((cfg.n_sparse - 1,), jnp.int32)
        a_cand = sds((n_cand,), jnp.int32)
        p_sh = shd.param_shardings(a_params, mesh)
        u_sh = NamedSharding(mesh, P())
        c_sh = NamedSharding(mesh, shd.logical_to_spec(
            ("candidates",), mesh, shd.DEFAULT_RULES))
        return (a_params, a_user, a_cand), (p_sh, u_sh, c_sh), None

    return CellProgram(arch.arch_id, shape.name, "serve", score,
                       make_abstract, notes="1 query x 1M candidates GEMV")


# ===========================================================================
# JEDI-net (the paper's own model)
# ===========================================================================

def jedi_infer_cell(arch: ArchSpec, shape: ShapeSpec, mesh) -> CellProgram:
    cfg = arch.model
    # §Perf cell A: batch 1000 doesn't divide the 16-way data axis and
    # would replicate onto every chip (a 15.6x memory-term regression);
    # serving pads the request batch to 1024.  The bilinear-split forward
    # is the optimized production path (paper-faithful forward_sr is the
    # baseline, measured in experiments/hillclimb_jedi.py).
    b = -(-int(shape.dim("batch")) // 1024) * 1024

    def infer(params, x):
        return inet.forward_sr_split(params, cfg, x, grid=False)

    def make_abstract():
        a_params = jax.eval_shape(
            lambda k: inet.init(k, cfg), jax.random.PRNGKey(0))
        a_x = sds((b, cfg.n_objects, cfg.n_features), jnp.float32)
        p_sh = _replicated(mesh, a_params)
        x_sh = shd.batch_shardings({"x": a_x}, mesh,
                                   {"x": ("batch", None, None)})["x"]
        return (a_params, a_x), (p_sh, x_sh), None

    return CellProgram(arch.arch_id, shape.name, "serve", infer,
                       make_abstract, notes="paper Table 3 inference path")


def jedi_train_cell(arch: ArchSpec, shape: ShapeSpec, mesh) -> CellProgram:
    cfg = arch.model
    b = int(shape.dim("batch"))
    opt = make_optimizer("adamw", warmup_cosine(2e-3, 100, 10000))
    step = make_train_step(
        lambda p, batch: inet.loss_fn(p, cfg, batch), opt)

    def make_abstract():
        a_params = jax.eval_shape(
            lambda k: inet.init(k, cfg), jax.random.PRNGKey(0))
        a_opt = jax.eval_shape(opt.init, a_params)
        a_state = {"params": a_params, "opt": a_opt,
                   "step": sds((), jnp.int32)}
        batch = {"x": sds((b, cfg.n_objects, cfg.n_features), jnp.float32),
                 "y": sds((b,), jnp.int32)}
        st_sh = shd.train_state_shardings(a_state, mesh)
        b_sh = shd.batch_shardings(batch, mesh, {
            "x": ("batch", None, None), "y": ("batch",)})
        return (a_state, batch), (st_sh, b_sh), (st_sh, None)

    return CellProgram(arch.arch_id, shape.name, "train", step,
                       make_abstract)


# ===========================================================================
# dispatch
# ===========================================================================

def build_cell(arch: ArchSpec, shape: ShapeSpec, mesh) -> CellProgram:
    fam, kind = arch.family, shape.kind
    if fam == "lm":
        if kind == "train":
            return lm_train_cell(arch, shape, mesh)
        if kind == "prefill":
            return lm_prefill_cell(arch, shape, mesh)
        if kind == "decode":
            return lm_decode_cell(arch, shape, mesh)
    if fam == "gnn":
        if kind == "full_graph":
            return gnn_fullgraph_cell(arch, shape, mesh)
        if kind == "minibatch":
            return gnn_fullgraph_cell(arch, shape, mesh, minibatch=True)
        if kind == "batched_graphs":
            return gnn_molecule_cell(arch, shape, mesh)
    if fam == "recsys":
        if kind == "recsys_train":
            return fm_train_cell(arch, shape, mesh)
        if kind == "recsys_serve":
            return fm_serve_cell(arch, shape, mesh)
        if kind == "retrieval":
            return fm_retrieval_cell(arch, shape, mesh)
    if fam == "jedinet":
        if kind == "jedi_infer":
            return jedi_infer_cell(arch, shape, mesh)
        if kind == "jedi_train":
            return jedi_train_cell(arch, shape, mesh)
    raise ValueError(f"no step builder for {arch.arch_id} x {shape.name}")
