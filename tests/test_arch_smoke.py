"""Per-assigned-architecture smoke tests: a REDUCED config of the same
family runs one forward/train step on CPU, asserting output shapes and
no NaNs.  (Full configs are exercised only via the dry-run.)"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig, RecsysConfig, \
    TransformerConfig
from repro.configs.registry import ALL_ARCHS, get_arch
from repro.models import recsys as fm_lib
from repro.models import transformer as tfm
from repro.models.gnn import GNN_MODULES


def reduced_lm(cfg: TransformerConfig) -> TransformerConfig:
    """Same family (MoE-ness, SWA, GQA ratio, tied embeddings), tiny dims."""
    kv = max(1, cfg.n_kv_heads * 4 // cfg.n_heads)
    moe = None
    if cfg.moe is not None:
        moe = MoEConfig(n_experts=4, top_k=min(2, cfg.moe.top_k),
                        dense_residual=cfg.moe.dense_residual)
    return dataclasses.replace(
        cfg, n_layers=2, d_model=64, n_heads=4, n_kv_heads=kv, head_dim=16,
        d_ff=96, vocab_size=128, moe=moe,
        sliding_window=(8 if cfg.sliding_window else None),
        remat="none", param_dtype="float32", compute_dtype="float32")


LM_ARCHS = [a for a in ALL_ARCHS if get_arch(a).family == "lm"]
GNN_ARCHS = [a for a in ALL_ARCHS if get_arch(a).family == "gnn"]


@pytest.mark.parametrize("arch_id", LM_ARCHS)
def test_lm_smoke(arch_id):
    cfg = reduced_lm(get_arch(arch_id).model)
    params = tfm.init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    # train step value+grad
    loss, metrics = tfm.loss_fn(params, cfg, {"tokens": toks,
                                              "labels": toks})
    assert np.isfinite(float(loss))
    grads = jax.grad(lambda p: tfm.loss_fn(p, cfg, {"tokens": toks,
                                                    "labels": toks})[0]
                     )(params)
    assert all(np.all(np.isfinite(g))
               for g in jax.tree_util.tree_leaves(grads))
    # prefill -> decode consistency of shapes
    logits, _, cache_pf = tfm.forward(params, cfg, toks, return_cache=True)
    assert logits.shape == (2, 16, tfm.padded_vocab(cfg))
    cache = tfm.init_cache(cfg, 2, 32)
    lg, cache = tfm.decode_step(params, cfg, cache, toks[:, 0])
    assert lg.shape == (2, tfm.padded_vocab(cfg))
    assert np.all(np.isfinite(np.asarray(lg)))
    assert int(cache["pos"][0]) == 1


@pytest.mark.parametrize("arch_id", GNN_ARCHS)
def test_gnn_smoke(arch_id, rng):
    full = get_arch(arch_id).model
    cfg = dataclasses.replace(full, n_layers=2, d_hidden=16,
                              l_max=min(full.l_max, 2),
                              m_max=min(full.m_max, 1),
                              n_heads=min(full.n_heads, 2) or 1)
    mod = GNN_MODULES[cfg.kind]
    n, e, d = 24, 72, 8
    g = {
        "x": jnp.asarray(rng.normal(0, 1, (n, d)).astype(np.float32)),
        "senders": jnp.asarray(rng.randint(0, n, e).astype(np.int32)),
        "receivers": jnp.asarray(rng.randint(0, n, e).astype(np.int32)),
        "pos": jnp.asarray(rng.normal(0, 1, (n, 3)).astype(np.float32)),
    }
    params = mod.init(jax.random.PRNGKey(0), cfg, d, cfg.n_classes)
    out = mod.apply(params, cfg, g)
    assert out.shape == (n, cfg.n_classes)
    assert np.all(np.isfinite(np.asarray(out)))
    # one grad step through a scalar loss
    grads = jax.grad(
        lambda p: jnp.mean(jnp.square(mod.apply(p, cfg, g))))(params)
    assert all(np.all(np.isfinite(x))
               for x in jax.tree_util.tree_leaves(grads))


def test_fm_smoke(rng):
    full = get_arch("fm").model
    cfg = RecsysConfig(name="fm-small", n_sparse=6, embed_dim=4,
                       vocab_sizes=(50, 40, 30, 20, 10, 5))
    params = fm_lib.init(jax.random.PRNGKey(0), cfg)
    ids = jnp.asarray(np.stack([rng.randint(0, s, 16)
                                for s in cfg.vocab_sizes], 1))
    logits = fm_lib.forward(params, cfg, ids)
    assert logits.shape == (16,)
    assert np.all(np.isfinite(np.asarray(logits)))
    loss, m = fm_lib.loss_fn(params, cfg, {"ids": ids,
                                           "y": jnp.ones((16,))})
    assert np.isfinite(float(loss))
    # kernel path matches XLA path
    lk = fm_lib.forward(params, cfg, ids, use_kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(lk),
                               rtol=1e-4, atol=1e-4)
    # retrieval
    sc = fm_lib.retrieval_score(params, cfg, ids[0, :-1], jnp.arange(5))
    assert sc.shape == (5,) and np.all(np.isfinite(np.asarray(sc)))


@pytest.mark.parametrize("arch_id", ["jedinet-30p", "jedinet-50p"])
def test_jedi_smoke(arch_id):
    from repro.core import interaction_net as inet
    cfg = dataclasses.replace(get_arch(arch_id).model,
                              fr_hidden=(8,), fo_hidden=(8,),
                              phi_hidden=(8,))
    params = inet.init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (4, cfg.n_objects, cfg.n_features))
    logits = inet.forward_sr(params, cfg, x)
    assert logits.shape == (4, cfg.n_targets)
    assert np.all(np.isfinite(np.asarray(logits)))


def test_registry_covers_assignment():
    """All 10 assigned archs + 4 shapes each are registered (40 cells)."""
    from repro.configs.registry import ASSIGNED_ARCHS, iter_cells
    assert len(ASSIGNED_ARCHS) == 10
    total = list(iter_cells(include_skipped=True))
    assert len(total) == 40
    runnable = list(iter_cells())
    assert len(runnable) == 36       # 4 documented long_500k skips


def test_part_jetclass_is_a_trigger_arch_of_its_own_family():
    """ParT registers with family ``part``: served on the trigger path,
    never picked up by the LM/GNN dry-run sweep."""
    from repro.configs.registry import (ASSIGNED_ARCHS, TRIGGER_FAMILIES,
                                        archs, iter_cells)
    spec = get_arch("part-jetclass")
    assert spec.family == "part" == spec.model.family
    assert "part-jetclass" in archs(*TRIGGER_FAMILIES)
    assert "part-jetclass" not in ASSIGNED_ARCHS
    assert all(a.family not in TRIGGER_FAMILIES for a, _ in iter_cells())
    assert archs("jedinet") == ["jedinet-30p", "jedinet-50p",
                                "jedinet-tracks-128"]
    # one vocabulary: an arch's family is its model's, which the paths
    # that serve it name (PathSpec.model)
    for a in archs(*TRIGGER_FAMILIES):
        assert get_arch(a).family == get_arch(a).model.family, a
