"""The system under test for Particle Transformer configurations: the
trigger serving path as ``trigger_serve --arch part-jetclass`` users run
it, the same front end and engine as ``jedinet_trigger``.

``ServingLoop.submit`` -> ``DeadlineBatcher`` -> ``ResilientEngine.
run_plan(plan, sync=False)`` (sentinel off) -> the configuration's
forward path (``part``, plain XLA; its ladder sized against HBM) ->
``RequestFuture.result()``, with the program's defaults everywhere else.
One chip, no mesh.
"""

from __future__ import annotations

from chipbench.systems.jedinet_trigger import (  # noqa: F401  (the same)
    front_end,
    health_checks,
)


def model_config(cfg: dict):
    from repro.core.particle_transformer import ParticleTransformerConfig

    return ParticleTransformerConfig(
        n_objects=cfg["n_objects"], n_features=cfg["n_features"],
        embed_dims=tuple(cfg["embed_dims"]),
        pair_embed_dims=tuple(cfg["pair_embed_dims"]),
        n_heads=cfg["n_heads"], n_blocks=cfg["n_blocks"],
        n_cls_blocks=cfg["n_cls_blocks"], ffn_dim=cfg["ffn_dim"],
        n_targets=cfg["n_targets"], compute_dtype=cfg["compute_dtype"])


def build(cfg: dict, params, chips: int):
    """The engine, every rung of its bucket ladder compiled and run once."""
    from repro.serving import ResilientEngine

    if chips != 1:
        raise ValueError(f"this system runs on one chip, not {chips}")
    engine = ResilientEngine(params, model_config(cfg), forward=cfg["forward"],
                             mesh=None)
    engine.warm()
    return engine
