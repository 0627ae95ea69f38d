"""Quickstart: the paper's technique in 60 seconds.

    PYTHONPATH=src python examples/quickstart.py

1. Build JEDI-net-30p and enumerate the forward-path registry
   (`repro.core.paths`) — every optimization tier of the paper is one
   registered `PathSpec`, from the dense-MMM baseline of [5] to the
   int8-quantized whole-network kernel.
2. Run each registered path against its own declared reference fn
   (Pallas kernels in interpret mode on CPU) at its declared tolerance.
3. Print the Fig-8 op-count reduction and a wall-clock comparison.
"""

import time

import jax
import jax.numpy as jnp

from repro.core import adjacency, interaction_net as inet, paths


def main():
    cfg = inet.JediNetConfig(n_objects=30, n_features=16)
    params = inet.init(jax.random.PRNGKey(0), cfg, scale="lecun")
    x = jax.random.normal(jax.random.PRNGKey(1), (256, 30, 16))

    print("registered forward paths:\n" + paths.describe() + "\n")

    # every path vs its own spec-declared reference (small batch: the
    # Pallas kernels run in interpret mode on CPU)
    xs = x[:8]
    for name in paths.available():
        spec = paths.get(name)
        p = spec.prepare_params(params)
        out = (spec.forward(p, cfg, xs, interpret=True) if spec.pallas
               else spec.forward(p, cfg, xs))
        err = float(jnp.max(jnp.abs(out - spec.ref(p, cfg, xs))))
        ok = "ok" if err < spec.tolerance else "FAIL"
        print(f"{name:>16} vs ref: max err {err:.2e} "
              f"(tol {spec.tolerance:.0e}) {ok}")

    c = adjacency.mmm_op_counts(30, 16, 8)
    print(f"\nFig 8 (30p): MMM1/2 mults {c['mmm12_baseline_mults']:,} -> 0, "
          f"MMM3 adds {c['mmm3_baseline_adds']:,} -> {c['mmm3_sr_adds']:,} "
          f"({c['mmm3_sr_adds']/c['mmm3_baseline_adds']*100:.1f}%), "
          f"iterations {c['iterations_baseline']} -> {c['iterations_sr']}")

    # wall-clock for the XLA paths (kernel paths are TPU-targeted;
    # interpret-mode timing on CPU says nothing)
    print()
    for name in paths.available(pallas=False):
        spec = paths.get(name)
        pparams = spec.prepare_params(params)
        f = jax.jit(lambda p, a, s=spec: s.forward(p, cfg, a))
        f(pparams, x).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(10):
            f(pparams, x).block_until_ready()
        print(f"{name:>16}: {(time.perf_counter()-t0)/10*1e3:.2f} ms / "
              "256-jet batch (CPU)")

    # 4. the large-graph regime: N_o=128 track-level events fit ONLY
    # through the sender-tiled kernel — the untiled working-set model
    # rejects even a single sample's (N_o, N_o, H1) grid.
    from repro.configs.jedi_tracks_128 import MODEL as tcfg
    from repro.data.jets import make_tracks
    from repro.kernels.fused_jedinet import autotune as fj_autotune
    import numpy as np
    tparams = inet.init(jax.random.PRNGKey(0), tcfg, scale="lecun")
    widths = tuple(fj_autotune.mlp_widths(tparams[k])
                   for k in ("fr", "fo", "phi"))
    untiled = fj_autotune.full_forward_bytes_per_sample(
        tcfg.n_objects, tcfg.n_features, *widths)
    # the forward call's own tile decision, so the printed tile is the
    # tile that actually runs
    tiles = fj_autotune.modeled_residency(tcfg, tparams, 4)
    bb, bs = tiles["block_b"], tiles["block_s"]
    xt = jnp.asarray(make_tracks(np.random.RandomState(0), 4)[0])
    spec = paths.get("fused_full")
    logits = spec.forward(tparams, tcfg, xt, interpret=True)
    err = float(jnp.max(jnp.abs(logits - spec.ref(tparams, tcfg, xt))))
    print(f"\ntracks128 (N_o={tcfg.n_objects}): untiled model needs "
          f"{untiled / 2**20:.2f} MiB/sample (> budget, rejected); "
          f"tiled kernel runs block_b={bb} block_s={bs}, "
          f"err vs ref {err:.1e}")


if __name__ == "__main__":
    main()
