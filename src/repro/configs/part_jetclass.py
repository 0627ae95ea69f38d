"""part-jetclass — Particle Transformer at its published JetClass widths.

Qu, Li and Qian, arXiv:2202.03772 (ICML 2022), Sec. 3: 128 particles of
17 features, particle embedding (128, 512, 128), pair embedding (64, 64,
64, 8), 8 particle-attention blocks (d = 128, 8 heads of 16, feed-forward
512), 2 class-attention blocks, 10 classes.  Served on the trigger path
(``trigger_serve --arch part-jetclass --forward part``); it has no
dry-run cells.
"""

from repro.configs.base import ArchSpec
from repro.core.particle_transformer import ParticleTransformerConfig

MODEL = ParticleTransformerConfig()

ARCH = ArchSpec(
    arch_id="part-jetclass",
    family="part",
    model=MODEL,
    shapes={},
    source="arXiv:2202.03772 (Particle Transformer, JetClass)",
    notes="O(N^2) pair embedding as per-head attention bias; 8128 "
          "unordered pairs per jet.",
)
