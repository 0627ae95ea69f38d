"""jedinet-tracks-128 — large-graph regime: 128 tracks per event.

The paper's JEDI-net tops out at N_o=50; real-time graph building on
FPGAs (Neu et al., arXiv:2307.07289) and JEDI-linear (Que et al.,
arXiv:2508.15468) target O(100) tracks per event.  At N_o=128 with
f_R width 128 the UNTILED whole-network kernel's (N_o, N_o, H1) grid
needs > 8 MiB of VMEM for a SINGLE sample — the working-set model
rejects it outright (`autotune.fits_vmem`) — so this config is only
servable through the sender-tiled kernel, which holds one
(N_o, block_s, H1) slab plus the Ebar accumulator instead.
16,256 edges per event.
"""

from repro.configs.base import ArchSpec, JEDI_SHAPES
from repro.core.interaction_net import JediNetConfig

MODEL = JediNetConfig(
    n_objects=128,
    n_features=16,
    d_e=8,
    d_o=24,
    n_targets=5,
    fr_hidden=(128, 128),
    fo_hidden=(64, 64),
    phi_hidden=(32, 32),
)

ARCH = ArchSpec(
    arch_id="jedinet-tracks-128",
    family="jedinet",
    model=MODEL,
    shapes=dict(JEDI_SHAPES),
    source="arXiv:2307.07289 (track-graph regime) + this repo",
    notes="Large-graph variant: 16,256 edges; untiled full kernel "
          "exceeds the VMEM budget at block_b=1 — sender tiling only.",
)
